"""What the decode loop says of the device's queue with no profiler on.

Every ``loop_*_ms`` metric of the benchmark is read in the traced slice, under
the profiler's Python tracer, which stretches the host's turn (PERF.md, PR 26
and PR 37). The loop's own account (``DecodeEngine``, ISSUE 38: three
counters, always on) costs the same traced and untraced, so this tool drives
a serving cell's traffic through the harness's own ``ServeRun`` (its set-up,
its clients, its first round) with NO profiler, reads the three counters where
a window of ``--seconds`` opens and where it closes, and prints, as one JSON
line on standard output:

* ``dry_share``, ``dry_slack_share`` (percent of the window's turn seconds)
  and ``dry_by_phase``: the lower bound by the phase of the turn;
* ``turn_ms`` (the window's turn seconds over its turns), ``tokens_per_s``
  (the engine's own count over the window's seconds, not the clients');
* ``spans``: over the window's head-sampled, unprofiled turns that stepped,
  each ``loop.*`` span's self time and own CPU time in ms a turn, and the
  medians of the ``loop.step`` span's ``emit_*`` attributes.

    python tools/loop_dry.py --workload <cell> [--seed n] [--seconds 30] [--rehearse]

It is a tool, not benchmark code: it compares nothing and claims nothing. On a
program without the counters (a parent commit) the shares read ``null`` and
the spans what that program's spans carry.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

from benchmarks import run as bench_run  # noqa: E402

LOOP_S = "dl4j_tpu_decode_loop_seconds_total"
DRY_S = "dl4j_tpu_decode_device_dry_seconds_total"
SLACK_S = "dl4j_tpu_decode_device_dry_slack_seconds_total"
TOKENS = "dl4j_tpu_generate_tokens_total"


def counters(engine) -> dict:
    """``{counter: {last label: value}}`` of this engine's children."""
    out = {}
    for name in (LOOP_S, DRY_S, SLACK_S, TOKENS):
        fam = engine.registry.get(name)
        out[name] = {} if fam is None else {
            labels[-1]: child.value for labels, child in fam.items()
            if labels[0] == engine.name}
    return out


def rose(lo: dict, hi: dict, name: str) -> dict:
    return {k: v - lo[name].get(k, 0.0) for k, v in hi[name].items()}


def sampled_spans(traces: list, t_lo: float, t_hi: float) -> dict:
    """Per ``loop.turn`` that stepped, started in ``[t_lo, t_hi)`` on the
    tracer's clock and was NOT taken by a profiler session: the mean self
    time and own CPU time of each span name, and the medians of the step's
    ``emit_*`` attributes."""
    turns = []
    for t in traces:
        top = next((s for s in t["spans"] if s["parent_id"] is None), None)
        if top is None or top["name"] != "loop.turn" \
                or top["attrs"].get("profiled") \
                or not t_lo <= top["start"] < t_hi \
                or not any(s["name"] == "loop.step" for s in t["spans"]):
            continue
        turns.append(t)
    if not turns:
        return {"turns": 0}
    out, names = {"turns": len(turns)}, sorted(
        {s["name"] for t in turns for s in t["spans"]})
    for name in names:
        spans = [s for t in turns for s in t["spans"] if s["name"] == name]
        cpu = [s["self_cpu_ms"] for s in spans if "self_cpu_ms" in s]
        out[name] = {
            "self_ms": sum(s["self_ms"] for s in spans) / len(turns),
            "self_cpu_ms": sum(cpu) / len(turns) if cpu else None}
    steps = [s["attrs"] for t in turns for s in t["spans"]
             if s["name"] == "loop.step"]
    for key in ("emit_rows", "emit_put_ms", "emit_count_ms",
                "emit_retire_ms"):
        vals = [a[key] for a in steps if key in a]
        out[key] = statistics.median(vals) if vals else None
    roots = [next(s for s in t["spans"] if s["parent_id"] is None)["attrs"]
             for t in turns]
    for key in ("dry_ms", "dry_slack_ms"):
        vals = [a[key] for a in roots if key in a]
        out["turn_" + key] = sum(vals) / len(vals) if vals else None
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell, config, traffic, family_file = bench_run.load_cell(
        bench_run.HERE, args.workload, args.rehearse)
    bench_run.start_jax(args.rehearse)

    from benchmarks.harness import runtime, serve_driver
    from deeplearning4j_tpu.obs.tracing import get_tracer, trace_now

    run = runtime.Run(
        cell=cell, config=config, traffic=traffic,
        family=runtime.load_family(family_file), kernel_names=(),
        seed=args.seed, seconds=args.seconds, trace=False,
        rehearse=args.rehearse, t_start=time.perf_counter(),
        out_dir=os.path.join(bench_run.ROOT, ".bench_out", args.workload))
    os.makedirs(run.out_dir, exist_ok=True)
    sv = serve_driver.ServeRun(run)
    sv.setup()
    sv.start_clients()
    run.log("every client has finished its first request: window opens")
    engine = sv.engine
    c0, n0, t0 = counters(engine), engine._n_turns, trace_now()
    time.sleep(args.seconds)
    c1, n1, t1 = counters(engine), engine._n_turns, trace_now()
    sv.window(0.0)  # no new request; those in flight drain
    tracer = get_tracer()
    tracer.flush()
    spans = sampled_spans(tracer.store.traces(limit=tracer.store.max_traces),
                          t0, t1)
    device = runtime.device_report(sv.devs, 1)
    failed = engine.stats()["failed"]
    sv.free()

    wall = sum(rose(c0, c1, LOOP_S).values())
    dry = {k: v for k, v in rose(c0, c1, DRY_S).items() if v}
    slack = sum(rose(c0, c1, SLACK_S).values())

    def share(s):
        return 100.0 * s / wall if wall else None

    line = {
        "workload": args.workload, "seed": args.seed,
        "window_s": t1 - t0, "turns": n1 - n0, "turn_seconds": wall or None,
        "turn_ms": 1e3 * wall / (n1 - n0) if wall and n1 > n0 else None,
        "tokens_per_s": sum(rose(c0, c1, TOKENS).values()) / (t1 - t0),
        "dry_share": share(sum(dry.values())),
        "dry_slack_share": share(slack),
        "dry_by_phase": {k: share(v) for k, v in sorted(dry.items())},
        "spans": spans, "engine_failed": failed,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
