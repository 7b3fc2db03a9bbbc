#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

reads ``benchmarks/workloads/<cell>.json`` -> its ``config``
(``benchmarks/configs/<config>.json``, whose ``driver`` is "train" or
"serve" and whose ``family`` names ``benchmarks/families/<family>.py``) and
``traffic`` (``benchmarks/traffic/<traffic>.json``), and every
``benchmarks/layer_metrics/*.json`` whose ``workloads`` names the cell. A
later cell, configuration, traffic mix, per-layer metric or MODEL is new
files and entries in ``BENCHMARK.json``; nothing here or under ``harness/``
is edited for it.

A new model is made of: ``families/<family>.py`` (unless a family there
already is its architecture), ``configs/<name>.json`` (``family``,
``driver``, the zoo's ``model_class`` with its ``model`` arguments, the
types, ``layout``), a traffic file, a cell file (with its ``limits``),
per-layer metric files, a reader under ``harness/readers/`` only for a new
KIND of reading, and the entries in ``BENCHMARK.json``. The two drivers are
paths (train, serve), not models, and ask the family for everything that
depends on the architecture. A family's file gives, as module-level
functions (there is no default family and no fallback):

``dims(config) -> dict``
    the sizes the yardstick uses, under the family's own names, from the
    whole configuration: what is HELD HERE where the configuration is one
    chip's share (experts held beside experts published, the vocabulary's
    slice). It has to hold ``vocab_size``, the number of ids the traffic
    draws from (a sliced vocabulary is a smaller vocabulary).
``groups(dims) -> {group: count}`` and
``leaves(dims) -> {key: (group or None, shape of one member)}``
    the canonical weight tree: flat keys; a key of a group is stacked
    ``count`` times on a leading axis. Several kinds of block (leading dense
    layers, a layer pattern, two attention blocks a layer) are several
    groups. The SORTED keys fix the order of the seed's draws
    (``harness/weights.py``): a family's set of keys stands once a cell
    runs it.
``init_scale(key, shape) -> (mean, std)``
    of the key's normal draw (float32, then cast to the configuration's
    type). Give gains and biases a random part, so that one dropped shows.
``decoder_logits(w, ids[b, t], dims, quant=None) -> [b, t, vocab_size]``
    (a family that is served) and
``loss(w, ids[b, t], labels[b, t], dims, quant=None) -> scalar``
    (a family that is trained): the plain reference over the canonical
    tree, float32, every product through ``harness.reference.mm`` (matmul
    precision "highest"; ``quant`` is the control's rounding), no kernel, no
    cache, nothing of the program. A chip's share computes the share.
``cache_bytes(dims, position, dtype_bytes) -> bytes``
    what one request standing at ``position`` (prompt and tokens so far)
    holds in the decode state: ``position`` entries of a cache, of a latent
    cache, ``min(position, window)`` of a ring, a constant for a recurrent
    state (a family that is served).
the work functions, under the names the cell's layer-metric files give as
``"work"``: ``f(slice) -> (FLOPs, bytes or None)``
    MODEL operations from shapes alone, whatever implements them: of the
    whole slice for a step's ``mfu`` (``trace_step_mfu``), of ONE call of
    the kernel averaged over the slice's calls for a roofline
    (``trace_kernel_roofline``). ``slice`` is what the driver recorded of the
    traced slice: ``model`` (the ``dims``), ``dtype_bytes``, for training
    ``steps``, ``batch``, ``seq``, for serving ``decode_attended`` and
    ``prefill_lengths`` (``[position or length, share inside]`` of every
    token and prefill), ``decode_steps``, and ``counters``: what each of the
    program's counters that the configuration lists under
    ``slice_counters`` rose by over the slice, ``{name: {"label,values":
    rise}}``, for work that only the run knows (tokens routed to the
    experts held).

One process per run, no child. Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result. ``--rehearse`` runs
the cell's ``rehearse`` block instead (tiny widths) on the CPU: its
``device`` says ``cpu``, and no file or document ever carries its numbers.

The last line of standard output is the result object; everything else goes
to standard error.
"""

from __future__ import annotations

import sys
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # run as a script: the package is one level up
    sys.path.insert(0, ROOT)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_cell(data_dir: str, workload: str, rehearse: bool):
    """The cell's three data files, the rehearsal's overrides applied, and
    the path of the file of the configuration's family."""
    from benchmarks.harness import runtime

    data = os.path.abspath(data_dir)
    cell = _load(os.path.join(data, "workloads", workload + ".json"))
    config = _load(os.path.join(data, "configs", cell["config"] + ".json"))
    traffic = _load(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        over = cell.get("rehearse", {})
        config = _merge(config, over.get("config", {}))
        traffic = _merge(traffic, over.get("traffic", {}))
        cell = _merge(cell, over.get("cell", {}))
    return cell, config, traffic, runtime.family_file(data, config)


def start_jax(rehearse: bool):
    """Import JAX for a run: held to the CPU for a rehearsal; otherwise with
    the persistent compile cache on, through the program's own switch
    ($JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_cache),
    keeping the small programs too. Returns the cache's directory."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        return None
    import jax

    from deeplearning4j_tpu.core.env import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--data-dir", default=HERE,
                    help="where workloads/, configs/, traffic/ and "
                         "layer_metrics/ are looked up (tests use their own)")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()

    manifest = _load(args.manifest)
    cell, config, traffic, family_file = load_cell(
        args.data_dir, args.workload, args.rehearse)
    metric_files = [m for m in map(_load, sorted(glob.glob(os.path.join(
        os.path.abspath(args.data_dir), "layer_metrics", "*.json"))))
        if args.workload in m["workloads"]]
    seconds = float(manifest["run_seconds"] if args.seconds is None
                    else args.seconds)
    cache = start_jax(args.rehearse)

    from benchmarks.harness import compare, runtime
    from benchmarks.harness import serve_driver, train_driver

    run = runtime.Run(
        cell=cell, config=config, traffic=traffic,
        family=runtime.load_family(family_file),
        kernel_names=tuple(sorted({k for m in metric_files for k in
                                   m.get("params", {}).get("kernels", ())})),
        seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        t_start=t_start,
        out_dir=os.path.join(ROOT, ".bench_out", args.workload))
    os.makedirs(run.out_dir, exist_ok=True)
    if cache:
        run.log(f"compile cache at {cache}")
    driver = {"train": train_driver, "serve": serve_driver}[config["driver"]]
    res = driver.run(run)

    checks = res["checks"]
    correct = compare.verdict(checks) and res["failed"] == 0
    device = res["device"]
    record = dict(res["record"], device_kind=device["kind"],
                  chips=run.chips, family=run.family)
    if args.trace:
        metrics = runtime.read_layer_metrics(metric_files, record)
        tr = record["trace"]
        if tr is not None:
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
    else:
        mine = [m for m in manifest["end_to_end"]
                if args.workload in m.get("workloads", [args.workload])]
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in mine}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and record["trace"] is not None:
        line["breakdown"] = {"device_ops": record["trace"]["top_ops"],
                             "idle_gaps": record["trace"]["idle_gaps"]}
    # a number that is not finite is no JSON: it goes out as null (and fails)
    line["checks"] = {c["name"]: {
        "value": c["value"] if c["value"] == c["value"] else None,
        "limit": c["limit"]} for c in compare.compared(checks)}
    for k, v in res.get("notes", {}).items():
        run.log(f"{k}: {v}")
    print(compare.format_checks(checks), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
