#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

reads ``benchmarks/workloads/<cell>.json`` -> its ``config``
(``benchmarks/configs/<config>.json``, whose ``driver`` is "train" or
"serve") and ``traffic`` (``benchmarks/traffic/<traffic>.json``), and every
``benchmarks/layer_metrics/*.json`` whose ``workloads`` names the cell. A
later cell, configuration, traffic mix or per-layer metric is a new file and
an entry in ``BENCHMARK.json``; nothing here is edited for it.

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
    """The cell's three data files, the rehearsal's overrides applied."""
    data = os.path.abspath(data_dir)
    cell = _load(os.path.join(data, "workloads", workload + ".json"))
    config = _load(os.path.join(data, "configs", cell["config"] + ".json"))
    traffic = _load(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        over = cell.get("rehearse", {})
        config = _merge(config, over.get("config", {}))
        traffic = _merge(traffic, over.get("traffic", {}))
        cell = _merge(cell, over.get("cell", {}))
    return cell, config, traffic


def start_jax(rehearse: bool):
    """Import JAX for a run: held to the CPU for a rehearsal; otherwise with
    the persistent compile cache on, through the program's own switch
    ($JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_cache),
    keeping the small programs too. Returns the cache's directory."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
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
    cell, config, traffic = load_cell(args.data_dir, args.workload,
                                      args.rehearse)
    metric_files = [m for m in map(_load, sorted(glob.glob(os.path.join(
        os.path.abspath(args.data_dir), "layer_metrics", "*.json"))))
        if args.workload in m["workloads"]]
    seconds = float(manifest["run_seconds"] if args.seconds is None
                    else args.seconds)
    cache = start_jax(args.rehearse)

    from benchmarks.harness import compare, runtime
    from benchmarks.harness import serve_driver, train_driver

    run = runtime.Run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
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
                  chips=run.chips)
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
