#!/usr/bin/env python3
"""Readings from which a cell's ``correct`` limits are set (PERF.md says how).

    python3 benchmarks/limits.py --workload <cell> --seeds 3000000001,3000000002,... \\
        [--control 3] [--seconds 10] [--rehearse]

For every seed it drives the cell's timed path as a run does (set-up and, for
a served model, a short window at the cell's own load) and prints each number
compared: the LOWER readings. For the first ``--control`` seeds it also puts
the plain reference in the program's place, computed in the configuration's
``control_quant`` (the nearest precision below the one it states); for a
training cell with half of the batch left out and with the state left
unchanged by every step; for a served model with one token of one sampled
request altered: the UPPER readings. One
process, so set-up's compiles are paid once. A benchmark run never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> list:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks import run as bench_run

    bench_run.start_jax(args.rehearse)
    from benchmarks.harness import compare, runtime
    from benchmarks.harness import serve_driver, train_driver

    cell, config, traffic, family_file = bench_run.load_cell(
        HERE, args.workload, args.rehearse)
    family = runtime.load_family(family_file)
    quant = config["control_quant"]
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = runtime.Run(cell=cell, config=config, traffic=traffic,
                          family=family, kernel_names=(), seed=seed,
                          seconds=args.seconds, trace=False,
                          rehearse=args.rehearse, t_start=t0,
                          out_dir=os.path.join(ROOT, ".bench_out", "limits"))
        row = {"seed": seed}
        if config["driver"] == "train":
            tr = train_driver.TrainRun(run)
            tr.setup()
            tr.free()
            ref = tr.reference_numbers()
            row["program"] = _vals(compare.train_checks(
                tr.program, ref, cell["limits"]))
            if i < args.control:
                row["control_" + quant] = _vals(compare.train_checks(
                    tr.reference_numbers(quant=quant), ref, cell["limits"]))
                row["fault_half_batch"] = _vals(compare.train_checks(
                    tr.reference_numbers(half_batch=True), ref,
                    cell["limits"]))
                row["fault_state_unchanged"] = _vals(compare.train_checks(
                    tr.reference_numbers(state_unchanged=True), ref,
                    cell["limits"]))
        else:
            sv = serve_driver.ServeRun(run)
            sv.setup()
            sv.start_clients()
            m = sv.metrics(sv.window(run.seconds))
            sv.free()
            sample = sv.sample(m["sent"])
            def read(quant=None):
                gaps = sv.served_gaps(sample, quant=quant)
                return _vals(serve_driver.served_checks(gaps, {})) | {
                    "per_request": [g["gap"] for g in gaps],
                    "tokens": sum(g["tokens"] for g in gaps)}

            row["program"] = read() | {"failed": m["failed"],
                                       "sent": m["attempted"]}
            if i < args.control:
                row["control_" + quant] = read(quant)
                # one token altered in one request, as the kept test plants
                # it in GenerationHandle._emit: the client receives another
                # token than the engine goes on with
                r = sample[0]
                at = len(r["tokens"]) // 2
                r["tokens"][at] = (r["tokens"][at] + 1) % sv.dims["vocab_size"]
                row["fault_one_token_altered"] = read()
        row["seconds"] = time.perf_counter() - t0
        print("limits " + json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


def _vals(checks: list) -> dict:
    return {c["name"]: c["value"] for c in checks} | {
        c["name"] + "_at": c["at"] for c in checks if c.get("at")}


if __name__ == "__main__":
    main()
