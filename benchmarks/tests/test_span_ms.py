"""The ``span_ms`` reader on hand-made traces, and the metrics it gives in
both cells' rehearsed ``--trace 1`` line (CPU; the numbers are never written
anywhere)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness.readers import span_ms
from deeplearning4j_tpu.obs.tracing import TraceStore

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SPAN_METRICS = {m["name"]: m for m in MANIFEST["per_layer"]
                if m["source"] == "program_span" and m["name"] not in (
                    "decode_step_ms", "prefill_ms")}
TURN = dict(root="loop.turn", seq="turn", having="loop.step")


def _span(trace, sid, parent, name, start, end, **attrs):
    return {"trace_id": trace, "span_id": f"{trace}.{sid}",
            "parent_id": None if parent is None else f"{trace}.{parent}",
            "name": name, "start": start, "end": end,
            "duration_ms": (end - start) * 1e3, "error": False,
            "attrs": attrs}


def _turn(store, n, t0, profiled=True, step=True, wait_ms=5.0):
    """One loop turn of 100 ms: admit 0-20 (a prefill 2-18 inside), step
    20-90 whose upload 20-30 and dispatch 25-40 overlap, fetch 40-80."""
    tid = f"turn{n}"
    attrs = {"turn": n, "profiled": True} if profiled else {"turn": n}
    spans = [_span(tid, "r", None, "loop.turn", t0, t0 + .100, **attrs),
             _span(tid, "a", "r", "loop.admit", t0, t0 + .020),
             _span(tid, "p", "a", "loop.prefill", t0 + .002, t0 + .018,
                   queue_wait_ms=wait_ms)]
    if step:
        spans += [
            _span(tid, "s", "r", "loop.step", t0 + .020, t0 + .090),
            _span(tid, "u", "s", "loop.upload", t0 + .020, t0 + .030),
            _span(tid, "d", "s", "loop.dispatch", t0 + .025, t0 + .040),
            _span(tid, "f", "s", "loop.fetch", t0 + .040, t0 + .080)]
    for s in spans:
        store.add(s)


def _traces(store):
    return store.traces(limit=store.max_traces)


def test_self_time_per_turn_with_overlapping_children():
    store = TraceStore()
    for n in (4, 5, 6):
        _turn(store, n, float(n), wait_ms=float(n))
    _turn(store, 7, 7.0, profiled=False)   # sampled off the profiler
    _turn(store, 3, 3.0, step=False, wait_ms=1.0)  # profiled, did not step
    traces = _traces(store)
    assert span_ms.value(traces, "loop.turn", stat="duration_mean", **TURN) \
        == (pytest.approx(100.0), 3, 3)
    # the turn's own time: 100 - admit 20 - step 70
    assert span_ms.value(traces, "loop.turn", **TURN)[0] == pytest.approx(10.0)
    # the step's: 70 less the union of its children, 20-30, 25-40, 40-80
    assert span_ms.value(traces, "loop.step", **TURN)[0] == pytest.approx(10.0)
    assert span_ms.value(traces, "loop.fetch", **TURN)[0] == pytest.approx(40.0)
    assert span_ms.value(traces, "loop.admit", **TURN)[0] == pytest.approx(4.0)
    assert span_ms.value(traces, "loop.admit", stat="duration_mean",
                         **TURN)[0] == pytest.approx(20.0)
    # an attribute's median, over the spans of all the profiled turns
    assert span_ms.value(traces, "loop.prefill", "loop.turn", "turn",
                         attr="queue_wait_ms") == (pytest.approx(4.5), 4, 4)
    assert span_ms.value(traces, "loop.prefill", attr="queue_wait_ms",
                         **TURN)[0] == pytest.approx(5.0)
    # a span the program does not have: nothing per turn, never an error
    assert span_ms.value(traces, "loop.nothing", **TURN)[0] == 0.0
    assert span_ms.value(traces, "fit.step", "fit.step", "step") == (None, 0, 0)


def test_a_gap_in_the_numbers_gives_nothing():
    store = TraceStore()
    for n in (4, 5, 7):
        _turn(store, n, float(n))
    assert span_ms.profiled_roots(_traces(store), "loop.turn", "turn") is None
    assert span_ms.value(_traces(store), "loop.fetch", **TURN) == (None, 0, 0)
    _turn(store, 6, 6.0)
    assert span_ms.value(_traces(store), "loop.fetch", **TURN)[0] == \
        pytest.approx(40.0)


def test_each_span_metric_names_a_span_of_the_program():
    assert len(SPAN_METRICS) == 9
    src = "".join(open(os.path.join(ROOT, "deeplearning4j_tpu", p)).read()
                  for p in ("parallel/decode.py", "train/graph_solver.py"))
    for name in SPAN_METRICS:
        f = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json")))
        assert f["reader"] == "span_ms"
        for key in ("span", "root", "having"):
            if key in f["params"]:
                assert f'"{f["params"][key]}"' in src, (name, key)
        assert f'"{f["params"]["seq"]}"' in src


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_the_rehearsed_traced_line_has_the_span_metrics(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000023", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    want = {n for n, m in SPAN_METRICS.items() if cell in m["workloads"]}
    assert want and want <= set(line["metrics"])
    for n in want:
        assert line["metrics"][n]["value"] > 0
        assert line["metrics"][n]["unit"] == "ms"
    if "loop_turn_ms" in want:
        v = {n: line["metrics"][n]["value"] for n in want}
        parts = sum(v[n] for n in ("loop_admit_ms", "loop_upload_ms",
                                   "loop_dispatch_ms", "loop_fetch_ms",
                                   "loop_emit_ms", "loop_sweep_ms"))
        # the rest is the own time of loop.turn and loop.step
        assert parts < v["loop_turn_ms"] < 1.5 * parts
