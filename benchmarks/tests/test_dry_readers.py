"""The two readers of ISSUE 38 on hand-made traces (``span_attr_share``: an
attribute's sum over the slice's wall time, or over another attribute's sum;
``span_off_cpu_ms``: self time less own CPU time), and the metrics they and
``span_ms`` give in each named cell's rehearsed ``--trace 1`` line (CPU: the
numbers are never written anywhere)."""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness.readers import span_attr_share, span_off_cpu_ms
from deeplearning4j_tpu.obs.tracing import TraceStore

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("loop_device_dry_share", "loop_device_dry_slack_share",
       "loop_dry_in_emit_share", "loop_dry_in_admit_share",
       "loop_step_self_ms", "loop_emit_off_cpu_ms", "loop_emit_put_ms",
       "loop_emit_count_ms")
METRICS = {f["name"]: f for f in map(json.load, map(open, sorted(glob.glob(
    os.path.join(ROOT, "benchmarks", "layer_metrics", "*.json")))))
    if f["name"] in NEW}
TURN = dict(root="loop.turn", seq="turn")


def _span(trace, sid, parent, name, start, end, cpu_ms=None, **attrs):
    rec = {"trace_id": trace, "span_id": f"{trace}.{sid}",
           "parent_id": None if parent is None else f"{trace}.{parent}",
           "name": name, "start": start, "end": end,
           "duration_ms": (end - start) * 1e3, "error": False,
           "attrs": attrs}
    if cpu_ms is not None:
        rec.update(cpu_ms=cpu_ms, thread=1)
    return rec


def _turn(store, n, t0, dry=None, profiled=True, cpu=True):
    """A loop turn of 100 ms at ``t0``: a step 20-90 with two emits, 40-60
    (of which the thread ran 12 ms) and 70-80 (ran 10). ``dry``: the turn's
    ``dry_*`` attributes, in ms; ``None``: a program that has none."""
    tid = f"turn{n}"
    attrs = dict(dry or {}, turn=n, **({"profiled": True} if profiled else {}))
    for s in (_span(tid, "r", None, "loop.turn", t0, t0 + .100,
                    90.0 if cpu else None, **attrs),
              _span(tid, "s", "r", "loop.step", t0 + .020, t0 + .090,
                    50.0 if cpu else None),
              _span(tid, "e1", "s", "loop.emit", t0 + .040, t0 + .060,
                    12.0 if cpu else None),
              _span(tid, "e2", "s", "loop.emit", t0 + .070, t0 + .080,
                    10.0 if cpu else None)):
        store.add(s)


def _traces(store):
    return store.traces(limit=store.max_traces)


def test_an_attributes_sum_over_the_slices_wall_time():
    store = TraceStore()
    _turn(store, 4, 4.0, {"dry_ms": 30.0, "dry_emit_ms": 20.0,
                          "dry_sweep_ms": 10.0})
    _turn(store, 5, 4.1, {"dry_ms": 0.0})
    _turn(store, 6, 4.25, {"dry_ms": 10.0, "dry_admit_ms": 10.0})
    _turn(store, 7, 9.0, {"dry_ms": 99.0}, profiled=False)  # not the slice's
    traces = _traces(store)
    # 40 ms dry of the 350 from the first root's start to the last one's end
    assert span_attr_share.value(traces, "loop.turn", "dry_ms", **TURN) == \
        (pytest.approx(100.0 * 40.0 / 350.0), 3, 3)
    # where in the turn: over the same spans' dry time
    assert span_attr_share.value(traces, "loop.turn", "dry_emit_ms",
                                 over="dry_ms", **TURN) == \
        (pytest.approx(50.0), 3, 3)
    assert span_attr_share.value(traces, "loop.turn", "dry_admit_ms",
                                 over="dry_ms", **TURN)[0] == \
        pytest.approx(25.0)
    # a phase that never had a share is 0 of the dry time, not nothing
    assert span_attr_share.value(traces, "loop.turn", "dry_fetch_ms",
                                 over="dry_ms", **TURN)[0] == 0.0


def test_no_dry_turn_gives_nothing_for_the_over_form_and_zero_for_the_share():
    store = TraceStore()
    for n in (1, 2):
        _turn(store, n, float(n), {"dry_ms": 0.0})
    traces = _traces(store)
    assert span_attr_share.value(traces, "loop.turn", "dry_ms", **TURN)[0] \
        == 0.0
    assert span_attr_share.value(traces, "loop.turn", "dry_emit_ms",
                                 over="dry_ms", **TURN) == (None, 2, 2)


def test_a_program_without_the_attributes_gives_nothing():
    store = TraceStore()
    for n in (1, 2):
        _turn(store, n, float(n), cpu=False)
    traces = _traces(store)
    assert span_attr_share.value(traces, "loop.turn", "dry_ms", **TURN) == \
        (None, 2, 0)
    assert span_attr_share.value(traces, "loop.turn", "dry_emit_ms",
                                 over="dry_ms", **TURN) == (None, 2, 0)
    assert span_off_cpu_ms.value(traces, "loop.emit", **TURN) == (None, 2, 0)
    assert span_attr_share.value([], "loop.turn", "dry_ms", **TURN) == \
        (None, 0, 0)
    assert span_off_cpu_ms.value([], "loop.emit", **TURN) == (None, 0, 0)


@pytest.mark.parametrize("reader", ["span_attr_share", "span_off_cpu_ms"])
def test_a_gap_in_the_numbers_gives_nothing(reader):
    store = TraceStore()
    for n in (4, 5, 7):
        _turn(store, n, float(n), {"dry_ms": 5.0})

    def read():
        if reader == "span_attr_share":
            return span_attr_share.value(_traces(store), "loop.turn",
                                         "dry_ms", **TURN)
        return span_off_cpu_ms.value(_traces(store), "loop.emit", **TURN)

    assert read() == (None, 0, 0)
    _turn(store, 6, 6.0, {"dry_ms": 5.0})
    assert read()[0] is not None and read()[1] == 4


def test_off_cpu_is_self_time_less_own_cpu_time_a_root():
    store = TraceStore()
    for n in (1, 2, 3):
        _turn(store, n, float(n))
    _turn(store, 4, 4.0, profiled=False)
    traces = _traces(store)
    # two emits a turn: (20 - 12) + (10 - 10) ms off the CPU
    assert span_off_cpu_ms.value(traces, "loop.emit", having="loop.step",
                                 **TURN) == (pytest.approx(8.0), 3, 6)
    # the step's own: 70 - 30 of wall, 50 - 22 of CPU
    assert span_off_cpu_ms.value(traces, "loop.step", **TURN)[0] == \
        pytest.approx(12.0)


def test_each_new_metric_names_what_the_program_emits():
    assert sorted(METRICS) == sorted(NEW)
    src = open(os.path.join(ROOT, "deeplearning4j_tpu", "parallel",
                            "decode.py")).read()
    for name, f in METRICS.items():
        assert f["source"] == "program_span" and f["layer"] == "decode engine"
        par = f["params"]
        assert f'"{par["span"]}"' in src and f'"{par["root"]}"' in src, name
        for key in ("attr", "over"):
            if key in par and par[key].startswith("emit_"):
                assert f'"{par[key]}"' in src, (name, key)
            elif key in par:  # dry_<phase>_ms, spelled from the phase
                assert par[key] in ("dry_ms", "dry_slack_ms", "dry_emit_ms",
                                    "dry_admit_ms")
                assert '"dry_ms"' in src and '"dry_slack_ms"' in src \
                    and '"emit"' in src and '"admit"' in src


CELLS = sorted({c for f in METRICS.values() for c in f["workloads"]})


@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsed_traced_line_has_the_new_metrics(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000029", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    want = {n: f for n, f in METRICS.items() if cell in f["workloads"]}
    assert "loop_device_dry_share" in want
    for n, f in want.items():
        assert n in line["metrics"], (n, sorted(line["metrics"]))
        got = line["metrics"][n]
        assert got["unit"] == f["unit"]
        if f["unit"] == "%":
            assert 0.0 <= got["value"] <= 100.0
        else:
            assert got["value"] >= 0.0
    # the readers say what they found: spans in profiled roots
    found = [(int(m[1]), int(m[2])) for m in re.finditer(
        r"benchmark span_(?:attr_share|off_cpu_ms) [^\n]*: (\d+) spans in "
        r"(\d+) profiled loop.turn roots", p.stderr)]
    assert len(found) == sum(f["reader"] != "span_ms" for f in want.values())
    assert all(spans > 0 and roots > 0 for spans, roots in found)
    # on a CPU the host is the slower side: the loop does see the queue empty
    assert line["metrics"]["loop_device_dry_share"]["value"] > 0.0
