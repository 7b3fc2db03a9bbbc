"""The comparison that decides ``correct`` has been shown to fail.

* The control: the plain reference put in the program's place, computed in
  the nearest precision below the one the configuration states
  (``control_quant``), at a size a test run can hold, read against the
  cells' own limits.
* The faults: the rest of a run driven with the timed path broken
  underneath (``--rehearse`` skips only the look for a chip), once for each
  fault the cells can have: a step that returns its state unchanged; half of
  the batch left out, the mean taken over the rest; a token altered where it
  is produced. (Both cells run on one chip: there is no exchange to leave
  out.)
"""

import json
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run
from benchmarks.harness import compare

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = {w["name"]: json.load(open(os.path.join(
    os.path.dirname(HERE), "workloads", w["name"] + ".json")))
    for w in json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")))["workloads"]}


def _as_checks(values, limits_of):
    return [{"name": k, "value": v, "limit": limits_of(k)}
            for k, v in values.items()
            if isinstance(v, float) and not k.endswith("_at")]


def _train_limit(cell):
    return cell["limits"].get  # a number without a limit is not compared


def test_control_in_lower_precision_fails_the_train_cell():
    cell = CELLS["bert-base-seq512"]
    (row,) = limits_tool.main(["--workload", "bert-base-seq512", "--seeds",
                               "3000000021", "--control", "1", "--rehearse"])
    lim = _train_limit(cell)
    assert compare.verdict(_as_checks(row["program"], lim))
    assert not compare.verdict(
        _as_checks(row["control_float8_e4m3fn"], lim))
    assert not compare.verdict(_as_checks(row["fault_half_batch"], lim))
    assert not compare.verdict(_as_checks(row["fault_state_unchanged"], lim))


def test_control_in_lower_precision_fails_the_serve_cell():
    cell = CELLS["gpt2s-chat-closed128"]
    (row,) = limits_tool.main([
        "--workload", "gpt2s-chat-closed128", "--seeds", "3000000022",
        "--control", "1", "--seconds", "2", "--rehearse"])
    lim = cell["limits"]
    assert all(row["program"][k] <= v for k, v in lim.items())
    # the lower precision fails the mean gap; one altered token the widest
    assert row["control_float8_e4m3fn"]["served_mean_logit_gap"] > \
        lim["served_mean_logit_gap"]
    assert row["fault_one_token_altered"]["served_logit_gap"] > \
        lim["served_logit_gap"]


TEST_DATA = ["--data-dir", os.path.join(HERE, "data"), "--manifest",
             os.path.join(HERE, "data", "manifest.json")]


def _rehearse(cell, seed="3000000023", where=()):
    return bench_run.main(["--workload", cell, "--seed", seed, "--seconds",
                           "1", "--trace", "0", "--rehearse", *where])


def test_sound_rehearsals_are_correct():
    assert _rehearse("bert-base-seq512")["correct"] is True
    assert _rehearse("gpt2s-chat-closed128")["correct"] is True


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    import jax

    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    def broken_step_fn(self, n_in, n_out, return_grads=False):
        model = self.model

        def step(params, opt_state, state, xs, ys, rng):
            score, _ = model.loss_pure(params, state, xs, ys, rng=rng,
                                       train=True)
            return params, opt_state, state, score

        return jax.jit(step)

    monkeypatch.setattr(GraphSolver, "_step_fn", broken_step_fn)
    line = _rehearse("bert-base-seq512")
    assert line["correct"] is False
    # nothing moved: the change of every leaf reads 1 by the measure
    assert line["checks"]["dparam_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    whole = GraphSolver.fit_batch

    def half(self, xs, ys):
        n = xs[0].shape[0] // 2
        return whole(self, tuple(x[:n] for x in xs), tuple(y[:n] for y in ys))

    monkeypatch.setattr(GraphSolver, "fit_batch", half)
    assert _rehearse("bert-base-seq512")["correct"] is False


@pytest.mark.parametrize("cell, vocab, where", [
    ("gpt2s-chat-closed128", 50, ()),
    ("tiny-lstm-closed", 40, TEST_DATA),  # the fixture's second family
])
def test_fault_token_altered_where_it_is_produced(monkeypatch, cell, vocab,
                                                  where):
    """One token of ONE request, sent in the window: the widest gap sees it
    whatever the mean over all compared tokens says. (A rehearsal compares
    every request the window finished, so the altered one is among them.)"""
    from deeplearning4j_tpu.parallel.decode import (DecodeEngine,
                                                    GenerationHandle)

    from benchmarks.harness.serve_driver import ServeRun

    seen = {"open": False, "after": 0, "handle": None}
    window, submit = ServeRun.window, DecodeEngine.submit
    emit = GenerationHandle._emit

    def opened(self, *a, **k):
        seen["open"] = True
        return window(self, *a, **k)

    def marking(self, *a, **k):
        handle = submit(self, *a, **k)
        if seen["open"]:
            seen["after"] += 1
            if seen["after"] == 3:  # well inside the window
                seen["handle"] = handle
        return handle

    def altered(self, index, token):
        wrong = self is seen["handle"] and index == 1
        emit(self, index, (token + 1) % vocab if wrong else token)

    monkeypatch.setattr(ServeRun, "window", opened)
    monkeypatch.setattr(DecodeEngine, "submit", marking)
    monkeypatch.setattr(GenerationHandle, "_emit", altered)
    line = _rehearse(cell, where=where)
    assert seen["handle"] is not None
    assert line["correct"] is False
    check = line["checks"]["served_logit_gap"]
    assert check["value"] > check["limit"]
