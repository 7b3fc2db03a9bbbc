"""The cell ``evabyte-longctx-closed16``: its comparison has been shown to
fail, at rehearsal size (tiny widths, window 16, chunk 4, prompts that cross
up to six windows), on the pattern of ``test_correct_can_fail.py``.

* the control: the plain reference in the program's place, computed in
  ``float8_e4m3fn`` (the nearest precision below the configuration's);
* the cell's own faults, planted in the PROGRAM under a rehearsed run: the
  summaries left out, so that a query attends its window alone; summaries
  that stop at the first window's. A check that the window-only model
  passes is no check of EVA.

Each must fail one of the cell's limits; the sound rehearsal passes both.
"""

import json
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "evabyte-longctx-closed16"
LIMITS = json.load(open(os.path.join(
    os.path.dirname(HERE), "workloads", CELL + ".json")))["limits"]


def _rehearse(seed="3000000031"):
    return bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                           "1", "--trace", "0", "--rehearse"])


def test_sound_rehearsal_is_correct_and_crosses_windows():
    line = _rehearse()
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(LIMITS)
    for name, check in line["checks"].items():
        assert check["value"] <= check["limit"], name


def test_control_in_lower_precision_fails_the_cell():
    (row,) = limits_tool.main([
        "--workload", CELL, "--seeds", "3000000032", "--control", "1",
        "--seconds", "2", "--rehearse"])
    assert all(row["program"][k] <= v for k, v in LIMITS.items())
    # the lower precision fails the mean gap; one altered token the widest
    assert row["control_float8_e4m3fn"]["served_mean_logit_gap"] > \
        LIMITS["served_mean_logit_gap"]
    assert row["fault_one_token_altered"]["served_logit_gap"] > \
        LIMITS["served_logit_gap"]


PER_WINDOW = 4  # the rehearsal's window is 16 and its chunk 4


@pytest.mark.parametrize("fault", ["window_only", "first_window"])
def test_fault_in_the_summaries_fails_the_cell(monkeypatch, fault):
    """The fault sits where the program attends: the step's attention is
    told of fewer summaries than the row's position makes valid, and a
    prompt's later windows see fewer among their keys: none at all (the
    window alone attended), or the first window's and no later one's."""
    import deeplearning4j_tpu.ops as ops
    from deeplearning4j_tpu.ops import eva_attention as ops_eva

    step, whole = ops_eva.eva_decode_attention, ops.mha_attention
    seen = 0 if fault == "window_only" else PER_WINDOW

    def faulty_step(q, k, v, n_sum, n_win, win_start, scale=None):
        return step(q, k, v, n_sum.clip(0, seen), n_win, win_start,
                    scale=scale)

    def faulty_window(q, k, v, mask=None, causal=False, scale=None):
        # a prompt's window over the state's planes: the summaries lead
        if mask is not None and causal and k.shape[2] > q.shape[2]:
            n_sum = k.shape[2] - q.shape[2]
            mask = mask.at[:, seen:n_sum].set(0.0)
        return whole(q, k, v, mask=mask, causal=causal, scale=scale)

    monkeypatch.setattr(ops_eva, "eva_decode_attention", faulty_step)
    monkeypatch.setattr(ops, "mha_attention", faulty_window)
    line = _rehearse()
    assert line["correct"] is False
    failed = [n for n, c in line["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert failed, line["checks"]
