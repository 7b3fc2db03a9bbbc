"""The cell ``longcat-agent-closed128``: its comparison has been shown to
fail, at rehearsal size (hidden 64, 2 double layers, 4 heads of 16 + 8, ranks
32 / 16, 16 routed + 8 zero-compute experts of which 4 are held, top-4), on
the pattern of ``test_evabyte_cell.py``.

* the control: the plain reference in the program's place, computed in
  ``float8_e4m3fn`` (the nearest precision below the configuration's);
* the cell's own faults, planted in the PROGRAM under a rehearsed run: the
  held experts' part left out; zero-compute experts that return nought; the
  selection bias added to the weight as well as to the choice; the rotary
  part of the latent left unrotated. A check that a model without its
  experts passes is no check of this model.

Each must fail one of the cell's limits; the sound rehearsal passes both.
"""

import json
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "longcat-agent-closed128"
LIMITS = json.load(open(os.path.join(
    os.path.dirname(HERE), "workloads", CELL + ".json")))["limits"]


def _rehearse(seed="3000000041"):
    return bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                           "1", "--trace", "0", "--rehearse"])


def test_sound_rehearsal_is_correct():
    line = _rehearse()
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(LIMITS)
    for name, check in line["checks"].items():
        assert check["value"] <= check["limit"], name


def test_control_in_lower_precision_fails_the_cell():
    (row,) = limits_tool.main([
        "--workload", CELL, "--seeds", "3000000042", "--control", "1",
        "--seconds", "2", "--rehearse"])
    assert all(row["program"][k] <= v for k, v in LIMITS.items())
    assert row["control_float8_e4m3fn"]["served_mean_logit_gap"] > \
        LIMITS["served_mean_logit_gap"]
    assert row["fault_one_token_altered"]["served_logit_gap"] > \
        LIMITS["served_logit_gap"]


def _held_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.layers.moe import ExpertShareMoELayer

    parts = ExpertShareMoELayer.parts

    def faulty(self, params, x2, token_mask=None):
        held, zero, counts = parts(self, params, x2, token_mask)
        return 0.0 * held, zero, counts

    monkeypatch.setattr(ExpertShareMoELayer, "parts", faulty)


def _zero_experts_return_nought(monkeypatch):
    from deeplearning4j_tpu.nn.layers.moe import ExpertShareMoELayer

    parts = ExpertShareMoELayer.parts

    def faulty(self, params, x2, token_mask=None):
        held, zero, counts = parts(self, params, x2, token_mask)
        return held, 0.0 * zero, counts

    monkeypatch.setattr(ExpertShareMoELayer, "parts", faulty)


def _bias_in_the_weight(monkeypatch):
    from deeplearning4j_tpu.nn.layers import moe
    from deeplearning4j_tpu.ops.moe_dispatch import top_k_routing

    def faulty(scores, bias, top_k, scale=1.0):
        vals, idx = top_k_routing(scores + bias, top_k)
        return scale * vals, idx

    monkeypatch.setattr(moe, "biased_top_k_routing", faulty)


def _latent_left_unrotated(monkeypatch):
    from deeplearning4j_tpu.nn.layers import mla

    rotary = mla.rotary_positions

    def faulty(x, positions, theta):
        # the one key all heads share is the call with one head
        return x if x.shape[1] == 1 else rotary(x, positions, theta)

    monkeypatch.setattr(mla, "rotary_positions", faulty)


@pytest.mark.parametrize("plant", [
    _held_left_out, _zero_experts_return_nought, _bias_in_the_weight,
    _latent_left_unrotated], ids=lambda f: f.__name__.strip("_"))
def test_fault_in_the_program_fails_the_cell(monkeypatch, plant):
    plant(monkeypatch)
    line = _rehearse()
    assert line["correct"] is False
    failed = [n for n, c in line["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert failed, line["checks"]


# ------------------------------------------------- the family's work counts
def _family_and_dims():
    from benchmarks.harness import runtime

    bench = os.path.dirname(HERE)
    config = json.load(open(os.path.join(bench, "configs",
                                         "longcat-flash-ep32.json")))
    family = runtime.load_family(runtime.family_file(bench, config))
    return family, family.dims(config), config


def test_the_cut_weighs_what_the_issue_reckoned():
    """ISSUE 34's arithmetic from the family's own tree: a layer outside
    its experts, an expert, the chip's share of four layers and an eighth of
    the vocabulary; every number of the catalog's row stands in the file."""
    import math

    family, d, config = _family_and_dims()
    counts = family.groups(d)
    total = sum(math.prod(shape) * (counts[g] if g else 1)
                for g, shape in family.leaves(d).values())
    expert = 3 * 6144 * 2048
    assert family.expert_params(d) == expert == 37_748_736
    layer = 638_874_368 + 16 * expert
    assert total == 4 * layer + 2 * 16384 * 6144 + 6144
    assert round(2 * total / 1e9, 2) == 10.35
    assert family.cache_bytes(d, 1, 2) == 9216
    assert family.matmul_params(d) == 4 * (638_874_368 - 24_576 - 2 * 2_048
                                           - 768) + 6144 * 16384
    row = {"attention_bias": False, "hidden_size": 6144,
           "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
           "num_attention_heads": 64, "kv_lora_rank": 512,
           "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
           "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
           "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
           "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
           "rope_theta": 10000000, "attention_method": "MLA",
           "zero_expert_num": 256, "zero_expert_type": "identity",
           "moe_topk": 12}
    assert {k: config[k] for k in row} == row
    assert config["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                   "vocab_size": 131072}
    assert sorted(config["reduced"]) == sorted(config["published"])


def test_work_counts_and_the_counter_reader():
    from benchmarks.harness.readers import (counter_children_ratio,
                                            trace_kernel_roofline)

    family, d, _ = _family_and_dims()
    choices = {"e,held": 300.0, "e,absent": 500.0, "e,zero": 400.0}
    loads = {f"e,{i}": float(i) for i in range(16)}
    s = {"model": d, "dtype_bytes": 2, "decode_steps": 2,
         "decode_attended": [[800, 1.0]] * 256, "prefill_lengths": [],
         "counters": {"dl4j_tpu_moe_choices_total": choices,
                      "dl4j_tpu_moe_expert_tokens_total": loads}}
    flops, _ = family.longcat_serve_slice(s)
    token = 2 * family.matmul_params(d) + 2 * 64 * 320 * 8 * 800
    assert flops == 256 * token + 2 * 37_748_736 * 300
    # one attention block's call: 128 rows at 800 entries of 1,152 bytes
    flops, nbytes = family.mla_decode_call(s)
    assert nbytes == 128 * 800 * 1152 + 128 * 64 * (576 + 512) * 2
    assert flops == 2 * 64 * (576 + 512) * 128 * 800
    record = {"family": family, "slice": s, "device_kind": "TPU v5 lite",
              "trace": {"kernels": {"mla_decode": (1e-3, 2)}}}
    assert trace_kernel_roofline.bound(record, "mla_decode_call") == "bytes"
    metrics = os.path.join(os.path.dirname(HERE), "layer_metrics")
    for name, want in (("moe_zero_choice_share", 100 * 400 / 1200),
                       ("moe_held_load_max_over_mean", 15 / 7.5)):
        f = json.load(open(os.path.join(metrics, name + ".json")))
        assert counter_children_ratio.read(record, **f["params"]) == \
            pytest.approx(want)
        # the parent has no such counter: nothing, and no error
        assert counter_children_ratio.read(
            {"slice": dict(s, counters={})}, **f["params"]) is None
        assert counter_children_ratio.read({"slice": None},
                                           **f["params"]) is None
