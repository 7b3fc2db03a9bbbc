"""The cell ``lfm2-multiturn-closed128``: its comparison has been shown to
fail, at rehearsal size (hidden 64, 6 layers ``[conv, conv, attention, conv,
conv, conv]`` of which 2 dense, 4 query and 2 K/V heads of 16, 8 experts of
32 top-2, vocabulary 512, float32), on the pattern of
``test_longcat_cell.py``.

* the control: the plain reference in the program's place, computed in
  ``float8_e4m3fn`` (the nearest precision below the configuration's);
* the cell's own faults, planted in the PROGRAM under a rehearsed run: the
  convolution's state handed over at the bucket's end and not at the row's
  true length; K/V head ``h % n_kv`` for ``h // group``; the QK-norm left
  out; the expert bias added to the weight as well as to the choice; the
  chosen weights not renormalised.

Each must fail one of the limits a rehearsed run is held to (the GPT-2
cell's two: the rehearsal is float32); the sound rehearsal passes both. On
the chip the cell holds the mean gap alone, at a limit read there.
"""

import json
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "lfm2-multiturn-closed128"
_CELL = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
# what a rehearsed run is held to: the cell's limits with the rehearsal's own
# over them (float32, where the GPT-2 cell's two limits hold)
LIMITS = _CELL["limits"] | _CELL["rehearse"]["cell"]["limits"]


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


def _state_at_the_buckets_end(monkeypatch):
    from deeplearning4j_tpu.nn.layers.short_conv import ShortConvLayer

    mix = ShortConvLayer.mix

    def faulty(self, params, state, x, mask):
        return mix(self, params, state, x, None)

    monkeypatch.setattr(ShortConvLayer, "mix", faulty)


def _kv_head_by_remainder(monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.gqa import GroupedQueryAttentionLayer

    projections = GroupedQueryAttentionLayer._projections

    def faulty(self, params, x, at):
        q, k, v = projections(self, params, x, at)
        g = self.n_heads // self.n_kv_heads
        # query head h = kv * g + j goes where head j * n_kv + kv stood:
        # it now reads the K/V head (its own number) % n_kv
        b, _, t, d = q.shape
        q = q.reshape(b, g, self.n_kv_heads, t, d).swapaxes(1, 2)
        return q.reshape(b, self.n_heads, t, d), k, v

    monkeypatch.setattr(GroupedQueryAttentionLayer, "_projections", faulty)


def _qk_norm_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.layers import gqa

    monkeypatch.setattr(gqa, "rms_norm", lambda x, gain, eps: x)


def _bias_in_the_weight(monkeypatch):
    from deeplearning4j_tpu.nn.layers import moe
    from deeplearning4j_tpu.ops.moe_dispatch import top_k_routing

    def faulty(scores, bias, top_k, scale=1.0):
        vals, idx = top_k_routing(scores + bias, top_k)
        return scale * vals, idx

    monkeypatch.setattr(moe, "biased_top_k_routing", faulty)


def _top_k_not_renormalised(monkeypatch):
    from deeplearning4j_tpu.model.zoo import lfm2_moe

    init = lfm2_moe.Lfm2MoeLM.__init__

    def faulty(self, *args, **kw):
        init(self, *args, **dict(kw, norm_topk_prob=False))

    monkeypatch.setattr(lfm2_moe.Lfm2MoeLM, "__init__", faulty)


@pytest.mark.parametrize("plant", [
    _state_at_the_buckets_end, _kv_head_by_remainder, _qk_norm_left_out,
    _bias_in_the_weight, _top_k_not_renormalised],
    ids=lambda f: f.__name__.strip("_"))
def test_fault_in_the_program_fails_the_cell(monkeypatch, plant):
    plant(monkeypatch)
    line = _rehearse()
    assert line["correct"] is False
    failed = [n for n, c in line["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_the_chips_limit_lies_between_its_two_readings():
    """On the chip the cell compares the MEAN gap alone (bfloat16 routing
    leaves the float32 reference's: the cell's file says why): the limit has
    room above the program's largest reading and under the fp8 control's
    least, and one altered token, which it cannot see, is said to pass."""
    read = _CELL["limits_read"]
    assert set(_CELL["limits"]) == {"served_mean_logit_gap"}
    limit = _CELL["limits"]["served_mean_logit_gap"]
    assert len(read["program"]["seeds"]) >= 6
    assert 1.5 * read["program"]["served_mean_logit_gap"][1] < limit
    assert 1.5 * limit < read["control_float8_e4m3fn"]["served_mean_logit_gap"][0]
    assert read["one_token_altered"]["served_mean_logit_gap"][1] < limit
    # the widest gap separates nothing there
    assert read["program"]["served_logit_gap"][1] > \
        read["one_token_altered"]["served_logit_gap"][0]


# ------------------------------------------------- the family's work counts
def _family_and_dims():
    from benchmarks.harness import runtime

    config = json.load(open(os.path.join(BENCH, "configs",
                                         "lfm2-8b-a1b-pp2.json")))
    family = runtime.load_family(runtime.family_file(BENCH, config))
    return family, family.dims(config), config


def test_the_cut_weighs_what_the_issue_reckoned():
    """ISSUE 36's arithmetic from the family's own tree: the two operators,
    an expert layer's feed-forward, the stage's 14 layers with the tied
    head; every number of the catalog's row stands in the file."""
    import math

    family, d, config = _family_and_dims()
    shapes = {k: math.prod(shape) for k, (_, shape) in
              family.leaves(d).items()}

    def layer(i, keys):
        return sum(shapes[f"l{i:02d}_{k}"] for k in keys)

    assert layer(0, ("win", "wc", "wout")) == 16_783_360
    assert layer(2, ("wq", "wk", "wv", "wo", "gq", "gk")) == 10_485_888
    assert layer(0, ("w1", "w3", "w2")) == 44_040_192
    assert layer(2, ("wr", "br", "eg", "eu", "ed")) == 352_387_104
    assert family.expert_params(d) == 11_010_048
    total = sum(shapes.values())
    assert total == 121_655_296 + 3 * 362_877_088 + 9 * 369_174_560 \
        + 134_217_728 + 2_048 == 4_667_077_376
    assert round(2 * total / 1e9, 3) == 9.334
    assert family.cache_bytes(d, 1, 2) - family.cache_bytes(d, 0, 2) == 6144
    assert family.cache_bytes(d, 0, 2) == 90_112
    row = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
           "intermediate_size": 7168, "max_position_embeddings": 128000,
           "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
           "norm_eps": 1e-05, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_dense_layers": 2,
           "num_experts": 32, "num_experts_per_tok": 4,
           "num_key_value_heads": 8, "rope_theta": 1000000,
           "routed_scaling_factor": 1, "use_expert_bias": True,
           "vocab_size": 65536}
    assert {k: config[k] for k in row} == row
    published = config["published"]
    assert published["num_hidden_layers"] == 24
    assert [i for i, t in enumerate(published["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert config["layer_types"] == published["layer_types"][:14] \
        == config["model"]["layer_types"]
    assert config["num_hidden_layers"] == 14
    assert sorted(config["reduced"]) == sorted(published)
    assert "two pipeline stages" in config["deployment"]


def test_work_counts_and_the_counter_reader():
    from benchmarks.harness.readers import (counter_children_ratio,
                                            trace_kernel_roofline)

    family, d, _ = _family_and_dims()
    choices = {"e,held": 3000.0, "e,absent": 0.0, "e,zero": 0.0}
    loads = {f"e,{i}": float(i) for i in range(32)}
    s = {"model": d, "dtype_bytes": 2, "decode_steps": 2,
         "decode_attended": [[1500, 1.0]] * 256, "prefill_lengths": [],
         "counters": {"dl4j_tpu_moe_choices_total": choices,
                      "dl4j_tpu_moe_expert_tokens_total": loads}}
    outside = 11 * 4 * 2048 * 2048 + 3 * 2 * (2048 * 2048 + 2048 * 512) \
        + 2 * 3 * 2048 * 7168 + 12 * 2048 * 32 + 2048 * 65536
    assert family.matmul_params(d) == outside
    flops, _ = family.lfm2_serve_slice(s)
    token = 2 * outside + 11 * 2 * 3 * 2048 + 3 * 4 * 2048 * 1500
    assert flops == 256 * token + 2 * 11_010_048 * 3000
    # one attention layer's call: 128 rows at 1,500 entries of 2,048 bytes
    flops, nbytes = family.lfm2_gqa_decode_call(s)
    assert nbytes == 128 * 1500 * 2048 + 128 * 2 * 2048 * 2
    assert flops == 4 * 2048 * 128 * 1500
    record = {"family": family, "slice": s, "device_kind": "TPU v5 lite",
              "trace": {"kernels": {"flash_decode": (1e-3, 2)}}}
    assert trace_kernel_roofline.bound(record,
                                       "lfm2_gqa_decode_call") == "bytes"
    f = json.load(open(os.path.join(
        BENCH, "layer_metrics", "lfm2_expert_load_max_over_mean.json")))
    assert counter_children_ratio.read(record, **f["params"]) == \
        pytest.approx(31 / 15.5)
    # the parent has no such counter: nothing, and no error
    assert counter_children_ratio.read(
        {"slice": dict(s, counters={})}, **f["params"]) is None
