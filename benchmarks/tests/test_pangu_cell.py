"""The cell ``pangu-agent-closed128``: its comparison has been shown to fail,
at rehearsal size (hidden 64, 1 dense + 4 expert layers and the MTP module,
4 heads of 16 + 8, ranks 32 / 16, 16 routed experts of which 4 are held, one
shared, top-4), on the pattern of ``test_longcat_cell.py``.

* the control: the plain reference in the program's place, computed in
  ``float8_e4m3fn`` (the nearest precision below the configuration's);
* the cell's own faults, planted in the PROGRAM under a rehearsed run: the
  shared expert left out; the sandwich's output norms left out; the held
  experts' part left out; the draft kept without being the stack's own
  token (a verify that always accepts). A check that a model without its
  experts, or a speculation that commits what it did not verify, passes is
  no check of this model.

Each must fail one of the cell's limits; the sound rehearsal passes both.
"""

import json
import math
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "pangu-agent-closed128"
LIMITS = json.load(open(os.path.join(
    os.path.dirname(HERE), "workloads", CELL + ".json")))["limits"]


def _rehearse(seed="3000000044"):
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
        "--workload", CELL, "--seeds", "3000000045", "--control", "1",
        "--seconds", "2", "--rehearse"])
    assert all(row["program"][k] <= v for k, v in LIMITS.items())
    assert row["control_float8_e4m3fn"]["served_mean_logit_gap"] > \
        LIMITS["served_mean_logit_gap"]
    assert row["fault_one_token_altered"]["served_logit_gap"] > \
        LIMITS["served_logit_gap"]


def _shared_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.layers.moe import ExpertShareMoELayer

    shared = ExpertShareMoELayer.shared
    monkeypatch.setattr(ExpertShareMoELayer, "shared",
                        lambda self, p, x: 0.0 * shared(self, p, x))


def _post_norms_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.layers import DecoderBlockLayer

    normed = DecoderBlockLayer._normed
    monkeypatch.setattr(DecoderBlockLayer, "_normed",
                        lambda self, p, x, part: x if part in ("po", "pf")
                        else normed(self, p, x, part))


def _held_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.layers.moe import ExpertShareMoELayer

    parts = ExpertShareMoELayer.parts

    def faulty(self, params, x2, token_mask=None):
        held, zero, counts = parts(self, params, x2, token_mask)
        return 0.0 * held, zero, counts

    monkeypatch.setattr(ExpertShareMoELayer, "parts", faulty)


def _every_draft_kept(monkeypatch):
    from deeplearning4j_tpu.generate import session

    sample = session.sample_tokens
    draft = {}

    def step(self, params, state, carry, sv, rows):
        draft["now"] = sv[:, session.SV_DRAFT]
        return mtp_step(self, params, state, carry, sv, rows)

    def faulty(logits, *a):
        # the verify's first token is the draft whenever one is there
        if "now" in draft and logits.shape[0] == draft["now"].shape[0]:
            return draft.pop("now")
        return sample(logits, *a)

    mtp_step = session.GenerationSession.mtp_step
    monkeypatch.setattr(session.GenerationSession, "mtp_step", step)
    monkeypatch.setattr(session, "sample_tokens", faulty)


@pytest.mark.parametrize("plant", [
    _shared_left_out, _post_norms_left_out, _held_left_out,
    _every_draft_kept], ids=lambda f: f.__name__.strip("_"))
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
                                         "pangu-ultra-moe-ep16.json")))
    family = runtime.load_family(runtime.family_file(bench, config))
    return family, family.dims(config), config


def test_the_cut_weighs_what_was_reckoned():
    """The cut's arithmetic from the family's own tree: the dense layer,
    an expert layer with its 16 held experts, the MTP module, an eighth of
    the vocabulary; every number of the catalog's row stands in the file,
    under its own key, but those the cut names."""
    family, d, config = _family_and_dims()
    counts = family.groups(d)
    leaves = family.leaves(d)
    total = sum(math.prod(shape) * (counts[g] if g else 1)
                for g, shape in leaves.values())
    expert = 3 * 7680 * 2048
    assert family.expert_params(d) == expert == 47_185_920
    mla = family._mla_params(d)
    assert mla == 196_575_232
    # the gains: four of the hidden a layer, the two ranks' norms, the bias
    dense = mla + 3 * 7680 * 18432 + 4 * 7680 + 1536 + 512
    moe = family._moe_layer_params(d) + 16 * expert + 4 * 7680 + 1536 \
        + 512 + 256
    assert round(dense / 1e6) == 621 and round(moe / 1e6) == 1001
    mtp = 2 * 7680 * 7680 + moe + 3 * 7680
    assert round(mtp / 1e6) == 1119
    assert total == dense + 4 * moe + mtp + 2 * 19200 * 7680 + 7680
    assert round(2 * total / 1e9, 2) == 12.08
    # 6 latent planes of 576 numbers a position
    assert family.cache_bytes(d, 1, 2) == 6 * 1152
    assert round(128 * 2560 * family.cache_bytes(d, 1, 2) / 1e9, 2) == 2.26
    row = {"attention_bias": False, "hidden_act": "silu",
           "hidden_size": 7680, "intermediate_size": 18432,
           "kv_lora_rank": 512, "max_position_embeddings": 131072,
           "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
           "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 128, "num_experts_per_tok": 8,
           "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
           "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
           "rope_theta": 25600000, "routed_scaling_factor": 2.5,
           "sandwich_norm": True, "tie_word_embeddings": False,
           "v_head_dim": 128}
    assert {k: config[k] for k in row} == row
    assert config["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600}
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == \
        (5, 1, 16, 19200)
    assert config["engine"]["speculative_k"] == 1
    assert config["engine"]["adaptive"] is False


def test_work_counts_and_the_readers():
    from benchmarks.harness.readers import (counter_children_ratio,
                                            trace_kernel_roofline)

    family, d, _ = _family_and_dims()
    choices = {"e,held": 300.0, "e,absent": 500.0, "e,zero": 0.0}
    s = {"model": d, "dtype_bytes": 2, "decode_steps": 2,
         "decode_attended": [[800, 1.0]] * 256, "prefill_lengths": [],
         "counters": {"dl4j_tpu_moe_choices_total": choices,
                      "dl4j_tpu_generate_spec_proposed_total": {"e": 256.0},
                      "dl4j_tpu_generate_spec_accepted_total": {"e": 0.0}}}
    flops, _ = family.pangu_serve_slice(s)
    head = 2 * 7680 * 19200
    attn = 2 * 128 * (128 + 64 + 128) * 6 * (2 * 800 + 1)
    step = 2 * (2 * family.trunk_params(d) + 2 * family.mtp_params(d)) \
        + 3 * head + attn
    assert flops == pytest.approx(256 * step + 2 * 47_185_920 * 300)
    # one layer's verify: 128 rows, 2 x 128 queries over 800 + 1 entries
    flops, nbytes = family.mla_verify_call(s)
    assert nbytes == pytest.approx(128 * 801 * 1152
                                   + 128 * 2 * 128 * (576 + 512) * 2)
    assert flops == pytest.approx(2 * 128 * (576 + 512) * 128 * 1601)
    record = {"family": family, "slice": s, "device_kind": "TPU v5 lite",
              "trace": {"kernels": {"mla_verify": (1e-3, 2)}}}
    assert trace_kernel_roofline.bound(record, "mla_verify_call") == "flops"
    # a kept draft rides in the step that verified it
    kept = dict(s, counters=dict(s["counters"], **{
        "dl4j_tpu_generate_spec_accepted_total": {"e": 56.0}}))
    assert family.pangu_serve_slice(kept)[0] == pytest.approx(
        200 * step + 2 * 47_185_920 * 300)
    metrics = os.path.join(os.path.dirname(HERE), "layer_metrics")
    f = json.load(open(os.path.join(metrics,
                                    "pangu_mtp_accept_share.json")))
    assert counter_children_ratio.read(
        {"slice": kept}, **f["params"]) == pytest.approx(100 * 56 / 256)
    # the parent has no such counter: nothing, and no error
    assert counter_children_ratio.read(
        {"slice": dict(s, counters={})}, **f["params"]) is None
