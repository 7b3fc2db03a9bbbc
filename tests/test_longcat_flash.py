"""LongCat-Flash on the serving path: the zoo's ``LongCatFlashLM`` (a double
layer with a shortcut-connected mixture of routed and zero-compute experts,
latent attention that owns one latent plane an attention block) against the
benchmark's plain reference (``benchmarks/families/longcat_flash.py``:
float32, the NON-absorbed form, no cache, no kernel, nothing of the program)
on seeded random weights at toy widths: hidden 64, 4 heads of 16 + 8, ranks
32 / 16, 16 routed + 8 zero-compute experts of which 4 are held, top-4.

Tolerances: everything is float32 on both sides and the two differ only in
the order of their sums (a head's scores through a 24-wide latent against a
24-wide key, the experts' parts added in another order), so logits of the
order of 1 agree to 2e-5; a routing choice cannot flip at that distance
unless two scores tie to six digits, which these seeds do not.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import runtime, weights
from deeplearning4j_tpu.generate.paged import (freeze_rows,
                                               mask_inactive_writes)
from deeplearning4j_tpu.generate.session import (
    GenerationSession, SpeculativeGenerationSession)
from deeplearning4j_tpu.model.zoo import LongCatFlashLM
from deeplearning4j_tpu.nn.layers import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.base import fresh_rows
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import Tracer
from deeplearning4j_tpu.parallel.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = runtime.load_family(os.path.join(ROOT, "benchmarks", "families",
                                          "longcat_flash.py"))
MODEL = dict(vocab_size=96, hidden=64, n_layers=2, n_heads=4,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             q_lora_rank=32, kv_lora_rank=16, ffn_size=128,
             expert_ffn_size=32, n_routed_experts=16, zero_expert_num=8,
             n_held_experts=4, first_held_expert=4, moe_topk=4,
             routed_scaling_factor=6.0, rope_theta=1e7, max_len=64)
DIMS = FAMILY.dims({"model": MODEL})
# the benchmark configuration's own layout of the family's tree
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "longcat-flash-ep32.json")) as _f:
    LAYOUT = json.load(_f)["layout"]
# 2 x 160 tokens: over the 128 up to which every held expert runs over every
# token, so the whole sequence takes the sorted dispatch; the first 40
# positions are decoded through the cache
T, T_DECODE, TOL = 160, 40, 2e-5
SEED = 3000000011


def _model():
    model = LongCatFlashLM(**MODEL, seed=1, dtype="float32").init()
    weights.install(model, weights.program_weights(
        FAMILY, DIMS, SEED, "float32", LAYOUT))
    return model


@pytest.fixture(scope="module")
def lm():
    """The program with the seed's weights, and the reference's logits over
    two sequences."""
    model = _model()
    w = weights.make_weights(FAMILY, DIMS, SEED, "float32")
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"], (2, T))
    ref = np.asarray(FAMILY.decoder_logits(w, jnp.asarray(ids), DIMS))
    return model, w, ids, ref


def test_whole_sequence_equals_the_reference(lm):
    model, _, ids, ref = lm
    out = np.asarray(model.output(jnp.asarray(ids))).transpose(0, 2, 1)
    assert np.abs(ref).max() > 0.5  # the comparison is of something
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_a_short_sequence_takes_the_masked_form_and_equals_the_reference(lm):
    """80 tokens: every held expert over every token under a mask (the
    decode step's form); causal, so the reference's first positions are the
    short sequence's."""
    model, _, ids, ref = lm
    out = np.asarray(model.output(jnp.asarray(ids[:, :T_DECODE])))
    np.testing.assert_allclose(out.transpose(0, 2, 1), ref[:, :T_DECODE],
                               atol=TOL, rtol=0)


def test_prefill_then_decode_through_the_latent_cache(lm):
    """Rows at different positions in one batch: prompts of 7 and 19 tokens
    prefilled (the expanded form), then every later position decoded
    through the latent planes (the absorbed form), logits at every position
    against the reference's full forward."""
    model, _, ids, ref = lm
    sess = GenerationSession(model, max_len=64)
    n = (7, 19)
    carry, logits, _ = sess.prefill([ids[r, :n[r]].tolist() for r in (0, 1)])
    for r in (0, 1):
        np.testing.assert_allclose(np.asarray(logits)[r], ref[r, n[r] - 1],
                                   atol=TOL, rtol=0)
    for i in range(T_DECODE - max(n)):
        tokens = [ids[r, n[r] + i] for r in (0, 1)]
        carry, logits = sess.decode(carry, tokens)
        for r in (0, 1):
            np.testing.assert_allclose(np.asarray(logits)[r],
                                       ref[r, n[r] + i], atol=TOL, rtol=0)
    block = model.layer_names()[1]
    assert np.asarray(carry[block]["pos"]).tolist() == [
        n[0] + T_DECODE - max(n), T_DECODE]


def test_absorbed_step_equals_the_expanded_form():
    """The mixer alone: the same 12 positions once as a whole sequence
    (keys and values a head, expanded from the latent) and once as a
    prefill of 5 and seven one-token steps that attend the plane itself."""
    mixer = LatentAttentionLayer(n_in=64, n_heads=4, qk_nope_head_dim=16,
                                 qk_rope_head_dim=8, v_head_dim=16,
                                 q_lora_rank=32, kv_lora_rank=16)
    params = mixer.init(jax.random.PRNGKey(3), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 64), jnp.float32)
    whole, _ = mixer.mix(params, {}, x, None)
    state = mixer.decode_state(2, 16, jnp.float32)
    with fresh_rows():  # the prefill's own form
        first, state = mixer.mix(params, state, x[:, :5], None)
    steps = []
    for t in range(5, 12):
        o, state = mixer.mix(params, state, x[:, t:t + 1], None)
        steps.append(o)
    got = jnp.concatenate([first] + steps, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               atol=2e-6, rtol=0)
    assert np.asarray(state["pos"]).tolist() == [12, 12]
    assert state["latent"].shape == (2, 1, 16, 24)


def test_what_the_block_declares_of_its_decode_state(lm):
    model = lm[0]
    block = model.layers[1]
    assert block.decode_planes() == ("latent0", "latent1")
    assert block.decode_counts() == {"moe_choices": (
        "expert:4", "expert:5", "expert:6", "expert:7", "absent", "zero")}
    assert block.decode_live_bytes(10, 2) == {"latent": 2 * 10 * 24 * 2}
    st = block.decode_state(3, 32, jnp.float32)
    assert st["latent0"].shape == st["latent1"].shape == (3, 1, 32, 24)
    assert st["moe_choices"].shape == (3, 6)
    assert not block.pages_decode_planes
    with pytest.raises(ValueError, match="position-indexed"):
        SpeculativeGenerationSession(model, model, max_len=32)
    with pytest.raises(ValueError, match="not paged|not pageable"):
        DecodeEngine(model, max_len=32, slots=2, block_size=4,
                     registry=MetricsRegistry())


def test_a_masked_row_writes_nothing_and_counts_nothing(lm):
    """The fused step over an active and an idle row: the idle row's planes
    and position stay as they were by what it writes, not by a select."""
    model, _, ids, _ = lm
    sess = GenerationSession(model, max_len=32)
    carry, _, _ = sess.prefill([ids[0, :6].tolist(), ids[1, :9].tolist()])
    active = jnp.asarray([True, False])
    fwd = mask_inactive_writes(carry, active, sess.planes)
    _, new = sess._forward(model.params, model.state,
                           sess._prep(jnp.asarray(ids[:, 9:10])), None, fwd)
    counts = sess.summed_counts(new, active)
    kept = freeze_rows(new, fwd, active, sess.planes)
    for name in sess.planes:
        for plane in sess.planes[name]:
            np.testing.assert_array_equal(np.asarray(new[name][plane])[1],
                                          np.asarray(carry[name][plane])[1])
            assert not np.array_equal(np.asarray(new[name][plane])[0],
                                      np.asarray(carry[name][plane])[0])
        assert np.asarray(kept[name]["pos"]).tolist() == [7, 9]
    # one active token, two layers, top-4: eight choices counted
    assert int(np.asarray(counts["moe_choices"]).sum()) == 2 * 4


def test_engine_equals_the_session_and_its_counters_a_hand_count(lm):
    """``DecodeEngine`` (4 slots, run-ahead on, five requests so that one
    waits for a slot) gives the tokens ``GenerationSession`` alone gives,
    and the new counters rise by what a hand count gives: every token that
    went through the model (a prompt's, and each served token but the last
    fed back) makes ``moe_topk`` choices in each of the two layers; the
    held experts' counter holds the held ones among them by expert."""
    model, w, _, _ = lm
    reg = MetricsRegistry()
    tracer = Tracer(sample_rate=1.0)
    eng = DecodeEngine(model, max_len=64, slots=4, registry=reg,
                       tracer=tracer)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).tolist()
               for n in (5, 12, 20, 9, 15)]
    try:
        handles = [eng.submit(p, max_tokens=10, greedy=True) for p in prompts]
        outs = [h.result(timeout=120) for h in handles]
        assert eng.stats()["steps_ahead"] > 0
        # the latent planes are written by writes of their own
        assert eng.stats()["kv_write_fused_share"] == 0.0
    finally:
        eng.shutdown(drain=True)
    sess = GenerationSession(model, max_len=64)
    assert outs == [sess.generate([p], 10)[0] for p in prompts]

    def children(name):
        return {labels[-1]: c.value for labels, c in reg.get(name).items()}

    choices = children("dl4j_tpu_moe_choices_total")
    fed = sum(len(p) + 9 for p in prompts)
    assert sum(choices.values()) == fed * 2 * MODEL["moe_topk"]
    # the reference's router over the same tokens says where they went
    want = {"held": 0, "absent": 0, "zero": 0}
    per_expert = np.zeros(4)
    for p, o in zip(prompts, outs):
        seq = jnp.asarray([p + o[:-1]])
        x = w["tok_emb"][seq]
        for layer in range(MODEL["n_layers"]):
            wb = {k: w[k][layer] for k in FAMILY.layer_keys(DIMS)}
            h1 = x + FAMILY._mla(FAMILY._norm(x, wb["a0_gn"], 1e-5), wb, 0,
                                 DIMS, None)
            u = FAMILY._norm(h1, wb["f0_gn"], 1e-5)
            chosen = np.asarray(FAMILY.route(u, wb["m_wr"], wb["m_br"],
                                             DIMS))[0] > 0
            per_expert += chosen[:, 4:8].sum(0)
            want["held"] += int(chosen[:, 4:8].sum())
            want["zero"] += int(chosen[:, 16:].sum())
            want["absent"] += int(chosen[:, :4].sum()
                                  + chosen[:, 8:16].sum())
            x = FAMILY._layer(x, wb, DIMS, None)
    assert choices == want
    assert children("dl4j_tpu_moe_expert_tokens_total") == {
        str(4 + e): per_expert[e] for e in range(4)}
    assert "latent" in children("dl4j_tpu_decode_state_bytes")
    # a prefill's held pairs are said by the span that lands its counts
    tracer.flush()
    said = [s["attrs"]["moe_held_pairs"]
            for t in tracer.store.traces(limit=10_000)
                for s in tracer.store.get(t["trace_id"])["spans"]
            if s["name"] == "loop.fetch"
            and "moe_held_pairs" in s["attrs"]]
    assert len(said) == len(prompts)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
def test_mla_decode_kernel_equals_its_xla_spelling(dtype, tol):
    """``mla_decode``, interpreted: rows of length 1, a block's edge on
    both sides, the whole plane and 0 (attends nothing: exactly 0); what
    lies past a row's length may be anything, NaN included. float32 agrees
    to rounding; bfloat16 to the rounding of the weights and of the
    result (values of the order of 1)."""
    from deeplearning4j_tpu.ops import (mla_decode_attention_pallas,
                                        mla_decode_attention_reference)

    b, h, w, L, rank = 6, 8, 24, 256, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(k1, (b, h, w), dtype)
    plane = jax.random.normal(k2, (b, 1, L, w), dtype)
    n = np.asarray([1, 128, 129, 256, 77, 0])
    stale = np.arange(L)[None, :] >= n[:, None]
    plane = jnp.where(stale[:, None, :, None], jnp.nan, plane)
    got = mla_decode_attention_pallas(q, plane, jnp.asarray(n), rank, 0.2,
                                      block_k=128, interpret=True)
    ref = mla_decode_attention_reference(
        q, jnp.nan_to_num(plane), jnp.asarray(n), rank, 0.2)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and not got[5].any()
    np.testing.assert_allclose(got[:5], np.asarray(ref, np.float32)[:5],
                               atol=tol, rtol=0)


def test_the_step_through_the_kernel_equals_the_step_without(lm):
    """The model's decode step with the kernel selected (interpreted here)
    gives the logits the XLA spelling gives."""
    from deeplearning4j_tpu.ops import set_attention_impl

    model, _, ids, ref = lm
    try:
        set_attention_impl("flash")
        sess = GenerationSession(model, max_len=128)
        carry, _, _ = sess.prefill([ids[0, :9].tolist()])
        for i in range(9, 14):
            carry, logits = sess.decode(carry, [ids[0, i]])
            np.testing.assert_allclose(np.asarray(logits)[0], ref[0, i],
                                       atol=TOL, rtol=0)
    finally:
        set_attention_impl("auto")
