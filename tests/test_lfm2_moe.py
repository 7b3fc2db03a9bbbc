"""LFM2-8B-A1B on the serving path: the zoo's ``Lfm2MoeLM`` (one decoder
block of parts: a gated short convolution that owns a rolling state or
grouped-query attention with QK-norm over a K/V cache of fewer heads, a
dense gated FFN or a sigmoid-scored, bias-selected, renormalised expert
layer with every expert held; a head tied to the embedding) against the
benchmark's plain reference (``benchmarks/families/lfm2_moe.py``: float32,
no cache, no kernel, nothing of the program) on seeded random weights at toy
widths: hidden 64, 6 layers ``[conv, conv, attention, conv, conv, conv]`` of
which 2 dense, 4 query and 2 K/V heads of 16, 8 experts of 32 top-2.

Tolerances: everything is float32 on both sides and the two differ only in
the order of their sums (the convolution's taps, the experts' parts added in
another order), so logits of the order of 1 agree to 2e-5; a routing choice
cannot flip at that distance unless two scores tie to six digits, which
these seeds do not.
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
from deeplearning4j_tpu.model.zoo import Lfm2MoeLM
from deeplearning4j_tpu.nn.layers import (GroupedQueryAttentionLayer,
                                          ShortConvLayer)
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import Tracer
from deeplearning4j_tpu.parallel.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = runtime.load_family(os.path.join(ROOT, "benchmarks", "families",
                                          "lfm2_moe.py"))
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
MODEL = dict(vocab_size=96, hidden=64, layer_types=TYPES, n_dense_layers=2,
             n_heads=4, n_kv_heads=2, ffn_size=128, expert_ffn_size=32,
             n_experts=8, top_k=2, conv_L_cache=3, norm_topk_prob=True,
             use_expert_bias=True, routed_scaling_factor=1.0, rope_theta=1e6,
             max_len=64)
DIMS = FAMILY.dims({"model": MODEL})
# the benchmark configuration's own layout of the family's tree (its first
# six layers are this model's)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "lfm2-8b-a1b-pp2.json")) as _f:
    LAYOUT = json.load(_f)["layout"]
# 2 x 160 tokens: over the 128 up to which every expert runs over every
# token, so the whole sequence takes the sorted dispatch; the first 40
# positions are decoded through the state
T, T_DECODE, TOL = 160, 40, 2e-5
SEED = 3000000013


@pytest.fixture(scope="module")
def lm():
    """The program with the seed's weights, and the reference's logits over
    two sequences."""
    model = Lfm2MoeLM(**MODEL, seed=1, dtype="float32").init()
    weights.install(model, weights.program_weights(
        FAMILY, DIMS, SEED, "float32", LAYOUT))
    w = weights.make_weights(FAMILY, DIMS, SEED, "float32")
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"], (2, T))
    ref = np.asarray(FAMILY.decoder_logits(w, jnp.asarray(ids), DIMS))
    return model, w, ids, ref


def test_whole_sequence_equals_the_reference(lm):
    model, _, ids, ref = lm
    out = np.asarray(model.output(jnp.asarray(ids))).transpose(0, 2, 1)
    assert np.abs(ref).max() > 0.5  # the comparison is of something
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_the_head_is_the_embeddings_matrix(lm):
    """The model holds the matrix once: the output layer owns nothing and
    reads layer 0's ``W``; a change to it moves both ends."""
    model = lm[0]
    names = model.layer_names()
    assert not model.params.get(names[-1])
    assert model.layers[-1].tied_params() == {"W": (0, "W")}
    assert model.layer_params(model.params, len(names) - 1)["W"] is \
        model.params[names[0]]["W"]
    total = sum(int(a.size) for p in model.params.values()
                for a in p.values())
    assert total == sum(int(np.prod(s)) for _, s in
                        FAMILY.leaves(DIMS).values())


def test_prefill_at_true_lengths_then_decode_through_the_states(lm):
    """Rows of DIFFERENT true lengths right-padded into one bucket (7 and
    19 tokens in 32): the convolutions hand over the columns at each row's
    own length, the K/V pair its own entries; then every later position is
    decoded, rows at different positions in one call, logits at every
    position against the reference's full forward."""
    model, _, ids, ref = lm
    sess = GenerationSession(model, max_len=64)
    n = (7, 19)
    carry, logits, _ = sess.prefill([ids[r, :n[r]].tolist() for r in (0, 1)])
    assert ("prefill", 32) in sess._fns
    for r in (0, 1):
        np.testing.assert_allclose(np.asarray(logits)[r], ref[r, n[r] - 1],
                                   atol=TOL, rtol=0)
    for i in range(T_DECODE - max(n)):
        tokens = [ids[r, n[r] + i] for r in (0, 1)]
        carry, logits = sess.decode(carry, tokens)
        for r in (0, 1):
            np.testing.assert_allclose(np.asarray(logits)[r],
                                       ref[r, n[r] + i], atol=TOL, rtol=0)
    names = model.layer_names()
    assert np.asarray(carry[names[3]]["pos"]).tolist() == [
        n[0] + T_DECODE - max(n), T_DECODE]
    assert carry[names[3]]["cache_k"].shape == (2, 2, 64, 16)  # 2 K/V heads
    assert carry[names[1]]["conv"].shape == (2, 2, 64)         # two columns


def test_a_prompt_of_one_token_hands_over_a_zero_column():
    """The mixer alone: the same 12 positions once as a whole sequence and
    once as a right-padded prefill of 1 and 5 tokens (true lengths under
    the mask, in a call of 8) and one-token steps; a row shorter than the
    state is deep hands over zeros left of position 0."""
    mixer = ShortConvLayer(n_in=16, kernel=3)
    params = mixer.init(jax.random.PRNGKey(3), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 16), jnp.float32)
    whole, _ = mixer.mix(params, {}, x, None)
    n = np.asarray([1, 5])
    mask = (np.arange(8)[None, :] < n[:, None]).astype(np.float32)
    state = mixer.decode_state(2, 16, jnp.float32)
    first, state = mixer.mix(params, state, x[:, :8], jnp.asarray(mask))
    assert not np.asarray(state["conv"])[0, 0].any()  # position -1
    for r in (0, 1):
        np.testing.assert_allclose(np.asarray(first)[r, :n[r]],
                                   np.asarray(whole)[r, :n[r]], atol=2e-6)
    for i in range(7):  # row r steps through positions n[r] + i
        tok = jnp.stack([x[r, n[r] + i] for r in (0, 1)])[:, None]
        o, state = mixer.mix(params, state, tok, None)
        for r in (0, 1):
            np.testing.assert_allclose(np.asarray(o)[r, 0],
                                       np.asarray(whole)[r, n[r] + i],
                                       atol=2e-6)
    assert state["conv"].shape == (2, 2, 16)


def test_grouped_attention_step_equals_the_whole_sequence():
    """The attention mixer alone: 12 positions once as a whole sequence (no
    cache) and once as a prefill of 5 and seven one-token steps against a
    cache of 2 K/V heads for 4 query heads."""
    mixer = GroupedQueryAttentionLayer(n_in=64, n_heads=4, n_kv_heads=2)
    params = mixer.init(jax.random.PRNGKey(3), jnp.float32)
    params["gq"] = params["gq"] * 1.3
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 64), jnp.float32)
    whole, _ = mixer.mix(params, {}, x, None)
    state = mixer.decode_state(2, 16, jnp.float32)
    first, state = mixer.mix(params, state, x[:, :5], None)
    steps = []
    for t in range(5, 12):
        o, state = mixer.mix(params, state, x[:, t:t + 1], None)
        steps.append(o)
    got = jnp.concatenate([first] + steps, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               atol=2e-6, rtol=0)
    assert np.asarray(state["pos"]).tolist() == [12, 12]
    assert state["cache_k"].shape == state["cache_v"].shape == (2, 2, 16, 16)


def test_what_the_blocks_declare_of_their_decode_state(lm):
    model = lm[0]
    conv, attn = model.layers[1], model.layers[3]
    assert conv.decode_planes() == () and not conv.pages_decode_planes
    assert set(attn.decode_planes()) >= {"cache_k", "cache_v"}
    assert attn.pages_decode_planes
    assert conv.decode_counts() == {}  # a dense feed-forward counts nothing
    assert attn.decode_counts() == {"moe_choices": tuple(
        f"expert:{e}" for e in range(8)) + ("absent", "zero")}
    assert model.layers[4].decode_counts() == attn.decode_counts()
    assert conv.decode_live_bytes(10, 2) == {"conv": 2 * 64 * 2}
    assert conv.decode_live_bytes(999, 2) == conv.decode_live_bytes(0, 2)
    assert attn.decode_live_bytes(10, 2) == {"kv": 10 * 2 * 2 * 16 * 2}
    assert set(conv.decode_state(3, 32, jnp.float32)) == {"conv"}
    st = model.layers[4].decode_state(3, 32, jnp.float32)
    assert st["conv"].shape == (3, 2, 64)
    assert st["moe_choices"].shape == (3, 10)
    with pytest.raises(ValueError, match="position-indexed"):
        SpeculativeGenerationSession(model, model, max_len=32)
    with pytest.raises(ValueError, match="not paged|not pageable"):
        DecodeEngine(model, max_len=32, slots=2, block_size=4,
                     registry=MetricsRegistry())


def test_an_idle_rows_state_is_unchanged_by_the_fused_step(lm):
    """The fused step over an active and an idle row: the idle row's K/V
    planes stay as they were by what it writes (no select over a plane),
    its rolling states and position by the row select."""
    model, _, ids, _ = lm
    sess = GenerationSession(model, max_len=32)
    carry, _, _ = sess.prefill([ids[0, :6].tolist(), ids[1, :9].tolist()])
    active = jnp.asarray([True, False])
    fwd = mask_inactive_writes(carry, active, sess.planes)
    _, new = sess._forward(model.params, model.state,
                           sess._prep(jnp.asarray(ids[:, 9:10])), None, fwd)
    counts = sess.summed_counts(new, active)
    kept = freeze_rows(new, fwd, active, sess.planes)
    names = model.layer_names()
    assert set(sess.planes) == {names[3]}
    for plane in ("cache_k", "cache_v"):
        np.testing.assert_array_equal(np.asarray(new[names[3]][plane])[1],
                                      np.asarray(carry[names[3]][plane])[1])
        assert not np.array_equal(np.asarray(new[names[3]][plane])[0],
                                  np.asarray(carry[names[3]][plane])[0])
    assert np.asarray(kept[names[3]]["pos"]).tolist() == [7, 9]
    for name in (names[1], names[2], names[4], names[5], names[6]):
        np.testing.assert_array_equal(np.asarray(kept[name]["conv"])[1],
                                      np.asarray(carry[name]["conv"])[1])
        assert not np.array_equal(np.asarray(kept[name]["conv"])[0],
                                  np.asarray(carry[name]["conv"])[0])
    # one active token, four expert layers, top-2: eight choices counted
    assert int(np.asarray(counts["moe_choices"]).sum()) == 4 * 2


def test_engine_equals_the_session_and_its_counters_a_hand_count(lm):
    """``DecodeEngine`` (4 slots, run-ahead on, five requests so that one
    waits for a slot) gives the tokens ``GenerationSession`` alone gives,
    and the counters rise by what a hand count gives: every token that went
    through the model makes ``top_k`` choices in each of the four expert
    layers, ALL of them held (``absent`` and ``zero`` stay 0); the gauge
    has the kinds ``kv`` and ``conv``."""
    model, w, _, _ = lm
    reg = MetricsRegistry()
    tracer = Tracer(sample_rate=1.0)
    eng = DecodeEngine(model, max_len=64, slots=4, registry=reg,
                       tracer=tracer)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).tolist()
               for n in (5, 12, 20, 9, 15)]
    seen = {}

    def gauge():
        for labels, c in reg.get("dl4j_tpu_decode_state_bytes").items():
            seen[labels[-1]] = max(seen.get(labels[-1], 0), c.value)

    eng._step_hook = gauge
    try:
        handles = [eng.submit(p, max_tokens=10, greedy=True) for p in prompts]
        outs = [h.result(timeout=120) for h in handles]
        assert eng.stats()["steps_ahead"] > 0
    finally:
        eng.shutdown(drain=True)
    sess = GenerationSession(model, max_len=64)
    assert outs == [sess.generate([p], 10)[0] for p in prompts]

    def children(name):
        return {labels[-1]: c.value for labels, c in reg.get(name).items()}

    choices = children("dl4j_tpu_moe_choices_total")
    fed = sum(len(p) + 9 for p in prompts)
    assert choices == {"held": fed * 4 * MODEL["top_k"], "absent": 0,
                       "zero": 0}
    # the reference's router over the same tokens says where they went
    per_expert = np.zeros(8)
    for p, o in zip(prompts, outs):
        x = w["tok_emb"][jnp.asarray([p + o[:-1]])]
        for i in range(len(TYPES)):
            wl = FAMILY.layer_weights(w, i)
            if i >= MODEL["n_dense_layers"]:
                op = FAMILY._conv if FAMILY.is_conv(DIMS, i) else FAMILY._attn
                h1 = x + op(FAMILY._norm(x, wl["g1"], 1e-5), wl, DIMS, None)
                u = FAMILY._norm(h1, wl["g2"], 1e-5)
                per_expert += (np.asarray(FAMILY.route(
                    u, wl["wr"], wl["br"], DIMS))[0] > 0).sum(0)
            x = FAMILY._layer(x, wl, i, DIMS, None)
    assert children("dl4j_tpu_moe_expert_tokens_total") == {
        str(e): per_expert[e] for e in range(8)}
    # four rows at most: five convolutions x 2 columns x 64 x 4 bytes each;
    # one attention layer's keys and values of 2 heads of 16 a position
    assert seen["conv"] == 4 * 5 * 2 * 64 * 4
    assert 0 < seen["kv"] <= 4 * 64 * 2 * 2 * 16 * 4
    tracer.flush()
    said = [s["attrs"]["moe_held_pairs"]
            for t in tracer.store.traces(limit=10_000)
            for s in tracer.store.get(t["trace_id"])["spans"]
            if s["name"] == "loop.fetch" and "moe_held_pairs" in s["attrs"]]
    assert sorted(said) == sorted(len(p) * 4 * MODEL["top_k"]
                                  for p in prompts)


def test_the_step_through_the_kernels_equals_the_step_without(lm):
    """The model's decode step with the kernels selected (the grouped
    single-query kernel and the in-place cache write, interpreted here)
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


def test_the_tied_head_trains_the_one_matrix(lm):
    """``fit``'s loss reaches the embedding's matrix through both of its
    uses, and the output layer has no gradient of its own."""
    model, _, ids, _ = lm
    grads = model.calculate_gradients(ids[:, :16], ids[:, 1:17])
    names = model.layer_names()
    assert not grads.get(names[-1])
    g = np.asarray(grads[names[0]]["W"])
    # rows that are neither fed in nor the label still move: the head's use
    fed = set(ids[:, :17].ravel().tolist())
    others = [r for r in range(MODEL["vocab_size"]) if r not in fed]
    assert others and np.abs(g[others]).max() > 0
