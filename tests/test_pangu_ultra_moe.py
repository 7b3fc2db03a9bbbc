"""openPangu-Ultra-MoE on the serving path: the zoo's ``PanguUltraMoeLM``
(latent attention without LoRA scales under sandwich norms, a dense layer
then sigmoid-routed expert layers of which a share of the experts is held
beside a shared expert, and a head with one multi-token-prediction module
whose drafts the engine verifies at two positions) against the benchmark's
plain reference (``benchmarks/families/pangu_ultra_moe.py``: float32, the
non-absorbed MLA, no cache, no kernel, nothing of the program) on seeded
random weights at toy widths: hidden 64, 1 dense + 4 expert layers, 4 heads
of 16 + 8 and 16, ranks 32 / 16, 16 routed experts of 32 top-4 of which 4
are held, one shared.

Tolerances: everything is float32 on both sides and the two differ in the
order of their sums and in the form of attention (absorbed against
expanded), so logits of the order of 1 agree to 2e-5; a routing choice
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
from deeplearning4j_tpu.generate.session import (
    SV_ACCEPTED, SV_DRAFT, SV_EMITTED, SV_LAST, SV_N, SV_PROPOSED, SV_TOK0,
    SV_TOK1, SV_WIDTH, GenerationSession, SpeculativeGenerationSession,
    _check_rewindable, pack_row_spec)
from deeplearning4j_tpu.model.zoo import PanguUltraMoeLM
from deeplearning4j_tpu.nn.layers import ExpertShareMoELayer
from deeplearning4j_tpu.nn.layers.base import fresh_rows
from deeplearning4j_tpu.nn.layers.mla import LatentAttentionLayer
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import Tracer
from deeplearning4j_tpu.ops import set_attention_impl
from deeplearning4j_tpu.ops.mla_attention import (
    mla_decode_attention_pallas, mla_decode_attention_reference)
from deeplearning4j_tpu.parallel.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = runtime.load_family(os.path.join(ROOT, "benchmarks", "families",
                                          "pangu_ultra_moe.py"))
MODEL = dict(vocab_size=96, hidden=64, n_layers=5, n_dense_layers=1,
             n_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, q_lora_rank=32, kv_lora_rank=16, ffn_size=128,
             expert_ffn_size=32, n_routed_experts=16, n_held_experts=4,
             first_held_expert=4, n_shared_experts=1, top_k=4,
             routed_scaling_factor=2.5, rope_theta=25600000.0, max_len=64)
DIMS = FAMILY.dims({"model": MODEL})
# the benchmark configuration's own layout of the family's tree
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "pangu-ultra-moe-ep16.json")) as _f:
    LAYOUT = json.load(_f)["layout"]
T, TOL, SEED = 40, 2e-5, 3000000044
MAX_LEN = 64
# the reference's two forwards, each one program
REF = jax.jit(lambda w, x: FAMILY.decoder_logits(w, x, DIMS))
MTP_REF = jax.jit(lambda w, x: FAMILY.mtp_logits(w, x, DIMS))


def _program(seed=SEED, **over):
    m = PanguUltraMoeLM(**(MODEL | over), seed=1, dtype="float32").init()
    d = FAMILY.dims({"model": MODEL | over})
    weights.install(m, weights.program_weights(FAMILY, d, seed, "float32",
                                               LAYOUT))
    return m, d


@pytest.fixture(scope="module")
def lm():
    """The program with the seed's weights, and the reference's logits
    (the stack's and the MTP module's) over two sequences."""
    model, _ = _program()
    w = weights.make_weights(FAMILY, DIMS, SEED, "float32")
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"], (2, T))
    ref = np.asarray(REF(w, jnp.asarray(ids)))
    mref = np.asarray(MTP_REF(w, jnp.asarray(ids)))
    return model, w, ids, ref, mref


def test_whole_sequence_equals_the_reference(lm):
    model, _, ids, ref, _ = lm
    out = np.asarray(model.output(jnp.asarray(ids))).transpose(0, 2, 1)
    assert np.abs(ref).max() > 0.5  # the comparison is of something
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_the_mtp_module_equals_the_reference(lm):
    """The module over the stack's outputs at every position but the last,
    each paired with the id one on, predicts the id two on."""
    model, _, ids, _, mref = lm
    sess = GenerationSession(model, max_len=MAX_LEN)
    head, name = model.layers[-1], sess._layer_names[-1]

    @jax.jit
    def module(params, x):
        out, _ = sess._forward(params, model.state, x, None,
                               sess.decode_state(2))
        hp = sess._mtp_params(params, out)
        g, _ = head.draft(hp, sess.decode_state(2)[name], out[:, :, :-1],
                          x[:, 1:])
        return head.draft_logits(hp, g)

    got = np.asarray(module(model.params, jnp.asarray(ids)))
    assert np.abs(mref).max() > 0.5
    np.testing.assert_allclose(got, mref, atol=TOL, rtol=0)


def _rows(active, limit, slots):
    """The host's image of greedy rows for ``mtp_step``."""
    out = np.zeros((7, slots), np.int32)
    out[0], out[2], out[6] = active, True, limit
    out[3] = np.ones((slots,), np.float32).view(np.int32)
    out[5] = np.ones((slots,), np.float32).view(np.int32)
    return jnp.asarray(out)


class _Rows:
    """Prompts prefilled and stepped through the session's self-speculating
    functions, jitted, over a batch carry of one row a prompt."""

    def __init__(self, model, prompts, limit=10 ** 6):
        from deeplearning4j_tpu.parallel.decode import install_row

        self.model, self.sess = model, GenerationSession(model,
                                                         max_len=MAX_LEN)
        self.prefill = jax.jit(self.sess.mtp_prefill_row)
        self.step = jax.jit(self.sess.mtp_step)
        b = len(prompts)
        self.carry = self.sess.decode_state(b)
        self.sv = np.zeros((b, SV_WIDTH), np.int32)
        self.seqs = []
        for i, p in enumerate(prompts):
            row, tok, draft = self.fresh(p)
            self.carry = install_row(self.carry, row, jnp.asarray(i))
            self.sv[i, :3] = tok, draft, 1
            self.seqs.append(list(p) + [tok])
        self.sv = jnp.asarray(self.sv)
        self.limit = limit

    def fresh(self, prompt):
        """A fresh prefill of ``prompt`` -> (row, first token, draft)."""
        ids = np.zeros((1, MAX_LEN // 2), np.int32)
        ids[0, :len(prompt)] = prompt
        row, tok, draft, _ = self.prefill(
            self.model.params, self.model.state, jnp.asarray(ids),
            jnp.asarray(pack_row_spec(len(prompt), 0, 0, True, 1.0, 0, 1.0)))
        return row, int(tok), int(draft)

    def advance(self, active=None):
        b = len(self.seqs)
        active = np.ones((b,), bool) if active is None else active
        self.carry, sv, _ = self.step(
            self.model.params, self.model.state, self.carry, self.sv,
            _rows(active, self.limit, b))
        self.sv = sv
        sv = np.asarray(sv)
        for i in range(b):
            n = int(sv[i, SV_N])
            self.seqs[i] += [int(sv[i, SV_TOK0]), int(sv[i, SV_TOK1])][:n]
        return sv


def test_speculative_decoding_through_both_caches_follows_the_reference(lm):
    """Two prompts prefilled (the MTP module over the prompt too), then
    eight self-speculating steps: every committed token is the reference's
    greedy token at its position, every draft is the reference MTP module's
    greedy token, and the logits of the next position through the rewound
    cache equal the reference's."""
    model, w, ids, _, _ = lm
    rows = _Rows(model, [ids[0, :9].tolist(), ids[1, :14].tolist()])
    for _ in range(8):
        sv = rows.advance()
    for i, seq in enumerate(rows.seqs):
        n = len(ids[0, :9]) if i == 0 else 14
        x = jnp.asarray([seq])
        ref = np.asarray(REF(w, x))[0]
        mref = np.asarray(MTP_REF(w, x))[0]
        # position j predicts the token at j + 1
        np.testing.assert_array_equal(np.argmax(ref[n - 1:-1], axis=-1),
                                      seq[n:])
        assert sv[i, SV_LAST] == seq[-1]
        assert sv[i, SV_EMITTED] == len(seq) - n
        # the draft: the module at the last committed position's input
        assert sv[i, SV_DRAFT] == int(np.argmax(mref[-1]))
        # the next logits through the rewound carry
        one = jax.tree_util.tree_map(lambda a: a[i:i + 1], rows.carry)
        _, logits = rows.sess.decode(one, jnp.asarray([seq[-1]]))
        np.testing.assert_allclose(np.asarray(logits)[0],
                                   ref[-1], atol=TOL, rtol=0)


@pytest.fixture(scope="module")
def tiny_vocab():
    """A vocabulary of 16, where a random module's draft is kept now and
    then."""
    model, dims = _program(seed=SEED + 1, vocab_size=16)
    prompts = [np.random.default_rng(i).integers(0, 16, n).tolist()
               for i, n in enumerate((5, 7, 12, 14, 6, 9))]
    return model, prompts


def test_after_a_rejection_and_an_acceptance_both_planes_are_a_fresh_prefills(
        tiny_vocab):
    """After a step that dropped its draft (one token committed) and one
    that kept it (two), each row's carry is what a fresh prefill of its
    committed tokens makes: the stack's planes and the module's up to the
    row's position, the positions, the draft, and the next logits."""
    model, prompts = tiny_vocab
    rows = _Rows(model, prompts)
    seen = set()
    for _ in range(40):
        sv = rows.advance()
        for i in range(len(prompts)):
            if not sv[i, SV_PROPOSED]:
                continue
            kind = "kept" if sv[i, SV_ACCEPTED] else "dropped"
            assert sv[i, SV_N] == (2 if kind == "kept" else 1)
            if kind in seen:
                continue
            seen.add(kind)
            seq = rows.seqs[i]
            row, tok, draft = rows.fresh(seq[:-1])
            assert tok == seq[-1] and draft == sv[i, SV_DRAFT]
            mine = jax.tree_util.tree_map(lambda a: a[i:i + 1], rows.carry)
            pos = len(seq) - 1
            for name, st in row.items():
                assert int(mine[name]["pos"][0]) == int(st["pos"][0]) == pos
                np.testing.assert_allclose(
                    np.asarray(mine[name]["latent"])[:, :, :pos],
                    np.asarray(st["latent"])[:, :, :pos], atol=TOL, rtol=0)
            _, a = rows.sess.decode(mine, jnp.asarray([seq[-1]]))
            _, b = rows.sess.decode(row, jnp.asarray([seq[-1]]))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=TOL, rtol=0)
        if seen == {"kept", "dropped"}:
            break
    assert seen == {"kept", "dropped"}, seen


def _serve(model, prompts, k, tracer=None, max_tokens=40):
    reg = MetricsRegistry()
    eng = DecodeEngine(model, max_len=MAX_LEN, slots=4, speculative_k=k,
                       registry=reg, tracer=tracer)
    try:
        hs = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
        out = [h.result(timeout=600) for h in hs]
        return out, eng.stats(), {
            name: reg.get(f"dl4j_tpu_generate_spec_{name}_total").labels(
                eng.name).value for name in ("steps", "proposed", "accepted")}
    finally:
        eng.shutdown()


def test_the_speculative_stream_is_the_plain_greedy_stream(tiny_vocab):
    """The engine with the module drafting (``speculative_k=1``) serves the
    tokens the same engine serves without it (``speculative_k=0``), never
    past ``max_tokens``, and keeps some drafts and drops others; the
    counters, the ``loop.step`` spans and ``stats()`` say so."""
    model, prompts = tiny_vocab
    plain, st0, _ = _serve(model, prompts, 0)
    tracer = Tracer(sample_rate=1.0)
    spec, st1, counted = _serve(model, prompts, 1, tracer)
    assert spec == plain
    assert all(len(o) == 40 for o in spec)
    assert not st0["speculative"]["enabled"]
    sp = st1["speculative"]
    assert sp["enabled"] and sp["self_draft"] and sp["max_k"] == 1
    assert sp["accepted"] > 0 and sp["proposed"] > sp["accepted"]
    assert counted == {k: sp[k] for k in counted}
    assert sp["steps"] == st1["decode_steps"]
    assert st1["tokens"] == sum(len(o) for o in spec)
    tracer.flush()
    committed = [s["attrs"]["committed"]
                 for t in tracer.store.traces(limit=1 << 20)
                 for s in t["spans"] if s["name"] == "loop.step"
                 and "committed" in s["attrs"]]
    # every token but each request's first (its prefill's) is committed by
    # a step; a kept draft makes a step's count exceed its rows
    assert committed and sum(committed) <= st1["tokens"] - len(prompts)
    assert max(committed) <= 2 * 4


def test_what_the_model_declares_and_what_the_engine_refuses(lm):
    model, _, _, _, _ = lm
    sess = GenerationSession(model, max_len=MAX_LEN)
    assert sess.mtp
    _check_rewindable(sess, "target")  # latent planes rewind by position
    name = sess._layer_names[-1]
    assert sess.planes[name] == frozenset({"latent"})
    assert sess.counts[name]["moe_choices"][-2:] == ("absent", "zero")
    eng = DecodeEngine(model, max_len=MAX_LEN, slots=2,
                       registry=MetricsRegistry())
    try:
        assert eng.speculative_k == 1 and eng._toks.shape == (2, SV_WIDTH)
        with pytest.raises(ValueError, match="self-speculating"):
            eng.submit_prefilled({"prompt": [1, 2], "pos": 2})
    finally:
        eng.shutdown()


def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Sixteen chips holding one routed expert each: their held parts, with
    the shared expert (every chip's, for its own rows) counted once, add up
    to the layer that holds all sixteen."""
    kw = dict(n_in=32, hidden=16, n_routed_experts=16, top_k=4,
              scoring="sigmoid", norm_topk_prob=True,
              routed_scaling_factor=2.5, n_shared_experts=1)
    whole = ExpertShareMoELayer(**kw)
    p = whole.init(jax.random.PRNGKey(0), jnp.float32)
    p["br"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 32))
    want, _ = whole.share(p, x)
    total = whole.shared(p, x)
    for e in range(16):
        share = ExpertShareMoELayer(**kw, n_held_experts=1,
                                    first_held_expert=e)
        cut = dict(p, Eg=p["Eg"][e:e + 1], Eu=p["Eu"][e:e + 1],
                   Ed=p["Ed"][e:e + 1])
        held, zero, _ = share.parts(cut, x)
        total = total + held + zero
    assert np.abs(np.asarray(whole.shared(p, x))).max() > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("fault", ["post_norms", "shared_expert"])
def test_a_model_without_its_post_norms_or_shared_expert_fails(lm, fault):
    """The comparison sees what it is for: the program with the sandwich's
    two output norms left out, or with a shared expert that adds nothing,
    lies far outside the tolerance."""
    model, _, ids, ref, _ = lm
    if fault == "shared_expert":
        params = {name: {k: (jnp.zeros_like(v) if k.endswith("ff_Sd") else v)
                         for k, v in layer.items()}
                  for name, layer in model.params.items()}
        saved, model.params = model.params, params
        try:
            out = model.output(jnp.asarray(ids))
        finally:
            model.params = saved
    else:
        from deeplearning4j_tpu.nn.layers import DecoderBlockLayer

        normed = DecoderBlockLayer._normed
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(DecoderBlockLayer, "_normed",
                       lambda self, p, x, part: x if part in ("po", "pf")
                       else normed(self, p, x, part))
            model._output_fn_cache.clear()
            out = model.output(jnp.asarray(ids))
        model._output_fn_cache.clear()
    gap = np.abs(np.asarray(out).transpose(0, 2, 1) - ref).max()
    assert gap > 1000 * TOL, gap


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
def test_mla_verify_kernel_equals_its_xla_spelling(dtype, tol):
    """``mla_verify``, interpreted: two query positions a row, the first
    one entry short of the second, rows whose window straddles a block's
    edge, fills it, and is the plane's first two entries; what lies past a
    row's length may be anything, NaN included."""
    b, tq, h, w, L, rank = 5, 2, 8, 24, 256, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(k1, (b, tq * h, w), dtype)
    plane = jax.random.normal(k2, (b, 1, L, w), dtype)
    n = np.asarray([2, 128, 129, 256, 77])
    stale = np.arange(L)[None, :] >= n[:, None]
    plane = jnp.where(stale[:, None, :, None], jnp.nan, plane)
    got = mla_decode_attention_pallas(q, plane, jnp.asarray(n), rank, 0.2,
                                      block_k=128, interpret=True, tq=tq)
    ref = mla_decode_attention_reference(
        q, jnp.nan_to_num(plane), jnp.asarray(n), rank, 0.2, tq=tq)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(ref, np.float32), atol=tol,
                               rtol=0)
    # the first query of a row attends one entry fewer than the second
    one = mla_decode_attention_reference(
        q[:, :h], jnp.nan_to_num(plane), jnp.asarray(n - 1), rank, 0.2)
    np.testing.assert_allclose(got[:, :h], np.asarray(one, np.float32),
                               atol=tol, rtol=0)


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("t", [2, 9])
def test_a_window_over_the_plane_equals_one_token_at_a_time(impl, t):
    """A call of ``t`` tokens over a filled plane (the verify of ``t - 1``
    drafts) gives the outputs, the plane and the position that ``t``
    one-token steps give, through the XLA spelling and through the kernel
    (interpreted), at any ``t``: only a prefill of fresh rows, declared as
    one, attends its own tokens alone."""
    layer = LatentAttentionLayer(
        n_in=32, n_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, q_lora_rank=16, kv_lora_rank=16, rope_theta=1e4,
        lora_scales=False)
    p = layer.init(jax.random.PRNGKey(3), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7 + t, 32))
    st = layer.decode_state(3, 128, jnp.float32)
    mix = jax.jit(layer.mix)
    with fresh_rows():                                      # the prompt
        _, st = layer.mix(p, st, x[:, :7], jnp.ones((3, 7)))
    set_attention_impl(impl)
    try:
        window, s2 = mix(p, st, x[:, 7:], None)             # the window
        s1, steps = st, []
        for j in range(7, 7 + t):
            o, s1 = mix(p, s1, x[:, j:j + 1], None)
            steps.append(o)
    finally:
        set_attention_impl("auto")
    np.testing.assert_allclose(np.asarray(window),
                               np.concatenate(steps, axis=1),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(s2["pos"]), [7 + t] * 3)
    np.testing.assert_allclose(np.asarray(s2["latent"])[:, :, :7 + t],
                               np.asarray(s1["latent"])[:, :, :7 + t],
                               atol=1e-6, rtol=0)


def test_a_draft_model_verifying_nine_positions_follows_a_fresh_prefill(lm):
    """A separate draft model at depth 8 (``SpeculativeGenerationSession``,
    the step ``DecodeEngine(draft_model=)`` runs): the target verifies nine
    positions a row over its filled planes. Its greedy stream is the plain
    session's, and after a step its carry gives the next logits that a
    fresh prefill of the committed tokens gives. The draft is the target
    itself, so that every draft is kept and all nine positions count."""
    model, _, ids, _, _ = lm
    prompts = [ids[0, :9].tolist(), ids[1, :14].tolist()]
    spec = SpeculativeGenerationSession(model, model, max_len=MAX_LEN, k=8)
    plain = GenerationSession(model, max_len=MAX_LEN)
    assert spec.generate(prompts, 24) == plain.generate(prompts, 24)
    assert spec.last_stats["accepted"] > spec.last_stats["spec_steps"]
    tcarry, logits, _ = spec.target.prefill(prompts)
    dcarry, _, _ = spec.draft.prefill(prompts)
    last = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
    ones = np.ones((2,), np.float32)
    tcarry, _, toks, _, n_emit = spec.step(
        tcarry, dcarry, last, np.ones((2,), np.int32), np.ones((2,), bool),
        np.zeros((2,), np.uint32), np.ones((2,), bool), ones,
        np.zeros((2,), np.int32), ones, np.full((2,), 8, np.int32))
    toks, n_emit = np.asarray(toks), np.asarray(n_emit)
    assert n_emit.tolist() == [9, 9]
    for i, p in enumerate(prompts):
        fed = p + [int(last[i])] + toks[i, :8].tolist()
        fresh, _, _ = plain.prefill([fed])
        one = jax.tree_util.tree_map(lambda a: a[i:i + 1], tcarry)
        _, a = plain.decode(one, jnp.asarray([toks[i, 8]]))
        _, b = plain.decode(fresh, jnp.asarray([toks[i, 8]]))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=0)
