"""EvaByte on the serving path: the zoo's ``EvaByteLM`` (EVA attention: an
exact window beside one learned summary a chunk) against the benchmark's
plain reference (``benchmarks/families/evabyte.py``: float32, no cache, no
kernel, nothing of the program) on seeded random weights, at a small size:
window 16, chunk 4, so 72 positions cross four windows.

* the full forward, all 8 prediction heads;
* prefill then decode through the cache, logits and greedy bytes, with rows
  standing at a window's last position, its first, a chunk's edge and inside
  the first window: through ``GenerationSession`` and through
  ``DecodeEngine`` with rows at different positions and idle rows among them;
* what the mixer declares of its decode state, and what the carry's masking,
  freezing and paging make of it;
* the step's kernels (``eva_decode``, the head-dimension-minor
  ``kv_cache_write``) against their ``jax.numpy`` spellings, interpreted.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import runtime, weights
from deeplearning4j_tpu.generate.paged import (freeze_rows,
                                               mask_inactive_writes)
from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.model.zoo import EvaByteLM, TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import Tracer
from deeplearning4j_tpu.ops import (eva_decode_attention_pallas,
                                    eva_decode_attention_reference,
                                    flash_masked_cache_write,
                                    masked_cache_write_reference,
                                    set_attention_impl)
from deeplearning4j_tpu.parallel.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = runtime.load_family(os.path.join(ROOT, "benchmarks", "families",
                                          "evabyte.py"))
MODEL = dict(vocab_size=40, hidden=32, n_layers=2, n_heads=2, ffn_size=64,
             window=16, chunk=4, n_pred_heads=8, rope_theta=1e5, max_len=128)
DIMS = FAMILY.dims({"model": MODEL})
LAYOUT = {
    "index": {"j": ["i", 1], "n": ["n_layers", 1], "m": ["n_layers", 2]},
    "tok_emb": ["layer_0", "W"], "gf": ["layer_{n}", "gamma"],
    "head_w": ["layer_{m}", "W"],
    "block": {k: ["layer_{j}", v] for k, v in dict(
        g1="g1", wq="Wq", wk="Wk", wv="Wv", wo="Wo", mu="mu", phi="phi",
        g2="g2", wg="Wg", wu="Wu", wd="Wd").items()},
}
T = 72


@pytest.fixture(scope="module")
def lm():
    """The program with the seed's weights, and the reference's logits of
    every head over one sequence of 72 bytes."""
    model = EvaByteLM(**MODEL, seed=1, dtype="float32").init()
    w = weights.make_weights(FAMILY, DIMS, 3000000011, "float32")
    weights.install(model, weights.program_tree(FAMILY, DIMS, w, LAYOUT))
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"], (2, T))
    ref = np.asarray(FAMILY.decoder_logits(w, jnp.asarray(ids), DIMS,
                                           pred_heads=8))
    return model, w, ids, ref


_LOGITS = jax.jit(lambda w, ids: FAMILY.decoder_logits(w, ids, DIMS))


def _greedy(w, prompt, n):
    """The reference's own greedy continuation: one full forward a byte
    (over a fixed length: causal, so the zeros after the frontier move
    nothing before it)."""
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, T), np.int32)
        ids[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(_LOGITS(w, jnp.asarray(ids))[0,
                                                              len(seq) - 1])))
    return seq[len(prompt):]


def test_full_forward_matches_the_reference_on_every_head(lm):
    model, _, ids, ref = lm
    out = np.asarray(model.output(jnp.asarray(ids)))  # [b, 8 * vocab, t]
    assert out.shape == (2, 8 * MODEL["vocab_size"], T)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out.transpose(0, 2, 1), ref, atol=5e-6)
    # the summaries are in it: the reference without them reads elsewhere
    assert np.abs(ref).max() > 0.5


# a window's last position, its first, a chunk's edge, inside the first
# window, and deeper: the prompt's length is where the row first stands
@pytest.mark.parametrize("n", [16, 15, 17, 32, 31, 33, 4, 5, 20, 47, 48, 64])
def test_prefill_then_decode_matches_the_full_forward(lm, n):
    model, _, ids, ref = lm
    sess = GenerationSession(model, max_len=MODEL["max_len"])
    v = MODEL["vocab_size"]
    carry, logits, _ = sess.prefill([ids[0, :n].tolist()])
    np.testing.assert_allclose(np.asarray(logits)[0], ref[0, n - 1, :v],
                               atol=5e-6)
    for i in range(8):  # teacher-forced along the sequence the reference ran
        carry, logits = sess.decode(carry, [int(ids[0, n + i])])
        np.testing.assert_allclose(np.asarray(logits)[0], ref[0, n + i, :v],
                                   atol=5e-6)
    assert int(carry["layer_1"]["pos"][0]) == n + 8


def test_session_generates_the_references_greedy_bytes(lm):
    model, w, ids, _ = lm
    sess = GenerationSession(model, max_len=MODEL["max_len"])
    prompts = [ids[0, :15].tolist(), ids[1, :30].tolist(), ids[1, :3].tolist()]
    got = sess.generate(prompts, 6)
    assert got == [_greedy(w, p, 6) for p in prompts]


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_engine_rows_at_different_positions_with_idle_rows(lm, impl):
    """Five requests over six slots (one stays idle throughout, others fall
    idle as they finish), prompts that leave the rows in different windows
    and chunks; "flash" runs the step's Pallas kernels interpreted."""
    model, w, ids, _ = lm
    set_attention_impl(impl)
    eng = DecodeEngine(model, max_len=MODEL["max_len"], slots=6,
                       registry=MetricsRegistry())
    try:
        prompts = [ids[0, :15].tolist(), ids[1, :16].tolist(),
                   ids[0, :33].tolist(), ids[1, :5].tolist(),
                   ids[0, :48].tolist()]
        lens = [20, 4, 7, 13, 18]
        handles = [eng.submit(p, max_tokens=n) for p, n in zip(prompts, lens)]
        got = [h.result(timeout=300) for h in handles]
        assert got == [_greedy(w, p, n) for p, n in zip(prompts, lens)]
        assert eng.stats()["failed"] == 0 and eng.stats()["carry_rebuilds"] == 0
        # EVA's planes are written by writes of their own, either spelling
        assert eng.stats()["kv_write_fused_share"] == 0.0
    finally:
        eng.shutdown(drain=False)
        set_attention_impl("auto")


def test_engine_counts_closed_windows_live_bytes_and_prefill_spans(lm):
    model, _, ids, _ = lm
    reg, tracer = MetricsRegistry(), Tracer(sample_rate=1.0)
    eng = DecodeEngine(model, max_len=MODEL["max_len"], slots=2, registry=reg,
                       tracer=tracer, name="eva")
    try:
        # 30 bytes of prompt close one window; 20 more close two (32, 48)
        eng.submit(ids[0, :30].tolist(), max_tokens=21).result(timeout=300)
        closed = reg.get("dl4j_tpu_decode_windows_closed_total").labels("eva")
        assert closed.value == 2
        live = reg.get("dl4j_tpu_decode_state_bytes")
        assert {k for (_, k), _ in live.items()} == {"window", "summary"}
        tracer.flush()
        spans = [s for t in tracer.store.traces(limit=1000)
                 for s in t["spans"] if s["name"] == "loop.prefill"]
        assert [s["attrs"]["windows"] for s in spans] == [1]
        assert spans[0]["attrs"]["bucket"] == 32
        assert spans[0]["attrs"]["pad_share"] == pytest.approx(100 * 2 / 32)
    finally:
        eng.shutdown(drain=False)
    # what a row holds where it stands: 2 layers x (k, v) x 32 numbers x 4 B
    block = model.layers[1]
    assert block.decode_live_bytes(37, 4) == {"window": 5 * 256,
                                              "summary": 2 * 4 * 256}
    assert FAMILY.cache_bytes(DIMS, 37, 4) == 2 * (5 + 8) * 256


def test_the_mixer_declares_its_planes_and_the_carry_works_from_them(lm):
    model, _, _, _ = lm
    sess = GenerationSession(model, max_len=MODEL["max_len"])
    assert sess.planes == {"layer_1": frozenset({"eva_k", "eva_v"}),
                           "layer_2": frozenset({"eva_k", "eva_v"})}
    assert sess.paged_layers == frozenset()
    st = sess.decode_state(3)["layer_1"]
    # 8 windows x 4 summaries, then the open window's 16 singletons
    assert st["eva_k"].shape == (3, 2, 32 + 16, 16)
    assert st["chunk_k"].shape == (3, 2, 4, 16)  # the open chunk: per row
    active = jnp.asarray([True, False, True])
    carry = {"layer_1": st, "rec": {"h": jnp.zeros((3, 4))}}
    fwd = mask_inactive_writes(carry, active, sess.planes)
    assert fwd["layer_1"]["write_mask"] is active
    assert "write_mask" not in fwd["rec"]
    # after a step: planes pass as they are (no select), per-row leaves are
    # frozen for the idle row, an undeclared layer is selected leaf by leaf
    new = {"layer_1": {"eva_k": st["eva_k"] + 1, "eva_v": st["eva_v"] + 1,
                       "pos": st["pos"] + 1},
           "rec": {"h": jnp.ones((3, 4))}}
    out = freeze_rows(new, fwd, active, sess.planes)
    assert out["layer_1"]["eva_k"] is new["layer_1"]["eva_k"]
    np.testing.assert_array_equal(out["layer_1"]["pos"], [1, 0, 1])
    np.testing.assert_array_equal(out["rec"]["h"][:, 0], [1, 0, 1])
    # the K/V block declares its caches and pages them
    gpt = GenerationSession(TransformerLM(
        vocab_size=11, hidden=16, n_layers=1, n_heads=2, max_len=16).init(),
        max_len=16)
    assert gpt.planes == {"layer_2": frozenset({
        "cache_k", "cache_v", "cache_k_scale", "cache_v_scale"})}
    assert gpt.paged_layers == frozenset({"layer_2"})


def test_paged_and_speculative_engines_refuse_the_bounded_state(lm):
    model, _, _, _ = lm
    with pytest.raises(ValueError, match="bounded decode state.*not paged"):
        DecodeEngine(model, max_len=MODEL["max_len"], slots=2, block_size=16,
                     registry=MetricsRegistry())
    with pytest.raises(ValueError, match="cannot be rewound"):
        DecodeEngine(model, max_len=MODEL["max_len"], slots=2,
                     draft_model=model, registry=MetricsRegistry())


def test_buckets_reach_32768_and_a_prompt_without_room_is_refused():
    """The published context: prefill buckets up to 32,768, and a prompt
    that leaves no room (longer than ``max_len`` less the one token a
    request at least asks for) is refused by ``submit()`` with the
    ``ValueError`` the HTTP front answers with 400, not found by a step."""
    model = EvaByteLM(vocab_size=40, hidden=16, n_layers=1, n_heads=1,
                      ffn_size=16, n_pred_heads=2, max_len=32768).init()
    eng = DecodeEngine(model, max_len=32768, slots=1,
                       registry=MetricsRegistry())
    try:
        assert eng.bucket_sizes()[-5:] == [2048, 4096, 8192, 16384, 32768]
        # window 2048, chunk 16: 2,048 summaries and 2,048 singletons a row
        assert eng._carry["layer_1"]["eva_k"].shape == (1, 1, 4096, 16)
        with pytest.raises(ValueError, match="no room to generate"):
            eng.submit([1] * 32768, max_tokens=1)
        assert eng.stats()["failed"] == 0 and eng.stats()["in_flight"] == 0
    finally:
        eng.shutdown(drain=False)


def test_multi_token_loss_reads_every_head(lm):
    model, _, ids, _ = lm
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])

    def loss(params):
        return model.loss_pure(params, model.state, x, y, train=False,
                               rng=None)[0]

    value, grads = jax.value_and_grad(loss)(model.params)
    assert np.isfinite(float(value)) and float(value) > 1.0
    head = np.asarray(grads["layer_4"]["W"]).reshape(32, 8, 40)
    assert (np.abs(head).sum(axis=(0, 2)) > 0).all()


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_eva_decode_kernel_matches_its_reference(dtype):
    b, h, d, n_sum, w = 5, 4, 16, 32, 16
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, h, 1, d), dtype)
    k = jnp.asarray(rs.randn(b, h, n_sum + w, d), dtype)
    v = jnp.asarray(rs.randn(b, h, n_sum + w, d), dtype)
    # no summaries yet; a full window; the last window; mid-chunk; one entry
    sums = jnp.asarray([0, 4, 28, 12, 0], jnp.int32)
    wins = jnp.asarray([3, 16, 16, 7, 1], jnp.int32)
    got = eva_decode_attention_pallas(q, k, v, sums, wins, n_sum,
                                      head_block=2, interpret=True)
    want = eva_decode_attention_reference(q, k, v, sums, wins, n_sum)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-6 if dtype == jnp.float32 else 2e-2)


def test_cache_write_where_the_head_dimension_fills_the_lanes():
    """A plane whose ``d`` is a multiple of 128 is written as it lies
    (position-major blocks of 32), with the same contract."""
    rs = np.random.RandomState(1)
    cache = jnp.asarray(rs.randn(4, 2, 64, 128), jnp.bfloat16)
    new = jnp.asarray(rs.randn(4, 2, 1, 128), jnp.bfloat16)
    pos = jnp.asarray([0, 31, 32, 63], jnp.int32)
    for mask in (jnp.asarray([True, False, True, True]),
                 jnp.ones((4,), bool)):
        np.testing.assert_array_equal(
            np.asarray(flash_masked_cache_write(cache, new, pos, mask,
                                                interpret=True), np.float32),
            np.asarray(masked_cache_write_reference(cache, new, pos, mask),
                       np.float32))
