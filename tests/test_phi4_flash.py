"""Phi-4-mini-flash-reasoning on the serving path: the zoo's ``Phi4FlashLM``
(a SambaY decoder-hybrid-decoder: Mamba mixers and differential attention
over 16-entry window rings in the self-decoder, then one layer holding the
cross-decoder, whose Mamba layer's scan output is the memory of its gated
memory units and whose one full differential attention layer writes the
K/V cache its cross layers read) against the benchmark's plain reference
(``benchmarks/families/phi4_flash.py``: float32, no cache, no kernel,
nothing of the program) on seeded random weights at toy widths: hidden 64,
8 layers by the published rule (Mamba, window, Mamba, window | memory
Mamba, full, GMU, cross), 8 query and 4 K/V heads of 8, FFN 128, window
16, d_inner 128, d_state 16, dt_rank 4.

Tolerances: everything is float32 on both sides and the two differ only in
the order of their sums (the scan's state kept the other way round, the
window by blocks, the head by columns), so logits of the order of 1 agree
to 2e-5.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import runtime, weights
from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.model.zoo import Phi4FlashLM
from deeplearning4j_tpu.nn.layers import (CrossDecoderLayer,
                                          DifferentialAttentionLayer,
                                          MambaMixerLayer)
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.ops import set_attention_impl
from deeplearning4j_tpu.ops.diff_attention import (
    decode_live_schedule, diff_decode_attention_pallas,
    diff_decode_attention_reference)
from deeplearning4j_tpu.ops.flash_attention import decode_fetched_entries
from deeplearning4j_tpu.ops.selective_scan import (selective_scan_pallas,
                                                   selective_scan_reference)
from deeplearning4j_tpu.parallel.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = runtime.load_family(os.path.join(ROOT, "benchmarks", "families",
                                          "phi4_flash.py"))
MODEL = dict(vocab_size=96, hidden=64, n_layers=8, mb_per_layer=2,
             n_heads=8, n_kv_heads=4, ffn_size=128, sliding_window=16,
             d_inner=128, d_state=16, d_conv=4, dt_rank=4, max_len=262144,
             eps=1e-5)
DIMS = FAMILY.dims({"model": MODEL})
# the benchmark configuration's own layout: one layout for every depth
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "phi4-mini-flash.json")) as _f:
    LAYOUT = json.load(_f)["layout"]
# 2 x 72 tokens: past the window's 16 four times over; positions from 20 on
# are decoded through the rings, the scans' states and the one cache
T, TOL, SEED, MAX_LEN = 72, 2e-5, 3000000017, 96


def _model(**over):
    model = Phi4FlashLM(**(MODEL | over), seed=1, dtype="float32").init()
    weights.install(model, weights.program_weights(
        FAMILY, DIMS, SEED, "float32", LAYOUT))
    return model


@pytest.fixture(scope="module")
def lm():
    """The program with the seed's weights, and the reference's logits over
    two sequences."""
    w = weights.make_weights(FAMILY, DIMS, SEED, "float32")
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"], (2, T))
    ref = np.asarray(FAMILY.decoder_logits(w, jnp.asarray(ids), DIMS))
    return _model(), ids, ref


def _decode_all(model, ids, n, upto=T):
    """Prefill rows of true lengths ``n`` (right-padded into one bucket),
    then decode every later position up to ``upto``: the logits at every
    position from ``n[r] - 1`` on, ``[rows, positions, vocab]``."""
    sess = GenerationSession(model, max_len=MAX_LEN)
    carry, logits, _ = sess.prefill([ids[r, :n[r]].tolist()
                                     for r in range(len(n))])
    out = [np.asarray(logits)]
    for i in range(upto - max(n)):
        carry, logits = sess.decode(carry, [ids[r, n[r] + i]
                                            for r in range(len(n))])
        out.append(np.asarray(logits))
    return np.stack(out, axis=1), carry


def test_the_tree_is_the_published_rule_and_the_layout_fits_it(lm):
    model = lm[0]
    kinds = [FAMILY.kind(DIMS, i) for i in range(8)]
    assert kinds == ["mamba", "window", "mamba", "window", "memory", "full",
                     "gmu", "cross"]
    assert isinstance(model.layers[5], CrossDecoderLayer)
    assert [type(p.mixer).__name__ for p in model.layers[5].parts] == [
        "MambaMixerLayer", "DifferentialAttentionLayer", "GatedMemoryLayer",
        "DifferentialAttentionLayer"]
    total = sum(int(a.size) for p in model.params.values()
                for a in p.values())
    import math
    assert total == sum(math.prod(s) * FAMILY.groups(DIMS).get(g, 1)
                        for g, s in FAMILY.leaves(DIMS).values())
    assert not model.params.get(model.layer_names()[-1])  # the tied head


def test_whole_sequence_equals_the_reference(lm):
    model, ids, ref = lm
    out = np.asarray(model.output(jnp.asarray(ids))).transpose(0, 2, 1)
    assert np.abs(ref).max() > 0.5  # the comparison is of something
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_prefill_at_true_lengths_then_decode_past_the_window(lm):
    """Rows of DIFFERENT true lengths right-padded into one bucket (5 and
    20 tokens in 32; 20 is past the window): the scans hand over their state
    at each row's own length, the rings take each row's last 16 entries
    (the longer row's wrapped), the cache its own; then every later
    position to 72 is decoded, rows at different positions in one call:
    logits at every position against the reference's full forward."""
    model, ids, ref = lm
    n = (5, 20)
    got, carry = _decode_all(model, ids, n)
    for r in (0, 1):
        want = ref[r, n[r] - 1:n[r] - 1 + got.shape[1]]
        np.testing.assert_allclose(got[r], want, atol=TOL, rtol=0)
    names = model.layer_names()
    assert carry[names[2]]["ring_k"].shape == (2, 4, 16, 8)
    assert carry[names[1]]["ssm"].shape == (2, 16, 128)
    assert carry[names[5]]["cache_k"].shape == (2, 4, MAX_LEN, 8)
    assert np.asarray(carry[names[5]]["pos"]).tolist() == [
        n[0] + T - max(n), T]


def test_a_prompt_longer_than_its_bucket_is_the_whole_scan():
    """The Mamba mixer alone: a sequence in one call, and the same in three
    calls through its decode state (7 + 1 + 24 positions) agree; a
    right-padded call hands the state over at the true length."""
    mix = MambaMixerLayer(n_in=16, d_inner=32, d_state=16, d_conv=4,
                          dt_rank=2)
    params = mix.init(jax.random.PRNGKey(3), jnp.float32)
    params["bdt"] = jnp.full((32,), -1.0)  # fast scans: the state matters
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 16), jnp.float32)
    whole, _ = mix.mix(params, {}, x, None)
    st = mix.decode_state(2, 0, jnp.float32)
    parts = []
    for lo, hi in ((0, 7), (7, 8), (8, 32)):
        o, st = mix.mix(params, st, x[:, lo:hi], None)
        parts.append(o)
    np.testing.assert_allclose(np.concatenate(parts, 1), whole, atol=1e-5)
    mask = (jnp.arange(12)[None, :] < jnp.array([[5], [12]])).astype(
        jnp.float32)
    _, padded = mix.mix(params, mix.decode_state(2, 0, jnp.float32),
                        x[:, :12], mask)
    _, five = mix.mix(params, mix.decode_state(1, 0, jnp.float32),
                      x[:1, :5], None)
    np.testing.assert_allclose(padded["ssm"][0], five["ssm"][0], atol=1e-6)
    np.testing.assert_allclose(padded["conv"][0], five["conv"][0], atol=1e-6)


@pytest.mark.parametrize("t", [300, 5])
def test_selective_scan_kernel_equals_the_scan(t):
    """The prompt's scan kernel (interpreted) against ``lax.scan`` one
    position a step: a length no block divides and one under a block."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    b, di, n = 2, 64, 16
    xs = jax.random.normal(ks[0], (b, t, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, di)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)))
    B = jax.random.normal(ks[3], (b, t, n))
    C = jax.random.normal(ks[4], (b, t, n))
    s0 = jax.random.normal(ks[5], (b, n, di))
    want = selective_scan_reference(xs, dt, A, B, C, s0)
    got = selective_scan_pallas(xs, dt, A, B, C, s0, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-6)


@pytest.mark.parametrize("b,hq,hk,L,d,lengths", [
    (3, 8, 4, 512, 16, (1, 512, 300)),   # two heads a K/V pair, two blocks
    (2, 4, 4, 256, 16, (256, 77)),       # one head a pair
    # a lone row at each edge of a block, in a cache of eight blocks
    (1, 8, 4, 2048, 16, (0,)),
    (1, 8, 4, 2048, 16, (1,)),
    (1, 8, 4, 2048, 16, (255,)),
    (1, 8, 4, 2048, 16, (256,)),
    (1, 8, 4, 2048, 16, (257,)),
    (1, 8, 4, 2048, 16, (2048,)),
    (7, 8, 4, 2048, 16, (257, 0, 2048, 1, 256, 1000, 255)),   # all at once
    (3, 4, 4, 4096, 16, (4096, 0, 3001)),                     # sixteen
    (2, 40, 40, 512, 64, (300, 0)),      # two pair blocks: a 2-D grid
])
def test_diff_decode_equals_the_xla_spelling(b, hq, hk, L, d, lengths):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hk, L, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hk, L, d), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    want = diff_decode_attention_reference(q, k, v, n, 0.37)
    got = diff_decode_attention_pallas(q, k, v, n, 0.37, block_k=256,
                                       interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # both maps are in it: the second one's weight moves the result by a
    # fifth of its size (rows with no entry give 0 either way)
    other = diff_decode_attention_reference(q, k, v, n, 0.0)
    gap = np.abs(np.asarray(other) - np.asarray(want)).max()
    assert gap > 0.2 * np.abs(np.asarray(want)).max() or not any(lengths)


@pytest.mark.parametrize("L,lengths", [
    (2048, (0, 1, 255, 256, 257, 2048)),
    (2048, (2048,) * 4),
    (512, (0, 0, 0)),
    (256, (256, 3)),
    (10240, tuple(np.random.default_rng(45).integers(0, 10241, 64))),
])
def test_the_live_schedule_names_each_rows_live_blocks_once(L, lengths):
    """Every (row, block) pair up to the block that holds a row's last
    entry (one block for a row with none), in order, and nothing past it;
    the entries past the count repeat the last pair."""
    bk = min(256, L)
    lens = np.asarray(lengths, np.int32)
    n, rows, blocks = (np.asarray(a) for a in decode_live_schedule(
        jnp.asarray(lens), L, bk))
    counts = decode_fetched_entries(lens, L) // bk
    assert n == counts.sum()
    assert rows.shape == blocks.shape == (len(lens) * L // bk + 1,)
    assert list(zip(rows[:n], blocks[:n])) == [
        (r, j) for r in range(len(lens)) for j in range(counts[r])]
    assert set(rows[:n]) == set(range(len(lens)))
    assert (rows[n:] == rows[n - 1]).all() and (
        blocks[n:] == blocks[n - 1]).all()


def test_diff_decode_grid_is_the_live_count():
    """The kernel's grid has as many steps as the rows' live blocks: its
    second dimension is read at run time, and it is the schedule's count."""
    b, hq, hk, L, d = 5, 8, 4, 2048, 16
    lens = jnp.asarray([0, 1, 257, 2048, 700], jnp.int32)
    q = jnp.zeros((b, hq, d), jnp.float32)
    k = jnp.zeros((b, hk, L, d), jnp.float32)
    closed = jax.make_jaxpr(
        lambda q, k, v, n: diff_decode_attention_pallas(
            q, k, v, n, 0.37, interpret=True))(q, k, k, lens)
    jaxpr = closed.jaxpr
    (i, eqn), = [(i, e) for i, e in enumerate(jaxpr.eqns)
                 if e.primitive.name == "pallas_call"]
    grid = eqn.params["grid_mapping"].grid
    assert grid[0] == 1 and not isinstance(grid[1], int)
    assert eqn.params["grid_mapping"].num_dynamic_grid_bounds == 1
    head = jaxpr.replace(eqns=jaxpr.eqns[:i], outvars=[eqn.invars[0]])
    (steps,) = jax.core.eval_jaxpr(head, closed.consts, q, k, k, lens)
    assert int(steps) == 1 + 1 + 2 + 8 + 3 < b * L // 256


def test_the_kernels_give_what_the_xla_spelling_gives(lm):
    """The prompt's scans through ``selective_scan`` and the decode steps
    through ``diff_decode`` / ``diff_decode_window`` (interpreted) against
    the reference, both kinds of read."""
    model, ids, ref = lm
    set_attention_impl("flash")
    try:
        got, _ = _decode_all(model, ids, (19, 19), upto=26)
    finally:
        set_attention_impl("auto")
    np.testing.assert_allclose(got[0], ref[0, 18:26], atol=TOL, rtol=0)


def test_decode_engine_serves_what_the_full_forward_gives(lm):
    """Through the serving engine: four requests of different lengths
    admitted one by one into a fused step, greedy; each served token is the
    reference's best at its position, and the engine counts the window's
    and the cache's entries."""
    model, ids, ref = lm
    reg = MetricsRegistry()
    eng = DecodeEngine(model, max_len=MAX_LEN, slots=4, registry=reg)
    try:
        starts = (9, 17, 30, 44)
        handles = [eng.submit(ids[r % 2, :n].tolist(), max_tokens=6,
                              greedy=True)
                   for r, n in enumerate(starts)]
        served = [[e["token"] for e in h.events(timeout=120)
                   if "token" in e] for h in handles]
        assert eng.stats()["kv_fetch_valid_share"] is not None
        # the one cache's and the rings' planes: writes of their own
        assert eng.stats()["kv_write_fused_share"] == 0.0
    finally:
        eng.shutdown(drain=False)
    for r, (n, toks) in enumerate(zip(starts, served)):
        seq = list(ids[r % 2, :n]) + toks
        w = weights.make_weights(FAMILY, DIMS, SEED, "float32")
        logits = np.asarray(FAMILY.decoder_logits(
            w, jnp.asarray([seq]), DIMS))[0]
        assert toks == list(np.argmax(logits[n - 1:n - 1 + len(toks)],
                                      axis=-1)), r
    window = reg.get("dl4j_tpu_decode_window_entries_attended_total")
    cache = reg.get("dl4j_tpu_decode_kv_entries_attended_total")
    (w_count,) = [c.value for _, c in window.items()]
    (c_count,) = [c.value for _, c in cache.items()]
    assert 0 < w_count < c_count  # rings stop at 16, the cache does not


@pytest.mark.parametrize("family", ["phi4f", "gpt2"])
def test_the_engine_counts_its_kernels_reads_by_grid(lm, family):
    """Under the step's TPU spelling (interpreted): Phi-4-mini-flash's two
    rings and two readers of the one cache are read by ``diff_decode``,
    which visits live blocks only, GPT-2's two layers by ``flash_decode``,
    whose grid spans the cache; the counter rises by the step's reads once
    a dispatched step, and the share is None before one."""
    if family == "phi4f":
        model, max_len, reads, grid = lm[0], MAX_LEN, 4, "live"
    else:
        from deeplearning4j_tpu.model.zoo import TransformerLM
        model = TransformerLM(vocab_size=MODEL["vocab_size"], hidden=32,
                              n_layers=2, n_heads=4, max_len=128).init()
        max_len, reads, grid = 128, 2, "full"
    reg = MetricsRegistry()
    set_attention_impl("flash")
    eng = DecodeEngine(model, max_len=max_len, slots=2, registry=reg,
                       name="kvr")
    try:
        assert eng.stats()["kv_read_live_share"] is None
        m = 4
        eng.submit(list(range(1, 12)), max_tokens=m, greedy=True).result(
            timeout=300)
        share = eng.stats()["kv_read_live_share"]
    finally:
        eng.shutdown(drain=False)
        set_attention_impl("auto")
    c = reg.get("dl4j_tpu_decode_kv_reads_total")
    counted = {g: c.labels("kvr", g).value for g in ("live", "full")}
    steps = m - 1  # the prefill hands out the first token
    assert counted == {g: reads * steps if g == grid else 0
                       for g in ("live", "full")}
    assert share == (1.0 if grid == "live" else 0.0)


# ----------------------------------------------------- faults that must fail
def _band_unbounded(monkeypatch):
    return _model(sliding_window=MAX_LEN)


def _second_map_dropped(monkeypatch):
    monkeypatch.setattr(DifferentialAttentionLayer, "_lam",
                        lambda self, params: jnp.zeros((), jnp.float32))


def _gmu_reads_after_the_gate(monkeypatch):
    mix = MambaMixerLayer._mix

    def faulty(self, params, state, x, mask, tap):
        out = mix(self, params, state, x, mask, tap)
        if not tap:
            return out
        z = jnp.dot(x, params["Win"])[..., self.d_inner:]
        return out[0], out[1], out[2] * jax.nn.silu(z)

    monkeypatch.setattr(MambaMixerLayer, "_mix", faulty)


def _cross_layers_use_fresh_kv(monkeypatch):
    """The cross layers attend keys and values of their OWN input (the
    full layer's projections), not the cache the full layer wrote."""
    mix = DifferentialAttentionLayer._mix
    full = {}

    def faulty(self, params, state, x, mask, shared):
        if self.kind == "full":
            full.update(Wk=params["Wk"], Wv=params["Wv"])
        elif self.kind == "cross":
            from deeplearning4j_tpu.nn.layers.attention import _split_heads

            shared = {"k": _split_heads(x @ full["Wk"], self.n_kv_heads),
                      "v": _split_heads(x @ full["Wv"], self.n_kv_heads)}
        return mix(self, params, state, x, mask, shared)

    monkeypatch.setattr(DifferentialAttentionLayer, "_mix", faulty)


def _dt_not_masked_at_padding(monkeypatch):
    mix = MambaMixerLayer._mix

    def faulty(self, params, state, x, mask, tap):
        if mask is None:
            return mix(self, params, state, x, mask, tap)
        # the convolution still hands over at the true length; the scan runs
        # through the pad
        from deeplearning4j_tpu.nn.layers import mamba

        conv = mamba.rolling_conv
        try:
            mamba.rolling_conv = lambda xs, st, w, m: conv(xs, st, w, mask)
            return mix(self, params, state, x, None, tap)
        finally:
            mamba.rolling_conv = conv

    monkeypatch.setattr(MambaMixerLayer, "_mix", faulty)


@pytest.mark.parametrize("plant", [
    _band_unbounded, _second_map_dropped, _gmu_reads_after_the_gate,
    _cross_layers_use_fresh_kv, _dt_not_masked_at_padding],
    ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_moves_the_logits(lm, monkeypatch, plant):
    """Each fault, planted in the program, leaves prefill-then-decode far
    from the reference, by 100 times the tolerance at least."""
    model, ids, ref = lm
    model = plant(monkeypatch) or model
    got, _ = _decode_all(model, ids, (5, 20), upto=40)
    gap = max(np.abs(got[r] - ref[r, n - 1:n - 1 + got.shape[1]]).max()
              for r, n in enumerate((5, 20)))
    assert gap > 100 * TOL, gap
