"""Flash-attention helper vs builtin parity — the ValidateCuDNN pattern
(SURVEY.md §4: helper enabled vs disabled, compare outputs/grads within eps).
Runs the Pallas kernel in interpreter mode on the CPU test platform."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import (
    flash_attention,
    mha_attention,
    mha_attention_reference,
    set_attention_impl,
)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("tq,tk", [(64, 64), (96, 128), (40, 72)])
def test_flash_matches_reference(tq, tk):
    q = _rand(0, 2, 2, tq, 16)
    k = _rand(1, 2, 2, tk, 16)
    v = _rand(2, 2, 2, tk, 16)
    ref = mha_attention_reference(q, k, v)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_with_padding_mask():
    q = _rand(0, 2, 2, 48, 16)
    k = _rand(1, 2, 2, 48, 16)
    v = _rand(2, 2, 2, 48, 16)
    mask = jnp.asarray(np.random.RandomState(0).rand(2, 48) > 0.3,
                       jnp.float32)
    ref = mha_attention_reference(q, k, v, mask=mask)
    out = flash_attention(q, k, v, mask=mask, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal():
    q = _rand(0, 1, 2, 64, 16)
    k = _rand(1, 1, 2, 64, 16)
    v = _rand(2, 1, 2, 64, 16)
    ref = mha_attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_match():
    q = _rand(0, 1, 1, 32, 8)
    k = _rand(1, 1, 1, 32, 8)
    v = _rand(2, 1, 1, 32, 8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_attention_reference(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_impl_seam_dispatch():
    q = _rand(0, 1, 1, 32, 8)
    try:
        set_attention_impl("flash")
        out_flash = mha_attention(q, q, q)
        set_attention_impl("xla")
        out_xla = mha_attention(q, q, q)
    finally:
        set_attention_impl("auto")
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla),
                               atol=2e-5)
    with pytest.raises(ValueError):
        set_attention_impl("bogus")


def test_attention_layer_with_flash_helper():
    """Layer-level helper-vs-builtin parity (ValidateCuDNN shape)."""
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import LayerContext

    layer = SelfAttentionLayer(n_in=16, n_out=16, n_heads=2).with_input(
        __import__("deeplearning4j_tpu.nn.input_type",
                   fromlist=["RecurrentType"]).RecurrentType(size=16,
                                                             timesteps=32))
    params = layer.init(jax.random.PRNGKey(0), jnp.float32)
    x = _rand(5, 3, 16, 32)
    ctx = LayerContext(train=False, rng=None, mask=None)
    try:
        set_attention_impl("xla")
        ref, _ = layer.apply(params, {}, x, ctx)
        set_attention_impl("flash")
        out, _ = layer.apply(params, {}, x, ctx)
    finally:
        set_attention_impl("auto")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fully_masked_rows_zero_on_both_impls():
    q = _rand(0, 1, 1, 16, 8)
    k = _rand(1, 1, 1, 10, 8)
    v = _rand(2, 1, 1, 10, 8)
    mask = jnp.zeros((1, 10), jnp.float32)
    ref = mha_attention_reference(q, k, v, mask=mask)
    out = flash_attention(q, k, v, mask=mask, block_q=8, block_k=4)
    np.testing.assert_allclose(np.asarray(ref), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-7)


# ---------------------------------------------------------------------------
# memory-efficient backward (round 4): blockwise recompute, gradient parity
# ---------------------------------------------------------------------------

def _grads(fn, *args):
    loss = lambda *a: jnp.sum(jnp.square(fn(*a)))
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("tq,tk", [(64, 64), (96, 128), (40, 72)])
def test_flash_backward_matches_reference(tq, tk):
    q = _rand(10, 2, 2, tq, 16)
    k = _rand(11, 2, 2, tk, 16)
    v = _rand(12, 2, 2, tk, 16)
    ref = _grads(lambda a, b, c: mha_attention_reference(a, b, c), q, k, v)
    got = _grads(lambda a, b, c: flash_attention(a, b, c, block_q=32,
                                                 block_k=32), q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_flash_backward_causal_and_masked():
    q = _rand(13, 1, 2, 64, 16)
    k = _rand(14, 1, 2, 64, 16)
    v = _rand(15, 1, 2, 64, 16)
    mask = jnp.asarray(np.random.RandomState(9).rand(1, 64) > 0.3, jnp.float32)

    ref = _grads(lambda a, b, c: mha_attention_reference(
        a, b, c, mask=mask, causal=True), q, k, v)
    got = _grads(lambda a, b, c: flash_attention(
        a, b, c, mask=mask, causal=True, block_q=32, block_k=32), q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_flash_backward_ragged_blocks():
    """Sequence lengths that do NOT divide the block size (padding path)."""
    q = _rand(16, 1, 1, 50, 8)
    k = _rand(17, 1, 1, 70, 8)
    v = _rand(18, 1, 1, 70, 8)
    ref = _grads(lambda a, b, c: mha_attention_reference(a, b, c), q, k, v)
    got = _grads(lambda a, b, c: flash_attention(a, b, c, block_q=32,
                                                 block_k=32), q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# single-query decode over GROUPED heads (ISSUE 36)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("group, h_kv", [(4, 8), (2, 3), (4, 1)])
def test_grouped_flash_decode_equals_the_reference_over_repeated_heads(
        group, h_kv, dtype, tol):
    """``flash_decode`` (interpreted) with K and V of ``h_kv`` heads beside
    ``group x h_kv`` query heads against ``decode_attention_reference`` with
    the K/V heads REPEATED by hand, query head ``h`` reading pair ``h //
    group``: rows at position 0, a block's edge on both sides, the whole
    cache, and -1 (attends nothing: exactly 0); what lies past a row's
    position may be anything, NaN included. float32 agrees to rounding;
    bfloat16 to the rounding of the result (values of the order of 1)."""
    from deeplearning4j_tpu.ops.flash_attention import (
        decode_attention_reference, flash_decode_attention)

    b, d, L = 6, 16, 600
    ks = jax.random.split(jax.random.PRNGKey(group * 10 + h_kv), 3)
    q = jax.random.normal(ks[0], (b, group * h_kv, 1, d), dtype)
    k = jax.random.normal(ks[1], (b, h_kv, L, d), dtype)
    v = jax.random.normal(ks[2], (b, h_kv, L, d), dtype)
    pos = np.asarray([0, 255, 256, 599, 77, -1])
    stale = np.arange(L)[None, :] > pos[:, None]
    k, v = (jnp.where(stale[:, None, :, None], jnp.nan, a) for a in (k, v))
    got = np.asarray(flash_decode_attention(
        q, k, v, jnp.asarray(pos), interpret=True), np.float32)
    rep = [jnp.repeat(jnp.nan_to_num(a), group, axis=1) for a in (k, v)]
    assert rep[0].shape[1] == q.shape[1]
    want = np.asarray(decode_attention_reference(
        q, rep[0], rep[1], jnp.asarray(pos)), np.float32)
    assert got.shape == (b, group * h_kv, 1, d)
    assert np.isfinite(got).all() and not got[5].any()
    np.testing.assert_allclose(got[:5], want[:5], atol=tol, rtol=0)
    # the reference takes the grouped call itself, too (the XLA spelling)
    np.testing.assert_allclose(
        np.asarray(decode_attention_reference(
            q, jnp.nan_to_num(k), jnp.nan_to_num(v), jnp.asarray(pos)),
            np.float32)[:5], want[:5], atol=tol, rtol=0)


def test_ungrouped_flash_decode_is_the_program_it_was():
    """With as many K/V heads as query heads the call traces to what it
    traced to before grouped heads: one query row a head into the kernel,
    broadcast inside it, the same operations in the kernel's body; a group
    of 3 does not fit the kernel's eight sublanes and is refused."""
    from deeplearning4j_tpu.ops.flash_attention import flash_decode_attention

    def trace(hq, hkv):
        f32 = jnp.float32
        return str(jax.make_jaxpr(lambda q, k, v, p: flash_decode_attention(
            q, k, v, p, interpret=True))(
            jnp.zeros((2, hq, 1, 16), f32), jnp.zeros((2, hkv, 300, 16), f32),
            jnp.zeros((2, hkv, 300, 16), f32), jnp.zeros((2,), jnp.int32)))

    def block(rows):
        return ("Blocked(block_size=1), Blocked(block_size=4), "
                f"Blocked(block_size={rows}), Blocked(block_size=16)")

    plain, grouped = trace(4, 4), trace(8, 4)
    assert plain.count("pallas_call") == grouped.count("pallas_call") == 1
    assert "name=flash_decode" in plain and "name=flash_decode" in grouped
    # the ungrouped call hands the kernel one query row a head, as it came,
    # and takes one row a head back ...
    assert "float32[2,4,1,16]" in plain and plain.count(block(1)) == 2
    assert block(8) not in plain
    # ... the grouped one eight rows a K/V head (the group's, twice over)
    # and the group's two back
    assert block(8) in grouped and block(2) in grouped
    assert "float32[2,4,2,16]" in grouped
    with pytest.raises(ValueError, match="group of 1, 2 or 4"):
        trace(12, 4)


# ---------------------------------------------------------------------------
# the decode kernel writes the step's K and V itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("group", [1, 4])
def test_flash_decode_writes_what_the_separate_writes_write(group, dtype, tol):
    """``flash_decode_attention(..., new=, write_mask=)`` (interpreted)
    against ``masked_cache_write_reference`` on each plane followed by
    ``decode_attention_reference``: rows at positions 0, 127, 128, 255,
    256 and L - 1 (a block's and a tile's edges on both sides), a row the
    mask keeps off and a row at -1. The outputs agree to the tolerances
    above; both planes agree BIT FOR BIT at every entry, what lies past a
    row's position (NaN here) included. A row at -1 attends nothing and
    writes nothing, kept or not (the reference's scatter would clamp it
    onto position 0, as ``dynamic_update_slice`` does: no caller has such
    a row)."""
    from deeplearning4j_tpu.ops.flash_attention import (
        decode_attention_reference, flash_decode_attention,
        masked_cache_write_reference)

    b, h_kv, d, L = 8, 2, 16, 512
    ks = jax.random.split(jax.random.PRNGKey(group), 5)
    q = jax.random.normal(ks[0], (b, group * h_kv, 1, d), dtype)
    k, v = (jax.random.normal(kk, (b, h_kv, L, d), dtype) for kk in ks[1:3])
    kn, vn = (jax.random.normal(kk, (b, h_kv, 1, d), dtype) for kk in ks[3:])
    pos = jnp.asarray([0, 127, 128, 255, 256, L - 1, 300, -1], jnp.int32)
    keep = jnp.asarray([True] * 6 + [False, True])
    stale = np.arange(L)[None, :] > np.asarray(pos)[:, None]
    k, v = (jnp.where(stale[:, None, :, None], jnp.nan, a) for a in (k, v))
    out, k2, v2 = flash_decode_attention(q, k, v, pos, new=(kn, vn),
                                         write_mask=keep, interpret=True)
    writes = keep & (pos >= 0)
    want_k = masked_cache_write_reference(k, kn, pos, writes)
    want_v = masked_cache_write_reference(v, vn, pos, writes)
    bits = {jnp.float32: np.uint32, jnp.bfloat16: np.uint16}[dtype]
    for got, want, old in ((k2, want_k, k), (v2, want_v, v)):
        got, want, old = (np.asarray(a).view(bits) for a in (got, want, old))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got[6:], old[6:])  # kept off; at -1
    for r in range(6):  # the kept rows' entries are the new ones
        np.testing.assert_array_equal(
            np.asarray(k2[r, :, pos[r]]).view(bits),
            np.asarray(kn[r, :, 0]).view(bits))
    ref = np.asarray(decode_attention_reference(
        q, jnp.nan_to_num(want_k), jnp.nan_to_num(want_v), pos), np.float32)
    got = np.asarray(out, np.float32)
    assert got.shape == (b, group * h_kv, 1, d)
    assert np.isfinite(got).all() and not got[7].any()
    np.testing.assert_allclose(got, ref, atol=tol, rtol=0)


def test_the_write_fuses_only_on_the_flash_path_over_an_unpadded_plane():
    """``decode_write_fuses``: the Pallas kernel selected, one query row, a
    floating-point plane, and a length of whole blocks and whole tiles
    (GPT-2's 1,024, LFM2's 6,144); everything else writes on its own."""
    from deeplearning4j_tpu.ops import decode_write_fuses

    def fuses(L, t=1, dtype=jnp.bfloat16):
        return decode_write_fuses(jnp.zeros((2, 4, t, 8), dtype),
                                  jnp.zeros((2, 4, L, 8), dtype))

    set_attention_impl("flash")
    try:
        assert fuses(1024) and fuses(6144) and fuses(128) and fuses(512)
        assert fuses(1024, dtype=jnp.float32)
        assert not (fuses(600) or fuses(384) or fuses(16) or fuses(1000))
        assert not fuses(1024, t=4)
        assert not fuses(1024, dtype=jnp.int8)
        set_attention_impl("xla")
        assert not fuses(1024)
    finally:
        set_attention_impl("auto")
    assert not fuses(1024)  # "auto" off the chip is the XLA spelling
