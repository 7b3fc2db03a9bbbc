"""Compiled parity for the two Pallas kernels that post-date the flash
forward/backward: ``flash_decode_attention`` and ``grouped_matmul``
(forward + grad), plus flash attention at a sequence length that is not a
multiple of any block. bf16 inputs; references in float32 at "highest"
matmul precision (on a TPU an f32 einsum otherwise multiplies in bf16).

Tolerances: one bf16 rounding of an O(1) output is 2^-8 = 0.4% and the
backward rounds p/ds to bf16 before its matmuls, so errors are bounded at
2% (forward) and 4% (gradients) of the reference's largest magnitude —
computing below bf16, or dropping a term, lands far outside.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.flash_attention import (
    decode_attention_reference, flash_attention, flash_decode_attention,
    mha_attention_reference)
from deeplearning4j_tpu.ops.grouped_matmul import (
    grouped_matmul, grouped_matmul_reference)

FWD_TOL, GRAD_TOL = 2e-2, 4e-2


def _rand(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.bfloat16)


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*(
            a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
            else a for a in args))


def _rel(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape and np.isfinite(got).all()
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-30))


@pytest.mark.parametrize("b,L,block_k", [
    (8, 1024, 512), (8, 600, 512), (8, 64, 512),
    (8, 1024, 256), (8, 1024, 128),
    (128, 1024, 256),           # the serve cell's step: positions 16-896
])
def test_flash_decode_compiled_matches_reference(tpu_device, b, L, block_k):
    h, d = 12, 64
    q, k, v = _rand(0, b, h, 1, d), _rand(1, b, h, L, d), _rand(2, b, h, L, d)
    if b == 8:
        pos = np.asarray([0, L // 2 - 1, L // 2, L - 1, -1, 5, L // 3, L - 2])
    else:
        pos = np.random.RandomState(11).permutation(
            np.linspace(16, 896, b).astype(np.int32))
        pos[4] = -1
    run = jax.jit(lambda *a: flash_decode_attention(
        *a, block_k=block_k, interpret=False))
    start = jnp.asarray(pos, jnp.int32)
    got = run(q, k, v, start)
    ref = _highest(decode_attention_reference, q, k, v, start)
    assert _rel(got, ref) < FWD_TOL
    # the inactive row (pos -1) attends nothing and outputs exactly 0
    assert not np.asarray(got, np.float32)[4].any()
    # what lies past a row's position may be anything: the result is the same
    stale = np.arange(L)[None, :] > pos[:, None]              # [b, L]
    k2, v2 = (jnp.where(stale[:, None, :, None], jnp.nan, a) for a in (k, v))
    again = run(q, k2, v2, start)
    assert (np.asarray(again, np.float32) == np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("h,h_kv,b,L", [
    (32, 8, 8, 1024), (4, 2, 8, 600),
    (32, 8, 128, 6144),         # the LFM2 cell's step: positions 200-6,100
])
def test_grouped_flash_decode_compiled_matches_reference(tpu_device, h, h_kv,
                                                         b, L):
    """K and V of fewer heads than the queries: the compiled kernel against
    the reference over the K/V heads repeated by hand."""
    d, g = 64, h // h_kv
    q, k, v = (_rand(0, b, h, 1, d), _rand(1, b, h_kv, L, d),
               _rand(2, b, h_kv, L, d))
    if b == 8:
        pos = np.asarray([0, L // 2 - 1, L // 2, L - 1, -1, 5, L // 3, L - 2])
    else:
        pos = np.random.RandomState(11).permutation(
            np.linspace(200, 6100, b).astype(np.int32))
        pos[4] = -1
    run = jax.jit(lambda *a: flash_decode_attention(*a, interpret=False))
    start = jnp.asarray(pos, jnp.int32)
    got = run(q, k, v, start)
    # sixteen rows at a time: the repeated planes of all 128 in float32
    # would be 12.9 GB
    ref = np.concatenate([np.asarray(_highest(
        decode_attention_reference, q[r:r + 16],
        jnp.repeat(k[r:r + 16], g, axis=1), jnp.repeat(v[r:r + 16], g, axis=1),
        start[r:r + 16])) for r in range(0, b, 16)])
    assert _rel(got, ref) < FWD_TOL
    assert not np.asarray(got, np.float32)[4].any()
    stale = np.arange(L)[None, :] > pos[:, None]              # [b, L]
    k2, v2 = (jnp.where(stale[:, None, :, None], jnp.nan, a) for a in (k, v))
    again = run(q, k2, v2, start)
    assert (np.asarray(again, np.float32) == np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("h,h_kv,b,L", [
    (12, 12, 8, 1024),
    (12, 12, 128, 1024),        # the GPT-2 cell's step
    (32, 8, 128, 6144),         # the LFM2 cell's step
])
def test_flash_decode_write_compiled_matches_the_separate_writes(
        tpu_device, h, h_kv, b, L):
    """The decode kernel given the step's new K and V entries: both planes
    equal the masked scatter's bit for bit (kept rows at their positions,
    clamped past the end; a row kept off and a row at -1 write nothing),
    and the output equals the reference's over the written planes."""
    from deeplearning4j_tpu.ops.flash_attention import (
        masked_cache_write_reference)

    d, g = 64, h // h_kv
    q, k, v = (_rand(0, b, h, 1, d), _rand(1, b, h_kv, L, d),
               _rand(2, b, h_kv, L, d))
    kn, vn = _rand(3, b, h_kv, 1, d), _rand(4, b, h_kv, 1, d)
    if b == 8:
        pos = np.asarray([0, 127, 128, 255, 256, L - 1, L + 3, -1])
    else:
        pos = np.random.RandomState(11).permutation(
            np.linspace(16, L - 1, b).astype(np.int32))
        pos[4] = -1
    keep = np.ones((b,), bool)
    keep[[2, 9 % b]] = False
    start, mask = jnp.asarray(pos, jnp.int32), jnp.asarray(keep)
    run = jax.jit(lambda *a: flash_decode_attention(
        *a[:4], interpret=False, new=a[4:6], write_mask=a[6]))
    want_k, want_v = (jax.jit(masked_cache_write_reference)(
        c, n, start, mask & (start >= 0)) for c, n in ((k, kn), (v, vn)))
    got, k2, v2 = run(q, k, v, start, kn, vn, mask)
    for a, want in ((k2, want_k), (v2, want_v)):
        assert (np.asarray(a).view(np.uint16)
                == np.asarray(want).view(np.uint16)).all()
    ref = np.concatenate([np.asarray(_highest(
        decode_attention_reference, q[r:r + 16],
        jnp.repeat(want_k[r:r + 16], g, axis=1),
        jnp.repeat(want_v[r:r + 16], g, axis=1),
        start[r:r + 16])) for r in range(0, b, 16)])
    assert _rel(got, ref) < FWD_TOL
    assert not np.asarray(got, np.float32)[4 if b > 8 else 7].any()


@pytest.mark.parametrize("t,dtype", [(1, jnp.bfloat16), (1, jnp.int8),
                                     (4, jnp.bfloat16)])
def test_masked_cache_write_compiled_drops_idle_rows(tpu_device, t, dtype):
    """The decode step's masked cache write on the chip (``t == 1``: the
    in-place Pallas kernel; more: the scatter): a row that is masked off
    writes nothing, a row that writes lands where the vmapped
    ``dynamic_update_slice`` puts it, the clamp at the cache's end too."""
    from deeplearning4j_tpu.nn.layers.attention import _cache_write
    from deeplearning4j_tpu.ops import masked_cache_write

    b, h, L, d = 8, 12, 1024, 64
    cache = (_rand(6, b, h, L, d) * 40).astype(dtype)
    new = (_rand(7, b, h, t, d) * 40).astype(dtype)
    pos = jnp.asarray([0, 5, L - t, L - 1, L, 17, L // 2, L + 3], jnp.int32)
    mask = jnp.asarray([True, False, True, True, False, True, False, True])
    text = jax.jit(masked_cache_write).lower(cache, new, pos, mask).as_text()
    assert ("kv_cache_write" in text) == (t == 1)
    got = jax.jit(masked_cache_write)(cache, new, pos, mask)
    want = jnp.where(mask[:, None, None, None],
                     jax.jit(_cache_write)(cache, new, pos), cache)
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    scales, ns = _rand(8, b, h, L).astype(jnp.float32), _rand(
        9, b, h, t).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import _scale_write

    got = jax.jit(masked_cache_write)(scales, ns, pos, mask)
    want = jnp.where(mask[:, None, None],
                     jax.jit(_scale_write)(scales, ns, pos), scales)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("cap", [2560, None])
def test_grouped_matmul_compiled_matches_reference(tpu_device, cap):
    n, d, e, h = 16384, 768, 8, 1536
    lhs, w = _rand(3, n, d), _rand(4, n, h)
    rhs = _rand(5, e, d, h) * 0.05
    rs = np.random.RandomState(0)
    sizes = rs.multinomial(n - n // 16, rs.dirichlet(np.ones(e) * 2.0))
    if cap is not None:
        sizes = np.minimum(sizes, cap)
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(fn, lhs, rhs, **kw):
        o = fn(lhs, sizes, rhs, max_group_size=cap, **kw)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    step = jax.jit(jax.grad(
        lambda a, b: loss(grouped_matmul, a, b, interpret=False),
        argnums=(0, 1), has_aux=True))
    # "auto" must have picked the Pallas kernel here, forward and dgrad
    assert step.lower(lhs, rhs).as_text().count(
        'kernel_name = "grouped_matmul"') == 2
    (dl, dr), out = step(lhs, rhs)
    (rdl, rdr), rout = _highest(jax.grad(
        lambda a, b: loss(grouped_matmul_reference, a, b),
        argnums=(0, 1), has_aux=True), lhs, rhs)
    assert _rel(out, rout) < FWD_TOL
    assert _rel(dl, rdl) < GRAD_TOL
    assert _rel(dr, rdr) < GRAD_TOL
    # rows parked past the frontier come back as exact zeros
    assert not np.asarray(out, np.float32)[int(sizes.sum()):].any()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_unaligned_length_compiled(tpu_device, causal):
    """t=600: no block size divides it, so every kernel pads."""
    b, h, t, d = 2, 12, 600, 64
    q, k, v, g = (_rand(10 + i, b, h, t, d) for i in range(4))
    mask = jnp.ones((b, t), jnp.float32).at[-1, t // 2:].set(0.0)

    def loss(fn, q, k, v, **kw):
        o = fn(q, k, v, mask=mask, causal=causal, **kw)
        return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o

    grads, out = jax.jit(jax.grad(
        lambda *a: loss(flash_attention, *a, interpret=False),
        argnums=(0, 1, 2), has_aux=True))(q, k, v)
    rgrads, rout = _highest(jax.grad(
        lambda *a: loss(mha_attention_reference, *a),
        argnums=(0, 1, 2), has_aux=True), q, k, v)
    assert _rel(out, rout) < FWD_TOL
    for name, a, r in zip("qkv", grads, rgrads):
        assert _rel(a, r) < GRAD_TOL, f"d{name}"


@pytest.mark.parametrize("b,block_k", [(8, 512), (8, 256), (128, 512)])
def test_mla_decode_compiled_matches_reference(tpu_device, b, block_k):
    """LongCat-Flash's latent plane at its published widths: 64 heads over
    one plane of 2,560 entries of 512 + 64 numbers (128 rows: the cell's
    step). A row of length 0 attends nothing; what lies past a row's length
    may be anything, NaN included."""
    from deeplearning4j_tpu.ops.mla_attention import (
        mla_decode_attention_pallas, mla_decode_attention_reference)

    h, L, rank, w = 64, 2560, 512, 576
    q, plane = _rand(0, b, h, w), _rand(1, b, 1, L, w)
    if b == 8:
        n = np.asarray([1, 512, 513, L, 0, 6, L // 3, L - 1])
    else:
        n = np.random.RandomState(11).permutation(
            np.linspace(65, 2400, b).astype(np.int32))
        n[4] = 0
    lengths = jnp.asarray(n, jnp.int32)
    run = jax.jit(lambda q, p, n: mla_decode_attention_pallas(
        q, p, n, rank, 192 ** -0.5, block_k=block_k, interpret=False))
    got = run(q, plane, lengths)
    ref = _highest(lambda q, p, n: mla_decode_attention_reference(
        q, p, n, rank, 192 ** -0.5), q, plane, lengths)
    assert _rel(got, ref) < FWD_TOL
    assert not np.asarray(got, np.float32)[4].any()
    stale = np.arange(L)[None, :] >= n[:, None]               # [b, L]
    again = run(q, jnp.where(stale[:, None, :, None], jnp.nan, plane),
                lengths)
    assert (np.asarray(again, np.float32) == np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("L,name", [(10240, "diff_decode"),
                                    (512, "diff_decode_window")])
def test_diff_decode_compiled_matches_reference(tpu_device, L, name):
    """Phi-4-mini-flash's step at its published widths: 64 rows, 40 query
    heads over 20 K/V heads of 64 in pairs, the shared cache of 10,240
    entries and a window's ring of 512, rows from 0 to ``L`` entries (the
    kernel's grid is the rows' live blocks). A row of length 0 attends
    nothing; what lies past a row's length may be anything, NaN included.
    The reference runs eight rows at a time (in float32 the whole batch's
    planes would not fit beside the kernel's)."""
    from deeplearning4j_tpu.ops.diff_attention import (
        diff_decode_attention_pallas, diff_decode_attention_reference)

    b, hq, hk, d, lam = 64, 40, 20, 64, 0.37
    q, k, v = _rand(0, b, hq, d), _rand(1, b, hk, L, d), _rand(2, b, hk, L, d)
    n = np.random.RandomState(45).permutation(
        np.linspace(0, L, b).astype(np.int32))
    n[:6] = [0, 1, 255, 256, 257, L]
    lengths = jnp.asarray(n, jnp.int32)
    run = jax.jit(lambda q, k, v, n: diff_decode_attention_pallas(
        q, k, v, n, lam, name=name, interpret=False))
    got = np.asarray(run(q, k, v, lengths), np.float32)
    ref = np.concatenate([np.asarray(_highest(
        lambda q, k, v, n: diff_decode_attention_reference(q, k, v, n, lam),
        q[i:i + 8], k[i:i + 8], v[i:i + 8], lengths[i:i + 8]), np.float32)
        for i in range(0, b, 8)])
    assert _rel(got, ref) < FWD_TOL
    assert not got[0].any()
    stale = jnp.asarray(np.arange(L)[None, :] >= n[:, None])[:, None, :, None]
    again = run(q, jnp.where(stale, jnp.nan, k), jnp.where(stale, jnp.nan, v),
                lengths)
    assert (np.asarray(again, np.float32) == got).all()
