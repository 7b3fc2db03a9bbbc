"""Sorted grouped (ragged) expert matmul — the MoE fast path.

``grouped_matmul(lhs [N, d], group_sizes [E], rhs [E, d, h]) -> [N, h]``
contracts each row of ``lhs`` against the weight slab of the group it
belongs to. Rows are PRE-SORTED by group: group ``e`` owns the contiguous
row range ``[offsets[e], offsets[e] + group_sizes[e])`` where ``offsets``
is the exclusive cumsum of ``group_sizes``. Rows at or past the global
frontier ``sum(group_sizes)`` belong to no group and produce zeros —
that is how MoE dispatch parks dropped assignments.

One kernel covers all experts — no per-expert host loop. Internally rows
are viewed as zero-padded per-group tiles ``[E, m_pad, d]`` (``m_pad`` =
``max_group_size`` rounded to the m-block); the Pallas kernel reads the
per-group row count from SMEM and m-tiles past a group's frontier skip
their matmul entirely — the same skip-past-the-frontier trick as
``flash_decode_attention`` — so MXU time is proportional to *actual*
per-group load, not to the capacity bound. The masked XLA spelling
(:func:`grouped_matmul_reference`) is the same gather→batched-einsum→
scatter with zero-filled padding, and is the parity/fallback reference.

The op carries a custom VJP: dgrad is a grouped matmul against ``rhs``
transposed, wgrad is the per-group accumulation
``drhs[e] = lhs_e^T @ g_e`` spelled over the zero-padded group tiles.

``set_grouped_matmul_impl`` is the helper-impl seam, mirroring
``ops/flash_attention.set_attention_impl`` (reference: LayerHelper SPI).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# helper-impl seam
# ---------------------------------------------------------------------------

_IMPL = "auto"  # "auto" | "pallas" | "xla"


def set_grouped_matmul_impl(impl: str) -> None:
    """Select the grouped-matmul implementation: "xla" (masked reference
    spelling), "pallas" (TPU kernel; interpreted off-TPU), or "auto"
    (pallas on TPU, xla elsewhere). Read at trace time; jit caches are
    cleared on change so the toggle takes effect everywhere."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown grouped_matmul impl {impl!r}")
    global _IMPL
    if impl != _IMPL:
        _IMPL = impl
        jax.clear_caches()


def grouped_matmul_impl() -> str:
    return _IMPL


# ---------------------------------------------------------------------------
# sorted-rows <-> zero-padded group tiles
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _to_groups(x: jax.Array, group_sizes: jax.Array, m_pad: int) -> jax.Array:
    """Gather sorted rows ``x [N, c]`` into ``[E, m_pad, c]`` group tiles;
    slots past a group's size (and rows past the global frontier) are 0."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes  # exclusive cumsum [E]
    m_idx = jax.lax.broadcasted_iota(jnp.int32, (e, m_pad), 1)
    row = starts[:, None] + m_idx
    row = jnp.where(m_idx < sizes[:, None], row, x.shape[0])  # OOB -> fill
    return jnp.take(x, row.reshape(-1), axis=0, mode="fill",
                    fill_value=0).reshape(e, m_pad, x.shape[1])


def _from_groups(buf: jax.Array, group_sizes: jax.Array, n: int) -> jax.Array:
    """Scatter ``[E, m_pad, h]`` group tiles back to sorted rows ``[n, h]``;
    rows past ``sum(group_sizes)`` come back as zeros."""
    e, m_pad, h = buf.shape
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    rid = jnp.arange(n, dtype=jnp.int32)
    gid = jnp.searchsorted(ends, rid, side="right").astype(jnp.int32)
    safe = jnp.minimum(gid, e - 1)
    local = rid - (ends[safe] - sizes[safe])
    pos = safe * m_pad + local
    pos = jnp.where((gid < e) & (local < m_pad), pos, e * m_pad)  # OOB -> 0
    return jnp.take(buf.reshape(e * m_pad, h), pos, axis=0, mode="fill",
                    fill_value=0)


# ---------------------------------------------------------------------------
# masked XLA reference spelling
# ---------------------------------------------------------------------------


def _gmm_xla(lhs, rhs, group_sizes, m_pad):
    buf = _to_groups(lhs, group_sizes, m_pad)  # [E, m_pad, d], zero-masked
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)
    if out_dtype in (jnp.bfloat16, jnp.float16):
        out = jnp.einsum("emd,edh->emh", buf, rhs.astype(buf.dtype),
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("emd,edh->emh", buf, rhs)
    return _from_groups(out.astype(out_dtype), group_sizes, lhs.shape[0])


def grouped_matmul_reference(
    lhs: jax.Array,
    group_sizes: jax.Array,
    rhs: jax.Array,
    max_group_size: Optional[int] = None,
) -> jax.Array:
    """Masked XLA spelling of :func:`grouped_matmul` (plain autodiff, no
    custom VJP) — the parity reference for the Pallas kernel and for the
    custom VJP's gradients."""
    _check_shapes(lhs, group_sizes, rhs)
    m_pad, _ = _tiling(lhs.shape[0], max_group_size, 128)
    return _gmm_xla(lhs, rhs, group_sizes.astype(jnp.int32), m_pad)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _gmm_kernel(size_ref, lhs_ref, rhs_ref, out_ref, *, block_m):
    """One (group, n-tile, m-tile) grid step. The per-group row counts
    arrive as a scalar-prefetch SMEM vector; tiles wholly past the group
    frontier skip the matmul and just zero their output block (padded
    input rows are already zero, so partially-filled tiles need no extra
    masking)."""
    j = pl.program_id(2)
    size = size_ref[pl.program_id(0)]

    @pl.when(j * block_m >= size)
    def _():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(j * block_m < size)
    def _():
        out_ref[0] = jax.lax.dot_general(
            lhs_ref[0], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


# One rhs tile [d, block_n] stays resident while the m-tiles of its group
# stream past it, so it is sized in bytes: small enough that two buffers
# of it plus the lhs/out tiles sit well inside VMEM at any expert width.
_RHS_TILE_BYTES = 2 << 20


def _block_n(d: int, h: int, itemsize: int) -> int:
    """Widest multiple of 128 that divides ``h`` and keeps the rhs tile
    under ``_RHS_TILE_BYTES``; the whole of ``h`` when it is not a
    multiple of 128 (a block equal to the array dim is always legal)."""
    if h % 128:
        return h
    cap = max(128, _RHS_TILE_BYTES // (d * itemsize) // 128 * 128)
    bn = min(h, cap)
    while h % bn:
        bn -= 128
    return bn


def _gmm_pallas(lhs, rhs, group_sizes, m_pad, block_m, interpret):
    e, d, h = rhs.shape
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)
    itemsize = jnp.dtype(out_dtype).itemsize
    buf = _to_groups(lhs, group_sizes, m_pad).astype(out_dtype)
    block_n = _block_n(d, h, itemsize)
    kern = functools.partial(_gmm_kernel, block_m=block_m)
    kw = dict(memory_space=pltpu.VMEM)
    # double-buffered lhs / rhs / out tiles plus the f32 product
    vmem = (2 * itemsize * (block_m * d + d * block_n + block_m * block_n)
            + 4 * block_m * block_n)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # m innermost: the rhs tile's block index does not change
            # across it, so each [d, block_n] slab is fetched once
            grid=(e, h // block_n, m_pad // block_m),
            in_specs=[
                pl.BlockSpec((1, block_m, d),
                             lambda ge, n, j, sizes: (ge, j, 0), **kw),
                pl.BlockSpec((1, d, block_n),
                             lambda ge, n, j, sizes: (ge, 0, n), **kw),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda ge, n, j, sizes: (ge, j, n), **kw),
        ),
        out_shape=jax.ShapeDtypeStruct((e, m_pad, h), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        interpret=interpret,
        name="grouped_matmul",
    )(group_sizes.astype(jnp.int32), buf, rhs.astype(out_dtype))
    return _from_groups(out, group_sizes, lhs.shape[0])


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------


def _gmm_any(lhs, rhs, group_sizes, m_pad, block_m, use_pallas, interpret):
    if use_pallas:
        return _gmm_pallas(lhs, rhs, group_sizes, m_pad, block_m, interpret)
    return _gmm_xla(lhs, rhs, group_sizes, m_pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, group_sizes, m_pad, block_m, use_pallas, interpret):
    return _gmm_any(lhs, rhs, group_sizes, m_pad, block_m, use_pallas,
                    interpret)


def _gmm_fwd(lhs, rhs, group_sizes, m_pad, block_m, use_pallas, interpret):
    out = _gmm_any(lhs, rhs, group_sizes, m_pad, block_m, use_pallas,
                   interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(m_pad, block_m, use_pallas, interpret, res, g):
    lhs, rhs, group_sizes = res
    # dgrad: grouped matmul against rhs transposed — rows past the frontier
    # had zero output, so they correctly get zero cotangent back.
    dlhs = _gmm_any(g, jnp.swapaxes(rhs, 1, 2), group_sizes, m_pad, block_m,
                    use_pallas, interpret).astype(lhs.dtype)
    # wgrad: per-group accumulation drhs[e] = lhs_e^T @ g_e over the
    # zero-padded group tiles (padding rows contribute nothing).
    lhs_buf = _to_groups(lhs, group_sizes, m_pad)
    g_buf = _to_groups(g, group_sizes, m_pad)
    if jnp.promote_types(lhs.dtype, g.dtype) in (jnp.bfloat16, jnp.float16):
        drhs = jnp.einsum("emd,emh->edh", lhs_buf, g_buf,
                          preferred_element_type=jnp.float32)
    else:
        drhs = jnp.einsum("emd,emh->edh", lhs_buf, g_buf)
    dgs = np.zeros(group_sizes.shape, dtype=jax.dtypes.float0)
    return dlhs, drhs.astype(rhs.dtype), dgs


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def _check_shapes(lhs, group_sizes, rhs):
    if lhs.ndim != 2 or rhs.ndim != 3 or group_sizes.ndim != 1:
        raise ValueError(
            f"grouped_matmul expects lhs [N, d], group_sizes [E], "
            f"rhs [E, d, h]; got {lhs.shape}, {group_sizes.shape}, "
            f"{rhs.shape}")
    if rhs.shape[0] != group_sizes.shape[0] or rhs.shape[1] != lhs.shape[1]:
        raise ValueError(
            f"grouped_matmul shape mismatch: lhs {lhs.shape}, "
            f"group_sizes {group_sizes.shape}, rhs {rhs.shape}")


def _tiling(n: int, max_group_size: Optional[int], block_m: int,
            itemsize: int = 4):
    """(m_pad, block_m): the padded per-group tile height and the m-block,
    both multiples of the dtype's sublane count — one TPU register tile
    is 8 rows of 32 bits, so 8 rows of f32 and 16 of bf16."""
    sublanes = 8 * max(1, 4 // itemsize)
    m = n if max_group_size is None else int(max_group_size)
    m = max(1, min(m, max(n, 1)))
    bm = min(_round_up(block_m, sublanes), _round_up(m, sublanes))
    return _round_up(m, bm), bm


def grouped_matmul(
    lhs: jax.Array,
    group_sizes: jax.Array,
    rhs: jax.Array,
    max_group_size: Optional[int] = None,
    block_m: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged grouped matmul over rows pre-sorted by group (see module
    docstring for the row-layout contract).

    ``max_group_size`` is a static upper bound on any single group's row
    count (e.g. the MoE capacity); it bounds the padded per-group tile so
    compute stays proportional to the bound instead of ``N``. Groups
    exceeding the bound have their overflow rows zeroed — callers must
    guarantee the bound. Defaults to ``N`` (always safe)."""
    _check_shapes(lhs, group_sizes, rhs)
    if not jnp.issubdtype(rhs.dtype, jnp.inexact):  # e.g. int8 expert slabs
        rhs = rhs.astype(lhs.dtype)
    itemsize = jnp.dtype(jnp.promote_types(lhs.dtype, rhs.dtype)).itemsize
    m_pad, bm = _tiling(lhs.shape[0], max_group_size, block_m, itemsize)
    impl = _IMPL
    if impl == "auto":
        use_pallas = jax.default_backend() == "tpu"
    else:
        use_pallas = impl == "pallas"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), m_pad, bm,
                use_pallas, bool(interpret))
