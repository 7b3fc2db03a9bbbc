"""Differential attention's single-query step (DIFF Transformer, Ye et al.
2024, arXiv:2410.05258) over K/V heads kept in pairs.

A differential head ``j`` has two queries, ``q1 = q[2j]`` and ``q2 =
q[2j + 1]``; its K/V pair ``g = j // r`` (``r = heads / kv_heads / ...``:
the differential heads that share a pair, 1 or 2) has two keys, ``k1 =
k[2g]`` and ``k2 = k[2g + 1]``, and ONE value twice a head wide, ``v[2g] ++
v[2g + 1]``:

    o_j = softmax(q1 . k1 * scale) v  -  lam softmax(q2 . k2 * scale) v

``q`` is ``[b, heads, d]``, ``k`` and ``v`` ``[b, kv_heads, L, d]`` with the
row's valid entries first (``lengths``); the result ``[b, heads / 2, 2 d]``
is ``o`` before the head's norm. The kernel reads each K/V block ONCE for
both maps of every differential head of the pair: the pair's two key heads
lie next to each other in the plane, so a ``[2 d, block]`` block of the
plane as the chip keeps it (position-minor) holds ``k1`` over ``k2``, and a
query row that is ``q1 ++ 0`` scores against ``k1``, one that is ``0 ++
q2`` against ``k2``, in one product. The values' block is the pair's ``v``
as it is.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_DECODE_BLOCK_K, _NEG, attention_impl,
                              count_kv, decode_fetched_entries)

_F32 = jnp.float32


def _pairs(q, k, v):
    """``(q [b, P, r, 2, d], k1, k2 [b, P, L, d], v [b, P, L, 2 d])``: the
    queries by K/V pair, differential head and map, the pair's keys, and
    its value two heads wide."""
    b, hq, d = q.shape
    hk, L = k.shape[1], k.shape[2]
    pairs = hk // 2
    r = hq // hk
    kp = k.reshape(b, pairs, 2, L, d)
    vp = v.reshape(b, pairs, 2, L, d).transpose(0, 1, 3, 2, 4).reshape(
        b, pairs, L, 2 * d)
    return q.reshape(b, pairs, r, 2, d), kp[:, :, 0], kp[:, :, 1], vp


def diff_decode_attention_reference(q, k, v, lengths, lam, scale=None):
    """Plain XLA spelling: both maps of every differential head over the
    whole planes, masked past each row's length; a row of length 0 gives
    0."""
    b, hq, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qp, k1, k2, vp = _pairs(q, k, v)
    L = k.shape[2]
    seen = (jnp.arange(L, dtype=jnp.int32)[None, :]
            < lengths.astype(jnp.int32)[:, None])[:, None, None, :]

    def one_map(qm, km):                   # [b, P, r, d], [b, P, L, d]
        s = jnp.einsum("bprd,bpld->bprl", qm, km,
                       preferred_element_type=_F32) * scale
        s = jnp.where(seen, s, _NEG)
        p = jnp.where(seen, jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("bprl,bple->bpre", p.astype(vp.dtype), vp,
                          preferred_element_type=_F32)

    o = one_map(qp[:, :, :, 0], k1) - lam * one_map(qp[:, :, :, 1], k2)
    return o.reshape(b, hq // 2, 2 * d)


def decode_live_schedule(lengths, max_len: int, block_k: int):
    """The (row, block) pairs a single-query decode kernel visits for rows
    of ``lengths`` valid entries in a cache of ``max_len`` (``lengths``
    within ``[0, max_len]``): each row's blocks in order, whole blocks up to
    the one that holds its last entry and one block for a row with none
    (:func:`~.flash_attention.decode_fetched_entries`), then the next
    row's. ``(n, rows, blocks)``: the count of live pairs and two
    ``int32[b * max_len / block_k + 1]`` vectors whose first ``n`` entries
    are the pairs; the entries past ``n`` repeat the last pair, so that
    what a look-ahead past the end names is already there. No loop and no
    gather: a step's row is the number of rows that end at or before it,
    and its block its distance from the last of those ends."""
    b = lengths.shape[0]
    counts = decode_fetched_entries(lengths, max_len, block_k) // block_k
    ids = jnp.arange(b, dtype=jnp.int32)
    ends = jnp.sum(jnp.where(ids[None, :] <= ids[:, None], counts[None, :],
                             0), axis=1, dtype=jnp.int32)
    n = ends[b - 1]
    steps = jnp.minimum(
        jnp.arange(b * (max_len // block_k) + 1, dtype=jnp.int32), n - 1)
    before = ends[None, :] <= steps[:, None]
    rows = jnp.sum(before, axis=1, dtype=jnp.int32)
    starts = jnp.max(jnp.where(before, ends[None, :], 0), axis=1)
    return n, rows, steps - starts


def _diff_decode_kernel(len_ref, rows_ref, blocks_ref, lam_ref, q_ref, k_ref,
                        v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                        block_k, r, precision):
    """One live (row, block) step of a pair block (``decode_live_schedule``):
    the ``4 r`` query rows of every pair of the block (``r`` first maps,
    ``r`` second maps, then the same again: eight sublanes) against
    ``block_k`` entries of the pair's keys and values, both ``[2 d,
    block_k]``. The online softmax runs per row, from the row's first
    block to the one that holds its last entry; the second product takes
    the weights in two parts, as ``flash_decode`` does: rows ``0 .. 2 r``
    the weights rounded to V's precision, rows ``2 r .. 4 r`` what the
    rounding lost."""
    step = pl.program_id(1)
    ki = blocks_ref[step]
    length = len_ref[rows_ref[step]]
    n = 2 * r

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < length)
    def _():
        q8 = q_ref[0]                                   # [pb, 8, 2d]
        kt, vt = k_ref[0], v_ref[0]                     # [pb, 2d, block_k]
        s = jax.lax.dot_general(
            q8, kt, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=_F32) * scale        # [pb, 8, block_k]
        k_ids = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2)
        keep = k_ids < length
        s = jnp.where(keep, s, _NEG)
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l * alpha + jnp.sum(p, axis=2, keepdims=True)
        hi = p.astype(vt.dtype).astype(_F32)
        row = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        p2 = jnp.where(row < n, hi, jnp.where(row < 2 * n, p - hi, 0.0))
        vt = jnp.where(keep, vt, jnp.zeros_like(vt))
        acc_scr[...] = acc * alpha + jax.lax.dot_general(
            p2.astype(vt.dtype), vt, (((2,), (2,)), ((0,), (0,))),
            precision=precision, preferred_element_type=_F32)  # [pb, 8, 2d]

    @pl.when((ki + 1) * block_k >= length)              # the row's last
    def _():
        acc = acc_scr[...]
        o = (acc[:, 0:n, :] + acc[:, n:2 * n, :]) / jnp.maximum(
            l_scr[:, 0:n, :], 1e-30)                    # [pb, 2r, 2d]
        lam = lam_ref[0:1, :]                           # [1, 2d], all lam
        o_ref[0] = (o[:, 0:r, :] - lam[None] * o[:, r:n, :]).astype(
            o_ref.dtype)


def diff_decode_attention_pallas(q, k, v, lengths, lam, scale=None, *,
                                 name: str = "diff_decode",
                                 block_k: int = _DECODE_BLOCK_K,
                                 interpret: Optional[bool] = None):
    """The Pallas kernel (same contract as
    :func:`diff_decode_attention_reference`). ``L`` is a multiple of
    ``block_k`` or smaller than it. The grid is ``(pair blocks, n)``, ``n``
    the number of live (row, block) pairs of :func:`decode_live_schedule`,
    a length the program reads at run time: no step exists for a block
    past a row's last entry, each row's blocks come in their order, and
    the pipeline's look-ahead brings the next row's first block in under
    this row's last. ``name`` is the ``pallas_call``'s, so that two uses of
    the kernel (a ring of a window, a whole cache) each have their own
    time in a trace."""
    b, hq, d = q.shape
    hk, L = k.shape[1], k.shape[2]
    r = hq // hk
    if hk % 2 or hq != r * hk or r not in (1, 2):
        raise ValueError(
            f"diff_decode: {hq} query heads over {hk} K/V heads (K/V heads "
            "in pairs; one or two differential heads a pair)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pairs, wide = hk // 2, 2 * d
    block_k = min(block_k, L)
    if L % block_k:
        raise ValueError(f"diff_decode: {L} entries in blocks of {block_k}")
    count_kv("live")
    # the query rows of a pair: q1 ++ 0 for each head, 0 ++ q2 for each,
    # twice over (the weights' two parts), as the eight sublanes
    qp = q.reshape(b, pairs, r, 2, d)
    zeros = jnp.zeros_like(qp[:, :, :, 0])
    rows = jnp.concatenate([
        jnp.concatenate([qp[:, :, :, 0], zeros], axis=-1),
        jnp.concatenate([zeros, qp[:, :, :, 1]], axis=-1)], axis=2)
    q8 = jnp.concatenate([rows, rows] + ([jnp.zeros_like(rows)] * 2
                                         if r == 1 else []), axis=2)
    # [b, hk, L, d] as the chip keeps it, [b, hk, d, L]: a pair's two heads
    # one over the other are [2d, L] (a relabelling, no copy)
    kt = jnp.swapaxes(k, 2, 3).reshape(b, pairs, wide, L)
    vt = jnp.swapaxes(v, 2, 3).reshape(b, pairs, wide, L)
    pb = max(g for g in range(1, pairs + 1) if pairs % g == 0 and (
        g == 1 or g * 2 * wide * block_k * k.dtype.itemsize <= 4 << 20))
    lens = jnp.clip(lengths.astype(jnp.int32), 0, L)
    n, row_of, block_of = decode_live_schedule(lens, L, block_k)

    def kv_block(g, step, lens, row_of, block_of):
        return (row_of[step], g, 0, block_of[step])

    def row_block(g, step, lens, row_of, block_of):
        return (row_of[step], g, 0, 0)

    kern = functools.partial(
        _diff_decode_kernel, scale=float(scale), block_k=block_k, r=r,
        precision=(jax.lax.Precision.HIGHEST if k.dtype == _F32 else None))
    kw = dict(memory_space=pltpu.VMEM)
    lam_block = jnp.full((8, wide), lam, _F32)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pairs // pb, n),
            in_specs=[
                pl.BlockSpec((8, wide), lambda g, step, *_: (0, 0), **kw),
                pl.BlockSpec((1, pb, 8, wide), row_block, **kw),
                pl.BlockSpec((1, pb, wide, block_k), kv_block, **kw),
                pl.BlockSpec((1, pb, wide, block_k), kv_block, **kw),
            ],
            out_specs=pl.BlockSpec((1, pb, r, wide), row_block, **kw),
            scratch_shapes=[
                pltpu.VMEM((pb, 8, 1), _F32),
                pltpu.VMEM((pb, 8, 1), _F32),
                pltpu.VMEM((pb, 8, wide), _F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, pairs, r, wide), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(lens, row_of, block_of, lam_block, q8.astype(k.dtype), kt, vt)
    return out.reshape(b, hq // 2, wide)


def diff_decode_attention(q, k, v, lengths, lam, scale=None, *,
                          name: str = "diff_decode"):
    """Helper-seam dispatch (as :func:`~.flash_attention.decode_attention`):
    the Pallas kernel when "flash" is selected or on a TPU, the XLA
    spelling otherwise."""
    impl = attention_impl()
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash":
        return diff_decode_attention_pallas(q, k, v, lengths, lam, scale,
                                            name=name)
    return diff_decode_attention_reference(q, k, v, lengths, lam, scale)
