"""Single-query attention over a LATENT plane (multi-head latent attention's
decode step in its absorbed form, ``nn/layers/mla.py``): one entry of
``rank + rope`` numbers a position serves every head as its key, and the
entry's first ``rank`` numbers as its value.

    scores[b, h, t] = scale * q[b, h, :] . plane[b, 0, t, :]      t < lengths[b]
    ctx[b, h, :]    = sum_t softmax_t(scores)[b, h, t] plane[b, 0, t, :rank]

``q`` is ``[b, h, rank + rope]`` (the up-projection already inside it),
``plane`` ``[b, 1, L, rank + rope]``; the result ``[b, h, rank]`` goes
through the value up-projection outside. Per entry and head that is
``2 (rank + rope) + 2 rank`` FLOPs over ``(rank + rope)`` numbers read once
for all heads: 64 heads make it 121 FLOPs a byte in bfloat16, where a
per-head K/V cache is 1.

A VERIFY window (speculative decoding's check of drafted tokens) puts ``tq``
query positions a row on the same plane, causal among themselves: ``q`` is
``[b, tq * h, rank + rope]`` (position-major), ``lengths`` the LAST query's,
and query ``j`` attends the first ``lengths - (tq - 1) + j`` entries. The
``tq * h`` queries of a row share one read of its entries, so 2 x 128 heads
make it about 480 FLOPs a byte: over the chip's ridge, where the one-query
step is under it (``mla_verify``, a kernel of its own name).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (attention_impl, count_kv,
                              decode_fetched_entries)

_F32 = jnp.float32
_NEG = -1e30
_BLOCK_K = 512


def _query_lengths(lengths: jax.Array, rows: int, tq: int) -> jax.Array:
    """``[b, rows]``: the entries each of a row's ``rows = tq * h`` queries
    attends, the last query's ``lengths`` and one fewer a position back."""
    j = jnp.arange(rows, dtype=jnp.int32) // (rows // tq)
    return lengths.astype(jnp.int32)[:, None] - (tq - 1) + j[None, :]


def mla_decode_attention_reference(q: jax.Array, plane: jax.Array,
                                   lengths: jax.Array, rank: int,
                                   scale: float, tq: int = 1) -> jax.Array:
    """Plain XLA spelling: the whole plane is read for the scores and again
    for the values, whatever the rows' lengths. A row of length 0 attends
    nothing and gives 0, as the kernel does. ``tq > 1``: a verify window
    (the module's docstring)."""
    with jax.named_scope("mla_decode_xla" if tq == 1 else "mla_verify_xla"):
        L = plane.shape[2]
        s = jnp.einsum("bhc,blc->bhl", q, plane[:, 0],
                       preferred_element_type=_F32) * scale
        seen = jnp.arange(L, dtype=jnp.int32)[None, None, :] \
            < _query_lengths(lengths, q.shape[1], tq)[:, :, None]
        s = jnp.where(seen, s, _NEG)
        p = jnp.where(seen, jax.nn.softmax(s, axis=-1),
                      0.0).astype(plane.dtype)
        return jnp.einsum("bhl,blc->bhc", p, plane[:, 0, :, :rank],
                          preferred_element_type=_F32)


def _mla_decode_kernel(len_ref, q_ref, kt_ref, o_ref, m_scr, l_scr, acc_scr,
                       *, scale, block_k, rank, precision, tq=1):
    """One (row, k-block) grid step: all the heads of a row over ``block_k``
    entries of the row's latent plane, which arrives POSITION-MINOR,
    ``[rank + rope, block_k]``: that is how the chip stores a plane whose
    entries do not fill its lanes, so the kernel reads it where it lies.
    Both products are MXU matmuls over the heads: the scores ``q [h, rank +
    rope] x entries``, and the values over the same block's first ``rank``
    sublanes, contracted over the positions. The online-softmax state is
    carried across the row's blocks in VMEM; a block past the row's length
    is neither fetched nor computed. With ``tq > 1`` (a verify window) the
    query rows ``j h .. (j + 1) h`` stop ``tq - 1 - j`` entries short of the
    row's length."""
    ki = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < length)
    def _():
        q, kt = q_ref[0], kt_ref[0, 0]          # [h, w], [w, block_k]
        keep = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1) < length
        # what lies past the frontier is anyone's (0 x NaN is NaN)
        kt = jnp.where(keep, kt, jnp.zeros_like(kt))
        if tq > 1:  # causal among the window's queries
            at = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[0], block_k), 1)
            keep = at < length - (tq - 1) + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[0], block_k), 0) // (q.shape[0] // tq)
        s = jnp.where(keep, jax.lax.dot_general(
            q, kt, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=_F32) * scale, _NEG)   # [h, block_k]
        m, l = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kt.dtype), kt[:rank], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=_F32)  # [h, rank]

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def mla_decode_attention_pallas(q: jax.Array, plane: jax.Array,
                                lengths: jax.Array, rank: int, scale: float,
                                block_k: int = _BLOCK_K,
                                interpret: Optional[bool] = None,
                                tq: int = 1) -> jax.Array:
    """Pallas kernel ``mla_decode`` (same contract as
    :func:`mla_decode_attention_reference`; the result in ``q``'s type).
    The plane goes to the kernel as ``[b, 1, rank + rope, L]``: for a plane
    the chip keeps position-minor (one whose entries are not a multiple of
    its 128 lanes) that transpose is a relabelling, not a copy. A grid step
    takes ``block_k`` entries for all the heads of a row, and only the
    blocks a row's length makes valid are moved
    (:func:`~.flash_attention.decode_fetched_entries`): a step the length
    leaves dead names the NEXT row's first block, which so comes in under
    this row's arithmetic. ``tq > 1``: the verify window of the module's
    docstring, under the name ``mla_verify``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, w = q.shape
    L = plane.shape[2]
    block_k = min(block_k, L)
    if L % block_k:
        raise ValueError(f"max_len {L} is not a multiple of {block_k}")
    lengths = jnp.maximum(lengths.astype(jnp.int32), 0)
    count_kv("full")

    def entries(r, ki, lens):
        live = ki * block_k < lens[r]
        stay = live | (r == b - 1)
        own_last = decode_fetched_entries(lens[r], L, block_k) // block_k - 1
        return (jnp.where(stay, r, r + 1), 0, 0,
                jnp.where(live, ki, jnp.where(stay, own_last, 0)))

    kern = functools.partial(
        _mla_decode_kernel, scale=float(scale), block_k=block_k, rank=rank,
        precision=(jax.lax.Precision.HIGHEST if plane.dtype == jnp.float32
                   else None), tq=tq)
    kw = dict(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, L // block_k),
            in_specs=[
                pl.BlockSpec((1, h, w), lambda r, ki, lens: (r, 0, 0), **kw),
                pl.BlockSpec((1, 1, w, block_k), entries, **kw),
            ],
            out_specs=pl.BlockSpec((1, h, rank),
                                   lambda r, ki, lens: (r, 0, 0), **kw),
            scratch_shapes=[
                pltpu.VMEM((h, 1), _F32),
                pltpu.VMEM((h, 1), _F32),
                pltpu.VMEM((h, rank), _F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        interpret=interpret,
        name="mla_decode" if tq == 1 else "mla_verify",
    )(lengths, q.astype(plane.dtype), jnp.swapaxes(plane, 2, 3))


def mla_decode_attention(q: jax.Array, plane: jax.Array, lengths: jax.Array,
                         rank: int, scale: Optional[float] = None,
                         tq: int = 1) -> jax.Array:
    """``[b, h, rank]``: each row's heads over the first ``lengths[b]``
    entries of its row of the plane (``tq > 1``: ``[b, tq * h, rank]``, a
    verify window, query ``j`` over ``lengths[b] - (tq - 1) + j``, through
    ``mla_verify``). Helper-seam dispatch (mirrors
    :func:`~.flash_attention.decode_attention`): the Pallas kernel when
    "flash" is selected, or automatically on TPU where the plane's blocks
    fit the kernel; the builtin XLA spelling otherwise."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    impl = attention_impl()
    if impl == "auto":
        impl = ("flash" if jax.default_backend() == "tpu"
                and plane.shape[2] % min(_BLOCK_K, plane.shape[2]) == 0
                and plane.shape[2] % 128 == 0 else "xla")
    if impl == "flash":
        return mla_decode_attention_pallas(q, plane, lengths, rank, scale,
                                           tq=tq)
    return mla_decode_attention_reference(q, plane, lengths, rank, scale,
                                          tq=tq)

