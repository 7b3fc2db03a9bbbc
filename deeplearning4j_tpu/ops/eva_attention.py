"""EVA attention (Zheng et al. 2023, arXiv:2302.04542) as EvaByte computes
it: a query attends, under ONE softmax, the singletons of its own aligned
window (causally) and one learned summary for each chunk of every window
closed before it.

The decode state of a row is one plane for the keys and one for the values,
``[b, h, S + w, d]`` with the head dimension minor (how the chip stores a
plane whose ``d`` fills its 128 lanes): entries ``[0, S)`` hold the
summaries, chunk ``j`` of the sequence at index ``j`` (``S`` = chunks in
``max_len``), entries ``[S, S + w)`` the open window's singletons, position
``p`` at index ``S + p mod w``. A row at position ``p`` in window ``W = p //
w`` attends the summaries ``[0, m W)`` (``m`` = chunks a window) and the
singletons ``[S, S + p mod w]``: two prefixes, bounded by ``S + w`` entries
where a K/V cache holds ``max_len``.

* :func:`eva_decode_attention` - the step's single-query attention over the
  two prefixes: the Pallas kernel ``eva_decode`` on the TPU (it fetches only
  the blocks a row's position makes valid), a ``jax.numpy`` spelling
  elsewhere, behind the same seam as the other attention helpers
  (``set_attention_impl``).
* :func:`eva_prefill_attention` - a whole prompt, window by window: exact
  causal attention inside the window with the earlier windows' summaries
  beside it, which is causal attention of ``w`` queries over ``m W + w``
  keys (``mha_attention``: the flash forward kernel on the TPU), so the
  scores alive at a time are one window's.
* :func:`chunk_summaries` - ``k~ = sum_t softmax_t(s mu.k_t) k_t`` and
  ``v~ = sum_t softmax_t(s phi.k_t) v_t`` over each chunk's own positions.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NEG, attention_impl, count_kv, mha_attention


def chunk_summaries(k, v, mu, phi, valid=None):
    """k, v ``[b, h, n, c, d]`` (n chunks of c positions), mu, phi ``[h, d]``
    -> ``(k~, v~)`` ``[b, h, n, d]`` in k's type; the softmaxes in float32.
    ``valid`` ``[b, c]`` masks the positions of a chunk that is not full yet
    (its first is always valid)."""
    with jax.named_scope("eva.summarize"):
        f32 = jnp.float32
        s = k.shape[-1] ** -0.5
        k32, v32 = k.astype(f32), v.astype(f32)

        def weights(vec):
            logit = s * jnp.einsum("bhncd,hd->bhnc", k32, vec.astype(f32))
            if valid is not None:
                logit = jnp.where(valid[:, None, None, :], logit, _NEG)
            return jax.nn.softmax(logit, axis=-1)

        ks = jnp.einsum("bhnc,bhncd->bhnd", weights(mu), k32)
        vs = jnp.einsum("bhnc,bhncd->bhnd", weights(phi), v32)
        return ks.astype(k.dtype), vs.astype(v.dtype)


# ---------------------------------------------------------------------------
# the step: single-query attention over two prefixes of one plane
# ---------------------------------------------------------------------------


def eva_decode_attention_reference(q, k, v, n_sum, n_win, win_start,
                                   scale=None):
    """``jax.numpy`` spelling. q ``[b, h, 1, d]``; k, v ``[b, h, L, d]``;
    row ``b`` attends entries ``[0, n_sum[b])`` and ``[win_start, win_start
    + n_win[b])`` of its planes under one softmax (float32)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    f32 = jnp.float32
    e = jnp.arange(k.shape[2], dtype=jnp.int32)[None, :]
    keep = (e < n_sum[:, None]) | ((e >= win_start)
                                   & (e < win_start + n_win[:, None]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=f32) * scale
    s = jnp.where(keep[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(keep[:, None, None, :], p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def _eva_decode_kernel(nsum_ref, nwin_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                       l_scr, acc_scr, *, scale, block_k, sum_blocks):
    """One (row, head group, entry block) grid step. The entry axis is the
    innermost, so the online-softmax accumulators carry across it; a block
    that the row's position leaves wholly invalid is neither fetched (the
    index map stays on a block already there) nor computed. The one query
    row is broadcast to eight sublanes, so that both products are MXU
    matmuls over the head group."""
    ki = pl.program_id(2)
    n_sum = nsum_ref[pl.program_id(0)]
    n_win = nwin_ref[pl.program_id(0)]
    start = sum_blocks * block_k

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo = ki * block_k
    live = (lo < n_sum) | ((ki >= sum_blocks) & (lo < start + n_win))

    @pl.when(live)
    def _():
        q = q_ref[0]                                   # [hb, 1, d]
        kb, vb = k_ref[0], v_ref[0]                    # [hb, block_k, d]
        q8 = jnp.broadcast_to(q, (q.shape[0], 8, q.shape[2]))
        s = jax.lax.dot_general(
            q8, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [hb, 8, block_k]
        e = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        keep = (e < n_sum) | ((e >= start) & (e < start + n_win))
        s = jnp.where(keep, s, _NEG)
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # [hb, 8, d]

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out[:, 0:1, :].astype(o_ref.dtype)


def _entry_block(n_sum_entries: int, n_win_entries: int, limit: int) -> int:
    """The largest power of two up to ``limit`` that divides both segments."""
    b = 1
    while b * 2 <= limit and n_sum_entries % (b * 2) == 0 \
            and n_win_entries % (b * 2) == 0:
        b *= 2
    return b


def eva_decode_attention_pallas(q, k, v, n_sum, n_win, win_start, scale=None,
                                block_k: int = 512, head_block: int = 8,
                                interpret: Optional[bool] = None):
    """The Pallas kernel ``eva_decode`` (same contract as
    :func:`eva_decode_attention_reference`). The planes go in as they lie,
    ``[b, h, L, d]``; a grid step takes ``head_block`` heads and
    ``block_k`` entries."""
    if q.shape[2] != 1:
        raise ValueError("eva_decode is the single-query kernel")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, L, d = k.shape
    bk = _entry_block(win_start, L - win_start, block_k)
    hb = max(g for g in range(1, min(h, head_block) + 1) if h % g == 0)
    sb, nb = win_start // bk, L // bk
    count_kv("full")

    def entry(r, g, ki, nsum, nwin):
        ns = (nsum[r] + bk - 1) // bk      # summary blocks the row attends
        nw = (nwin[r] + bk - 1) // bk      # window blocks (at least one)
        dead_sum = jnp.where(ns > 0, ns - 1, sb)
        blk = jnp.where(ki < ns, ki, jnp.where(
            ki < sb, dead_sum, jnp.minimum(ki, sb + jnp.maximum(nw, 1) - 1)))
        return (r, g, blk, 0)

    kw = dict(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_eva_decode_kernel, scale=float(scale), block_k=bk,
                          sum_blocks=sb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // hb, nb),
            in_specs=[
                pl.BlockSpec((1, hb, 1, d),
                             lambda r, g, ki, nsum, nwin: (r, g, 0, 0), **kw),
                pl.BlockSpec((1, hb, bk, d), entry, **kw),
                pl.BlockSpec((1, hb, bk, d), entry, **kw),
            ],
            out_specs=pl.BlockSpec(
                (1, hb, 1, d), lambda r, g, ki, nsum, nwin: (r, g, 0, 0),
                **kw),
            scratch_shapes=[
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="eva_decode",
    )(n_sum.astype(jnp.int32), n_win.astype(jnp.int32), q, k, v)
    return out


def eva_decode_attention(q, k, v, n_sum, n_win, win_start, scale=None):
    """Helper-seam dispatch (mirrors :func:`~.flash_attention.
    decode_attention`): the Pallas kernel when "flash" is selected or, on
    "auto", on the TPU; the ``jax.numpy`` spelling otherwise."""
    with jax.named_scope("eva.attend"):
        impl = attention_impl()
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "xla"
        if impl == "flash":
            return eva_decode_attention_pallas(q, k, v, n_sum, n_win,
                                               win_start, scale=scale)
        return eva_decode_attention_reference(q, k, v, n_sum, n_win,
                                              win_start, scale=scale)


# ---------------------------------------------------------------------------
# the prefill: window by window
# ---------------------------------------------------------------------------


def eva_prefill_attention(q, k, v, ks, vs, window: int, chunk: int,
                          scale=None):
    """q, k, v ``[b, h, t, d]`` from position 0, ``t`` a multiple of
    ``window`` (or shorter than one); ks, vs ``[b, h, t / chunk, d]`` the
    summaries of every chunk. Window ``W``'s queries attend causally the
    keys ``[summaries of the windows before it, the window's own]``: the
    helper seam's causal attention with fewer queries than keys puts the
    frontier where EVA has it (query i sees keys up to ``m W + i``)."""
    with jax.named_scope("eva.attend"):
        t = q.shape[2]
        if t <= window:
            return mha_attention(q, k, v, causal=True, scale=scale)
        m = window // chunk
        outs = []
        for i in range(t // window):
            lo, hi = i * window, (i + 1) * window
            keys = jnp.concatenate([ks[:, :, :m * i], k[:, :, lo:hi]], axis=2)
            vals = jnp.concatenate([vs[:, :, :m * i], v[:, :, lo:hi]], axis=2)
            outs.append(mha_attention(q[:, :, lo:hi], keys, vals,
                                          causal=True, scale=scale))
        return jnp.concatenate(outs, axis=2)
