"""Pallas TPU flash attention.

Blockwise online-softmax attention (Dao et al. flash attention, computed the
TPU way): the q×k score matrix is never materialised in HBM — each q block
streams over k/v blocks held in VMEM, carrying running max/denominator, so
HBM traffic is O(t·d) instead of O(t²). Matmuls hit the MXU via
``dot_general`` with ``preferred_element_type=float32``.

This is the accelerated "helper" implementation for the attention layers
(deeplearning4j_tpu.nn.layers.attention); the reference's analogous seam is
the cuDNN attention/mha helper consulted before the builtin math
(SURVEY.md §2.1 "platform helpers", §2.2 "Helper SPI").

The backward pass is blockwise too (_mea_bwd_single — Dao et al. alg. 4 as
nested lax.scan): score blocks are recomputed per (q-chunk, k-chunk) with
the row logsumexp rebuilt on the fly, so TRAINING memory is O(t·d) like
the forward — long-context backprop never materialises the t² matrix.
Inputs [batch, heads, time, head_dim].
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # finite "-inf": keeps exp/max well-defined for fully-masked rows

# ---------------------------------------------------------------------------
# helper-impl seam (reference: LayerHelper SPI — cuDNN vs builtin)
# ---------------------------------------------------------------------------

_IMPL = "auto"  # "auto" | "flash" | "xla"


def set_attention_impl(impl: str) -> None:
    """Select the attention implementation: "xla" (builtin einsum path),
    "flash" (Pallas kernel), or "auto" (flash on TPU for long sequences).

    The choice is read at trace time, so already-compiled functions would
    keep their traced impl; jit caches are cleared here so the toggle takes
    effect everywhere (recompilation on next call)."""
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    global _IMPL
    if impl != _IMPL:
        _IMPL = impl
        jax.clear_caches()


def attention_impl() -> str:
    return _IMPL


# ---------------------------------------------------------------------------
# reference (builtin) implementation — also the backward path for flash
# ---------------------------------------------------------------------------


def mha_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain XLA attention: softmax(q·kᵀ·scale + bias)·v. Masks are additive
    large-negative biases so shapes stay static for the compiler."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    neg = jnp.asarray(_NEG, scores.dtype)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :] > 0, scores, neg)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + (tk - tq)
        ki = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        scores = jnp.where(qi >= ki, scores, neg)
    weights = jax.nn.softmax(scores, axis=-1)
    if mask is not None or causal:
        # Rows with no valid key output 0 (matching the flash kernel) rather
        # than softmax-of-constant uniform weights.
        any_valid = jnp.any(scores > _NEG * 0.5, axis=-1, keepdims=True)
        weights = jnp.where(any_valid, weights, 0.0)
    return jnp.einsum("bhqk,bhkv->bhqv", weights, v)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr,
                  l_scr, acc_scr, *, scale, block_q, block_k, causal,
                  tk_offset):
    """One (batch·head, q-block, k-block) grid step.

    The k dimension is the innermost grid axis; TPU grids execute
    sequentially, so the VMEM scratch accumulators (running max /
    denominator / weighted sum) carry across k steps for a fixed q block.
    Only (block, d) tiles are ever resident in VMEM — Pallas pipelines the
    HBM→VMEM tile loads — so sequence length is bounded by HBM, not VMEM.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale  # [block_q, d]
        ks = k_ref[0].astype(jnp.float32)  # [block_k, d]
        vs = v_ref[0].astype(jnp.float32)  # [block_k, dv]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, block_k]
        mk = mask_ref[0, 0]  # [block_k]
        s = jnp.where(mk[None, :] > 0, s, _NEG)
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + tk_offset
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, _NEG)

        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # Zero masked entries explicitly: when a row is ENTIRELY masked,
        # m_new == _NEG and exp(s - m_new) == 1, which would weight masked
        # keys uniformly. Zeroing keeps l == 0 so the row output is 0 —
        # the defined semantics for fully-masked rows on both impls.
        p = jnp.where(s > _NEG * 0.5, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    if causal:
        # Skip k-blocks strictly above the causal frontier (every entry
        # masked): max q_id in the block < min k_id in the block. Halves
        # the causal FLOPs — the flash-attention point, at block level.
        @pl.when(qi * block_q + tk_offset + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        l_fin = l_scr[...]
        acc_fin = acc_scr[...]
        out = acc_fin / jnp.maximum(l_fin, 1e-30)  # fully-masked rows → 0
        o_ref[0] = out.astype(o_ref.dtype)
        # row logsumexp for the backward (saves its recompute pass there);
        # fully-masked rows get +big so exp(s - lse) -> 0 downstream
        lse_ref[0] = jnp.where(
            l_fin > 0, m_scr[...] + jnp.log(jnp.maximum(l_fin, 1e-30)),
            -_NEG)


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _flash_forward(q, k, v, mask, causal, scale, block_q, block_k, interpret,
                   with_lse: bool = False):
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    block_q = min(block_q, max(tq, 1))
    block_k = min(block_k, max(tk, 1))

    if mask is None:
        mask = jnp.ones((b, tk), jnp.float32)
    # [b, 1, tk]: a leading singleton keeps the block's trailing two dims
    # equal to the array dims, satisfying the mosaic tiling constraint.
    mask = _pad_to(mask.astype(jnp.float32), 1, block_k, 0.0)[:, None, :]
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    tq_p, tk_p = qp.shape[2], kp.shape[2]

    qp = qp.reshape(b * h, tq_p, d)
    kp = kp.reshape(b * h, tk_p, d)
    vp = vp.reshape(b * h, tk_p, dv)

    grid = (b * h, tq_p // block_q, tk_p // block_k)
    kern = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, tk_offset=tk - tq)
    kwargs = dict(memory_space=pltpu.VMEM)
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0),
                         **kwargs),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0),
                         **kwargs),
            pl.BlockSpec((1, block_k, dv), lambda bh, qi, ki: (bh, ki, 0),
                         **kwargs),
            pl.BlockSpec((1, 1, block_k), lambda bh, qi, ki: (bh // h, 0, ki),
                         **kwargs),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv),
                         lambda bh, qi, ki: (bh, qi, 0), **kwargs),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, ki: (bh, qi, 0), **kwargs),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq_p, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp, mask)
    out = out.reshape(b, h, tq_p, dv)[:, :, :tq, :]
    if not with_lse:
        return out
    return out, lse.reshape(b, h, tq_p)[:, :, :tq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, mask, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret):
    return _flash_forward(q, k, v, mask, causal, scale, block_q, block_k,
                          interpret)


def _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret):
    out, lse = _flash_forward(q, k, v, mask, causal, scale, block_q, block_k,
                              interpret, with_lse=True)
    return out, (q, k, v, mask, out, lse)


def _mea_bwd_single(q, k, v, mask_k, g, out, lse_rows, *, causal, scale,
                    tk_off, bq, bk):
    """Memory-efficient attention backward for ONE head (Dao et al. alg. 4,
    the XLA spelling): two-level ``lax.scan`` over (q-chunk, k-chunk)
    recomputes score blocks instead of materializing the [tq, tk] matrix —
    backward memory is O(t·d) like the flash forward, so long-context
    TRAINING fits, not just inference. Returns (dq, dk, dv).

    MXU discipline (round-5 backward tuning): operands stay in the INPUT
    dtype (bf16 on TPU) and every matmul accumulates in f32 via
    ``preferred_element_type`` — the same policy as the forward kernel.
    The softmax/statistics math (exp, lse, delta, ds scaling) runs in f32;
    only the 5 big dot_generals see bf16 operands, which doubles their MXU
    rate vs the previous cast-everything-to-f32 spelling."""
    tq, d = q.shape
    tk, dv = v.shape
    nq, nk = tq // bq, tk // bk
    op_dtype = q.dtype  # matmul operand dtype (bf16 on the TPU path)
    qc = q.reshape(nq, bq, d)
    gc = g.reshape(nq, bq, dv)
    oc = out.reshape(nq, bq, dv)
    lc = lse_rows.reshape(nq, bq, 1)
    kc = k.reshape(nk, bk, d)
    vc = v.reshape(nk, bk, dv)
    mc = mask_k.reshape(nk, bk)
    neg = jnp.float32(_NEG)

    def dotf32(a, b, dims):
        return lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)

    def scores(qch, kch, mch, qi, ki):
        s = dotf32(qch, kch, ((1,), (1,))) * scale  # [bq, bk] f32
        s = jnp.where(mch[None, :] > 0, s, neg)
        if causal:
            q_ids = (qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                     + tk_off)
            k_ids = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_ids >= k_ids, s, neg)
        return s

    def outer(carry, xs):
        dk_acc, dv_acc = carry
        qi, qch, gch, och, lse = xs  # lse: saved by the forward kernel
        delta = jnp.sum(gch.astype(jnp.float32) * och.astype(jnp.float32),
                        axis=-1, keepdims=True)  # D_i

        # pass 2: dq for this q-chunk; per-k-chunk dk/dv contributions
        def p2(dq, ys):
            ki, kch, vch, mch = ys
            s = scores(qch, kch, mch, qi, ki)
            p = jnp.where(s > neg * 0.5, jnp.exp(s - lse), 0.0)  # [bq, bk]
            dp = dotf32(gch, vch, ((1,), (1,)))                  # [bq, bk]
            ds = (p * (dp - delta)).astype(op_dtype)
            p_c = p.astype(op_dtype)
            dq = dq + dotf32(ds, kch, ((1,), (0,))) * scale
            return dq, (dotf32(ds, qch, ((0,), (0,))) * scale,
                        dotf32(p_c, gch, ((0,), (0,))))

        dq, (dks, dvs) = lax.scan(
            p2, jnp.zeros((bq, d), jnp.float32),
            (jnp.arange(nk), kc, vc, mc))
        return (dk_acc + dks, dv_acc + dvs), dq

    (dk_out, dv_out), dqs = lax.scan(
        outer,
        (jnp.zeros((nk, bk, d), jnp.float32),
         jnp.zeros((nk, bk, dv), jnp.float32)),
        (jnp.arange(nq), qc, gc, oc, lc))
    return dqs.reshape(tq, d), dk_out.reshape(tk, d), dv_out.reshape(tk, dv)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
               dq_ref, dq_scr, *, scale, block_q, block_k, causal, tk_offset):
    """Pallas backward kernel 1: dq. Grid (bh, q-block, k-block), k
    innermost; dq accumulates in VMEM scratch across the sequential k
    steps (same carry discipline as the forward kernel's online softmax).
    Per step: recompute the score block from q/k (bf16 operands, f32
    accumulation), p = exp(s - lse), ds = p * (g·vᵀ - delta),
    dq += ds·k · scale."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body():
        q = q_ref[0]                       # [bq, d] bf16
        ks = k_ref[0]                      # [bk, d]
        vs = v_ref[0]                      # [bk, dv]
        gs = g_ref[0]                      # [bq, dv]
        lse = lse_ref[0]                   # [bq, 1] f32
        delta = delta_ref[0]               # [bq, 1] f32
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mk = mask_ref[0, 0]
        s = jnp.where(mk[None, :] > 0, s, _NEG)
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + tk_offset
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_ids >= k_ids, s, _NEG)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            gs, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(qi * block_q + tk_offset + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q, block_k,
                causal, tk_offset):
    """Pallas backward kernel 2: dk and dv. Grid (bh, k-block, q-block),
    q innermost; dk/dv accumulate in VMEM scratch across q steps.

    Everything is computed in TRANSPOSED orientation — sᵀ = k·qᵀ [bk, bq],
    pᵀ, dsᵀ — so the two accumulating contractions are natural
    ([bk, bq]·[bq, d]) with no Mosaic tile transposes; lse/delta arrive as
    ROW vectors (tile (1, bq)) for the same reason."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body():
        q = q_ref[0]                        # [bq, d]
        ks = k_ref[0]                       # [bk, d]
        vs = v_ref[0]                       # [bk, dv]
        gs = g_ref[0]                       # [bq, dv]
        lse_row = lse_ref[0]                # [1, bq] f32
        delta_row = delta_ref[0]            # [1, bq] f32
        s_t = jax.lax.dot_general(
            ks, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bk, bq]
        mk = mask_ref[0, 0]                 # [bk]
        s_t = jnp.where(mk[:, None] > 0, s_t, _NEG)
        if causal:
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1) + tk_offset
            s_t = jnp.where(q_ids >= k_ids, s_t, _NEG)
        p_t = jnp.where(s_t > _NEG * 0.5, jnp.exp(s_t - lse_row), 0.0)
        dp_t = jax.lax.dot_general(
            vs, gs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, bq]
        ds_t = (p_t * (dp_t - delta_row)).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bk, d]
        dv_scr[...] += jax.lax.dot_general(
            p_t.astype(q.dtype), gs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, dv]

    if causal:
        # skip q-blocks entirely ABOVE the diagonal for this k block
        @pl.when(qi * block_q + tk_offset + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, mask, out, lse, g, causal, scale, bq, bk):
    """Pallas two-kernel backward (dq pass + dkv pass). Requires the lse
    saved by the Pallas forward. Inputs [b, h, t, d]."""
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    bq = min(bq, max(tq, 1))
    bk = min(bk, max(tk, 1))
    # halve blocks while padding waste exceeds 25% (t=1100 with bq=1024
    # would pad to 2048 — every padded tile still runs all five matmuls)
    while bq > 128 and -(-tq // bq) * bq > 1.25 * tq:
        bq //= 2
    while bk > 128 and -(-tk // bk) * bk > 1.25 * tk:
        bk //= 2

    mask_k = jnp.ones((b, tk), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    mp = _pad_to(mask_k, 1, bk, 0.0)[:, None, :]
    qp = _pad_to(q, 2, bq)
    gp = _pad_to(g.astype(q.dtype), 2, bq)
    # delta precomputed in XLA (cheap elementwise+reduce, fuses upstream)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dp_ = _pad_to(delta[..., None], 2, bq, 0.0)
    lp = _pad_to(lse.astype(jnp.float32)[..., None], 2, bq, -_NEG)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    tq_p, tk_p = qp.shape[2], kp.shape[2]

    qp = qp.reshape(b * h, tq_p, d)
    kp = kp.reshape(b * h, tk_p, d)
    vp = vp.reshape(b * h, tk_p, dv)
    gp = gp.reshape(b * h, tq_p, dv)
    lp = lp.reshape(b * h, tq_p, 1)
    dp_ = dp_.reshape(b * h, tq_p, 1)

    kw = dict(memory_space=pltpu.VMEM)
    kern_q = functools.partial(
        _dq_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal,
        tk_offset=tk - tq)
    dq = pl.pallas_call(
        kern_q,
        grid=(b * h, tq_p // bq, tk_p // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0), **kw),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0), **kw),
            pl.BlockSpec((1, bk, dv), lambda bh, qi, ki: (bh, ki, 0), **kw),
            pl.BlockSpec((1, bq, dv), lambda bh, qi, ki: (bh, qi, 0), **kw),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0), **kw),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0), **kw),
            pl.BlockSpec((1, 1, bk), lambda bh, qi, ki: (bh // h, 0, ki),
                         **kw),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                               **kw),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_bwd_dq",
    )(qp, kp, vp, gp, lp, dp_, mp)

    kern_kv = functools.partial(
        _dkv_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal,
        tk_offset=tk - tq)
    # row-vector stats for the transposed dkv kernel
    lp_row = jnp.transpose(lp, (0, 2, 1))     # [bh, 1, tq_p]
    dp_row = jnp.transpose(dp_, (0, 2, 1))
    dk, dv_out = pl.pallas_call(
        kern_kv,
        grid=(b * h, tk_p // bk, tq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0), **kw),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0), **kw),
            pl.BlockSpec((1, bk, dv), lambda bh, ki, qi: (bh, ki, 0), **kw),
            pl.BlockSpec((1, bq, dv), lambda bh, ki, qi: (bh, qi, 0), **kw),
            pl.BlockSpec((1, 1, bq), lambda bh, ki, qi: (bh, 0, qi), **kw),
            pl.BlockSpec((1, 1, bq), lambda bh, ki, qi: (bh, 0, qi), **kw),
            pl.BlockSpec((1, 1, bk), lambda bh, ki, qi: (bh // h, 0, ki),
                         **kw),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0), **kw),
            pl.BlockSpec((1, bk, dv), lambda bh, ki, qi: (bh, ki, 0), **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk_p, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        name="flash_bwd_dkv",
    )(qp, kp, vp, gp, lp_row, dp_row, mp)

    dq = dq.reshape(b, h, tq_p, d)[:, :, :tq].astype(q.dtype)
    dk = dk.reshape(b, h, tk_p, d)[:, :, :tk].astype(k.dtype)
    dv_out = dv_out.reshape(b, h, tk_p, dv)[:, :, :tk].astype(v.dtype)
    return dq, dk, dv_out


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, res, g):
    q, k, v, mask, out, lse = res
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    if not interpret:
        # compiled path: the two-kernel Pallas backward
        dq, dk, dv_g = _flash_bwd_pallas(
            q, k, v, mask, out, lse, g, causal, scale,
            bwd_block_q or block_q, bwd_block_k or block_k)
        dmask = None if mask is None else jnp.zeros_like(mask)
        return dq, dk, dv_g, dmask
    # interpreter/CPU fallback: the scan-based memory-efficient backward
    bq = min(bwd_block_q or block_q, max(tq, 1))
    bk = min(bwd_block_k or block_k, max(tk, 1))

    mask_k = jnp.ones((b, tk), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    # operands stay in the input dtype (bf16 on TPU): every matmul in
    # _mea_bwd_single accumulates f32 via preferred_element_type
    qp = _pad_to(q, 2, bq)
    gp = _pad_to(g.astype(q.dtype), 2, bq)
    op = _pad_to(out.astype(q.dtype), 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    mp = _pad_to(mask_k, 1, bk, 0.0)
    lp = _pad_to(lse.astype(jnp.float32)[..., None], 2, bq, -_NEG)

    single = functools.partial(
        _mea_bwd_single, causal=causal, scale=scale, tk_off=tk - tq,
        bq=bq, bk=bk)
    # vmap heads (mask is per-batch), then batch
    per_batch = jax.vmap(single, in_axes=(0, 0, 0, None, 0, 0, 0))
    dq, dk, dv = jax.vmap(per_batch)(qp, kp, vp, mp, gp, op, lp)

    dq = dq[:, :, :tq].astype(q.dtype)
    dk = dk[:, :, :tk].astype(k.dtype)
    dv = dv[:, :, :tk].astype(v.dtype)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over [b, h, t, d] tensors. ``mask`` is a [b, t_k]
    key-padding mask (1 = keep). Runs the Pallas kernel compiled on TPU and
    in interpreter mode elsewhere (the CPU test path).

    Block defaults come from sweeps at d=64, bf16 on another machine's
    v5e (2026-07, not re-measured since): forward block_q=256 with
    block_k adaptive on sequence length — 512 up to 4k and 1024 beyond;
    backward 1024x1024, so that each step's five matmuls fill the MXU on
    their own. Operands stay bf16 with f32 accumulation."""
    if block_k is None:
        block_k = 512 if k.shape[2] < 8192 else 1024
    if bwd_block_q is None:
        bwd_block_q = 1024
    if bwd_block_k is None:
        bwd_block_k = 1024
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(q, k, v, mask, causal, float(scale), block_q, block_k,
                  bwd_block_q, bwd_block_k, interpret)


# ---------------------------------------------------------------------------
# KV-cache decode attention (single-query-block flash)
# ---------------------------------------------------------------------------


def decode_attention_reference(
    q: jax.Array,           # [b, h, tq, d] — queries at positions start+i
    k: jax.Array,           # [b, h_kv, L, d]  — static-shape KV cache
    v: jax.Array,           # [b, h_kv, L, dv]
    start_pos: jax.Array,   # [b] int32 — absolute position of q's first row
    scale: Optional[float] = None,
) -> jax.Array:
    """Builtin XLA decode attention against a cached K/V: query ``i`` of row
    ``b`` sits at absolute position ``start_pos[b] + i`` and attends cache
    entries ``[0, start_pos[b] + i]`` inclusive. With fewer K/V heads than
    query heads (grouped queries) query head ``h`` reads K/V head ``h //
    (h / h_kv)``; this spelling repeats the cache's heads for the call. Cache slots past the
    frontier (pad garbage, not-yet-written zeros) are masked out, so the
    cache can stay a fixed ``[b, h, max_len, d]`` allocation for the whole
    generation — no shape ever depends on how far decoding has advanced."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:  # grouped queries: head h reads K/V h // g
        k, v = (jnp.repeat(a, q.shape[1] // a.shape[1], axis=1)
                for a in (k, v))
    tq, L = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (tq, L), 0)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (tq, L), 1)
    limit = start_pos.astype(jnp.int32)[:, None, None, None] + q_ids[None, None]
    keep = k_ids[None, None] <= limit
    neg = jnp.asarray(_NEG, scores.dtype)
    scores = jnp.where(keep, scores, neg)
    weights = jax.nn.softmax(scores, axis=-1)
    # rows with no valid key (start_pos < 0 — an inactive slot) output 0
    any_valid = jnp.any(scores > _NEG * 0.5, axis=-1, keepdims=True)
    weights = jnp.where(any_valid, weights, 0.0)
    return jnp.einsum("bhqk,bhkv->bhqv", weights, v)


# entries a grid step of ``flash_decode`` takes of each head: timed on the chip
# at the serve cell's shapes (128 rows at positions 16-896 of 1,024: PERF.md
# section 6, PR 32) against 128 (more steps) and 512 (more invalid bytes)
_DECODE_BLOCK_K = 256


def decode_fetched_entries(lengths, max_len: int,
                           block_k: int = _DECODE_BLOCK_K):
    """Entries of its K plane (and as many of its V plane) that
    ``flash_decode`` moves out of HBM for a row with ``lengths`` valid
    entries in a cache of ``max_len``: whole blocks of ``block_k`` up to the
    one that holds the row's last entry, and one block for a row with none.
    Plain arithmetic, for Python ints, NumPy arrays and traced values alike:
    the kernel's index map, the engine's counter and the tests share it."""
    bk = min(block_k, max(max_len, 1))
    blocks = (lengths + bk - 1) // bk
    return (blocks + (blocks == 0)) * bk


def _decode_kernel(len_ref, *refs, scale, block_k, precision, group=1,
                   write=False):
    """One (row, head group, k-block) grid step of single-query flash
    decode: every head of the group at once.

    The k axis is the innermost (sequential) grid dim so the VMEM online-
    softmax accumulators carry across k blocks, exactly like the training
    forward kernel — but the q block is a single row (the token being
    decoded) and the valid cache lengths arrive as a scalar-prefetch SMEM
    vector, so a k-block entirely past the decode frontier is neither
    fetched (the index map names no block of the row's there) nor
    computed: the per-step work is O(position), not O(max_len).

    K and V blocks arrive POSITION-MINOR, ``[heads, d, block_k]``: that is
    how the chip stores a ``[b, h, L, d]`` cache whose ``d`` is narrower
    than its 128 lanes, so the kernel reads the cache where it lies and no
    step transposes it. The one query row is broadcast to eight sublanes so
    that both products are MXU matmuls over the head group. The softmax
    weights stay float32 through the second one: rows ``0 .. group`` of its
    left operand are the weights rounded to V's precision, rows ``group ..
    2 group`` what the rounding lost, and the two products are added.

    GROUPED queries (``group`` query heads share a K/V head; 2 or 4): the
    group's queries arrive as the eight sublanes (the group, repeated), so
    a K/V block is read ONCE for all of them and the products keep their
    shapes.

    WRITING (``write``: :func:`flash_decode_attention` given the step's new
    entries): on the one step whose block holds a kept row's position, the
    128-lane tile round that position takes the new K and V entries in
    VMEM before the scores are formed, and the tile alone goes back to the
    cache (aliased, left in HBM) by one DMA a plane from a scratch of its
    own, waited on before the scratch is used again and at the end."""
    if write:
        (wpos_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref, ko_ref, vo_ref,
         m_scr, l_scr, acc_scr, k_tile, v_tile, sems, pending) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(2)
    length = len_ref[pl.program_id(0)]  # valid entries = pos + 1
    if write:
        _decode_write_tile(wpos_ref, k_ref, v_ref, kn_ref, vn_ref, ko_ref,
                           vo_ref, k_tile, v_tile, sems, pending,
                           block_k=block_k)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < length)
    def _():
        q = q_ref[0]                     # [hb, 1, d]; grouped: [hb, 8, d]
        kt, vt = k_ref[0], v_ref[0]                     # [hb, d, block_k]
        q8 = q if group > 1 else jnp.broadcast_to(
            q, (q.shape[0], 8, q.shape[2]))
        s = jax.lax.dot_general(
            q8, kt, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32) * scale  # [hb, 8, block_k]
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
        hi = p.astype(vt.dtype).astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        p2 = jnp.where(row < group, hi,
                       jnp.where(row < 2 * group, p - hi, 0.0))
        # what lies past the frontier is anyone's (0 x NaN is NaN)
        vt = jnp.where(keep, vt, jnp.zeros_like(vt))
        acc_scr[...] = acc * alpha + jax.lax.dot_general(
            p2.astype(vt.dtype), vt, (((2,), (2,)), ((0,), (0,))),
            precision=precision,
            preferred_element_type=jnp.float32)          # [hb, 8, dv]

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        acc, g = acc_scr[...], group
        o_ref[0] = ((acc[:, 0:g, :] + acc[:, g:2 * g, :]) /
                    jnp.maximum(l_scr[:, 0:g, :], 1e-30)).astype(o_ref.dtype)


def _tile_copies(ko_ref, vo_ref, k_tile, v_tile, sems, r=0, head=0, lane=0):
    """The two DMAs that send a row's K and V tiles from their scratches
    to the caches at ``[r, head:, :, lane:]`` (a wait needs only a copy's
    size: the defaults do)."""
    hb, tile = k_tile.shape[0], k_tile.shape[2]
    return [pltpu.make_async_copy(
        src, dst.at[r, pl.ds(head, hb), :, pl.ds(lane, tile)], sems.at[i])
        for i, (src, dst) in enumerate(((k_tile, ko_ref), (v_tile, vo_ref)))]


def _decode_write_tile(wpos_ref, k_ref, v_ref, kn_ref, vn_ref, ko_ref, vo_ref,
                       k_tile, v_tile, sems, pending, *, block_k):
    """The write of :func:`_decode_kernel`, on the step whose block holds
    row ``r``'s write position ``wpos[r]`` (-1: the row writes nothing):
    the lane of the position takes the new entry in the K and V blocks as
    they lie in VMEM, so the scores see it, and the 128-lane tile round it
    is copied to a scratch and sent to the cache. A copy is waited for
    before the scratches are written again, and at the last step of all."""
    r, g, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    w = wpos_ref[r]
    hb, tile = k_tile.shape[0], k_tile.shape[2]

    def wait():
        for c in _tile_copies(ko_ref, vo_ref, k_tile, v_tile, sems):
            c.wait()

    @pl.when((r == 0) & (g == 0) & (ki == 0))
    def _():
        pending[0] = 0

    @pl.when((w >= 0) & (ki == w // block_k))
    def _():
        pl.when(pending[0] != 0)(wait)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile), 2)
        put = lane == w % tile
        for t in range(block_k // tile):  # the tile that holds the lane
            @pl.when((w % block_k) // tile == t)
            def _():
                at = slice(t * tile, (t + 1) * tile)
                for blk, new, scr in ((k_ref, kn_ref, k_tile),
                                      (v_ref, vn_ref, v_tile)):
                    # the entry arrives as a row [hb, 1, d] and goes in
                    # as a column (d on sublanes, position on lanes)
                    col = jnp.swapaxes(new[0].astype(jnp.float32), 1, 2)
                    val = jnp.where(put, col.astype(blk.dtype),
                                    blk[0, :, :, at])
                    scr[...] = val
                    blk[0, :, :, at] = val

        for c in _tile_copies(ko_ref, vo_ref, k_tile, v_tile, sems, r,
                              g * hb, pl.multiple_of(w // tile * tile, tile)):
            c.start()
        pending[0] = 1

    pl.when((r == pl.num_programs(0) - 1) & (g == pl.num_programs(1) - 1)
            & (ki == pl.num_programs(2) - 1) & (pending[0] != 0))(wait)


def flash_decode_attention(
    q: jax.Array,           # [b, h, 1, d]
    k: jax.Array,           # [b, h_kv, L, d]
    v: jax.Array,           # [b, h_kv, L, dv]
    start_pos: jax.Array,   # [b] int32
    scale: Optional[float] = None,
    block_k: int = _DECODE_BLOCK_K,
    interpret: Optional[bool] = None,
    new: Optional[Tuple[jax.Array, jax.Array]] = None,
    write_mask: Optional[jax.Array] = None,
):
    """Pallas single-query-block decode attention (same contract as
    :func:`decode_attention_reference` with ``tq == 1``). K and V go to
    the kernel as ``[b, h_kv, d, L]``: for a cache the chip keeps
    position-minor that transpose is a relabelling, not a copy. A grid
    step takes ``block_k`` entries of every K/V head of a row (of a divisor
    of the heads where a block of all of them would crowd VMEM), and only
    the blocks that a row's position makes valid are moved
    (:func:`decode_fetched_entries`). With ``h = group x h_kv`` (grouped
    queries, ``group`` 2 or 4) the ``group`` query heads of a K/V head are
    rows of one product: the cache is read once for them, never repeated.

    ``new = (k_new, v_new)`` (``[b, h_kv, 1, d]``) makes the call the
    step's cache write too, and it returns ``(out, k, v)``: each row that
    ``write_mask`` (``[b]`` bool; None keeps every row) keeps writes its
    entries at its position (clamped to the cache, as
    :func:`masked_cache_write_reference`) and attends them; every other
    entry stays as it was. The caches are aliased to the two outputs and
    only the 128-position tile that holds a kept row's position goes back
    (:func:`decode_write_fuses` says where that fits: ``L`` a multiple of
    128 and of the block). A row at a negative position attends nothing
    and writes nothing."""
    if q.shape[2] != 1:
        raise ValueError("flash_decode_attention is the tq=1 kernel; use "
                         "decode_attention for multi-row queries")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, hq, _, d = q.shape
    h, L, dv = k.shape[1], k.shape[2], v.shape[3]
    group = hq // h
    if hq != group * h or group not in (1, 2, 4):
        raise ValueError(
            f"flash_decode_attention: {hq} query heads over {h} K/V heads "
            "(a group of 1, 2 or 4 fits the kernel's eight sublanes)")
    count_kv("full")
    if group > 1:  # the group's queries, twice over, as the eight sublanes
        q = jnp.tile(q.reshape(b, h, group, d), (1, 1, 8 // group, 1))
    rows = 8 if group > 1 else 1
    block_k = min(block_k, max(L, 1))
    kp = jnp.swapaxes(_pad_to(k, 2, block_k), 2, 3)
    vp = jnp.swapaxes(_pad_to(v, 2, block_k), 2, 3)
    # heads a step: all, unless a K and a V block of them would pass 4 MiB
    # (each is held twice)
    hb = max(g for g in range(1, h + 1) if h % g == 0 and (
        g == 1 or g * (d + dv) * block_k * k.dtype.itemsize <= 4 << 20))
    groups = h // hb
    lengths = jnp.maximum(start_pos.astype(jnp.int32) + 1, 0)  # [b]

    def kv_block(r, g, ki, lens, *_):
        """A step the row's length leaves dead names the block that the
        next row (or head group) starts with, which so comes in under this
        row's arithmetic and dead steps and is there when its own step
        names it again; the last row of all stays on its own last block.
        Nothing is fetched twice, and no block past a row's length."""
        live = ki * block_k < lens[r]
        nxt = r * groups + g + 1
        stay = live | (nxt == b * groups)
        own_last = decode_fetched_entries(lens[r], L, block_k) // block_k - 1
        return (jnp.where(stay, r, nxt // groups),
                jnp.where(stay, g, nxt % groups), 0,
                jnp.where(live, ki, jnp.where(stay, own_last, 0)))

    def row_block(r, g, ki, lens, *_):
        return (r, g, 0, 0)

    kern = functools.partial(
        _decode_kernel, scale=float(scale), block_k=block_k,
        precision=(jax.lax.Precision.HIGHEST if k.dtype == jnp.float32
                   else None), group=group)
    kw = dict(memory_space=pltpu.VMEM)
    if new is not None:
        return _flash_decode_write(
            kern, q, kp, vp, new, write_mask, lengths, start_pos, block_k,
            hb, rows, group, interpret, (row_block, kv_block))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, groups, kp.shape[3] // block_k),
            in_specs=[
                pl.BlockSpec((1, hb, rows, d), row_block, **kw),
                pl.BlockSpec((1, hb, d, block_k), kv_block, **kw),
                pl.BlockSpec((1, hb, dv, block_k), kv_block, **kw),
            ],
            out_specs=pl.BlockSpec((1, hb, group, dv), row_block, **kw),
            scratch_shapes=[
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, group, dv), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(lengths, q, kp, vp)
    return out.reshape(b, hq, 1, dv)


#: positions of a row that the fused write sends back: one lane tile
_WRITE_TILE = 128


def _write_fits(max_len: int, block_k: int = _DECODE_BLOCK_K) -> bool:
    """Whether :func:`flash_decode_attention` can take a cache of
    ``max_len`` positions as it lies and write into it: whole blocks and
    whole 128-lane tiles, no pad (a padded copy could not be aliased)."""
    bk = min(block_k, max(max_len, 1))
    return max_len % bk == 0 and bk % _WRITE_TILE == 0


def _flash_decode_write(kern, q, kp, vp, new, write_mask, lengths, start_pos,
                        block_k, hb, rows, group, interpret, maps):
    """The ``new=`` form of :func:`flash_decode_attention`: the same grid
    and index maps, a second scalar vector (each row's write position, -1
    for a row that writes nothing), the new entries a row's block, and the
    caches aliased to two outputs left in HBM for the tile's DMA."""
    row_block, kv_block = maps
    b, h, d, L = kp.shape
    dv = vp.shape[2]
    if not _write_fits(L, block_k):
        raise ValueError(f"flash_decode_attention: a write into a cache of "
                         f"{L} positions needs no pad (decode_write_fuses)")
    count_kv("fused", 2)
    keep = (jnp.ones((b,), bool) if write_mask is None
            else write_mask.astype(bool))
    pos = start_pos.astype(jnp.int32)
    wpos = jnp.where(keep & (pos >= 0), jnp.minimum(pos, L - 1), -1)
    # the new entries as they come, [b, h, 1, d]: a [.., d, 1] plane
    # would lie padded to 128 lanes, a copy of 12.6 MB a plane in GPT-2
    kn, vn = (a.astype(c.dtype) for a, c in zip(new, (kp, vp)))
    kw = dict(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, ko, vo = pl.pallas_call(
        functools.partial(kern, write=True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // hb, L // block_k),
            in_specs=[
                pl.BlockSpec((1, hb, rows, d), row_block, **kw),
                pl.BlockSpec((1, hb, d, block_k), kv_block, **kw),
                pl.BlockSpec((1, hb, dv, block_k), kv_block, **kw),
                pl.BlockSpec((1, hb, 1, d), row_block, **kw),
                pl.BlockSpec((1, hb, 1, dv), row_block, **kw),
            ],
            out_specs=[pl.BlockSpec((1, hb, group, dv), row_block, **kw),
                       hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, 1), jnp.float32),
                pltpu.VMEM((hb, 8, dv), jnp.float32),
                pltpu.VMEM((hb, d, _WRITE_TILE), kp.dtype),
                pltpu.VMEM((hb, dv, _WRITE_TILE), vp.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, group, dv), q.dtype),
                   jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        # operands: lengths, wpos, q, k, v, k_new, v_new
        input_output_aliases={3: 1, 4: 2},
        interpret=interpret,
        name="flash_decode",
    )(lengths, wpos, q, kp, vp, kn, vn)
    return (out.reshape(b, h * group, 1, dv), jnp.swapaxes(ko, 2, 3),
            jnp.swapaxes(vo, 2, 3))


# ---------------------------------------------------------------------------
# KV-cache write of a fused batch decode step (idle rows masked off)
# ---------------------------------------------------------------------------


def masked_cache_write_reference(
    cache: jax.Array,        # [b, h, L, d], or a scale plane [b, h, L]
    new: jax.Array,          # [b, h, t, d], or [b, h, t]
    pos: jax.Array,          # [b] int32
    write_mask: jax.Array,   # [b] bool
) -> jax.Array:
    """Builtin XLA spelling: write ``new`` into the static-shape cache at
    per-row positions ``pos + [0, t)`` for the rows ``write_mask`` keeps,
    and leave the other rows of the cache as they were. It is the scatter
    that the layers' vmapped ``dynamic_update_slice`` becomes, with one
    difference: a row that is masked off gets a position past the cache,
    and the scatter drops an update that is out of range. A row that
    writes is clamped as ``dynamic_update_slice`` clamps it. Nothing of
    the cache is read or selected: the cost stays that of the positions
    written."""
    L, t = cache.shape[2], new.shape[2]
    p = jnp.where(write_mask, jnp.clip(pos.astype(jnp.int32), 0, L - t), L)
    z = jnp.zeros_like(p)
    idx = jnp.stack([z, p] + [z] * (cache.ndim - 3), axis=1)  # [b, ndim-1]
    dims = tuple(range(1, cache.ndim))
    return jax.lax.scatter(
        cache, idx, new.astype(cache.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=dims, inserted_window_dims=(),
            scatter_dims_to_operand_dims=dims, operand_batching_dims=(0,),
            scatter_indices_batching_dims=(0,)),
        indices_are_sorted=True, unique_indices=True,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _cache_write_kernel(pos_ref, keep_ref, new_ref, cache_ref, out_ref, *,
                        block):
    """One row of the batch: the ``block`` positions round the row's own
    come in position-minor, the lane at the row's position takes the new
    entry (every lane stays as it was for a row that is masked off), and
    the block goes back where it came from."""
    r = pl.program_id(0)
    c = cache_ref[0]                                # [h, d, block] / [h, block]
    wide = (jnp.float32 if jnp.issubdtype(c.dtype, jnp.floating)
            else jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, c.ndim - 1)
    hit = (lane == pos_ref[r] % block) & (keep_ref[r] != 0)
    out_ref[0] = jnp.where(hit, new_ref[0].astype(wide),
                           c.astype(wide)).astype(c.dtype)


def _cache_write_rows_kernel(pos_ref, keep_ref, new_ref, cache_ref, out_ref,
                             *, block):
    """:func:`_cache_write_kernel` for a plane that lies head-dimension-
    minor: the ``block`` positions round the row's own come in as
    ``[h, block, d]`` and the sublane at the row's position takes the new
    entry."""
    r = pl.program_id(0)
    c = cache_ref[0]                                # [h, block, d]
    wide = (jnp.float32 if jnp.issubdtype(c.dtype, jnp.floating)
            else jnp.int32)
    row = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    hit = (row == pos_ref[r] % block) & (keep_ref[r] != 0)
    out_ref[0] = jnp.where(hit, new_ref[0].astype(wide),
                           c.astype(wide)).astype(c.dtype)


def flash_masked_cache_write(
    cache: jax.Array,
    new: jax.Array,
    pos: jax.Array,
    write_mask: jax.Array,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas one-token cache write (same contract as
    :func:`masked_cache_write_reference` with ``t == 1``), in place: the
    cache goes through the kernel as the chip keeps it, aliased to its
    output, and each grid step moves only the block of positions that
    holds its row's position. A ``[b, h, L, d]`` plane whose ``d`` is
    narrower than the 128 lanes the chip keeps position-minor, so it goes
    as ``[b, h, d, L]`` (a relabelling, not a copy) in blocks of 128
    positions; one whose ``d`` fills the lanes lies as it is written and
    goes in blocks of 32. XLA's scatter does the same write as a loop of
    one small update a row, seven operations each."""
    if new.shape[2] != 1:
        raise ValueError("flash_masked_cache_write is the t=1 kernel")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, L = cache.shape[0], cache.shape[2]
    p = jnp.clip(pos.astype(jnp.int32), 0, L - 1)
    keep = write_mask.astype(jnp.int32)
    if cache.ndim == 4 and cache.shape[3] % 128 == 0:
        h, d = cache.shape[1], cache.shape[3]
        block = 32 if L % 32 == 0 else L
        kw = dict(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            functools.partial(_cache_write_rows_kernel, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, h, 1, d),
                                 lambda r, pos, keep: (r, 0, 0, 0), **kw),
                    pl.BlockSpec((1, h, block, d),
                                 lambda r, pos, keep: (r, 0, pos[r] // block,
                                                       0), **kw),
                ],
                out_specs=pl.BlockSpec(
                    (1, h, block, d),
                    lambda r, pos, keep: (r, 0, pos[r] // block, 0), **kw),
            ),
            out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
            input_output_aliases={3: 0},  # operands: pos, keep, new, cache
            interpret=interpret,
            name="kv_cache_write",
        )(p, keep, new.astype(cache.dtype), cache)
    block = 128 if L % 128 == 0 else L
    planes = cache.ndim == 4
    if planes:  # [b, h, L, d] -> [b, h, d, L]; new [b, h, 1, d] -> [b, h, d, 1]
        cache, new = jnp.swapaxes(cache, 2, 3), jnp.swapaxes(new, 2, 3)
    lead = cache.shape[1:-1]
    zeros = (0,) * len(lead)
    kw = dict(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_cache_write_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1,) + lead + (1,),
                             lambda r, pos, keep: (r,) + zeros + (0,), **kw),
                pl.BlockSpec((1,) + lead + (block,),
                             lambda r, pos, keep: (r,) + zeros
                             + (pos[r] // block,), **kw),
            ],
            out_specs=pl.BlockSpec((1,) + lead + (block,),
                                   lambda r, pos, keep: (r,) + zeros
                                   + (pos[r] // block,), **kw),
        ),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={3: 0},  # operands: pos, keep, new, cache
        interpret=interpret,
        name="kv_cache_write",
    )(p, keep, new.astype(cache.dtype), cache)
    return jnp.swapaxes(out, 2, 3) if planes else out


_KV_TALLIES = threading.local()


@contextlib.contextmanager
def kv_tally():
    """Count what the program traced inside the block does with its cache
    planes: the planes it writes, by how (``"fused"``: the decode kernel's
    own write of K and V; ``"separate"``: a write of a plane of its own),
    and the calls of a decode kernel that read them, by its grid
    (``"live"``: it visits a row's live blocks only; ``"full"``: its grid
    spans the cache). Tracing is where the count is made: a compiled
    program is not counted again."""
    tally = {"fused": 0, "separate": 0, "live": 0, "full": 0}
    stack = _KV_TALLIES.__dict__.setdefault("open", [])
    stack.append(tally)
    try:
        yield tally
    finally:
        stack.pop()


def count_kv(key: str, n: int = 1) -> None:
    """Add ``n`` to ``key`` (see :func:`kv_tally`) of every tally open on
    this thread."""
    for tally in getattr(_KV_TALLIES, "open", ()):
        tally[key] += n


def _decode_impl() -> str:
    """The selected implementation, "auto" resolved: flash on TPU."""
    if _IMPL == "auto":
        return "flash" if jax.default_backend() == "tpu" else "xla"
    return _IMPL


def masked_cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array,
                       write_mask: jax.Array) -> jax.Array:
    """Helper-seam dispatch for the cache write of a fused batch decode
    step (mirrors :func:`decode_attention`): the Pallas in-place kernel
    when "flash" is selected (or automatically on TPU) and one token is
    written, the builtin scatter otherwise."""
    count_kv("separate")
    if _decode_impl() == "flash" and new.shape[2] == 1:
        return flash_masked_cache_write(cache, new, pos, write_mask)
    return masked_cache_write_reference(cache, new, pos, write_mask)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    start_pos: jax.Array,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Helper-seam dispatch for KV-cache decode attention (mirrors
    :func:`mha_attention`): the Pallas single-query kernel when "flash" is
    selected (or automatically on TPU) and the single-row query fits it,
    the builtin XLA spelling otherwise. ``set_attention_impl`` switches
    every decode step in the process, so flash-vs-reference parity checks
    run the same model code both ways.

    ``k_scale``/``v_scale`` ([b, h, L] f32, per-slot/per-head) mark an
    int8-quantized cache: the dequant (``cache * scale``) happens here,
    inside the reference path, where XLA fuses it into the score/value
    matmuls — the cache itself stays int8 in HBM (the capacity win). The
    Pallas kernel is fp-only, so quantized caches always take the
    reference spelling."""
    if k_scale is not None or v_scale is not None:
        if k_scale is not None:
            k = k.astype(q.dtype) * k_scale[..., None].astype(q.dtype)
        if v_scale is not None:
            v = v.astype(q.dtype) * v_scale[..., None].astype(q.dtype)
        return decode_attention_reference(q, k, v, start_pos, scale=scale)
    if _decode_impl() == "flash" and q.shape[2] == 1:
        return flash_decode_attention(q, k, v, start_pos, scale=scale)
    return decode_attention_reference(q, k, v, start_pos, scale=scale)


def decode_write_fuses(q: jax.Array, cache: jax.Array) -> bool:
    """Whether a decode step's write into the static cache ``cache``
    (``[b, h_kv, L, d]``) and its attention over it are one call of
    :func:`flash_decode_attention` (``new=``): the Pallas kernel selected
    (or automatically on TPU), one query row, a floating-point plane, and
    ``L`` that needs no pad. Everything else writes with
    :func:`masked_cache_write` and attends with :func:`decode_attention`."""
    return (_decode_impl() == "flash" and q.shape[2] == 1
            and jnp.issubdtype(cache.dtype, jnp.floating)
            and _write_fits(cache.shape[2]))


def mha_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Dispatch through the helper seam: builtin XLA path by default, the
    Pallas flash kernel when selected (or automatically on TPU for sequences
    long enough that materialising q·kᵀ matters)."""
    impl = _IMPL
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        # Gate on the larger of tq/tk: the materialised score matrix is
        # tq×tk, so long keys with few queries (LearnedSelfAttention) also
        # benefit from k/v streaming.
        impl = ("flash" if (on_tpu and max(q.shape[2], k.shape[2]) >= 512)
                else "xla")
    if impl == "flash":
        return flash_attention(q, k, v, mask=mask, causal=causal, scale=scale)
    return mha_attention_reference(q, k, v, mask=mask, causal=causal,
                                   scale=scale)
