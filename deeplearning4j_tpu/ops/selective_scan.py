"""The selective scan of a Mamba mixer over a prompt (``nn/layers/mamba.py``):

    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T,   y_t = s_t C_t

``x``, ``dt`` ``[b, t, d_inner]``, ``B``, ``C`` ``[b, t, d_state]``, ``A``
``[d_inner, d_state]``, the state ``[b, d_state, d_inner]``; everything
float32. The recurrence is sequential in ``t``: XLA's ``lax.scan`` runs one
position a loop iteration, a few small operations each, and a loop
iteration costs the chip microseconds whatever it holds (a prompt of 8,192
positions took about 0.2 s a layer that way). The kernel keeps a block of
the state, ``[d_state, block_d]``, in registers and runs 128 positions a
grid step over blocks of ``x``, ``dt``, ``B`` and ``C`` brought to VMEM,
``d_inner`` in parallel blocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import attention_impl

_F32 = jnp.float32
_BLOCK_T = 128
_BLOCK_D = 512


def selective_scan_reference(xs, dt, A, B, C, s0):
    """``lax.scan`` one position a step -> ``(y [b, t, d_inner], the state
    after the last position)``."""
    At = A.T                                             # [d_state, d_inner]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp                        # [b, di], [b, n]
        s = jnp.exp(dt_t[:, None, :] * At) * s \
            + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s, y = jax.lax.scan(step, s0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (xs, dt, B, C)))
    return jnp.swapaxes(y, 0, 1), s


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, s_ref,
                 st, *, block_t):
    """One (row, d-block, t-block) grid step; the t axis is the innermost
    and sequential, the state carried across it in ``st``. Eight positions
    a loop iteration: their rows of ``x`` and ``dt`` come in as one aligned
    ``[8, block_d]`` load and their ``y`` goes out as one; a position's
    column of ``B`` and ``C`` (``[d_state, 1]``) is picked from the
    block's ``[d_state, block_t]`` by a mask and a lane sum."""
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        st[...] = s0_ref[0]

    a = a_ref[...]                                       # [n, bd]
    bb, cc = b_ref[0], c_ref[0]                          # [n, block_t]
    lane = jax.lax.broadcasted_iota(jnp.int32, bb.shape, 1)

    def eight(j, s):
        at = pl.multiple_of(j * 8, 8)
        x8 = x_ref[0, pl.ds(at, 8), :]                   # [8, bd]
        d8 = dt_ref[0, pl.ds(at, 8), :]
        ys = []
        for r in range(8):
            x, d = x8[r:r + 1], d8[r:r + 1]              # [1, bd]
            hit = lane == at + r
            bcol = jnp.sum(jnp.where(hit, bb, 0.0), axis=1, keepdims=True)
            ccol = jnp.sum(jnp.where(hit, cc, 0.0), axis=1, keepdims=True)
            s = jnp.exp(d * a) * s + bcol * (d * x)
            ys.append(jnp.sum(s * ccol, axis=0, keepdims=True))
        y_ref[0, pl.ds(at, 8), :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, block_t // 8, eight, st[...])
    st[...] = s

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = s


def selective_scan_pallas(xs, dt, A, B, C, s0, *, block_t: int = _BLOCK_T,
                          block_d: int = _BLOCK_D,
                          interpret: Optional[bool] = None):
    """The Pallas kernel (same contract as :func:`selective_scan_reference`).
    ``t`` is padded to a multiple of ``block_t`` with ``dt = 0``, which
    leaves the state as it was."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, di = xs.shape
    n = B.shape[2]
    bd = block_d if di % block_d == 0 else di
    tp = -(-t // block_t) * block_t

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, tp - t), (0, 0))) if tp != t else a

    xs, dt = pad(xs.astype(_F32)), pad(dt.astype(_F32))
    bt = jnp.swapaxes(pad(B.astype(_F32)), 1, 2)         # [b, n, tp]
    ct = jnp.swapaxes(pad(C.astype(_F32)), 1, 2)
    kw = dict(memory_space=pltpu.VMEM)
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t),
        grid=(b, di // bd, tp // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, bd), lambda i, j, k: (i, k, j), **kw),
            pl.BlockSpec((1, block_t, bd), lambda i, j, k: (i, k, j), **kw),
            pl.BlockSpec((n, bd), lambda i, j, k: (0, j), **kw),
            pl.BlockSpec((1, n, block_t), lambda i, j, k: (i, 0, k), **kw),
            pl.BlockSpec((1, n, block_t), lambda i, j, k: (i, 0, k), **kw),
            pl.BlockSpec((1, n, bd), lambda i, j, k: (i, 0, j), **kw),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, bd), lambda i, j, k: (i, k, j), **kw),
            pl.BlockSpec((1, n, bd), lambda i, j, k: (i, 0, j), **kw),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, tp, di), _F32),
                   jax.ShapeDtypeStruct((b, n, di), _F32)],
        scratch_shapes=[pltpu.VMEM((n, bd), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(xs, dt, A.T.astype(_F32), bt, ct, s0.astype(_F32))
    return y[:, :t], s


def selective_scan(xs, dt, A, B, C, s0):
    """Helper-seam dispatch (as :func:`~.flash_attention.mha_attention`):
    the kernel for a prompt on a TPU or where "flash" is selected, the
    ``lax.scan`` spelling elsewhere; a one-position call (a decode step) is
    the step itself, no loop."""
    if xs.shape[1] == 1:
        At = A.T
        s = jnp.exp(dt[:, 0, None, :] * At) * s0 \
            + B[:, 0, :, None] * (dt[:, 0] * xs[:, 0])[:, None, :]
        return jnp.sum(s * C[:, 0, :, None], axis=1)[:, None], s
    impl = attention_impl()
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash":
        return selective_scan_pallas(xs, dt, A, B, C, s0)
    return selective_scan_reference(xs, dt, A, B, C, s0)
