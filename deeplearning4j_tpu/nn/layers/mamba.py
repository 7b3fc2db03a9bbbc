"""The Mamba mixer (Mamba-1, Gu & Dao 2023, arXiv:2312.00752): a selective
state-space scan between a causal depthwise convolution and a gate, in
place of attention.

    [xs; z] = Win u                         (two blocks of d_inner)
    xs      = silu(conv(xs) + bc)           (depthwise, d_conv taps, causal)
    [r; B; C] = Wx xs                       (dt_rank, d_state, d_state)
    dt      = softplus(Wdt r + bdt),  A = -exp(A_log)   [d_inner, d_state]
    s_t     = exp(dt_t A) * s_{t-1} + (dt_t xs_t) B_t^T
    y_t     = s_t C_t + D * xs_t
    Mix(u)  = Wout (y * silu(z))

No other bias. The mixer OWNS its decode state, and it is not indexed by
position: ``ssm`` float32 ``[b, d_state, d_inner]`` (the scan's state, kept
``d_inner``-minor: a minor dimension of ``d_state`` = 16 would be padded to
the chip's 128 lanes) and ``conv`` ``[b, d_conv - 1, d_inner]``, the
convolution's rolling columns (``short_conv.rolling_conv``). A right-padded
prefill hands both over at each row's TRUE length: the convolution's
columns by the mask's count, and the scan because ``dt`` is 0 at a pad,
where ``exp(0 A) = 1`` and nothing is added, so the state passes the pad
unchanged. The scan never holds more than one position's ``[b, d_state,
d_inner]``: on a TPU a prompt runs through ``ops.selective_scan``'s kernel
(the state in registers, 128 positions a grid step), a decode step is one
position without a loop.

``mix(..., tap=True)`` also gives ``y`` (before the gate ``silu(z)``): the
memory that a cross-decoder's gated memory units read
(``cross_decoder.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ...ops.selective_scan import selective_scan
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import Layer, LayerContext, Params, State, apply_input_dropout
from .short_conv import rolling_conv

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class MambaMixerLayer(Layer):
    """``Mix(x)`` as a sequential layer (input/output ``[b, n_in, t]``; no
    norm before it and no residual round it: a block adds those). Matmul
    operands take the parameters' type; the convolution's input is rounded
    to it once, where the state keeps it; the scan, ``dt``, the gate and the
    skip are float32."""

    n_in: int = 0
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "MambaMixerLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.d_inner:
            out = dataclasses.replace(out, d_inner=2 * out.n_in)
        if not out.dt_rank:
            out = dataclasses.replace(out, dt_rank=-(-out.n_in // 16))
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Win", "Wc", "bc", "Wx", "Wdt", "bdt", "A_log", "D", "Wout")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Win", "Wx", "Wdt", "Wout")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, di, n, r = self.n_in, self.d_inner, self.d_state, self.dt_rank
        ks = jax.random.split(key, 5)

        def mat(k, rows, cols):
            return init_weights(k, (rows, cols), wi, rows, cols, None, dtype)

        return {
            "Win": mat(ks[0], h, 2 * di),
            "Wc": (jax.random.normal(ks[1], (di, self.d_conv), _F32)
                   * self.d_conv ** -0.5).astype(dtype),
            "bc": jnp.zeros((di,), dtype),
            "Wx": mat(ks[2], di, r + 2 * n),
            "Wdt": mat(ks[3], r, di),
            # dt = softplus(bdt) = 0.01 at a zero input
            "bdt": jnp.full((di,), -4.6, dtype),
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=_F32), (di, n))).astype(dtype),
            "D": jnp.ones((di,), dtype),
            "Wout": mat(ks[4], di, h),
        }

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        return {"ssm": jnp.zeros((batch, self.d_state, self.d_inner), _F32),
                "conv": jnp.zeros((batch, self.d_conv - 1, self.d_inner),
                                  dtype)}

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {"ssm": self.d_state * self.d_inner * 4
                + (self.d_conv - 1) * self.d_inner * itemsize}

    # ---- the mixer ----------------------------------------------------------
    def mix(self, params: Params, state: State, x: jax.Array, mask,
            tap: bool = False):
        """x ``[b, t, n_in]`` in the parameters' type -> ``(Mix(x) [b, t,
        n_in], the new state)``, and with ``tap`` also ``y`` ``[b, t,
        d_inner]`` float32; ``state`` may be empty (a whole sequence from
        position 0)."""
        with jax.named_scope("mamba"):
            return self._mix(params, state, x, mask, tap)

    def _mix(self, params, state, x, mask, tap):
        b, t, _ = x.shape
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        cd = x.dtype
        xz = jnp.dot(x, params["Win"], preferred_element_type=_F32)
        conv, new_conv = rolling_conv(xz[..., :di].astype(cd),
                                      state.get("conv"), params["Wc"], mask)
        xs = jax.nn.silu(conv + params["bc"].astype(_F32))
        rbc = jnp.dot(xs.astype(cd), params["Wx"], preferred_element_type=_F32)
        dt = jax.nn.softplus(
            jnp.dot(rbc[..., :r].astype(cd), params["Wdt"],
                    preferred_element_type=_F32)
            + params["bdt"].astype(_F32))
        if mask is not None:  # a pad leaves the state as it found it
            dt = dt * (mask > 0)[..., None].astype(_F32)
        s0 = state["ssm"] if "ssm" in state else jnp.zeros((b, n, di), _F32)
        y, s = selective_scan(xs, dt, -jnp.exp(params["A_log"].astype(_F32)),
                              rbc[..., r:r + n], rbc[..., r + n:], s0)
        y = y + params["D"].astype(_F32) * xs
        z = xz[..., di:]
        o = jnp.dot((y * jax.nn.silu(z)).astype(cd), params["Wout"])
        new = state
        if "ssm" in state:
            new = {**state, "ssm": s,
                   "conv": new_conv.astype(state["conv"].dtype)}
        return (o, new, y) if tap else (o, new)

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1).astype(params["Win"].dtype)
        y, new_state = self.mix(params, state, xt, ctx.mask)
        return y.astype(x.dtype).transpose(0, 2, 1), new_state
