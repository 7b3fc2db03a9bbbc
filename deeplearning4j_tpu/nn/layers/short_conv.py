"""Gated short convolution (LFM2's convolution mixer; LiquidAI 2025): a
causal depthwise convolution of a few taps over positions between two
multiplicative gates, in place of attention.

    [B; C; X] = Win u            (three blocks of n_in)
    z_t       = sum_j Wc[:, j] * (B * X)_{t - (L - 1) + j},  j = 0 .. L - 1
    y_t       = Wout (C_t * z_t)

No activation, no bias; left of position 0 the convolution reads zeros. The
mixer OWNS its decode state, and it is not indexed by position: ``conv``
``[b, L - 1, n_in]``, the last ``L - 1`` columns of ``B * X`` of every row,
whatever the row's position. No plane: a per-row leaf that takes the row
select of ``freeze_rows`` and the install's ``write_row`` as a recurrent
layer's ``(h, c)`` do. A call reads the state's columns to the left of its
first token, so a decode step is one column in and one out, and a
right-padded prefill hands over the columns at each row's TRUE length (the
mask's count), not at the bucket's end: :func:`rolling_conv`, which the
Mamba mixer's convolution calls too.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import Layer, LayerContext, Params, State, apply_input_dropout

_F32 = jnp.float32


def rolling_conv(x: jax.Array, state, w: jax.Array, mask):
    """The causal depthwise convolution of ``x [b, t, c]`` by ``w [c, L]``
    (tap ``L - 1`` on the current position) -> ``(z [b, t, c] float32, the
    new state or None)``. ``state`` is the last ``L - 1`` columns of the
    row before the call, ``[b, L - 1, c]``, or None (a whole sequence from
    position 0: zeros on the left, and no state handed on). The new state
    is the ``L - 1`` columns left of each row's first pad (``mask [b, t]``;
    None: every token is real) in ``x``'s type. The taps multiply in
    float32."""
    b, t, c = x.shape
    taps = w.shape[1]
    left = taps - 1
    before = state.astype(x.dtype) if state is not None \
        else jnp.zeros((b, left, c), x.dtype)
    ext = jnp.concatenate([before, x], axis=1)           # [b, left + t, c]
    wc = w.astype(_F32)
    z = sum(wc[:, j] * ext[:, j:j + t].astype(_F32) for j in range(taps))
    if state is None:
        return z, None
    if mask is None:  # every token is real: a step, an unpadded prompt
        return z, ext[:, t:]
    at = jnp.sum(mask > 0, axis=1).astype(jnp.int32)[:, None] \
        + jnp.arange(left, dtype=jnp.int32)[None, :]
    return z, jnp.take_along_axis(ext, at[:, :, None], axis=1)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class ShortConvLayer(Layer):
    """``Conv(x)`` as a sequential layer (input/output ``[b, n_in, t]``; no
    norm before it and no residual round it: a block adds those). Matmul
    operands take the parameters' type; ``B * X`` is rounded to it once,
    where the state keeps it, so a prefill and the steps after it convolve
    the same numbers; the taps and the gate ``C`` multiply in float32."""

    n_in: int = 0
    kernel: int = 3        # L: taps, the current position included

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "ShortConvLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Win", "Wc", "Wout")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h = self.n_in
        k_in, k_c, k_out = jax.random.split(key, 3)
        return {
            "Win": init_weights(k_in, (h, 3 * h), wi, h, 3 * h, None, dtype),
            "Wc": (jax.random.normal(k_c, (h, self.kernel), _F32)
                   * self.kernel ** -0.5).astype(dtype),
            "Wout": init_weights(k_out, (h, h), wi, h, h, None, dtype),
        }

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        return {"conv": jnp.zeros((batch, self.kernel - 1, self.n_in), dtype)}

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {"conv": (self.kernel - 1) * self.n_in * itemsize}

    # ---- the mixer ----------------------------------------------------------
    @jax.named_scope("short_conv")
    def mix(self, params: Params, state: State, x: jax.Array,
            mask) -> Tuple[jax.Array, State]:
        """x ``[b, t, n_in]`` in the parameters' type -> ``(Conv(x) [b, t,
        n_in], the new state)``; ``state`` may be empty (a whole sequence
        from position 0)."""
        h = x.shape[2]
        bcx = jnp.dot(x, params["Win"], preferred_element_type=_F32)
        bx = (bcx[..., :h] * bcx[..., 2 * h:]).astype(x.dtype)
        z, new = rolling_conv(bx, state.get("conv"), params["Wc"], mask)
        y = jnp.dot((bcx[..., h:2 * h] * z).astype(x.dtype), params["Wout"])
        if new is None:
            return y, state
        return y, {**state, "conv": new.astype(state["conv"].dtype)}

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1).astype(params["Win"].dtype)
        y, new_state = self.mix(params, state, xt, ctx.mask)
        return y.astype(x.dtype).transpose(0, 2, 1), new_state
