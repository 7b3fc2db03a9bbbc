"""The gated memory unit (SambaY, Ren et al. 2025): a mixer that attends
nothing and re-reads a MEMORY, another layer's output at the same
positions, gated by its own input:

    GMU(u; m) = W2 (m * silu(W1 u))        (W1: n_in -> d_memory, W2 back)

In a SambaY cross-decoder ``m`` is the last Mamba layer's scan output
(``mamba.py``, ``mix(..., tap=True)``), handed over by the layer that holds
both (``cross_decoder.py``). No bias, no decode state: the memory of the
step's own position is recomputed with it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import Layer, Params, State

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class GatedMemoryLayer(Layer):
    """A block's mixer part; called through ``mix(..., memory=m)``. Matmul
    operands take the parameters' type, the gate float32."""

    n_in: int = 0
    d_memory: int = 0

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "GatedMemoryLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.d_memory:
            out = dataclasses.replace(out, d_memory=2 * out.n_in)
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("W1", "W2")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, m = self.n_in, self.d_memory
        k1, k2 = jax.random.split(key)
        return {"W1": init_weights(k1, (h, m), wi, h, m, None, dtype),
                "W2": init_weights(k2, (m, h), wi, m, h, None, dtype)}

    def mix(self, params: Params, state: State, x: jax.Array, mask, *,
            memory: jax.Array) -> Tuple[jax.Array, State]:
        """x ``[b, t, n_in]`` in the parameters' type, ``memory [b, t,
        d_memory]`` -> ``(GMU(x; memory) [b, t, n_in], state)``."""
        with jax.named_scope("gmu"):
            g = jax.nn.silu(jnp.dot(x, params["W1"],
                                    preferred_element_type=_F32))
            return jnp.dot((memory.astype(_F32) * g).astype(x.dtype),
                           params["W2"]), state
