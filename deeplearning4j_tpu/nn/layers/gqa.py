"""Grouped-query attention with QK-norm and rotary positions (the attention
mixer of LFM2, Qwen3 and their kin): ``n_heads`` query heads read
``n_kv_heads`` key/value heads, query head ``h`` the pair ``h // (n_heads /
n_kv_heads)``, so the decode state is ``n_kv_heads`` heads a position, not
``n_heads``.

    q = Wq x (n_heads x d),  k = Wk x,  v = Wv x (n_kv_heads x d each)
    q_h <- N(q_h; gq),  k_g <- N(k_g; gk)    RMSNorm over the d numbers of a
                                              head, one gain for all heads
    both rotated (rotate-half over the whole head, positions from 0)
    scores q . k d^-1/2, causal softmax;  o = Wo [n_heads x d]

The mixer OWNS its decode state: ``cache_k`` (after norm and rotation) and
``cache_v``, ``[b, n_kv_heads, max_len, d]``, declared as planes written in
place and as pageable K/V caches. A one-token call (a decode STEP) writes
its entry and attends the planes where they lie: the single-query kernel
takes the group's query heads as rows of one product, so a K/V block is
read once for them and nothing repeats the cache
(``ops/flash_attention.py``). A multi-token call on the static layout is a
PREFILL of fresh rows: the tokens attend each other causally (the K/V heads
repeated for the length of the call only) and their entries are written
from the rows' positions on.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .attention import (KV_PLANES, _cached_attention, _merge_heads,
                        _split_heads)
from .base import Layer, LayerContext, Params, State, apply_input_dropout
from .eva import rotary_positions
from .norm import rms_norm


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class GroupedQueryAttentionLayer(Layer):
    """``Attn(x)`` as a sequential layer (input/output ``[b, n_in, t]``; no
    norm before it and no residual round it: a block adds those). Matmul
    operands take the parameters' type, the norms' statistics, the rotation
    and the softmax float32.

    A multi-token call with a static decode state is a prefill of rows that
    stand at their state's position with nothing before it that they attend
    (a fresh row, position 0); on a paged or int8 cache it attends the
    cache itself, as ``SelfAttentionLayer`` does."""

    n_in: int = 0
    n_heads: int = 1
    n_kv_heads: int = 1
    rope_theta: float = 1e6
    eps: float = 1e-5

    pages_decode_planes = True

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads do not divide "
                             f"over {self.n_kv_heads} K/V heads")

    @property
    def head(self) -> int:
        return self.n_in // self.n_heads

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "GroupedQueryAttentionLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wq", "Wk", "Wv", "Wo", "gq", "gk")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Wq", "Wk", "Wv", "Wo")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, d = self.n_in, self.head
        ks = jax.random.split(key, 4)

        def mat(k, rows, cols):
            return init_weights(k, (rows, cols), wi, rows, cols, None, dtype)

        return {"Wq": mat(ks[0], h, self.n_heads * d),
                "Wk": mat(ks[1], h, self.n_kv_heads * d),
                "Wv": mat(ks[2], h, self.n_kv_heads * d),
                "Wo": mat(ks[3], self.n_heads * d, h),
                "gq": jnp.ones((d,), dtype), "gk": jnp.ones((d,), dtype)}

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        shape = (batch, self.n_kv_heads, max_len, self.head)
        return {"cache_k": jnp.zeros(shape, dtype),
                "cache_v": jnp.zeros(shape, dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def decode_planes(self) -> Tuple[str, ...]:
        return KV_PLANES

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {"kv": position * 2 * self.n_kv_heads * self.head * itemsize}

    # ---- the mixer ----------------------------------------------------------
    def _projections(self, params: Params, x: jax.Array, at: jax.Array):
        """x ``[b, t, n_in]`` at positions ``at`` (``[t]`` or ``[b, t]``) ->
        ``(q [b, n_heads, t, d], k, v [b, n_kv_heads, t, d])``, q and k
        normed and rotated."""
        cd = x.dtype
        q = _split_heads(x @ params["Wq"], self.n_heads)
        k = _split_heads(x @ params["Wk"], self.n_kv_heads)
        v = _split_heads(x @ params["Wv"], self.n_kv_heads)
        q = rms_norm(q, params["gq"], self.eps)
        k = rms_norm(k, params["gk"], self.eps)
        return (rotary_positions(q, at, self.rope_theta).astype(cd),
                rotary_positions(k, at, self.rope_theta).astype(cd), v)

    def _fresh(self, q, k, v, mask):
        """The tokens of one call attend each other causally, the K/V heads
        repeated for the call -> ``[b, n_heads, t, d]``."""
        from ...ops import mha_attention

        g = self.n_heads // self.n_kv_heads
        if g > 1:
            k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        return mha_attention(q, k, v, mask=mask, causal=True,
                             scale=self.head ** -0.5)

    @jax.named_scope("gqa_attn")
    def mix(self, params: Params, state: State, x: jax.Array,
            mask) -> Tuple[jax.Array, State]:
        """x ``[b, t, n_in]`` in the parameters' type -> ``(Attn(x) [b, t,
        n_in], the new state)``; ``state`` may be empty (a whole sequence
        from position 0, no cache)."""
        from ...ops import masked_cache_write

        b, t, _ = x.shape
        if "cache_k" not in state:
            q, k, v = self._projections(
                params, x, jnp.arange(t, dtype=jnp.int32))
            return _merge_heads(self._fresh(q, k, v, mask)) @ params["Wo"], \
                state
        pos = state["pos"].astype(jnp.int32)
        at = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q, k, v = self._projections(params, x, at)
        if t == 1 or "block_table" in state or "cache_k_scale" in state:
            o, new = _cached_attention(q, k, v, state, mask)
        else:
            keep = state.get("write_mask")
            if keep is None:
                keep = jnp.ones(pos.shape, bool)
            valid = (jnp.full((b,), t, jnp.int32) if mask is None
                     else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
            new = {"cache_k": masked_cache_write(state["cache_k"], k, pos,
                                                 keep),
                   "cache_v": masked_cache_write(state["cache_v"], v, pos,
                                                 keep),
                   "pos": pos + valid}
            o = self._fresh(q, k, v, mask)
        return _merge_heads(o) @ params["Wo"], new

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1).astype(params["Wq"].dtype)
        y, new_state = self.mix(params, state, xt, ctx.mask)
        return y.astype(x.dtype).transpose(0, 2, 1), new_state
