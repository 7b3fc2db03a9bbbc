"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as
LongCat-Flash and openPangu-Ultra-MoE use it): low-rank query and key/value
projections, a head split into a non-rotary and a rotary part, and a decode
state that is ONE latent entry a position whatever the number of heads.

    q        = Wqb (N(Wqa x; gq) (n_in/q_lora_rank)^1/2), per head
               qk_nope_head_dim non-rotary then qk_rope_head_dim rotary
    [c; kr]  = Wkva x;  c' = N(c; gkv) (n_in/kv_lora_rank)^1/2;
               rot(kr) one rotary key for all heads
    [k_nope; v] = Wkvb c'  (per head qk_nope_head_dim + v_head_dim)
    scores   = (q_nope.k_nope + rot(q_rope).rot(kr)) (nope + rope)^-1/2
    o        = Wo [heads x v_head_dim]

The two ``(n_in/rank)^1/2`` factors are LongCat's (``lora_scales``); the
DeepSeek-V3 layout (openPangu) has neither.

The mixer OWNS its decode state: ``latent`` ``[b, 1, max_len, kv_lora_rank +
qk_rope_head_dim]``, the entry ``[c'; rot(kr)]`` of every position, declared
as a plane written in place (:meth:`LatentAttentionLayer.decode_planes`), so
the engine's fused step masks an idle row's write and selects over nothing.
Two forms. A multi-token call of fresh rows (a PREFILL, traced inside
:func:`~deeplearning4j_tpu.nn.layers.base.fresh_rows`) expands the fresh
entries to per-head keys and values and attends them causally. Every other
call with a decode state (a decode STEP of one token, or a speculative
VERIFICATION window of several) attends the plane itself: the up-projection
goes into the query (``q~_h = Wkvb_k,h^T q_nope,h``) and the output (``o_h =
Wkvb_v,h sum_t p_t c'_t``), so a call reads one entry a position and not a
key and a value a head, and a window's ``t`` query positions, causal among
themselves, share one read of the plane (``ops/mla_attention.py``:
``mla_decode``, ``mla_verify``); the plane rewinds by its ``pos`` alone.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .attention import _merge_heads, _split_heads
from .base import (Layer, LayerContext, Params, State, apply_input_dropout,
                   rows_are_fresh)
from .eva import rotary_positions
from .norm import rms_norm

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LatentAttentionLayer(Layer):
    """``MLA(x)`` as a sequential layer (input/output ``[b, n_in, t]``; no
    norm before it and no residual round it: a block adds those). Matmul
    operands take the parameters' type, the norms' statistics and the
    softmax float32.

    A multi-token call with a decode state inside ``fresh_rows`` is a
    prefill of rows that stand at their state's position with nothing
    before it that they attend (a fresh row, position 0): the tokens attend
    each other causally, and their entries are written from the rows'
    positions on. Any other multi-token call is a window over the filled
    plane, whatever its length: its entries are written first, then each of
    its queries attends the plane up to its own position."""

    n_in: int = 0
    n_heads: int = 1
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    rope_theta: float = 1e7
    eps: float = 1e-5
    lora_scales: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "LatentAttentionLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wqa", "gq", "Wqb", "Wkva", "gkv", "Wkvb", "Wo")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Wqa", "Wqb", "Wkva", "Wkvb", "Wo")

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, n = self.n_in, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        ks = jax.random.split(key, 5)

        def mat(k, rows, cols):
            return init_weights(k, (rows, cols), wi, rows, cols, None, dtype)

        return {
            "Wqa": mat(ks[0], h, self.q_lora_rank),
            "gq": jnp.ones((self.q_lora_rank,), dtype),
            "Wqb": mat(ks[1], self.q_lora_rank, n * qk),
            "Wkva": mat(ks[2], h, self.latent_width),
            "gkv": jnp.ones((self.kv_lora_rank,), dtype),
            "Wkvb": mat(ks[3], self.kv_lora_rank,
                        n * (self.qk_nope_head_dim + self.v_head_dim)),
            "Wo": mat(ks[4], n * self.v_head_dim, h),
        }

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        return {"latent": jnp.zeros((batch, 1, max_len, self.latent_width),
                                    dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def decode_planes(self) -> Tuple[str, ...]:
        return ("latent",)

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {"latent": position * self.latent_width * itemsize}

    # ---- the mixer ----------------------------------------------------------
    def _projections(self, params: Params, x: jax.Array, at: jax.Array):
        """x ``[b, t, n_in]`` at positions ``at`` (``[t]`` or ``[b, t]``) ->
        ``(q_nope [b, h, t, nope], rot(q_rope) [b, h, t, rope], entries
        [b, t, rank + rope])``, the entries ``[c'; rot(kr)]``."""
        cd = params["Wqa"].dtype
        dn, rkv = self.qk_nope_head_dim, self.kv_lora_rank
        cq = rms_norm(x @ params["Wqa"], params["gq"], self.eps)
        if self.lora_scales:
            cq = cq * math.sqrt(self.n_in / self.q_lora_rank)
        q = _split_heads(cq.astype(cd) @ params["Wqb"], self.n_heads)
        q_rope = rotary_positions(q[..., dn:], at, self.rope_theta)
        ckr = x @ params["Wkva"]
        c = rms_norm(ckr[..., :rkv], params["gkv"], self.eps)
        if self.lora_scales:
            c = c * math.sqrt(self.n_in / rkv)
        kr = rotary_positions(ckr[:, None, :, rkv:], at, self.rope_theta)[:, 0]
        return q[..., :dn], q_rope, jnp.concatenate(
            [c.astype(cd), kr.astype(cd)], axis=-1)

    def _expanded(self, params: Params, q_nope, q_rope, entries, mask):
        """The prefill's form: keys and values a head from the entries
        ``[b, t, rank + rope]``, attended causally -> ``[b, h, t, v]``."""
        from ...ops import mha_attention_reference

        n, dn, rkv = self.n_heads, self.qk_nope_head_dim, self.kv_lora_rank
        kv = _split_heads(entries[..., :rkv] @ params["Wkvb"], n)
        kr = jnp.broadcast_to(entries[:, None, :, rkv:],
                              q_rope.shape[:1] + (n,) + entries.shape[1:2]
                              + q_rope.shape[3:])
        return mha_attention_reference(
            jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([kv[..., :dn], kr], axis=-1), kv[..., dn:],
            mask=mask, causal=True,
            scale=(dn + self.qk_rope_head_dim) ** -0.5)

    def _absorbed(self, params: Params, q_nope, q_rope, plane, lengths):
        """The absorbed form: ``t`` queries a row (a step's one, or a
        verify window's), the last at the plane's ``lengths``, attend the
        plane's entries directly -> ``[b, h, t, v]``, in one kernel call
        (``mla_decode``, or ``mla_verify`` for ``t > 1``)."""
        from ...ops.mla_attention import mla_decode_attention

        n, dn, rkv = self.n_heads, self.qk_nope_head_dim, self.kv_lora_rank
        b, _, t, _ = q_nope.shape
        cd = plane.dtype
        wkvb = params["Wkvb"].reshape(rkv, n, dn + self.v_head_dim)
        qt = jnp.einsum("bhtn,chn->bthc", q_nope, wkvb[..., :dn],
                        preferred_element_type=_F32).astype(cd)
        q = jnp.concatenate([qt, q_rope.transpose(0, 2, 1, 3).astype(cd)],
                            axis=-1).reshape(b, t * n, self.latent_width)
        ctx = mla_decode_attention(q, plane, lengths, rkv,
                                   scale=(dn + self.qk_rope_head_dim) ** -0.5,
                                   tq=t)
        o = jnp.einsum("bthc,chv->bhtv",
                       ctx.reshape(b, t, n, rkv).astype(wkvb.dtype),
                       wkvb[..., dn:], preferred_element_type=_F32)
        return o.astype(q_nope.dtype)

    def mix(self, params: Params, state: State, x: jax.Array,
            mask) -> Tuple[jax.Array, State]:
        """x ``[b, t, n_in]`` in the parameters' type -> ``(MLA(x) [b, t,
        n_in], the new state)``; ``state`` may be empty (a whole sequence
        from position 0, no cache)."""
        from ...ops import masked_cache_write

        b, t, _ = x.shape
        if "latent" not in state:
            q_nope, q_rope, entries = self._projections(
                params, x, jnp.arange(t, dtype=jnp.int32))
            o = self._expanded(params, q_nope, q_rope, entries, mask)
            return _merge_heads(o) @ params["Wo"], state
        pos = state["pos"].astype(jnp.int32)
        keep = state.get("write_mask")
        if keep is None:
            keep = jnp.ones(pos.shape, bool)
        at = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q_nope, q_rope, entries = self._projections(params, x, at)
        if t > 1 and rows_are_fresh():
            plane = masked_cache_write(state["latent"], entries[:, None], pos,
                                       keep)
            o = self._expanded(params, q_nope, q_rope, entries, mask)
        else:
            # a window's few entries one at a time: the one-entry write is
            # the in-place kernel, a scatter of t is a loop over the rows
            plane = state["latent"]
            for j in range(t):
                plane = masked_cache_write(plane, entries[:, None, j:j + 1],
                                           pos + j, keep)
            o = self._absorbed(params, q_nope, q_rope, plane, pos + t)
        valid = (jnp.full((b,), t, jnp.int32) if mask is None
                 else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
        new = {k: v for k, v in state.items() if k != "write_mask"}
        new.update(latent=plane, pos=pos + valid)
        return _merge_heads(o) @ params["Wo"], new

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1).astype(params["Wqa"].dtype)
        y, new_state = self.mix(params, state, xt, ctx.mask)
        return y.astype(x.dtype).transpose(0, 2, 1), new_state
