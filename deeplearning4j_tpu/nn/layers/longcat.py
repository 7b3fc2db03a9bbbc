"""LongCat-Flash's decoder layer (LongCat-Flash-Chat 560B-A27B, 2025-09): a
DOUBLE layer with a shortcut-connected mixture of experts. Two latent-
attention blocks and two dense gated feed-forwards a layer; the expert
layer is fed from the first block's post-attention norm and joins the
stream after the second feed-forward, so its work (and, across chips, its
exchange) lies beside the second attention block and not before it.

    h1 = x  + MLA_0(N(x));   u = N(h1);   m = MoE(u)
    h2 = h1 + FFN_0(u)
    h3 = h2 + MLA_1(N(h2));  y = h3 + FFN_1(N(h3)) + m

``N`` is an RMSNorm with a gain of its own each time, ``MLA`` is
:class:`~.mla.LatentAttentionLayer`, ``FFN`` :func:`~.eva.gated_silu_ffn`,
``MoE`` :class:`~.moe.ExpertShareMoELayer`: the chip's share of the routed
experts and every zero-compute expert. The layer holds no sub-layer's
parameters under another name than its own flat ones: ``a0_*`` / ``a1_*``
(an attention block and the norm before it, ``gn``), ``f0_*`` / ``f1_*``
(a feed-forward and the norm before it), ``m_*`` (the router, its selection
bias and the held experts).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import (Layer, LayerContext, Params, State, apply_input_dropout,
                   sub_params)
from .eva import gated_silu_ffn
from .mla import LatentAttentionLayer
from .moe import ExpertShareMoELayer
from .norm import rms_norm

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LongCatBlockLayer(Layer):
    """One double layer as ONE sequential layer (input/output ``[b, n_in,
    t]``). The residual stream is float32 whatever the parameters' type;
    matmul operands take the parameters' type.

    Decode state: one latent plane for each attention block
    (``latent0``, ``latent1``: ``[b, 1, max_len, kv_lora_rank +
    qk_rope_head_dim]``, declared as planes written in place), the rows'
    position, and ``moe_choices`` ``[b, held + 2]``: where the choices of
    the row's tokens OF THE LAST CALL went (:meth:`decode_counts`), which
    the engine sums over a step's rows and brings home with the step's
    tokens."""

    n_in: int = 0
    n_heads: int = 1
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    ffn_size: int = 0
    expert_ffn_size: int = 0
    n_routed_experts: int = 8
    zero_expert_num: int = 0
    n_held_experts: int = 0
    first_held_expert: int = 0
    moe_topk: int = 2
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e7
    eps: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "LongCatBlockLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.ffn_size:
            out = dataclasses.replace(out, ffn_size=2 * out.n_in)
        if not out.expert_ffn_size:
            out = dataclasses.replace(out, expert_ffn_size=out.n_in // 2)
        return out

    @property
    def mixer(self) -> LatentAttentionLayer:
        return LatentAttentionLayer(
            n_in=self.n_in, n_heads=self.n_heads,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, rope_theta=self.rope_theta,
            eps=self.eps, weight_init=self.weight_init)

    @property
    def moe(self) -> ExpertShareMoELayer:
        return ExpertShareMoELayer(
            n_in=self.n_in, hidden=self.expert_ffn_size,
            n_routed_experts=self.n_routed_experts,
            zero_expert_num=self.zero_expert_num,
            n_held_experts=self.n_held_experts,
            first_held_expert=self.first_held_expert, top_k=self.moe_topk,
            routed_scaling_factor=self.routed_scaling_factor,
            weight_init=self.weight_init)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        mixer, moe = self.mixer, self.moe
        names = []
        for j in (0, 1):
            names += [f"a{j}_gn"] + [f"a{j}_{n}" for n in
                                     mixer.trainable_param_names()]
            names += [f"f{j}_{n}" for n in ("gn", "Wg", "Wu", "Wd")]
        return tuple(names + [f"m_{n}" for n in moe.trainable_param_names()])

    def weight_param_names(self) -> Tuple[str, ...]:
        return tuple(n for n in self.trainable_param_names()
                     if n.split("_", 1)[1][0] in "WE")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, f = self.n_in, self.ffn_size
        ks = jax.random.split(key, 9)
        out: Dict[str, jax.Array] = {}
        for j in (0, 1):
            out[f"a{j}_gn"] = jnp.ones((h,), dtype)
            out |= {f"a{j}_{n}": v for n, v in
                    self.mixer.init(ks[4 * j], dtype).items()}
            out[f"f{j}_gn"] = jnp.ones((h,), dtype)
            for i, (n, rows, cols) in enumerate(
                    (("Wg", h, f), ("Wu", h, f), ("Wd", f, h))):
                out[f"f{j}_{n}"] = init_weights(
                    ks[4 * j + 1 + i], (rows, cols), wi, rows, cols, None,
                    dtype)
        return out | {f"m_{n}": v for n, v in
                      self.moe.init(ks[8], dtype).items()}

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        one = self.mixer.decode_state(batch, max_len, dtype)
        return {"latent0": one["latent"],
                "latent1": jnp.zeros_like(one["latent"]), "pos": one["pos"],
                "moe_choices": jnp.zeros((batch, self.moe.held + 2),
                                         jnp.int32)}

    def decode_planes(self) -> Tuple[str, ...]:
        return ("latent0", "latent1")

    def decode_counts(self) -> Dict[str, Tuple[str, ...]]:
        return {"moe_choices": self.moe.choice_columns()}

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {k: 2 * n for k, n in
                self.mixer.decode_live_bytes(position, itemsize).items()}

    # ---- forward ------------------------------------------------------------
    def _attend(self, j: int, params: Params, state: State, h: jax.Array,
                mask, cd) -> Tuple[jax.Array, jax.Array]:
        """``MLA_j(N(h))`` on the block's own plane -> ``(the mixer's
        output in the stream's type, the plane as the call left it)``."""
        u = rms_norm(h, params[f"a{j}_gn"], self.eps).astype(cd)
        sub = {} if "pos" not in state else {
            "latent": state[f"latent{j}"], "pos": state["pos"],
            **({"write_mask": state["write_mask"]}
               if "write_mask" in state else {})}
        with jax.named_scope(f"mla_{j}"):
            o, new = self.mixer.mix(sub_params(params, f"a{j}_"), sub, u, mask)
        return o.astype(h.dtype), new

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1)                            # [b, t, h]
        xt = xt.astype(jnp.promote_types(xt.dtype, _F32))    # the residual
        b, t, h = xt.shape
        cd = params["a0_Wqa"].dtype
        o, new0 = self._attend(0, params, state, xt, ctx.mask, cd)
        h1 = xt + o
        u = rms_norm(h1, params["f0_gn"], self.eps)  # float32: the router's
        token_mask = None if ctx.mask is None else ctx.mask.reshape(b * t)
        m, counts = self.moe.share(sub_params(params, "m_"),
                                   u.reshape(b * t, h),
                                   token_mask)
        u = u.astype(cd)
        with jax.named_scope("ffn_0"):
            h2 = h1 + gated_silu_ffn(u, params["f0_Wg"], params["f0_Wu"],
                                     params["f0_Wd"]).astype(xt.dtype)
        o, new1 = self._attend(1, params, state, h2, ctx.mask, cd)
        h3 = h2 + o
        u = rms_norm(h3, params["f1_gn"], self.eps).astype(cd)
        with jax.named_scope("ffn_1"):
            y = h3 + gated_silu_ffn(u, params["f1_Wg"], params["f1_Wu"],
                                    params["f1_Wd"]).astype(xt.dtype) \
                + m.reshape(b, t, h).astype(xt.dtype)
        new_state = state
        if "pos" in state:
            new_state = {"latent0": new0["latent"], "latent1": new1["latent"],
                         "pos": new0["pos"],
                         "moe_choices": jnp.sum(
                             counts.reshape(b, t, -1), axis=1)}
        return y.transpose(0, 2, 1), new_state
