"""A network's head with ONE multi-token-prediction (MTP) module
(DeepSeek-V3, arXiv:2412.19437, section 2.2, eqs. 21-23), as the last layer
of a decoder:

    logits_i = N(x_i; gf) W                       (the next token, t_{i+1})
    h'_i     = M [N(x_i; gh); N(E[t_{i+1}]; ge)]  (M: [2 n_in, n_in])
    g_i      = Block(h')_i                        (a block of its own, with
                                                    its own decode state)
    draft_i  = N(g_i; gm) W                       (the token after, t_{i+2})

``x`` is the main stack's last output before the final norm (the layer's
input), ``E`` the embedding's matrix (read from layer ``tied_layer``,
``Layer.tied_params``) and ``W`` the same head. ``apply`` gives the main
logits alone; the module runs only where a caller drafts
(:meth:`MtpOutputLayer.draft`): a generation session that speculates with
the model's own module (``generate/session.py``). The module's block owns a
decode state of its own (a latent plane for an MLA block), which the layer
declares as its own, so the carry holds it beside the main stack's and
rewinds it by position with them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import LayerContext, Params, State, sub_params
from .decoder_block import DecoderBlockLayer
from .norm import rms_norm
from .output import BaseOutputLayer

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class MtpOutputLayer(BaseOutputLayer):
    """Final RMSNorm and an untied head ``W [n_in, n_out]`` (float32
    LOGITS ``[b, n_out, t]``, operands in the parameters' type), beside one
    MTP module: the norms ``gh``, ``ge``, ``gm``, the projection ``M`` and
    the module's ``block`` (its parameters under ``b_``). Labels of the
    loss are sparse next-token ids ``[b, t]``; the module adds nothing to
    it (the layer serves a trained module, it does not train one)."""

    n_in: int = 0
    n_out: int = 0
    tied_layer: int = 0
    block: Optional[DecoderBlockLayer] = None
    eps: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentType) \
            else None
        return RecurrentType(size=self.n_out, timesteps=ts)

    def with_input(self, input_type: InputType) -> "MtpOutputLayer":
        n_in = self.n_in or input_type.size
        here = RecurrentType(size=n_in, timesteps=input_type.timesteps)
        return dataclasses.replace(self, n_in=n_in,
                                   block=self.block.with_input(here))

    def has_params(self) -> bool:
        return True

    def tied_params(self):
        return {"E": (self.tied_layer, "W")}

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("gf", "W", "gh", "ge", "M", "gm") + tuple(
            f"b_{n}" for n in self.block.trainable_param_names())

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("W", "M") + tuple(
            f"b_{n}" for n in self.block.weight_param_names())

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, v = self.n_in, self.n_out
        kw, km, kb = jax.random.split(key, 3)
        out = {"gf": jnp.ones((h,), dtype),
               "W": init_weights(kw, (h, v), wi, h, v, None, dtype),
               "gh": jnp.ones((h,), dtype), "ge": jnp.ones((h,), dtype),
               "M": init_weights(km, (2 * h, h), wi, 2 * h, h, None, dtype),
               "gm": jnp.ones((h,), dtype)}
        return out | {f"b_{n}": p for n, p in
                      self.block.init(kb, dtype).items()}

    # ---- the module's decode state is the layer's --------------------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        return self.block.decode_state(batch, max_len, dtype)

    def decode_planes(self) -> Tuple[str, ...]:
        return self.block.decode_planes()

    def decode_counts(self) -> Dict[str, Tuple[str, ...]]:
        return self.block.decode_counts()

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return self.block.decode_live_bytes(position, itemsize)

    # ---- the head -----------------------------------------------------------
    def _head(self, params: Params, xt: jax.Array, gain: str) -> jax.Array:
        """``[b, t, n_in]`` -> float32 logits ``[b, t, n_out]``."""
        w = params["W"]
        u = rms_norm(xt, params[gain], self.eps).astype(w.dtype)
        return jnp.einsum("btf,fo->bto", u, w, preferred_element_type=_F32)

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        return self._head(params, x.transpose(0, 2, 1), "gf")

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        return self.preoutput(params, x, ctx).transpose(0, 2, 1), state

    def decode_logits(self, params: Params, x: jax.Array) -> jax.Array:
        """The main logits [b, n_out, t] from the layer's input."""
        return self.apply(params, {}, x, LayerContext())[0]

    def draft(self, params: Params, state: State, x: jax.Array,
              nxt: jax.Array, mask=None) -> Tuple[jax.Array, State]:
        """The module over the main stack's outputs ``x [b, n_in, t]`` (the
        layer's input at ``t`` positions) and the ids one position on,
        ``nxt [b, t]`` -> ``(g [b, t, n_in] float32, the module's new
        decode state)``: its block writes ``t`` entries of its own plane
        from the state's position on, as the main stack's blocks do."""
        with jax.named_scope("mtp_draft"):
            m = params["M"]
            xt = x.transpose(0, 2, 1).astype(_F32)
            e = jnp.take(params["E"], nxt.astype(jnp.int32), axis=0)
            u = jnp.concatenate([rms_norm(xt, params["gh"], self.eps),
                                 rms_norm(e, params["ge"], self.eps)],
                                axis=-1).astype(m.dtype)
            hp = jnp.einsum("btf,fo->bto", u, m, preferred_element_type=_F32)
            return self.block.run(sub_params(params, "b_"), state, hp, mask)

    def draft_logits(self, params: Params, g: jax.Array) -> jax.Array:
        """:meth:`draft`'s ``g [b, t, n_in]`` -> float32 logits of the token
        two positions on, ``[b, t, n_out]``."""
        with jax.named_scope("mtp_draft"):
            return self._head(params, g, "gm")

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        b, _, t = x.shape
        logp = jax.nn.log_softmax(self.preoutput(params, x, ctx), axis=-1)
        labels = labels.reshape(b, t).astype(jnp.int32)
        mask = label_mask if label_mask is not None else ctx.mask
        mask = (jnp.ones((b, t), logp.dtype) if mask is None
                else mask.reshape(b, t).astype(logp.dtype))
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)
