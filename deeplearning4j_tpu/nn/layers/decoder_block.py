"""A decoder block made of parts: ``norm | mixer | norm | feed-forward``.

    h = x + Op(N(x; op_gn));   y = h + FF(N(h; ff_gn))

or, with ``sandwich`` (Pangu Ultra, arXiv:2504.07866), a norm of its own on
each part's output before the residual add:

    h = x + N(Op(N(x; op_gn)); po_gn);   y = h + N(FF(N(h; ff_gn)); pf_gn)

``N`` is an RMSNorm with a gain of its own each time (``norm="layer"``: a
LayerNorm with a gain and a bias, ``op_gb`` / ``ff_gb``), ``Op`` and ``FF`` are
the block's two PARTS, layers of their own that the block is configured
with, not code of the block's:

* a MIXER (``Op``) gives ``mix(params, state, u [b, t, n_in], mask) -> (o,
  new state)`` and owns its decode state (``decode_state``, ``decode_planes``,
  ``pages_decode_planes``, ``decode_live_bytes``) and the scope its
  operations carry in a device trace:
  :class:`~.short_conv.ShortConvLayer` (a rolling state a row),
  :class:`~.gqa.GroupedQueryAttentionLayer` (K/V planes),
  :class:`~.mla.LatentAttentionLayer` (a latent plane),
  :class:`~.mamba.MambaMixerLayer` (a selective scan's state),
  :class:`~.diff_attention.DifferentialAttentionLayer` (a window's ring, or
  a K/V cache that other blocks read), :class:`~.gmu.GatedMemoryLayer`;
* a FEED-FORWARD (``FF``) gives ``feed(params, u [n, n_in], token_mask) ->
  (y [n, n_in], counts or None)`` over the block's tokens:
  :class:`GatedFFNLayer` (dense), :class:`~.moe.ExpertShareMoELayer` (an
  expert layer, whose ``counts`` the block keeps in its decode state for
  the engine to bring home).

A model whose layers differ in their parts (LFM2: convolution or attention,
dense or experts) is the same block configured four ways, not four classes.
The block holds its parts' parameters flat under ``op_`` and ``ff_``, each
with the gain of the norm before it (``op_gn``, ``ff_gn``). The residual
stream is float32 whatever the parameters' type.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import (Layer, LayerContext, Params, State, apply_input_dropout,
                   sub_params)
from .eva import gated_silu_ffn
from .norm import layer_norm, rms_norm

_F32 = jnp.float32


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class GatedFFNLayer(Layer):
    """``Wd (silu(Wg x) * Wu x)`` without biases, as a sequential layer
    (input/output ``[b, n_in, t]`` or ``[n, n_in]``) and as a block's
    feed-forward part."""

    n_in: int = 0
    hidden: int = 0

    def with_input(self, input_type: InputType) -> "GatedFFNLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.hidden:
            out = dataclasses.replace(out, hidden=4 * out.n_in)
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wg", "Wu", "Wd")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, f = self.n_in, self.hidden
        kg, ku, kd = jax.random.split(key, 3)
        return {"Wg": init_weights(kg, (h, f), wi, h, f, None, dtype),
                "Wu": init_weights(ku, (h, f), wi, h, f, None, dtype),
                "Wd": init_weights(kd, (f, h), wi, f, h, None, dtype)}

    def feed(self, params: Params, x2: jax.Array, token_mask=None):
        """Tokens ``x2 [n, n_in]`` -> ``(y [n, n_in], None)``: the operands
        in the parameters' type; it counts nothing."""
        with jax.named_scope("ffn"):
            return gated_silu_ffn(x2.astype(params["Wg"].dtype), params["Wg"],
                                  params["Wu"], params["Wd"]), None

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1) if x.ndim == 3 else x
        y = self.feed(params, xt)[0].astype(x.dtype)
        return (y.transpose(0, 2, 1) if x.ndim == 3 else y), state


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class DecoderBlockLayer(Layer):
    """One block as ONE sequential layer (input/output ``[b, n_in, t]``).

    Decode state: the mixer's own leaves under their own names (its planes
    are the block's planes), and, with a feed-forward that counts,
    ``moe_choices`` ``[b, held + 2]``: where the choices of the row's tokens
    OF THE LAST CALL went (:meth:`decode_counts`)."""

    n_in: int = 0
    mixer: Optional[Layer] = None
    ffn: Optional[Layer] = None
    eps: float = 1e-5
    norm: str = "rms"      # "rms" | "layer" (a LayerNorm with a bias)
    sandwich: bool = False

    @property
    def _norm_params(self) -> Tuple[str, ...]:
        return ("gn", "gb") if self.norm == "layer" else ("gn",)

    @property
    def _post_norms(self) -> Tuple[str, ...]:
        """The sandwich's two output norms' parameters."""
        if not self.sandwich:
            return ()
        return tuple(f"{part}_{n}" for part in ("po", "pf")
                     for n in self._norm_params)

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "DecoderBlockLayer":
        n_in = self.n_in or input_type.size
        here = RecurrentType(size=n_in, timesteps=input_type.timesteps)
        return dataclasses.replace(
            self, n_in=n_in, mixer=self.mixer.with_input(here),
            ffn=self.ffn.with_input(here))

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return tuple(f"op_{n}" for n in self._norm_params) + tuple(
            f"op_{n}" for n in self.mixer.trainable_param_names()) \
            + tuple(f"ff_{n}" for n in self._norm_params) + tuple(
            f"ff_{n}" for n in self.ffn.trainable_param_names()) \
            + self._post_norms

    def weight_param_names(self) -> Tuple[str, ...]:
        return tuple(f"op_{n}" for n in self.mixer.weight_param_names()) \
            + tuple(f"ff_{n}" for n in self.ffn.weight_param_names())

    def init(self, key: jax.Array, dtype: Any) -> Params:
        k_op, k_ff = jax.random.split(key)
        out: Dict[str, jax.Array] = {"op_gn": jnp.ones((self.n_in,), dtype),
                                     "ff_gn": jnp.ones((self.n_in,), dtype)}
        if self.norm == "layer":
            out |= {"op_gb": jnp.zeros((self.n_in,), dtype),
                    "ff_gb": jnp.zeros((self.n_in,), dtype)}
        for name in self._post_norms:
            out[name] = (jnp.zeros if name.endswith("gb") else jnp.ones)(
                (self.n_in,), dtype)
        out |= {f"op_{n}": v for n, v in
                self.mixer.init(k_op, dtype).items()}
        return out | {f"ff_{n}": v for n, v in
                      self.ffn.init(k_ff, dtype).items()}

    # ---- the decode state and what the layer declares of it ---------------
    @property
    def _counts(self) -> Tuple[str, ...]:
        columns = getattr(self.ffn, "choice_columns", None)
        return columns() if columns else ()

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        out = dict(self.mixer.decode_state(batch, max_len, dtype))
        if self._counts:
            out["moe_choices"] = jnp.zeros((batch, len(self._counts)),
                                           jnp.int32)
        return out

    def decode_planes(self) -> Tuple[str, ...]:
        return self.mixer.decode_planes()

    @property
    def pages_decode_planes(self) -> bool:
        return self.mixer.pages_decode_planes

    def decode_window(self) -> Optional[int]:
        return self.mixer.decode_window()

    def decode_ring(self) -> Optional[int]:
        return self.mixer.decode_ring()

    def decode_counts(self) -> Dict[str, Tuple[str, ...]]:
        return {"moe_choices": self._counts} if self._counts else {}

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return self.mixer.decode_live_bytes(position, itemsize)

    # ---- forward ------------------------------------------------------------
    def _normed(self, params: Params, x: jax.Array, part: str) -> jax.Array:
        if self.norm == "layer":
            return layer_norm(x, params[f"{part}_gn"], params[f"{part}_gb"],
                              self.eps)
        return rms_norm(x, params[f"{part}_gn"], self.eps)

    def block(self, params: Params, state: State, xt: jax.Array, mask,
              mix=None):
        """The block over the residual stream ``xt [b, t, n_in]`` (float32)
        -> ``(y, the mixer's new state, the feed-forward's counts or None,
        what else the mixer gave)``. ``mix`` stands in for ``self.mixer.mix``
        where a caller hands the mixer more than its own state (a memory, a
        cache another block wrote: ``cross_decoder.py``); it returns ``(o,
        new state, *more)``."""
        b, t, h = xt.shape
        cd = params["op_gn"].dtype
        u = self._normed(params, xt, "op").astype(cd)
        o, new, *more = (mix or self.mixer.mix)(
            sub_params(params, "op_"), state, u, mask)
        if self.sandwich:
            o = self._normed(params, o.astype(xt.dtype), "po")
        h1 = xt + o.astype(xt.dtype)
        # float32 into the part: an expert layer's router reads it as it is
        u = self._normed(params, h1, "ff")
        token_mask = None if mask is None else mask.reshape(b * t)
        m, counts = self.ffn.feed(sub_params(params, "ff_"),
                                  u.reshape(b * t, h), token_mask)
        m = m.reshape(b, t, h).astype(xt.dtype)
        if self.sandwich:
            m = self._normed(params, m, "pf")
        return h1 + m.astype(xt.dtype), new, counts, more

    def run(self, params: Params, state: State, xt: jax.Array,
            mask) -> Tuple[jax.Array, State]:
        """:meth:`block` over ``xt [b, t, n_in]`` with the layer's decode
        state as the layer keeps it -> ``(y [b, t, n_in], new state)``: the
        mixer's, and where the feed-forward counts, ``moe_choices``."""
        b, t, h = xt.shape
        # the block keeps no state between calls but its decode state
        sub = {k: v for k, v in state.items() if k != "moe_choices"}
        y, new, counts, _ = self.block(params, sub, xt, mask)
        new_state = state
        if sub:
            new_state = dict(new)
            if counts is not None:
                new_state["moe_choices"] = jnp.sum(
                    counts.reshape(b, t, -1), axis=1)
        return y, new_state

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1)                            # [b, t, h]
        xt = xt.astype(jnp.promote_types(xt.dtype, _F32))    # the residual
        y, new_state = self.run(params, state, xt, ctx.mask)
        return y.transpose(0, 2, 1), new_state
