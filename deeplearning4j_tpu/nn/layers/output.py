"""Output / loss layers.

Reference: org.deeplearning4j.nn.conf.layers.{OutputLayer, RnnOutputLayer,
RnnLossLayer, LossLayer, CnnLossLayer, CenterLossOutputLayer}. An output layer
= (optional dense projection) + ILossFunction; the model calls
``compute_loss`` during fit and ``apply`` during output().
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..activations import Activation
from ..input_type import ConvolutionalType, FeedForwardType, InputType, RecurrentType
from ..losses import LossFunction
from ..weights import WeightInit, init_weights
from .base import Layer, LayerContext, Params, State, apply_input_dropout


class BaseOutputLayer(Layer):
    """Marker base for layers that terminate a network with a loss."""

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        raise NotImplementedError

    def compute_loss(
        self,
        params: Params,
        x: jax.Array,
        labels: jax.Array,
        ctx: LayerContext,
        label_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        raise NotImplementedError


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class OutputLayer(BaseOutputLayer):
    """Dense + loss on feed-forward input (reference: OutputLayer).
    Default activation SOFTMAX + MCXENT, matching the reference."""

    n_in: int = 0
    n_out: int = 0
    loss: LossFunction = LossFunction.MCXENT
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return FeedForwardType(size=self.n_out)

    def with_input(self, input_type: InputType) -> "OutputLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.flat_size())

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("W", "b") if self.has_bias else ("W",)

    def init(self, key: jax.Array, dtype: Any) -> Params:
        w = init_weights(key, (self.n_in, self.n_out),
                         self.weight_init or WeightInit.XAVIER,
                         self.n_in, self.n_out, self.weight_init_distribution, dtype)
        p: Params = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        x = apply_input_dropout(self, x, ctx)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        act = self.activation or Activation.SOFTMAX
        return act(self.preoutput(params, x, ctx)), state

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        pre = self.preoutput(params, x, ctx)
        act = self.activation or Activation.SOFTMAX
        return self.loss.score(labels, pre, act, mask=label_mask)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LossLayer(BaseOutputLayer):
    """Loss without params (reference: LossLayer). Activation default IDENTITY."""

    loss: LossFunction = LossFunction.MCXENT

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        return x

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        act = self.activation or Activation.IDENTITY
        return act(x), state

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        act = self.activation or Activation.IDENTITY
        return self.loss.score(labels, x, act, mask=label_mask)


def _rnn_to_ff(a: jax.Array) -> jax.Array:
    """[b, f, t] -> [b*t, f] preserving the reference's flattening order."""
    b, f, t = a.shape
    return a.transpose(0, 2, 1).reshape(b * t, f)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep dense + loss (reference: RnnOutputLayer). Input [b, nIn, t],
    labels [b, nOut, t], mask [b, t]."""

    n_in: int = 0
    n_out: int = 0
    loss: LossFunction = LossFunction.MCXENT
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentType) else None
        return RecurrentType(size=self.n_out, timesteps=ts)

    def with_input(self, input_type: InputType) -> "RnnOutputLayer":
        if self.n_in or not isinstance(input_type, RecurrentType):
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("W", "b") if self.has_bias else ("W",)

    def init(self, key: jax.Array, dtype: Any) -> Params:
        w = init_weights(key, (self.n_in, self.n_out),
                         self.weight_init or WeightInit.XAVIER,
                         self.n_in, self.n_out, self.weight_init_distribution, dtype)
        p: Params = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        x = apply_input_dropout(self, x, ctx)
        flat = _rnn_to_ff(x)
        y = flat @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y  # [b*t, nOut]

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        b, _, t = x.shape
        act = self.activation or Activation.SOFTMAX
        y = act(self.preoutput(params, x, ctx))
        return y.reshape(b, t, self.n_out).transpose(0, 2, 1), state

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        pre = self.preoutput(params, x, ctx)  # [b*t, nOut]
        # sparse integer labels [b, t] (SPARSE_MCXENT) flatten in the same
        # (batch, time) order as _rnn_to_ff; dense labels are [b, nOut, t]
        lab = _rnn_to_ff(labels) if labels.ndim == 3 else labels.reshape(-1)
        act = self.activation or Activation.SOFTMAX
        mask = None
        if label_mask is not None:
            mask = label_mask.reshape(-1)
        elif ctx.mask is not None:
            mask = ctx.mask.reshape(-1)
        return self.loss.score(lab, pre, act, mask=mask)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class MultiTokenRnnOutputLayer(BaseOutputLayer):
    """Per-timestep head of ``n_pred_heads`` x ``n_out`` columns without a
    bias: head ``j`` predicts the token ``j + 1`` positions ahead (head 0 the
    next one). ``apply`` gives every head's LOGITS side by side,
    ``[b, n_pred_heads * n_out, t]`` in float32 (operands in the parameters'
    type, float32 accumulation). Decoding reads head 0 alone
    (:meth:`decode_logits`: ``n_out`` is the served vocabulary). Labels are
    sparse next-token ids ``[b, t]``; the loss is the mean over the heads of
    head ``j``'s cross-entropy against the labels ``j`` positions on."""

    n_in: int = 0
    n_out: int = 0
    n_pred_heads: int = 1

    def output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentType) else None
        return RecurrentType(size=self.n_pred_heads * self.n_out, timesteps=ts)

    def with_input(self, input_type: InputType) -> "MultiTokenRnnOutputLayer":
        if self.n_in or not isinstance(input_type, RecurrentType):
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("W",)

    def init(self, key: jax.Array, dtype: Any) -> Params:
        cols = self.n_pred_heads * self.n_out
        return {"W": init_weights(key, (self.n_in, cols),
                                  self.weight_init or WeightInit.XAVIER,
                                  self.n_in, cols,
                                  self.weight_init_distribution, dtype)}

    def _project(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """x [b, n_in, t] -> float32 logits [b, t, columns of w]."""
        return jnp.einsum("bft,fo->bto", x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        x = apply_input_dropout(self, x, ctx)
        return self._project(x, params["W"])  # [b, t, heads * n_out]

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        return self.preoutput(params, x, ctx).transpose(0, 2, 1), state

    def decode_logits(self, params: Params, x: jax.Array) -> jax.Array:
        """Head 0's logits [b, n_out, t] from the layer's input: the only
        columns a served token depends on."""
        return self._project(x, params["W"][:, :self.n_out]).transpose(0, 2, 1)

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        b, _, t = x.shape
        logits = self.preoutput(params, x, ctx).reshape(
            b, t, self.n_pred_heads, self.n_out)
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = labels.reshape(b, t).astype(jnp.int32)
        mask = label_mask if label_mask is not None else ctx.mask
        mask = (jnp.ones((b, t), logp.dtype) if mask is None
                else mask.reshape(b, t).astype(logp.dtype))
        total = 0.0
        for j in range(min(self.n_pred_heads, t)):
            picked = jnp.take_along_axis(
                logp[:, :t - j, j], labels[:, j:, None], axis=-1)[..., 0]
            m = mask[:, j:]
            total = total - jnp.sum(picked * m) / jnp.maximum(jnp.sum(m), 1.0)
        return total / min(self.n_pred_heads, t)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class TiedRnnOutputLayer(BaseOutputLayer):
    """Per-timestep head that SHARES the embedding's matrix: ``logits = x
    E^T`` with ``E [n_out, n_in]`` the ``W`` of layer ``tied_layer`` of the
    network (``Layer.tied_params``). It owns no parameter, so the matrix is
    held, trained and saved once. ``apply`` gives float32 LOGITS ``[b,
    n_out, t]`` (operands in the matrix's type, float32 accumulation);
    labels are sparse next-token ids ``[b, t]``."""

    n_in: int = 0
    n_out: int = 0
    tied_layer: int = 0

    def output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentType) else None
        return RecurrentType(size=self.n_out, timesteps=ts)

    def with_input(self, input_type: InputType) -> "TiedRnnOutputLayer":
        if self.n_in or not isinstance(input_type, RecurrentType):
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def tied_params(self):
        return {"W": (self.tied_layer, "W")}

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        x = apply_input_dropout(self, x, ctx)
        w = params["W"]
        return jnp.einsum("bft,of->bto", x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        return self.preoutput(params, x, ctx).transpose(0, 2, 1), state

    def decode_logits(self, params: Params, x: jax.Array) -> jax.Array:
        """The logits [b, n_out, t] from the layer's input."""
        return self.apply(params, {}, x, LayerContext())[0]

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        b, _, t = x.shape
        logp = jax.nn.log_softmax(self.preoutput(params, x, ctx), axis=-1)
        labels = labels.reshape(b, t).astype(jnp.int32)
        mask = label_mask if label_mask is not None else ctx.mask
        mask = (jnp.ones((b, t), logp.dtype) if mask is None
                else mask.reshape(b, t).astype(logp.dtype))
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class RnnLossLayer(BaseOutputLayer):
    """Per-timestep loss without params (reference: RnnLossLayer)."""

    loss: LossFunction = LossFunction.MCXENT

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        return x

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        act = self.activation or Activation.IDENTITY
        b, f, t = x.shape
        y = act(_rnn_to_ff(x))
        return y.reshape(b, t, f).transpose(0, 2, 1), state

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        pre = _rnn_to_ff(x)
        lab = _rnn_to_ff(labels)
        act = self.activation or Activation.IDENTITY
        mask = None
        if label_mask is not None:
            mask = label_mask.reshape(-1)
        elif ctx.mask is not None:
            mask = ctx.mask.reshape(-1)
        return self.loss.score(lab, pre, act, mask=mask)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class CnnLossLayer(BaseOutputLayer):
    """Per-pixel loss on CNN output [b, c, h, w] (reference: CnnLossLayer).
    Labels same shape; mask [b, 1, h, w] or [b, h, w] optional."""

    loss: LossFunction = LossFunction.MCXENT

    def preoutput(self, params: Params, x: jax.Array, ctx: LayerContext) -> jax.Array:
        return x

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        act = self.activation or Activation.IDENTITY
        # activation applied over channel axis: move C last, apply, move back
        y = act(x.transpose(0, 2, 3, 1)).transpose(0, 3, 1, 2)
        return y, state

    def compute_loss(self, params, x, labels, ctx, label_mask=None):
        b, c, h, w = x.shape
        pre = x.transpose(0, 2, 3, 1).reshape(b * h * w, c)
        lab = labels.transpose(0, 2, 3, 1).reshape(b * h * w, c)
        act = self.activation or Activation.IDENTITY
        mask = None
        if label_mask is not None:
            mask = label_mask.reshape(-1)
        return self.loss.score(lab, pre, act, mask=mask)
