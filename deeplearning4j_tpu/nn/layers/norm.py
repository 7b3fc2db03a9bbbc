"""Normalization layers.

Reference: org.deeplearning4j.nn.conf.layers.{BatchNormalization,
LocalResponseNormalization} (+ cuDNN helpers CudnnBatchNormalizationHelper,
CudnnLocalResponseNormalizationHelper — here XLA fuses the normalization math
into neighbours, no helper needed).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..activations import Activation
from ..input_type import ConvolutionalType, FeedForwardType, InputType, RecurrentType
from .base import Layer, LayerContext, Params, State


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class BatchNormalizationLayer(Layer):
    """Batch normalization (reference: BatchNormalization).

    Params: gamma/beta [nOut]; state: running mean/var [nOut] updated with the
    reference's decay convention: global = decay*global + (1-decay)*batch.
    Supports FF [b,f], recurrent [b,f,t] and CNN [b,c,h,w] inputs (per-channel).
    """

    n_out: int = 0
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    gamma_init: float = 1.0
    beta_init: float = 0.0
    # Affine-precompute form (set by rewrite.BatchNormAffinePass): apply the
    # normalization as ONE fused multiply-add with per-channel
    # scale = gamma*rsqrt(var+eps), shift = beta - mean*scale, instead of the
    # 4-op subtract/rsqrt/scale/shift chain — same math to float tolerance,
    # but XLA fuses the single FMA into the neighbouring op's epilogue.
    fused: bool = False
    # Distributed batch norm (MLPerf TPU-pods paper, arxiv 1909.09756):
    # training batch statistics are averaged over groups of this many
    # adjacent data-parallel replicas instead of whichever batch slice one
    # replica sees — the per-chip batch shrinks as DP widens and
    # per-replica moments degrade. None inherits the trainer's
    # bn_group_size= default (and stays fully local outside a
    # DistributedTrainer). Running-stat state keeps its [n_out] shape, so
    # checkpoints are group-size independent.
    stats_axis_group: Optional[int] = None

    def with_input(self, input_type: InputType) -> "BatchNormalizationLayer":
        if self.n_out:
            return self
        if isinstance(input_type, (ConvolutionalType, RecurrentType)):
            n = input_type.channels if isinstance(input_type, ConvolutionalType) else input_type.size
        else:
            n = input_type.flat_size()
        return dataclasses.replace(self, n_out=n)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return () if self.lock_gamma_beta else ("gamma", "beta")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ()  # reference never regularizes gamma/beta

    def init(self, key: jax.Array, dtype: Any) -> Params:
        if self.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((self.n_out,), self.gamma_init, dtype),
            "beta": jnp.full((self.n_out,), self.beta_init, dtype),
        }

    def init_state(self, dtype: Any) -> State:
        return {
            "mean": jnp.zeros((self.n_out,), dtype),
            "var": jnp.ones((self.n_out,), dtype),
        }

    def _stats_group(self, ctx: LayerContext) -> Optional[int]:
        """Resolved statistics group size (replicas per group), or None
        for the classic local spelling. Layer field wins over the
        trainer's ``bn_group_size=`` default; validated against the data
        axis at trace time."""
        dist = ctx.dist
        if dist is None:
            return None
        g = (self.stats_axis_group if self.stats_axis_group is not None
             else dist.bn_group_size)
        if g is None:
            return None
        g = int(g)
        if g < 1 or dist.n_shards % g:
            raise ValueError(
                f"BatchNormalization stats_axis_group={g} must divide the "
                f"data axis ({dist.n_shards} shards)")
        return g

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        # reduce over all axes except the feature axis (1)
        axes = (0,) + tuple(range(2, x.ndim))
        bshape = (1, self.n_out) + (1,) * (x.ndim - 2)
        # statistics always in >= f32: under bf16 mixed precision the batch
        # moments and running stats would otherwise lose too many mantissa
        # bits (running state arrives in the master dtype and stays there)
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        x32 = x.astype(stat_dtype)
        group = self._stats_group(ctx) if ctx.train else None
        if ctx.train and group is not None and ctx.dist.axis is None:
            # GSPMD path: x is the GLOBAL batch; one group = the rows of
            # `group` adjacent replicas (the batch-dim sharding places row
            # blocks on replicas in order), spelled as a reshape so XLA
            # keeps each group's moments on its own devices
            return self._apply_grouped_global(params, state, x, x32,
                                              stat_dtype, group, ctx)
        if ctx.train:
            if group is not None:
                # explicit (shard_map) path: x is this replica's shard —
                # group moments are slice-local sums psummed over the
                # replica groups of the data axis
                dist = ctx.dist
                groups = [list(range(i, i + group))
                          for i in range(0, dist.n_shards, group)]
                s1 = jnp.sum(x32, axis=axes)
                s2 = jnp.sum(jnp.square(x32), axis=axes)
                tot = jax.lax.psum(jnp.stack([s1, s2]), dist.axis,
                                   axis_index_groups=groups)
                denom = float(x32.size // self.n_out) * group
                mean = tot[0] / denom
                var = jnp.maximum(tot[1] / denom - jnp.square(mean), 0.0)
            else:
                mean = jnp.mean(x32, axis=axes)
                var = jnp.var(x32, axis=axes)
            # grouped: each replica folds ITS group's moments into the
            # running stats; the trainer's cross-replica state average
            # then yields the across-group mean (same value the GSPMD
            # spelling writes directly)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean.astype(state["mean"].dtype),
                "var": self.decay * state["var"] + (1.0 - self.decay) * var.astype(state["var"].dtype),
            }
        else:
            mean, var = state["mean"].astype(stat_dtype), state["var"].astype(stat_dtype)
            new_state = state
        if self.fused:
            rstd = jax.lax.rsqrt(var + self.eps)
            if self.lock_gamma_beta:
                scale, shift = rstd, -mean * rstd
            else:
                scale = params["gamma"].astype(stat_dtype) * rstd
                shift = params["beta"].astype(stat_dtype) - mean * scale
            xhat = x32 * scale.reshape(bshape) + shift.reshape(bshape)
        else:
            xhat = (x32 - mean.reshape(bshape)) * jax.lax.rsqrt(var.reshape(bshape) + self.eps)
            if not self.lock_gamma_beta:
                xhat = (xhat * params["gamma"].astype(stat_dtype).reshape(bshape)
                        + params["beta"].astype(stat_dtype).reshape(bshape))
        act = self.activation or Activation.IDENTITY
        return act(xhat).astype(x.dtype), new_state

    def _apply_grouped_global(self, params: Params, state: State,
                              x: jax.Array, x32: jax.Array, stat_dtype,
                              group: int, ctx: LayerContext) -> Tuple[jax.Array, State]:
        """Grouped statistics over a GLOBAL batch array (the implicit
        GSPMD trainer path): reshape [B, ...] -> [G, B/G, ...] so each
        group of ``group`` adjacent replicas normalizes with its own
        moments (same moments as the explicit path's grouped psum — the
        batch-dim sharding lays contiguous row blocks out in replica
        order). Running stats take the across-group mean, which is what
        the explicit path's per-replica update + trainer state average
        converges to, so both paths write identical state."""
        dist = ctx.dist
        n_groups = dist.n_shards // group
        b = x32.shape[0]
        if b % max(n_groups, 1):
            raise ValueError(
                f"global batch {b} not divisible into {n_groups} "
                f"batch-norm statistics groups")
        xg = x32.reshape((n_groups, b // n_groups) + x32.shape[1:])
        axes_g = (1,) + tuple(range(3, xg.ndim))
        mean_g = jnp.mean(xg, axis=axes_g)  # [G, C]
        var_g = jnp.maximum(
            jnp.mean(jnp.square(xg), axis=axes_g) - jnp.square(mean_g), 0.0)
        gshape = (n_groups, 1, self.n_out) + (1,) * (xg.ndim - 3)
        # per-group affine form: gamma/beta fold into scale/shift like the
        # fused spelling (same math to float tolerance as the 4-op chain)
        rstd_g = jax.lax.rsqrt(var_g + self.eps)
        if self.lock_gamma_beta:
            scale_g, shift_g = rstd_g, -mean_g * rstd_g
        else:
            scale_g = params["gamma"].astype(stat_dtype)[None, :] * rstd_g
            shift_g = params["beta"].astype(stat_dtype)[None, :] - mean_g * scale_g
        yg = xg * scale_g.reshape(gshape) + shift_g.reshape(gshape)
        new_state = {
            "mean": self.decay * state["mean"]
            + (1.0 - self.decay) * jnp.mean(mean_g, axis=0).astype(state["mean"].dtype),
            "var": self.decay * state["var"]
            + (1.0 - self.decay) * jnp.mean(var_g, axis=0).astype(state["var"].dtype),
        }
        act = self.activation or Activation.IDENTITY
        return act(yg.reshape(x32.shape)).astype(x.dtype), new_state


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LocalResponseNormalizationLayer(Layer):
    """Cross-channel LRN over NCHW (reference: LocalResponseNormalization;
    AlexNet-era). y = x / (k + alpha*sum_adjacent(x^2))^beta."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        half = int(self.n) // 2
        sq = x * x
        # sum over a window of channels: pad then reduce_window over axis 1
        summed = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add,
            window_dimensions=(1, int(self.n), 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (half, half), (0, 0), (0, 0)),
        )
        return x / jnp.power(self.k + self.alpha * summed, self.beta), state


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LayerNormLayer(Layer):
    """Layer normalization over the feature axis. The reference exposes this as
    the ``layerNorm`` option on dense/RNN layers and the SameDiff ``layerNorm``
    op; here it is also a standalone layer (transformer building block)."""

    n_out: int = 0
    eps: float = 1e-5

    def with_input(self, input_type: InputType) -> "LayerNormLayer":
        if self.n_out:
            return self
        n = input_type.size if isinstance(input_type, RecurrentType) else input_type.flat_size()
        return dataclasses.replace(self, n_out=n)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("gamma", "beta")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ()

    def init(self, key: jax.Array, dtype: Any) -> Params:
        return {
            "gamma": jnp.ones((self.n_out,), dtype),
            "beta": jnp.zeros((self.n_out,), dtype),
        }

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        feat_axis = 1 if x.ndim == 3 else -1  # recurrent [b,f,t] vs ff [b,f]
        # statistics in >= f32 under bf16 mixed precision (same rationale as
        # BatchNormalizationLayer; LN runs 2/block on the transformer path)
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        x32 = x.astype(stat_dtype)
        mean = jnp.mean(x32, axis=feat_axis, keepdims=True)
        var = jnp.var(x32, axis=feat_axis, keepdims=True)
        xhat = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        bshape = (1, self.n_out, 1) if x.ndim == 3 else (1, self.n_out)
        y = (xhat * params["gamma"].astype(stat_dtype).reshape(bshape)
             + params["beta"].astype(stat_dtype).reshape(bshape))
        act = self.activation or Activation.IDENTITY
        return act(y).astype(x.dtype), state


def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-5,
             unit_offset: bool = False, axis: int = -1) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * g`` over ``axis``, with ``g = 1 +
    gain`` under ``unit_offset`` (the gain is then stored round 0). The
    statistics and the result are float32 at least: the caller casts."""
    x32 = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=axis, keepdims=True) + eps)
    g = gain.astype(x32.dtype)
    if unit_offset:
        g = 1.0 + g
    shape = [1] * x.ndim
    shape[axis] = -1
    return x32 * inv * g.reshape(shape)


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = 1e-5, axis: int = -1) -> jax.Array:
    """``(x - mean(x)) / sqrt(var(x) + eps) * g + b`` over ``axis``; the
    statistics and the result float32 at least, as :func:`rms_norm`."""
    x32 = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=axis, keepdims=True)
    shape = [1] * x.ndim
    shape[axis] = -1
    return (x32 - mean) * jax.lax.rsqrt(var + eps) \
        * gain.astype(x32.dtype).reshape(shape) \
        + bias.astype(x32.dtype).reshape(shape)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class RMSNormLayer(Layer):
    """Root-mean-square normalization over the feature axis (no mean, no
    bias). ``unit_offset`` stores the gain round 0 and applies ``1 +
    gamma``. The output keeps the input's type (a float32 residual stream
    stays float32)."""

    n_out: int = 0
    eps: float = 1e-5
    unit_offset: bool = False

    def with_input(self, input_type: InputType) -> "RMSNormLayer":
        if self.n_out:
            return self
        n = input_type.size if isinstance(input_type, RecurrentType) else input_type.flat_size()
        return dataclasses.replace(self, n_out=n)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("gamma",)

    def weight_param_names(self) -> Tuple[str, ...]:
        return ()

    def init(self, key: jax.Array, dtype: Any) -> Params:
        fill = jnp.zeros if self.unit_offset else jnp.ones
        return {"gamma": fill((self.n_out,), dtype)}

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        y = rms_norm(x, params["gamma"], self.eps, self.unit_offset,
                     axis=1 if x.ndim == 3 else -1)
        act = self.activation or Activation.IDENTITY
        return act(y).astype(x.dtype), state
