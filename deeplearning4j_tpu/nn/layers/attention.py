"""Attention layers.

Reference: org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer, RecurrentAttentionLayer} and the SameDiff
``multiHeadDotProductAttention`` op (SURVEY.md §5.7).

TPU design: attention is expressed as einsums that XLA maps to MXU matmuls.
The masked-softmax uses an additive -inf bias (no data-dependent shapes). A
Pallas flash-attention kernel can be slotted in as the accelerated helper for
long sequences (ops/pallas) — the layer semantics here are the reference ones.

Data format follows the recurrent convention [batch, features, time]; heads
are split internally.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..activations import Activation
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import Layer, LayerContext, Params, State, apply_input_dropout


def dot_product_attention(
    q: jax.Array,  # [b, h, tq, d]
    k: jax.Array,  # [b, h, tk, d]
    v: jax.Array,  # [b, h, tk, dv]
    mask: Optional[jax.Array] = None,  # [b, tk]
    scaled: bool = True,
    causal: bool = False,
) -> jax.Array:
    # Routed through the helper seam (ops.mha_attention): builtin XLA einsum
    # path or the Pallas flash kernel, mirroring the reference's per-layer
    # cuDNN-helper probe (SURVEY.md §2.2 "Helper SPI").
    from ...ops import mha_attention

    scale = 1.0 / math.sqrt(q.shape[-1]) if scaled else 1.0
    return mha_attention(q, k, v, mask=mask, scale=scale, causal=causal)


def _cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` [b, h, t, d] into the static-shape cache [b, h, L, d]
    at per-row positions ``pos + [0, t)`` — the position-indexed
    ``lax.dynamic_update_slice`` that keeps every decode step the same
    compiled shape regardless of how far each sequence has advanced."""
    def row(c, n, p):
        z = jnp.zeros((), p.dtype)  # homogeneous index dtypes (x64-safe)
        return jax.lax.dynamic_update_slice(c, n, (z, p, z))

    return jax.vmap(row)(cache, new.astype(cache.dtype),
                         pos.astype(jnp.int32))


def _scale_write(scales: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Per-slot scale counterpart of :func:`_cache_write`: write ``new``
    [b, h, t] into the scale plane [b, h, L] at ``pos + [0, t)``."""
    def row(c, n, p):
        z = jnp.zeros((), p.dtype)
        return jax.lax.dynamic_update_slice(c, n, (z, p))

    return jax.vmap(row)(scales, new.astype(scales.dtype),
                         pos.astype(jnp.int32))


def quantize_kv_rows(x: jax.Array, eps: float = 1e-8):
    """Symmetric per-(row, head, position) int8 quantization of a K/V
    write [b, h, t, d]: the scale is the absmax over the head dim, so one
    f32 scale rides each cached slot. Returns ``(q int8, scale f32
    [b, h, t])``; dequant is ``q * scale[..., None]``."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _cached_attention(q, k_new, v_new, state, mask):
    """Shared KV-cache attention step: write this call's K/V into the
    cache at each row's position, then attend causally against the cache.
    Returns (output, new_state). ``mask`` (the prompt's [b, t] validity
    mask) bounds how far ``pos`` advances, so right-padded prefill rows
    keep their true length and the pad slots are overwritten by later
    decode steps before anything ever attends to them.

    An int8 cache (``cache_dtype="int8"`` on the session/engine — the
    state then carries ``cache_k_scale``/``cache_v_scale`` planes) writes
    quantized slots with per-slot/per-head scales and dequantizes inside
    :func:`~deeplearning4j_tpu.ops.decode_attention`'s reference path —
    the resident cache holds ~1/2 the bytes of an fp16 cache (1/4 of
    f32), so the same HBM budget fits ~2× the concurrent sequences."""
    from ...ops import (decode_attention, decode_write_fuses,
                        flash_decode_attention, masked_cache_write)

    t = q.shape[2]
    pos = state["pos"].astype(jnp.int32)
    valid = (jnp.asarray(t, jnp.int32) if mask is None
             else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
    if "block_table" in state:  # paged KV cache (shared block pools)
        from ...ops import paged_cache_write, paged_decode_attention

        table = state["block_table"]
        if "cache_k_scale" in state:  # int8 blocks + f32 scale pools
            kq, ks = quantize_kv_rows(k_new)
            vq, vs = quantize_kv_rows(v_new)
            cache_k = paged_cache_write(state["cache_k"], kq, table, pos)
            cache_v = paged_cache_write(state["cache_v"], vq, table, pos)
            k_scale = paged_cache_write(state["cache_k_scale"], ks,
                                        table, pos)
            v_scale = paged_cache_write(state["cache_v_scale"], vs,
                                        table, pos)
            o = paged_decode_attention(q, cache_k, cache_v, table, pos,
                                       k_scale=k_scale, v_scale=v_scale)
            new_state = {"cache_k": cache_k, "cache_v": cache_v,
                         "cache_k_scale": k_scale, "cache_v_scale": v_scale,
                         "block_table": table, "pos": pos + valid}
            return o, new_state
        cache_k = paged_cache_write(state["cache_k"], k_new, table, pos)
        cache_v = paged_cache_write(state["cache_v"], v_new, table, pos)
        o = paged_decode_attention(q, cache_k, cache_v, table, pos)
        new_state = {"cache_k": cache_k, "cache_v": cache_v,
                     "block_table": table, "pos": pos + valid}
        return o, new_state
    # a fused batch step's idle rows (generate/paged.py
    # mask_inactive_writes) write nothing
    keep = state.get("write_mask")
    if keep is None:
        write, write_scale = _cache_write, _scale_write
    else:
        write = write_scale = functools.partial(masked_cache_write,
                                                write_mask=keep)
    if "cache_k_scale" in state:  # int8 KV cache
        kq, ks = quantize_kv_rows(k_new)
        vq, vs = quantize_kv_rows(v_new)
        cache_k = write(state["cache_k"], kq, pos)
        cache_v = write(state["cache_v"], vq, pos)
        k_scale = write_scale(state["cache_k_scale"], ks, pos)
        v_scale = write_scale(state["cache_v_scale"], vs, pos)
        o = decode_attention(q, cache_k, cache_v, pos,
                             k_scale=k_scale, v_scale=v_scale)
        new_state = {"cache_k": cache_k, "cache_v": cache_v,
                     "cache_k_scale": k_scale, "cache_v_scale": v_scale,
                     "pos": pos + valid}
        return o, new_state
    if decode_write_fuses(q, state["cache_k"]):
        # the decode kernel writes the step's entries into the planes it
        # reads (aliased): no separate write of either plane
        o, cache_k, cache_v = flash_decode_attention(
            q, state["cache_k"], state["cache_v"], pos, new=(k_new, v_new),
            write_mask=keep)
        return o, {"cache_k": cache_k, "cache_v": cache_v, "pos": pos + valid}
    cache_k = write(state["cache_k"], k_new, pos)
    cache_v = write(state["cache_v"], v_new, pos)
    # query i at absolute position pos+i attends cache [0, pos+i]; the
    # single-token hot path (t == 1) dispatches to the flash decode kernel
    o = decode_attention(q, cache_k, cache_v, pos)
    new_state = {"cache_k": cache_k, "cache_v": cache_v, "pos": pos + valid}
    return o, new_state


#: the K/V layers' decode planes: the caches and, for an int8 cache
#: (``generate/session.py`` ``quantize_decode_state``), their scale planes
KV_PLANES = ("cache_k", "cache_v", "cache_k_scale", "cache_v_scale")


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, f = x.shape
    return x.reshape(b, t, n_heads, f // n_heads).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class SelfAttentionLayer(Layer):
    """Multi-head dot-product self-attention (reference: SelfAttentionLayer).
    Input/output [b, f, t]. With ``project_input`` learns Wq/Wk/Wv/Wo.

    ``causal=True`` masks attention to positions <= the query's (an
    autoregressive decoder block) and unlocks the KV-cached incremental
    decode path: when the per-sequence decode carry from
    :meth:`decode_state` is threaded in through ``apply``'s state, each
    call writes its K/V into the static-shape cache and attends against
    it instead of re-running the prefix."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    project_input: bool = True
    causal: bool = False

    def __post_init__(self):
        if self.n_out and not self.head_size:
            object.__setattr__(self, "head_size", self.n_out // self.n_heads)

    def output_type(self, input_type: InputType) -> InputType:
        size = self.n_out if self.project_input else input_type.size
        return RecurrentType(size=size, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "SelfAttentionLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.n_out and not out.project_input:
            out = dataclasses.replace(out, n_out=input_type.size)
        if out.n_out and not out.head_size:
            out = dataclasses.replace(out, head_size=out.n_out // out.n_heads)
        return out

    def has_params(self) -> bool:
        return self.project_input

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wq", "Wk", "Wv", "Wo") if self.project_input else ()

    def init(self, key: jax.Array, dtype: Any) -> Params:
        if not self.project_input:
            return {}
        wi = self.weight_init or WeightInit.XAVIER
        hs = self.n_heads * self.head_size
        ks = jax.random.split(key, 4)
        return {
            "Wq": init_weights(ks[0], (self.n_in, hs), wi, self.n_in, hs, None, dtype),
            "Wk": init_weights(ks[1], (self.n_in, hs), wi, self.n_in, hs, None, dtype),
            "Wv": init_weights(ks[2], (self.n_in, hs), wi, self.n_in, hs, None, dtype),
            "Wo": init_weights(ks[3], (hs, self.n_out), wi, hs, self.n_out, None, dtype),
        }

    pages_decode_planes = True

    def decode_planes(self) -> Tuple[str, ...]:
        return KV_PLANES if self.causal else ()

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        if not self.causal:
            return {}  # bidirectional attention has no incremental decode
        d = (self.head_size if self.project_input
             else self.n_in // self.n_heads)
        shape = (batch, self.n_heads, max_len, d)
        return {"cache_k": jnp.zeros(shape, dtype),
                "cache_v": jnp.zeros(shape, dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1)  # [b, t, f]
        if self.project_input:
            q = _split_heads(xt @ params["Wq"], self.n_heads)
            k = _split_heads(xt @ params["Wk"], self.n_heads)
            v = _split_heads(xt @ params["Wv"], self.n_heads)
        else:
            q = k = v = _split_heads(xt, self.n_heads)
        if "cache_k" in state:
            if not self.causal:
                raise ValueError(
                    "KV-cached decode requires causal=True — bidirectional "
                    "attention cannot be decoded incrementally")
            o, new_state = _cached_attention(q, k, v, state, ctx.mask)
        else:
            o = dot_product_attention(q, k, v, mask=ctx.mask,
                                      causal=self.causal)
            new_state = state
        o = _merge_heads(o)
        if self.project_input:
            o = o @ params["Wo"]
        act = self.activation or Activation.IDENTITY
        return act(o).transpose(0, 2, 1), new_state


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class LearnedSelfAttentionLayer(Layer):
    """Attention with learned query vectors (reference:
    LearnedSelfAttentionLayer): output has fixed n_queries timesteps."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    n_queries: int = 1
    project_input: bool = True

    def __post_init__(self):
        if self.n_out and not self.head_size:
            object.__setattr__(self, "head_size", self.n_out // self.n_heads)

    def output_type(self, input_type: InputType) -> InputType:
        size = self.n_out if self.project_input else input_type.size
        return RecurrentType(size=size, timesteps=self.n_queries)

    def with_input(self, input_type: InputType) -> "LearnedSelfAttentionLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.n_out and not out.project_input:
            out = dataclasses.replace(out, n_out=input_type.size)
        if out.n_out and not out.head_size:
            out = dataclasses.replace(out, head_size=out.n_out // out.n_heads)
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        base = ("Q",)
        return base + (("Wq", "Wk", "Wv", "Wo") if self.project_input else ())

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        hs = self.n_heads * self.head_size if self.project_input else self.n_in
        ks = jax.random.split(key, 5)
        p: Params = {"Q": init_weights(ks[4], (self.n_queries, hs), wi, hs, hs, None, dtype)}
        if self.project_input:
            p.update({
                "Wq": init_weights(ks[0], (hs, hs), wi, hs, hs, None, dtype),
                "Wk": init_weights(ks[1], (self.n_in, hs), wi, self.n_in, hs, None, dtype),
                "Wv": init_weights(ks[2], (self.n_in, hs), wi, self.n_in, hs, None, dtype),
                "Wo": init_weights(ks[3], (hs, self.n_out), wi, hs, self.n_out, None, dtype),
            })
        return p

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        b = x.shape[0]
        xt = x.transpose(0, 2, 1)
        queries = jnp.broadcast_to(params["Q"], (b,) + params["Q"].shape)
        if self.project_input:
            q = _split_heads(queries @ params["Wq"], self.n_heads)
            k = _split_heads(xt @ params["Wk"], self.n_heads)
            v = _split_heads(xt @ params["Wv"], self.n_heads)
        else:
            q = _split_heads(queries, self.n_heads)
            k = v = _split_heads(xt, self.n_heads)
        o = _merge_heads(dot_product_attention(q, k, v, mask=ctx.mask))
        if self.project_input:
            o = o @ params["Wo"]
        act = self.activation or Activation.IDENTITY
        return act(o).transpose(0, 2, 1), state

    def feed_forward_mask(self, mask, input_type):
        return None  # output timesteps are the learned queries — all valid


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class RecurrentAttentionLayer(Layer):
    """Recurrent cell attending over the input sequence at each step
    (reference: RecurrentAttentionLayer): h_t = act(x_t W + h_{t-1} RW +
    attn(h_{t-1}, X) Wa + b).

    The ``h`` carry threads through ``apply`` state (rnnTimeStep
    semantics — streaming calls resume instead of re-running the prefix).
    ``causal=True`` restricts step t's attention to inputs [0, t] — the
    autoregressive mode required for incremental decode, where the decode
    carry from :meth:`decode_state` additionally caches past inputs so a
    single-step call attends over everything seen so far."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    causal: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_out, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "RecurrentAttentionLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("W", "RW", "Wa", "b")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        ks = jax.random.split(key, 3)
        return {
            "W": init_weights(ks[0], (self.n_in, self.n_out), wi, self.n_in, self.n_out, None, dtype),
            "RW": init_weights(ks[1], (self.n_out, self.n_out), wi, self.n_out, self.n_out, None, dtype),
            "Wa": init_weights(ks[2], (self.n_in, self.n_out), wi, self.n_in, self.n_out, None, dtype),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        if not self.causal:
            return {}  # future-peeking attention has no incremental decode
        return {"h": jnp.zeros((batch, self.n_out), dtype),
                "cache_x": jnp.zeros((batch, max_len, self.n_in), dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        b, f, t = x.shape
        act = self.activation or Activation.TANH
        xt = x.transpose(2, 0, 1)  # [t, b, f]
        x_proj = jnp.einsum("tbf,fo->tbo", xt, params["W"]) + params["b"]
        mask = ctx.mask
        cache = state.get("cache_x")
        if cache is not None and not self.causal:
            raise ValueError("cached decode requires causal=True — a step "
                             "cannot attend inputs that do not exist yet")
        pos = None
        if cache is None:
            keys = x.transpose(0, 2, 1)  # [b, t, f]
        else:
            pos = state["pos"].astype(jnp.int32)
            keys = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (p, jnp.zeros((), p.dtype))))(
                    cache, x.transpose(0, 2, 1).astype(cache.dtype), pos)
        t_keys = keys.shape[1]
        neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)

        # freeze h through right-pad steps only on the cached (prefill)
        # path: the full-sequence training path keeps its semantics; the
        # padding-key mask only applies when keys == this call's input
        # (cached keys are masked by the causal frontier instead)
        use_m = mask is not None and cache is not None

        def step(h, inp):
            if use_m:
                xp, i, m = inp
            else:
                (xp, i), m = inp, None
            # attention of h over the (cached) input sequence
            scores = jnp.einsum("bo,fo,btf->bt", h, params["Wa"], keys) / math.sqrt(f)
            if mask is not None and cache is None:
                scores = jnp.where(mask > 0, scores, neg)
            if self.causal:
                limit = i if pos is None else pos[:, None] + i
                ids = jnp.arange(t_keys, dtype=jnp.int32)[None, :]
                scores = jnp.where(ids <= limit, scores, neg)
            w = jax.nn.softmax(scores, axis=-1)
            attended = jnp.einsum("bt,btf->bf", w, keys)  # [b, f]
            h_new = act(xp + h @ params["RW"] + attended @ params["Wa"])
            if m is not None:
                mm = m[:, None]
                h_new = mm * h_new + (1.0 - mm) * h
            return h_new, h_new

        h0 = state.get("h")
        if h0 is None:
            h0 = jnp.zeros((b, self.n_out), x.dtype)
        steps = jnp.arange(t, dtype=jnp.int32)
        xs = ((x_proj, steps, mask.T.astype(x.dtype)) if use_m
              else (x_proj, steps))
        h_f, hs = jax.lax.scan(step, h0, xs)
        out_state: State = {"h": h_f}
        if cache is not None:
            valid = (jnp.asarray(t, jnp.int32) if mask is None
                     else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
            out_state.update({"cache_x": keys, "pos": pos + valid})
        return hs.transpose(1, 2, 0), out_state


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class TransformerDecoderBlockLayer(Layer):
    """Pre-LN causal transformer decoder block as ONE sequential layer:
    x + CausalAttn(LN(x)), then x + FFN(LN(x)) — residuals internal, so
    autoregressive stacks compose in a MultiLayerNetwork (whose
    ``rnn_state`` channel threads the KV cache; ComputationGraph has no
    transient-state carry). Input/output [b, n_in, t].

    Decode: :meth:`decode_state` preallocates the static-shape
    ``[b, heads, max_len, head_dim]`` K/V cache + position counter; with
    it threaded in, each ``apply`` writes the new K/V at the per-row
    position (``lax.dynamic_update_slice``) and runs single-query flash
    decode attention against the cache — the prefix is never re-run."""

    n_in: int = 0
    n_heads: int = 1
    ffn_size: int = 0
    eps: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "TransformerDecoderBlockLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.ffn_size:
            out = dataclasses.replace(out, ffn_size=4 * out.n_in)
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("ln1_g", "ln1_b", "Wq", "Wk", "Wv", "Wo",
                "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Wq", "Wk", "Wv", "Wo", "W1", "W2")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, ffn = self.n_in, self.ffn_size
        ks = jax.random.split(key, 6)
        return {
            "ln1_g": jnp.ones((h,), dtype), "ln1_b": jnp.zeros((h,), dtype),
            "Wq": init_weights(ks[0], (h, h), wi, h, h, None, dtype),
            "Wk": init_weights(ks[1], (h, h), wi, h, h, None, dtype),
            "Wv": init_weights(ks[2], (h, h), wi, h, h, None, dtype),
            "Wo": init_weights(ks[3], (h, h), wi, h, h, None, dtype),
            "ln2_g": jnp.ones((h,), dtype), "ln2_b": jnp.zeros((h,), dtype),
            "W1": init_weights(ks[4], (h, ffn), wi, h, ffn, None, dtype),
            "b1": jnp.zeros((ffn,), dtype),
            "W2": init_weights(ks[5], (ffn, h), wi, ffn, h, None, dtype),
            "b2": jnp.zeros((h,), dtype),
        }

    pages_decode_planes = True

    def decode_planes(self) -> Tuple[str, ...]:
        return KV_PLANES

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        d = self.n_in // self.n_heads
        shape = (batch, self.n_heads, max_len, d)
        return {"cache_k": jnp.zeros(shape, dtype),
                "cache_v": jnp.zeros(shape, dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def _ln(self, x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + self.eps) * g + b

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1)  # [b, t, h]
        h1 = self._ln(xt, params["ln1_g"], params["ln1_b"])
        q = _split_heads(h1 @ params["Wq"], self.n_heads)
        k = _split_heads(h1 @ params["Wk"], self.n_heads)
        v = _split_heads(h1 @ params["Wv"], self.n_heads)
        if "cache_k" in state:
            o, new_state = _cached_attention(q, k, v, state, ctx.mask)
        else:
            o = dot_product_attention(q, k, v, mask=ctx.mask, causal=True)
            new_state = state
        r1 = xt + _merge_heads(o) @ params["Wo"]
        h2 = self._ln(r1, params["ln2_g"], params["ln2_b"])
        act = self.activation or Activation.GELU
        ffn = act(h2 @ params["W1"] + params["b1"]) @ params["W2"] + params["b2"]
        return (r1 + ffn).transpose(0, 2, 1), new_state
