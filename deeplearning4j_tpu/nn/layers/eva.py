"""EvaByte's decoder block and its parts (EvaByte 6.5B, 2025-01; EVA
attention: Zheng et al. 2023, arXiv:2302.04542).

Parts: :func:`~.norm.rms_norm` (RMSNorm with the unit offset; the standalone
layer is :class:`~.norm.RMSNormLayer`), :func:`rotary_positions` (rotate-half
over the whole head), :func:`gated_silu_ffn` (``Wd (silu(Wg u) * (Wu u))``,
no bias). The mixer is EVA: a query attends, under one softmax, the
singletons of its own aligned window of ``window`` positions and one learned
summary for each ``chunk`` positions of every window closed before it
(``ops/eva_attention.py``).

The mixer OWNS its decode state: one plane for keys and one for values,
``[b, h, S + window, d]`` (``S`` summaries, one a chunk of ``max_len``; then
the open window's singletons), bounded where a K/V cache grows with
``max_len``, and declares both as planes written in place
(:meth:`EvaDecoderBlockLayer.decode_planes`), so the engine's fused step
masks an idle row's writes and selects over neither. Beside them it keeps
the entries of the chunk the row stands in (``[b, h, chunk, d]``, per-row
leaves): the chunk's summary is made from those, because a gather out of a
plane makes the chip's compiler copy the plane into another layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .attention import _merge_heads, _split_heads
from .base import Layer, LayerContext, Params, State, apply_input_dropout
from .norm import rms_norm

_F32 = jnp.float32


def rotary_positions(x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    """Rotary position embedding of ``x`` ``[b, h, t, d]`` at ``positions``
    (``[t]``, or ``[b, t]`` for rows at different positions): rotate-half
    over all ``d`` dimensions, angles in float32, the result in x's type."""
    with jax.named_scope("rope"):
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
        ang = positions.astype(_F32)[..., None] * inv        # [(b,) t, d/2]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
        sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
        if ang.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]            # [b, 1, t, d]
        x32 = x.astype(_F32)
        rot = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]],
                              axis=-1)
        return (x32 * cos + rot * sin).astype(x.dtype)


def gated_silu_ffn(u: jax.Array, wg: jax.Array, wu: jax.Array,
                   wd: jax.Array) -> jax.Array:
    """``(silu(u Wg) * (u Wu)) Wd`` without biases; the gate's product in
    float32, the matmul operands in u's type."""
    gate = jax.nn.silu((u @ wg).astype(_F32)) * (u @ wu).astype(_F32)
    return gate.astype(u.dtype) @ wd


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class EvaDecoderBlockLayer(Layer):
    """``h = x + Attn(N(x; g1))``, ``y = h + FFN(N(h; g2))`` as ONE
    sequential layer (input/output ``[b, n_in, t]``), ``N`` the RMSNorm with
    the unit offset, ``Attn`` EVA with rotary positions, ``FFN`` the gated
    SiLU feed-forward. The residual stream is float32 whatever the
    parameters' type; matmul operands take the parameters' type.

    Decode: with :meth:`decode_state` threaded in, a one-token call writes
    the token's key and value into the open window, rewrites the summary of
    the chunk the row stands in from the chunk's own entries (so nothing is
    conditional on a window closing: an open window's summaries are never
    attended) and attends the two prefixes its position makes valid. A
    multi-token call is a piece of a PREFILL: one whole window of rows that
    stand at a window's first position (the session cuts a long prompt into
    its windows, so what is alive is a window's), or a prompt shorter than
    a window from position 0. A multi-token window at any other position
    (speculative verification) is not expressed, and the speculative
    session refuses the layer's state."""

    n_in: int = 0
    n_heads: int = 1
    ffn_size: int = 0
    window: int = 2048
    chunk: int = 16
    rope_theta: float = 1e5
    eps: float = 1e-5

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError(f"window {self.window} is not a multiple of "
                             f"chunk {self.chunk}")

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "EvaDecoderBlockLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.ffn_size:
            out = dataclasses.replace(out, ffn_size=4 * out.n_in)
        return out

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("g1", "Wq", "Wk", "Wv", "Wo", "mu", "phi",
                "g2", "Wg", "Wu", "Wd")

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Wq", "Wk", "Wv", "Wo", "Wg", "Wu", "Wd")

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, f, n = self.n_in, self.ffn_size, self.n_heads
        d = h // n
        ks = jax.random.split(key, 9)

        def mat(k, rows, cols):
            return init_weights(k, (rows, cols), wi, rows, cols, None, dtype)

        def vec(k):
            return (jax.random.normal(k, (n, d), _F32) * d ** -0.5
                    ).astype(dtype)

        return {
            "g1": jnp.zeros((h,), dtype),
            "Wq": mat(ks[0], h, h), "Wk": mat(ks[1], h, h),
            "Wv": mat(ks[2], h, h), "Wo": mat(ks[3], h, h),
            "mu": vec(ks[4]), "phi": vec(ks[5]),
            "g2": jnp.zeros((h,), dtype),
            "Wg": mat(ks[6], h, f), "Wu": mat(ks[7], h, f),
            "Wd": mat(ks[8], f, h),
        }

    # ---- the decode state and what the layer declares of it ---------------
    def _summaries(self, max_len: int) -> int:
        """Summary entries a row can come to hold: one a chunk of every
        window that ``max_len`` touches."""
        return -(-max_len // self.window) * (self.window // self.chunk)

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        shape = (batch, self.n_heads, self._summaries(max_len) + self.window,
                 self.n_in // self.n_heads)
        chunk = shape[:2] + (self.chunk,) + shape[3:]
        return {"eva_k": jnp.zeros(shape, dtype),
                "eva_v": jnp.zeros(shape, dtype),
                "chunk_k": jnp.zeros(chunk, dtype),
                "chunk_v": jnp.zeros(chunk, dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def decode_planes(self) -> Tuple[str, ...]:
        return ("eva_k", "eva_v")

    def decode_window(self) -> Optional[int]:
        return self.window

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        entry = 2 * self.n_in * itemsize  # a key and a value, every head
        return {"window": (position % self.window) * entry,
                "summary": (self.window // self.chunk)
                * (position // self.window) * entry}

    # ---- attention ----------------------------------------------------------
    def _attend_whole(self, q, k, v, params):
        """Positions from 0, no state: q, k, v ``[b, h, t, d]`` with the
        rotary positions applied -> ``(o, k~, v~, padded k, padded v)``."""
        from ...ops.eva_attention import (chunk_summaries,
                                          eva_prefill_attention)

        b, h, t, d = q.shape
        pad = (-t) % self.window if t > self.window else (-t) % self.chunk
        if pad:  # causal: a pad moves nothing before it
            q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for a in (q, k, v))
        n = (t + pad) // self.chunk
        ks, vs = chunk_summaries(k.reshape(b, h, n, self.chunk, d),
                                 v.reshape(b, h, n, self.chunk, d),
                                 params["mu"], params["phi"])
        o = eva_prefill_attention(q, k, v, ks, vs, self.window, self.chunk)
        return o[:, :, :t], ks, vs, k, v

    def _prefill(self, q, k, v, params, state, mask):
        """One window of a prompt (``t == window``, the rows standing at a
        window's first position), or a whole prompt shorter than a window
        (from position 0): attend, and write the window's singletons and
        its chunks' summaries into the state. The session hands a long
        prompt over a window at a time (``prefill_logits``)."""
        from ...ops import mha_attention
        from ...ops.eva_attention import chunk_summaries

        b, h, t, d = q.shape
        w, c = self.window, self.chunk
        if t > w:
            raise ValueError(
                f"a prefill call takes at most one window ({w} positions), "
                f"got {t}: GenerationSession.prefill_logits cuts a prompt")
        pos = state["pos"].astype(jnp.int32)
        valid = (jnp.full((b,), t, jnp.int32) if mask is None
                 else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
        at = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = rotary_positions(q, at, self.rope_theta)
        k = rotary_positions(k, at, self.rope_theta)
        pk, pv = state["eva_k"], state["eva_v"]
        n_sum = pk.shape[2] - w
        if t < w:  # the only window: nothing before it to attend
            o, ks, vs, k, v = self._attend_whole(q, k, v, params)
        else:
            ks, vs = chunk_summaries(k.reshape(b, h, w // c, c, d),
                                     v.reshape(b, h, w // c, c, d),
                                     params["mu"], params["phi"])

        def written(plane, summ, own):
            """The window's singletons from the plane's window offset on,
            its summaries at the rows' own chunk index."""
            z = jnp.zeros((), jnp.int32)
            plane = jax.lax.dynamic_update_slice(
                plane, own.astype(plane.dtype),
                (z, z, jnp.asarray(n_sum, jnp.int32), z))
            return jax.vmap(
                lambda a, s, i: jax.lax.dynamic_update_slice_in_dim(
                    a, s, i, axis=1))(plane, summ.astype(plane.dtype),
                                      pos // c)

        new_k, new_v = written(pk, ks, k), written(pv, vs, v)
        if t == w:
            # the plane is the keys: the closed windows' summaries (those
            # before the rows' window), then the window itself, causally
            e = jnp.arange(n_sum + w, dtype=jnp.int32)[None, :]
            seen = (e < ((w // c) * (pos // w))[:, None]) | (e >= n_sum)
            o = mha_attention(q, new_k, new_v, mask=seen.astype(_F32),
                              causal=True, scale=d ** -0.5)

        def open_chunk(full):
            full = jnp.pad(full, ((0, 0), (0, 0), (0, c), (0, 0)))
            return jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(
                a, s, c, axis=1))(full, (valid // c) * c
                                  ).astype(state["chunk_k"].dtype)

        new = {"eva_k": new_k, "eva_v": new_v, "chunk_k": open_chunk(k),
               "chunk_v": open_chunk(v), "pos": pos + valid}
        if b > 1:  # a row whose prompt ended before this window stays put
            live = valid > 0
            new = {name: jnp.where(
                live.reshape((b,) + (1,) * (leaf.ndim - 1)), leaf,
                state[name]) for name, leaf in new.items()}
        return o, new

    def _step(self, q, k, v, params, state):
        """One token a row, rows at their own positions."""
        from ...ops import masked_cache_write
        from ...ops.eva_attention import (chunk_summaries,
                                          eva_decode_attention)

        w, c = self.window, self.chunk
        pos = state["pos"].astype(jnp.int32)
        keep = state.get("write_mask")
        if keep is None:
            keep = jnp.ones(pos.shape, bool)
        q = rotary_positions(q, pos[:, None], self.rope_theta)
        k = rotary_positions(k, pos[:, None], self.rope_theta)
        n_sum = state["eva_k"].shape[2] - w
        r = pos % w
        pk = masked_cache_write(state["eva_k"], k, n_sum + r, keep)
        pv = masked_cache_write(state["eva_v"], v, n_sum + r, keep)
        # the chunk the row stands in, from its own entries so far (what
        # a chunk before it left beyond them is masked off)
        at = jnp.arange(c, dtype=jnp.int32)[None, :]
        here = (at == (r % c)[:, None])[:, None, :, None]
        ck = jnp.where(here, k.astype(pk.dtype), state["chunk_k"])
        cv = jnp.where(here, v.astype(pv.dtype), state["chunk_v"])
        ks, vs = chunk_summaries(ck[:, :, None], cv[:, :, None], params["mu"],
                                 params["phi"], at <= (r % c)[:, None])
        pk = masked_cache_write(pk, ks, pos // c, keep)
        pv = masked_cache_write(pv, vs, pos // c, keep)
        o = eva_decode_attention(q, pk, pv, (w // c) * (pos // w), r + 1,
                                 n_sum)
        return o, {"eva_k": pk, "eva_v": pv, "chunk_k": ck, "chunk_v": cv,
                   "pos": pos + 1}

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1)                            # [b, t, h]
        xt = xt.astype(jnp.promote_types(xt.dtype, _F32))    # the residual
        cd = params["Wq"].dtype
        u = rms_norm(xt, params["g1"], self.eps, True).astype(cd)
        q, k, v = (_split_heads(u @ params[n], self.n_heads)
                   for n in ("Wq", "Wk", "Wv"))
        if "eva_k" not in state:
            pos = jnp.arange(q.shape[2], dtype=jnp.int32)
            o = self._attend_whole(
                rotary_positions(q, pos, self.rope_theta),
                rotary_positions(k, pos, self.rope_theta), v, params)[0]
            new_state = state
        elif q.shape[2] == 1:
            o, new_state = self._step(q, k, v, params, state)
        else:
            o, new_state = self._prefill(q, k, v, params, state, ctx.mask)
        h = xt + (_merge_heads(o) @ params["Wo"]).astype(xt.dtype)
        u = rms_norm(h, params["g2"], self.eps, True).astype(cd)
        y = h + gated_silu_ffn(u, params["Wg"], params["Wu"],
                               params["Wd"]).astype(xt.dtype)
        return y.transpose(0, 2, 1), new_state
