"""A SambaY cross-decoder (Ren et al. 2025, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation") as ONE
sequential layer: the second half of a decoder's layers, whose mixers read
what two of them compute, so the layer that holds them all passes it
along.

Of layers ``half .. n_layers - 1`` (``half = n_layers // 2``), each a
:class:`~.decoder_block.DecoderBlockLayer` (LayerNorm with a bias, the
dense gated feed-forward):

* layer ``half``: a Mamba mixer (:class:`~.mamba.MambaMixerLayer`) whose
  scan output before its gate is the MEMORY ``m`` of every later GMU;
* layer ``half + 1``: full differential attention
  (:class:`~.diff_attention.DifferentialAttentionLayer`, ``"full"``), which
  writes THE K/V cache of the model's second half;
* every later layer ``i``: a gated memory unit (:class:`~.gmu.
  GatedMemoryLayer`) over ``m`` where ``i % mb_per_layer == 0``, else
  cross differential attention (``"cross"``: queries alone) over that one
  cache.

Parameters: layer ``half + k``'s under ``p{k}_`` (``p0_op_Win``,
``p1_op_Wq``, ...). Decode state: layer ``half``'s ``ssm`` and ``conv`` and
layer ``half + 1``'s ``cache_k``, ``cache_v`` (the planes) and ``pos``: the
GMUs and the cross layers keep nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from .base import (Layer, LayerContext, Params, State, apply_input_dropout,
                   sub_params)
from .decoder_block import DecoderBlockLayer, GatedFFNLayer
from .diff_attention import DifferentialAttentionLayer
from .gmu import GatedMemoryLayer
from .mamba import MambaMixerLayer

_F32 = jnp.float32
_MAMBA = ("ssm", "conv")


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class CrossDecoderLayer(Layer):
    """Layers ``n_layers // 2 ..`` of a SambaY decoder (input/output ``[b,
    n_in, t]``), the residual stream float32."""

    n_in: int = 0
    n_layers: int = 4
    mb_per_layer: int = 2
    n_heads: int = 2
    n_kv_heads: int = 2
    ffn_size: int = 0
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    eps: float = 1e-5

    pages_decode_planes = True

    @property
    def half(self) -> int:
        return self.n_layers // 2

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "CrossDecoderLayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.ffn_size:
            out = dataclasses.replace(out, ffn_size=4 * out.n_in)
        if not out.d_inner:
            out = dataclasses.replace(out, d_inner=2 * out.n_in)
        if not out.dt_rank:
            out = dataclasses.replace(out, dt_rank=-(-out.n_in // 16))
        return out

    @property
    def parts(self) -> Tuple[DecoderBlockLayer, ...]:
        h, out = self.n_in, []
        for i in range(self.half, self.n_layers):
            if i == self.half:
                mixer = MambaMixerLayer(
                    n_in=h, d_inner=self.d_inner, d_state=self.d_state,
                    d_conv=self.d_conv, dt_rank=self.dt_rank)
            elif i == self.half + 1 or i % self.mb_per_layer:
                mixer = DifferentialAttentionLayer(
                    n_in=h, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                    kind="full" if i == self.half + 1 else "cross",
                    layer_index=i, eps=self.eps)
            else:
                mixer = GatedMemoryLayer(n_in=h, d_memory=self.d_inner)
            out.append(DecoderBlockLayer(
                n_in=h, mixer=mixer, ffn=GatedFFNLayer(
                    n_in=h, hidden=self.ffn_size), eps=self.eps,
                norm="layer", weight_init=self.weight_init))
        return tuple(out)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return tuple(f"p{k}_{n}" for k, part in enumerate(self.parts)
                     for n in part.trainable_param_names())

    def weight_param_names(self) -> Tuple[str, ...]:
        return tuple(f"p{k}_{n}" for k, part in enumerate(self.parts)
                     for n in part.weight_param_names())

    def init(self, key: jax.Array, dtype: Any) -> Params:
        out: Dict[str, jax.Array] = {}
        for k, (part, kk) in enumerate(zip(
                self.parts, jax.random.split(key, len(self.parts)))):
            out |= {f"p{k}_{n}": v for n, v in part.init(kk, dtype).items()}
        return out

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        return {**self.parts[0].decode_state(batch, max_len, dtype),
                **self.parts[1].decode_state(batch, max_len, dtype)}

    def decode_planes(self) -> Tuple[str, ...]:
        return self.parts[1].decode_planes()

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        return {**self.parts[0].decode_live_bytes(position, itemsize),
                **self.parts[1].decode_live_bytes(position, itemsize)}

    # ---- forward ------------------------------------------------------------
    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        y = x.transpose(0, 2, 1)                             # [b, t, h]
        y = y.astype(jnp.promote_types(y.dtype, _F32))       # the residual
        mask, parts = ctx.mask, self.parts

        def run(k, st, **more):
            part = parts[k]
            return part.block(sub_params(params, f"p{k}_"), st, y, mask,
                              mix=functools.partial(part.mixer.mix, **more))

        y, new0, _, (memory,) = run(
            0, {n: state[n] for n in _MAMBA if n in state}, tap=True)
        y, new1, _, (shared,) = run(
            1, {n: v for n, v in state.items() if n not in _MAMBA})
        for k in range(2, len(parts)):
            if isinstance(parts[k].mixer, GatedMemoryLayer):
                y, *_ = run(k, {}, memory=memory)
            else:
                y, *_ = run(k, {}, shared=shared)
        new_state = {**new0, **new1} if state else state
        return y.transpose(0, 2, 1), new_state
