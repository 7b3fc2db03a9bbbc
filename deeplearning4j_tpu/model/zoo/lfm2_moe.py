"""Lfm2MoeLM — LFM2-8B-A1B's decoder (LiquidAI, 2025-10, ``lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-8B-A1B) for autoregressive generation
serving, whole or as the leading layers of it (a pipeline stage).

Token embedding → one :class:`~deeplearning4j_tpu.nn.layers.DecoderBlockLayer`
a layer (``h = x + Op(N(x))``, ``y = h + FF(N(h))``; RMSNorm, no bias
anywhere, a float32 residual stream) → final RMSNorm → a head that shares
the embedding's matrix. The block's parts follow the published lists:

* ``Op`` by ``layer_types[i]``: ``"conv"`` a gated short convolution
  (:class:`~deeplearning4j_tpu.nn.layers.ShortConvLayer`, ``conv_L_cache``
  taps, a rolling state of ``conv_L_cache - 1`` columns a row),
  ``"full_attention"`` grouped-query attention with QK-norm and rotary
  positions (:class:`~deeplearning4j_tpu.nn.layers.GroupedQueryAttentionLayer`,
  a K/V cache of ``n_kv_heads`` heads);
* ``FF``: the dense gated feed-forward at ``ffn_size`` for the first
  ``n_dense_layers`` layers, then an expert layer
  (:class:`~deeplearning4j_tpu.nn.layers.ExpertShareMoELayer`): every one
  of the ``n_experts`` held, scored by sigmoid, ``top_k`` chosen by score +
  the served ``expert_bias``, their weights renormalised.

The published sizes: hidden 2048, 24 layers (attention at 2, 6, 10, 14, 18,
21, convolution elsewhere), 32 query heads and 8 K/V heads of 64, dense FFN
7168 in the first two layers, 32 experts of 1792 top-4 from there on,
3 taps, vocabulary 65536, rope theta 1e6, eps 1e-5: 8.3 B parameters. A
stage of a pipeline gives the first entries of ``layer_types``; nothing
here stands in for the layers it leaves out. The defaults are toy widths.
"""

from __future__ import annotations

from typing import Sequence

from ...nn import NeuralNetConfiguration, WeightInit
from ...nn.layers import (
    DecoderBlockLayer,
    EmbeddingSequenceLayer,
    ExpertShareMoELayer,
    GatedFFNLayer,
    GroupedQueryAttentionLayer,
    RMSNormLayer,
    ShortConvLayer,
    TiedRnnOutputLayer,
)
from ...nn.sequential import MultiLayerNetwork
from ...train.updaters import Adam


class Lfm2MoeLM:
    def __init__(
        self,
        vocab_size: int = 512,
        hidden: int = 64,
        layer_types: Sequence[str] = ("conv", "conv", "full_attention",
                                      "conv"),
        n_dense_layers: int = 2,
        n_heads: int = 4,
        n_kv_heads: int = 2,
        ffn_size: int = 0,
        expert_ffn_size: int = 0,
        n_experts: int = 8,
        top_k: int = 2,
        conv_L_cache: int = 3,
        norm_topk_prob: bool = True,
        use_expert_bias: bool = True,
        routed_scaling_factor: float = 1.0,
        rope_theta: float = 1e6,
        max_len: int = 128000,
        seed: int = 123,
        updater=None,
        dtype: str = "float32",
        eps: float = 1e-5,
    ) -> None:
        unknown = set(layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types: unknown {sorted(unknown)}")
        if not use_expert_bias:
            raise ValueError("use_expert_bias=False: serve a bias of zeros")
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layer_types = tuple(layer_types)
        self.n_dense_layers = n_dense_layers
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.ffn_size = ffn_size or 4 * hidden
        self.expert_ffn_size = expert_ffn_size or hidden
        self.n_experts = n_experts
        self.top_k = top_k
        self.conv_L_cache = conv_L_cache
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_theta = float(rope_theta)
        self.max_len = max_len  # positions are rotary: nothing is sized by it
        self.seed = seed
        self.updater = updater or Adam(1e-4)
        self.dtype = dtype
        self.eps = eps

    def block(self, i: int) -> DecoderBlockLayer:
        """Layer ``i`` of the published lists as a block of its parts."""
        h = self.hidden
        if self.layer_types[i] == "conv":
            mixer = ShortConvLayer(n_in=h, kernel=self.conv_L_cache)
        else:
            mixer = GroupedQueryAttentionLayer(
                n_in=h, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                rope_theta=self.rope_theta, eps=self.eps)
        if i < self.n_dense_layers:
            ffn = GatedFFNLayer(n_in=h, hidden=self.ffn_size)
        else:
            ffn = ExpertShareMoELayer(
                n_in=h, hidden=self.expert_ffn_size,
                n_routed_experts=self.n_experts, top_k=self.top_k,
                scoring="sigmoid", norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor)
        return DecoderBlockLayer(n_in=h, mixer=mixer, ffn=ffn, eps=self.eps)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).data_type(self.dtype).updater(self.updater)
             .weight_init(WeightInit.XAVIER).list())
        b.layer(EmbeddingSequenceLayer(n_in=self.vocab_size,
                                       n_out=self.hidden))
        for i in range(len(self.layer_types)):
            b.layer(self.block(i))
        b.layer(RMSNormLayer(n_out=self.hidden, eps=self.eps))
        b.layer(TiedRnnOutputLayer(n_in=self.hidden, n_out=self.vocab_size,
                                   tied_layer=0))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()
