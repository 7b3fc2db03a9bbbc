"""LongCatFlashLM — LongCat-Flash's decoder (LongCat-Flash-Chat 560B-A27B,
2025-09, https://huggingface.co/meituan-longcat/LongCat-Flash-Chat) for
autoregressive generation serving, whole or as ONE CHIP'S SHARE of it.

Token embedding → N :class:`~deeplearning4j_tpu.nn.layers.LongCatBlockLayer`
double layers (two latent-attention blocks and two dense gated feed-forwards
a layer, a shortcut-connected mixture of experts beside the second block;
RMSNorm, no bias anywhere, a float32 residual stream) → final RMSNorm → an
untied head.

The published sizes: hidden 6144, 28 layers, 64 heads of 128 non-rotary +
64 rotary query/key numbers and 128 value numbers, ranks 1536 (queries) and
512 (keys/values), dense FFN 12288, 512 routed experts of width 2048 beside
256 zero-compute (identity) experts, 12 experts a token without
renormalisation, scaling factor 6, vocabulary 131072, rope theta 1e7. The
whole model is 560 B parameters: what one chip serves is a SHARE, as expert
parallelism divides a layer: ``n_held_experts`` of the routed experts from
``first_held_expert`` on (the router keeps its published width and its
experts a token; what an absent expert would add is another chip's and is
left out), ``vocab_size`` rows of the vocabulary, ``n_layers`` of the
depth. The defaults are toy widths.
"""

from __future__ import annotations

from ...nn import NeuralNetConfiguration, WeightInit
from ...nn.layers import (
    EmbeddingSequenceLayer,
    LongCatBlockLayer,
    MultiTokenRnnOutputLayer,
    RMSNormLayer,
)
from ...nn.sequential import MultiLayerNetwork
from ...train.updaters import Adam


class LongCatFlashLM:
    def __init__(
        self,
        vocab_size: int = 512,
        hidden: int = 64,
        n_layers: int = 2,
        n_heads: int = 4,
        qk_nope_head_dim: int = 16,
        qk_rope_head_dim: int = 8,
        v_head_dim: int = 16,
        q_lora_rank: int = 32,
        kv_lora_rank: int = 16,
        ffn_size: int = 0,
        expert_ffn_size: int = 0,
        n_routed_experts: int = 16,
        zero_expert_num: int = 8,
        n_held_experts: int = 0,
        first_held_expert: int = 0,
        moe_topk: int = 4,
        routed_scaling_factor: float = 6.0,
        rope_theta: float = 1e7,
        max_len: int = 131072,
        seed: int = 123,
        updater=None,
        dtype: str = "float32",
        eps: float = 1e-5,
    ) -> None:
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.ffn_size = ffn_size or 2 * hidden
        self.expert_ffn_size = expert_ffn_size or hidden // 2
        self.n_routed_experts = n_routed_experts
        self.zero_expert_num = zero_expert_num
        self.n_held_experts = n_held_experts or n_routed_experts
        self.first_held_expert = first_held_expert
        self.moe_topk = moe_topk
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_theta = float(rope_theta)
        self.max_len = max_len  # positions are rotary: nothing is sized by it
        self.seed = seed
        self.updater = updater or Adam(1e-4)
        self.dtype = dtype
        self.eps = eps

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).data_type(self.dtype).updater(self.updater)
             .weight_init(WeightInit.XAVIER).list())
        b.layer(EmbeddingSequenceLayer(n_in=self.vocab_size,
                                       n_out=self.hidden))
        for _ in range(self.n_layers):
            b.layer(LongCatBlockLayer(
                n_in=self.hidden, n_heads=self.n_heads,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank, ffn_size=self.ffn_size,
                expert_ffn_size=self.expert_ffn_size,
                n_routed_experts=self.n_routed_experts,
                zero_expert_num=self.zero_expert_num,
                n_held_experts=self.n_held_experts,
                first_held_expert=self.first_held_expert,
                moe_topk=self.moe_topk,
                routed_scaling_factor=self.routed_scaling_factor,
                rope_theta=self.rope_theta, eps=self.eps))
        b.layer(RMSNormLayer(n_out=self.hidden, eps=self.eps))
        b.layer(MultiTokenRnnOutputLayer(n_in=self.hidden,
                                         n_out=self.vocab_size,
                                         n_pred_heads=1))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()
