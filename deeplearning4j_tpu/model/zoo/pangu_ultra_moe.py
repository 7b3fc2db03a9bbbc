"""PanguUltraMoeLM — openPangu-Ultra-MoE-718B's decoder (2025, the
DeepSeek-V3 layout with sandwich norms;
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B) for
autoregressive generation serving, whole or as one chip's share of it.

Token embedding → one
:class:`~deeplearning4j_tpu.nn.layers.DecoderBlockLayer` a layer with
SANDWICH norms (``h = x + N(MLA(N(x)))``, ``y = h + N(FF(N(h)))``; RMSNorm,
no bias anywhere, a float32 residual stream) → a head with one
multi-token-prediction module (:class:`~deeplearning4j_tpu.nn.layers.
MtpOutputLayer`: the final norm and the untied head, and the module's
projection and block, whose drafts a serving engine verifies). The parts:

* ``MLA``: latent attention (:class:`~deeplearning4j_tpu.nn.layers.
  LatentAttentionLayer`) WITHOUT LongCat's LoRA scales: ``n_heads`` heads of
  ``qk_nope_head_dim`` + ``qk_rope_head_dim`` query/key numbers and
  ``v_head_dim`` value numbers over ranks ``q_lora_rank`` / ``kv_lora_rank``;
* ``FF``: the dense gated feed-forward at ``ffn_size`` for the first
  ``n_dense_layers`` layers, then an expert layer
  (:class:`~deeplearning4j_tpu.nn.layers.ExpertShareMoELayer`): sigmoid
  scores over ``n_routed_experts``, ``top_k`` chosen by score + the served
  selection bias, renormalised and scaled by ``routed_scaling_factor``, of
  which this model holds ``n_held_experts`` from ``first_held_expert`` on
  (expert parallelism's share), and ``n_shared_experts`` shared experts
  beside them.

The MTP module's block is an expert layer's block. The published sizes:
hidden 7680, 61 layers (3 dense), 128 heads of 128 + 64 and 128, ranks 1536 /
512, dense FFN 18432, 256 experts of 2048 top-8 with one shared, factor 2.5,
vocabulary 153600, rope theta 2.56e7, eps 1e-5, one MTP module. The defaults
are toy widths.
"""

from __future__ import annotations

from ...nn import NeuralNetConfiguration, WeightInit
from ...nn.layers import (
    DecoderBlockLayer,
    EmbeddingSequenceLayer,
    ExpertShareMoELayer,
    GatedFFNLayer,
    LatentAttentionLayer,
    MtpOutputLayer,
)
from ...nn.sequential import MultiLayerNetwork
from ...train.updaters import Adam


class PanguUltraMoeLM:
    def __init__(
        self,
        vocab_size: int = 512,
        hidden: int = 64,
        n_layers: int = 3,
        n_dense_layers: int = 1,
        n_heads: int = 4,
        qk_nope_head_dim: int = 16,
        qk_rope_head_dim: int = 8,
        v_head_dim: int = 16,
        q_lora_rank: int = 32,
        kv_lora_rank: int = 16,
        ffn_size: int = 0,
        expert_ffn_size: int = 0,
        n_routed_experts: int = 16,
        n_held_experts: int = 0,
        first_held_expert: int = 0,
        n_shared_experts: int = 1,
        top_k: int = 4,
        routed_scaling_factor: float = 2.5,
        expert_rows: int = 256,
        rope_theta: float = 25600000.0,
        max_len: int = 131072,
        seed: int = 123,
        updater=None,
        dtype: str = "float32",
        eps: float = 1e-5,
    ) -> None:
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_layers = n_layers
        self.n_dense_layers = n_dense_layers
        self.n_heads = n_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.ffn_size = ffn_size or 4 * hidden
        self.expert_ffn_size = expert_ffn_size or hidden // 2
        self.n_routed_experts = n_routed_experts
        self.n_held_experts = n_held_experts or n_routed_experts
        self.first_held_expert = first_held_expert
        self.n_shared_experts = n_shared_experts
        self.top_k = top_k
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.expert_rows = expert_rows
        self.rope_theta = float(rope_theta)
        self.max_len = max_len  # positions are rotary: nothing is sized by it
        self.seed = seed
        self.updater = updater or Adam(1e-4)
        self.dtype = dtype
        self.eps = eps

    def block(self, dense: bool) -> DecoderBlockLayer:
        """A layer of the stack (or the MTP module's) as a block of its
        parts."""
        h = self.hidden
        mixer = LatentAttentionLayer(
            n_in=h, n_heads=self.n_heads,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, rope_theta=self.rope_theta,
            eps=self.eps, lora_scales=False)
        if dense:
            ffn = GatedFFNLayer(n_in=h, hidden=self.ffn_size)
        else:
            ffn = ExpertShareMoELayer(
                n_in=h, hidden=self.expert_ffn_size,
                n_routed_experts=self.n_routed_experts,
                n_held_experts=self.n_held_experts,
                first_held_expert=self.first_held_expert,
                top_k=self.top_k, scoring="sigmoid", norm_topk_prob=True,
                routed_scaling_factor=self.routed_scaling_factor,
                n_shared_experts=self.n_shared_experts,
                expert_rows=self.expert_rows)
        return DecoderBlockLayer(n_in=h, mixer=mixer, ffn=ffn, eps=self.eps,
                                 sandwich=True)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).data_type(self.dtype).updater(self.updater)
             .weight_init(WeightInit.XAVIER).list())
        b.layer(EmbeddingSequenceLayer(n_in=self.vocab_size,
                                       n_out=self.hidden))
        for i in range(self.n_layers):
            b.layer(self.block(dense=i < self.n_dense_layers))
        b.layer(MtpOutputLayer(n_in=self.hidden, n_out=self.vocab_size,
                               tied_layer=0, block=self.block(dense=False),
                               eps=self.eps))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()
