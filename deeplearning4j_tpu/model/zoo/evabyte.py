"""EvaByteLM — EvaByte's byte-level decoder (EvaByte 6.5B, 2025-01,
https://huggingface.co/EvaByte/EvaByte; its attention is EVA, Zheng et al.
2023, arXiv:2302.04542) for autoregressive generation serving.

Byte embedding → N :class:`~deeplearning4j_tpu.nn.layers.EvaDecoderBlockLayer`
blocks (RMSNorm with the unit offset, rotary positions, EVA attention over an
exact ``window`` beside one learned summary per ``chunk``, a gated SiLU
feed-forward, no bias anywhere, a float32 residual stream) → final RMSNorm →
an untied head of ``n_pred_heads`` x ``vocab_size`` columns
(:class:`~deeplearning4j_tpu.nn.layers.MultiTokenRnnOutputLayer`: head 0 is
the next byte, the others predict further ahead).

Serving decodes the vanilla way, one byte a step from head 0
(:class:`~deeplearning4j_tpu.generate.session.GenerationSession` reads only
that head's columns); ``output()`` gives every head's logits. The mixer's
decode state is bounded: ``window + max_len / chunk`` entries a row and layer
where a K/V cache holds ``max_len``. The published sizes are the defaults'
widths: hidden 4096, 32 heads, FFN 11008, 32 layers, vocabulary 320 (bytes
and specials), 8 prediction heads, window 2048, chunk 16, rope theta 1e5.
"""

from __future__ import annotations

from ...nn import NeuralNetConfiguration, WeightInit
from ...nn.layers import (
    EmbeddingSequenceLayer,
    EvaDecoderBlockLayer,
    MultiTokenRnnOutputLayer,
    RMSNormLayer,
)
from ...nn.sequential import MultiLayerNetwork
from ...train.updaters import Adam


class EvaByteLM:
    def __init__(
        self,
        vocab_size: int = 320,
        hidden: int = 256,
        n_layers: int = 4,
        n_heads: int = 4,
        ffn_size: int = 0,
        window: int = 2048,
        chunk: int = 16,
        n_pred_heads: int = 8,
        rope_theta: float = 1e5,
        max_len: int = 32768,
        seed: int = 123,
        updater=None,
        dtype: str = "float32",
        eps: float = 1e-5,
    ) -> None:
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.ffn_size = ffn_size or 4 * hidden
        self.window = window
        self.chunk = chunk
        self.n_pred_heads = n_pred_heads
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
            b.layer(EvaDecoderBlockLayer(
                n_in=self.hidden, n_heads=self.n_heads,
                ffn_size=self.ffn_size, window=self.window, chunk=self.chunk,
                rope_theta=self.rope_theta, eps=self.eps))
        b.layer(RMSNormLayer(n_out=self.hidden, eps=self.eps,
                             unit_offset=True))
        b.layer(MultiTokenRnnOutputLayer(n_in=self.hidden,
                                         n_out=self.vocab_size,
                                         n_pred_heads=self.n_pred_heads))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()
