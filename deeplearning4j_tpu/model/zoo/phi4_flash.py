"""Phi4FlashLM — Phi-4-mini-flash-reasoning's decoder (Microsoft, 2025-07,
``phi4flash``, https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning;
the SambaY decoder-hybrid-decoder of Ren et al. 2025) for autoregressive
generation serving.

Token embedding -> the SELF-decoder, layers ``0 .. half - 1`` (``half =
n_layers // 2``), one :class:`~deeplearning4j_tpu.nn.layers.DecoderBlockLayer`
a layer (LayerNorm with a bias, a float32 residual stream, the dense gated
feed-forward): a Mamba mixer where ``i % mb_per_layer == 0``
(:class:`~deeplearning4j_tpu.nn.layers.MambaMixerLayer`), else differential
attention over a sliding window of ``sliding_window`` positions with a ring
of its own (:class:`~deeplearning4j_tpu.nn.layers.DifferentialAttentionLayer`)
-> the CROSS-decoder, layers ``half .. n_layers - 1`` as one layer
(:class:`~deeplearning4j_tpu.nn.layers.CrossDecoderLayer`: a Mamba layer
whose scan output is the memory, the one full attention layer that writes
the only full-length K/V cache, then gated memory units over the memory and
cross attention over that cache) -> final LayerNorm -> a head that shares
the embedding's matrix. No positional encoding.

The kinds follow ``n_layers`` and ``mb_per_layer`` as the published config
class derives them, so a shallower model is the same rule. The published
sizes: hidden 2560, 32 layers, 40 query heads and 20 K/V heads of 64, FFN
10240, window 512, Mamba d_inner 5120, d_state 16, d_conv 4, dt_rank 160,
vocabulary 200064, eps 1e-5: 3,852,457,984 parameters. The defaults are toy
widths.
"""

from __future__ import annotations

from ...nn import NeuralNetConfiguration, WeightInit
from ...nn.layers import (
    CrossDecoderLayer,
    DecoderBlockLayer,
    DifferentialAttentionLayer,
    EmbeddingSequenceLayer,
    GatedFFNLayer,
    LayerNormLayer,
    MambaMixerLayer,
    TiedRnnOutputLayer,
)
from ...nn.sequential import MultiLayerNetwork
from ...train.updaters import Adam


class Phi4FlashLM:
    def __init__(
        self,
        vocab_size: int = 512,
        hidden: int = 64,
        n_layers: int = 8,
        mb_per_layer: int = 2,
        n_heads: int = 8,
        n_kv_heads: int = 4,
        ffn_size: int = 0,
        sliding_window: int = 16,
        d_inner: int = 0,
        d_state: int = 16,
        d_conv: int = 4,
        dt_rank: int = 0,
        max_len: int = 262144,
        seed: int = 123,
        updater=None,
        dtype: str = "float32",
        eps: float = 1e-5,
    ) -> None:
        half = n_layers // 2
        if half % mb_per_layer or (half + 1) % mb_per_layer == 0:
            raise ValueError(
                f"{n_layers} layers with mb_per_layer {mb_per_layer}: layer "
                f"{half} must be a Mamba layer and {half + 1} an attention "
                "layer")
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_layers = n_layers
        self.mb_per_layer = mb_per_layer
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.ffn_size = ffn_size or 4 * hidden
        self.sliding_window = sliding_window
        self.d_inner = d_inner or 2 * hidden
        self.d_state = d_state
        self.d_conv = d_conv
        self.dt_rank = dt_rank or -(-hidden // 16)
        self.max_len = max_len  # no positions: nothing is sized by it
        self.seed = seed
        self.updater = updater or Adam(1e-4)
        self.dtype = dtype
        self.eps = eps

    def block(self, i: int) -> DecoderBlockLayer:
        """Layer ``i`` of the self-decoder as a block of its parts."""
        h = self.hidden
        if i % self.mb_per_layer == 0:
            mixer = MambaMixerLayer(n_in=h, d_inner=self.d_inner,
                                    d_state=self.d_state, d_conv=self.d_conv,
                                    dt_rank=self.dt_rank)
        else:
            mixer = DifferentialAttentionLayer(
                n_in=h, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                kind="window", window=self.sliding_window, layer_index=i,
                eps=self.eps)
        return DecoderBlockLayer(
            n_in=h, mixer=mixer, ffn=GatedFFNLayer(n_in=h,
                                                   hidden=self.ffn_size),
            eps=self.eps, norm="layer")

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).data_type(self.dtype).updater(self.updater)
             .weight_init(WeightInit.XAVIER).list())
        b.layer(EmbeddingSequenceLayer(n_in=self.vocab_size,
                                       n_out=self.hidden))
        for i in range(self.n_layers // 2):
            b.layer(self.block(i))
        b.layer(CrossDecoderLayer(
            n_in=self.hidden, n_layers=self.n_layers,
            mb_per_layer=self.mb_per_layer, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, ffn_size=self.ffn_size,
            d_inner=self.d_inner, d_state=self.d_state, d_conv=self.d_conv,
            dt_rank=self.dt_rank, eps=self.eps))
        b.layer(LayerNormLayer(n_out=self.hidden, eps=self.eps))
        b.layer(TiedRnnOutputLayer(n_in=self.hidden, n_out=self.vocab_size,
                                   tied_layer=0))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()
