"""The shipped rewrite passes: space-to-depth stem, conv+BN fold, BN affine.

A 2026-07 calibration on another machine's v5e (a claim to check, ROADMAP
S1) located ResNet-50's two remaining step-time losses: the 7×7/2 conv1
stem runs at 8.3 TF/s against a 183–191 TF/s body because a 3-channel input
pads the 128×128 MXU to 2.3% occupancy, and ~5.6 ms/step of
BatchNorm/elementwise HBM traffic rides on every step. Google's MLPerf TPU
submissions ("Scale MLPerf-0.6 models on
Google TPU-v3 Pods", PAPERS.md) close exactly this gap with the
space-to-depth stem transform implemented here; the BN passes remove or
collapse the elementwise chain so XLA fuses it into the conv epilogue.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..activations import Activation
from ..conf import MultiLayerConfiguration
from ..graph_conf import ComputationGraphConfiguration, VertexSpec
from ..input_type import ConvolutionalType
from ..layers.conv import ConvolutionLayer, ConvolutionMode
from ..layers.norm import BatchNormalizationLayer
from ..layers.pooling import SpaceToDepthLayer
from .base import (
    Params,
    PassResult,
    RewritePass,
    State,
    remap_sequential,
    unique_vertex_name,
)


def _identity_act(layer) -> bool:
    return layer.activation is None or layer.activation is Activation.IDENTITY


def _asarray(x, orig):
    """Cast a float64 numpy result back to the original array's dtype."""
    import jax.numpy as jnp

    return jnp.asarray(x, np.asarray(orig).dtype)


# ---------------------------------------------------------------------------
# 1. space-to-depth stem
# ---------------------------------------------------------------------------

class SpaceToDepthStemPass(RewritePass):
    """Rewrite a leading 7×7 stride-2 SAME conv on few channels into a 2×2
    space-to-depth followed by an equivalent 4×4 stride-1 SAME conv on 4×
    the channels (the MLPerf-0.6 TPU stem transform).

    Exactness: for even H×W, XLA's SAME padding for the original conv is
    (2, 3) per spatial dim and for the new conv (1, 2); writing the
    original tap ``x[2i' + dh - 2]`` as ``x[2(i' + m - 1) + u]`` gives
    ``dh = 2m + u`` — so the 7×7 kernel zero-padded to 8×8 and reshaped
    into (4×4, 4·C) taps reads *exactly* the same input pixels with
    exactly the same weights. The kernel transform is a pure pad+reshape
    (no arithmetic), hence bit-exact on the weights; outputs match to
    float tolerance (summation order inside the conv may differ).
    """

    name = "space_to_depth_stem"
    training_safe = True
    BLOCK = 2
    #: stem channels worth rewriting — the MXU-padding pathology is a
    #: small-nIn property (3-channel images); wide convs occupy the MXU.
    MAX_STEM_CHANNELS = 4

    # ---- pattern ----------------------------------------------------------
    def _matches(self, layer, input_type) -> bool:
        if type(layer) is not ConvolutionLayer:
            return False
        if not isinstance(input_type, ConvolutionalType):
            return False
        return (
            layer.kernel_size == (7, 7)
            and layer.stride == (2, 2)
            and layer.convolution_mode is ConvolutionMode.SAME
            and layer.dilation == (1, 1)
            and layer.data_format == "NCHW"
            and 0 < layer.n_in <= self.MAX_STEM_CHANNELS
            and layer.n_in == input_type.channels
            and input_type.height % 2 == 0
            and input_type.width % 2 == 0
        )

    # ---- transform --------------------------------------------------------
    @staticmethod
    def _transform_kernel(w) -> np.ndarray:
        """[O, C, 7, 7] -> [O, 4C, 4, 4] via zero-pad to 8×8 + reshape.
        New channel index (u·2 + v)·C + c matches SpaceToDepthLayer's
        block-major channel layout."""
        w = np.asarray(w)
        o, c, kh, kw = w.shape
        wp = np.zeros((o, c, 8, 8), w.dtype)
        wp[:, :, :kh, :kw] = w
        return (wp.reshape(o, c, 4, 2, 4, 2)
                  .transpose(0, 3, 5, 1, 2, 4)
                  .reshape(o, 4 * c, 4, 4))

    def _rewritten(self, conv: ConvolutionLayer,
                   conv_params: Dict[str, Any]):
        s2d = SpaceToDepthLayer(
            block_size=self.BLOCK,
            name=f"{conv.name}_s2d" if conv.name else None)
        new_conv = dataclasses.replace(
            conv, n_in=conv.n_in * 4, kernel_size=(4, 4), stride=(1, 1),
            padding=(0, 0))
        new_params = dict(conv_params)
        new_params["W"] = _asarray(
            self._transform_kernel(conv_params["W"]), conv_params["W"])
        return s2d, new_conv, new_params

    # ---- sequential -------------------------------------------------------
    def apply_sequential(self, conf: MultiLayerConfiguration,
                         params: Params, state: State) -> PassResult:
        if not conf.layers or not self._matches(conf.layers[0], conf.input_type):
            return conf, params, state, False
        conv = conf.layers[0]
        s2d, new_conv, new_conv_params = self._rewritten(
            conv, params.get(conf.layer_name(0), {}))
        new_layers = (s2d, new_conv) + tuple(conf.layers[1:])
        index_map = {i: i + 1 for i in range(len(conf.layers))}
        new_conf, new_params, new_state = remap_sequential(
            conf, new_layers, index_map, params, state,
            param_overrides={0: new_conv_params})
        return new_conf, new_params, new_state, True

    # ---- graph ------------------------------------------------------------
    def apply_graph(self, conf: ComputationGraphConfiguration,
                    params: Params, state: State) -> PassResult:
        if not conf.input_types:
            return conf, params, state, False
        in_types = dict(zip(conf.network_inputs, conf.input_types))
        new_vertices: List[VertexSpec] = []
        new_params = dict(params)
        new_state = dict(state)
        changed = False
        for spec in conf.vertices:
            if (not changed
                    and spec.layer is not None
                    and spec.preprocessor is None
                    and len(spec.inputs) == 1
                    and spec.inputs[0] in in_types
                    and self._matches(spec.layer, in_types[spec.inputs[0]])):
                s2d, new_conv, new_conv_params = self._rewritten(
                    spec.layer, params.get(spec.name, {}))
                s2d_name = unique_vertex_name(conf, f"{spec.name}_s2d")
                new_vertices.append(VertexSpec(
                    name=s2d_name, layer=s2d, inputs=spec.inputs))
                new_vertices.append(dataclasses.replace(
                    spec, layer=new_conv, inputs=(s2d_name,)))
                new_params[spec.name] = new_conv_params
                new_params[s2d_name] = {}
                new_state[s2d_name] = {}
                changed = True
            else:
                new_vertices.append(spec)
        if not changed:
            return conf, params, state, False
        new_conf = dataclasses.replace(conf, vertices=tuple(new_vertices))
        return new_conf, new_params, new_state, True


# ---------------------------------------------------------------------------
# 2. conv + BN fold (inference only)
# ---------------------------------------------------------------------------

class ConvBatchNormFoldPass(RewritePass):
    """Fold a BatchNormalizationLayer into the preceding identity-activation
    ConvolutionLayer for inference: with s = γ/√(σ²+ε),

        W' = W · s (per out-channel)      b' = β + (b − μ)·s

    eliminating the BN op and its HBM round-trip from every served
    forward. Weight math runs in float64 and casts back to the param
    dtype. Inference-only: the fold freezes the running statistics into
    the conv, so training through it would silently stop updating them —
    ``resolve_passes(context="training")`` rejects this pass.
    """

    name = "conv_bn_fold"
    training_safe = False

    @staticmethod
    def _foldable(conv, bn) -> bool:
        return (
            type(conv) is ConvolutionLayer
            and type(bn) is BatchNormalizationLayer
            and _identity_act(conv)
            and bn.n_out == conv.n_out
            and conv.n_out > 0
        )

    @staticmethod
    def _fold(conv: ConvolutionLayer, bn: BatchNormalizationLayer,
              conv_params: Dict[str, Any], bn_params: Dict[str, Any],
              bn_state: Dict[str, Any]):
        w = np.asarray(conv_params["W"], np.float64)
        n = bn.n_out
        gamma = (np.asarray(bn_params["gamma"], np.float64)
                 if "gamma" in bn_params else np.full(n, bn.gamma_init))
        beta = (np.asarray(bn_params["beta"], np.float64)
                if "beta" in bn_params else np.full(n, bn.beta_init))
        mean = np.asarray(bn_state["mean"], np.float64)
        var = np.asarray(bn_state["var"], np.float64)
        scale = gamma / np.sqrt(var + bn.eps)
        b = (np.asarray(conv_params["b"], np.float64)
             if "b" in conv_params else np.zeros(n))
        new_w = w * scale.reshape(-1, 1, 1, 1)
        new_b = beta + (b - mean) * scale
        new_conv = dataclasses.replace(
            conv, has_bias=True,
            activation=bn.activation if bn.activation is not None
            else conv.activation)
        new_params = {
            "W": _asarray(new_w, conv_params["W"]),
            "b": _asarray(new_b, conv_params.get("W")),
        }
        return new_conv, new_params

    # ---- sequential -------------------------------------------------------
    def apply_sequential(self, conf: MultiLayerConfiguration,
                         params: Params, state: State) -> PassResult:
        layers = conf.layers
        new_layers: List[Any] = []
        index_map: Dict[int, int] = {}
        overrides: Dict[int, Dict[str, Any]] = {}
        changed = False
        i = 0
        while i < len(layers):
            nxt = layers[i + 1] if i + 1 < len(layers) else None
            if nxt is not None and self._foldable(layers[i], nxt):
                bn_state = state.get(conf.layer_name(i + 1), {})
                if "mean" in bn_state and "var" in bn_state:
                    new_conv, new_conv_params = self._fold(
                        layers[i], nxt,
                        params.get(conf.layer_name(i), {}),
                        params.get(conf.layer_name(i + 1), {}), bn_state)
                    index_map[i] = len(new_layers)
                    overrides[i] = new_conv_params
                    new_layers.append(new_conv)
                    changed = True
                    i += 2  # BN dropped: no mapping for old index i+1
                    continue
            index_map[i] = len(new_layers)
            new_layers.append(layers[i])
            i += 1
        if not changed:
            return conf, params, state, False
        new_conf, new_params, new_state = remap_sequential(
            conf, new_layers, index_map, params, state,
            param_overrides=overrides)
        return new_conf, new_params, new_state, True

    # ---- graph ------------------------------------------------------------
    def apply_graph(self, conf: ComputationGraphConfiguration,
                    params: Params, state: State) -> PassResult:
        consumers: Dict[str, List[str]] = {}
        by_name = {v.name: v for v in conf.vertices}
        for v in conf.vertices:
            for inp in v.inputs:
                consumers.setdefault(inp, []).append(v.name)

        # BN vertices whose single input is a conv that feeds ONLY that BN
        # (rewiring away a conv with other consumers would change them)
        folds: Dict[str, str] = {}  # conv name -> bn name
        for v in conf.vertices:
            if (v.layer is None or v.preprocessor is not None
                    or len(v.inputs) != 1):
                continue
            src = by_name.get(v.inputs[0])
            if src is None or src.layer is None:
                continue
            if not self._foldable(src.layer, v.layer):
                continue
            if consumers.get(src.name, []) != [v.name]:
                continue
            if src.name in conf.network_outputs:
                continue
            bn_state = state.get(v.name, {})
            if "mean" not in bn_state or "var" not in bn_state:
                continue
            folds[src.name] = v.name

        if not folds:
            return conf, params, state, False

        bn_to_conv = {bn: cv for cv, bn in folds.items()}
        new_vertices: List[VertexSpec] = []
        new_params = dict(params)
        new_state = dict(state)
        for v in conf.vertices:
            if v.name in bn_to_conv:  # folded BN: vertex disappears
                new_params.pop(v.name, None)
                new_state.pop(v.name, None)
                continue
            # consumers of a folded BN now read the conv directly
            inputs = tuple(bn_to_conv.get(i, i) for i in v.inputs)
            if v.name in folds:
                bn_name = folds[v.name]
                bn_spec = by_name[bn_name]
                new_conv, conv_params = self._fold(
                    v.layer, bn_spec.layer, params.get(v.name, {}),
                    params.get(bn_name, {}), state.get(bn_name, {}))
                v = dataclasses.replace(v, layer=new_conv, inputs=inputs)
                new_params[v.name] = conv_params
            elif inputs != v.inputs:
                v = dataclasses.replace(v, inputs=inputs)
            new_vertices.append(v)
        outputs = tuple(bn_to_conv.get(o, o) for o in conf.network_outputs)
        new_conf = dataclasses.replace(
            conf, vertices=tuple(new_vertices), network_outputs=outputs)
        return new_conf, new_params, new_state, True


# ---------------------------------------------------------------------------
# 3. BN affine precompute (training-safe)
# ---------------------------------------------------------------------------

class BatchNormAffinePass(RewritePass):
    """Collapse BN's normalize+scale+shift chain into one fused
    multiply-add: precompute per-channel ``scale = γ·rsqrt(σ²+ε)`` and
    ``shift = β − μ·scale`` (O(channels) work), then apply
    ``y = x·scale + shift`` as a single FMA over the tensor instead of the
    4-op elementwise chain — XLA fuses it into one epilogue, cutting the
    BN HBM round-trips. Pure config rewrite (``fused=True`` on each BN);
    params/state are untouched and batch statistics are still computed in
    training mode, so this is training-safe and checkpoint-neutral.
    """

    name = "bn_affine_precompute"
    training_safe = True

    @staticmethod
    def _fuse(layer):
        if type(layer) is BatchNormalizationLayer and not layer.fused:
            return dataclasses.replace(layer, fused=True), True
        return layer, False

    def apply_sequential(self, conf: MultiLayerConfiguration,
                         params: Params, state: State) -> PassResult:
        fused = [self._fuse(l) for l in conf.layers]
        if not any(c for _, c in fused):
            return conf, params, state, False
        new_conf = dataclasses.replace(
            conf, layers=tuple(l for l, _ in fused))
        return new_conf, params, state, True

    def apply_graph(self, conf: ComputationGraphConfiguration,
                    params: Params, state: State) -> PassResult:
        new_vertices: List[VertexSpec] = []
        changed = False
        for v in conf.vertices:
            if v.layer is not None:
                new_layer, c = self._fuse(v.layer)
                if c:
                    v = dataclasses.replace(v, layer=new_layer)
                    changed = True
            new_vertices.append(v)
        if not changed:
            return conf, params, state, False
        return (dataclasses.replace(conf, vertices=tuple(new_vertices)),
                params, state, True)
