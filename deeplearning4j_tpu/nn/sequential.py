"""MultiLayerNetwork — the sequential-stack model.

Reference: org.deeplearning4j.nn.multilayer.MultiLayerNetwork (~4k LoC,
SURVEY.md §2.2, call stack §3.1). Capability-equivalent API: ``init``, ``fit``,
``output``, ``feed_forward``, ``score``, ``evaluate``, ``rnn_time_step``,
truncated BPTT, masks, serialization hooks.

TPU design: where the reference's fit() interprets layers one native call at a
time (hot loops #1/#2 in SURVEY §3.1), here the ENTIRE training iteration —
forward, loss, backward, gradient normalization, updater, param update — is a
single jitted XLA program with donated params (donation ≈ the reference's
workspaces: steady-state allocation is zero). Python only feeds batches.

State model:
* ``params``    — {layer_name: {param_name: array}} trainable pytree
* ``state``     — persistent non-trainable state (BN running stats)
* ``rnn_state`` — streaming-inference carry (h/c), only used by
                  rnn_time_step / TBPTT, never carried across fit batches
                  (reference semantics)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtypes import as_input
from ..core.listeners import ListenerBus, TrainingListener
from ..core.rng import RngState
from .conf import BackpropType, MultiLayerConfiguration
from .input_type import RecurrentType
from .layers.base import Layer, LayerContext, apply_layer as _apply_layer
from .layers.output import BaseOutputLayer


def _layer_reg_score(layer: Layer, params: Dict[str, jax.Array], score_dtype) -> jax.Array:
    """l1/l2 regularization contribution (reference: calcRegularizationScore).
    Weight-decay is decoupled (applied in the updater), not part of the score."""
    score = jnp.asarray(0.0, score_dtype)
    weight_names = set(layer.weight_param_names())
    for name, arr in params.items():
        is_weight = name in weight_names
        l1 = layer.l1 if is_weight else layer.l1_bias
        l2 = layer.l2 if is_weight else layer.l2_bias
        if l1:
            score = score + l1 * jnp.sum(jnp.abs(arr)).astype(score_dtype)
        if l2:
            score = score + 0.5 * l2 * jnp.sum(jnp.square(arr)).astype(score_dtype)
    return score


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration) -> None:
        self.conf = conf
        self.layers: Tuple[Layer, ...] = conf.layers
        if not self.layers:
            raise ValueError("Configuration has no layers")
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self.rnn_state: Dict[str, Dict[str, jax.Array]] = {}
        self._persistent_keys: Dict[str, Tuple[str, ...]] = {}
        self.listeners = ListenerBus()
        self.iteration_count = 0
        self.epoch_count = 0
        self.last_batch_size = 0
        self.score_value = float("nan")
        self._rng = RngState(conf.seed)
        self._trainer = None
        self._output_fn_cache: Dict[Any, Any] = {}
        self._initialized = False

    # ------------------------------------------------------------------ init
    @property
    def dtype(self):
        return jnp.dtype(self.conf.dtype)

    def keeps_int_input(self) -> bool:
        """True when the first layer consumes integer indices (embedding):
        inputs then keep their integer dtype through every cast boundary
        (see core.dtypes.as_input)."""
        return bool(self.layers) and getattr(self.layers[0], "consumes_indices", False)

    def _to_compute(self, params, x):
        """Mixed-precision boundary: cast params + input to compute_dtype
        (bf16 on the TPU MXU) while master params stay in ``dtype``.
        No-op when compute_dtype is unset or equals dtype. Idempotent."""
        cd = getattr(self.conf, "compute_dtype", None)
        if not cd or jnp.dtype(cd) == self.dtype:
            return params, x
        from ..core.dtypes import cast_floats

        return cast_floats(params, cd), cast_floats(x, cd)

    def layer_names(self) -> List[str]:
        return [self.conf.layer_name(i) for i in range(len(self.layers))]

    def layer_params(self, params, i: int) -> Dict[str, jax.Array]:
        """Layer ``i``'s parameters out of the network's ``params``: its
        own, and beside them those it reads from another layer
        (``Layer.tied_params``)."""
        own = params.get(self.conf.layer_name(i), {})
        tied = self.layers[i].tied_params()
        if not tied:
            return own
        return {**own, **{mine: params[self.conf.layer_name(j)][theirs]
                          for mine, (j, theirs) in tied.items()}}

    def named_param_layers(self):
        """(name, layer) pairs for layers holding trainable params — the
        updater-block boundaries (used by the Solver's LayerOptimizers)."""
        return [
            (self.conf.layer_name(i), l)
            for i, l in enumerate(self.layers)
            if l.has_params()
        ]

    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        rng = RngState(self.conf.seed if seed is None else seed)
        dtype = self.dtype
        self.params, self.state, self._persistent_keys = {}, {}, {}
        for i, layer in enumerate(self.layers):
            name = self.conf.layer_name(i)
            self.params[name] = layer.init(rng.next_key(), dtype) if layer.has_params() else {}
            st = layer.init_state(dtype)
            self.state[name] = st
            self._persistent_keys[name] = tuple(st.keys())
        self.rnn_state = {}
        self._initialized = True
        self._output_fn_cache.clear()
        self._trainer = None
        return self

    def _check_init(self) -> None:
        if not self._initialized:
            self.init()

    def migrate_state(self) -> None:
        """Fill persistent-state keys introduced by newer framework versions
        with their ``init_state`` defaults, keeping every existing value
        (BN running stats survive untouched). E.g. PR 3 added
        ``expert_tokens``/``dropped_tokens`` to MixtureOfExpertsLayer state;
        pre-PR-3 state pytrees restored onto this version would otherwise
        break the jitted scan's carry structure. Called automatically at
        Solver construction and ``make_servable`` — a manual
        ``init_state`` re-run is never required."""
        if not self._initialized:
            return
        changed = False
        for i, layer in enumerate(self.layers):
            defaults = layer.init_state(self.dtype)
            if not defaults:
                continue
            name = self.conf.layer_name(i)
            cur = dict(self.state.get(name, {}))
            missing = [k for k in defaults if k not in cur]
            if missing:
                for k in missing:
                    cur[k] = defaults[k]
                self.state[name] = cur
                self._persistent_keys[name] = tuple(cur.keys())
                changed = True
        if changed:
            self._output_fn_cache.clear()

    # -------------------------------------------------------------- forward
    def forward_pure(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        state: Dict[str, Dict[str, jax.Array]],
        x: jax.Array,
        *,
        train: bool,
        rng: Optional[jax.Array],
        mask: Optional[jax.Array] = None,
        rnn_state: Optional[Dict[str, Dict[str, jax.Array]]] = None,
        upto: Optional[int] = None,
        start: int = 0,
        collect: bool = False,
        dist=None,
    ):
        """Pure forward through layers [start, upto). Returns
        (out, new_state, new_rnn_state, activations?).

        With ``start > 0`` (pipeline stages fold a layer RANGE), ``x`` is
        the activation entering layer ``start`` and ``mask`` the mask at
        that boundary; the InputType walk still advances from the input so
        per-layer mask propagation and RNG folds stay index-aligned with
        the full forward."""
        params, x = self._to_compute(params, x)
        new_state: Dict[str, Dict[str, jax.Array]] = {}
        new_rnn: Dict[str, Dict[str, jax.Array]] = {}
        acts: List[jax.Array] = []
        cur_mask = mask
        n = len(self.layers) if upto is None else upto
        # per-layer input types for mask propagation (from config walk)
        it = self.conf.input_type
        for i in range(start):
            if it is not None:
                it = self.layers[i].output_type(it)
        for i in range(start, n):
            layer = self.layers[i]
            name = self.conf.layer_name(i)
            lstate = dict(state.get(name, {}))
            if rnn_state is not None and name in rnn_state:
                lstate.update(rnn_state[name])
            key = jax.random.fold_in(rng, i) if rng is not None else None
            ctx = LayerContext(train=train, rng=key, mask=cur_mask, dist=dist)
            y, lstate_out = _apply_layer(
                layer, self.layer_params(params, i), lstate, x, ctx, name=name,
                remat=self.conf.gradient_checkpointing and train)
            persistent = self._persistent_keys.get(name, ())
            new_state[name] = {k: v for k, v in lstate_out.items() if k in persistent}
            transient = {k: v for k, v in lstate_out.items() if k not in persistent}
            if transient:
                new_rnn[name] = transient
            if it is not None:
                cur_mask = layer.feed_forward_mask(cur_mask, it)
                it = layer.output_type(it)
            x = y
            if collect:
                acts.append(y)
        if collect:
            return x, new_state, new_rnn, acts
        return x, new_state, new_rnn

    def loss_pure(
        self,
        params,
        state,
        x: jax.Array,
        labels: jax.Array,
        *,
        rng: Optional[jax.Array],
        mask: Optional[jax.Array] = None,
        label_mask: Optional[jax.Array] = None,
        rnn_state=None,
        train: bool = True,
        dist=None,
    ):
        """Score = loss + regularization (reference: computeGradientAndScore).
        Returns (score, (new_state, new_rnn_state))."""
        out_layer = self.layers[-1]
        if not isinstance(out_layer, BaseOutputLayer):
            raise ValueError("Last layer must be an output/loss layer to compute a score")
        # regularization is computed on the master (uncast) params below;
        # the compute-dtype cast applies to forward math only
        master_params = params
        params, x = self._to_compute(params, x)
        feat, new_state, new_rnn = self.forward_pure(
            params, state, x, train=train, rng=rng, mask=mask,
            rnn_state=rnn_state, upto=len(self.layers) - 1, dist=dist,
        )
        # mask as transformed by the stack for the output layer
        cur_mask = mask
        it = self.conf.input_type
        if it is not None and cur_mask is not None:
            for i in range(len(self.layers) - 1):
                cur_mask = self.layers[i].feed_forward_mask(cur_mask, it)
                it = self.layers[i].output_type(it)
        name = self.conf.layer_name(len(self.layers) - 1)
        key = jax.random.fold_in(rng, len(self.layers) - 1) if rng is not None else None
        ctx = LayerContext(train=train, rng=key, mask=cur_mask)
        with jax.named_scope(name):  # as apply_layer names the others
            loss = out_layer.compute_loss(
                self.layer_params(params, len(self.layers) - 1), feat, labels,
                ctx, label_mask=label_mask)
        # output layer state passes through unchanged (loss layers are stateless)
        new_state[name] = dict(state.get(name, {}))
        # score in >= float32 precision; float64 models keep float64 (gradcheck)
        score_dtype = jnp.promote_types(self.dtype, jnp.float32)
        reg = jnp.asarray(0.0, score_dtype)
        for i, layer in enumerate(self.layers):
            lname = self.conf.layer_name(i)
            if master_params.get(lname):
                reg = reg + _layer_reg_score(layer, master_params[lname], score_dtype)
            # MoE load-balance aux loss (GShard): the layer computed this
            # batch's aux during forward and stashed it in state
            bl_w = getattr(layer, "balance_loss_weight", 0.0)
            if bl_w:
                aux = new_state.get(lname, {}).get("aux_load_balance")
                if aux is not None:
                    reg = reg + bl_w * aux.astype(score_dtype)
        return loss.astype(score_dtype) + reg, (new_state, new_rnn)

    # -------------------------------------------------------------- user API
    def output(self, x, mask=None):
        """Inference forward (reference: MultiLayerNetwork.output)."""
        self._check_init()
        x = as_input(x, self.dtype, self.keeps_int_input())
        key = ("output", mask is not None)
        if key not in self._output_fn_cache:
            def fn(params, state, xx, mk):
                out, _, _ = self.forward_pure(params, state, xx, train=False, rng=None, mask=mk)
                # user-facing outputs in the model dtype even under a bf16
                # compute_dtype (mixed precision is an internal property)
                return out.astype(self.dtype)

            self._output_fn_cache[key] = jax.jit(fn)
        return self._output_fn_cache[key](self.params, self.state, x,
                                          None if mask is None else jnp.asarray(mask))

    def feed_forward(self, x, train: bool = False, mask=None):
        """All layer activations (reference: feedForward). Host-side list."""
        self._check_init()
        x = as_input(x, self.dtype, self.keeps_int_input())
        rng = self._rng.next_key() if train else None
        _, _, _, acts = self.forward_pure(
            self.params, self.state, x, train=train, rng=rng, mask=mask, collect=True
        )
        return acts

    def score(self, features, labels, mask=None, label_mask=None) -> float:
        self._check_init()
        s, _ = self.loss_pure(
            self.params, self.state,
            as_input(features, self.dtype, self.keeps_int_input()), jnp.asarray(labels),
            rng=None, mask=mask, label_mask=label_mask, train=False,
        )
        return float(s)

    def calculate_gradients(self, features, labels, mask=None, label_mask=None):
        """Full gradient pytree for the given batch — the grad-check entry
        point (reference: computeGradientAndScore + Gradient object)."""
        self._check_init()
        x = as_input(features, self.dtype, self.keeps_int_input())
        y = jnp.asarray(labels)

        def loss_of(p):
            s, _ = self.loss_pure(p, self.state, x, y, rng=None,
                                  mask=mask, label_mask=label_mask, train=True)
            return s

        return jax.grad(loss_of)(self.params)

    # ------------------------------------------------------------------ fit
    def add_listeners(self, *listeners: TrainingListener) -> None:
        for l in listeners:
            self.listeners.add(l)

    # reference spelling
    def set_listeners(self, *listeners: TrainingListener) -> None:
        self.listeners.clear()
        for l in listeners:
            self.listeners.add(l)

    def fit(self, data, labels=None, *, epochs: int = 1, mask=None, label_mask=None):
        """Train (reference: MultiLayerNetwork.fit). ``data`` may be a
        (features, labels) pair, a DataSet, or a DataSetIterator."""
        self._check_init()
        from ..train.solver import Solver

        if self._trainer is None:
            self._trainer = Solver(self)
        self._trainer.fit(data, labels, epochs=epochs, mask=mask, label_mask=label_mask)
        return self

    # ------------------------------------------------------- rnn streaming
    def rnn_time_step(self, x, mask=None):
        """Stateful streaming inference (reference: rnnTimeStep): state (h/c)
        carries across calls."""
        self._check_init()
        x = as_input(x, self.dtype, self.keeps_int_input())
        single_step = False
        if x.ndim == 2 and self._expects_sequence_input():
            x = x[:, :, None]
            single_step = True
        out, _, new_rnn = self.forward_pure(
            self.params, self.state, x, train=False, rng=None, mask=mask,
            rnn_state=self.rnn_state if self.rnn_state else None,
        )
        self.rnn_state = new_rnn
        if single_step and out.ndim == 3:
            out = out[:, :, -1]
        return out

    def rnn_clear_previous_state(self) -> None:
        self.rnn_state = {}

    def rnn_get_previous_state(self) -> Dict[str, Dict[str, jax.Array]]:
        return self.rnn_state

    def rnn_set_previous_state(self, state) -> None:
        self.rnn_state = state

    def _expects_sequence_input(self) -> bool:
        return isinstance(self.conf.input_type, RecurrentType)

    # ------------------------------------------------------------- params
    def num_params(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.params)
        return int(sum(l.size for l in leaves))

    def params_flat(self) -> np.ndarray:
        """Single flat param vector — the reference's contiguous-params
        invariant (coefficients.bin), reproduced for serialization parity."""
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(self.params)
        return np.asarray(flat)

    def set_params_flat(self, vec) -> None:
        from jax.flatten_util import ravel_pytree

        _, unravel = ravel_pytree(self.params)
        self.params = jax.tree_util.tree_map(
            lambda a: a, unravel(jnp.asarray(vec))
        )
        self._output_fn_cache.clear()

    def get_layer_params(self, i: int) -> Dict[str, jax.Array]:
        return self.params[self.conf.layer_name(i)]

    def evaluate(self, iterator_or_features, labels=None, mask=None):
        """Classification evaluation (reference: MultiLayerNetwork.evaluate)."""
        from ..train.evaluation import Evaluation

        ev = Evaluation()
        for feats, labs, msk, lmsk in _as_batches(iterator_or_features, labels, mask):
            out = self.output(feats, mask=msk)
            ev.eval(np.asarray(labs), np.asarray(out), mask=None if lmsk is None else np.asarray(lmsk))
        return ev

    def evaluate_regression(self, iterator_or_features, labels=None):
        from ..train.evaluation import RegressionEvaluation

        ev = RegressionEvaluation()
        for feats, labs, msk, _ in _as_batches(iterator_or_features, labels, None):
            out = self.output(feats, mask=msk)
            ev.eval(np.asarray(labs), np.asarray(out))
        return ev

    def summary(self) -> str:
        lines = [f"{'idx':<4}{'name':<28}{'type':<30}{'params':>10}"]
        total = 0
        for i, layer in enumerate(self.layers):
            name = self.conf.layer_name(i)
            n = sum(int(a.size) for a in self.params.get(name, {}).values()) if self._initialized else 0
            total += n
            lines.append(f"{i:<4}{name:<28}{type(layer).__name__:<30}{n:>10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf)
        if self._initialized:
            m.params = jax.tree_util.tree_map(lambda a: a, self.params)
            m.state = jax.tree_util.tree_map(lambda a: a, self.state)
            m._persistent_keys = dict(self._persistent_keys)
            m._initialized = True
        return m


# Alias with the TPU-native project's own idiom
Sequential = MultiLayerNetwork


def _as_batches(data, labels, mask):
    """Normalize (features, labels) / DataSet / iterator into batch tuples."""
    from ..data.dataset import DataSet

    if labels is not None:
        yield data, labels, mask, None
        return
    if isinstance(data, DataSet):
        yield data.features, data.labels, data.features_mask, data.labels_mask
        return
    for item in data:
        if isinstance(item, DataSet):
            yield item.features, item.labels, item.features_mask, item.labels_mask
        else:
            f, l = item[0], item[1]
            yield f, l, None, None
