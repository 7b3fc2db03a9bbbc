"""ComputationGraph — the DAG model.

Reference: org.deeplearning4j.nn.graph.ComputationGraph (~5k LoC, SURVEY.md
§2.2/§3.2 — the ResNet-50 path). Topologically-ordered forward over vertices,
multi-input/multi-output, per-output loss weighting. Backward is jax autodiff
over the whole graph; the reference's reverse-topo epsilon accumulation has no
hand-written equivalent here.

The training step is one jitted donated XLA program, same design as the
Sequential solver (SURVEY.md §7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtypes import as_input
from ..core.listeners import ListenerBus, TrainingListener
from ..core.rng import RngState
from .graph_conf import ComputationGraphConfiguration, VertexSpec
from .layers.base import Layer, LayerContext, apply_layer as _apply_layer
from .layers.output import BaseOutputLayer
from .sequential import _layer_reg_score


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration) -> None:
        self.conf = conf
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self._persistent_keys: Dict[str, Tuple[str, ...]] = {}
        self.listeners = ListenerBus()
        self.iteration_count = 0
        self.epoch_count = 0
        self.last_batch_size = 0
        self.score_value = float("nan")
        self._rng = RngState(conf.seed)
        self._solver = None
        self._output_fn_cache: Dict[Any, Any] = {}
        self._initialized = False
        # loss weights per output (reference: setOutputs + loss weighting)
        self.output_weights: Dict[str, float] = {n: 1.0 for n in conf.network_outputs}

    @property
    def dtype(self):
        return jnp.dtype(self.conf.dtype)

    def keeps_int_input(self, input_name: str) -> bool:
        """True when ``input_name`` feeds an index-consuming layer
        (embedding lookup) — its integer dtype is then preserved through
        every cast boundary (see core.dtypes.as_input)."""
        for spec in self.conf.vertices:
            if input_name in spec.inputs and spec.layer is not None \
                    and getattr(spec.layer, "consumes_indices", False):
                return True
        return False

    def _as_inputs(self, xs) -> tuple:
        names = self.conf.network_inputs
        return tuple(
            as_input(x, self.dtype,
                     self.keeps_int_input(names[i]) if i < len(names) else False)
            for i, x in enumerate(xs)
        )

    def _to_compute(self, params, inputs):
        """Mixed-precision boundary (see MultiLayerNetwork._to_compute)."""
        cd = getattr(self.conf, "compute_dtype", None)
        if not cd or jnp.dtype(cd) == self.dtype:
            return params, inputs
        from ..core.dtypes import cast_floats

        return cast_floats(params, cd), [cast_floats(x, cd) for x in inputs]

    # Solver compatibility surface ------------------------------------------
    def named_param_layers(self) -> List[Tuple[str, Layer]]:
        return [
            (s.name, s.layer) for s in self.conf.vertices
            if s.layer is not None and s.layer.has_params()
        ]

    def linear_chain(self) -> List[VertexSpec]:
        """The vertex sequence when this graph is one input→output layer
        chain (each vertex a layer consuming exactly the previous vertex's
        output) — the shape pipeline-stage partitioning requires. Raises
        ``ValueError`` for branching/merging topologies or op vertices."""
        conf = self.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError(
                "pipeline partitioning needs exactly one graph input and "
                f"one output, got {len(conf.network_inputs)}/"
                f"{len(conf.network_outputs)}")
        prev = conf.network_inputs[0]
        chain: List[VertexSpec] = []
        for spec in conf.vertices:
            if spec.layer is None:
                raise ValueError(
                    f"vertex {spec.name!r} is an op vertex — pipeline "
                    "partitioning needs a pure layer chain")
            if tuple(spec.inputs) != (prev,):
                raise ValueError(
                    f"vertex {spec.name!r} consumes {spec.inputs}, not the "
                    f"previous vertex {prev!r} — not a linear chain")
            chain.append(spec)
            prev = spec.name
        if prev != conf.network_outputs[0]:
            raise ValueError(
                f"the chain ends at {prev!r}, not the network output "
                f"{conf.network_outputs[0]!r}")
        return chain

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        rng = RngState(self.conf.seed if seed is None else seed)
        dtype = self.dtype
        self.params, self.state, self._persistent_keys = {}, {}, {}
        for spec in self.conf.vertices:
            if spec.layer is None:
                continue
            name = spec.name
            self.params[name] = (
                spec.layer.init(rng.next_key(), dtype) if spec.layer.has_params() else {}
            )
            st = spec.layer.init_state(dtype)
            self.state[name] = st
            self._persistent_keys[name] = tuple(st.keys())
        self._initialized = True
        self._output_fn_cache.clear()
        self._solver = None
        return self

    def _check_init(self) -> None:
        if not self._initialized:
            self.init()

    def migrate_state(self) -> None:
        """Fill persistent-state keys added by newer framework versions with
        their ``init_state`` defaults, keeping existing values (see
        MultiLayerNetwork.migrate_state — e.g. PR 3's MoE
        ``expert_tokens``/``dropped_tokens`` keys)."""
        if not self._initialized:
            return
        changed = False
        for spec in self.conf.vertices:
            if spec.layer is None:
                continue
            defaults = spec.layer.init_state(self.dtype)
            if not defaults:
                continue
            cur = dict(self.state.get(spec.name, {}))
            missing = [k for k in defaults if k not in cur]
            if missing:
                for k in missing:
                    cur[k] = defaults[k]
                self.state[spec.name] = cur
                self._persistent_keys[spec.name] = tuple(cur.keys())
                changed = True
        if changed:
            self._output_fn_cache.clear()

    # -------------------------------------------------------------- forward
    def forward_pure(
        self,
        params,
        state,
        inputs: Sequence[jax.Array],
        *,
        train: bool,
        rng: Optional[jax.Array],
        masks: Optional[Sequence[Optional[jax.Array]]] = None,
        stop_at_outputs: bool = True,
        dist=None,
    ):
        """Topo-order forward. Returns ({vertex: activation}, new_state)."""
        params, inputs = self._to_compute(params, inputs)
        acts: Dict[str, jax.Array] = dict(zip(self.conf.network_inputs, inputs))
        vmasks: Dict[str, Optional[jax.Array]] = {}
        if masks is not None:
            vmasks.update(zip(self.conf.network_inputs, masks))
        new_state: Dict[str, Dict[str, jax.Array]] = {}
        for vi, spec in enumerate(self.conf.vertices):
            xs = [acts[i] for i in spec.inputs]
            in_mask = vmasks.get(spec.inputs[0]) if spec.inputs else None
            if spec.layer is not None:
                x = xs[0]
                key = jax.random.fold_in(rng, vi) if rng is not None else None
                ctx = LayerContext(train=train, rng=key, mask=in_mask, dist=dist)
                if spec.preprocessor is not None:
                    x, _ = spec.preprocessor.apply({}, {}, x, ctx)
                lstate = dict(state.get(spec.name, {}))
                y, lstate_out = _apply_layer(
                    spec.layer, params.get(spec.name, {}), lstate, x, ctx,
                    name=spec.name,
                    remat=self.conf.gradient_checkpointing and train)
                persistent = self._persistent_keys.get(spec.name, ())
                new_state[spec.name] = {k: v for k, v in lstate_out.items() if k in persistent}
                vmasks[spec.name] = spec.layer.feed_forward_mask(in_mask, None) if in_mask is not None else None
            else:
                y = spec.vertex.apply(*xs)
                vmasks[spec.name] = in_mask
            acts[spec.name] = y
        return acts, new_state

    def loss_pure(
        self,
        params,
        state,
        inputs: Sequence[jax.Array],
        labels: Sequence[jax.Array],
        *,
        rng: Optional[jax.Array],
        masks=None,
        label_masks: Optional[Sequence[Optional[jax.Array]]] = None,
        train: bool = True,
        dist=None,
    ):
        """Weighted sum of output-layer losses + regularization."""
        # regularization runs on master (uncast) params; forward math in
        # compute_dtype
        master_params = params
        params, inputs = self._to_compute(params, inputs)
        acts_needed: Dict[str, jax.Array] = {}
        # run the full graph once; output layers need their INPUT activations,
        # so run forward but for output layer vertices compute loss instead.
        acts: Dict[str, jax.Array] = dict(zip(self.conf.network_inputs, inputs))
        vmasks: Dict[str, Optional[jax.Array]] = {}
        if masks is not None:
            vmasks.update(zip(self.conf.network_inputs, masks))
        new_state: Dict[str, Dict[str, jax.Array]] = {}
        losses: Dict[str, jax.Array] = {}
        label_by_output = dict(zip(self.conf.network_outputs, labels))
        lmask_by_output: Dict[str, Optional[jax.Array]] = {}
        if label_masks is not None:
            lmask_by_output.update(zip(self.conf.network_outputs, label_masks))

        for vi, spec in enumerate(self.conf.vertices):
            xs = [acts[i] for i in spec.inputs]
            in_mask = vmasks.get(spec.inputs[0]) if spec.inputs else None
            if spec.layer is not None:
                x = xs[0]
                key = jax.random.fold_in(rng, vi) if rng is not None else None
                ctx = LayerContext(train=train, rng=key, mask=in_mask, dist=dist)
                if spec.preprocessor is not None:
                    x, _ = spec.preprocessor.apply({}, {}, x, ctx)
                lstate = dict(state.get(spec.name, {}))
                is_loss_output = (
                    isinstance(spec.layer, BaseOutputLayer)
                    and spec.name in label_by_output
                )
                if is_loss_output:
                    # under the layer's name, as apply_layer puts the others
                    with jax.named_scope(spec.name):
                        losses[spec.name] = spec.layer.compute_loss(
                            params.get(spec.name, {}), x,
                            label_by_output[spec.name], ctx,
                            label_mask=lmask_by_output.get(spec.name),
                        )
                y, lstate_out = _apply_layer(
                    spec.layer, params.get(spec.name, {}), lstate, x, ctx,
                    name=spec.name,
                    remat=self.conf.gradient_checkpointing and train)
                persistent = self._persistent_keys.get(spec.name, ())
                new_state[spec.name] = {k: v for k, v in lstate_out.items() if k in persistent}
                vmasks[spec.name] = None if in_mask is None else spec.layer.feed_forward_mask(in_mask, None)
            else:
                y = spec.vertex.apply(*xs)
                vmasks[spec.name] = in_mask
            acts[spec.name] = y

        score_dtype = jnp.promote_types(self.dtype, jnp.float32)
        total = jnp.asarray(0.0, score_dtype)
        for name, l in losses.items():
            total = total + self.output_weights.get(name, 1.0) * l.astype(score_dtype)
        for name, layer in self.named_param_layers():
            if master_params.get(name):
                total = total + _layer_reg_score(layer, master_params[name], score_dtype)
            # MoE load-balance aux loss (GShard), same contract as the
            # sequential path: forward stashed this batch's aux in state
            bl_w = getattr(layer, "balance_loss_weight", 0.0)
            if bl_w:
                aux = new_state.get(name, {}).get("aux_load_balance")
                if aux is not None:
                    total = total + bl_w * aux.astype(score_dtype)
        return total, new_state

    # -------------------------------------------------------------- user API
    @staticmethod
    def _as_tuple(x) -> Tuple:
        if isinstance(x, (list, tuple)):
            return tuple(x)
        return (x,)

    def output(self, *inputs, masks=None):
        """Inference; returns one array or a tuple matching network_outputs."""
        self._check_init()
        xs = self._as_inputs(inputs)
        key = ("output", masks is not None)
        if key not in self._output_fn_cache:
            def fn(params, state, xs, masks):
                acts, _ = self.forward_pure(params, state, xs, train=False, rng=None, masks=masks)
                # user-facing outputs in the model dtype even under a bf16
                # compute_dtype (mixed precision is an internal property)
                return tuple(acts[n].astype(self.dtype) for n in self.conf.network_outputs)

            self._output_fn_cache[key] = jax.jit(fn)
        outs = self._output_fn_cache[key](self.params, self.state, xs, masks)
        return outs[0] if len(outs) == 1 else outs

    def score(self, features, labels, masks=None, label_masks=None) -> float:
        self._check_init()
        xs = self._as_inputs(self._as_tuple(features))
        ys = tuple(jnp.asarray(y) for y in self._as_tuple(labels))
        s, _ = self.loss_pure(self.params, self.state, xs, ys, rng=None,
                              masks=masks, label_masks=label_masks, train=False)
        return float(s)

    def calculate_gradients(self, features, labels, mask=None, label_mask=None):
        self._check_init()
        xs = self._as_inputs(self._as_tuple(features))
        ys = tuple(jnp.asarray(y) for y in self._as_tuple(labels))
        masks = None if mask is None else self._as_tuple(mask)
        lmasks = None if label_mask is None else self._as_tuple(label_mask)

        def loss_of(p):
            s, _ = self.loss_pure(p, self.state, xs, ys, rng=None,
                                  masks=masks, label_masks=lmasks, train=True)
            return s

        return jax.grad(loss_of)(self.params)

    # ------------------------------------------------------------------ fit
    def add_listeners(self, *listeners: TrainingListener) -> None:
        for l in listeners:
            self.listeners.add(l)

    def fit(self, data, labels=None, *, epochs: int = 1) -> "ComputationGraph":
        self._check_init()
        from ..train.graph_solver import GraphSolver

        if self._solver is None:
            self._solver = GraphSolver(self)
        self._solver.fit(data, labels, epochs=epochs)
        return self

    # alias used by serializer
    @property
    def _trainer(self):
        return self._solver

    @_trainer.setter
    def _trainer(self, v) -> None:
        self._solver = v

    def evaluate(self, iterator_or_features, labels=None):
        from ..train.evaluation import Evaluation
        from .sequential import _as_batches

        ev = Evaluation()
        for feats, labs, msk, lmsk in _as_batches(iterator_or_features, labels, None):
            out = self.output(*self._as_tuple(feats))
            first = out[0] if isinstance(out, tuple) else out
            first_lab = self._as_tuple(labs)[0]
            ev.eval(np.asarray(first_lab), np.asarray(first))
        return ev

    def num_params(self) -> int:
        return int(sum(l.size for l in jax.tree_util.tree_leaves(self.params)))

    def summary(self) -> str:
        lines = [f"{'name':<28}{'type':<28}{'inputs':<30}{'params':>10}"]
        total = 0
        for spec in self.conf.vertices:
            kind = type(spec.layer or spec.vertex).__name__
            n = sum(int(a.size) for a in self.params.get(spec.name, {}).values())
            total += n
            lines.append(f"{spec.name:<28}{kind:<28}{','.join(spec.inputs):<30}{n:>10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)

    def clone(self) -> "ComputationGraph":
        m = ComputationGraph(self.conf)
        if self._initialized:
            m.params = jax.tree_util.tree_map(lambda a: a, self.params)
            m.state = jax.tree_util.tree_map(lambda a: a, self.state)
            m._persistent_keys = dict(self._persistent_keys)
            m._initialized = True
        return m
