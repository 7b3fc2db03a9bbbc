"""DistributedTrainer — SPMD training over a device mesh.

Replaces (SURVEY.md §2.3): ``ParallelWrapper`` (single-node multi-device DP),
``SharedTrainingMaster``/``ModelParameterServer`` (multi-node gradient
sharing), and ``ParameterAveragingTrainingMaster`` (periodic averaging) with
ONE jitted step over a ``jax.sharding.Mesh``. Where the reference replicated
the model per device and moved gradients through host-side accumulators and
Aeron UDP (SURVEY.md §3.4), here the batch is sharded over the ``data`` axis
and the gradient exchange is a compiler-scheduled all-reduce over ICI —
or an explicit strategy (threshold-compressed / parameter averaging) run
inside ``shard_map``.

Tensor parallelism (absent in the reference, §2.3) comes from
``param_sharding_rules``: regex → PartitionSpec over a ``model`` axis; XLA
inserts the activation collectives. Multi-host: call
``initialize_distributed()`` first and feed per-host batch shards.
"""

from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.dtypes import as_input, as_input_np
from ..nn.layers.base import DistContext
from ..obs.compiles import watch_compiles
from ..obs.tracing import get_tracer
from ..train.solver import LayerOptimizers, _normalize_gradients
from .mesh import make_mesh, shmap, zero1_partition_spec
from .strategies import GradientSyncStrategy, SyncAllReduce


_shmap = shmap  # parallel/mesh.py


def moe_expert_parallel_rules(axis: str = "model",
                              layer_pattern: str = r".*"
                              ) -> List[Tuple[str, P]]:
    """``param_sharding_rules`` for expert parallelism over ``axis``.

    Shards every :class:`~deeplearning4j_tpu.nn.layers.MixtureOfExpertsLayer`
    expert-dim parameter (``We1``/``be1``/``We2``/``be2`` all carry a
    leading ``E``) and leaves the router ``Wg`` replicated.

    On the default implicit (GSPMD) path this is valid for every
    ``dispatch_mode``: the sort/grouped paths' expert buffers keep the
    same leading expert dim as the einsum path, so GSPMD partitions the
    batched expert MLP identically and inserts the all-to-alls around the
    gather/scatter instead of the one-hot contractions.

    With an EXPLICIT strategy (shard_map path — e.g.
    ``BucketedAllReduceSync``) these rules are the sanctioned exception
    to the no-TP-rules restriction: because every matched param shards
    only its leading expert dim over one non-data axis, the trainer
    slices expert params over ``axis``, hands layers the axis name via
    ``DistContext.ep_axis``, and ``MixtureOfExpertsLayer`` spells the
    local-expert compute + ``psum_scatter`` combine itself
    (``dispatch_mode`` "sort" or "grouped"; composes with ``zero1=True``,
    which keeps sharding the replicated params' updater slices over the
    data axis while expert slices stay on ``axis``).

    ``layer_pattern`` narrows the match to specific layer names (rules are
    matched against ``"layername/paramname"``).
    """
    return [(rf"{layer_pattern}/(?:We1|be1|We2|be2)$", P(axis))]


class DistributedTrainer:
    """Data-/tensor-parallel trainer for ``MultiLayerNetwork``-style models
    (anything exposing ``loss_pure``/``forward_pure`` + ``conf`` + params).

    Parameters
    ----------
    model: the network (params/state live on it; fit() writes back).
    mesh: a ``jax.sharding.Mesh``; default = all devices on a ``data`` axis.
    strategy: gradient sync strategy (default synchronous all-reduce).
    param_sharding_rules: ``[(regex, PartitionSpec), ...]`` matched against
        ``"layername/paramname"`` — first hit wins; unmatched params are
        replicated. Only valid with the default strategy (implicit-pjit
        path), where XLA derives all collectives from shardings.
    zero1: ZeRO-1 cross-replica weight-update sharding ("Automatic
        Cross-Replica Sharding of Weight Update in Data-Parallel
        Training", PAPERS.md). Updater (optimizer) state is partitioned
        1/N over the data axis — each replica updates only its parameter
        slice and the updated slices are all-gathered — cutting the
        dominant optimizer-memory term AND the update FLOPs per chip.
        On the implicit (GSPMD) path this is pure sharding annotations:
        opt_state leaves get ``P(data, ...)`` in/out shardings and the
        gradients a matching sharding constraint, so XLA emits the
        reduce-scatter → sharded update → all-gather schedule. On the
        explicit strategy path the same schedule is spelled by hand
        inside ``shard_map`` (dynamic-slice → sliced optax update →
        ``all_gather``). Composes with tensor parallelism (TP-sharded
        dims are preserved; dim 0 is sharded over ``data`` on top) and
        with compressed gradient exchange; rejected for strategies whose
        replicas apply *different* gradients between sync points
        (``ParameterAveragingSync``), because a replica may only own a
        param slice if every replica's update agrees. Leaves whose dim 0
        the data axis does not divide, and layers whose updater is not
        elementwise (``IUpdater.elementwise``), stay replicated.
    bn_group_size: distributed batch norm — every
        :class:`~deeplearning4j_tpu.nn.layers.BatchNormalizationLayer`
        without its own ``stats_axis_group`` averages its training batch
        statistics over groups of this many adjacent data-parallel
        replicas (must divide the data axis). The per-chip batch shrinks
        as DP widens and per-replica moments degrade (MLPerf TPU-pods
        paper); a group of 2-8 replicas restores the effective
        normalization batch without paying a full-axis collective.
        ``None`` keeps each path's historical spelling (explicit: local
        stats; implicit GSPMD: global-batch stats).
    registry: metrics registry (default: process-global) for the
        ``dl4j_tpu_training_updater_state_bytes{sharded=}`` gauge and —
        for compressed strategies — the
        ``dl4j_tpu_training_grad_compression_ratio`` histogram, plus the
        ``dl4j_tpu_training_trust_ratio{layer=}`` /
        ``dl4j_tpu_training_grad_norm{layer=}`` series when the updater
        is trust-ratio based (Lars/Lamb).
    metrics_every: record the compression ratio / trust-ratio series
        every N iterations (reading them fetches device scalars;
        0 disables the per-step recording entirely).
    """

    def __init__(
        self,
        model,
        mesh: Optional[Mesh] = None,
        strategy: Optional[GradientSyncStrategy] = None,
        param_sharding_rules: Optional[Sequence[Tuple[str, P]]] = None,
        data_axis: str = "data",
        donate_inputs: bool = False,
        zero1: bool = False,
        bn_group_size: Optional[int] = None,
        registry=None,
        metrics_every: int = 1,
    ) -> None:
        self.model = model
        # donate the batch buffers to the jitted step (sharded-loader
        # path: every batch is a fresh per-shard device_put, so XLA can
        # reuse the input HBM across steps). Callers re-feeding the same
        # device array each step must leave this off (see Solver).
        self.donate_inputs = bool(donate_inputs)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.strategy = strategy or SyncAllReduce()
        self.data_axis = data_axis
        self.zero1 = bool(zero1)
        if data_axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no {data_axis!r} axis: {self.mesh.axis_names}")
        self.bn_group_size = None if bn_group_size is None else int(bn_group_size)
        if self.bn_group_size is not None and (
                self.bn_group_size < 1
                or self.n_data_shards % self.bn_group_size):
            raise ValueError(
                f"bn_group_size {self.bn_group_size} must divide the data "
                f"axis ({self.n_data_shards} shards)")
        self._ep_axis: Optional[str] = None
        if param_sharding_rules and self.strategy.explicit:
            # Sanctioned exception: pure expert-parallel rules (every spec
            # shards ONLY dim 0 over one non-data mesh axis — the shape
            # moe_expert_parallel_rules emits). The MoE layers spell the
            # local compute + combine themselves via DistContext.ep_axis;
            # any other rule shape still has no explicit-path spelling.
            self._ep_axis = self._resolve_ep_axis(param_sharding_rules)
            if self._ep_axis is None:
                raise ValueError(
                    "param_sharding_rules (tensor parallelism) requires the "
                    "default SyncAllReduce strategy — explicit strategies "
                    "replicate params. Exception: expert-parallel rules "
                    "(every spec P(axis) on dim 0 over one non-data axis, "
                    "e.g. moe_expert_parallel_rules()) are spelled "
                    "explicitly by the MoE layers."
                )
        if self.zero1 and not getattr(self.strategy, "replicated_grads", True):
            raise ValueError(
                "zero1 requires a strategy whose synced gradients are identical "
                "on every replica; ParameterAveragingSync applies purely local "
                "updates between sync points, so no replica may own a 1/N "
                "parameter slice"
            )
        self.rules = [(re.compile(pat), spec) for pat, spec in (param_sharding_rules or [])]

        self.dropped_rows = 0  # unshardable tail rows (see fit)
        self.optim = LayerOptimizers(model)
        self._replicated = NamedSharding(self.mesh, P())
        self._data_sharding = NamedSharding(self.mesh, P(data_axis))  # batch dim sharded
        # Multi-process ("multi-node without a cluster", SURVEY §4): the mesh
        # spans devices this process cannot address, so global arrays are
        # assembled from process-local data. Pure DP only — every process
        # must hold identical params (same seed), the reference's
        # SharedTrainingWrapper contract.
        self._multiprocess = jax.process_count() > 1 and any(
            d.process_index != jax.process_index() for d in self.mesh.devices.flat)
        if self._multiprocess and self.rules:
            raise ValueError(
                "param_sharding_rules (TP) is single-process; multi-process "
                "training is data-parallel with replicated params")
        self._zero1_shapes = self._zero1_shardable_shapes()
        self._zero1_flags = {
            ln: {pn: tuple(np.shape(p)) in self._zero1_shapes[ln]
                 for pn, p in lp.items()}
            for ln, lp in model.params.items()
        }
        host_opt = self.optim.init(model.params)
        self._opt_shardings = self._updater_shardings(host_opt)
        self.params = self._put_tree(model.params, self._param_shardings())
        self.state = self._put_tree(model.state, self._replicated)
        self.opt_state = self._put_tree(host_opt, self._opt_shardings)
        # Explicit EP: the sync strategy sees LOCAL (per-expert-shard)
        # grad shapes inside shard_map, so shape-derived layouts (e.g.
        # BucketedAllReduceSync's buckets) must be sized from the local
        # template, and per-shard persistent sync state (compression
        # error feedback) would diverge across the expert axis — reject.
        strat_template = (model.params if self._ep_axis is None
                          else self._ep_local_template())
        strat0 = self.strategy.init_state(strat_template)
        if self._ep_axis is not None and any(
                np.ndim(leaf) > 0
                for leaf in jax.tree_util.tree_leaves(strat0)):
            raise ValueError(
                "expert parallelism on the explicit path requires a sync "
                "strategy without per-replica persistent state (error "
                "feedback would diverge across expert shards); use "
                "BucketedAllReduceSync or SyncAllReduce")
        self.strat_state = self._put_tree(strat0, self._replicated)
        self.iteration = 0
        self._step = None
        watch_compiles()
        self.metrics_every = int(metrics_every)
        self._init_metrics(registry)

    def _put_tree(self, tree, shardings):
        if not self._multiprocess:
            return jax.device_put(tree, shardings)

        def put_one(leaf, sh):
            arr = np.asarray(leaf)
            if not sh.is_fully_replicated:
                # zero1-sharded updater leaf: every process holds the
                # identical full value host-side (same-seed contract), so
                # each addressable device picks its global slice
                return jax.make_array_from_callback(
                    arr.shape, sh, lambda idx, a=arr: a[idx])
            return jax.make_array_from_process_local_data(sh, arr)

        if isinstance(shardings, NamedSharding):
            return jax.tree_util.tree_map(
                lambda leaf: put_one(leaf, shardings), tree)
        return jax.tree_util.tree_map(put_one, tree, shardings)

    # ----- explicit expert parallelism -------------------------------
    def _resolve_ep_axis(self, rules) -> Optional[str]:
        """The expert-parallel mesh axis IF every rule spec is P(axis) on
        dim 0 over one shared non-data mesh axis; None otherwise."""
        axes = set()
        for _, spec in rules:
            entries = tuple(spec)
            if len(entries) != 1 or entries[0] is None:
                return None
            ax = entries[0]
            if isinstance(ax, (tuple, list)):
                return None
            axes.add(ax)
        if len(axes) != 1:
            return None
        ax = axes.pop()
        if ax == self.data_axis or ax not in self.mesh.axis_names:
            return None
        return ax

    @property
    def ep_shards(self) -> int:
        return self.mesh.shape[self._ep_axis] if self._ep_axis else 1

    def _ep_local_template(self):
        """Host template of the PER-SHARD param shapes under explicit EP
        (expert dim divided over the EP axis) — what grads look like
        inside shard_map, for shape-derived strategy layouts."""
        n = self.ep_shards
        out = {}
        for ln, lp in self.model.params.items():
            d = {}
            for pn, p in lp.items():
                shp = list(np.shape(p))
                spec = self._spec_for(f"{ln}/{pn}")
                if tuple(spec) and shp:
                    if shp[0] % n:
                        raise ValueError(
                            f"expert-parallel param {ln}/{pn} dim 0 "
                            f"({shp[0]}) must divide the {self._ep_axis!r} "
                            f"axis ({n} shards)")
                    shp[0] //= n
                d[pn] = np.zeros(shp, dtype=np.asarray(p).dtype)
            out[ln] = d
        return out

    # ----- shardings -------------------------------------------------
    def _spec_for(self, path: str) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        return P()

    def _param_shardings(self):
        if not self.rules:
            return self._replicated

        def one(layer_params, lname):
            return {
                k: NamedSharding(self.mesh, self._spec_for(f"{lname}/{k}"))
                for k in layer_params
            }

        return {ln: one(lp, ln) for ln, lp in self.model.params.items()}

    # ----- ZeRO-1 updater sharding -----------------------------------
    def _zero1_shardable_shapes(self):
        """Per layer: the set of param shapes ZeRO-1 may shard — dim 0
        divisible by the data axis, layer trainable under an elementwise
        update chain, and dim 0 not already taken by a TP rule. Updater
        leaves are matched to params BY SHAPE (optax moments/traces are
        param-shaped), so one predicate keeps grads/params/opt slices
        aligned on the explicit path and the sharding annotations
        consistent on the implicit path."""
        n = self.n_data_shards
        out = {}
        for lname, lparams in self.model.params.items():
            shapes = set()
            if (self.zero1 and n > 1 and lname in self.optim.txs
                    and self.optim.elementwise.get(lname, False)):
                for pname, p in lparams.items():
                    shp = tuple(np.shape(p))
                    base = self._spec_for(f"{lname}/{pname}")
                    if zero1_partition_spec(shp, n, self.data_axis, base) != base:
                        shapes.add(shp)
            out[lname] = shapes
        return out

    def _zero1_spec(self, lname: str, shape: Tuple[int, ...],
                    base: Optional[P] = None) -> P:
        base = base if base is not None else P()
        if shape in self._zero1_shapes.get(lname, ()):
            return zero1_partition_spec(
                shape, self.n_data_shards, self.data_axis, base)
        return base

    def _updater_shardings(self, host_opt):
        """Sharding tree for opt_state: under zero1, param-shaped leaves
        shard dim 0 over the data axis (composed with the param's TP spec
        when rules shard other dims); everything else — scalars (step
        counts), non-divisible leaves, non-elementwise layers — stays
        replicated. Without zero1: fully replicated (the historical
        layout, and what pre-zero1 checkpoints expect) — except under
        explicit EP, where param-shaped leaves follow their param's
        expert sharding so the per-shard optax update sees matching
        slices."""
        if not self.zero1 and self._ep_axis is None:
            return self._replicated
        out = {}
        for lname, lstate in host_opt.items():
            base_by_shape = {}
            if self.rules:
                for pname, p in self.model.params[lname].items():
                    base_by_shape.setdefault(
                        tuple(np.shape(p)), self._spec_for(f"{lname}/{pname}"))

            def spec_one(leaf, _l=lname, _b=base_by_shape):
                shp = tuple(np.shape(leaf))
                return NamedSharding(
                    self.mesh, self._zero1_spec(_l, shp, _b.get(shp)))

            out[lname] = jax.tree_util.tree_map(spec_one, lstate)
        return out

    def _updater_pspecs(self):
        """PartitionSpec mirror of :meth:`_updater_shardings` for the
        explicit (shard_map) path's in/out specs."""
        if not self.zero1 and self._ep_axis is None:
            return P()
        return jax.tree_util.tree_map(
            lambda sh: sh.spec, self._opt_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    # ----- step compilation ------------------------------------------
    def _build_step(self):
        model = self.model
        conf = model.conf
        optim = self.optim
        strategy = self.strategy
        axis = self.data_axis

        is_graph = self._is_graph

        def local_grads(params, state, x, y, rng, dist):
            def loss_fn(p):
                return model.loss_pure(p, state, x, y, rng=rng, train=True,
                                       dist=dist)

            if is_graph:  # graph aux is new_state directly
                (score, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            else:
                (score, (new_state, _)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            return score, new_state, grads

        if not strategy.explicit:
            # Implicit path: sharded batch + (possibly rule-sharded) params;
            # the mean-loss gradient IS the all-reduced gradient — XLA emits
            # the psum/all-gathers from the shardings (GSPMD). Under zero1
            # the opt_state in/out shardings plus a matching gradient
            # sharding constraint turn the update into the ZeRO-1 schedule:
            # reduce-scatter(grads) → 1/N-sharded update → all-gather(params)
            # — all placed by XLA from the annotations.
            grad_cons = None
            if self.zero1:
                grad_cons = {
                    ln: {pn: (NamedSharding(
                            self.mesh,
                            self._zero1_spec(ln, tuple(np.shape(p)),
                                             self._spec_for(f"{ln}/{pn}")))
                          if self._zero1_flags[ln][pn] else None)
                         for pn, p in lp.items()}
                    for ln, lp in model.params.items()
                }

            dist = DistContext(axis=None, n_shards=self.n_data_shards,
                               bn_group_size=self.bn_group_size)

            def train_step(params, opt_state, state, strat_state, x, y, rng,
                           it):
                with jax.named_scope("loss_and_grad"):
                    score, new_state, grads = local_grads(
                        params, state, x, y, rng, dist)
                grads = _normalize_gradients(
                    grads, conf.gradient_normalization, conf.gradient_normalization_threshold
                )
                if grad_cons is not None:
                    grads = {
                        ln: {pn: (g if grad_cons[ln].get(pn) is None else
                                  jax.lax.with_sharding_constraint(
                                      g, grad_cons[ln][pn]))
                             for pn, g in lg.items()}
                        for ln, lg in grads.items()
                    }
                new_params, new_opt = optim.update(grads, opt_state, params)
                return new_params, new_opt, new_state, strat_state, score

            return jax.jit(
                train_step,
                in_shardings=(
                    self._param_shardings(), self._opt_shardings, self._replicated,
                    self._replicated, self._data_sharding, self._data_sharding,
                    self._replicated, self._replicated,
                ),
                out_shardings=(
                    self._param_shardings(), self._opt_shardings, self._replicated,
                    self._replicated, self._replicated,
                ),
                donate_argnums=(0, 1, 2, 3) + (
                    (4, 5) if self.donate_inputs else ()),
            )

        # Explicit path: per-replica grads -> strategy.sync collective.
        # Under zero1, the post-sync gradients agree on every replica, so
        # each replica dynamic-slices its 1/N of (grads, params), applies
        # the optax update against its resident opt_state slice (arriving
        # pre-sliced via the P(data) in_specs), and all-gathers the
        # updated param slices — the hand-spelled ZeRO-1 schedule.
        n = self.n_data_shards
        flags = self._zero1_flags if self.zero1 else None
        if flags is not None:
            # trust-ratio updaters (Lars/Lamb) must compute their layer
            # norms as slice-local sums + psum when applied to 1/N
            # slices; the zero1-spelled chains share state trees with
            # self.optim, so init/checkpoints stay compatible
            optim = LayerOptimizers(model, zero1_axis=axis,
                                    zero1_sliced=flags)
        dist = DistContext(axis=axis, n_shards=n,
                           bn_group_size=self.bn_group_size,
                           ep_axis=self._ep_axis, ep_shards=self.ep_shards)

        def train_step(params, opt_state, state, strat_state, x, y, rng, it):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            with jax.named_scope("loss_and_grad"):
                score, new_state, grads = local_grads(
                    params, state, x, y, rng, dist)
            with jax.named_scope("grad_sync"):
                grads, new_strat = strategy.sync(grads, strat_state, axis)
            grads = _normalize_gradients(
                grads, conf.gradient_normalization, conf.gradient_normalization_threshold
            )
            if flags is not None:
                idx = jax.lax.axis_index(axis)

                def slc(leaf):
                    size = leaf.shape[0] // n
                    return jax.lax.dynamic_slice_in_dim(
                        leaf, idx * size, size, axis=0)

                params_l = {ln: {pn: (slc(p) if flags[ln][pn] else p)
                                 for pn, p in lp.items()}
                            for ln, lp in params.items()}
                grads_l = {ln: {pn: (slc(g) if flags[ln][pn] else g)
                                for pn, g in lg.items()}
                           for ln, lg in grads.items()}
                new_params, new_opt = optim.update(grads_l, opt_state, params_l)
                new_params = {
                    ln: {pn: (jax.lax.all_gather(p, axis, axis=0, tiled=True)
                              if flags[ln][pn] else p)
                         for pn, p in lp.items()}
                    for ln, lp in new_params.items()
                }
            else:
                new_params, new_opt = optim.update(grads, opt_state, params)
            new_params = strategy.sync_params(new_params, it, axis)
            # state (e.g. batchnorm running stats) follows the local shard;
            # average it so replicas agree, like the reference's param
            # averaging of each worker's model.
            new_state = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, axis) if jnp.issubdtype(s.dtype, jnp.floating) else s,
                new_state,
            )
            score = jax.lax.pmean(score, axis)
            return new_params, new_opt, new_state, new_strat, score

        rep = P()
        data = P(self.data_axis)
        opt_specs = self._updater_pspecs()
        # Under explicit EP, expert params enter/leave the shard_map
        # sliced over the expert axis; everything else stays replicated.
        if self._ep_axis is not None:
            param_specs = {
                ln: {pn: self._spec_for(f"{ln}/{pn}") for pn in lp}
                for ln, lp in model.params.items()
            }
        else:
            param_specs = rep
        mapped = _shmap(
            train_step,
            self.mesh,
            in_specs=(param_specs, opt_specs, rep, rep, data, data, rep, rep),
            out_specs=(param_specs, opt_specs, rep, rep, rep),
        )
        return jax.jit(mapped, donate_argnums=(0, 1, 2, 3) + (
            (4, 5) if self.donate_inputs else ()))

    # ----- public API -------------------------------------------------
    @property
    def n_data_shards(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def data_sharding(self) -> NamedSharding:
        """The batch-dim sharding the jitted step consumes — hand this to
        :class:`~deeplearning4j_tpu.data.sharded.ShardedDataSetIterator`
        so the input tier assembles batches directly against it (per-host
        loading; no full-batch staging through one device)."""
        return self._data_sharding

    def _is_presharded(self, a) -> bool:
        """True for a global jax.Array already laid out on this trainer's
        data sharding (a ShardedDataSetIterator batch): host prep and
        device_put are both skipped — the rows are already in HBM on
        their owning shards."""
        return (isinstance(a, jax.Array)
                and getattr(a, "sharding", None) is not None
                and a.sharding.is_equivalent_to(self._data_sharding, a.ndim))

    @property
    def _is_graph(self) -> bool:
        """ComputationGraph models take SEQUENCES of inputs/labels and key
        keeps_int_input by input name — the ResNet-50/BERT path."""
        return hasattr(self.model.conf, "network_inputs")

    def _keeps_int_input(self) -> bool:
        fn = getattr(self.model, "keeps_int_input", None)
        return bool(fn()) if callable(fn) else False

    def _prep_inputs(self, x, y):
        """Host-side dtype handling for both model families: returns
        (x, y) as a single array each (Sequential) or tuples (Graph).
        Pre-sharded global arrays pass through untouched (their dtype
        prep happened host-side in the sharded loader, per shard)."""
        model = self.model
        if self._is_graph:
            xs = (x,) if not isinstance(x, (list, tuple)) else tuple(x)
            ys = (y,) if not isinstance(y, (list, tuple)) else tuple(y)
            names = model.conf.network_inputs
            xs = tuple(
                xi if self._is_presharded(xi) else
                as_input_np(xi, model.dtype,
                            model.keeps_int_input(names[i])
                            if i < len(names) else False)
                for i, xi in enumerate(xs))
            return xs, tuple(
                yi if self._is_presharded(yi) else np.asarray(yi)
                for yi in ys)
        if self._is_presharded(x):
            return x, (y if self._is_presharded(y) else np.asarray(y))
        return as_input_np(x, model.dtype, self._keeps_int_input()), \
            np.asarray(y)

    def _put_data(self, tree):
        """Shard a data array or tuple of arrays over the data axis.
        Leaves already assembled against the data sharding (per-shard
        device_put in the input tier) are NOT re-transferred."""
        def put_one(a):
            if self._is_presharded(a):
                return a
            if self._multiprocess:
                return jax.make_array_from_process_local_data(
                    self._data_sharding, a)
            return jax.device_put(a, self._data_sharding)

        return jax.tree_util.tree_map(put_one, tree)

    def fit_batch(self, x, y) -> float:
        # one ``fit.step`` trace of the process's tracer per call, as the
        # single-device solvers give (train/solver.py)
        span = get_tracer().span
        with span("fit.step", parent=None,
                  attrs={"step": self.iteration + 1}) as step:
            return self._fit_batch(x, y, span, step)

    def _fit_batch(self, x, y, span, step) -> float:
        if self._step is None:
            self._step = self._build_step()
        model = self.model
        # keep host arrays host-side until device_put so each row goes
        # host->owning-shard once (jnp.asarray first would commit to the
        # default device and pay a second device->device scatter)
        with span("fit.h2d", parent=step):
            x, y = self._prep_inputs(x, y)
            first = x[0] if isinstance(x, tuple) else x
            n = self.n_data_shards
            if self._is_presharded(first):
                # already a GLOBAL array assembled by the sharded input tier
                if first.shape[0] % n:
                    raise ValueError(
                        f"global batch {first.shape[0]} not divisible by "
                        f"data axis {n}")
            elif self._multiprocess:
                # each process feeds its LOCAL rows; the global batch is the
                # concatenation across processes (local_rows * process_count)
                global_rows = first.shape[0] * jax.process_count()
                if global_rows % n:
                    raise ValueError(
                        f"global batch {global_rows} not divisible by data "
                        f"axis {n}")
            elif first.shape[0] % n:
                raise ValueError(
                    f"batch {first.shape[0]} not divisible by data axis {n}")
            # PerformanceListener/MetricsListener read examples-per-iteration
            # off the model
            model.last_batch_size = int(first.shape[0])
            x = self._put_data(x)
            y = self._put_data(y)
        step.set_attribute("batch", model.last_batch_size)
        rng = model._rng.next_key()
        self.iteration += 1
        it = jnp.asarray(self.iteration, jnp.int32)
        with span("fit.dispatch", parent=step):
            out = self._step(
                self.params, self.opt_state, self.state, self.strat_state,
                x, y, rng, it)
        with span("fit.host", parent=step):
            (self.params, self.opt_state, self.state, self.strat_state,
             score) = out
            self._record_compression()
        return score

    def fit(self, data, labels=None, *, epochs: int = 1) -> float:
        """Train; accepts (features, labels) arrays or a DataSetIterator.

        Batches are re-chunked to a uniform size that divides the data axis
        (the reference's Spark path repartitioned to uniform shards,
        SURVEY.md §2.2): rows left over from a non-divisible batch are
        carried into the next one, so no row silently vanishes. Only a
        final remainder smaller than the data axis cannot be sharded; it is
        counted in ``self.dropped_rows`` and warned about (VERDICT.md
        round-1 weak item 6)."""
        import warnings

        from ..nn.sequential import _as_batches

        model = self.model
        n = self.n_data_shards
        if self._multiprocess:
            # fit() sees only this process's LOCAL rows; the divisibility
            # unit is the local shard count. Every process MUST iterate the
            # same number of identically-sized batches (the reference's
            # Spark repartition contract) — a shorter stream on one process
            # would leave the others blocked in the all-reduce.
            n = max(n // jax.process_count(), 1)
        last = None
        sync = bool(model.listeners.listeners)
        for _ in range(epochs):
            model.listeners.epoch_start(model)
            carry_x: Optional[np.ndarray] = None
            carry_y: Optional[np.ndarray] = None
            emit: Optional[int] = None  # fixed chunk size -> one jit shape
            for feats, labs, _msk, _lmsk in _as_batches(data, labels, None):
                fx, fy = np.asarray(feats), np.asarray(labs)
                if carry_x is not None:
                    fx = np.concatenate([carry_x, fx])
                    fy = np.concatenate([carry_y, fy])
                    carry_x = carry_y = None
                if not emit:
                    # recompute until nonzero: a first batch smaller than the
                    # data axis must not freeze emit at 0 (carry would then
                    # swallow the whole epoch)
                    emit = (fx.shape[0] // n) * n
                while emit and fx.shape[0] >= emit:
                    last = self.fit_batch(fx[:emit], fy[:emit])
                    fx, fy = fx[emit:], fy[emit:]
                    self._fit_iteration_done(sync, last)
                if fx.shape[0]:
                    carry_x, carry_y = fx, fy
            if carry_x is not None and carry_x.shape[0]:
                m = (carry_x.shape[0] // n) * n
                if m:
                    last = self.fit_batch(carry_x[:m], carry_y[:m])
                    self._fit_iteration_done(sync, last)
                left = carry_x.shape[0] - m
                if left:
                    self.dropped_rows += left
                    warnings.warn(
                        f"DistributedTrainer.fit: {left} tail row(s) smaller "
                        f"than the data axis ({n}) could not be sharded and "
                        f"were dropped this epoch (total {self.dropped_rows})"
                    )
            model.listeners.epoch_end(model)
            model.epoch_count += 1
        if last is not None:
            model.score_value = float(last)
        self.sync_to_model()
        return model.score_value

    def fit_iterator(self, iterator, *, epochs: int = 1) -> float:
        """Train from a ``DataSetIterator`` WITHOUT host-side re-chunking —
        the sharded input path. Each batch feeds ``fit_batch`` exactly as
        produced; batches assembled by a
        :class:`~deeplearning4j_tpu.data.sharded.ShardedDataSetIterator`
        (global jax.Arrays on :attr:`data_sharding`) skip host prep and
        ``device_put`` entirely, so per-step H2D happens only on the
        loader's prefetch thread. Batch sizes must already divide the
        data axis (the sharded assembly guarantees it).

        Exact mid-epoch resume: a ``DataSetIterator`` is consumed from
        its CURRENT position (an iterator repositioned via
        ``load_state_dict()`` continues the interrupted epoch, which
        counts as the first of ``epochs``) and ``reset()`` only when
        exhausted. Plain iterables without ``has_next`` keep the old
        reset-per-epoch ``for`` path."""
        model = self.model
        sync = bool(model.listeners.listeners)
        last = None
        resumable = hasattr(iterator, "has_next")
        for _ in range(epochs):
            model.listeners.epoch_start(model)
            if resumable:
                if not iterator.has_next():
                    iterator.reset()
                while iterator.has_next():
                    ds = iterator.next()
                    last = self.fit_batch(ds.features, ds.labels)
                    self._fit_iteration_done(sync, last)
            else:
                for ds in iterator:
                    last = self.fit_batch(ds.features, ds.labels)
                    self._fit_iteration_done(sync, last)
            model.listeners.epoch_end(model)
            model.epoch_count += 1
        if last is not None:
            model.score_value = float(last)
        self.sync_to_model()
        return model.score_value

    def _fit_iteration_done(self, sync: bool, last) -> None:
        model = self.model
        model.iteration_count += 1
        if sync:
            if model.listeners.requires_score:
                model.score_value = float(last)
                score = model.score_value
            else:
                # score-free listeners (MetricsListener) must not force a
                # per-step device→host fetch of the loss
                score = float("nan")
            if model.listeners.requires_arrays:
                # array-hungry listeners (StatsListener) must see the
                # LIVE params, not the stale pre-fit model copy
                # (gradients stay inside the SPMD step; records omit
                # the gradients section on this path)
                self.sync_to_model()
            model.listeners.iteration_done(
                model, model.iteration_count, model.epoch_count, score
            )

    def output(self, x) -> jax.Array:
        """Sharded forward pass (inference over the data axis). Graph
        models return their first network output (or a tuple for
        multi-output graphs)."""
        model = self.model
        is_graph = self._is_graph
        if not hasattr(self, "_fwd"):
            if is_graph:
                outs = model.conf.network_outputs

                def fwd(params, state, xs):
                    acts, _ = model.forward_pure(
                        params, state, xs, train=False, rng=None)
                    # user-facing dtype, matching ComputationGraph.output
                    res = tuple(acts[n].astype(model.dtype) for n in outs)
                    return res[0] if len(res) == 1 else res
            else:
                def fwd(params, state, x):
                    out, _, _ = model.forward_pure(
                        params, state, x, train=False, rng=None)
                    return out

            self._fwd = jax.jit(
                fwd,
                in_shardings=(self._param_shardings(), self._replicated, self._data_sharding),
                out_shardings=self._data_sharding,
            )
        self._reconcile_params()
        if is_graph:
            xa, _ = self._prep_inputs(x, ())
        else:
            xa = as_input_np(x, model.dtype, self._keeps_int_input())
        if self._multiprocess:  # local rows -> global array (as in fit_batch)
            xa = jax.tree_util.tree_map(
                lambda a: jax.make_array_from_process_local_data(
                    self._data_sharding, np.asarray(a)), xa)
        return self._fwd(self.params, self.state, xa)

    def _reconcile_params(self) -> None:
        """For strategies whose replicas drift between sync points
        (parameter averaging), all-reduce params so every replica holds the
        average — this IS the averaging step, just taken out of schedule,
        matching the reference master's end-of-epoch aggregation."""
        if not getattr(self.strategy, "params_diverge", False):
            return
        axis = self.data_axis

        def avg(params):
            return jax.tree_util.tree_map(lambda p: jax.lax.pmean(p, axis), params)

        mapped = _shmap(avg, self.mesh, in_specs=(P(),), out_specs=P())
        self.params = jax.jit(mapped)(self.params)

    def sync_to_model(self) -> None:
        """Write trained params/state back onto the wrapped model (the
        reference's 'aggregate final params to driver' step). Replicas agree
        already except under parameter averaging, where this first performs
        the final average."""
        self._reconcile_params()
        self.model.params = jax.device_get(self.params)
        self.model.state = jax.device_get(self.state)

    def load_updater_state(self, host_opt) -> None:
        """Re-shard a restored updater (optimizer) state onto this
        trainer's mesh. ``host_opt`` holds GLOBAL-shape leaves (what a
        zip checkpoint written via ``jax.device_get`` or the orbax
        global-shape path stores); under ZeRO-1 each leaf is re-split
        into this mesh's ``data_axis``-width slices. Because the input is
        global-shape, it is valid regardless of the data-parallel width
        that wrote it — the elastic-resize restore path."""
        live_leaves, treedef = jax.tree_util.tree_flatten(self.opt_state)
        new_leaves = jax.tree_util.tree_leaves(host_opt)
        if len(new_leaves) != len(live_leaves):
            raise ValueError(
                "updater state structure mismatch: checkpoint has "
                f"{len(new_leaves)} leaves, trainer expects "
                f"{len(live_leaves)} — was the model/updater "
                "configuration changed between save and restore?")
        host = []
        for i, (new, live) in enumerate(zip(new_leaves, live_leaves)):
            arr = np.asarray(jax.device_get(new))
            want = tuple(live.shape)
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"updater state leaf {i} has global shape "
                    f"{tuple(arr.shape)}, trainer expects {want} — "
                    "checkpoint updater state must be saved at global "
                    "shape to restore onto a resized mesh")
            host.append(arr.astype(live.dtype))
        host_tree = jax.tree_util.tree_unflatten(treedef, host)
        self.opt_state = self._put_tree(host_tree, self._opt_shardings)

    # ----- observability ---------------------------------------------
    def _init_metrics(self, registry) -> None:
        from ..obs import get_registry

        self.registry = registry if registry is not None else get_registry()
        gauge = self.registry.gauge(
            "dl4j_tpu_training_updater_state_bytes",
            "Updater (optimizer) state bytes resident per data-parallel "
            "replica", labelnames=("sharded",))
        gauge.labels("true" if self.zero1 else "false").set(
            float(self.updater_state_bytes()))
        self._comp_hist = None
        if getattr(self.strategy, "compressed", False):
            self._comp_hist = self.registry.histogram(
                "dl4j_tpu_training_grad_compression_ratio",
                "Measured gradient-exchange compression ratio "
                "(elements per exchanged element) per recorded step",
                labelnames=("strategy",),
                buckets=(1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                         1000.0, 10000.0),
            ).labels(type(self.strategy).__name__)
        self._trust_gauge = self._gnorm_gauge = None
        if self._has_trust_state():
            self._trust_gauge = self.registry.gauge(
                "dl4j_tpu_training_trust_ratio",
                "Last recorded LARS/LAMB layer-wise trust ratio "
                "(||w||/||update||) per parameter tensor",
                labelnames=("layer",))
            self._gnorm_gauge = self.registry.gauge(
                "dl4j_tpu_training_grad_norm",
                "Last recorded per-parameter-tensor update norm (the "
                "trust-ratio denominator: grad/adam direction + decoupled "
                "weight decay)", labelnames=("layer",))

    def _has_trust_state(self) -> bool:
        """Structure-only probe: does any layer's updater state carry the
        trust-ratio scalars (Lars/Lamb)? No device fetch."""
        found = [False]

        def walk(node):
            if found[0]:
                return
            if isinstance(node, dict):
                if "trust" in node and isinstance(node["trust"], dict):
                    found[0] = True
                    return
                for v in node.values():
                    walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)

        walk(self.opt_state)
        return found[0]

    def trust_ratio_stats(self) -> dict:
        """Per-parameter-tensor trust ratio and update norm from a
        trust-ratio updater's state (Lars/Lamb):
        ``{"layer/param": {"trust_ratio": float, "update_norm": float}}``.
        Empty for other updaters. Reads device scalars — a blocking
        fetch, so call it off the hot loop (``metrics_every`` paces the
        automatic recording)."""
        out = {}

        def walk(node, lname):
            if isinstance(node, dict):
                if "trust" in node and isinstance(node["trust"], dict):
                    for pn, v in node["trust"].items():
                        entry = {"trust_ratio": float(np.asarray(v))}
                        gn = node.get("gnorm", {})
                        if pn in gn:
                            entry["update_norm"] = float(np.asarray(gn[pn]))
                        out[f"{lname}/{pn}"] = entry
                    return
                for v in node.values():
                    walk(v, lname)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v, lname)

        for lname, lstate in (self.opt_state or {}).items():
            walk(lstate, lname)
        return out

    def _record_compression(self) -> None:
        if self.metrics_every <= 0 or self.iteration % self.metrics_every:
            return
        if self._comp_hist is not None:
            stats = self.compression_stats() or {}
            ratio = stats.get("compression_ratio")
            if ratio:
                self._comp_hist.observe(float(ratio))
        if self._trust_gauge is not None:
            for label, entry in self.trust_ratio_stats().items():
                self._trust_gauge.labels(label).set(entry["trust_ratio"])
                if "update_norm" in entry:
                    self._gnorm_gauge.labels(label).set(entry["update_norm"])

    def updater_state_bytes(self, *, per_replica: bool = True) -> int:
        """Bytes of updater (optimizer) state — per replica (the HBM that
        actually sits on each data-parallel replica; under zero1 the
        sharded leaves count 1/N) or global logical bytes."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.opt_state):
            if isinstance(leaf, jax.Array) and per_replica:
                shard = leaf.sharding.shard_shape(leaf.shape)
                total += int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
            else:
                total += np.asarray(leaf).nbytes if not isinstance(
                    leaf, jax.Array) else leaf.nbytes
        return int(total)

    def compression_stats(self) -> Optional[dict]:
        """The strategy's compression view (threshold / measured density /
        ratio) or ``None`` for uncompressed strategies. Reads device
        scalars — a blocking fetch, so call it off the hot loop (or let
        ``metrics_every`` pace the automatic recording)."""
        fn = getattr(self.strategy, "compression_stats", None)
        return fn(self.strat_state) if fn is not None else None

    def stats(self) -> dict:
        """Operational snapshot: iteration/shard counts, ZeRO-1 state and
        per-replica updater bytes, plus the strategy's compression stats
        when it has any."""
        out = {
            "iteration": self.iteration,
            "dropped_rows": self.dropped_rows,
            "data_shards": self.n_data_shards,
            "strategy": type(self.strategy).__name__,
            "zero1": self.zero1,
            "bn_group_size": self.bn_group_size,
            "updater_state_bytes": self.updater_state_bytes(),
            "updater_state_bytes_global": self.updater_state_bytes(
                per_replica=False),
        }
        comp = self.compression_stats()
        if comp is not None:
            out["compression"] = comp
        return out

    def threshold_value(self) -> Optional[float]:
        """Current adaptive threshold, for any strategy exposing one via
        ``compression_stats()`` (``None`` otherwise — e.g. top-k
        compression has a fixed density, no threshold)."""
        comp = self.compression_stats() or {}
        t = comp.get("threshold")
        if t is None and isinstance(self.strat_state, dict):
            t = self.strat_state.get("threshold")  # custom strategies
        return None if t is None else float(t)


# ===========================================================================
# Pipeline-parallel training (PP × DP)
# ===========================================================================


class PipelineParallelTrainer:
    """Pipeline-parallel trainer: the layer sequence split over a ``pipe``
    mesh axis, microbatches streamed through the stages under a GPipe or
    1F1B tick schedule, composing with data parallelism (and ZeRO-1
    updater-state sharding) inside each stage across the ``data`` axis.

    Layout: :func:`~deeplearning4j_tpu.parallel.pipeline.partition_stages`
    splits the model into prelude (stage 0) / periodic blocks / head
    (last stage). Block params stack as ``[S, k_max, *shape]`` leaves
    sharded over ``pipe`` — each device holds ONLY its own stage's blocks,
    which is what lets a model bigger than one device's memory train
    (see :meth:`stage_param_bytes`). Prelude/head params are replicated
    (they are small: embeddings/heads) but computed only at their owning
    stage; their gradients come back zero elsewhere and a psum over
    ``pipe`` recovers the totals.

    The checkpoint surface (``params`` / ``opt_state`` / ``state``
    properties) speaks GLOBAL name-keyed trees structurally identical to
    the single-device model's, so orbax/zip checkpoints interchange with
    ``Solver`` and ``DistributedTrainer`` both ways — PP↔non-PP restores
    re-shard exactly like zero1↔replicated already do.

    Scope (clear errors otherwise): sequential models / linear-chain
    graphs with a periodic middle; stateless layers (no BN running stats
    / MoE counters); full-precision compute; no masks/TBPTT; gradient
    normalization NONE or elementwise clip; elementwise updaters on block
    layers (LARS/LAMB trust-ratio norms would span the stacked leaves —
    they remain fine on prelude/head and in DistributedTrainer).
    """

    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 n_micro: int = 8, schedule: str = "1f1b",
                 pipe_axis: str = "pipe", data_axis: str = "data",
                 zero1: bool = False, partition=None,
                 registry=None, stage_time_probe: bool = True) -> None:
        from ..nn.layers.output import BaseOutputLayer
        from ..train.updaters import updater_from_any, Sgd as _Sgd
        from .pipeline import (_model_units, build_pipeline_schedule,
                               partition_stages)

        if mesh is None:
            mesh = make_mesh(pipe=len(jax.devices()))
        if pipe_axis not in mesh.shape:
            raise ValueError(f"mesh has no {pipe_axis!r} axis: {mesh.shape}")
        self.model = model
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        self.data_axis = data_axis
        self.n_stages = int(mesh.shape[pipe_axis])
        self._n_data = int(mesh.shape.get(data_axis, 1))
        self.n_micro = int(n_micro)
        self.schedule = schedule
        self.zero1 = bool(zero1) and self._n_data > 1
        self.iteration = 0
        self.strat_state: dict = {}
        self._multiprocess = False
        self._step_cache: dict = {}
        self._stage_probe_pending = bool(stage_time_probe)

        model._check_init()
        conf = model.conf
        if getattr(conf, "compute_dtype", None):
            raise ValueError(
                "PipelineParallelTrainer does not support compute_dtype "
                "mixed precision yet — drop compute_dtype or use "
                "DistributedTrainer")
        from ..nn.conf import GradientNormalization as _GN
        if conf.gradient_normalization not in (
                _GN.NONE, _GN.CLIP_ELEMENT_WISE_ABSOLUTE_VALUE):
            raise ValueError(
                f"gradient normalization {conf.gradient_normalization} "
                "computes per-layer/param-type norms that would span the "
                "stacked pipeline blocks; use NONE or "
                "CLIP_ELEMENT_WISE_ABSOLUTE_VALUE")
        for name, st in model.state.items():
            if st:
                raise ValueError(
                    f"layer {name!r} carries persistent state "
                    f"({sorted(st)}): stateful layers (batch norm running "
                    "stats, MoE counters) do not pipeline here yet")

        self._units = _model_units(model)
        self._n_units = len(self._units)
        if not isinstance(self._units[-1][1], BaseOutputLayer):
            raise ValueError("the last layer must be an output/loss layer")
        self.partition = (partition if partition is not None
                          else partition_stages(model, self.n_stages))
        if self.partition.n_stages != self.n_stages:
            raise ValueError(
                f"partition is for {self.partition.n_stages} stages, mesh "
                f"{pipe_axis!r} axis has {self.n_stages}")
        self._sched = build_pipeline_schedule(
            self.n_stages, self.n_micro, schedule)

        part = self.partition
        self._k_max = max(part.blocks_per_stage)
        # block b -> (stage, slot); unit i -> location
        self._block_place = [part.locate_block(b)
                             for b in range(part.n_blocks)]
        self._aux_names = [self._units[i][0]
                           for i in (*part.prelude, *part.head)
                           if model.params.get(self._units[i][0])]

        # per-layer optax chains (shared construction with Solver /
        # DistributedTrainer — checkpoint structure compatibility)
        self.optim = LayerOptimizers(model)
        global_upd = (updater_from_any(conf.updater)
                      if conf.updater is not None else _Sgd())
        self._body_tx = []
        for j, i0 in enumerate(part.blocks[0]):
            name0, layer0, _ = self._units[i0]
            if not model.params.get(name0):
                import optax as _optax
                self._body_tx.append(_optax.set_to_zero())
                continue
            upd = (updater_from_any(layer0.updater)
                   if layer0.updater is not None else global_upd)
            # Trust-ratio updaters (Lars/Lamb) keep elementwise=True for
            # ZeRO-1 (their norms re-spell as slice-local + psum), but here
            # the coupling is the problem itself: a per-tensor norm over a
            # stacked [S, k, ...] leaf spans every block instance. Their
            # to_optax_zero1 override is the marker for that coupling.
            from ..train.updaters import IUpdater as _IUpd
            coupled = (not getattr(upd, "elementwise", False)
                       or type(upd).to_optax_zero1
                       is not _IUpd.to_optax_zero1)
            if not layer0.frozen and coupled:
                raise ValueError(
                    f"block layer {name0!r} uses {type(upd).__name__}, "
                    "whose per-tensor (trust-ratio) norms would span the "
                    "stacked [S, k] pipeline leaves; use an elementwise "
                    "updater (Sgd/Adam/...) on block layers")
            self._body_tx.append(self.optim.txs[name0])

        # ---- device layout --------------------------------------------
        self._pipe_sh = NamedSharding(mesh, P(pipe_axis))
        self._repl_sh = NamedSharding(mesh, P())
        S, K = self.n_stages, self._k_max
        self._aux = {
            name: jax.device_put(model.params[name], self._repl_sh)
            for name in self._aux_names}
        self._body = []
        for j, i0 in enumerate(part.blocks[0]):
            stacked = {}
            for pname, p0 in model.params[self._units[i0][0]].items():
                arr = np.zeros((S, K) + tuple(p0.shape),
                               jnp.asarray(p0).dtype)
                for b in range(part.n_blocks):
                    s, kb = self._block_place[b]
                    bname = self._units[part.blocks[b][j]][0]
                    arr[s, kb] = np.asarray(
                        jax.device_get(model.params[bname][pname]))
                stacked[pname] = jax.device_put(arr, self._pipe_sh)
            self._body.append(stacked)

        self._aux_opt = {}
        self._aux_opt_sh = {}
        for name in self._aux_names:
            st = self.optim.txs[name].init(self._aux[name])
            shs = jax.tree_util.tree_map(
                lambda leaf: NamedSharding(mesh, zero1_partition_spec(
                    tuple(np.shape(leaf)), self._n_data, data_axis))
                if self.zero1 and self.optim.elementwise.get(name, False)
                else self._repl_sh, st)
            self._aux_opt[name] = jax.tree_util.tree_map(
                jax.device_put, st, shs)
            self._aux_opt_sh[name] = shs
        self._body_opt = [tx.init(bp)
                          for tx, bp in zip(self._body_tx, self._body)]
        self._validate_body_opt_roundtrip()

        self._has_reg = any(
            getattr(layer, f, None)
            for _, layer, _ in self._units
            for f in ("l1", "l2", "l1_bias", "l2_bias"))
        self._active_counts = np.asarray(part.blocks_per_stage, np.int32)
        self._block_offsets = np.asarray(part.block_offsets(), np.int32)
        self._init_metrics(registry)

    # ------------------------------------------------------------ metrics
    def _init_metrics(self, registry) -> None:
        from ..obs import get_registry

        self.registry = registry if registry is not None else get_registry()
        self.registry.gauge(
            "dl4j_tpu_training_pipeline_bubble_share",
            "Fraction of pipeline stage-ticks idle under the tick "
            "schedule: (S-1)/(M+S-1) for GPipe and 1F1B both",
            labelnames=("schedule",)).labels(self.schedule).set(
                self._sched.bubble_share)
        self.registry.gauge(
            "dl4j_tpu_training_pipeline_resident_microbatches",
            "Peak per-stage stashed boundary activations (microbatches): "
            "min(S, M) under 1F1B vs M under GPipe",
            labelnames=("schedule",)).labels(self.schedule).set(
                self._sched.max_inflight)
        spg = self.registry.gauge(
            "dl4j_tpu_training_pipeline_stage_params",
            "Parameter count owned per pipeline stage (partition balance)",
            labelnames=("stage",))
        for s, c in enumerate(self.partition.stage_costs):
            spg.labels(str(s)).set(float(c))
        self._stage_time_gauge = self.registry.gauge(
            "dl4j_tpu_training_pipeline_stage_step_seconds",
            "Per-stage compiled fold time (one-off probe at first "
            "fit_batch): the schedule's tick length is the max over "
            "stages", labelnames=("stage",))

    # ---------------------------------------------------- layer folding
    def _apply_unit(self, i, params_by_name, h, key):
        from ..nn.layers.base import LayerContext, apply_layer
        name, layer, preproc = self._units[i]
        k = jax.random.fold_in(key, i) if key is not None else None
        ctx = LayerContext(train=True, rng=k, mask=None, dist=None)
        if preproc is not None:
            h, _ = preproc.apply({}, {}, h, ctx)
        y, _ = apply_layer(layer, params_by_name.get(name, {}), {}, h, ctx,
                           name=name)
        return y

    def _fold_prelude(self, aux, xmb, key):
        h = xmb
        for i in self.partition.prelude:
            h = self._apply_unit(i, aux, h, key)
        return h

    def _fold_block(self, body, kb, g, h, key):
        """One pipeline block: position-j params sliced at stacked slot kb.
        ``g`` is the global block index — folded into the rng so dropout
        differs between block instances."""
        from ..nn.layers.base import LayerContext, apply_layer
        for j, i0 in enumerate(self.partition.blocks[0]):
            name, layer, preproc = self._units[i0]
            pj = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, kb, 0, False),
                body[j])
            k = (jax.random.fold_in(
                jax.random.fold_in(key, self._n_units + j), g)
                if key is not None else None)
            ctx = LayerContext(train=True, rng=k, mask=None, dist=None)
            if preproc is not None:
                h, _ = preproc.apply({}, {}, h, ctx)
            h, _ = apply_layer(layer, pj, {}, h, ctx, name=name)
        return h

    def _fold_body(self, body, h, key, n_active, g0):
        """Fold this stage's resident blocks: k_max scan steps, inactive
        (zero-padded) slots skipped under lax.cond."""
        def step(hh, kb):
            out = jax.lax.cond(
                kb < n_active,
                lambda v: self._fold_block(body, kb, g0 + kb, v, key),
                lambda v: v, hh)
            return out, None
        h, _ = jax.lax.scan(step, h, jnp.arange(self._k_max))
        return h

    def _fold_head_loss(self, aux, h, ymb, key):
        from ..nn.layers.base import LayerContext
        part = self.partition
        for i in part.head[:-1]:
            h = self._apply_unit(i, aux, h, key)
        i = part.head[-1]
        name, layer, preproc = self._units[i]
        k = jax.random.fold_in(key, i) if key is not None else None
        ctx = LayerContext(train=True, rng=k, mask=None, dist=None)
        if preproc is not None:
            h, _ = preproc.apply({}, {}, h, ctx)
        return layer.compute_loss(aux.get(name, {}), h, ymb, ctx)

    def _reg_score(self, aux, body):
        from ..nn.sequential import _layer_reg_score
        sd = jnp.float32
        total = jnp.zeros((), sd)
        for i in (*self.partition.prelude, *self.partition.head):
            name, layer, _ = self._units[i]
            if aux.get(name):
                total = total + _layer_reg_score(layer, aux[name], sd)
        for j, i0 in enumerate(self.partition.blocks[0]):
            if body[j]:
                # stacked leaves: elementwise |w| / w^2 sums cover every
                # block at once; zero pads contribute zero
                total = total + _layer_reg_score(
                    self._units[i0][1], body[j], sd)
        return total

    # ---------------------------------------------------------- the step
    def _boundary_struct(self, mb_shape, x_dtype):
        x_s = jax.ShapeDtypeStruct(mb_shape, x_dtype)

        def pre(aux, xm):
            return self._fold_prelude(aux, xm, jax.random.PRNGKey(0))

        boundary = jax.eval_shape(pre, self._aux, x_s)

        def blk(xm):
            body0 = [jax.tree_util.tree_map(lambda a: a[0], bj)
                     for bj in self._body]
            return self._fold_block(body0, jnp.int32(0), jnp.int32(0), xm,
                                    jax.random.PRNGKey(0))

        out = jax.eval_shape(blk, boundary)
        if (out.shape, out.dtype) != (boundary.shape, boundary.dtype):
            raise ValueError(
                f"pipeline block does not preserve the boundary activation "
                f"({boundary.shape}/{boundary.dtype} -> {out.shape}/"
                f"{out.dtype}): stages cannot ring-pass activations of "
                "differing shapes")
        return boundary

    def _build_step(self, x_shape, x_dtype, y_shape, y_dtype):
        import optax
        from ..nn.conf import GradientNormalization as _GN
        from .pipeline import run_pipeline_schedule

        mesh, S, D = self.mesh, self.n_stages, self._n_data
        pipe, data = self.pipe_axis, self.data_axis
        part, sched = self.partition, self._sched
        conf = self.model.conf
        mb_local = x_shape[1] // D
        boundary = self._boundary_struct((mb_local,) + tuple(x_shape[2:]),
                                         x_dtype)
        n_act = jnp.asarray(self._active_counts)
        offs = jnp.asarray(self._block_offsets)

        def worker(aux, body, xs, ys, kd):
            idx = jax.lax.axis_index(pipe)
            body_local = [jax.tree_util.tree_map(lambda a: a[0], bj)
                          for bj in body]
            key = jax.random.wrap_key_data(kd)
            if D > 1:
                key = jax.random.fold_in(key, jax.lax.axis_index(data))

            def fwd(p, m, xi):
                p_aux, p_body = p
                mkey = jax.random.fold_in(key, m)
                x0 = jax.lax.cond(
                    idx == 0,
                    lambda: self._fold_prelude(p_aux, xs[m], mkey).astype(
                        boundary.dtype),
                    lambda: xi)
                return self._fold_body(p_body, x0, mkey, n_act[idx],
                                       offs[idx])

            def lfn(p, h, m):
                p_aux, _ = p
                mkey = jax.random.fold_in(key, m)
                return self._fold_head_loss(p_aux, h, ys[m], mkey)

            loss, (g_aux, g_body) = run_pipeline_schedule(
                fwd, lfn, (aux, body_local), sched, pipe, boundary)
            inv = 1.0 / self.n_micro
            loss = jax.lax.psum(
                jnp.where(idx == S - 1, loss, 0.0), pipe) * inv
            # prelude/head grads live on one stage, zero elsewhere
            g_aux = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, pipe) * inv, g_aux)
            g_body = jax.tree_util.tree_map(
                lambda a: (a * inv)[None], g_body)
            if D > 1:
                loss = jax.lax.pmean(loss, data)
                g_aux = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, data), g_aux)
                g_body = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, data), g_body)
            return loss, g_aux, g_body

        x_spec = P(None, data) if D > 1 else P()
        mapped = _shmap(
            worker, mesh,
            in_specs=(P(), P(pipe), x_spec, x_spec, P()),
            out_specs=(P(), P(), P(pipe)))

        clip = (conf.gradient_normalization
                is _GN.CLIP_ELEMENT_WISE_ABSOLUTE_VALUE)
        thr = float(conf.gradient_normalization_threshold)

        def step(aux, body, aux_opt, body_opt, xs, ys, kd):
            loss, g_aux, g_body = mapped(aux, body, xs, ys, kd)
            if self._has_reg:
                reg, (r_aux, r_body) = jax.value_and_grad(
                    self._reg_score, argnums=(0, 1))(aux, body)
                g_aux = jax.tree_util.tree_map(
                    lambda a, b: a + b, g_aux, r_aux)
                g_body = jax.tree_util.tree_map(
                    lambda a, b: a + b, g_body, r_body)
                loss = loss + reg
            if clip:
                g_aux, g_body = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, -thr, thr), (g_aux, g_body))
            new_aux, new_aux_opt = {}, {}
            for name in self._aux_names:
                upd, st = self.optim.txs[name].update(
                    g_aux[name], aux_opt[name], aux[name])
                new_aux[name] = optax.apply_updates(aux[name], upd)
                new_aux_opt[name] = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint,
                    st, self._aux_opt_sh[name])
            new_body, new_body_opt = [], []
            for j, tx in enumerate(self._body_tx):
                upd, st = tx.update(g_body[j], body_opt[j], body[j])
                nb = optax.apply_updates(body[j], upd)
                new_body.append(jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, self._pipe_sh), nb))
                new_body_opt.append(jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, self._pipe_sh)
                    if self._is_stacked_leaf(a) else a, st))
            return new_aux, new_body, new_aux_opt, new_body_opt, loss

        return jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def _get_step(self, xs, ys):
        k = (tuple(xs.shape), str(xs.dtype), tuple(ys.shape), str(ys.dtype))
        if k not in self._step_cache:
            self._step_cache[k] = self._build_step(
                xs.shape, xs.dtype, ys.shape, ys.dtype)
        return self._step_cache[k]

    # ------------------------------------------------------------- train
    def fit_batch(self, x, y):
        """One optimizer step on a GLOBAL batch: split into ``n_micro``
        microbatches along dim 0 (each further sharded over the data
        axis), streamed through the stages under the tick schedule.
        Returns the scalar score (loss + regularization) — equal to the
        single-device Solver's at the same global batch."""
        model = self.model
        conf = model.conf
        keep_int = (model.keeps_int_input(conf.network_inputs[0])
                    if hasattr(conf, "network_inputs")
                    else model.keeps_int_input())
        x = as_input(x, model.dtype, keep_int)
        y = jnp.asarray(y)
        B = x.shape[0]
        M, D = self.n_micro, self._n_data
        if B % M or (B // M) % D:
            raise ValueError(
                f"global batch {B} must split into n_micro={M} microbatches "
                f"of {D}-divisible size (data axis); got "
                f"{B}/{M} = {B / M:g}")
        xs = x.reshape((M, B // M) + x.shape[1:])
        ys = y.reshape((M, B // M) + y.shape[1:])
        sh = (NamedSharding(self.mesh, P(None, self.data_axis))
              if D > 1 else self._repl_sh)
        xs = jax.device_put(xs, sh)
        ys = jax.device_put(ys, sh)
        if self._stage_probe_pending:
            self._stage_probe_pending = False
            self._probe_stage_times(xs, ys)
        fn = self._get_step(xs, ys)
        kd = jax.random.key_data(model._rng.next_key())
        out = fn(self._aux, self._body, self._aux_opt, self._body_opt,
                 xs, ys, kd)
        self._aux, self._body, self._aux_opt, self._body_opt, loss = out
        self.iteration += 1
        return loss

    def fit(self, x, y, *, batch_size: int, epochs: int = 1):
        """Minimal epoch loop over host arrays (shuffling/iterators stay
        the caller's job — see ``train.checkpoint`` for resumable input
        pipelines). Returns the last score."""
        n = int(np.shape(x)[0])
        loss = None
        for _ in range(int(epochs)):
            for lo in range(0, n - batch_size + 1, batch_size):
                loss = self.fit_batch(x[lo:lo + batch_size],
                                      y[lo:lo + batch_size])
        return loss

    def _probe_stage_times(self, xs, ys):
        """One-off per-stage compiled fold timing; feeds the
        ``dl4j_tpu_training_pipeline_stage_step_seconds`` gauge. The
        pipeline's tick length is max over stages — the balance view."""
        import time as _time
        part = self.partition
        host_params = jax.device_get(self.params)
        key = jax.random.PRNGKey(0)
        h = jax.device_get(xs)[0]
        y0 = jax.device_get(ys)[0]
        last = self._n_units - 1
        for s in range(self.n_stages):
            ids = part.stage_units[s]

            def fold(p, hh, ids=ids):
                out = hh
                for i in ids:
                    if i == last:
                        return self._fold_head_loss(p, out,
                                                    jnp.asarray(y0), key)
                    out = self._apply_unit(i, p, out, key)
                return out

            f = jax.jit(fold)
            out = jax.block_until_ready(f(host_params, h))
            t0 = _time.perf_counter()
            out = jax.block_until_ready(f(host_params, h))
            self._stage_time_gauge.labels(str(s)).set(
                _time.perf_counter() - t0)
            if s < self.n_stages - 1:
                h = out

    # ------------------------------------------- checkpoint-facing views
    def _is_stacked_leaf(self, a) -> bool:
        shape = tuple(np.shape(a))
        return (len(shape) >= 2
                and shape[:2] == (self.n_stages, self._k_max))

    def _unit_location(self, i):
        part = self.partition
        a = part.prelude[-1] + 1 if part.prelude else 0
        span = part.n_blocks * part.period
        if a <= i < a + span:
            b, j = divmod(i - a, part.period)
            s, kb = self._block_place[b]
            return ("body", j, s, kb)
        return ("aux",)

    @property
    def n_data_shards(self) -> int:
        return self._n_data

    @property
    def params(self):
        """GLOBAL name-keyed params, structurally identical to
        ``model.params`` — the orbax/zip checkpoint view."""
        out = {}
        for i, (name, _, _) in enumerate(self._units):
            loc = self._unit_location(i)
            if loc[0] == "aux":
                out[name] = dict(self._aux.get(name, {}))
            else:
                _, j, s, kb = loc
                out[name] = {pn: a[s, kb]
                             for pn, a in self._body[j].items()}
        return out

    @params.setter
    def params(self, tree):
        S, K = self.n_stages, self._k_max
        part = self.partition
        self._aux = {
            name: jax.device_put(
                jax.tree_util.tree_map(jnp.asarray, tree[name]),
                self._repl_sh)
            for name in self._aux_names}
        body = []
        for j, i0 in enumerate(part.blocks[0]):
            stacked = {}
            for pn, p0 in tree[self._units[i0][0]].items():
                arr = np.zeros((S, K) + tuple(np.shape(p0)),
                               jnp.asarray(p0).dtype)
                for b in range(part.n_blocks):
                    s, kb = self._block_place[b]
                    arr[s, kb] = np.asarray(jax.device_get(
                        tree[self._units[part.blocks[b][j]][0]][pn]))
                stacked[pn] = jax.device_put(arr, self._pipe_sh)
            body.append(stacked)
        self._body = body

    @property
    def opt_state(self):
        """GLOBAL per-layer updater state, matching ``LayerOptimizers``'s
        ``{layer: tx_state}`` structure (the zip/orbax wire format)."""
        out = {}
        for name, _ in self.model.named_param_layers():
            i = next(k for k, (n, _, _) in enumerate(self._units)
                     if n == name)
            loc = self._unit_location(i)
            if loc[0] == "aux":
                out[name] = self._aux_opt[name]
            else:
                _, j, s, kb = loc
                out[name] = jax.tree_util.tree_map(
                    lambda a: a[s, kb] if self._is_stacked_leaf(a) else a,
                    self._body_opt[j])
        return out

    @opt_state.setter
    def opt_state(self, tree):
        part = self.partition
        for name in self._aux_names:
            self._aux_opt[name] = jax.tree_util.tree_map(
                lambda leaf, sh: jax.device_put(jnp.asarray(leaf), sh),
                tree[name], self._aux_opt_sh[name])
        new_body_opt = []
        for j, i0 in enumerate(part.blocks[0]):
            tmpl = self._body_opt[j]
            per_block = [tree[self._units[part.blocks[b][j]][0]]
                         for b in range(part.n_blocks)]
            if not self.model.params.get(self._units[i0][0]):
                new_body_opt.append(tmpl)
                continue

            def imp(tl, *leaves):
                if self._is_stacked_leaf(tl):
                    arr = np.zeros(tuple(np.shape(tl)),
                                   jnp.asarray(tl).dtype)
                    for b, v in enumerate(leaves):
                        s, kb = self._block_place[b]
                        arr[s, kb] = np.asarray(jax.device_get(v))
                    return jax.device_put(arr, self._pipe_sh)
                return jax.device_put(jnp.asarray(leaves[0]),
                                      self._repl_sh)

            new_body_opt.append(jax.tree_util.tree_map(
                imp, tmpl, *per_block))
        self._body_opt = new_body_opt

    @property
    def state(self):
        """Per-layer persistent state: validated empty at construction
        (stateless layers only), so this is the model's empty-dict tree."""
        return {name: {} for name in self.model.state}

    @state.setter
    def state(self, tree):
        pass  # stateless by construction

    def _validate_body_opt_roundtrip(self) -> None:
        """The stacked body opt state must slice back into the exact
        per-layer structure ``LayerOptimizers.init`` produces — the
        checkpoint-interchange contract with Solver/DistributedTrainer."""
        for j, i0 in enumerate(self.partition.blocks[0]):
            name0 = self._units[i0][0]
            if not self.model.params.get(name0):
                continue
            ref = jax.eval_shape(self._body_tx[j].init,
                                 self.model.params[name0])
            got = jax.tree_util.tree_map(
                lambda a: a[0, 0] if self._is_stacked_leaf(a) else a,
                self._body_opt[j])
            rl, rt = jax.tree_util.tree_flatten(ref)
            gl, gt = jax.tree_util.tree_flatten(got)
            if rt != gt or [tuple(np.shape(v)) for v in gl] != [
                    tuple(r.shape) for r in rl]:
                raise ValueError(
                    f"updater state for block layer {name0!r} does not "
                    "round-trip through the stacked pipeline layout; use "
                    "an elementwise updater on block layers")

    # ------------------------------------------------- trainer interop
    def sync_to_model(self) -> None:
        """Write the trainer's params back into the host model (the
        checkpoint/save path — global shapes, so a non-PP restore works)."""
        self.model.params = jax.device_get(self.params)

    def load_updater_state(self, host_opt) -> None:
        """Install a host updater-state tree saved by ANY trainer (global
        per-layer shapes — Solver, DistributedTrainer zero1 or not, or a
        differently-staged PipelineParallelTrainer)."""
        live = jax.tree_util.tree_leaves(self.opt_state)
        new = jax.tree_util.tree_leaves(host_opt)
        if len(live) != len(new):
            raise ValueError(
                f"updater state leaf count mismatch: checkpoint has "
                f"{len(new)}, trainer expects {len(live)}")
        for a, b in zip(live, new):
            if tuple(np.shape(a)) != tuple(np.shape(b)):
                raise ValueError(
                    f"updater state leaf shape mismatch: {np.shape(b)} vs "
                    f"expected {np.shape(a)} — was this saved with "
                    "different GLOBAL shapes?")
        self.opt_state = host_opt

    def stage_param_bytes(self, *, per_device: bool = True) -> int:
        """Trainable-param bytes resident per device (stacked block slices
        + replicated prelude/head) — the over-one-chip proof reads this."""
        total = 0
        for leaf in jax.tree_util.tree_leaves((self._aux, self._body)):
            if per_device and isinstance(leaf, jax.Array):
                total += int(np.prod(
                    leaf.sharding.shard_shape(leaf.shape))) * leaf.dtype.itemsize
            else:
                total += leaf.size * leaf.dtype.itemsize
        return int(total)

    def stats(self) -> dict:
        return {
            "iteration": self.iteration,
            "schedule": self.schedule,
            "n_stages": self.n_stages,
            "n_micro": self.n_micro,
            "data_shards": self._n_data,
            "zero1": self.zero1,
            "bubble_share": self._sched.bubble_share,
            "resident_microbatches": self._sched.max_inflight,
            "stage_costs": list(self.partition.stage_costs),
            "stage_param_bytes": self.stage_param_bytes(),
            "stage_param_bytes_global": self.stage_param_bytes(
                per_device=False),
        }
