"""GraphSolver — training machinery for ComputationGraph.

Reference: ComputationGraph.fit() shares the Solver/StochasticGradientDescent
machinery with MultiLayerNetwork (SURVEY.md §3.2 "same skeleton"). Here the
GraphSolver reuses LayerOptimizers + gradient normalization from solver.py;
the jitted step takes tuples of inputs/labels (MultiDataSet).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import DataSet, MultiDataSet
from ..obs.compiles import watch_compiles
from ..obs.tracing import get_tracer
from .solver import LayerOptimizers, _normalize_gradients


class GraphSolver:
    def __init__(self, model, *, optimize=None, profiler=None,
                 donate_inputs: bool = False) -> None:
        """``optimize=`` applies training-safe graph rewrite passes at
        step-build time (see Solver.__init__ / nn/rewrite). ``profiler=``
        attaches a :class:`~deeplearning4j_tpu.obs.step_profiler.
        StepProfiler` for per-phase step attribution (see Solver).
        ``donate_inputs=True`` donates the batch buffers (xs/ys) so XLA
        reuses input HBM across steps — see Solver.__init__ for the
        freshness contract."""
        self.model = model
        self.donate_inputs = bool(donate_inputs)
        if hasattr(model, "migrate_state"):
            model.migrate_state()
        self.applied_rewrites = []
        if optimize:
            from ..nn.rewrite import rewrite_model_inplace

            self.applied_rewrites = rewrite_model_inplace(
                model, optimize, context="training")
        self.profiler = profiler
        self.optim = LayerOptimizers(model)
        self.opt_state = self.optim.init(model.params)
        self._step_cache: Dict[Any, Any] = {}
        self._n_steps = 0  # fit.step's sequence number
        watch_compiles()

    def _step_fn(self, n_in: int, n_out: int, return_grads: bool = False):
        key = ("step", n_in, n_out, return_grads)
        if key not in self._step_cache:
            model = self.model
            conf = model.conf

            def train_step(params, opt_state, state, xs, ys, rng):
                def loss_fn(p):
                    return model.loss_pure(p, state, xs, ys, rng=rng, train=True)

                with jax.named_scope("loss_and_grad"):
                    (score, new_state), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params)
                grads = _normalize_gradients(
                    grads, conf.gradient_normalization, conf.gradient_normalization_threshold
                )
                new_params, new_opt = self.optim.update(grads, opt_state, params)
                if return_grads:  # array-hungry listeners (StatsListener)
                    return new_params, new_opt, new_state, score, grads
                return new_params, new_opt, new_state, score

            donate = (0, 1, 2) + ((3, 4) if self.donate_inputs else ())
            self._step_cache[key] = jax.jit(train_step,
                                            donate_argnums=donate)
        return self._step_cache[key]

    def _scan_fn(self):
        key = ("scan",)
        if key not in self._step_cache:
            model = self.model
            conf = model.conf

            def one_step(carry, batch):
                params, opt_state, state, rng = carry
                xs, ys = batch
                rng, step_key = jax.random.split(rng)

                def loss_fn(p):
                    return model.loss_pure(p, state, xs, ys, rng=step_key, train=True)

                (score, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
                grads = _normalize_gradients(
                    grads, conf.gradient_normalization, conf.gradient_normalization_threshold
                )
                new_params, new_opt = self.optim.update(grads, opt_state, params)
                return (new_params, new_opt, new_state, rng), score

            def epoch(params, opt_state, state, xs, ys, rng):
                (params, opt_state, state, _), scores = jax.lax.scan(
                    one_step, (params, opt_state, state, rng), (xs, ys)
                )
                return params, opt_state, state, scores[-1]

            self._step_cache[key] = jax.jit(epoch, donate_argnums=(0, 1, 2))
        return self._step_cache[key]

    def fit_batch(self, xs: Tuple, ys: Tuple):
        # one ``fit.step`` trace of the process's tracer per call: what the
        # host spends to enqueue a step (head-sampled at the tracer's rate;
        # every step while a profiler session collects)
        span = get_tracer().span
        self._n_steps += 1
        with span("fit.step", parent=None,
                  attrs={"step": self._n_steps}) as step:
            return self._fit_batch(xs, ys, span, step)

    def _fit_batch(self, xs: Tuple, ys: Tuple, span, step):
        model = self.model
        # StepProfiler phase attribution; mirrors Solver.fit_batch (device
        # phases fenced only on sampled steps). prof=None costs nothing.
        prof = self.profiler
        fence = prof.begin_step() if prof is not None else False
        t0 = time.perf_counter() if prof is not None else 0.0
        with span("fit.h2d", parent=step):
            xs = model._as_inputs(xs)
            ys = tuple(jnp.asarray(y) for y in ys)
        step.set_attribute("batch", int(xs[0].shape[0]))
        if prof is not None and (fence or prof.sync_every == 0):
            if fence:
                jax.block_until_ready((xs, ys))
            prof.record("h2d", time.perf_counter() - t0, sampled=fence)
        want_grads = model.listeners.requires_arrays
        fn = self._step_fn(len(xs), len(ys), want_grads)
        rng = model._rng.next_key()
        tc = time.perf_counter() if prof is not None else 0.0
        with span("fit.dispatch", parent=step):
            out = fn(
                model.params, self.opt_state, model.state, xs, ys, rng
            )
        if prof is not None and (fence or prof.sync_every == 0):
            if fence:
                jax.block_until_ready(out)
            prof.record("compute", time.perf_counter() - tc, sampled=fence)
        th = time.perf_counter() if prof is not None else 0.0
        with span("fit.host", parent=step):
            grads = None
            if want_grads:
                params, opt_state, state, score, grads = out
            else:
                params, opt_state, state, score = out
            model.params = params
            model.state = state
            self.opt_state = opt_state
            model.last_batch_size = int(xs[0].shape[0])
            if grads is not None:
                # after reassignment: pre-step buffers were donated to the
                # step
                model.listeners.gradient_calculation(model, grads)
        if prof is not None:
            # sampled: post-fence host time is honest (see Solver)
            prof.record("host", time.perf_counter() - th, sampled=fence)
            prof.end_step()
        return score

    def fit_iterator(self, iterator, *, epochs: int = 1) -> float:
        """DataSet/MultiDataSet iterator training with exact mid-epoch
        resume semantics — see :meth:`Solver.fit_iterator` (solver.py):
        consumption starts at the iterator's CURRENT position, reset()
        only when exhausted."""
        from ..data.dataset import MultiDataSet

        model = self.model
        sync = bool(model.listeners.listeners)
        last = None
        for _ in range(epochs):
            if not iterator.has_next():
                iterator.reset()
            model.listeners.epoch_start(model)
            while iterator.has_next():
                ds = iterator.next()
                if isinstance(ds, MultiDataSet):
                    xs, ys = tuple(ds.features), tuple(ds.labels)
                else:
                    xs, ys = (ds.features,), (ds.labels,)
                score = self.fit_batch(xs, ys)
                last = score
                model.iteration_count += 1
                if sync:
                    model.score_value = float(score)
                    model.listeners.iteration_done(
                        model, model.iteration_count, model.epoch_count,
                        model.score_value)
            model.listeners.epoch_end(model)
            model.epoch_count += 1
        if last is not None:
            model.score_value = float(last)
        return model.score_value

    def fit(self, data, labels=None, *, epochs: int = 1) -> None:
        model = self.model
        sync_every_iter = bool(model.listeners.listeners)
        batches = list(self._as_multi_batches(data, labels))
        # scan fast path: uniform shapes, no listeners
        shapes = {
            tuple(np.shape(a) for a in xs) + tuple(np.shape(a) for a in ys)
            for xs, ys in batches
        }
        if (not sync_every_iter and self.profiler is None
                and batches and len(shapes) == 1):
            xs_stack = tuple(
                np.stack([np.asarray(b[0][i]) for b in batches])
                for i in range(len(batches[0][0]))
            )
            ys_stack = tuple(
                np.stack([np.asarray(b[1][i]) for b in batches])
                for i in range(len(batches[0][1]))
            )
            fn = self._scan_fn()
            last = None
            for _ in range(epochs):
                model.listeners.epoch_start(model)
                rng = model._rng.next_key()
                params, opt_state, state, score = fn(
                    model.params, self.opt_state, model.state,
                    model._as_inputs(xs_stack),
                    tuple(jnp.asarray(y) for y in ys_stack), rng,
                )
                model.params = params
                model.state = state
                self.opt_state = opt_state
                model.iteration_count += len(batches)
                model.last_batch_size = int(xs_stack[0].shape[1])
                last = score
                model.listeners.epoch_end(model)
                model.epoch_count += 1
            if last is not None:
                model.score_value = float(last)
            return

        last_score = None
        for _ in range(epochs):
            model.listeners.epoch_start(model)
            for xs, ys in batches:
                score = self.fit_batch(xs, ys)
                last_score = score
                model.iteration_count += 1
                if sync_every_iter:
                    model.score_value = float(score)
                    model.listeners.iteration_done(
                        model, model.iteration_count, model.epoch_count, model.score_value
                    )
            model.listeners.epoch_end(model)
            model.epoch_count += 1
        if last_score is not None:
            model.score_value = float(last_score)

    def _as_multi_batches(self, data, labels):
        as_tuple = self.model._as_tuple
        if labels is not None:
            yield as_tuple(data), as_tuple(labels)
            return
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        for item in data:
            if isinstance(item, MultiDataSet):
                yield tuple(item.features), tuple(item.labels)
            elif isinstance(item, DataSet):
                yield (item.features,), (item.labels,)
            else:
                yield as_tuple(item[0]), as_tuple(item[1])
