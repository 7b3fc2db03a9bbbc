"""Solver — the training-step machinery.

Reference: org.deeplearning4j.optimize.{Solver, solvers.StochasticGradientDescent},
MultiLayerUpdater/UpdaterBlock, gradient normalization (SURVEY.md §3.1).

TPU design: one jitted, donated train step per (mask-signature) — forward +
loss + backward + gradient normalization + per-layer updater + param update
compile into a single XLA program. The reference's per-op JNI dispatch, its
flat-buffer updater views, and its workspace management all collapse into this
one compiled function. Params and optimizer state are donated so XLA updates
buffers in place (steady-state allocation: zero — the workspace property).

Per-layer updater overrides (reference: UpdaterBlock boundaries) are honored:
each layer gets its own optax transformation chain; frozen layers get
``set_to_zero``. Decoupled weight decay applies to weight params only,
mirroring the reference's weightDecay semantics.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core.dtypes import as_input
from ..nn.conf import BackpropType, GradientNormalization
from ..nn.layers.base import Layer
from ..obs.compiles import watch_compiles
from ..obs.tracing import get_tracer
from .updaters import IUpdater, NoOp, Sgd, updater_from_any


@jax.named_scope("grad_norm")
def _normalize_gradients(
    grads: Dict[str, Dict[str, jax.Array]],
    mode: GradientNormalization,
    threshold: float,
) -> Dict[str, Dict[str, jax.Array]]:
    """Reference: GradientNormalization applied before the updater. Its
    operations carry the scope ``grad_norm`` in every train step."""
    eps = 1e-8
    if mode is GradientNormalization.NONE:
        return grads
    if mode is GradientNormalization.RENORMALIZE_L2_PER_LAYER:
        out = {}
        for lname, lg in grads.items():
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in lg.values()) + eps)
            out[lname] = {k: g / norm for k, g in lg.items()}
        return out
    if mode is GradientNormalization.RENORMALIZE_L2_PER_PARAM_TYPE:
        return jax.tree_util.tree_map(
            lambda g: g / (jnp.linalg.norm(g.ravel()) + eps), grads
        )
    if mode is GradientNormalization.CLIP_ELEMENT_WISE_ABSOLUTE_VALUE:
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, -threshold, threshold), grads
        )
    if mode is GradientNormalization.CLIP_L2_PER_LAYER:
        out = {}
        for lname, lg in grads.items():
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in lg.values()) + eps)
            scale = jnp.minimum(1.0, threshold / norm)
            out[lname] = {k: g * scale for k, g in lg.items()}
        return out
    if mode is GradientNormalization.CLIP_L2_PER_PARAM_TYPE:
        def clip(g):
            norm = jnp.linalg.norm(g.ravel()) + eps
            return g * jnp.minimum(1.0, threshold / norm)

        return jax.tree_util.tree_map(clip, grads)
    raise ValueError(f"Unhandled normalization {mode}")


class LayerOptimizers:
    """Per-layer optax chains (reference: UpdaterBlock boundaries).

    ``zero1_axis``/``zero1_sliced`` select the explicit-path ZeRO-1
    spelling of each layer's transformation
    (:meth:`~deeplearning4j_tpu.train.updaters.IUpdater.to_optax_zero1`):
    updaters whose math includes cross-element reductions (LARS/LAMB
    trust-ratio norms) re-spell them as slice-local + psum over the data
    axis, so applying the chain to 1/N parameter slices stays exactly the
    replicated update. State trees are identical either way."""

    def __init__(self, model, *, zero1_axis: Optional[str] = None,
                 zero1_sliced: Optional[Dict[str, Dict[str, bool]]] = None) -> None:
        conf = model.conf
        self.txs: Dict[str, optax.GradientTransformation] = {}
        # per-layer: is the whole update chain elementwise per tensor
        # element? (The ZeRO-1 slicing contract — see IUpdater.elementwise.
        # The weight-decay prologue is elementwise, so the chain inherits
        # the updater's flag.)
        self.elementwise: Dict[str, bool] = {}
        global_updater = updater_from_any(conf.updater) if conf.updater is not None else Sgd()
        for name, layer in model.named_param_layers():
            if layer.frozen:
                self.txs[name] = optax.set_to_zero()
                self.elementwise[name] = True
                continue
            updater = updater_from_any(layer.updater) if layer.updater is not None else global_updater
            self.elementwise[name] = bool(getattr(updater, "elementwise", False))
            sliced = (zero1_sliced or {}).get(name)
            parts = []
            wd = layer.weight_decay
            if wd:
                weight_names = set(layer.weight_param_names())
                parts.append(
                    optax.masked(
                        optax.add_decayed_weights(wd),
                        {k: (k in weight_names) for k in layer.trainable_param_names()},
                    )
                )
            if zero1_axis is not None and sliced and any(sliced.values()):
                parts.append(updater.to_optax_zero1(zero1_axis, sliced))
            else:
                parts.append(updater.to_optax())
            self.txs[name] = optax.chain(*parts) if len(parts) > 1 else parts[0]

    def init(self, params) -> Dict[str, Any]:
        return {name: tx.init(params[name]) for name, tx in self.txs.items()}

    @jax.named_scope("optimizer")
    def update(self, grads, opt_state, params):
        new_params = {}
        new_opt = {}
        for name, p in params.items():
            if name in self.txs:
                with jax.named_scope(name):
                    updates, new_opt[name] = self.txs[name].update(
                        grads[name], opt_state[name], p)
                    new_params[name] = optax.apply_updates(p, updates)
            else:
                new_params[name] = p
        return new_params, new_opt


class Solver:
    def __init__(self, model, *, optimize=None, profiler=None,
                 donate_inputs: bool = False) -> None:
        """``optimize=`` applies training-safe graph rewrite passes at
        step-build time (``True``/``"training"`` -> the default set:
        space-to-depth stem + BN affine precompute; or an explicit pass
        list — inference-only passes are rejected). The model is rewritten
        in place to a numerically equivalent form; rewrites are in-memory
        only and never serialized (nn/rewrite).

        ``profiler=`` attaches a
        :class:`~deeplearning4j_tpu.obs.step_profiler.StepProfiler`: each
        ``fit_batch`` attributes its time to h2d / compute / host phases
        (device phases fenced on the profiler's sampling schedule), and
        ``fit`` skips the whole-epoch ``lax.scan`` fast path because one
        fused dispatch has no per-step structure to attribute.

        ``donate_inputs=True`` additionally donates the BATCH buffers
        (x/y) to the jitted step, so XLA reuses the input HBM across
        steps instead of allocating a fresh batch-sized block every step
        — the steady-state input footprint becomes the prefetch ring
        alone. Only safe when every step gets a FRESH batch array (the
        from-files pipeline: each prefetch ``device_put`` makes a new
        buffer); callers that re-feed the same device array every step
        (synthetic micro-benches) must leave it off. Numpy inputs are
        always safe — jit copies them to device first and donates its own
        copy."""
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

    def _make_step(self, has_mask: bool, has_label_mask: bool, stateful: bool,
                   return_grads: bool = False):
        model = self.model
        conf = model.conf

        def train_step(params, opt_state, state, rnn_state, x, y, rng, mask,
                       label_mask):
            def loss_fn(p):
                return model.loss_pure(
                    p, state, x, y, rng=rng, mask=mask, label_mask=label_mask,
                    rnn_state=rnn_state if stateful else None, train=True,
                )

            with jax.named_scope("loss_and_grad"):
                (score, (new_state, new_rnn)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            grads = _normalize_gradients(
                grads, conf.gradient_normalization, conf.gradient_normalization_threshold
            )
            new_params, new_opt = self.optim.update(grads, opt_state, params)
            if return_grads:  # array-hungry listeners (StatsListener)
                return new_params, new_opt, new_state, new_rnn, score, grads
            return new_params, new_opt, new_state, new_rnn, score

        donate = (0, 1, 2)
        if self.donate_inputs:
            donate += (4, 5)  # x, y (masks excluded: commonly reused)
        return jax.jit(train_step, donate_argnums=donate)

    def _step_fn(self, has_mask, has_label_mask, stateful, return_grads=False):
        key = (has_mask, has_label_mask, stateful, return_grads)
        if key not in self._step_cache:
            self._step_cache[key] = self._make_step(*key)
        return self._step_cache[key]

    def fit_batch(self, x, y, mask=None, label_mask=None, rnn_state=None) -> Tuple[float, Optional[dict]]:
        # one ``fit.step`` trace of the process's tracer per call: what the
        # host spends to enqueue a step (head-sampled at the tracer's rate;
        # every step while a profiler session collects)
        span = get_tracer().span
        self._n_steps += 1
        with span("fit.step", parent=None,
                  attrs={"step": self._n_steps}) as step:
            return self._fit_batch(x, y, mask, label_mask, rnn_state, span,
                                   step)

    def _fit_batch(self, x, y, mask, label_mask, rnn_state, span, step):
        model = self.model
        # phase attribution (StepProfiler): h2d / compute measured under a
        # block_until_ready fence ONLY on the profiler's sampled steps so
        # steady-state async dispatch stays unperturbed; host time every
        # step. prof=None is the zero-overhead path.
        prof = self.profiler
        fence = prof.begin_step() if prof is not None else False
        t0 = time.perf_counter() if prof is not None else 0.0
        with span("fit.h2d", parent=step):
            x = as_input(x, model.dtype, model.keeps_int_input())
            y = jnp.asarray(y)
            mask_a = None if mask is None else jnp.asarray(mask, model.dtype)
            lmask_a = None if label_mask is None else jnp.asarray(
                label_mask, model.dtype)
        step.set_attribute("batch", int(x.shape[0]))
        if prof is not None and (fence or prof.sync_every == 0):
            if fence:
                jax.block_until_ready((x, y))
            prof.record("h2d", time.perf_counter() - t0, sampled=fence)
        stateful = rnn_state is not None
        want_grads = model.listeners.requires_arrays
        fn = self._step_fn(mask_a is not None, lmask_a is not None, stateful,
                           want_grads)
        rng = model._rng.next_key()
        tc = time.perf_counter() if prof is not None else 0.0
        with span("fit.dispatch", parent=step):
            out = fn(
                model.params, self.opt_state, model.state,
                rnn_state if stateful else {}, x, y, rng, mask_a, lmask_a,
            )
        if prof is not None and (fence or prof.sync_every == 0):
            if fence:
                jax.block_until_ready(out)
            prof.record("compute", time.perf_counter() - tc, sampled=fence)
        th = time.perf_counter() if prof is not None else 0.0
        with span("fit.host", parent=step):
            grads = None
            if want_grads:
                params, opt_state, state, new_rnn, score, grads = out
            else:
                params, opt_state, state, new_rnn, score = out
            model.params = params
            model.state = state
            self.opt_state = opt_state
            model.last_batch_size = int(x.shape[0])
            if grads is not None:
                # after reassignment: the pre-step buffers were donated to
                # the jitted step, so listeners must see the NEW params
                model.listeners.gradient_calculation(model, grads)
        if prof is not None:
            # sampled: after the fence the device is idle, so this host
            # segment's wall time is honest (unfenced steps share the
            # core with the in-flight device computation)
            prof.record("host", time.perf_counter() - th, sampled=fence)
            prof.end_step()
        return score, new_rnn

    def fit_scan(self, features, labels, *, steps_per_call: Optional[int] = None) -> float:
        """Compiled multi-step training: ``lax.scan`` over a stack of batches
        so an entire epoch is ONE device dispatch.

        ``features``/``labels`` are [n_batches, batch, ...] stacks. This is the
        TPU-native answer to dispatch latency (SURVEY.md §7): where the
        reference amortizes JNI overhead with workspaces, we amortize dispatch
        with a compiled training loop. Semantics identical to calling
        fit_batch n_batches times with no listeners attached; returns the
        final score.
        """
        model = self.model
        x = as_input(features, model.dtype, model.keeps_int_input())
        y = jnp.asarray(labels)
        key = ("scan",)
        if key not in self._step_cache:
            conf = model.conf

            def one_step(carry, batch):
                params, opt_state, state, rng = carry
                xb, yb = batch
                rng, step_key = jax.random.split(rng)

                def loss_fn(p):
                    return model.loss_pure(p, state, xb, yb, rng=step_key, train=True)

                (score, (new_state, _)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
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
        fn = self._step_cache[key]
        rng = self.model._rng.next_key()
        params, opt_state, state, score = fn(
            model.params, self.opt_state, model.state, x, y, rng
        )
        model.params = params
        model.state = state
        self.opt_state = opt_state
        model.iteration_count += int(x.shape[0])
        model.last_batch_size = int(x.shape[1])
        return score

    def fit_iterator(self, iterator, *, epochs: int = 1) -> float:
        """Train from a ``DataSetIterator`` WITHOUT resetting away its
        current position: consumption starts wherever the iterator
        stands, so an iterator repositioned by ``load_state_dict()``
        (train/checkpoint.py sidecar) resumes EXACTLY mid-epoch —
        finishing the interrupted epoch counts as the first of
        ``epochs``. An exhausted iterator is reset() at each epoch top
        (the normal fresh-epoch path). Listeners fire per iteration and
        per epoch exactly as in :meth:`fit`."""
        model = self.model
        sync = bool(model.listeners.listeners)
        last = None
        for _ in range(epochs):
            if not iterator.has_next():
                iterator.reset()
            model.listeners.epoch_start(model)
            while iterator.has_next():
                ds = iterator.next()
                score, _ = self.fit_batch(ds.features, ds.labels,
                                          ds.features_mask, ds.labels_mask)
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

    def fit(self, data, labels=None, *, epochs: int = 1, mask=None, label_mask=None) -> None:
        model = self.model
        from ..nn.sequential import _as_batches

        # Without listeners the per-iteration score stays a device scalar —
        # fetching it would force a host sync every step and stall the XLA
        # dispatch pipeline (the reference has the same async property on CUDA:
        # JITA syncs lazily, SURVEY.md §3.1).
        sync_every_iter = bool(model.listeners.listeners)

        # Fast path: no listeners, no masks, standard backprop -> stack uniform
        # batches and run the whole epoch as one compiled scan (one dispatch).
        # A step profiler needs per-step boundaries, so it opts out.
        if (
            not sync_every_iter
            and self.profiler is None
            and mask is None
            and label_mask is None
            and model.conf.backprop_type is not BackpropType.TRUNCATED_BPTT
        ):
            batches = [
                (f, l) for f, l, m, lm in _as_batches(data, labels, mask)
                if m is None and lm is None
            ]
            shapes = {(np.shape(f), np.shape(l)) for f, l in batches}
            if batches and len(shapes) == 1:
                xs = np.stack([np.asarray(f) for f, _ in batches])
                ys = np.stack([np.asarray(l) for _, l in batches])
                last = None
                for _ in range(epochs):
                    model.listeners.epoch_start(model)
                    last = self.fit_scan(xs, ys)
                    model.listeners.epoch_end(model)
                    model.epoch_count += 1
                if last is not None:
                    model.score_value = float(last)
                return

        last_score = None
        for _ in range(epochs):
            model.listeners.epoch_start(model)
            for feats, labs, msk, lmsk in _as_batches(data, labels, mask):
                if label_mask is not None:
                    lmsk = label_mask
                if (
                    model.conf.backprop_type is BackpropType.TRUNCATED_BPTT
                    and getattr(feats, "ndim", 0) == 3
                    and feats.shape[2] > model.conf.tbptt_fwd_length
                ):
                    score = self._fit_tbptt(feats, labs, msk, lmsk)
                else:
                    score, _ = self.fit_batch(feats, labs, msk, lmsk)
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

    def _fit_tbptt(self, feats, labs, msk, lmsk) -> float:
        """Truncated BPTT windowed loop (reference: doTruncatedBPTT): slide a
        window of tbptt_fwd_length steps, carry RNN state (h/c) across windows
        within the batch, reset between batches."""
        model = self.model
        t_total = feats.shape[2]
        length = model.conf.tbptt_fwd_length
        rnn_state: dict = {}
        last_score = 0.0
        for start in range(0, t_total, length):
            end = min(start + length, t_total)
            fw = feats[:, :, start:end]
            lw = labs[:, :, start:end] if getattr(labs, "ndim", 0) == 3 else labs
            mw = None if msk is None else msk[:, start:end]
            lmw = None if lmsk is None else lmsk[:, start:end]
            score, new_rnn = self.fit_batch(fw, lw, mw, lmw, rnn_state=rnn_state)
            rnn_state = jax.lax.stop_gradient(new_rnn) if new_rnn else {}
            last_score = score
        return last_score
