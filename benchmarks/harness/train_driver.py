"""Driver "train": builds the configuration's zoo model, drives the
trainer's per-batch call through the measured window, and compares the first
steps with the plain reference.

One chip (mesh product 1): ``GraphSolver.fit_batch``, the per-batch call
under ``ComputationGraph.fit(iterator)``. More: ``DistributedTrainer(model,
mesh=make_mesh(**mesh), zero1=...)`` with the batch put on
``trainer.data_sharding``. Which one is the configuration file's ``mesh``,
so a cell on four chips is a configuration file and a cell file.

Nothing here knows the model: the sizes, the seed's weight tree, the loss of
the plain reference and the work counts are the family's (``run.family``,
the file the configuration names).

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first three steps (through the window's own call and
feed; the reference follows them), and hands that same object to the window.
"""

from __future__ import annotations

import gc
import math
import time
from collections import deque

import numpy as np

from . import compare, reference, weights
from .runtime import (Run, TraceSlice, counters_between, device_report,
                      devices_for, dtype_bytes, read_counters)
from .traffic import token_batches

CHECK_STEPS = 3


def _adam_mu(opt_state) -> dict:
    """``{layer: mu tree}`` out of the per-layer optax states."""
    def find(node):
        if hasattr(node, "mu"):
            return node.mu
        if isinstance(node, (tuple, list)):
            for child in node:
                got = find(child)
                if got is not None:
                    return got
        return None

    return {layer: find(st) for layer, st in opt_state.items()}


class TrainRun:
    def __init__(self, run: Run) -> None:
        self.run = run
        self.family = run.family
        self.dims = run.family.dims(run.config)
        self.adam = dict(run.config["updater"]["adam"])
        self.layout = run.config["layout"]
        self.batch = int(run.traffic["batch"])
        self.seq = int(run.traffic["seq"])
        self.check_batches: list = []
        self.program: dict = {}
        self.scores: list = []

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.model import zoo
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.train.updaters import Adam

        run, cfg = self.run, self.run.config
        self.devs = devices_for(run)
        n_mesh = math.prod(cfg["mesh"].values())
        if n_mesh > len(self.devs):
            raise SystemExit(f"benchmark: mesh {cfg['mesh']} needs {n_mesh} "
                             f"devices, JAX offers {len(self.devs)}")
        self.used = n_mesh
        model = ComputationGraph(getattr(zoo, cfg["model_class"])(
            **cfg["model"], seed=run.seed & 0x7FFFFFFF,
            updater=Adam(**self.adam), dtype=cfg["dtype"],
            compute_dtype=cfg["compute_dtype"]).conf())
        w = weights.make_weights(self.family, self.dims, run.seed,
                                 cfg["dtype"])
        start = weights.program_tree(self.family, self.dims, w, self.layout)
        weights.install(model, weights.program_tree(
            self.family, self.dims, w, self.layout))
        del w
        if n_mesh > 1:
            from deeplearning4j_tpu.parallel import (DistributedTrainer,
                                                     make_mesh)

            mesh_devs = None if len(self.devs) == n_mesh \
                else self.devs[:n_mesh]
            trainer = DistributedTrainer(
                model, mesh=make_mesh(devices=mesh_devs, **cfg["mesh"]),
                zero1=bool(cfg.get("zero1", False)))
            sharding = trainer.data_sharding
            self._fit = trainer.fit_batch
            self._state = lambda: (trainer.params, trainer.opt_state)
        else:
            from deeplearning4j_tpu.train.graph_solver import GraphSolver

            solver = GraphSolver(model)
            # uncommitted, as the solver's own outputs are: a batch pinned to
            # the device would make step 2's arguments differ from step 1's
            # and compile the step twice
            sharding = None
            self._fit = lambda x, y: solver.fit_batch((x,), (y,))
            self._state = lambda: (model.params, solver.opt_state)
        self._put = lambda a: jax.device_put(a, sharding)
        jax.block_until_ready(model.params)
        run.log("weights from the seed installed, trainer built")
        self.feed = token_batches(run.traffic, run.seed,
                                  self.dims["vocab_size"])

        f32 = jnp.float32
        norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(f32)))), t))
        diff_norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(f32) - y.astype(f32)))), a, b))

        # the first steps, through the window's own call and feed
        losses, gnorm = [], None
        for _ in range(CHECK_STEPS):
            ids, labels = next(self.feed)
            self.check_batches.append((ids, labels))
            losses.append(self.step(ids, labels))
            run.log(f"check step {len(losses)} dispatched")
            if gnorm is None:
                # Adam's first moment after one step is (1 - beta1) g: the
                # gradient as the optimizer got it
                gnorm = norms(_adam_mu(self._state()[1]))
        dnorm = diff_norms(self._state()[0], start)
        del start
        def names(tree, scale=1.0):
            flat = weights.canonical_names(self.family, self.dims,
                                           jax.device_get(tree), self.layout)
            return {k: scale * v for k, v in flat.items()}

        run.log("first steps done")
        self.program = {
            "losses": [float(s) for s in losses],
            "gnorm": names(gnorm, 1.0 / (1.0 - self.adam["beta1"])),
            "dnorm": names(dnorm)}

    def step(self, ids, labels):
        """The window's call: one fresh batch through the trainer."""
        return self._fit(self._put(ids), self._put(labels))

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer: TraceSlice = None,
               slice_s: float = 3.0) -> dict:
        pending: deque = deque()
        scores, steps = self.scores, 0
        slice_info = None

        def drain(keep: int = 0) -> None:
            while len(pending) > keep:
                pending.popleft().block_until_ready()

        def one() -> None:
            ids, labels = next(self.feed)
            s = self.step(ids, labels)
            pending.append(s)
            scores.append(s)
            drain(keep=2)  # run-ahead bounded to two steps

        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            one()
            steps += 1
            elapsed = time.perf_counter() - t0
            if tracer is not None and slice_info is None and elapsed >= 1.0:
                drain()
                k = max(3, int(round(slice_s / (elapsed / steps))))
                c0 = read_counters(self.run)
                tracer.start()
                for _ in range(k):
                    one()
                drain()
                tracer.stop()
                steps += k
                slice_info = {"kind": "train", "steps": k,
                              "batch": self.batch, "seq": self.seq,
                              "model": self.dims, "dtype_bytes": dtype_bytes(
                                  self.run.config["compute_dtype"]),
                              "counters": counters_between(
                                  c0, read_counters(self.run))}
        drain()
        t1 = time.perf_counter()
        return {"steps": steps, "seconds": t1 - t0,
                "tokens": steps * self.batch * self.seq,
                "slice": slice_info,
                "trace": tracer.summary() if slice_info else None}

    # ------------------------------------------------------- after window
    def failed_steps(self) -> int:
        vals = np.asarray([float(s) for s in self.scores], float)
        return int(np.sum(~np.isfinite(vals)))

    def free(self) -> None:
        self._fit = self._state = self._put = self.feed = None
        self.scores = []
        gc.collect()  # the program's buffers go with their last reference

    def reference_numbers(self, quant=None, half_batch=False,
                          state_unchanged=False) -> dict:
        """The plain reference over the same first steps, from the seed."""
        cfg = self.run.config
        w = weights.make_weights(self.family, self.dims, self.run.seed,
                                 cfg["dtype"])
        stacked = weights.stacked_keys(self.family, self.dims)
        losses, gnorm, dnorm = reference.train_steps(
            self.family.loss, w, self.check_batches, self.dims, self.adam,
            stacked=stacked,
            row_block=int(self.run.cell.get("reference_row_block", 8)),
            quant=quant, half_batch=half_batch,
            state_unchanged=state_unchanged)
        return {"losses": losses,
                "gnorm": weights.stacked_names(gnorm, stacked),
                "dnorm": weights.stacked_names(dnorm, stacked)}


def run(run: Run) -> dict:
    tr = TrainRun(run)
    tr.setup()
    setup_s = run.setup_seconds()
    tracer = TraceSlice(run) if run.trace else None
    win = tr.window(run.seconds, tracer,
                    float(run.cell.get("trace_slice_s", 3.0)))
    device = device_report(tr.devs, tr.used)
    failed = tr.failed_steps()
    tr.free()
    run.log(f"window closed: {win['steps']} steps; reference starts")
    ref = tr.reference_numbers()
    run.log("reference done")
    checks = compare.train_checks(tr.program, ref, run.cell["limits"])
    return {
        "attempted": win["steps"], "failed": failed, "device": device,
        "end_to_end": {"train_tokens_per_s": win["tokens"] / win["seconds"],
                       "setup_s": setup_s},
        "record": {"trace": win["trace"], "slice": win["slice"], "hist": {}},
        "checks": checks,
        "notes": {"program_losses": tr.program["losses"],
                  "reference_losses": ref["losses"]},
    }
