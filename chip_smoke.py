#!/usr/bin/env python3
"""chip_smoke.py — does the program still start on the chip?

One process drives the main path once at published widths, through the
entry points a user calls, and checks what comes out:

* **train**   — zoo ``BertEncoder`` (BERT-base widths, bf16 compute) through
  ``ComputationGraph.fit(iterator)`` / ``GraphSolver``: the loss falls on a
  repeated batch, and the 512-token step's lowered program holds the Pallas
  flash forward and both backward kernels.
* **serve**   — zoo ``TransformerLM`` (GPT-2-small widths, bf16) behind
  ``JsonModelServer`` -> ``DecodeEngine``: concurrent ``POST /v1/generate``
  requests all end ``completed`` with their full ``max_tokens``; the paged
  engine returns the same greedy tokens; the compiled decode step holds the
  Pallas decode kernel.
* **kernels** — every Pallas kernel in ``ops/`` compiled (``interpret=False``)
  and compared on the device with its float32 reference.
* **four_chips** — the train model through ``DistributedTrainer`` under
  ZeRO-1 data parallelism and under DP x TP; skipped (and reported so) with
  fewer than four devices.

Without a TPU it exits 2 and prints no result. ``--dry-run-cpu`` is the CPU
rehearsal: toy sizes, interpreted kernels, ``"dry_run": true`` in the
result — it proves the script's own control flow and nothing about a chip.
A phase that fails raises; nothing is caught and summarised. Stdout ends
with two JSON lines: the report (versions, compile-cache directory,
per-phase seconds, environment facts), then the verdict the driver reads,
``{"ok": true, "device": {"platform", "kind", "count"}}`` with exactly
those keys. A chip belongs to one process, so no child
process ever touches JAX: the only one there can be is the host-side
native build that ``native.available()`` runs to completion when the
library is absent, after the phases.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Published widths (depth and width both as published; weights random from
# a seed). BertEncoder's defaults ARE bert-base: vocab 30,522, hidden 768,
# 12 layers, 12 heads of 64, FFN 3,072.
FULL = dict(
    bert=dict(),
    train_batch=(16, 128), train_steps=8,
    # 512 tokens: where mha_attention "auto" switches to the Pallas kernel
    long_batch=(4, 512),
    lm=dict(vocab_size=50257, hidden=768, n_layers=12, n_heads=12,
            ffn_size=3072, max_len=1024, dtype="bfloat16"),
    # EvaByte's published widths (two of its 32 layers): one stream whose
    # prompt ends 8 bytes short of the 2,048-byte window's edge
    eva=dict(vocab_size=320, hidden=4096, n_layers=2, n_heads=32,
             ffn_size=11008, window=2048, chunk=16, n_pred_heads=8,
             max_len=4096, dtype="bfloat16"),
    eva_prompt=2040, eva_tokens=24,
    slots=8, block_size=16, max_tokens=32,
    # prefill buckets are powers of two: 16 | 17 and 32 | 33 straddle two
    prompt_lens=(12, 16, 17, 30, 32, 33),
    flash_shapes=((4, 12, 512, 64, False), (2, 12, 1024, 64, True)),
    # the serve cell's step (128 slots) beside the eight-row one
    decode_shapes=((8, 12, 1024, 64), (128, 12, 1024, 64)),
    # ROADMAP S2: 8,192 tokens x top-2 = 16,384 routed rows, d 768, 8
    # experts of width 1,536, capacity 1.25 x 16,384 / 8
    gmm_shape=(16384, 768, 8, 1536), gmm_capacity=2560,
    four_batch=(16, 128),
)
DRY = dict(
    bert=dict(vocab_size=64, hidden=32, n_layers=1, n_heads=2, ffn_size=64,
              max_len=32),
    train_batch=(4, 8), train_steps=8, long_batch=(2, 32),
    lm=dict(vocab_size=50, hidden=32, n_layers=1, n_heads=2, ffn_size=64,
            max_len=32, dtype="float32"),
    eva=dict(vocab_size=40, hidden=32, n_layers=1, n_heads=2, ffn_size=64,
             window=16, chunk=4, n_pred_heads=2, max_len=64,
             dtype="float32"),
    eva_prompt=12, eva_tokens=8,
    slots=4, block_size=16, max_tokens=4, prompt_lens=(4, 5),
    flash_shapes=((1, 2, 64, 16, True),),
    decode_shapes=((2, 2, 64, 16), (12, 2, 64, 16)),
    gmm_shape=(96, 16, 4, 32), gmm_capacity=32,
    four_batch=(4, 8),
)

# bf16 inputs against a float32 reference computed at "highest" matmul
# precision: one bf16 rounding of an O(1) output is 2^-8 = 0.4%; the
# backward also rounds p and ds to bf16 before its matmuls. Errors are
# measured against the reference's largest magnitude. Computing in a
# precision below bf16, or skipping a term, lands far outside these.
FWD_TOL = 2e-2
GRAD_TOL = 4e-2
# four chips against one: same program, other reduction order, bf16
# compute. Measured 1e-5 relative on the v5e host (PR 21).
LOSS_TOL = 1e-3

BERT_TP_RULES = (  # Megatron column/row layout, as in __graft_entry__.py
    (r".*_attn/W[qkv]$", (None, "model")),
    (r".*_attn/Wo$", ("model", None)),
    (r".*_ffn1/W$", (None, "model")),
    (r".*_ffn1/b$", ("model",)),
    (r".*_ffn2/W$", ("model", None)),
)


class _CompileClock:
    """Sums JAX's backend-compile durations (cache look-ups included, so a
    warm persistent cache shows as fewer seconds) and counts cache hits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        import jax.monitoring as mon

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


@contextlib.contextmanager
def _phase(name: str, clock: _CompileClock, report: dict):
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    out: dict = {}
    yield out
    out.update(ok=True, wall_s=round(time.perf_counter() - t0, 2),
               compile_s=round(clock.seconds - c0, 2),
               cache_hits=clock.cache_hits - h0)
    report[name] = out
    print(f"chip_smoke: {name} ok {json.dumps(out)}", file=sys.stderr,
          flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _kernel_names(lowered_text: str) -> set:
    import re

    return set(re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    _check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    _check(bool(jnp.all(jnp.isfinite(got))), "non-finite kernel output")
    return float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-30))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _bert(cfg):
    from deeplearning4j_tpu.model.zoo import BertEncoder

    return BertEncoder(compute_dtype="bfloat16", **cfg["bert"])


def _token_batch(vocab: int, batch, seed: int):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, vocab, batch).astype(np.int32)
    return ids, ids.copy()  # memorise-the-input MLM stand-in


def phase_train(cfg, on_chip: bool, out: dict) -> None:
    from deeplearning4j_tpu.core.listeners import CollectScoresListener
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator

    enc = _bert(cfg)
    model = enc.init()
    scores = CollectScoresListener()
    model.add_listeners(scores)
    b, t = cfg["train_batch"]
    ids, labels = _token_batch(enc.vocab_size, (b, t), seed=0)
    steps = cfg["train_steps"]
    model.fit(ListDataSetIterator(
        DataSet(np.tile(ids, (steps, 1)), np.tile(labels, (steps, 1))), b))
    losses = list(scores.scores)
    _check(len(losses) == steps, f"{len(losses)} steps ran, wanted {steps}")
    _check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    out.update(first_loss=round(losses[0], 4), last_loss=round(losses[-1], 4))

    ids_l, labels_l = _token_batch(enc.vocab_size, cfg["long_batch"], seed=1)
    model.fit(DataSet(ids_l, labels_l))
    _check(len(scores.scores) == steps + 1 and np.isfinite(scores.scores[-1]),
           f"long step loss: {scores.scores[steps:]}")
    out["long_step_loss"] = round(scores.scores[-1], 4)
    if on_chip:
        # the step GraphSolver just ran, lowered again for its text
        import jax.numpy as jnp

        solver = model._solver
        text = solver._step_fn(
            1, 1, model.listeners.requires_arrays).lower(
            model.params, solver.opt_state, model.state,
            model._as_inputs((ids_l,)), (jnp.asarray(labels_l),),
            model._rng.next_key()).as_text()
        names = _kernel_names(text)
        want = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
        _check("tpu_custom_call" in text and want <= names,
               f"long step holds {sorted(names)}, wanted {sorted(want)}")
        out["long_step_kernels"] = sorted(names)
    else:
        out["long_step_kernels"] = "not checked (dry run: interpreted)"


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _generate_all(engine, prompts, max_tokens: int):
    """The prompts, concurrently, through POST /v1/generate on a loopback
    JsonModelServer in front of ``engine``; returns each stream's tokens
    after checking its TERMINAL event."""
    from deeplearning4j_tpu.remote import JsonModelServer, JsonRemoteInference

    server = JsonModelServer(generator=engine).start()
    try:
        client = JsonRemoteInference(
            f"http://127.0.0.1:{server.port}/v1/serving")

        def one(prompt):
            # the first request of each prefill bucket waits for a compile
            events = list(client.generate(prompt, max_tokens=max_tokens,
                                          timeout=900.0))
            done = events[-1]
            _check(done.get("done") is True and "error" not in done
                   and done.get("reason") == "completed"
                   and done.get("count") == max_tokens,
                   f"prompt of {len(prompt)} ended {done}")
            toks = [e["token"] for e in events[:-1]]
            _check(len(toks) == max_tokens, f"{len(toks)} token events")
            return toks

        with ThreadPoolExecutor(len(prompts)) as pool:
            return list(pool.map(one, prompts))
    finally:
        server.stop(drain=False)


def phase_serve(cfg, on_chip: bool, out: dict) -> None:
    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.parallel import DecodeEngine

    lm = TransformerLM(**cfg["lm"])
    model = lm.init()
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, lm.vocab_size, n).tolist()
               for n in cfg["prompt_lens"]]
    kw = dict(max_len=lm.max_len, slots=cfg["slots"])
    streams = {}
    for layout, extra in (("static", {}),
                          ("paged", {"block_size": cfg["block_size"]})):
        engine = DecodeEngine(model, **kw, **extra)
        try:
            streams[layout] = _generate_all(engine, prompts,
                                            cfg["max_tokens"])
            stats = engine.stats()
            _check(stats["failed"] == 0
                   and stats["completed"] == len(prompts),
                   f"{layout} engine stats {stats}")
            if on_chip and layout == "static":
                e = engine  # idle now: its carry is whatever the step left
                text = e._decode_step_fn().lower(
                    model.params, model.state, e._carry,
                    *e._step_args(e._active)).as_text()
                _check("flash_decode" in _kernel_names(text),
                       "decode step does not hold the Pallas decode kernel")
        finally:
            engine.shutdown(drain=False)
    _check(streams["static"] == streams["paged"],
           "paged and static engines disagree on greedy tokens")
    out.update(requests=2 * len(prompts),
               tokens=2 * len(prompts) * cfg["max_tokens"],
               decode_kernel=("flash_decode" if on_chip else
                              "not checked (dry run: XLA reference path)"))
    _serve_evabyte(cfg, on_chip, out)


def _serve_evabyte(cfg, on_chip: bool, out: dict) -> None:
    """One EvaByte stream that crosses a window's edge (its terminal event
    checked like the others: the engine swallows a step that fails to
    trace), and the step's program holding the EVA kernel."""
    from deeplearning4j_tpu.model.zoo import EvaByteLM
    from deeplearning4j_tpu.parallel import DecodeEngine

    lm = EvaByteLM(**cfg["eva"])
    model = lm.init()
    prompt = np.random.RandomState(3).randint(
        0, lm.vocab_size, cfg["eva_prompt"]).tolist()
    engine = DecodeEngine(model, max_len=lm.max_len, slots=2)
    try:
        (tokens,) = _generate_all(engine, [prompt], cfg["eva_tokens"])
        stats = engine.stats()
        _check(stats["failed"] == 0 and stats["completed"] == 1,
               f"EvaByte engine stats {stats}")
        crossed = (cfg["eva_prompt"] + cfg["eva_tokens"]) // lm.window \
            - cfg["eva_prompt"] // lm.window
        _check(crossed >= 1, "the EvaByte stream crossed no window's edge")
        if on_chip:
            e = engine
            text = e._decode_step_fn().lower(
                model.params, model.state, e._carry,
                *e._step_args(e._active)).as_text()
            _check({"eva_decode", "kv_cache_write"} <= _kernel_names(text),
                   "EvaByte's decode step does not hold its Pallas kernels")
    finally:
        engine.shutdown(drain=False)
    out.update(evabyte_tokens=len(tokens), evabyte_windows_crossed=crossed,
               evabyte_kernel=("eva_decode" if on_chip else
                               "not checked (dry run: XLA reference path)"))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def phase_kernels(cfg, on_chip: bool, out: dict) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.flash_attention import (
        decode_attention_reference, flash_attention, flash_decode_attention,
        mha_attention_reference)
    from deeplearning4j_tpu.ops.grouped_matmul import (
        grouped_matmul, grouped_matmul_reference)

    interpret = not on_chip  # on the chip: the COMPILED kernels, always
    f32 = jnp.float32
    errs = {}

    def run_kernel(fn, *args, holds):
        """Lower, compile and run ``fn``; on the chip its lowered program
        must hold the named Pallas kernels (not a reference spelling)."""
        lowered = jax.jit(fn).lower(*args)
        if on_chip:
            names = _kernel_names(lowered.as_text())
            _check(set(holds) <= names,
                   f"{sorted(holds)} not in the lowered program: "
                   f"{sorted(names)}")
        return lowered.compile()(*args)

    def rand(seed, *shape):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.bfloat16)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*(a.astype(f32) if jnp.issubdtype(
                a.dtype, jnp.floating) else a for a in args))

    for b, h, t, d, causal in cfg["flash_shapes"]:
        q, k, v, g = (rand(i, b, h, t, d) for i in range(4))
        # causal case also carries a key-padding mask (second half of the
        # last row masked), the TransformerLM training shape
        mask = None
        if causal:
            mask = jnp.ones((b, t), f32).at[-1, t // 2:].set(0.0)

        def loss(fn, q, k, v, g=g, mask=mask, causal=causal, **kw):
            o = fn(q, k, v, mask=mask, causal=causal, **kw)
            return jnp.sum(o.astype(f32) * g.astype(f32)), o

        got_g, got_o = run_kernel(jax.grad(
            lambda *a: loss(flash_attention, *a, interpret=interpret),
            argnums=(0, 1, 2), has_aux=True), q, k, v,
            holds=("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
        ref_g, ref_o = highest(jax.grad(
            lambda *a: loss(mha_attention_reference, *a),
            argnums=(0, 1, 2), has_aux=True), q, k, v)
        tag = f"flash_t{t}{'_causal' if causal else ''}"
        errs[f"{tag}_fwd"] = (_rel_err(got_o, ref_o), FWD_TOL)
        for n, a, r in zip("qkv", got_g, ref_g):
            errs[f"{tag}_d{n}"] = (_rel_err(a, r), GRAD_TOL)

    for b, h, L, d in cfg["decode_shapes"]:
        q, k, v = rand(5, b, h, 1, d), rand(6, b, h, L, d), rand(7, b, h, L, d)
        # frontiers: first slot, a block edge on either side, the last slot,
        # and an inactive row (-1: attends nothing, outputs 0); the many-row
        # shape has the serve cell's spread (positions 16-896 of 1,024)
        if b <= 8:
            pos = np.asarray(
                [0, L // 2 - 1, L // 2, L - 1, -1, 5, L // 3, L - 2][:b])
        else:
            pos = np.random.RandomState(11).permutation(
                np.linspace(L // 64, L * 7 // 8, b).astype(np.int32))
            pos[4] = -1
        idle = np.nonzero(pos < 0)[0]
        pos = jnp.asarray(pos, jnp.int32)
        got = run_kernel(lambda q, k, v, p: flash_decode_attention(
            q, k, v, p, interpret=interpret), q, k, v, pos,
            holds=("flash_decode",))
        ref = highest(decode_attention_reference, q, k, v, pos)
        errs[f"flash_decode_b{b}"] = (_rel_err(got, ref), FWD_TOL)
        _check(not np.asarray(got, np.float32)[idle].any(),
               "flash_decode: an inactive row's output is not 0")

    n, d, e, hdim = cfg["gmm_shape"]
    lhs, w = rand(8, n, d), rand(9, n, hdim)
    rhs = rand(10, e, d, hdim) * 0.05
    for cap in (cfg["gmm_capacity"], None):
        # uneven groups, a few rows parked past the frontier; under a
        # capacity no group may exceed it (the caller's contract)
        rs = np.random.RandomState(3)
        sizes = rs.multinomial(n - n // 16, rs.dirichlet(np.ones(e) * 2.0))
        if cap is not None:
            sizes = np.minimum(sizes, cap)
        sizes = jnp.asarray(sizes, jnp.int32)

        def gloss(fn, lhs, rhs, sizes=sizes, cap=cap, **kw):
            o = fn(lhs, sizes, rhs, max_group_size=cap, **kw)
            return jnp.sum(o.astype(f32) * w.astype(f32)), o

        # "auto" picks the Pallas kernel on a TPU and the XLA spelling
        # elsewhere; run_kernel proves which one the chip compiled
        got_g, got_o = run_kernel(jax.grad(
            lambda a, b_: gloss(grouped_matmul, a, b_, interpret=interpret),
            argnums=(0, 1), has_aux=True), lhs, rhs,
            holds=("grouped_matmul",))
        ref_g, ref_o = highest(jax.grad(
            lambda a, b_: gloss(grouped_matmul_reference, a, b_),
            argnums=(0, 1), has_aux=True), lhs, rhs)
        tag = "gmm" + ("" if cap is None else f"_cap{cap}")
        errs[f"{tag}_fwd"] = (_rel_err(got_o, ref_o), FWD_TOL)
        errs[f"{tag}_dlhs"] = (_rel_err(got_g[0], ref_g[0]), GRAD_TOL)
        errs[f"{tag}_drhs"] = (_rel_err(got_g[1], ref_g[1]), GRAD_TOL)

    bad = {name: et for name, et in errs.items() if not et[0] <= et[1]}
    _check(not bad, f"kernel/reference mismatch (rel err, tol): {bad}")
    out.update(compiled=on_chip, fwd_tol=FWD_TOL, grad_tol=GRAD_TOL,
               rel_err={name: float(f"{err:.2e}")
                        for name, (err, _) in errs.items()})


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(cfg, on_chip: bool, out: dict, one_chip_loss) -> None:
    import jax
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel import DistributedTrainer, make_mesh

    # all four of a four-chip host: make_mesh then takes JAX's ICI order
    devs = None if len(jax.devices()) == 4 else jax.devices()[:4]
    enc = _bert(cfg)
    ids, labels = _token_batch(enc.vocab_size, cfg["four_batch"], seed=0)
    rules = [(pat, P(*spec)) for pat, spec in BERT_TP_RULES]
    for tag, mesh, kw in (
            ("dp4_zero1", make_mesh(data=4, devices=devs), dict(zero1=True)),
            ("dp2_tp2", make_mesh(data=2, model=2, devices=devs),
             dict(param_sharding_rules=rules))):
        trainer = DistributedTrainer(enc.init(), mesh=mesh, **kw)
        x = jax.device_put(ids, trainer.data_sharding)
        y = jax.device_put(labels, trainer.data_sharding)
        loss = float(trainer.fit_batch(x, y))
        jax.block_until_ready(trainer.params)
        for what, tree in (("params", trainer.params),
                           ("updater state", trainer.opt_state),
                           ("batch", (x, y))):
            for leaf in jax.tree_util.tree_leaves(tree):
                if getattr(leaf, "ndim", 0):  # step counters stay scalar
                    _check(len(leaf.sharding.device_set) == 4,
                           f"{tag}: a {what} leaf sits on "
                           f"{len(leaf.sharding.device_set)} devices")
        split = [leaf for leaf in jax.tree_util.tree_leaves(
            (trainer.params, trainer.opt_state))
            if not leaf.sharding.is_fully_replicated]
        _check(split, f"{tag}: nothing is actually partitioned")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat]
        _check(not on_chip or all(in_use),
               f"{tag}: bytes_in_use per device {in_use}")
        _check(np.isfinite(loss) and
               abs(loss - one_chip_loss) <= LOSS_TOL * abs(one_chip_loss),
               f"{tag}: first-step loss {loss} vs one chip {one_chip_loss}")
        out[tag] = dict(first_loss=round(loss, 4),
                        partitioned_leaves=len(split), bytes_in_use=in_use)
    out["loss_tol_rel"] = LOSS_TOL


# ---------------------------------------------------------------------------
# environment facts (facts about the machine, not metrics of the program)
# ---------------------------------------------------------------------------


def env_facts(on_chip: bool) -> dict:
    import jax
    import jax.numpy as jnp

    n, chain, reps = (4096, 200, 200) if on_chip else (64, 4, 8)
    w = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    w = (w / np.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def run(x):
        y = jax.lax.fori_loop(0, chain, lambda _, a: a @ w, x)
        return y, y[0, 0].astype(jnp.float32)

    def timed(fence):
        t0 = time.perf_counter()
        fence(run(w))
        return time.perf_counter() - t0

    timed(jax.block_until_ready)  # compile
    t_block = min(timed(jax.block_until_ready) for _ in range(3))
    t_fetch = min(timed(lambda r: float(r[1])) for _ in range(3))

    tiny = jax.jit(lambda a: a + 1.0)
    z = tiny(jnp.zeros((8, 128), jnp.float32)).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        z = tiny(z).block_until_ready()
    fenced_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        z = tiny(z)
    z.block_until_ready()
    queued_ms = (time.perf_counter() - t0) / reps * 1e3

    mb = 256 if on_chip else 1
    host = np.ones((mb << 20,), np.uint8)
    jax.device_put(host[:1024]).block_until_ready()
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        rates.append(mb / (time.perf_counter() - t0))
    facts = dict(
        matmul_chain=f"{chain} x {n}^2 bf16",
        block_until_ready_s=round(t_block, 4),
        host_fetch_s=round(t_fetch, 4),
        block_until_ready_waits=bool(t_fetch <= 1.3 * t_block
                                     and t_block <= 1.3 * t_fetch),
        fenced_dispatch_ms=round(fenced_ms, 4),
        queued_dispatch_ms=round(queued_ms, 4),
        device_put_mb=mb,
        device_put_mb_per_s=round(float(np.median(rates)), 1))
    if on_chip:
        facts["matmul_chain_tflops"] = round(
            2 * n ** 3 * chain / t_block / 1e12, 1)
    return facts


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="CPU rehearsal at toy sizes; says nothing about "
                         "a chip and labels its result so")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.core.env import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.dry_run_cpu:
        print(f"chip_smoke: no TPU — JAX reports {dev.platform!r} "
              f"({len(jax.devices())} device(s)); nothing was built",
              file=sys.stderr)
        return 2
    if on_chip and args.dry_run_cpu:
        print("chip_smoke: --dry-run-cpu is the CPU rehearsal; JAX reports "
              "a TPU here — run without the flag", file=sys.stderr)
        return 2
    cfg = FULL if on_chip else DRY

    from deeplearning4j_tpu import native

    clock = _CompileClock()
    clock.install()
    phases: dict = {}
    t_start = time.perf_counter()
    with _phase("train", clock, phases) as out:
        phase_train(cfg, on_chip, out)
    with _phase("serve", clock, phases) as out:
        phase_serve(cfg, on_chip, out)
    with _phase("kernels", clock, phases) as out:
        phase_kernels(cfg, on_chip, out)
    if len(jax.devices()) >= 4:
        with _phase("four_chips", clock, phases) as out:
            phase_four_chips(cfg, on_chip, out,
                             phases["train"]["first_loss"])
    else:
        phases["four_chips"] = dict(
            skipped=f"{len(jax.devices())} device(s); needs 4")
    facts = env_facts(on_chip)

    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    stats = dev.memory_stats() or {}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    report = dict(
        device=device,
        dry_run=bool(args.dry_run_cpu),
        jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version,
        compile_cache_dir=cache_dir,
        native_available=native.available(),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        wall_s=round(time.perf_counter() - t_start, 2),
        phases=phases, env_facts=facts)
    print(json.dumps(report))
    # the verdict: these keys and no others (the driver checks the set)
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
