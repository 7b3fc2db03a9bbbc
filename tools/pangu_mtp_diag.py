"""What the self-speculating openPangu cell serves, against the family's
plain reference, and what a speculative step costs (PERF.md sections 2
and 5).

    python tools/pangu_mtp_diag.py [--f32] [--tiny] [--time] [--oracle]
                                   [--seed N]

From the repo's root, one process.
``benchmarks/configs/pangu-ultra-moe-ep16.json`` at the published widths (``--tiny``: the cell's rehearsal widths, for the
CPU), the seed's weights, the cell's traffic ids:

* ``drafts`` (always): four requests (their outputs cut to 128 tokens)
  served through ``DecodeEngine.submit()`` with the MTP module drafting (8
  slots); each request again through the
  engine's own step body over a one-row cache (``mtp_prefill_row``, then
  ``mtp_step``: the drafts the steps verify); then, with the program gone,
  the family's ``mtp_logits`` over each request's prompt and served tokens:
  how far the module's logits (the program's, over the whole sequence) lie
  from the reference's (mean and widest difference, the share whose greedy
  token differs), the share of the cached drafts that are the reference
  module's greedy token, the served tokens' gaps as the benchmark reads
  them, and the engine's acceptance. The benchmark's ``correct`` sees
  only the served tokens; this is where the drafts are held to the
  reference.
* ``--oracle`` (instead of ``drafts``): the ACCEPT path, which random
  weights almost never take. Each request's one-row greedy stream (the
  self-speculating step over a one-row cache, as ``drafts`` makes it), then
  the same stream again with every draft forced to the stream's own next
  token (an oracle draft), so that every step with room for two keeps its
  draft and commits two: whether the committed stream is the one-row
  stream, the share of the drafts kept, and, with the program gone, how far
  the verify's logits at each of its two positions (the stack over
  ``[last, draft]`` on the row's cache, the step's own forward) lie from
  the family's ``decoder_logits`` over the stream (mean and widest
  difference, the share whose greedy token differs).
* ``--f32``: the same in float32 at "highest" with 1 dense + 1 expert layer
  and 4 held experts (the float32 tree of the cell's depth does not fit
  beside its reference): program and reference then agree to rounding, so a
  difference there is a fault of the program's, not the precision's.
* ``--time``: the device time of one decode step of the cell's engine (128
  rows at the traffic's mean position, 700) with the module drafting and
  without it (``speculative_k=0``), each the mean of 20 steps fenced at the
  end: what a draft costs, and so the acceptance at which drafting breaks
  even.

Prints, never asserts.
"""
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import runtime, weights  # noqa: E402
from benchmarks.harness.traffic import RequestSource  # noqa: E402

TINY = "--tiny" in sys.argv
F32 = "--f32" in sys.argv
SEED = int(sys.argv[sys.argv.index("--seed") + 1]) if "--seed" in sys.argv \
    else 4400000001
REQUESTS = 4
cell = json.load(open("benchmarks/workloads/pangu-agent-closed128.json"))
cfg = json.load(open("benchmarks/configs/pangu-ultra-moe-ep16.json"))
traffic = json.load(open("benchmarks/traffic/agent-closed128.json"))
model_kw, engine_kw = dict(cfg["model"]), dict(cfg["engine"])
dtype = cfg["dtype"]
if TINY:
    model_kw = dict(cell["rehearse"]["config"]["model"])
    engine_kw = dict(cell["rehearse"]["config"]["engine"])
    traffic = dict(traffic, **cell["rehearse"]["traffic"])
if F32:
    dtype = "float32"
    jax.config.update("jax_default_matmul_precision", "highest")
    model_kw.update(n_layers=2, n_held_experts=min(
        4, model_kw["n_held_experts"]))
fam = runtime.load_family("benchmarks/families/pangu_ultra_moe.py")
d = fam.dims({"model": model_kw})
print(f"diag seed {SEED} dtype {dtype} layers {d['n_layers']} held "
      f"{d['n_held_experts']}", flush=True)


def build():
    from deeplearning4j_tpu.model.zoo import PanguUltraMoeLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    model = MultiLayerNetwork(PanguUltraMoeLM(
        **model_kw, seed=1, dtype=dtype).conf())
    weights.install(model, weights.program_weights(
        fam, d, SEED, dtype, cfg["layout"]))
    return model


def served():
    """The requests served by the engine, the program's module over each
    whole sequence, and the cached drafts, on the host; nothing of the
    program outlives it."""
    from deeplearning4j_tpu.generate.session import GenerationSession
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel import DecodeEngine

    model = build()
    eng = DecodeEngine(model, **dict(engine_kw, slots=8, queue_limit=16),
                       registry=MetricsRegistry())
    src = RequestSource(traffic, SEED, d["vocab_size"])
    reqs = [src.next() for _ in range(REQUESTS)]
    t0 = time.perf_counter()
    hs = [eng.submit(r["prompt"], max_tokens=min(r["max_tokens"], 128))
          for r in reqs]
    toks = [h.result(timeout=900) for h in hs]
    print(f"served {sum(map(len, toks))} tokens in "
          f"{time.perf_counter() - t0:.1f} s; speculative "
          f"{json.dumps(eng.stats()['speculative'])}", flush=True)
    eng.shutdown(drain=False)
    sess = GenerationSession(model, max_len=engine_kw["max_len"])
    head, name = model.layers[-1], sess._layer_names[-1]

    @jax.jit
    def program_drafts(params, x):
        out, _ = sess._forward(params, model.state, x, None,
                               sess.decode_state(1))
        hp = sess._mtp_params(params, out)
        g, _ = head.draft(hp, sess.decode_state(1)[name], out[:, :, :-1],
                          x[:, 1:])
        return head.draft_logits(hp, g)[0]

    got = [np.asarray(program_drafts(model.params, jnp.asarray(
        [r["prompt"] + s])), np.float32) for r, s in zip(reqs, toks)]
    cached = [cached_drafts(model, sess, r["prompt"], len(s))
              for r, s in zip(reqs, toks)]
    return reqs, toks, got, cached


def drafts():
    reqs, toks, got, cached = served()
    jax.clear_caches()  # the program's tree goes before the reference's
    gc.collect()
    w = weights.make_weights(fam, d, SEED, dtype)
    ref_of = jax.jit(lambda w, x: (fam.decoder_logits(w, x, d)[0],
                                   fam.mtp_logits(w, x, d)[0]))
    for r, s, g, (cs, cd) in zip(reqs, toks, got, cached):
        q = r["prompt"] + s
        ref, mref = (np.asarray(a, np.float32)
                     for a in ref_of(w, jnp.asarray([q])))
        n = len(r["prompt"])
        # the drafts the engine verified: position i >= n - 1 drafts the
        # token at i + 2
        at = np.arange(n - 1, len(q) - 1)
        diff = np.abs(g[at] - mref[at])
        off = np.mean(g[at].argmax(-1) != mref[at].argmax(-1))
        gap = ref[n - 1:-1].max(-1) - np.take_along_axis(
            ref[n - 1:-1], np.asarray(s)[:, None], -1)[:, 0]
        # a cached draft made after committing up to index j of ``cs``
        # (position n - 1 + j) is the module's token at that position
        hits = [mref[n - 1 + j].argmax() == t for j, t in cd
                if n - 1 + j < len(mref)]
        print(f"request {r['k']}: prompt {n} served {len(s)}: drafts' "
              f"logits mean|d| {diff.mean():.5f} max|d| {diff.max():.4f} "
              f"greedy off {off:.3f} (logits std {mref[at].std():.3f}); "
              f"cached drafts {len(hits)}, the reference's {np.mean(hits):.3f}"
              f", stream as served {cs == s}; "
              f"served widest gap {gap.max():.4f} mean {gap.mean():.6f} "
              f"off_best {np.mean(gap > 0):.3f}", flush=True)


def cached_drafts(model, sess, prompt, count):
    """``prompt`` through the engine's prefill body and its self-speculating
    step over a one-row cache until ``count`` tokens are committed ->
    (the tokens, [(index of the last token committed, the draft made
    there)])."""
    from deeplearning4j_tpu.generate.session import (SV_DRAFT, SV_N,
                                                     SV_TOK0, SV_TOK1,
                                                     SV_WIDTH, pack_row_spec)

    tb = 1 << (len(prompt) - 1).bit_length()
    ids = np.zeros((1, tb), np.int32)
    ids[0, :len(prompt)] = prompt
    carry, tok, draft, _ = jax.jit(sess.mtp_prefill_row)(
        model.params, model.state, jnp.asarray(ids),
        jnp.asarray(pack_row_spec(len(prompt), 0, 0, True, 1.0, 0, 1.0)))
    toks, made = [int(tok)], [(0, int(draft))]
    sv = np.zeros((1, SV_WIDTH), np.int32)
    sv[0, :3] = int(tok), int(draft), 1
    sv = jnp.asarray(sv)
    rows = np.zeros((7, 1), np.int32)
    rows[0], rows[2], rows[6] = 1, 1, count
    rows[3] = rows[5] = np.float32(1.0).view(np.int32)
    step = jax.jit(sess.mtp_step)
    while len(toks) < count:
        carry, sv, _ = step(model.params, model.state, carry, sv,
                            jnp.asarray(rows))
        h = np.asarray(sv)[0]
        toks += [int(h[SV_TOK0]), int(h[SV_TOK1])][:int(h[SV_N])]
        made.append((len(toks) - 1, int(h[SV_DRAFT])))
    return toks, made[:-1] if len(toks) >= count else made


def oracle():
    from deeplearning4j_tpu.generate.session import GenerationSession

    model = build()
    sess = GenerationSession(model, max_len=engine_kw["max_len"])
    src = RequestSource(traffic, SEED, d["vocab_size"])
    reqs = [src.next() for _ in range(REQUESTS)]
    verify = jax.jit(lambda p, s, c, x: sess._logits(
        sess._forward(p, s, x, None, c)[0], p)[0])
    runs = []
    for r in reqs:
        toks, _ = cached_drafts(model, sess, r["prompt"],
                                min(r["max_tokens"], 128))
        runs.append((toks,) + oracle_row(model, sess, verify, r["prompt"],
                                         toks))
    jax.clear_caches()
    del model, sess, verify
    gc.collect()
    w = weights.make_weights(fam, d, SEED, dtype)
    ref_of = jax.jit(lambda w, x: fam.decoder_logits(w, x, d)[0])
    for r, (toks, committed, kept, got) in zip(reqs, runs):
        n = len(r["prompt"])
        ref = np.asarray(ref_of(w, jnp.asarray([r["prompt"] + toks])),
                         np.float32)
        parts = []
        for j in (0, 1):  # the verify's first and second position
            at = [n + i + j for i, _ in got]
            g = np.stack([pair[j] for _, pair in got])
            diff = np.abs(g - ref[at])
            off = np.mean(g.argmax(-1) != ref[at].argmax(-1))
            parts.append(f"position {j}: mean|d| {diff.mean():.5f} "
                         f"max|d| {diff.max():.4f} greedy off {off:.3f}")
        print(f"oracle request {r['k']}: prompt {n} tokens {len(toks)}: "
              f"committed as the one-row stream {committed == toks}, drafts "
              f"kept {kept[0]} of {kept[1]}, steps {len(got)}; "
              + "; ".join(parts) + f" (logits std {ref[n:].std():.3f})",
              flush=True)


def oracle_row(model, sess, verify, prompt, toks):
    """``prompt`` through the engine's prefill body, then its
    self-speculating step over a one-row cache with every draft the next
    token of ``toks`` -> (the tokens committed, (drafts kept, verified),
    [(index of the step's last token in ``toks``, the verify's logits at
    its two positions)])."""
    from deeplearning4j_tpu.generate.session import (SV_ACCEPTED, SV_DRAFT,
                                                     SV_N, SV_PROPOSED,
                                                     SV_TOK0, SV_TOK1,
                                                     SV_WIDTH, pack_row_spec)

    count = len(toks)
    tb = 1 << (len(prompt) - 1).bit_length()
    ids = np.zeros((1, tb), np.int32)
    ids[0, :len(prompt)] = prompt
    carry, tok, _, _ = jax.jit(sess.mtp_prefill_row)(
        model.params, model.state, jnp.asarray(ids),
        jnp.asarray(pack_row_spec(len(prompt), 0, 0, True, 1.0, 0, 1.0)))
    out = [int(tok)]
    sv = np.zeros((1, SV_WIDTH), np.int32)
    sv[0, :3] = int(tok), 0, 1
    rows = np.zeros((7, 1), np.int32)
    rows[0], rows[2], rows[6] = 1, 1, count
    rows[3] = rows[5] = np.float32(1.0).view(np.int32)
    step = jax.jit(sess.mtp_step)
    kept = proposed = 0
    got = []
    while len(out) < count:
        j = len(out) - 1
        sv[0, SV_DRAFT] = toks[j + 1] if j + 1 < count else 0
        logits = verify(model.params, model.state, carry,
                        jnp.asarray([[out[-1], int(sv[0, SV_DRAFT])]]))
        got.append((j, np.asarray(logits, np.float32).T))
        carry, h, _ = step(model.params, model.state, carry,
                           jnp.asarray(sv), jnp.asarray(rows))
        sv = np.array(h)
        out += [int(sv[0, SV_TOK0]), int(sv[0, SV_TOK1])][:int(sv[0, SV_N])]
        kept += int(sv[0, SV_ACCEPTED])
        proposed += int(sv[0, SV_PROPOSED])
    return out, (kept, proposed), got


def step_time(k: int, position: int = 700, steps: int = 20) -> float:
    """ms of one decode step of the cell's engine, every row at
    ``position``."""
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel import DecodeEngine

    model = build()
    eng = DecodeEngine(model, **dict(engine_kw, speculative_k=k),
                       registry=MetricsRegistry())
    try:
        slots = eng.slots
        carry = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, position) if a.ndim == 1 else a,
            eng._carry)
        eng._carry = None
        rows = np.ones((slots,), bool)
        eng._steps[:] = 1
        eng._limit[:] = 1 << 20
        fn = eng._decode_step_fn()
        toks, image = eng._step_args(rows)
        if k:  # every row has a last token and a draft, and room
            toks = jnp.zeros_like(toks).at[:, 2].set(1)
        args = (model.params, model.state)
        carry, toks, _ = fn(*args, carry, toks, image)       # compiles
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        for _ in range(steps):
            carry, toks, _ = fn(*args, carry, toks, image)
        jax.block_until_ready(toks)
        return (time.perf_counter() - t0) / steps * 1e3
    finally:
        eng.shutdown(drain=False)


if "--oracle" in sys.argv:
    oracle()
else:
    drafts()
gc.collect()  # the reference's tree goes before a program is built again
if "--time" in sys.argv:
    spec = step_time(1)
    gc.collect()
    plain = step_time(0)
    print(f"step ms: drafting {spec:.3f}, plain {plain:.3f}; a kept draft "
          f"saves a plain step: break-even acceptance "
          f"{max(0.0, spec / plain - 1.0):.3f}", flush=True)
