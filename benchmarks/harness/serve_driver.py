"""Driver "serve": the configuration's zoo decoder behind ``DecodeEngine``,
driven through ``DecodeEngine.submit()`` and each ``GenerationHandle.events()``
by in-process clients that time every token on their own side.

Set-up warms the prefill buckets the mix's prompts can reach, the install and
the decode step, then starts the clients; the window opens once every closed-
loop client has finished one request (each client's FIRST request is cut to a
fraction of its length that spreads evenly over the clients, and to the mix's
``first_round_max_tokens``, so the requests in flight when the window opens
started at times spread over the round and not in one burst). At the close no new
request is sent; those in flight drain outside the rate's denominator.

Nothing here knows the model: the sizes, the seed's weight tree, the plain
reference, the work counts and the cache's bytes are the family's
(``run.family``, the file the configuration names).

``correct``: once the window has closed and the engine is gone, the family's
plain reference runs once over each sampled request's prompt and served
tokens.
Compared: the widest gap by which a served (greedy) token's logit lies below
the reference's best at its position, which one wrong token fails, and the
mean gap over all compared tokens, which a lower precision fails.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time

import numpy as np

from . import weights
from .runtime import (Run, TraceSlice, counters_between, device_report,
                      devices_for, dtype_bytes, percentile, read_counters)
from .traffic import RequestSource, poisson_due_times, size_set

HISTS = ("dl4j_tpu_generate_decode_latency_seconds",
         "dl4j_tpu_generate_prefill_latency_seconds")
DRAIN_S = 60.0


class ServeRun:
    def __init__(self, run: Run) -> None:
        self.run = run
        self.family = run.family
        self.dims = run.family.dims(run.config)
        self.traffic = run.traffic
        self.records: list = []
        self.lock = threading.Lock()
        self.stop_sending = threading.Event()
        self.threads: list = []
        self.firsts_done = 0
        self.all_first = threading.Event()
        self.t_close = float("inf")

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax

        from deeplearning4j_tpu.model import zoo
        from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork
        from deeplearning4j_tpu.parallel import DecodeEngine

        run, cfg = self.run, self.run.config
        self.devs = devices_for(run)
        model = MultiLayerNetwork(getattr(zoo, cfg["model_class"])(
            **cfg["model"], seed=run.seed & 0x7FFFFFFF,
            dtype=cfg["dtype"]).conf())
        w = weights.make_weights(self.family, self.dims, run.seed,
                                 cfg["dtype"])
        weights.install(model, weights.program_tree(
            self.family, self.dims, w, cfg["layout"]))
        del w
        self.engine = DecodeEngine(model, **cfg["engine"])
        run.log("weights from the seed installed, engine built")
        self.source = RequestSource(self.traffic, run.seed,
                                    self.dims["vocab_size"])
        # every prefill bucket the mix's prompts can reach, then one decode
        buckets = self.engine.session.bucket_sizes()
        reach = sorted({next(b for b in buckets if b >= n)
                        for n in size_set(self.traffic)[:, 0]})
        rng = np.random.default_rng([run.seed, 3])
        for b in reach:
            ids = rng.integers(0, self.dims["vocab_size"],
                               min(b, self.engine.max_len - 2))
            self.engine.generate(ids.tolist(), max_tokens=2)
            run.log(f"prefill bucket {b} warm")

    def hist(self) -> dict:
        out = {}
        for name in HISTS:
            child = self.engine.registry.get(name).labels(self.engine.name)
            out[name] = (child.sum, child.count)
        return out

    # ------------------------------------------------------------ clients
    def _one_request(self, req: dict, t_due: float, max_tokens=None) -> dict:
        rec = {"k": req["k"], "prompt": req["prompt"], "t_submit": t_due,
               "late_s": time.perf_counter() - t_due,
               "max_tokens": max_tokens or req["max_tokens"],
               "tokens": [], "times": [], "reason": None}
        try:
            handle = self.engine.submit(
                req["prompt"], max_tokens=rec["max_tokens"],
                greedy=True, seed=req["k"])
            for ev in handle.events(timeout=self.run.seconds + DRAIN_S):
                now = time.perf_counter()
                if ev.get("done"):
                    rec["reason"] = ev["reason"]
                    rec["t_done"] = now
                else:
                    rec["tokens"].append(ev["token"])
                    rec["times"].append(now)
        except Exception as e:  # noqa: BLE001 - refused or timed out: failed
            rec["reason"] = f"error: {type(e).__name__}: {e}"
        with self.lock:
            self.records.append(rec)
        return rec

    def _closed_client(self, i: int, n: int) -> None:
        first = True
        while not self.stop_sending.is_set():
            with self.lock:
                req = self.source.next()
            cut = None
            if first:  # start mid-life: client i keeps (i + 1) / n of it,
                # and no more than the mix's cap, so the round ends soon
                cut = max(2, min(round(req["max_tokens"] * (i + 1) / n),
                                 int(self.traffic.get(
                                     "first_round_max_tokens", 1 << 30))))
            self._one_request(req, time.perf_counter(), cut)
            if first:
                first = False
                with self.lock:
                    self.firsts_done += 1
                    if self.firsts_done == n:
                        self.all_first.set()

    def _open_schedule(self, horizon: float) -> None:
        due = poisson_due_times(self.traffic, horizon)
        t0 = time.perf_counter()
        self.all_first.set()  # an open loop has no warm-up round
        for d in due:
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                self.stop_sending.wait(wait)
            # a generator that runs late still sends what was due before the
            # close; each request is timed from when it was due
            if self.stop_sending.is_set() and t0 + d >= self.t_close:
                return
            with self.lock:
                req = self.source.next()
            th = threading.Thread(target=self._one_request,
                                  args=(req, t0 + d), daemon=True)
            th.start()
            with self.lock:
                self.threads.append(th)

    def start_clients(self) -> None:
        if self.traffic["arrival"] == "closed":
            n = int(self.traffic["clients"])
            ths = [threading.Thread(target=self._closed_client, args=(i, n),
                                    daemon=True) for i in range(n)]
        else:
            ths = [threading.Thread(
                target=self._open_schedule,
                args=(self.run.seconds + DRAIN_S,), daemon=True)]
        self.threads.extend(ths)
        for th in ths:
            th.start()
        if not self.all_first.wait(timeout=600.0):
            raise RuntimeError("the clients' first round did not finish")

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tracer: TraceSlice = None,
               slice_s: float = 3.0) -> dict:
        h_open, t_open = self.hist(), time.perf_counter()
        sl = None
        if tracer is not None:
            time.sleep(min(1.0, seconds / 4))
            h0, c0 = self.hist(), read_counters(self.run)
            tracer.start()
            time.sleep(min(slice_s, seconds / 2))
            h1, c1 = self.hist(), read_counters(self.run)
            tracer.stop()
            sl = {"t1": tracer.t1, "t_untraced": tracer.t_untraced,
                  "decode_steps": h1[HISTS[0]][1] - h0[HISTS[0]][1],
                  "counters": counters_between(c0, c1)}
        rest = t_open + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        self.t_close = t_close = time.perf_counter()
        self.stop_sending.set()
        h_close = self.hist()
        deadline = time.perf_counter() + seconds + DRAIN_S
        while True:
            with self.lock:
                alive = [th for th in self.threads if th.is_alive()]
            if not alive or time.perf_counter() > deadline:
                break
            alive[0].join(timeout=0.5)
        if sl is not None:  # every client has drained: now read the trace
            sl["trace"] = tracer.summary()
        return {"t_open": t_open, "t_close": t_close, "slice": sl,
                "hist": {n: (h_close[n][0] - h_open[n][0],
                             h_close[n][1] - h_open[n][1]) for n in HISTS}}

    # ------------------------------------------------------- after window
    def metrics(self, win: dict) -> dict:
        t_open, t_close = win["t_open"], win["t_close"]
        with self.lock:
            recs = list(self.records)
        sent = [r for r in recs if t_open <= r["t_submit"] < t_close]
        worst_ms = (self.run.seconds + DRAIN_S) * 1e3
        ttft, tpot, failed = [], [], 0
        for r in sent:
            ok = r["reason"] == "completed" and \
                len(r["tokens"]) == r["max_tokens"]
            failed += not ok
            ttft.append((r["times"][0] - r["t_submit"]) * 1e3
                        if ok else worst_ms)
            tpot.append((r["times"][-1] - r["times"][0]) * 1e3
                        / (len(r["times"]) - 1)
                        if ok and len(r["times"]) > 1 else worst_ms)
        out = {"attempted": len(sent), "failed": failed, "sent": sent,
               "serve_tokens_per_s": sum(
                   w for _, _, w in token_shares(recs, t_open, t_close))
               / (t_close - t_open),
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_p95_ms": percentile(ttft, 95),
               "tpot_p95_ms": percentile(tpot, 95),
               "kv_filled_bytes": self._kv_filled_bytes(recs, t_open, t_close)}
        sl = win["slice"]
        if sl is not None and sl["trace"] is not None:
            # starting and stopping the profiler stalls the engine once each:
            # the traced run's first-token tail is of the requests sent after
            out["ttft_p95_ms"] = percentile(
                [t for t, r in zip(ttft, sent)
                 if r["t_submit"] >= sl["t_untraced"]], 95)
            # the slice's length is the device's own window, read from the
            # trace; the host's clock only places it, ending where the
            # profiler was stopped, to say which tokens belong to it
            lo = sl["t1"] - sl["trace"]["window_s"]
            attended, prefills = [], []
            for r, i, share in token_shares(recs, lo, sl["t1"]):
                n = len(r["prompt"])
                if i == 0:
                    prefills.append([n, share])
                else:  # token i attends the prompt and i tokens
                    attended.append([n + i, share])
            out["slice"] = {
                "kind": "serve", "model": self.dims,
                "dtype_bytes": dtype_bytes(self.run.config["dtype"]),
                "decode_attended": attended, "prefill_lengths": prefills,
                "decode_steps": sl["decode_steps"],
                "counters": sl["counters"]}
        return out

    def _kv_filled_bytes(self, recs: list, t_open: float,
                         t_close: float) -> float:
        """The decode state the traffic really fills, as bytes, averaged
        over the window: a request at position p holds what the family says
        it holds there (``cache_bytes``: p entries of a cache, a constant
        state) for as long as its next token takes (the engine reserves
        slots x max_len)."""
        width = dtype_bytes(self.run.config["dtype"])
        held = 0.0
        for r, i, share in token_shares(recs, t_open, t_close):
            since = r["times"][i - 1] if i else r["t_submit"]
            held += self.family.cache_bytes(
                self.dims, len(r["prompt"]) + i, width) \
                * share * (r["times"][i] - since)
        return held / (t_close - t_open)

    def free(self) -> None:
        self.engine.shutdown(drain=False)
        self.engine = None
        gc.collect()  # the program's buffers go with their last reference

    def sample(self, sent: list) -> list:
        """The requests compared: a draw from the seed of those the window
        finished, with the longest in it."""
        done = sorted((r for r in sent if r["reason"] == "completed"
                       and r["tokens"]), key=lambda r: r["k"])
        if not done:
            return []
        n = int(self.run.cell.get("check_requests", 8))
        rng = np.random.default_rng([self.run.seed, 4])
        pick = {int(i) for i in rng.choice(len(done), min(n, len(done)),
                                           replace=False)}
        pick.add(max(range(len(done)), key=lambda i: len(
            done[i]["prompt"]) + len(done[i]["tokens"])))
        return [done[i] for i in sorted(pick)]

    def served_gaps(self, sample: list, quant=None) -> list:
        """For each sampled request the widest gap, over its served tokens,
        by which the token's reference logit lies below the reference's best
        at that position. With ``quant`` (the control) the token read at each
        position is the one the lower precision puts first instead."""
        import jax
        import jax.numpy as jnp

        cfg, dims = self.run.config, self.dims
        w = weights.make_weights(self.family, dims, self.run.seed,
                                 cfg["dtype"])
        length = int(cfg["engine"]["max_len"])
        logits_of = self.family.decoder_logits

        @jax.jit
        def gaps(w, ids, targets, mask):
            logits = logits_of(w, ids[None], dims)[0]
            if quant is not None:
                targets = jnp.argmax(logits_of(
                    w, ids[None], dims, quant=quant)[0], axis=-1)
            at = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
            gap = jnp.where(mask, jnp.max(logits, axis=-1) - at, 0.0)
            return jnp.max(gap), jnp.argmax(gap), jnp.sum(gap), \
                jnp.sum(gap > 0)

        out = []
        for r in sample:
            n, toks = len(r["prompt"]), r["tokens"]
            seq = (r["prompt"] + toks)[:length]
            ids = np.zeros((length,), np.int32)
            ids[:len(seq)] = seq
            targets = np.zeros((length,), np.int32)
            mask = np.zeros((length,), bool)
            # position n - 1 + i predicts served token i
            m = min(len(toks), length - (n - 1))
            targets[n - 1:n - 1 + m] = toks[:m]
            mask[n - 1:n - 1 + m] = True
            g, at, total, off = gaps(w, jnp.asarray(ids), jnp.asarray(targets),
                                     jnp.asarray(mask))
            out.append({"k": r["k"], "gap": float(g), "sum": float(total),
                        "off_best": int(off),
                        "token_index": int(at) - (n - 1), "tokens": m})
        return out


def token_shares(recs: list, lo: float, hi: float):
    """``(request, token index, share)`` of every output token of ``[lo,
    hi)``. A token is the work of the interval since its request's last one
    (since the submit, for the first), and counts by the share of that
    interval that lies inside: all the work and all the time of the window,
    with no step of the engine, which hands a token to every slot at once,
    counted whole for falling a millisecond to one side of an edge."""
    for r in recs:
        prev = r["t_submit"]
        for i, t in enumerate(r["times"]):
            if t > lo and prev < hi:
                yield r, i, ((min(t, hi) - max(prev, lo)) / (t - prev)
                             if t > prev else 1.0)
            prev = t


def served_checks(gaps: list, limits: dict) -> list:
    """The numbers read from the sampled requests: the widest gap of any
    served token, the mean gap over all of them, and the share of them that
    are not the reference's best. Those with a limit in the cell's file are
    compared; the others are read and printed (PERF.md says why)."""
    nan = float("nan")
    tokens = sum(g["tokens"] for g in gaps)
    worst = max(gaps, key=lambda g: g["gap"] if g["gap"] == g["gap"]
                else float("inf")) if gaps else None
    numbers = [
        {"name": "served_logit_gap", "value": worst["gap"] if gaps else nan,
         "at": (f"request {worst['k']} token {worst['token_index']} of "
                f"{tokens} compared") if gaps else "no request"},
        {"name": "served_mean_logit_gap",
         "value": sum(g["sum"] for g in gaps) / tokens if tokens else nan},
        {"name": "served_off_best_share",
         "value": sum(g["off_best"] for g in gaps) / tokens if tokens
         else nan},
    ]
    for n in numbers:
        n["limit"] = limits.get(n["name"])
    return numbers


def run(run: Run) -> dict:
    sv = ServeRun(run)
    sv.setup()
    sv.start_clients()
    run.log("every client has finished its first request: window opens")
    setup_s = run.setup_seconds()
    tracer = TraceSlice(run) if run.trace else None
    win = sv.window(run.seconds, tracer,
                    float(run.cell.get("trace_slice_s", 3.0)))
    m = sv.metrics(win)
    with open(os.path.join(run.out_dir, "requests.json"), "w") as f:
        # every request of the run, times in seconds from the window's open
        json.dump({"window_s": win["t_close"] - win["t_open"], "requests": [
            {"k": r["k"], "prompt": len(r["prompt"]), "reason": r["reason"],
             "submit": round(r["t_submit"] - win["t_open"], 4),
             "times": [round(t - win["t_open"], 4) for t in r["times"]]}
            for r in sorted(sv.records, key=lambda r: r["k"])]}, f)
    device = device_report(sv.devs, 1)
    stats = sv.engine.stats()
    sv.free()
    run.log(f"drained: {m['attempted']} requests sent in the window; "
            "reference starts")
    checks = served_checks(sv.served_gaps(sv.sample(m["sent"])),
                           run.cell["limits"])
    return {
        "attempted": m["attempted"], "failed": m["failed"], "device": device,
        "end_to_end": {k: m[k] for k in (
            "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")}
        | {"setup_s": setup_s},
        "record": {"trace": win["slice"]["trace"] if win["slice"] else None,
                   "slice": m.get("slice"), "hist": win["hist"],
                   "client": {k: m[k] for k in (
                       "ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms",
                       "serve_tokens_per_s")}},
        "checks": checks,
        "notes": {"engine_failed": stats["failed"], "shed": stats["shed"],
                  "kv_filled_bytes (mean over the window)":
                      m["kv_filled_bytes"],
                  "generator_late_p95_ms": 1e3 * percentile(
                      [r["late_s"] for r in m["sent"]], 95)},
    }
