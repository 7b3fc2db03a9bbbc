"""What one device call costs the host, on the machine this runs on.

The decode loop's turn is made of a few kinds of call (PERF.md, PR 37): a
small upload, a program's dispatch, a fetch. This script times each alone,
in one quiet thread, at the GPT-2 cell's widths (128 slots x 1,024
positions; ``--tiny`` rehearses the script on a CPU), and beside them the
calls of one step and of one admission as the loop made them before
ISSUE 37 (nine uploads a step; nine uploads and three programs an
admission) and as it makes them now (one upload a step; one upload and one
program an admission). Run from the root of a checkout:

    python tools/host_call_costs.py [--tiny]

A dispatch is timed with its operands on the device and the device idle,
and fenced outside the timed region; an upload likewise. One JSON line on
stdout: ms a call, median (p10, p90) of ``N`` calls, and the device's kind
(a CPU's numbers are the rehearsal's, never the chip's host's). The loop
itself runs beside 128 client threads that take turns at the interpreter
lock, so its calls cost more than these: the ratios carry over, not the
milliseconds.

Beside them, under ``account_us`` (microseconds, ISSUE 38): what the loop's
own account of the device's queue costs a turn, which is always on: one look
at the queue (``is_ready()`` of a finished array, the two clocks, a counter's
increment, each alone), and ``turn`` whole: the ten looks, seven marks, two
enqueues and the turn's end with its counters that a steady turn of 128 rows
with one admission makes, with nothing found dry (the dearer branch: every
look asks the device). Inside the running loop the same calls cost several
times more (PERF.md, PR 38). And under ``state_bytes_us`` what ``dl4j_tpu_decode_state_bytes``
costs a turn at 128 active rows of the LFM2 cell's layers, row by row as the
loop made it until ISSUE 38 and in one array expression a layer as it makes
it now (the two give the same numbers).

Under ``handoff``: what handing a token to its consumer costs. A ``put`` and
the matching ``get`` of one event, in one quiet thread, us, for
``queue.Queue`` (a ``GenerationHandle``'s queue before) and
``queue.SimpleQueue`` (its queue now); and the put pass of one step in
place, ms: 128 consumer threads each blocked in a ``get`` on its own queue
and running the benchmark client's per-token body, a producer that puts one
event into each queue and then waits 2 ms with the lock released, as the
loop waits for the device. The pass's time is what the row loop's puts cost
with 128 woken threads queued at the interpreter lock, the number beside the
quiet one.
"""

import json
import os
import queue
import sys
import threading
import time
from collections import Counter

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.generate.sampling import sample_tokens
from deeplearning4j_tpu.model.zoo import TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel.decode import (DecodeEngine, _Request,
                                                live_state_bytes)

N = 200


def timed(fn, n=N):
    """ms a call of ``fn``, whose result is fenced outside the clock."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        r = fn()
        out.append(1e3 * (time.perf_counter() - t0))
        jax.block_until_ready(r)
    out.sort()
    return [round(out[n // 2], 4), round(out[n // 10], 4),
            round(out[(9 * n) // 10], 4)]


def timed_fetch(make, fetch, n=50):
    """ms a first ``fetch`` of what ``make`` computed, once it is there (a
    second fetch of the same array reads the host's copy)."""
    out = []
    for _ in range(n):
        r = jax.block_until_ready(make())
        t0 = time.perf_counter()
        fetch(r)
        out.append(1e3 * (time.perf_counter() - t0))
    out.sort()
    return [round(out[n // 2], 4), round(out[n // 10], 4),
            round(out[(9 * n) // 10], 4)]


def timed_us(fn, batch=1000, n=30):
    """us a call of ``fn``: the median of ``n`` batches of ``batch`` calls
    (one call is under the clock's own cost)."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        for _ in range(batch):
            fn()
        out.append(1e6 * (time.perf_counter() - t0) / batch)
    out.sort()
    return round(out[n // 2], 4)


def account_costs(e) -> dict:
    """The dry account's pieces and one steady turn of it, us (the engine's
    loop is parked: nothing else touches the account)."""
    dry, real = e._dry, e._device_dry
    jax.block_until_ready(dry.newest)
    res = {"is_ready": timed_us(dry.newest.is_ready),
           "device_dry": timed_us(real),
           "perf_counter": timed_us(time.perf_counter),
           "thread_time": timed_us(time.thread_time),
           "counter_inc": timed_us(lambda: e._c_loop_s.inc(1e-9))}
    dry.begin()
    res["look_dry"] = timed_us(dry.look)  # already dry: no question asked
    # every look asks the device and hears "busy"
    e._device_dry = lambda: real() and False
    dry.begin()
    res["look_busy"] = timed_us(dry.look)

    def turn():
        dry.begin()
        dry.look()
        dry.enqueued(dry.newest)          # an admission
        for phase in ("step", "select", "upload"):
            dry.mark(phase)
        dry.look("dispatch")
        dry.enqueued(dry.newest)          # the step
        dry.mark("fetch")
        for _ in range(5):                # the emit loop's start, rows, end
            dry.look()
        for phase in ("fetch", "emit"):   # a first token
            dry.mark(phase)
        dry.look("step")
        dry.mark("sweep")
        e._c_loop_s.inc(dry.end())
        e._c_dry_slack.inc(dry.slack)
        for phase in ("emit", "sweep", "admit"):
            e._c_dry[phase].inc(1e-9)
    res["turn"] = timed_us(turn, batch=100)
    del e._device_dry
    return res


def state_bytes_costs(tiny: bool) -> dict:
    """``_update_state_bytes`` at 128 active rows of the LFM2 cell's layers
    (no weights: a layer is its configuration): row by row, and now."""
    from deeplearning4j_tpu.model import zoo
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    cfg = json.load(open(os.path.join(
        "benchmarks", "configs", "lfm2-8b-a1b-pp2.json")))
    model = dict(cfg["model"])
    if tiny:
        model.update(vocab_size=64, hidden=64, n_heads=4, n_kv_heads=2,
                     ffn_size=128, expert_ffn_size=32, n_experts=4, top_k=2,
                     max_len=64)
    layers = Counter(
        l for l in MultiLayerNetwork(getattr(zoo, cfg["model_class"])(
            **model, dtype=cfg["dtype"]).conf()).layers
        if l.decode_live_bytes(0, 1))
    rng = np.random.default_rng(0)
    active = np.ones((128,), bool)
    pos = rng.integers(200, 6100, 128).astype(np.int64)

    def by_row():
        live = Counter()
        for layer, count in layers.items():
            live.update(dict.fromkeys(layer.decode_live_bytes(0, 2), 0))
            for slot in np.nonzero(active)[0]:
                for kind, n in layer.decode_live_bytes(
                        int(pos[slot]), 2).items():
                    live[kind] += count * n
        return live

    def by_array():
        return live_state_bytes(layers, pos[active], 2)

    assert by_row() == by_array(), (by_row(), by_array())
    return {"layer_kinds": len(layers), "rows": 128,
            "row_by_row": timed_us(by_row, batch=20),
            "one_array_a_layer": timed_us(by_array, batch=20)}


def put_pass_ms(make_queue, rows=128, passes=200) -> list:
    """ms of one put pass into ``rows`` queues whose consumer threads wait
    in ``get``: median, p10, p90 of ``passes`` passes."""
    queues = [make_queue() for _ in range(rows)]
    seen = [[] for _ in range(rows)]

    def consume(q, tokens):  # the benchmark client's per-token body
        times = []
        while True:
            ev = q.get(timeout=60)
            now = time.perf_counter()
            if ev.get("done"):
                return
            tokens.append(ev["token"])
            times.append(now)

    threads = [threading.Thread(target=consume, args=(q, s), daemon=True)
               for q, s in zip(queues, seen)]
    for t in threads:
        t.start()
    out = []
    for i in range(passes):
        t0 = time.perf_counter()
        for q in queues:
            q.put({"token": i, "index": i})
        out.append(1e3 * (time.perf_counter() - t0))
        time.sleep(0.002)
    for q in queues:
        q.put({"done": True})
    for t in threads:
        t.join(timeout=60)
    assert all(s == list(range(passes)) for s in seen)
    out.sort()
    return [round(out[passes // 2], 4), round(out[passes // 10], 4),
            round(out[(9 * passes) // 10], 4)]


def handoff_costs() -> dict:
    """A put with its get in one quiet thread (us), and a step's put pass to
    128 waiting consumers (ms), for each kind of queue."""
    res = {}
    for name, make in (("Queue", queue.Queue),
                       ("SimpleQueue", queue.SimpleQueue)):
        q, ev = make(), {"token": 1, "index": 0}
        res[name] = {
            "put_get_us": timed_us(lambda: (q.put(ev), q.get(timeout=1))),
            "put_pass_128_ms": put_pass_ms(make)}
    res["put_pass_128_ms_is"] = "median, p10, p90"
    res["switch_interval_s"] = sys.getswitchinterval()
    res["cpus"] = os.cpu_count()
    return res


def main():
    if "--tiny" in sys.argv:
        model = TransformerLM(vocab_size=64, hidden=32, n_layers=2, n_heads=4,
                              max_len=64).init()
        slots, max_len, tb = 8, 64, 16
    else:
        model = TransformerLM(vocab_size=50257, hidden=768, n_layers=12,
                              n_heads=12, ffn_size=3072, max_len=1024,
                              dtype="bfloat16").init()
        slots, max_len, tb = 128, 1024, 128
    e = DecodeEngine(model, max_len=max_len, slots=slots,
                     registry=MetricsRegistry())
    sess, params, state = e.session, model.params, model.state
    res = {"device": jax.devices()[0].device_kind, "slots": slots,
           "bucket": tb, "calls": N, "ms": "median, p10, p90"}
    rows = np.ones((slots,), bool)
    hosts = (e._last, e._fresh, rows, e._seeds, e._steps, e._greedy,
             e._temps, e._ks, e._ps)

    res["upload_scalar"] = timed(lambda: jnp.asarray(3, jnp.int32))
    res["upload_row_vector"] = timed(lambda: jnp.asarray(e._last.copy()))
    res["step_uploads_before"] = timed(
        lambda: tuple(jnp.asarray(a.copy()) for a in hosts))
    res["step_uploads_now"] = timed(lambda: e._step_args(rows)[1])

    # the step's dispatch, operands ready
    image = e._step_args(rows)[1]
    step = e._decode_step_fn()

    def do_step():
        e._carry, e._toks, _ = step(params, state, e._carry, e._toks, image,
                                    e._table)
        return e._toks
    jax.block_until_ready(do_step())
    res["dispatch_decode_step"] = timed(do_step)
    res["fetch_step_tokens"] = timed_fetch(do_step, np.asarray)

    # an admission as the loop made it before ISSUE 37: the prefill's seven
    # operands, a row as large as a slot out, an install, the token's write
    def prefill_before(params, state, ids, lengths, seed, gflag, temp, k, p):
        row, last = sess.prefill_logits(params, state, sess.decode_state(1),
                                        ids, lengths)
        tok = sample_tokens(last, seed, jnp.zeros((1,), jnp.int32), gflag,
                            temp, k, p)
        return row, tok[0], sess.summed_counts(row)

    before = jax.jit(prefill_before)
    install, set_token = e._write_row_fn(), e._set_token_fn()
    prompt = list(range(1, 6))

    def uploads_before():
        ids = np.zeros((1, tb), np.int32)
        ids[0, :len(prompt)] = prompt
        return (jnp.asarray(ids), jnp.asarray([len(prompt)], jnp.int32),
                jnp.asarray([1], jnp.uint32), jnp.asarray([True], bool),
                jnp.asarray([1.0], jnp.float32), jnp.asarray([0], jnp.int32),
                jnp.asarray([1.0], jnp.float32))

    def admission_before():
        row, tok, _ = before(params, state, *uploads_before())
        e._carry = install(e._carry, row, jnp.asarray(1, jnp.int32))
        e._toks = set_token(e._toks, jnp.asarray(1, jnp.int32), tok)
        return e._toks

    ops = jax.block_until_ready(uploads_before())
    jax.block_until_ready(admission_before())
    res["dispatch_prefill_before"] = timed(
        lambda: before(params, state, *ops)[1], n=N // 2)
    res["admission_before"] = timed(admission_before, n=N // 2)

    # and now: one array up, one program that installs what it computes
    req = _Request(prompt, 4, None, None, 1, True, 1.0, 0, 1.0, None, None)
    fused = e._prefill_fn(tb)

    def admission_now():
        e._carry, e._toks, tok, _ = fused(params, state, e._carry,
                                          *e._prefill_args(tb, 1, req))
        return tok

    adm = jax.block_until_ready(e._prefill_args(tb, 1, req)[1])

    def dispatch_now():
        e._carry, e._toks, tok, _ = fused(params, state, e._carry, e._toks,
                                          adm)
        return tok
    jax.block_until_ready(admission_now())
    res["dispatch_prefill_now"] = timed(dispatch_now, n=N // 2)
    res["admission_now"] = timed(admission_now, n=N // 2)
    res["fetch_first_token"] = timed_fetch(dispatch_now, int)
    res["account_us"] = account_costs(e)
    res["state_bytes_us"] = state_bytes_costs("--tiny" in sys.argv)
    e.shutdown(drain=False)
    res["handoff"] = handoff_costs()
    print(json.dumps(res))


if __name__ == "__main__":
    main()
