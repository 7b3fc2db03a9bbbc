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
"""

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.generate.sampling import sample_tokens
from deeplearning4j_tpu.model.zoo import TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel.decode import DecodeEngine, _Request

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
    e.shutdown(drain=False)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
