"""A token's way from the decode loop to its consumer: ``GenerationHandle``.

The engine's thread puts a ``{"token", "index"}`` event per token and one
terminal ``{"done", "reason", "count"[, "error"]}`` event into the handle's
queue; a consumer thread iterates :meth:`GenerationHandle.events`. What a
consumer sees (the payloads, their order, a timeout, the done callbacks) is
the contract; the queue under it is the engine's to choose.
"""

import json
import queue
import sys
import threading
import time

import pytest

from deeplearning4j_tpu.core.resilience import Deadline
from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.model.zoo import TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel.decode import DecodeEngine, GenerationHandle

MAX_LEN = 32
VOCAB = 23


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=VOCAB, hidden=32, n_layers=2, n_heads=4,
                         max_len=MAX_LEN).init()


@pytest.fixture(scope="module")
def draft():
    return TransformerLM(vocab_size=VOCAB, hidden=16, n_layers=1, n_heads=2,
                         max_len=MAX_LEN, seed=99).init()


def _handle():
    return GenerationHandle("h", Deadline.never())


@pytest.mark.parametrize("error", [None, "device halted"])
def test_events_come_in_order_and_the_terminal_one_counts_the_tokens(error):
    h = _handle()
    toks = [7, 3, 3, 11, 0, 5]
    producer = threading.Thread(target=lambda: (
        [h._emit(i, t) for i, t in enumerate(toks)],
        h._finish("failed" if error else "completed", error)))
    producer.start()
    evs = list(h.events(timeout=30))
    producer.join(timeout=30)
    assert not producer.is_alive()
    assert evs[:-1] == [{"token": t, "index": i} for i, t in enumerate(toks)]
    want = {"done": True, "reason": "failed" if error else "completed",
            "count": len(toks)}
    if error:
        want["error"] = error
    assert evs[-1] == want
    assert h.tokens == toks and h.done and h.result(timeout=1) == toks
    assert h._events.empty()


def test_events_raise_empty_when_nothing_arrives_in_time():
    h = _handle()
    it = h.events(timeout=0.05)
    t0 = time.monotonic()
    with pytest.raises(queue.Empty):
        next(it)
    assert time.monotonic() - t0 >= 0.04
    h._emit(0, 4)
    # a token that came late is still the next event
    assert next(h.events(timeout=0.05)) == {"token": 4, "index": 0}
    with pytest.raises(queue.Empty):
        next(h.events(timeout=0.01))


@pytest.mark.parametrize("when", ["before", "after"])
def test_a_done_callback_fires_once_whenever_it_is_registered(when):
    h = _handle()
    heard = []
    if when == "before":
        h.add_done_callback(heard.append)
        assert heard == []
    h._emit(0, 1)
    h._finish("completed")
    if when == "after":
        h.add_done_callback(heard.append)
    assert heard == [h]
    # a second terminal event of the engine's never reaches the callback
    # again: the list was handed over once
    h._finish("completed")
    assert heard == [h]


def test_done_callbacks_race_the_finish_and_fire_exactly_once():
    """Registration from one thread against the engine's ``_finish`` in
    another, with the interpreter switching threads as often as it can:
    every callback fires exactly once, before or after the finish."""
    n = 300
    handles = [_handle() for _ in range(n)]
    fired = [0] * n
    lock = threading.Lock()

    def cb(i):
        def fire(_):
            with lock:
                fired[i] += 1
        return fire

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        finisher = threading.Thread(
            target=lambda: [h._finish("completed") for h in handles])
        registrar = threading.Thread(
            target=lambda: [h.add_done_callback(cb(i))
                            for i, h in enumerate(handles)])
        finisher.start()
        registrar.start()
        finisher.join(timeout=60)
        registrar.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not finisher.is_alive() and not registrar.is_alive()
    assert fired == [1] * n


def _engine(model, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    return DecodeEngine(model, **kw)


@pytest.mark.parametrize("engine", ["plain", "speculative"])
def test_a_consumer_thread_sees_every_token_of_a_multi_step_request_in_order(
        lm, draft, engine):
    """Consumers iterate ``events()`` in their own threads while the loop
    emits: each sees its request's tokens, as Python ints, indexed
    0..n-1, then one terminal event counting them, and the stream is the
    single-sequence session's."""
    e = _engine(lm, **({"draft_model": draft, "speculative_k": 2}
                       if engine == "speculative" else {}))
    plan = [([1, 2, 3], 20), ([4, 5], 13), ([6, 1, 7, 2], 9)]
    got = [None] * len(plan)

    def consume(i, h):
        got[i] = list(h.events(timeout=120))

    try:
        hs = [e.submit(p, max_tokens=n) for p, n in plan]
        threads = [threading.Thread(target=consume, args=(i, h))
                   for i, h in enumerate(hs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        undelivered = e.stats()["undelivered_events"]
    finally:
        e.shutdown()
    sess = GenerationSession(lm, max_len=MAX_LEN)
    for (p, n), evs, h in zip(plan, got, hs):
        want = sess.generate([p], n)[0]
        assert evs[:-1] == [{"token": t, "index": i}
                            for i, t in enumerate(want)]
        assert all(type(ev["token"]) is int and type(ev["index"]) is int
                   for ev in evs[:-1])
        assert evs[-1] == {"done": True, "reason": "completed", "count": n}
        assert h.tokens == want
        json.dumps(evs)  # what a streaming handler writes, as it is
    assert undelivered == 0


def test_undelivered_events_count_what_the_consumers_have_not_taken(lm):
    """``stats()["undelivered_events"]`` sums the live requests' queues:
    with the loop parked mid-stream it reads every token not yet taken,
    falls by one an event taken, and reads 0 once the consumers drain."""
    gate, parked = threading.Event(), threading.Event()
    landed = [0]

    def hook():  # the loop parks after its fourth step's tokens
        landed[0] += 1
        if landed[0] >= 4 and not gate.is_set():
            parked.set()
            gate.wait(60)

    e = _engine(lm, step_hook=hook)
    try:
        assert e.stats()["undelivered_events"] == 0
        a = e.submit([1, 2, 3], max_tokens=MAX_LEN - 4)
        b = e.submit([4, 5], max_tokens=MAX_LEN - 3)
        assert parked.wait(60)
        na, nb = len(a.tokens), len(b.tokens)
        assert na >= 3 and na < MAX_LEN - 4 and nb < MAX_LEN - 3  # live
        assert e.stats()["undelivered_events"] == na + nb
        ea, eb = a.events(timeout=60), b.events(timeout=60)
        taken = [next(ea) for _ in range(2)]
        assert [ev["index"] for ev in taken] == [0, 1]
        assert e.stats()["undelivered_events"] == na + nb - 2
        for _ in range(na - 2):
            next(ea)
        for _ in range(nb):
            next(eb)
        assert e.stats()["undelivered_events"] == 0
        gate.set()
        rest_a, rest_b = list(ea), list(eb)
        assert rest_a[-1]["count"] == MAX_LEN - 4
        assert rest_b[-1]["count"] == MAX_LEN - 3
        assert e.stats()["undelivered_events"] == 0
    finally:
        gate.set()
        e.shutdown()
