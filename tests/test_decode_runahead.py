"""The decode loop runs one step ahead of the host (ISSUE 30): step n is
dispatched from step n-1's tokens on the device, and the host fetches and
emits step n-1 underneath it.

What a caller can observe must not change but for when a token arrives:
values, indices, order, terminal reasons and counts are the synchronous
loop's, for every carry. What the host cannot know ahead (an eos, a cancel,
a deadline) costs a row at most one step, whose token is dropped; where the
next action needs the tokens on the host (going idle, a speculative turn, a
failure) the step in flight lands first.
"""

import time

import pytest

from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.model.zoo import EvaByteLM, TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import TraceStore, Tracer
from deeplearning4j_tpu.parallel.decode import DecodeEngine

MAX_LEN = 32
VOCAB = 23
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [2, 2], [9, 3, 1, 7], [5],
           [8, 8, 1], [3, 4, 5, 6, 7, 8, 9], [6, 1]]
LENGTHS = [6, 3, 9, 1, 7, 5, 12, 2]
SAMPLINGS = {
    "greedy": {"greedy": True},
    "sampled": {"greedy": False, "temperature": 0.9, "top_k": 7,
                "top_p": 0.95},
}
# EVA at rehearsal size: window 16, chunk 4, so the streams cross windows
EVA = dict(vocab_size=40, hidden=32, n_layers=2, n_heads=2, ffn_size=64,
           window=16, chunk=4, n_pred_heads=8, rope_theta=1e5, max_len=64)
EVA_PROMPTS = [list(range(1, 14)), list(range(3, 20)), [7, 8, 9],
               list(range(2, 32)), [5, 4, 3, 2, 1]]
EVA_LENGTHS = [9, 20, 16, 5, 25]


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=VOCAB, hidden=32, n_layers=2, n_heads=4,
                         max_len=MAX_LEN).init()


@pytest.fixture(scope="module")
def eva():
    return EvaByteLM(**EVA, seed=1, dtype="float32").init()


@pytest.fixture(scope="module")
def session(lm):
    return GenerationSession(lm, max_len=MAX_LEN)


def _engine(model, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    return DecodeEngine(model, **kw)


def _wait(cond, timeout=60.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end
        time.sleep(0.005)


@pytest.mark.parametrize("sampling", list(SAMPLINGS))
@pytest.mark.parametrize("carry", ["static", "paged", "eva"])
def test_streams_under_churn_equal_the_session(lm, eva, carry, sampling):
    """More requests than slots, mixed lengths (one of a single token, whose
    slot is refilled at once): every stream is the single-sequence
    session's, token for token, and no row-step was thrown away (nothing
    here ends a request that the host did not see coming)."""
    kw = SAMPLINGS[sampling]
    if carry == "eva":
        model, max_len = eva, EVA["max_len"]
        prompts, lengths, layout = EVA_PROMPTS, EVA_LENGTHS, {}
    else:
        model, max_len, prompts, lengths = lm, MAX_LEN, PROMPTS, LENGTHS
        layout = {"block_size": 4} if carry == "paged" else {}
    e = _engine(model, max_len=max_len, **layout)
    try:
        hs = [e.submit(p, max_tokens=n, seed=11 + i, **kw)
              for i, (p, n) in enumerate(zip(prompts, lengths))]
        got = [h.result(timeout=300) for h in hs]
        stats = e.stats()
    finally:
        e.shutdown()
    sess = GenerationSession(model, max_len=max_len)
    for i, (p, n) in enumerate(zip(prompts, lengths)):
        assert got[i] == sess.generate([p], n, seed=11 + i, **kw)[0], i
    assert [h.reason for h in hs] == ["completed"] * len(hs)
    assert stats["failed"] == 0 and stats["dropped_row_steps"] == 0
    # every step but a batch's first ran under the step before it
    assert stats["steps_ahead"] > 0.6 * stats["decode_steps"]
    if carry == "paged":
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]


# every row its own law: seeds past 2**31 and floats with no short binary
# form, which the step's packed image has to carry bit for bit (ISSUE 37)
OWN_SPECS = [
    {"seed": 0xFFFFFFFF, "temperature": 0.7, "top_k": 0, "top_p": 0.9},
    {"seed": 0x80000001, "temperature": 1.3, "top_k": 5, "top_p": 1.0},
    {"seed": 3, "temperature": 1 / 3, "top_k": 0, "top_p": 1.0},
    {"seed": 0xDEADBEEF, "temperature": 0.9, "top_k": 11, "top_p": 0.61},
    {"seed": 17, "greedy": True},
    {"seed": 0x7FFFFFFF, "temperature": 2.5, "top_k": 3, "top_p": 0.97},
    {"seed": 2 ** 31, "temperature": 0.11, "top_k": 0, "top_p": 0.3},
]


@pytest.mark.parametrize("carry", ["static", "paged", "eva"])
def test_rows_with_their_own_specs_are_sampled_as_the_session_samples(
        lm, eva, carry):
    """Three slots refilled as they free, so that turns admit up to three
    prompts at once beside rows mid-stream: each sampled stream is the
    single-sequence session's under the same seed and law."""
    if carry == "eva":
        model, max_len, prompts, lengths = (eva, EVA["max_len"], EVA_PROMPTS,
                                            EVA_LENGTHS)
    else:
        model, max_len, prompts, lengths = lm, MAX_LEN, PROMPTS, LENGTHS
    specs = [dict({"greedy": False}, **kw)
             for kw in OWN_SPECS[:len(prompts)]]
    e = _engine(model, max_len=max_len, slots=3,
                **({"block_size": 4} if carry == "paged" else {}))
    try:
        hs = [e.submit(p, max_tokens=n, **kw)
              for p, n, kw in zip(prompts, lengths, specs)]
        got = [h.result(timeout=300) for h in hs]
        stats = e.stats()
    finally:
        e.shutdown()
    sess = GenerationSession(model, max_len=max_len)
    for i, (p, n, kw) in enumerate(zip(prompts, lengths, specs)):
        assert got[i] == sess.generate([p], n, **kw)[0], i
    assert stats["failed"] == 0 and stats["dropped_row_steps"] == 0


@pytest.mark.parametrize("n", [2, 3])
def test_a_turns_admissions_are_all_dispatched_before_anything_is_fetched(
        lm, session, n):
    """ISSUE 37: no prefill waits for the one before it. A turn that admits
    ``n`` prompts dispatches all their programs, then its step, and only
    then fetches: the step before, then the ``n`` first tokens, which land
    in the order of admission, each as its request's index 0."""
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    gate = {"delay": 0.0}
    e = _engine(lm, slots=n + 1, tracer=tracer,
                step_hook=lambda: time.sleep(gate["delay"]))
    order = []
    try:
        e.generate([4, 5, 6], max_tokens=2)  # the bucket and the step compiled
        running = e.submit([7, 5, 4], max_tokens=MAX_LEN - 3)
        _wait(lambda: len(running.tokens) >= 2)
        gate["delay"] = 0.3     # the loop sleeps in its hook: all n queue up
        _wait(lambda: len(running.tokens) >= 4)
        prompts = [[1, 2, 3], [3, 2, 1], [2, 3, 1]][:n]
        hs = [e.submit(p, max_tokens=4) for p in prompts]
        for i, h in enumerate(hs):
            def emit(index, token, i=i, real=h._emit):
                order.append((i, index))
                real(index, token)
            h._emit = emit
        gate["delay"] = 0.0
        got = [h.result(timeout=120) for h in hs]
        running.result(timeout=120)
    finally:
        e.shutdown()
    assert got == [session.generate([p], 4)[0] for p in prompts]
    assert [i for i, index in order if index == 0] == list(range(n))
    assert all(order.index((i, 0)) < order.index((i, 1)) for i in range(n))
    assert tracer.flush()
    (turn,) = [t for t in tracer.store.traces(limit=10_000)
               if t["root"] == "loop.turn" and any(
                   s["attrs"].get("admitted") == n for s in t["spans"])]
    spans = sorted(turn["spans"], key=lambda s: s["start"])
    sent = [s for s in spans if s["name"] in ("loop.prefill.dispatch",
                                              "loop.dispatch")]
    fetched = [s for s in spans if s["name"] == "loop.fetch"]
    assert [s["name"] for s in sent] == \
        ["loop.prefill.dispatch"] * n + ["loop.dispatch"]
    # the step before, then a first token an admission
    assert len(fetched) == 1 + n
    assert max(s["end"] for s in sent) <= min(s["start"] for s in fetched)
    step = next(s for s in spans if s["name"] == "loop.step")
    assert all(s["parent_id"] == step["span_id"] for s in fetched)
    root = next(s for s in spans if s["parent_id"] is None)
    assert (root["attrs"]["uploads"], root["attrs"]["programs"],
            root["attrs"]["fetches"]) == (1 + n, 1 + n, 1 + n)


def _events_then_nothing(handle, timeout=60):
    """The handle's events; after the terminal one its queue is empty and
    stays so."""
    evs = list(handle.events(timeout=timeout))
    time.sleep(0.05)
    assert handle._events.empty()
    assert evs[-1]["done"] and evs[-1]["count"] == len(evs) - 1 \
        == len(handle.tokens)
    assert [ev["index"] for ev in evs[:-1]] == list(range(len(evs) - 1))
    return evs


def test_an_eos_hit_ends_the_request_and_drops_one_row_step(lm, session):
    """The host learns of an eos a step late: the row's step in flight is
    thrown away, never emitted, and the refilled slot starts clean."""
    want = session.generate([[1, 2, 3]], 20)[0]
    at = next(i for i in range(2, 20) if want[i] not in want[:i])
    e = _engine(lm, slots=1)
    try:
        first = e.submit([1, 2, 3], max_tokens=20, eos_id=want[at])
        # waits for the one slot: refilled right after the retire
        second = e.submit([4, 5, 6, 7, 8], max_tokens=6)
        evs = _events_then_nothing(first)
        got = second.result(timeout=120)
        stats = e.stats()
    finally:
        e.shutdown()
    assert evs[-1]["reason"] == "completed" and evs[-1]["count"] == at + 1
    assert [ev["token"] for ev in evs[:-1]] == want[:at + 1]
    # no token of the old request in the slot's next stream
    assert got == session.generate([[4, 5, 6, 7, 8]], 6)[0]
    assert stats["dropped_row_steps"] == 1
    assert stats["decode_steps"] == at + 1 + 5  # one step was wasted


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_cancel_and_a_deadline_end_it_with_at_most_one_dropped_step(
        lm, session, how):
    e = _engine(lm, slots=2, step_hook=lambda: time.sleep(0.03))
    try:
        # the bucket's prefill and the step compiled: the clock is fair
        e.generate([4, 5, 6], max_tokens=2)
        other = e.submit([7, 5, 4], max_tokens=MAX_LEN)
        h = e.submit([1, 2, 3], max_tokens=MAX_LEN,
                     timeout=0.25 if how == "deadline" else None)
        if how == "cancel":
            _wait(lambda: len(h.tokens) >= 3)
            h.cancel()
        evs = _events_then_nothing(h)
        dropped = e.stats()["dropped_row_steps"]
        assert other.result(timeout=120) == \
            session.generate([[7, 5, 4]], MAX_LEN)[0]
    finally:
        e.shutdown()
    assert evs[-1]["reason"] == ("cancelled" if how == "cancel"
                                 else "deadline")
    count = evs[-1]["count"]
    assert 1 <= count < MAX_LEN - 3
    assert [ev["token"] for ev in evs[:-1]] == \
        session.generate([[1, 2, 3]], MAX_LEN)[0][:count]
    assert dropped <= 1


def test_slots_refilled_after_every_retire_never_show_the_old_tokens(
        lm, session):
    """One slot, requests that each end at an eos the host could not see
    coming: every refill starts from its own prefill's token."""
    plans = []
    for p in PROMPTS:
        full = session.generate([p], 12)[0]
        eos = next((t for j, t in enumerate(full) if j and t not in full[:j]),
                   full[-1])
        plans.append((p, eos, full[:full.index(eos) + 1]))
    e = _engine(lm, slots=1, queue_limit=16)
    try:
        hs = [e.submit(p, max_tokens=12, eos_id=eos) for p, eos, _ in plans]
        got = [h.result(timeout=300) for h in hs]
        stats = e.stats()
    finally:
        e.shutdown()
    assert got == [want for _, _, want in plans]
    assert [h.reason for h in hs] == ["completed"] * len(hs)
    assert stats["dropped_row_steps"] <= len(plans)


def test_the_last_tokens_arrive_when_the_engine_goes_idle(lm, session):
    """The step in flight is work: the loop lands it before it parks, a
    drain waits for it and a shutdown delivers it."""
    want = [session.generate([p], n)[0] for p, n in zip(PROMPTS, LENGTHS)]
    e = _engine(lm)
    try:
        assert e.submit(PROMPTS[0], max_tokens=LENGTHS[0]).result(
            timeout=60) == want[0]
        _wait(lambda: e._flight is None and not e._active.any())
        assert e.stats()["in_flight"] == 0
        # idle, then woken again: the token vector on the device is stale
        assert e.generate(PROMPTS[1], max_tokens=LENGTHS[1]) == want[1]
        hs = [e.submit(p, max_tokens=n) for p, n in zip(PROMPTS, LENGTHS)]
        assert e.drain(timeout=120)
        assert all(h.done for h in hs)
        assert [h.tokens for h in hs] == want
    finally:
        e.shutdown()
    e = _engine(lm)
    hs = [e.submit(p, max_tokens=n) for p, n in zip(PROMPTS, LENGTHS)]
    e.shutdown()  # drains first
    assert [h.tokens for h in hs] == want
    assert [h.reason for h in hs] == ["completed"] * len(hs)
    assert e._flight is None


class _Poisoned:
    """A step's tokens whose computation died on the device: the dispatch
    returned, the fetch raises."""

    def __init__(self, toks):
        self.toks = toks

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device halted")


@pytest.mark.parametrize("layout", ["static", "paged"])
def test_a_step_that_dies_at_run_time_fails_at_the_fetch(lm, layout):
    """PR 27's guarantees under run-ahead: a zeroed carry is in place before
    any caller hears of the failure, every active request ends `failed`,
    one rebuild is counted, the step dispatched from the dead one is thrown
    away with it, and the next request completes."""
    e = _engine(lm, **({"block_size": 4} if layout == "paged" else {}))
    real = e._decode_step_fn()
    mode = {"poison": False}
    heard = []

    def step(params, state, carry, prev, *rest):
        dead = isinstance(prev, _Poisoned)
        new, toks, counts = real(params, state, carry,
                                 prev.toks if dead else prev, *rest)
        if dead or mode["poison"]:
            mode["poison"] = False
            return new, _Poisoned(toks), counts
        return new, toks, counts

    try:
        want = e.submit([1, 2, 3], max_tokens=5).result(timeout=120)
        e._fns["decode"] = step
        hs = [e.submit([1, 2, 3], max_tokens=12),
              e.submit([4, 5], max_tokens=12)]
        for h in hs:
            h.add_done_callback(lambda h: heard.append(
                (e._carry_lost(), e._flight is None, e._active.any())))
        _wait(lambda: all(len(h.tokens) >= 3 for h in hs))
        mode["poison"] = True
        # a handle reads done before its callbacks have run: wait for them
        _wait(lambda: all(h.done for h in hs) and len(heard) == 2)
        assert [h.reason for h in hs] == ["failed", "failed"]
        assert all("device halted" in list(h.events())[-1]["error"]
                   for h in hs)
        assert heard == [(False, True, True), (False, True, False)]
        assert e.stats()["carry_rebuilds"] == 1 and not e._carry_lost()
        if layout == "paged":
            assert e._allocator.free_blocks == e._allocator.total_blocks
        e._breaker.record_success()
        assert e.submit([1, 2, 3], max_tokens=5).result(timeout=120) == want
        assert e.stats()["carry_rebuilds"] == 1
    finally:
        e.shutdown(drain=False)


def test_a_steady_batch_overlaps_and_a_speculative_engine_never_does(lm):
    reg = MetricsRegistry()
    e = _engine(lm, registry=reg, name="steady")
    try:
        hs = [e.submit(p, max_tokens=MAX_LEN - 4)
              for p in ([1, 2, 3], [4, 5, 6])]
        for h in hs:
            h.result(timeout=120)
        s = e.stats()
    finally:
        e.shutdown()
    assert s["decode_steps"] >= MAX_LEN - 6
    assert s["steps_ahead"] / s["decode_steps"] > 0.9
    assert s["dropped_row_steps"] == 0
    assert reg.get("dl4j_tpu_decode_steps_ahead_total").labels(
        "steady").value == s["steps_ahead"]
    assert reg.get("dl4j_tpu_decode_dropped_row_steps_total").labels(
        "steady").value == 0

    draft = TransformerLM(vocab_size=VOCAB, hidden=16, n_layers=1, n_heads=2,
                          max_len=MAX_LEN, seed=99).init()
    e = _engine(lm, draft_model=draft, speculative_k=2)
    try:
        # one request speculates, one takes the plain step in the same turn
        hs = [e.submit([1, 2, 3], max_tokens=MAX_LEN - 3),
              e.submit([4, 5], max_tokens=10, speculative_k=0)]
        got = [h.result(timeout=300) for h in hs]
        s = e.stats()
    finally:
        e.shutdown()
    sess = GenerationSession(lm, max_len=MAX_LEN)
    assert got == [sess.generate([[1, 2, 3]], MAX_LEN - 3)[0],
                   sess.generate([[4, 5]], 10)[0]]
    assert s["decode_steps"] > 0 and s["steps_ahead"] == 0
    assert s["dropped_row_steps"] == 0


def test_step_n_is_dispatched_before_step_n_minus_1_is_emitted(lm):
    """By the spans' start times: in a turn that ran ahead, `loop.upload`
    and `loop.dispatch` (this turn's step) come before `loop.fetch` and
    `loop.emit` (the step before's), and the turn says so (`ahead`)."""
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    e = _engine(lm, tracer=tracer)
    try:
        # the third is admitted under the first's decoding
        hs = [e.submit(p, max_tokens=n)
              for p, n in (([1, 2, 3], 12), ([4, 5, 6], 5), ([7, 8], 9))]
        for h in hs:
            h.result(timeout=120)
        steps_ahead = e.stats()["steps_ahead"]
    finally:
        e.shutdown()
    assert tracer.flush()
    ahead = landed_only = both = 0
    for t in tracer.store.traces(limit=10_000):
        step = next((s for s in t["spans"] if s["name"] == "loop.step"), None)
        if step is None:
            continue
        parts = sorted((s for s in t["spans"]
                        if s["parent_id"] == step["span_id"]),
                       key=lambda s: s["start"])
        names = [s["name"] for s in parts]
        admitted = sum(s["name"] == "loop.prefill" for s in t["spans"])
        # after the step before, this turn's prefills' first tokens, each
        firsts = ["loop.fetch", "loop.emit"] * admitted
        sent = ["loop.select", "loop.upload", "loop.dispatch",
                "loop.account"]
        if step["attrs"]["ahead"]:
            ahead += 1
            both += bool(admitted)
            assert names == sent + ["loop.fetch", "loop.emit"] + firsts
            assert parts[2]["end"] <= parts[5]["start"]
        elif "loop.dispatch" in names:  # a batch's first step: nothing to land
            assert names == sent + firsts
        else:  # nothing left to step: the turn only lands the last step
            landed_only += 1
            assert names == ["loop.select", "loop.fetch", "loop.emit"]
    assert ahead == steps_ahead >= 8 and landed_only >= 1 and both >= 1


@pytest.mark.parametrize("carry", ["static", "paged", "eva", "int8"])
def test_kv_entries_counters_follow_the_rows_positions(lm, eva, carry):
    """ISSUE 32: a dispatched step's rows attend position + 1 entries of a
    static plane and the decode kernel moves whole blocks up to there (a
    cache of 32 is one block); engines whose step reads no static plane
    through that kernel (paged, EVA's own state, an int8 cache) count
    nothing and give no ratio."""
    reg = MetricsRegistry()
    layout = {"paged": {"block_size": 4}, "int8": {"cache_dtype": "int8"}}
    model, max_len = (eva, EVA["max_len"]) if carry == "eva" else (lm, MAX_LEN)
    e = _engine(model, max_len=max_len, registry=reg, name="kv", slots=1,
                **layout.get(carry, {}))
    try:
        n, m = 5, 9
        assert len(e.submit(list(range(1, n + 1)), max_tokens=m)
                   .result(timeout=120)) == m
        s = e.stats()
    finally:
        e.shutdown()
    attended = reg.get("dl4j_tpu_decode_kv_entries_attended_total").labels(
        "kv").value
    fetched = reg.get("dl4j_tpu_decode_kv_entries_fetched_total").labels(
        "kv").value
    if carry != "static":
        assert (attended, fetched, s["kv_fetch_valid_share"]) == (0, 0, None)
        return
    # the prefill hands out token 0; step i stands at position n + i
    assert attended == sum(n + i + 1 for i in range(m - 1))
    assert fetched == (m - 1) * MAX_LEN
    assert s["kv_fetch_valid_share"] == pytest.approx(attended / fetched)
