"""The decode loop's own account of the device's queue (ISSUE 38).

Where a turn starts and ends, before each program it enqueues, when a fetch
has returned and through the emit loop, the loop asks the newest program it
enqueued whether it has finished (`DecodeEngine._device_dry`, the one seam);
the turn's other boundaries only mark the phase. The look
that first finds the queue empty opens an interval that the loop's next
enqueue closes: its length is a lower bound of the device's idle time, split
over the phases of the turn it spans; the time back to the look before is its
slack. A turn says both on its span, three counters sum them, `stats()["loop"]`
gives the shares. Always on, traced or not; an engine parked in `loop.wait`
adds nothing.
"""

import time

import pytest

from deeplearning4j_tpu.model.zoo import TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import TraceStore, Tracer
from deeplearning4j_tpu.parallel import decode
from deeplearning4j_tpu.parallel.decode import DecodeEngine, _DryAccount

MAX_LEN = 32
LOOP_S = "dl4j_tpu_decode_loop_seconds_total"
DRY_S = "dl4j_tpu_decode_device_dry_seconds_total"
SLACK_S = "dl4j_tpu_decode_device_dry_slack_seconds_total"


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=23, hidden=32, n_layers=2, n_heads=4,
                         max_len=MAX_LEN).init()


class _Scripted:
    """An engine as the account sees it: `_device_dry` answers from a
    script, one answer a question."""

    def __init__(self, answers):
        self.answers = list(answers)
        self.asked = 0

    def _device_dry(self):
        self.asked += 1
        return self.answers.pop(0)


def _clock(monkeypatch, times):
    times = iter(times)
    monkeypatch.setattr(decode, "_now", lambda: next(times))


def test_an_interval_runs_from_the_look_that_found_it_to_the_next_enqueue(
        monkeypatch):
    """Scripted clock and queue: the lower bound is t2 - t1, split where the
    phase changed; the slack is t1 - t0; a look inside an open interval asks
    nothing; a mark asks nothing ever and reads no clock while the device is
    busy; an interval open at the turn's end is counted up to there."""
    eng = _Scripted([False, True, True])
    acc = _DryAccount(eng, None)
    _clock(monkeypatch, [10, 10,    # begin, with its look: what follows is admit
                         13,        # look("dispatch"): found dry, slack 13 - 10
                         15,        # enqueued: dispatch 15 - 13
                         18,        # look("emit"): dry again, slack 18 - 15
                         19, 21,    # look(), mark("step"): emit 1 + 2
                         22,        # mark("sweep"): step 1
                         25])       # end: sweep 3
    acc.begin()
    acc.mark("step")      # busy: no clock, no question
    acc.mark("upload")
    assert acc.phase == "upload" and eng.asked == 1
    acc.look("dispatch")
    acc.enqueued("toks")
    assert acc.newest == "toks" and acc.t_dry is None
    acc.mark("fetch")
    acc.look("emit")
    acc.look()
    acc.mark("step")
    acc.mark("sweep")
    assert acc.end() == 15
    # in the order in which the phases first had a share
    assert list(acc.by_phase.items()) == [
        ("dispatch", 2), ("emit", 3), ("step", 1), ("sweep", 3)]
    assert acc.slack == 6
    assert eng.asked == 3 and not eng.answers


def test_a_call_that_enqueues_and_fetches_by_itself_ends_the_interval_before_it(
        monkeypatch):
    """A speculative step: the interval open before it ends where it starts
    (`enqueued()` with nothing), its own time is the device's, and its
    output, fetched, reads dry at the next look with no slack to speak of."""
    eng = _Scripted([True, True])
    acc = _DryAccount(eng, "old")
    _clock(monkeypatch, [0, 0, 2, 2, 9, 9.5, 12])
    acc.begin()            # dry from the start, in admit
    acc.look("fetch")      # t=2: admit 2
    acc.enqueued()         # t=2: the call starts; newest stays
    assert acc.newest == "old" and acc.t_dry is None
    acc.enqueued("toks")   # t=9: the call returned, its tokens on the host
    acc.look("emit")       # t=9.5: dry, slack 0.5
    assert acc.end() == 12
    assert acc.by_phase == {"admit": 2, "emit": 2.5} and acc.slack == 0.5
    assert acc.newest == "toks"


class _Poisoned:
    """A step's tokens whose computation died: no array at all."""


class _Raises:
    def is_ready(self):
        raise RuntimeError("device halted")


@pytest.mark.parametrize("tokens", [_Poisoned(), _Raises(), None])
def test_tokens_that_cannot_say_are_not_dry_and_raise_nothing(lm, tokens):
    e = DecodeEngine(lm, max_len=MAX_LEN, slots=2,
                     registry=MetricsRegistry())
    try:
        assert e.generate([1, 2, 3], max_tokens=3)
        time.sleep(0.1)  # parked: the loop thread is out of the account
        assert e._dry.newest is e._toks and e._device_dry() is True
        e._dry.newest = tokens
        assert e._device_dry() is False
    finally:
        e.shutdown(drain=False)


def _serve(lm, script=None, n_requests=5, max_tokens=9, **kw):
    """An engine whose every turn is traced; `script` stands in for the
    device's answer (`None`: the device's own)."""
    reg = MetricsRegistry()
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    e = DecodeEngine(lm, max_len=MAX_LEN, slots=2, tracer=tracer,
                     registry=reg, name="dry", **kw)
    if script is not None:
        e._device_dry = script
    try:
        hs = [e.submit([1 + i, 2, 3], max_tokens=max_tokens)
              for i in range(n_requests)]
        tokens = [h.result(timeout=120) for h in hs]
        time.sleep(0.1)  # the last turn's counters
        stats = e.stats()
    finally:
        e.shutdown()
    assert tracer.flush()
    turns = [next(s for s in t["spans"] if s["parent_id"] is None)
             for t in tracer.store.traces(limit=10_000)
             if t["root"] == "loop.turn"]
    dry = reg.get(DRY_S)
    return {"tokens": tokens, "stats": stats, "turns": turns,
            "loop_s": reg.get(LOOP_S).labels("dry").value,
            "slack_s": reg.get(SLACK_S).labels("dry").value,
            "dry_s": {labels[1]: c.value for labels, c in dry.items()
                      if c.value}}


def _phase_attrs(turn):
    return {k[4:-3]: v for k, v in turn["attrs"].items()
            if k.startswith("dry_") and k not in ("dry_ms", "dry_slack_ms")}


@pytest.mark.parametrize("layout", ["static", "paged"])
def test_a_device_that_is_never_dry_counts_nothing(lm, layout):
    out = _serve(lm, lambda: False,
                 **({"block_size": 4} if layout == "paged" else {}))
    assert out["turns"]
    for t in out["turns"]:
        assert t["attrs"]["dry_ms"] == 0 and t["attrs"]["dry_slack_ms"] == 0
        assert _phase_attrs(t) == {}
    assert out["dry_s"] == {} and out["slack_s"] == 0 and out["loop_s"] > 0
    loop = out["stats"]["loop"]
    assert loop["turn_seconds"] == out["loop_s"]
    assert (loop["dry_share"], loop["dry_slack_share"],
            loop["dry_by_phase"]) == (0.0, 0.0, {})


@pytest.mark.parametrize("layout", ["static", "paged"])
def test_a_device_that_is_always_dry_fills_the_turn(lm, layout):
    """Every look finds the queue empty: each moment of a turn is inside an
    interval or in the slack between an enqueue and the look after it, the
    spans say what the counters rose by, and the streams are unchanged."""
    real = _serve(lm, **({"block_size": 4} if layout == "paged" else {}))
    out = _serve(lm, lambda: True,
                 **({"block_size": 4} if layout == "paged" else {}))
    assert out["tokens"] == real["tokens"]
    sums, slack = {}, 0.0
    for t in out["turns"]:
        a, phases = t["attrs"], _phase_attrs(t)
        assert set(phases) <= set(decode._PHASES)
        assert a["dry_ms"] == pytest.approx(sum(phases.values()))
        assert 0 < a["dry_ms"] + a["dry_slack_ms"] <= t["duration_ms"]
        for k, v in phases.items():
            sums[k] = sums.get(k, 0.0) + v
        slack += a["dry_slack_ms"]
    assert {k: v * 1e-3 for k, v in sums.items()} == \
        pytest.approx(out["dry_s"])
    assert slack * 1e-3 == pytest.approx(out["slack_s"])
    # nothing of a turn is outside the account
    assert sum(out["dry_s"].values()) + out["slack_s"] == \
        pytest.approx(out["loop_s"])
    assert {"admit", "upload", "dispatch", "fetch", "emit", "sweep"} <= \
        set(out["dry_s"])
    loop = out["stats"]["loop"]
    assert loop["dry_share"] + loop["dry_slack_share"] == pytest.approx(1.0)
    assert sum(loop["dry_by_phase"].values()) == \
        pytest.approx(loop["dry_share"])
    # an enqueue is followed at once by the host's books: the slack is small
    assert loop["dry_share"] > 0.5


def test_the_shares_are_none_before_the_first_turn_and_a_wait_adds_nothing(lm):
    reg = MetricsRegistry()
    e = DecodeEngine(lm, max_len=MAX_LEN, slots=2, registry=reg, name="idle")
    try:
        assert e.stats()["loop"] == {
            "turn_seconds": 0.0, "dry_share": None, "dry_slack_share": None,
            "dry_by_phase": {}}
        t0 = time.perf_counter()
        e.generate([1, 2, 3], max_tokens=4)
        # parked in `loop.wait`: idle, not starved
        while e._flight is not None or e._active.any():
            time.sleep(0.005)
        time.sleep(0.05)
        before = (e.stats()["loop"], e._n_turns)
        time.sleep(0.4)
        assert (e.stats()["loop"], e._n_turns) == before
        e.generate([3, 2, 1], max_tokens=4)
        time.sleep(0.05)
        after = e.stats()["loop"]
        wall = time.perf_counter() - t0
    finally:
        e.shutdown()
    assert before[0]["turn_seconds"] < after["turn_seconds"] < wall - 0.4
    # the device's own answer, on a CPU: dry wherever the host is slower
    assert 0.0 <= after["dry_share"] <= 1.0
    assert after["dry_share"] + after["dry_slack_share"] <= 1.0 + 1e-9


def test_a_speculative_engine_reads_dry_from_its_fetch_to_its_next_dispatch(
        lm):
    """It fetches inside its step, so the device has nothing queued while
    the host emits, sweeps and admits: that is true of it, and the account
    says so with the device's own answers."""
    draft = TransformerLM(vocab_size=23, hidden=16, n_layers=1, n_heads=2,
                          max_len=MAX_LEN, seed=99).init()
    out = _serve(lm, n_requests=2, max_tokens=MAX_LEN - 4, draft_model=draft,
                 speculative_k=2)
    plain = _serve(lm, n_requests=2, max_tokens=MAX_LEN - 4)
    assert out["tokens"] == plain["tokens"]
    stepped = [t for t in out["turns"]
               if t["attrs"]["rows"] and not t["attrs"]["admitted"]]
    assert len(stepped) >= 5
    for t in stepped:
        phases = _phase_attrs(t)
        # dry while the host hands out the tokens and sweeps; the step's
        # own call is the device's time, whatever the queue said before it
        assert phases.get("emit", 0) > 0 and phases.get("sweep", 0) > 0
        assert phases.get("fetch", 0) < t["attrs"]["dry_ms"]
        assert t["attrs"]["dry_ms"] + t["attrs"]["dry_slack_ms"] <= \
            t["duration_ms"]
    assert out["stats"]["loop"]["dry_by_phase"]["emit"] > 0


def test_a_handed_over_rows_install_is_an_enqueue_of_the_account(
        lm, monkeypatch):
    from deeplearning4j_tpu.serving.disagg import PrefillEngine

    handoff = PrefillEngine(lm, max_len=MAX_LEN, registry=MetricsRegistry()) \
        .prefill([1, 2, 3], max_tokens=4)
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    e = DecodeEngine(lm, max_len=MAX_LEN, slots=2, tracer=tracer,
                     registry=MetricsRegistry())
    seen = []
    real = _DryAccount.enqueued
    monkeypatch.setattr(
        _DryAccount, "enqueued",
        lambda acc, out=None: (seen.append(out), real(acc, out))[1])
    e._device_dry = lambda: True
    try:
        want = e.generate([1, 2, 3], max_tokens=4)
        seen.clear()
        assert e.submit_prefilled(handoff).result(timeout=120) == want
        time.sleep(0.05)
        # the install's token write, then a step an emitted token but the
        # first: each the token vector as it stood, the last still the newest
        assert len(seen) == 4 and all(hasattr(t, "is_ready") for t in seen)
        assert e._dry.newest is e._toks is seen[-1]
    finally:
        e.shutdown()
    assert tracer.flush()
    roots = [next(s for s in t["spans"] if s["parent_id"] is None)["attrs"]
             for t in tracer.store.traces(limit=10_000)
             if t["root"] == "loop.turn"]
    handed = next(r for r in roots if r["admitted"] and r["programs"] == 3)
    # dry until the install was enqueued, inside `loop.admit`
    assert handed["dry_admit_ms"] > 0
