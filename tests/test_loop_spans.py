"""Loop-turn and training-step spans (ISSUE 26): ``DecodeEngine._loop`` and
``fit_batch`` as traces of the one ``Tracer``, every span mirrored into a
``jax.profiler`` session while one collects, self times in the store, the
compile listener, and the names the device side carries (module names and
``jax.named_scope``s)."""

import glob
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.model.zoo import TransformerLM
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import MetricsRegistry, get_registry
from deeplearning4j_tpu.obs.tracing import (TraceStore, Tracer, profiling,
                                            set_tracer)
from deeplearning4j_tpu.parallel import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_PARTS = ("loop.upload", "loop.dispatch", "loop.fetch", "loop.emit")
# a step that is dispatched: its rows chosen, its image sent, its program
# enqueued, the host's books kept (ISSUE 38 gave the first and the last a
# span of their own: they were `loop.step`'s own time)
SENT = ("loop.select", "loop.upload", "loop.dispatch", "loop.account")


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=23, hidden=32, n_layers=2, n_heads=4,
                         max_len=32).init()


def _serve(lm, tracer, n_requests=5, max_tokens=6):
    """More requests than the two slots, so that some wait in the queue."""
    reg = MetricsRegistry()
    eng = DecodeEngine(lm, max_len=32, slots=2, tracer=tracer, registry=reg)
    try:
        handles = [eng.submit([1 + i, 2, 3], max_tokens=max_tokens)
                   for i in range(n_requests)]
        tokens = [h.result(timeout=120) for h in handles]
        stats = eng.stats()
        steps = eng._h_decode.count
    finally:
        eng.shutdown()
    assert tracer.flush()
    return {"tokens": tokens, "stats": stats, "steps": steps,
            "traces": tracer.store.traces(limit=10_000)}


def _root(trace):
    return next(s for s in trace["spans"] if s["parent_id"] is None)


def _turns(traces):
    return sorted((t for t in traces if t["root"] == "loop.turn"),
                  key=lambda t: _root(t)["attrs"]["turn"])


@pytest.fixture(scope="module")
def served(lm):
    return _serve(lm, Tracer(TraceStore(max_traces=4096), sample_rate=1.0))


def test_turn_children_and_self_time_add_up_to_the_turn(served):
    turns = _turns(served["traces"])
    assert turns
    for t in turns:
        root = _root(t)
        direct = [s for s in t["spans"] if s["parent_id"] == root["span_id"]]
        assert {s["name"] for s in direct} <= {"loop.admit", "loop.step",
                                               "loop.sweep"}
        assert sum(s["duration_ms"] for s in direct) + root["self_ms"] == \
            pytest.approx(root["duration_ms"], abs=1e-3)
        for s in t["spans"]:
            assert 0.0 <= s["self_ms"] <= s["duration_ms"] + 1e-6
    stepped = [t for t in turns
               if any(s["name"] == "loop.step" for s in t["spans"])]
    assert stepped
    kinds = set()
    for t in stepped:
        step = next(s for s in t["spans"] if s["name"] == "loop.step")
        parts = [s for s in t["spans"] if s["parent_id"] == step["span_id"]]
        # this turn's step is dispatched, then what is in flight is landed
        # (ISSUE 30): the step before (a batch's first turn has none, its
        # last nothing to dispatch), then this turn's prefills' first
        # tokens, one an admission (ISSUE 37)
        names = tuple(s["name"] for s in parts)
        assert names[0] == "loop.select"
        sent = names[:4] == SENT
        landed = names[4:] if sent else names[1:]
        lands = len(landed) // 2
        assert landed == lands * STEP_PARTS[2:] and lands <= 3, names
        assert step["attrs"]["spec"] is False
        prefills = sum(s["name"] == "loop.prefill" for s in t["spans"])
        assert step["attrs"]["ahead"] == (sent and lands - prefills == 1)
        kinds.add((sent, lands))
    assert {(True, 1), (False, 1)} <= kinds


def test_turn_numbers_are_consecutive_and_count_the_work(served):
    roots = [_root(t) for t in _turns(served["traces"])]
    numbers = [r["attrs"]["turn"] for r in roots]
    assert numbers == list(range(1, len(numbers) + 1))
    assert sum(r["attrs"]["admitted"] for r in roots) == 5
    assert sum(r["attrs"]["retired"] for r in roots) == 5
    assert all(0 <= r["attrs"]["rows"] <= 2 for r in roots)
    assert roots[0]["attrs"]["pending"] >= 1
    assert {r["attrs"]["engine"] for r in roots} == {roots[0]["attrs"]["engine"]}


def test_every_prefill_has_its_request_number_and_queue_wait(served):
    prefills = [s for t in served["traces"] for s in t["spans"]
                if s["name"] == "loop.prefill"]
    assert sorted(s["attrs"]["req"] for s in prefills) == list(range(5))
    assert all(s["attrs"]["queue_wait_ms"] >= 0.0 for s in prefills)
    # two slots: the later requests waited for a whole request to finish
    assert max(s["attrs"]["queue_wait_ms"] for s in prefills) > \
        min(s["attrs"]["queue_wait_ms"] for s in prefills)
    for s in prefills:
        assert s["attrs"]["prompt_len"] == 3 and s["attrs"]["bucket"] >= 3
        assert s["attrs"]["slot"] in (0, 1)
    by_parent = {}
    for t in served["traces"]:
        for s in t["spans"]:
            by_parent.setdefault(s["parent_id"], []).append(s["name"])
    for s in prefills:
        kids = [n for n in by_parent[s["span_id"]] if n != "xla.compile"]
        # no sync: the first token stays on the device (ISSUE 30) and is
        # fetched under `loop.step`, once the turn's step is dispatched. No
        # install either (ISSUE 37): the prefill's own program writes the
        # row and the token, so a turn's second prefill waits for nothing
        assert kids == ["loop.prefill.dispatch"]


def test_a_handed_over_row_is_the_one_admission_with_an_install(lm):
    from deeplearning4j_tpu.serving.disagg import PrefillEngine

    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    handoff = PrefillEngine(lm, max_len=32, registry=MetricsRegistry()) \
        .prefill([1, 2, 3], max_tokens=4)
    eng = DecodeEngine(lm, max_len=32, slots=2, tracer=tracer,
                       registry=MetricsRegistry())
    try:
        want = eng.generate([1, 2, 3], max_tokens=4)
        assert eng.submit_prefilled(handoff).result(timeout=120) == want
    finally:
        eng.shutdown()
    assert tracer.flush()
    traces = tracer.store.traces(limit=10_000)
    kids = [[c["name"] for c in sorted(t["spans"], key=lambda c: c["start"])
             if c["parent_id"] == s["span_id"] and c["name"] != "xla.compile"]
            for t in traces for s in t["spans"] if s["name"] == "loop.prefill"]
    assert sorted(kids) == [["loop.prefill.dispatch"],
                            ["loop.prefill.dispatch", "loop.install"]]
    # a handed-over row: its leaves, its token and the slot uploaded, the
    # install and the token's write dispatched, beside the turn's step
    roots = [_root(t)["attrs"] for t in _turns(traces)]
    handed = next(r for r in roots if r["admitted"] and r["programs"] == 3)
    assert handed["uploads"] > 4


@pytest.mark.parametrize("layout", ["static", "paged"])
def test_a_turn_says_what_it_asked_of_the_device(lm, layout):
    """ISSUE 37: a step is one upload (the rows' packed image) and one
    program, an admitted prompt one of each more, a landed step or first
    token one fetch; a paged engine's step takes the block table up again
    when a row's block list changed. The registry's counter and `stats()` hold the
    turns' sums."""
    reg = MetricsRegistry()
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    eng = DecodeEngine(lm, max_len=32, slots=2, tracer=tracer, registry=reg,
                       name="calls",
                       **({"block_size": 4} if layout == "paged" else {}))
    try:
        handles = [eng.submit([1 + i, 2, 3], max_tokens=9) for i in range(5)]
        for h in handles:
            h.result(timeout=120)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert tracer.flush()
    sums = dict.fromkeys(("uploads", "programs", "fetches"), 0)
    kinds = set()
    for t in _turns(tracer.store.traces(limit=10_000)):
        attrs = _root(t)["attrs"]
        names = [s["name"] for s in t["spans"]]
        stepped, admitted = names.count("loop.dispatch"), attrs["admitted"]
        assert attrs["programs"] == stepped + admitted
        assert attrs["fetches"] == names.count("loop.fetch")
        # the block table again, once a turn at most and with the step that
        # takes it, however many rows grew, came or went since the last
        tables = attrs["uploads"] - attrs["programs"]
        assert 0 <= tables <= (stepped if layout == "paged" else 0)
        if stepped and admitted <= 1:
            assert attrs["programs"] == 1 + admitted
            assert attrs["fetches"] <= 1 + admitted
        kinds.add((stepped, admitted))
        for k in sums:
            sums[k] += attrs[k]
    assert {(1, 0), (1, 1)} <= kinds
    counter = reg.get("dl4j_tpu_decode_device_calls_total")
    assert {k + ("es" if k == "fetch" else "s"):
            counter.labels("calls", k).value
            for k in ("upload", "program", "fetch")} == sums
    assert stats["device_calls"] == {
        "upload": sums["uploads"], "program": sums["programs"],
        "fetch": sums["fetches"]}
    assert sums["programs"] == stats["decode_steps"] + 5


def test_decode_histogram_counts_the_step_spans(served):
    """One observation a step, made where its tokens reach the host: as
    many as were dispatched, and as were fetched; a `loop.step` more for
    every turn that only landed a batch's last step."""
    count = {name: sum(s["name"] == name for t in served["traces"]
                       for s in t["spans"])
             for name in ("loop.step",) + STEP_PARTS}
    assert count["loop.dispatch"] == count["loop.upload"] == \
        served["steps"] > 0
    # a fetch and an emit for each step, and for each prefill's first token
    assert count["loop.fetch"] == count["loop.emit"] == served["steps"] + 5
    assert served["steps"] < count["loop.step"] <= 2 * served["steps"]
    assert served["stats"]["decode_steps"] == served["steps"]
    assert 0 < served["stats"]["steps_ahead"] < served["steps"]
    assert all(t["root"] in ("loop.turn", "loop.wait")
               for t in served["traces"])


def test_children_of_an_unsampled_turn_never_root_a_trace(lm):
    out = _serve(lm, Tracer(TraceStore(max_traces=4096), sample_rate=0.5),
                 n_requests=6, max_tokens=8)
    assert {t["root"] for t in out["traces"]} <= {"loop.turn", "loop.wait"}
    numbers = [_root(t)["attrs"]["turn"] for t in _turns(out["traces"])]
    assert len(numbers) < max(numbers)  # some turns were not sampled
    none = _serve(lm, Tracer(sample_rate=0.0))
    assert none["traces"] == []


def test_disabled_tracer_stores_nothing_and_serves_the_same(lm, served):
    tracer = Tracer(enabled=False)
    off = _serve(lm, tracer)
    assert len(tracer.store) == 0 and tracer.store.span_count() == 0
    assert off["tokens"] == served["tokens"]
    # a latency and the loop's clock readings: the keys that may differ
    timing = {"per_token_p95_s", "loop"}
    assert {k: v for k, v in off["stats"].items() if k not in timing} == \
        {k: v for k, v in served["stats"].items() if k not in timing}
    assert off["steps"] == served["steps"]


def test_a_profiler_session_takes_every_turn_and_mirrors_the_spans(
        lm, tmp_path):
    assert profiling() is False
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiling() is True
        out = _serve(lm, tracer, n_requests=3, max_tokens=4)
        with tracer.span("manager.deploy", parent=None,
                         attrs={"model": "m", "blob": object()}):
            pass
    finally:
        jax.profiler.stop_trace()
    assert profiling() is False
    roots = [_root(t) for t in _turns(out["traces"])]
    numbers = [r["attrs"]["turn"] for r in roots]
    assert numbers == list(range(1, len(numbers) + 1))
    assert all(r["attrs"]["profiled"] is True for r in roots)
    # off the profiler the same tracer samples nothing again
    assert tracer.span("x", parent=None).context is None

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("loop.", "manager.")):
                        names.setdefault(ev.name, []).append(dict(ev.stats))
    n_fetches = sum(s["name"] == "loop.fetch"
                    for t in out["traces"] for s in t["spans"])
    assert len(names["loop.fetch"]) == n_fetches > 0
    assert {"loop.turn", "loop.admit", "loop.prefill", "loop.step",
            "loop.select", "loop.upload", "loop.dispatch", "loop.account",
            "loop.emit", "loop.sweep"} <= set(names)
    assert sorted(e["turn"] for e in names["loop.turn"]) == numbers
    # every span of the repo is mirrored, scalar attributes only
    assert names["manager.deploy"] == [{"model": "m", "profiled": True}]


def test_a_root_that_outlives_the_session_is_not_profiled(tmp_path):
    tracer = Tracer(sample_rate=0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("inside", parent=None):
            pass
        straddles = tracer.span("straddles", parent=None)
        straddles.__enter__()
    finally:
        jax.profiler.stop_trace()
    straddles.__exit__(None, None, None)
    assert tracer.flush()
    attrs = {t["root"]: _root(t)["attrs"] for t in tracer.store.traces()}
    assert attrs == {"inside": {"profiled": True}, "straddles": {}}


def test_a_sessions_slice_outlives_the_sampled_traces_that_follow_it():
    """The store is bounded, and the traces a profiler session took go last:
    a fast loop's head-sampled turns after the slice must not push the
    slice out before its reader comes (ISSUE 37: a hundred turns a second
    fill the default store in half a minute)."""
    def root(i, **attrs):
        return {"trace_id": f"t{i}", "span_id": f"s{i}", "parent_id": None,
                "name": "loop.turn", "start": float(i), "end": i + 0.5,
                "duration_ms": 500.0, "error": False,
                "attrs": dict(attrs, turn=i)}

    store = TraceStore(max_traces=4)
    store.add(root(0))
    for i in (1, 2, 3):
        store.add(root(i, profiled=True))
    for i in range(4, 10):
        store.add(root(i))
    kept = sorted(_root(t)["attrs"]["turn"] for t in store.traces())
    assert kept == [1, 2, 3, 9] and store.evicted_traces == 6
    # nothing else left to go: the oldest of the slice goes
    for i in (10, 11):
        store.add(root(i, profiled=True))
    kept = sorted(_root(t)["attrs"]["turn"] for t in store.traces())
    assert kept == [2, 3, 10, 11] and len(store) == 4


def test_a_traced_request_gets_its_queue_wait_record(lm):
    tracer = Tracer(TraceStore(max_traces=4096), sample_rate=1.0)
    eng = DecodeEngine(lm, max_len=32, slots=1, tracer=tracer,
                       registry=MetricsRegistry())
    try:
        with tracer.span("request", parent=None) as req:
            handle = eng.submit([1, 2, 3], max_tokens=3)
        handle.result(timeout=120)
    finally:
        eng.shutdown()
    assert tracer.flush()
    spans = {s["name"]: s for s in tracer.store.get(req.trace_id)["spans"]}
    assert {"engine.queue_wait", "engine.prefill", "engine.decode"} <= \
        set(spans)
    wait = spans["engine.queue_wait"]
    assert wait["parent_id"] == req.span_id
    assert wait["end"] <= spans["engine.prefill"]["start"]
    assert wait["start"] >= spans["request"]["start"]


def test_self_time_takes_the_union_of_overlapping_children():
    def span(sid, parent, start, end):
        return {"trace_id": "t", "span_id": sid, "parent_id": parent,
                "name": sid, "start": start, "end": end,
                "duration_ms": (end - start) * 1e3, "error": False,
                "attrs": {}}

    store = TraceStore()
    for s in (span("root", None, 0.0, 1.0), span("a", "root", 0.1, 0.5),
              span("b", "root", 0.3, 0.6),      # overlaps a: union 0.1-0.6
              span("c", "root", 0.2, 0.4),      # inside the union
              span("d", "root", 0.9, 1.2),      # runs past its parent
              span("a1", "a", 0.1, 0.2)):
        store.add(s)
    got = {s["name"]: s["self_ms"] for s in store.get("t")["spans"]}
    assert got["root"] == pytest.approx(400.0)   # 1.0 - 0.5 - 0.1
    assert got["a"] == pytest.approx(300.0)
    assert got["b"] == pytest.approx(300.0) and got["a1"] == pytest.approx(100.0)
    # the stored records are not rewritten by reading them
    assert "self_ms" not in store._traces["t"]["spans"][0]


def _mln(seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=7):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(seed).graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_in=4, n_out=8), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=3), "d")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _batch(n=8):
    rng = np.random.RandomState(0)
    return (rng.randn(n, 4).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)])


def _fit_three(kind):
    x, y = _batch()
    if kind == "solver":
        from deeplearning4j_tpu.train.solver import Solver

        solver = Solver(_mln())
        return [solver.fit_batch(x, y)[0] for _ in range(3)]
    if kind == "graph_solver":
        from deeplearning4j_tpu.train.graph_solver import GraphSolver

        solver = GraphSolver(_graph())
        return [solver.fit_batch((x,), (y,)) for _ in range(3)]
    from deeplearning4j_tpu.parallel import DistributedTrainer, make_mesh

    trainer = DistributedTrainer(_mln(), mesh=make_mesh(data=-1))
    return [trainer.fit_batch(x, y) for _ in range(3)]


@pytest.mark.parametrize("kind", ["solver", "graph_solver", "trainer"])
def test_fit_batch_is_a_fit_step_trace(kind):
    tracer = Tracer(sample_rate=1.0)
    prev = set_tracer(tracer)
    try:
        scores = _fit_three(kind)
    finally:
        set_tracer(prev)
    assert all(np.isfinite(float(s)) for s in scores)
    assert tracer.flush()
    traces = sorted(tracer.store.traces(),
                    key=lambda t: _root(t)["attrs"]["step"])
    assert [t["root"] for t in traces] == ["fit.step"] * 3
    assert [_root(t)["attrs"]["step"] for t in traces] == [1, 2, 3]
    for t in traces:
        root = _root(t)
        assert root["attrs"]["batch"] == 8
        # (a small program compiled between the phases, a key split say,
        # is an xla.compile child of the root itself)
        kids = [s for s in t["spans"] if s["parent_id"] == root["span_id"]
                and s["name"] != "xla.compile"]
        assert [s["name"] for s in kids] == ["fit.h2d", "fit.dispatch",
                                             "fit.host"]
        assert sum(s["duration_ms"] for s in kids) + root["self_ms"] <= \
            root["duration_ms"] + 1e-3
    # the first step compiled under its dispatch span; the others did not
    compiles = [[s["parent_id"] for s in t["spans"]
                 if s["name"] == "xla.compile"] for t in traces]
    dispatch = next(s for s in traces[0]["spans"]
                    if s["name"] == "fit.dispatch")
    assert dispatch["span_id"] in compiles[0] and compiles[2] == []


@pytest.mark.parametrize("kind", ["solver", "graph_solver", "distributed"])
def test_fit_batch_never_fences_the_device(kind, monkeypatch):
    # the train cell runs two steps ahead of the device: nothing inside a
    # step may wait for it, and the score comes back as a device array
    def fence(*_a, **_k):
        raise AssertionError("fit_batch waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", fence)
    scores = _fit_three(kind)
    assert len(scores) == 3
    assert all(isinstance(s, jax.Array) for s in scores)


@pytest.mark.parametrize("kind", ["solver", "graph_solver"])
def test_solvers_take_no_profiler(kind):
    # the per-phase step timer went in PR 31: a stale caller fails loudly
    if kind == "solver":
        from deeplearning4j_tpu.train.solver import Solver as cls

        model = _mln()
    else:
        from deeplearning4j_tpu.train.graph_solver import GraphSolver as cls

        model = _graph()
    stale = {"profiler": object()}
    with pytest.raises(TypeError, match="profiler"):
        cls(model, **stale)
    assert not hasattr(cls(model), "profiler")


def test_compiles_are_counted_where_they_happen():
    from deeplearning4j_tpu.obs.compiles import watch_compiles

    watch_compiles()
    watch_compiles()  # once for the process, however often called
    listeners = jax.monitoring.get_event_duration_listeners() \
        if hasattr(jax.monitoring, "get_event_duration_listeners") else \
        jax._src.monitoring.get_event_duration_listeners()
    assert sum(getattr(cb, "__module__", "") ==
               "deeplearning4j_tpu.obs.compiles" for cb in listeners) == 1
    reg = get_registry()
    count = reg.counter("dl4j_tpu_xla_compiles_total")
    seconds = reg.counter("dl4j_tpu_xla_compile_seconds_total")
    n0, s0 = count.value, seconds.value
    tracer = Tracer(sample_rate=1.0)
    fn = jax.jit(lambda a: a * 3 + 1)
    x = np.ones((7, 3), np.float32)
    with tracer.span("caller", parent=None) as caller:
        fn(x).block_until_ready()
        n1 = count.value
        fn(x).block_until_ready()  # compiled already
    assert n1 == n0 + 1 and count.value == n1 and seconds.value > s0
    assert tracer.flush()
    spans = tracer.store.get(caller.trace_id)["spans"]
    compiled = [s for s in spans if s["name"] == "xla.compile"]
    assert len(compiled) == 1 and compiled[0]["parent_id"] == caller.span_id
    assert compiled[0]["start"] >= spans[0]["start"]
    fn2 = jax.jit(lambda a: a * 5)
    fn2(np.ones((3,), np.float32)).block_until_ready()  # no span: counted only
    assert count.value == n1 + 1


def test_decode_programs_carry_their_names_and_scopes(lm):
    eng = DecodeEngine(lm, max_len=32, slots=2, registry=MetricsRegistry(),
                       tracer=Tracer(enabled=False))
    try:
        sess = eng.session
        lowered = eng._decode_step_fn().lower(
            sess.model.params, sess.model.state, eng._carry,
            *eng._step_args(eng._active))
        text = lowered.as_text(debug_info=True)
        assert "module @jit_decode_step" in text
        layer = sess.model.conf.layer_name(1)
        for scope in ("forward", "logits", "sample", "freeze_rows",
                      f"forward/{layer}"):
            assert f"jit(decode_step)/{scope}" in text, scope
        hlo = lowered.compile().as_text()
        assert f"jit(decode_step)/forward/{layer}" in hlo
        assert eng._prefill_fn(8).__name__ == "prefill_8"
        assert eng._write_row_fn().__name__ == "install_row"
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kind", ["solver", "graph_solver", "trainer"])
def test_train_steps_carry_their_name_and_scopes(kind):
    x, y = _batch()
    rng = jax.random.PRNGKey(0)
    if kind == "solver":
        from deeplearning4j_tpu.train.solver import Solver

        s = Solver(_mln())
        lowered = s._step_fn(False, False, False).lower(
            s.model.params, s.opt_state, s.model.state, {}, x, y, rng,
            None, None)
        layer = s.model.conf.layer_name(0)
    elif kind == "graph_solver":
        from deeplearning4j_tpu.train.graph_solver import GraphSolver

        s = GraphSolver(_graph())
        lowered = s._step_fn(1, 1).lower(
            s.model.params, s.opt_state, s.model.state, (x,), (y,), rng)
        layer = "d"
    else:
        from deeplearning4j_tpu.parallel import DistributedTrainer, make_mesh

        s = DistributedTrainer(_mln(), mesh=make_mesh(data=-1))
        lowered = s._build_step().lower(
            s.params, s.opt_state, s.state, s.strat_state, x, y, rng,
            jnp.asarray(1, jnp.int32))
        layer = s.model.conf.layer_name(0)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_train_step" in text
    out = "out" if kind == "graph_solver" else s.model.conf.layer_name(1)
    for scope in ("loss_and_grad", "optimizer", f"loss_and_grad/jvp({layer})",
                  f"loss_and_grad/transpose(jvp({layer}))",
                  f"loss_and_grad/jvp({out})"):
        assert f"jit(train_step)/{scope}" in text, scope


def _host_gaps():
    spec = importlib.util.spec_from_file_location(
        "host_gaps", os.path.join(ROOT, "tools", "host_gaps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_host_gaps_splits_each_gap_over_the_innermost_spans():
    hg = _host_gaps()
    ms = 1e6  # ns
    ops = [("a", 0, 10 * ms), ("b", 10 * ms, 20 * ms),       # no gap
           ("c", 30 * ms, 40 * ms),                          # 10 ms after b
           ("w", 30 * ms, 60 * ms), ("d", 45 * ms, 50 * ms),  # inside w
           ("e", 75 * ms, 80 * ms)]                          # 15 ms after w
    spans = [("loop.turn", 0, 70 * ms), ("loop.step", 5 * ms, 65 * ms),
             ("loop.fetch", 15 * ms, 28 * ms), ("loop.emit", 28 * ms, 31 * ms),
             ("loop.fetch", 41 * ms, 62 * ms)]
    assert hg.idle_gaps(ops) == [(20 * ms, 30 * ms), (60 * ms, 75 * ms)]
    assert [seg[2] for seg in hg.timeline(spans)] == [
        "(no span)", "loop.turn", "loop.step", "loop.fetch", "loop.emit",
        "loop.step", "loop.fetch", "loop.step", "loop.turn", "(no span)"]
    got = hg.by_span(spans, ops)
    # gap 20-30: fetch to 28, emit to 30; gap 60-75: fetch to 62, step to
    # 65, turn to 70, nothing open to 75. Both began under a fetch.
    assert {k: [round(x, 6) for x in v] for k, v in got.items()} == {
        "loop.fetch": [0.010, 0.025], "loop.emit": [0.002, 0.0],
        "loop.step": [0.003, 0.0], "loop.turn": [0.005, 0.0],
        "(no span)": [0.005, 0.0]}
    assert sum(v[0] for v in got.values()) == pytest.approx(0.025)
    offs = hg.fetch_offsets(spans, ops, n=5)
    assert offs == [pytest.approx(2.0)]   # 62 - 60, the second fetch
