"""Distributed tracing unit coverage (obs/tracing.py): W3C traceparent
codec, contextvar span nesting incl. exception paths and thread isolation,
TraceStore bounds/filters, sampling, and the ModelManager deploy/rollback
span instrumentation. The cross-process propagation contract lives in
tools/check_trace_contract.py (tier-1 via test_trace_contract.py)."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import MetricsRegistry
from deeplearning4j_tpu.obs.tracing import (
    NULL_SPAN,
    TraceContext,
    TraceStore,
    Tracer,
    current_context,
    current_span,
    decode_traceparent,
    encode_traceparent,
    get_tracer,
    set_tracer,
    trace_now,
)
from deeplearning4j_tpu.serving import ModelManager, ModelStore


# ---------------------------------------------------------------------------
# traceparent codec
# ---------------------------------------------------------------------------
def test_traceparent_roundtrip():
    ctx = TraceContext("0af7651916cd43dd8448eb211c80319c",
                       "b7ad6b7169203331", sampled=True)
    hdr = encode_traceparent(ctx)
    assert hdr == ("00-0af7651916cd43dd8448eb211c80319c-"
                   "b7ad6b7169203331-01")
    back = decode_traceparent(hdr)
    assert back == ctx
    # unsampled flag survives
    off = TraceContext(ctx.trace_id, ctx.span_id, sampled=False)
    assert decode_traceparent(encode_traceparent(off)).sampled is False


@pytest.mark.parametrize("bad", [
    None,
    "",
    "garbage",
    "00-short-b7ad6b7169203331-01",
    "00-0af7651916cd43dd8448eb211c80319c-short-01",
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # ff version
    "00-00000000000000000000000000000000-b7ad6b7169203331-01",  # zero trace
    "00-0af7651916cd43dd8448eb211c80319c-0000000000000000-01",  # zero span
    "00-0af7651916cd43dd8448eb211c80319g-b7ad6b7169203331-01",  # non-hex
])
def test_traceparent_malformed_is_none(bad):
    assert decode_traceparent(bad) is None


def test_traceparent_future_version_accepted():
    hdr = "01-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-extra"
    ctx = decode_traceparent(hdr)
    assert ctx is not None and ctx.sampled


# ---------------------------------------------------------------------------
# span nesting / exception paths (satellite: thread- and contextvar-safety)
# ---------------------------------------------------------------------------
def test_span_nesting_and_restore():
    t = Tracer(TraceStore())
    assert current_span() is None
    with t.span("outer") as outer:
        assert current_span() is outer
        with t.span("inner") as inner:
            assert current_span() is inner
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        assert current_span() is outer
    assert current_span() is None
    assert t.flush()
    trace = t.store.traces()[0]
    assert trace["span_count"] == 2
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None


def test_span_body_raises_still_closes_records_error_restores_current():
    t = Tracer(TraceStore())
    with t.span("outer") as outer:
        with pytest.raises(ValueError):
            with t.span("boom") as boom:
                raise ValueError("nope")
        # previous current-span restored even though the body raised
        assert current_span() is outer
        assert boom.error is True
        assert boom.end_time is not None
        assert boom.attributes["exception"] == "ValueError"
    assert current_span() is None
    assert t.flush()
    spans = {s["name"]: s for s in t.store.traces()[0]["spans"]}
    assert spans["boom"]["error"] is True
    assert spans["outer"]["error"] is False


def test_span_threads_do_not_interfere():
    """Contextvars are per-thread: concurrent spans in different threads
    each see their own current-span stack, and exceptions in one thread
    never corrupt another's."""
    t = Tracer(TraceStore())
    barrier = threading.Barrier(4)
    errors = []

    def worker(i):
        try:
            assert current_span() is None
            with t.span(f"root-{i}") as root:
                barrier.wait(timeout=10)
                assert current_span() is root
                try:
                    with t.span(f"child-{i}"):
                        raise RuntimeError("thread-local failure")
                except RuntimeError:
                    pass
                assert current_span() is root
            assert current_span() is None
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=10)
    assert not errors
    assert t.flush()
    traces = t.store.traces()
    assert len(traces) == 4  # one independent trace per thread
    for tr in traces:
        assert tr["span_count"] == 2
        root = [s for s in tr["spans"] if s["parent_id"] is None]
        assert len(root) == 1


def test_span_finish_idempotent_and_attrs():
    t = Tracer(TraceStore())
    span = t.span("manual", attrs={"a": 1})
    span.set_attribute("b", "two")
    span.finish()
    span.finish()  # second finish is a no-op, not a duplicate export
    assert t.flush()
    assert t.store.span_count() == 1
    rec = t.store.traces()[0]["spans"][0]
    assert rec["attrs"] == {"a": 1, "b": "two"}
    assert rec["end"] >= rec["start"]


def test_record_span_cross_thread_parenting():
    t = Tracer(TraceStore())
    with t.span("handler") as handler:
        ctx = handler.context
    t0 = trace_now()
    t.record_span("worker.op", parent=ctx, start_time=t0,
                  end_time=t0 + 0.25, attrs={"k": "v"}, error=True)
    assert t.flush()
    trace = t.store.traces()[0]
    by_name = {s["name"]: s for s in trace["spans"]}
    rec = by_name["worker.op"]
    assert rec["parent_id"] == ctx.span_id
    assert rec["trace_id"] == ctx.trace_id
    assert rec["error"] is True
    assert abs(rec["duration_ms"] - 250.0) < 1e-6


# ---------------------------------------------------------------------------
# CPU time beside wall time (ISSUE 38)
# ---------------------------------------------------------------------------
def _only_span(tracer, name):
    assert tracer.flush()
    (span,) = [s for t in tracer.store.traces() for s in t["spans"]
               if s["name"] == name]
    return span


@pytest.mark.parametrize("body", ["busy", "sleeping"])
def test_a_span_carries_what_its_thread_ran_beside_what_it_took(body):
    """`cpu_ms` is the entering thread's CPU clock over the span: all of a
    busy span (never more than its wall time), next to none of one that
    waits. `duration_ms - cpu_ms` is what the thread did not run."""
    t = Tracer(TraceStore())
    with t.span(body):
        if body == "busy":
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
        else:
            time.sleep(0.05)
    rec = _only_span(t, body)
    assert rec["thread"] == threading.get_ident()
    assert 0.0 <= rec["cpu_ms"] <= rec["duration_ms"]
    assert rec["duration_ms"] >= 50.0
    if body == "busy":  # a preempted thread ran less than it took: half
        assert rec["cpu_ms"] > 25.0
    else:
        assert rec["cpu_ms"] < 10.0
    assert rec["self_cpu_ms"] == rec["cpu_ms"]  # no child took any of it


def test_self_cpu_is_cpu_less_the_children_on_the_same_thread():
    t = Tracer(TraceStore())
    with t.span("parent") as parent:
        with t.span("child"):
            end = time.perf_counter() + 0.03
            while time.perf_counter() < end:
                pass
        # another thread's child, timed by that thread's clock: none of it
        # is the parent's thread's
        th = threading.Thread(target=lambda: t.span(
            "elsewhere", parent=parent).__enter__().__exit__(None, None, None))
        th.start()
        th.join(timeout=10)
        t.record_span("measured", parent=parent, start_time=trace_now(),
                      end_time=trace_now() + 0.01)
    assert t.flush()
    spans = {s["name"]: s for s in t.store.get(parent.trace_id)["spans"]}
    assert spans["parent"]["self_cpu_ms"] == pytest.approx(
        spans["parent"]["cpu_ms"] - spans["child"]["cpu_ms"], abs=1e-5)
    assert spans["elsewhere"]["thread"] != spans["parent"]["thread"]
    assert 0.0 <= spans["parent"]["self_cpu_ms"] < spans["parent"]["cpu_ms"]
    # the stored records are not rewritten by reading them
    assert all("self_cpu_ms" not in s
               for s in t.store._traces[parent.trace_id]["spans"])


@pytest.mark.parametrize("how", ["record_span", "finished_only",
                                 "finished_on_another_thread"])
def test_spans_that_no_one_thread_timed_carry_no_cpu_time(how):
    t = Tracer(TraceStore())
    with t.span("root") as root:
        if how == "record_span":
            t.record_span("x", parent=root, start_time=trace_now(),
                          end_time=trace_now() + 0.01)
        elif how == "finished_only":
            t.span("x").finish()
        else:
            with t.span("x") as span:
                th = threading.Thread(target=span.finish)
                th.start()
                th.join(timeout=10)
                assert not th.is_alive()
    rec = _only_span(t, "x")
    assert rec["duration_ms"] >= 0.0 and rec["self_ms"] >= 0.0
    assert not {"cpu_ms", "thread", "self_cpu_ms"} & set(rec)
    assert "cpu_ms" in _only_span(t, "root")


def test_a_span_that_is_not_taken_reads_no_clock(monkeypatch):
    """Disabled and unsampled spans stay `NULL_SPAN`: no CPU clock, no wall
    clock, nothing stored."""
    def clock():
        raise AssertionError("an untaken span read a clock")

    for tracer in (Tracer(TraceStore(), enabled=False),
                   Tracer(TraceStore(), sample_rate=0.0)):
        monkeypatch.setattr(time, "thread_time", clock)
        monkeypatch.setattr(time, "perf_counter", clock)
        try:
            with tracer.span("root") as root:
                with tracer.span("child") as child:
                    pass
        finally:
            monkeypatch.undo()
        assert root is NULL_SPAN and child is NULL_SPAN
        assert len(tracer.store) == 0


# ---------------------------------------------------------------------------
# tracer policy: disabled / sampling
# ---------------------------------------------------------------------------
def test_disabled_tracer_is_null_and_stores_nothing():
    t = Tracer(TraceStore(), enabled=False)
    span = t.span("x")
    assert span is NULL_SPAN
    assert span.context is None
    with span:
        assert current_span() is None  # null spans never become current
        span.set_attribute("ignored", 1)
    t.record_span("y", parent=TraceContext("a" * 32, "b" * 16),
                  start_time=0.0, end_time=1.0)
    assert len(t.store) == 0


def test_unsampled_trace_takes_the_null_path():
    """Head-based sampling: an unsampled root is the SAME zero-cost null
    span as disabled tracing — no ids, no header to inject, no children
    recorded anywhere downstream."""
    t = Tracer(TraceStore(), sample_rate=0.0)
    with t.span("root") as root:
        assert root is NULL_SPAN
        assert root.context is None  # nothing to inject into traceparent
        with t.span("child") as child:
            assert child is NULL_SPAN
    assert len(t.store) == 0
    # an explicitly-unsampled REMOTE parent (traceparent flag 00) is
    # honored: no local recording either
    off_ctx = decode_traceparent("00-" + "a" * 32 + "-" + "b" * 16 + "-00")
    assert t.span("server", parent=off_ctx) is NULL_SPAN


def test_sample_rate_validation():
    with pytest.raises(ValueError):
        Tracer(sample_rate=1.5)


def test_set_tracer_roundtrip():
    mine = Tracer(TraceStore())
    prev = set_tracer(mine)
    try:
        assert get_tracer() is mine
    finally:
        set_tracer(prev)
    assert get_tracer() is prev


# ---------------------------------------------------------------------------
# store bounds / filters
# ---------------------------------------------------------------------------
def test_trace_store_bounds_and_eviction():
    store = TraceStore(max_traces=3, max_spans_per_trace=2)
    t = Tracer(store)
    for i in range(5):
        with t.span(f"root-{i}"):
            with t.span("c1"):
                pass
            with t.span("c2"):  # third span exceeds the per-trace cap
                pass
    assert t.flush()
    assert len(store) == 3
    assert store.evicted_traces == 2
    assert store.span_count() <= 3 * 2
    assert store.dropped_spans >= 1
    for tr in store.traces():
        assert tr["span_count"] <= 2


def test_trace_store_filters():
    store = TraceStore()
    t = Tracer(store)
    with t.span("slow", attrs={"route": "/a"}) as s:
        pass
    # synthesize a known-long trace (not sleep-based)
    t.record_span("long", parent=s.context, start_time=s.start_time,
                  end_time=s.start_time + 2.0)
    with t.span("fast", attrs={"route": "/b"}):
        pass
    assert t.flush()
    all_traces = store.traces()
    assert len(all_traces) == 2
    assert all_traces[0]["root"] == "fast"  # newest first
    long_only = store.traces(min_duration_ms=1000.0)
    assert len(long_only) == 1 and long_only[0]["routes"] == ["/a"]
    route_b = store.traces(route="/b")
    assert len(route_b) == 1 and route_b[0]["root"] == "fast"
    assert store.traces(route="/nope") == []
    assert len(store.traces(limit=1)) == 1


def test_trace_store_get_and_clear():
    store = TraceStore()
    t = Tracer(store)
    with t.span("a") as a:
        pass
    assert t.flush()
    assert store.get(a.trace_id)["root"] == "a"
    assert store.get("f" * 32) is None
    store.clear()
    assert len(store) == 0


# ---------------------------------------------------------------------------
# ModelManager deploy/rollback spans
# ---------------------------------------------------------------------------
def _model(seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    return MultiLayerNetwork(conf).init()


def test_manager_deploy_and_rollback_traced(tmp_path):
    store = ModelStore(str(tmp_path / "registry"))
    store.publish("m", _model(1))
    store.publish("m", _model(2))
    reg = MetricsRegistry()
    tstore = TraceStore()
    tracer = Tracer(tstore)
    mgr = ModelManager(store, "m", version=1, registry=reg, tracer=tracer,
                       probation_seconds=0.0, workers=1)
    # serve once so a warmup shape is known (deploy then warms the model)
    x = np.random.RandomState(0).randn(1, 4).astype(np.float32)
    mgr.output(x)
    assert tracer.flush()
    tstore.clear()

    mgr.deploy(2)
    assert tracer.flush()
    deploy_traces = [t for t in tstore.traces() if t["root"] == "manager.deploy"]
    assert deploy_traces, [t["root"] for t in tstore.traces()]
    spans = {s["name"]: s for s in deploy_traces[0]["spans"]}
    deploy = spans["manager.deploy"]
    assert deploy["attrs"]["model"] == "m"
    assert deploy["attrs"]["version"] == "2"
    assert deploy["attrs"]["outcome"] == "completed"
    # load/warmup/swap nest under the deploy span (a slow deploy is
    # diagnosable stage by stage after the fact)
    for child in ("manager.load", "manager.warmup", "manager.swap"):
        assert spans[child]["parent_id"] == deploy["span_id"], child
        assert spans[child]["start"] >= deploy["start"]

    mgr.rollback()
    assert tracer.flush()
    rb = [t for t in tstore.traces() if t["root"] == "manager.rollback"]
    assert rb and rb[0]["spans"][0]["attrs"]["rolled_back_from"] == "2"
    mgr.shutdown(drain=False)


def test_ui_server_traces_endpoint():
    """UIServer serves GET /v1/traces from its tracer (same query surface
    as JsonModelServer), so training-process deploy/step traces are
    browsable next to /metrics."""
    import json
    from urllib import request as urllib_request

    from deeplearning4j_tpu.ui.server import UIServer

    tracer = Tracer(TraceStore())
    with tracer.span("manager.deploy", attrs={"route": "/deploy"}):
        pass
    assert tracer.flush()
    ui = UIServer(port=0, tracer=tracer).start()
    try:
        with urllib_request.urlopen(
                f"http://127.0.0.1:{ui.port}/v1/traces?route=/deploy",
                timeout=10) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["trace_count"] == 1
        assert body["traces"][0]["root"] == "manager.deploy"
        with urllib_request.urlopen(
                f"http://127.0.0.1:{ui.port}/v1/traces?route=/nope",
                timeout=10) as r:
            assert json.loads(r.read())["traces"] == []
    finally:
        ui.stop()


def test_engine_spans_only_for_traced_requests():
    """Direct output_async callers with no open span store nothing; a
    traced caller gets queue_wait/batch/forward children."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    reg = MetricsRegistry()
    tstore = TraceStore()
    tracer = Tracer(tstore)
    pi = ParallelInference(_model(1), registry=reg, tracer=tracer, workers=1)
    x = np.random.RandomState(0).randn(1, 4).astype(np.float32)
    try:
        pi.output(x)  # untraced: no current span at enqueue
        assert tracer.flush() and len(tstore) == 0
        with tracer.span("request") as req:
            fut = pi.output_async(x)
        fut.result(timeout=30)
        pi.drain(timeout=10)
        assert tracer.flush()
        trace = tstore.get(req.trace_id)
        names = {s["name"] for s in trace["spans"]}
        assert {"engine.queue_wait", "engine.batch",
                "engine.forward"} <= names
        fwd = next(s for s in trace["spans"] if s["name"] == "engine.forward")
        assert fwd["parent_id"] == req.span_id
        assert fwd["attrs"]["model_version"] == "0"
    finally:
        pi.shutdown(drain=False)
