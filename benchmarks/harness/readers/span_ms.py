"""``program_span``: a statistic of the program's own spans over the traced
slice, in milliseconds per loop turn or training step.

The program's one tracer (``deeplearning4j_tpu.obs.tracing``) samples every
root span opened while a profiler session collects and marks it
``profiled``; the slice this harness traces is therefore whole in the
tracer's store, with no window to place on a second clock. ``root`` names
the traces taken (``loop.turn``, ``fit.step``), ``seq`` the root attribute
that numbers them: the numbers have to be consecutive, or a trace was lost
and there is no value (never a wrong mean). With ``having`` only the roots
whose trace holds a span of that name count (a loop turn that stepped).

``stat``: ``self_mean`` is the sum of the self times of the spans named
``span`` (duration less what their children cover, as ``/v1/traces`` gives
it) over the number of roots; ``duration_mean`` the same of their durations;
with ``attr`` the value is the median of that attribute over the spans. A
program without such spans gives nothing."""

from __future__ import annotations

import statistics
import sys


def profiled_roots(traces: list, root: str, seq: str, having=None):
    """The traces rooted at a ``profiled`` span named ``root``, in the order
    of their ``seq`` numbers, or ``None`` where the numbers have a gap."""
    taken = []
    for t in traces:
        top = next((s for s in t["spans"] if s["parent_id"] is None), None)
        if top is not None and top["name"] == root \
                and top["attrs"].get("profiled") and seq in top["attrs"]:
            taken.append((top["attrs"][seq], t))
    taken.sort(key=lambda p: p[0])
    numbers = [n for n, _ in taken]
    if any(b - a != 1 for a, b in zip(numbers, numbers[1:])):
        return None
    return [t for _, t in taken if having is None
            or any(s["name"] == having for s in t["spans"])]


def value(traces: list, span: str, root: str, seq: str,
          stat: str = "self_mean", attr=None, having=None):
    roots = profiled_roots(traces, root, seq, having)
    if not roots:
        return None, 0, 0
    spans = [s for t in roots for s in t["spans"] if s["name"] == span]
    if attr is not None:
        vals = [s["attrs"][attr] for s in spans if attr in s["attrs"]]
        return (statistics.median(vals) if vals else None), len(roots), \
            len(vals)
    key = {"self_mean": "self_ms", "duration_mean": "duration_ms"}[stat]
    return sum(s[key] for s in spans) / len(roots), len(roots), len(spans)


def read(record: dict, span: str, root: str, seq: str,
         stat: str = "self_mean", attr=None, having=None) -> float | None:
    try:
        from deeplearning4j_tpu.obs.tracing import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    tracer.flush()
    traces = tracer.store.traces(limit=tracer.store.max_traces)
    out, n_roots, n_spans = value(traces, span, root, seq, stat, attr, having)
    print(f"benchmark span_ms {span} ({attr or stat}): {n_spans} spans in "
          f"{n_roots} profiled {root} roots", file=sys.stderr, flush=True)
    return out
