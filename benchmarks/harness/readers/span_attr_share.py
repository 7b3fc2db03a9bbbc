"""``program_span``: what an attribute of the program's spans sums to over
the traced slice, as a share in percent.

The spans are those named ``span`` in the ``profiled`` roots that
``span_ms.profiled_roots`` takes (``root``, ``seq``, ``having`` as there:
consecutive numbers or no value). Without ``over`` the sum of ``attr`` is
set against the slice the roots cover on the program's clock, from the first
root's start to the last root's end: milliseconds of an attribute over
milliseconds of wall time (a loop turn's ``dry_ms``: the share of the slice
in which the loop saw the device's queue empty). With ``over``, against the
sum of that attribute over the same spans (``dry_emit_ms`` over ``dry_ms``:
where in the turn the device ran dry); a span that carries ``over`` and not
``attr`` counts nothing to the sum. A program whose spans carry no such
attribute, a slice without roots, and an ``over`` that sums to nothing give
nothing, never 0."""

from __future__ import annotations

import sys

from .span_ms import profiled_roots


def value(traces: list, span: str, attr: str, root: str, seq: str,
          over=None, having=None):
    """``(percent or None, roots, spans that carry the denominator's
    attribute)``."""
    roots = profiled_roots(traces, root, seq, having)
    if not roots:
        return None, 0, 0
    base = over or attr
    spans = [s for t in roots for s in t["spans"]
             if s["name"] == span and base in s["attrs"]]
    if not spans:
        return None, len(roots), 0
    total = sum(s["attrs"].get(attr, 0.0) for s in spans)
    if over is None:
        tops = [next(s for s in t["spans"] if s["parent_id"] is None)
                for t in roots]
        whole = (max(s["end"] for s in tops)
                 - min(s["start"] for s in tops)) * 1e3
    else:
        whole = sum(s["attrs"][over] for s in spans)
    return (100.0 * total / whole if whole > 0 else None), len(roots), \
        len(spans)


def run_traces() -> list | None:
    """Every trace the program's tracer holds, once what was exported so far
    is in its store; ``None`` without the program."""
    try:
        from deeplearning4j_tpu.obs.tracing import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    tracer.flush()
    return tracer.store.traces(limit=tracer.store.max_traces)


def read(record: dict, span: str, attr: str, root: str, seq: str,
         over=None, having=None) -> float | None:
    traces = run_traces()
    if traces is None:
        return None
    out, n_roots, n_spans = value(traces, span, attr, root, seq, over, having)
    print(f"benchmark span_attr_share {span} ({attr} over "
          f"{over or 'the slice'}): {n_spans} spans in {n_roots} profiled "
          f"{root} roots", file=sys.stderr, flush=True)
    return out
