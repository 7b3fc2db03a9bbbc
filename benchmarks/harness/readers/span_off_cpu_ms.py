"""``program_span``: the time the program's spans spent NOT running, in
milliseconds per loop turn or training step over the traced slice.

A span that the program enters and leaves on one thread carries the CPU time
its thread ran inside it beside its wall time (``self_cpu_ms`` beside
``self_ms`` in an assembled trace: each less what the span's children took).
``self_ms - self_cpu_ms`` is what the thread waited inside the span's own
code: for a phase that makes no blocking call, for the interpreter lock and
the operating system's scheduler, nothing else. The value is the sum of that
over the spans named ``span`` in the ``profiled`` roots that
``span_ms.profiled_roots`` takes (``root``, ``seq``, ``having`` as there),
over the number of roots. A program whose spans carry no CPU time gives
nothing."""

from __future__ import annotations

import sys

from .span_attr_share import run_traces
from .span_ms import profiled_roots


def value(traces: list, span: str, root: str, seq: str, having=None):
    """``(ms a root or None, roots, spans that carry a CPU time)``."""
    roots = profiled_roots(traces, root, seq, having)
    if not roots:
        return None, 0, 0
    spans = [s for t in roots for s in t["spans"]
             if s["name"] == span and "self_cpu_ms" in s]
    if not spans:
        return None, len(roots), 0
    return sum(s["self_ms"] - s["self_cpu_ms"] for s in spans) / len(roots), \
        len(roots), len(spans)


def read(record: dict, span: str, root: str, seq: str,
         having=None) -> float | None:
    traces = run_traces()
    if traces is None:
        return None
    out, n_roots, n_spans = value(traces, span, root, seq, having)
    print(f"benchmark span_off_cpu_ms {span}: {n_spans} spans in {n_roots} "
          f"profiled {root} roots", file=sys.stderr, flush=True)
    return out
