#!/usr/bin/env python3
"""The device's idle time by what the host was doing.

    python tools/host_gaps.py <trace dir or .xplane.pb>

reads a ``jax.profiler`` trace (taken with ``ui.profiling.device_trace`` or
``jax.profiler.start_trace``). While a profiler session collects, every span
of the program's tracer is also a ``TraceAnnotation`` on its thread's line of
the host plane (``obs/tracing.py``), on the clock the device's operations are
on. Each gap between the device's operations (the ``XLA Ops`` line of
``/device:TPU:0``) is split over the innermost ``loop.*``/``fit.*`` spans open
while it lasts; the table sums the idle seconds by span, and beside them the
seconds of the gaps that began under each. Below it, for consecutive
``loop.fetch`` spans, the time from the end of the last device operation
before the fetch returned to the fetch's end: what it takes the loop's thread
to come back once the device is done.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
HOST_SPAN = re.compile(r"^(loop|fit)\.")


def load(path: str):
    """``(host spans, device operations)`` as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise SystemExit(f"host_gaps: no .xplane.pb under {path}")
        path = found[-1]
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        host = plane.name.startswith("/host:")
        if not host and not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if not host and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if host and not HOST_SPAN.match(ev.name):
                    continue
                (spans if host else ops).append(
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns + ev.duration_ns)))
    return spans, ops


def idle_gaps(ops: list) -> list:
    """``(start, end)`` of every interval inside the device's window in
    which no operation ran."""
    gaps, reach = [], None
    for _, start, end in sorted(ops, key=lambda o: o[1]):
        if reach is not None and start > reach:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return gaps


def timeline(spans: list) -> list:
    """``(start, end, name)`` segments covering all of time, each named by
    the span open over it that started last (``(no span)`` where none is),
    so that a moment has one owner however the spans nest."""
    inf = float("inf")
    cuts = [-inf] + sorted({t for s in spans for t in s[1:]}) + [inf]
    out = []
    for lo, hi in zip(cuts, cuts[1:]):
        over = [s for s in spans if s[1] <= lo and hi <= s[2]]
        name = max(over, key=lambda s: s[1])[0] if over else "(no span)"
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], hi, name)
        else:
            out.append((lo, hi, name))
    return out


def by_span(spans: list, ops: list) -> dict:
    """``span -> [idle seconds under it, idle seconds of the gaps that began
    under it]`` over the device's window. A gap that begins while the loop
    waits in ``loop.fetch`` and lasts through the emit and the next upload
    is split over the three in the first number, and whole under
    ``loop.fetch`` in the second."""
    segs = timeline(spans)
    starts = [seg[0] for seg in segs]
    out: dict = {}
    for lo, hi in idle_gaps(ops):
        i = bisect.bisect_right(starts, lo) - 1
        out.setdefault(segs[i][2], [0.0, 0.0])[1] += (hi - lo) * 1e-9
        while lo < hi:
            upto = min(hi, segs[i][1])
            out.setdefault(segs[i][2], [0.0, 0.0])[0] += (upto - lo) * 1e-9
            lo, i = upto, i + 1
    return out


def fetch_offsets(spans: list, ops: list, n: int = 5) -> list:
    """For ``n`` consecutive ``loop.fetch`` spans from the middle of the
    trace: ms from the end of the last device operation that ended before
    the fetch did, to the fetch's end."""
    fetches = sorted((s for s in spans if s[0] == "loop.fetch"),
                     key=lambda s: s[1])
    ends = sorted(o[2] for o in ops)
    out = []
    for _, _, f_end in fetches[len(fetches) // 2:][:n]:
        i = bisect.bisect_right(ends, f_end)
        if i:
            out.append((f_end - ends[i - 1]) * 1e-6)
    return out


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    spans, ops = load(argv[1])
    if not ops:
        raise SystemExit("host_gaps: no XLA Ops on /device:TPU:0")
    window = (max(o[2] for o in ops) - min(o[1] for o in ops)) * 1e-9
    rows = sorted(by_span(spans, ops).items(), key=lambda kv: -kv[1][0])
    print(f"device window {window:.4f} s, {len(ops)} operations, "
          f"{len(spans)} host spans")
    print(f"{'innermost host span':24s} {'idle s':>9s} {'% of window':>12s} "
          f"{'s of gaps begun here':>21s}")
    for name, (sec, begun) in rows:
        print(f"{name:24s} {sec:9.4f} {100 * sec / window:12.2f} "
              f"{begun:21.4f}")
    total = sum(sec for _, (sec, _) in rows)
    print(f"{'total':24s} {total:9.4f} {100 * total / window:12.2f}")
    offs = fetch_offsets(spans, ops)
    if offs:
        print("last device operation's end -> loop.fetch's end, ms: "
              + ", ".join(f"{o:.3f}" for o in offs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
