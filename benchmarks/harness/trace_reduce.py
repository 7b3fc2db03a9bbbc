"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy union
and window, time by operation and by kernel name, the top operations and the
longest idle gaps.

Two stages, so that the arithmetic is tested without a chip:

``load_events(path)``  reads the device planes with ``jax.profiler.ProfileData``
                       into plain ``Event`` tuples;
``reduce(events)``     is pure Python over those tuples.

Which kernels to look for is the cell's own list: the names its per-layer
metric files give under ``params.kernels`` (``run.py`` gathers them), so a
model that brings a kernel brings its name in a data file.

Device planes are named ``/device:TPU:<n>``. On each, the line ``XLA Ops``
holds one event per executed operation, named by its whole HLO instruction
(``%jvp_flash_fwd_.23 = (bf16[384,512,64]...) custom-call(...)``): the
instruction's own name, before `` = ``, carries a Pallas kernel's ``name``
(``flash_fwd`` inside ``jvp_flash_fwd_``, ``flash_bwd_dkv`` inside
``transpose_jvp_flash_bwd_dkv__``, ``flash_decode``); a fusion has XLA's
name. The other lines (``XLA Modules``, ``Steps``, ``Async XLA Ops``) span
the same time again and are not counted. A ``%while`` spans the operations
of its body on the same line, so per-operation sums can count a moment
twice; the busy union never does.
"""

from __future__ import annotations

import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_events(path: str, lines=(OPS_LINE,)) -> list:
    """The events of ``lines`` on every device plane of the trace: an
    ``.xplane.pb`` as the profiler writes it, or the same as a text proto
    (``.txt``, the tests' recorded fixture)."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in lines:
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def busy_union_ns(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def instruction(name: str) -> str:
    """``%fusion.308 = f32[768,30522]{0,1} fusion(...)`` -> ``fusion.308``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def label(name: str) -> str:
    """A short name for the breakdown: the instruction and its first shape."""
    head, _, rest = name.partition(" = ")
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rest)
    return instruction(head) + (" " + shape.group(0) if shape else "")


def kernel_of(name: str, kernels):
    """The kernel of ``kernels`` an event belongs to: its instruction's name
    carries the
    kernel's between underscores, dots or the ends (``flash_bwd_dq`` is in
    ``transpose_jvp_flash_bwd_dq__.12`` and not in ``flash_bwd_dq2``). The
    operands' text is not searched: a reduce that reads a kernel's output is
    not the kernel."""
    ins = instruction(name)
    for k in sorted(kernels, key=len, reverse=True):
        if re.search(rf"(?<![A-Za-z0-9]){re.escape(k)}(?![A-Za-z0-9])", ins):
            return k
    return None


def reduce(events: list, chips: int, kernel_names, top: int = 10):
    """``window_s``: first operation's start to last operation's end, the
    widest over the chips; ``busy_s``: the union of operation intervals,
    averaged over the chips used; ``ops``/``kernels``: ``name -> [seconds,
    count]`` summed over the chips; ``top_ops`` (a kernel's calls taken
    together under the kernel's name, any other operation by instruction) and
    ``idle_gaps`` (by the operation that ran before) for the result line's
    ``breakdown``. ``None`` if no operation ran."""
    planes: dict = {}
    for ev in events:
        if ev.line == OPS_LINE and DEVICE_PLANE.match(ev.plane):
            planes.setdefault(ev.plane, []).append(ev)
    if not planes:
        return None
    window = busy = 0.0
    ops: dict = {}
    kernels: dict = {}
    groups: dict = {}
    gaps = []
    for evs in planes.values():
        evs.sort(key=lambda e: e.start_ns)
        start = evs[0].start_ns
        end = max(e.start_ns + e.dur_ns for e in evs)
        window = max(window, end - start)
        busy += busy_union_ns((e.start_ns, e.start_ns + e.dur_ns)
                              for e in evs)
        reach, last = start, None
        for e in evs:
            if last is not None and e.start_ns > reach:
                gaps.append((f"after {last}", (e.start_ns - reach) * 1e-9))
            if e.start_ns + e.dur_ns >= reach:
                reach, last = e.start_ns + e.dur_ns, label(e.name)
            o = ops.setdefault(label(e.name), [0.0, 0])
            o[0] += e.dur_ns * 1e-9
            o[1] += 1
            k = kernel_of(e.name, kernel_names)
            if k is not None:
                kk = kernels.setdefault(k, [0.0, 0])
                kk[0] += e.dur_ns * 1e-9
                kk[1] += 1
            # the breakdown takes a kernel's calls together, under its name
            g = k if k is not None else label(e.name)
            groups[g] = groups.get(g, 0.0) + e.dur_ns * 1e-9
    used = max(chips, 1)
    # idle gaps by what came before them: the host's own activity needs
    # annotations inside the program (the tracing issue's)
    by_prev: dict = {}
    for name, s in gaps:
        by_prev[name] = by_prev.get(name, 0.0) + s
    return {
        "window_s": window * 1e-9,
        "busy_s": busy * 1e-9 / used,
        "chips_traced": len(planes),
        "ops": ops,
        "kernels": kernels,
        "top_ops": [[n, v] for n, v in sorted(
            groups.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            by_prev.items(), key=lambda kv: -kv[1])[:top]],
    }
