"""What both drivers share: the run's description with its family, the
device's report, the traced slice, the program's counters over it, and the
result line."""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import math
import os
import shutil
import sys
import time


@dataclasses.dataclass
class Run:
    """One invocation of the benchmark: the cell's three data files with the
    rehearsal's overrides already applied, and the command line."""

    cell: dict
    config: dict
    traffic: dict
    family: object          # the module families/<config["family"]>.py
    kernel_names: tuple     # the kernels the cell's per-layer metrics read
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float          # perf_counter at process start
    out_dir: str            # scratch for this run (trace files), in the checkout

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def setup_seconds(self) -> float:
        return time.perf_counter() - self.t_start

    def log(self, msg: str) -> None:
        """A line on standard error with the seconds since process start."""
        print(f"benchmark [{self.setup_seconds():7.2f}s] {msg}",
              file=sys.stderr, flush=True)


def family_file(data_dir: str, config: dict) -> str:
    """Where the configuration's family lives: ``<data-dir>/families/
    <family>.py``. There is no default family and no fallback: a
    configuration that names none, or one whose file is missing, ends the
    run before it prints anything."""
    name = config.get("family")
    if not name:
        raise SystemExit(f"benchmark: configuration {config.get('name')!r} "
                         "has no \"family\" key; every configuration names "
                         "the file under families/ that knows its model")
    path = os.path.join(os.path.abspath(data_dir), "families", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: configuration {config.get('name')!r} "
                         f"names the family {name!r}, and there is no {path}")
    return path


def load_family(path: str):
    """Import a family's file by its path (once JAX is set up: a family's
    reference imports it). See ``run.py``'s docstring for what it gives."""
    name = "benchmarks_family_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_counters(run: Run) -> dict:
    """``{name: {"label,values": value}}`` of the program's counters that
    the configuration lists under ``slice_counters``, from the process's
    registry (a name it does not have yet reads as empty). A family counts
    work that only the run knows (tokens routed to the experts held, say)
    from these: a driver reads them where the traced slice starts and where
    it stops."""
    from deeplearning4j_tpu.obs.metrics import get_registry

    registry, out = get_registry(), {}
    for name in run.config.get("slice_counters", ()):
        fam = registry.get(name)
        out[name] = {} if fam is None else {
            ",".join(labels): child.value for labels, child in fam.items()}
    return out


def counters_between(lo: dict, hi: dict) -> dict:
    """What each counter rose by between two ``read_counters``."""
    return {name: {k: v - lo.get(name, {}).get(k, 0.0)
                   for k, v in children.items()}
            for name, children in hi.items()}


def dtype_bytes(name: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[name]


def devices_for(run: Run):
    """The run's devices. Without ``--rehearse`` they have to be TPU chips,
    as many as the cell asks for; a rehearsal takes what the CPU offers."""
    import jax

    devs = jax.devices()
    if not run.rehearse and (devs[0].platform != "tpu"
                             or len(devs) < run.chips):
        raise SystemExit(
            f"benchmark: the cell needs {run.chips} TPU chip(s); JAX offers "
            f"{len(devs)} x {devs[0].platform}")
    return devs


def device_report(devs, used: int) -> dict:
    """The device as JAX reports it, and the peak on the fullest chip.

    libtpu counts live buffers under ``peak_bytes_in_use`` and the loaded
    programs' temporaries (activations, scratch) apart, under
    ``peak_bytes_reserved``: the compiled 32 x 512 train step's
    ``memory_analysis().temp_size_in_bytes`` is that reserve to within 1%
    (PERF.md, PR 25). What the chip holds at its fullest is their sum."""
    peak = 0
    for d in devs[:used]:
        stats = d.memory_stats() or {}
        print("benchmark memory_stats " + " ".join(
            f"{k}={v}" for k, v in sorted(stats.items()) if "bytes" in k),
            file=sys.stderr, flush=True)
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class TraceSlice:
    """A profiler trace of a few seconds of the window, in a run of its own
    (``--trace 1``). ``summary()`` reduces it with ``trace_reduce``."""

    def __init__(self, run: Run) -> None:
        self.dir = os.path.join(run.out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.chips = run.chips
        self.kernel_names = run.kernel_names
        self.t1 = self.t_untraced = 0.0

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        """Stop the profiler and no more: reading the trace waits until the
        window has closed, so that it takes nothing from the measured path."""
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.t_untraced = time.perf_counter()

    def summary(self):
        from . import trace_reduce

        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            return None
        out = trace_reduce.reduce(trace_reduce.load_events(paths[0]),
                                  self.chips, self.kernel_names)
        shutil.rmtree(self.dir, ignore_errors=True)  # write little to disk
        return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (nearest rank, upper) of ``values``."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]


def read_layer_metrics(metric_files: list, record: dict) -> dict:
    """Each per-layer metric of the cell through its reader. A reader that
    finds nothing returns ``None`` and the metric is left out."""
    import importlib

    out = {}
    for mf in metric_files:
        reader = importlib.import_module(
            f"benchmarks.harness.readers.{mf['reader']}")
        value = reader.read(record, **mf.get("params", {}))
        if value is not None and math.isfinite(value):
            out[mf["name"]] = {"value": value, "unit": mf["unit"]}
    return out
