"""A traced benchmark run of a cell that also prints, on standard error, what
no reader reads yet (PERF.md section 7): the trace's ``XLA Modules`` line
(each program's calls and mean device time: ``jit_decode_step``,
``jit_prefill_<bucket>``) and the 45 operations that took most of the slice,
with their counts and mean times.

    python tools/trace_modules.py --workload <cell> --seed <n> --seconds 30 --trace 1

The arguments are ``benchmarks/run.py``'s; the result line is the run's own.
"""
import collections, glob, os, sys
sys.path.insert(0, os.getcwd())
from benchmarks import run as bench_run
from benchmarks.harness import runtime, trace_reduce

summary = runtime.TraceSlice.summary

def wrapped(self):
    paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
    if paths:
        mods = collections.defaultdict(lambda: [0.0, 0])
        for ev in trace_reduce.load_events(paths[0], lines=("XLA Modules",)):
            m = mods[ev.name.split("(")[0]]; m[0] += ev.dur_ns; m[1] += 1
        for name, (ns, n) in sorted(mods.items(), key=lambda kv: -kv[1][0]):
            print(f"module {name:40s} calls {n:5d} total {ns/1e9:8.4f} s mean {ns/1e6/n:9.3f} ms", file=sys.stderr)
        ops = collections.defaultdict(lambda: [0.0, 0])
        for ev in trace_reduce.load_events(paths[0]):
            o = ops[trace_reduce.label(ev.name)]; o[0] += ev.dur_ns; o[1] += 1
        for name, (ns, n) in sorted(ops.items(), key=lambda kv: -kv[1][0])[:45]:
            print(f"op {name:60s} calls {n:6d} total {ns/1e9:8.4f} s mean {ns/1e6/n:8.4f} ms", file=sys.stderr)
    return summary(self)

runtime.TraceSlice.summary = wrapped
bench_run.main(sys.argv[1:])
