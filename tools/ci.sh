#!/bin/sh
# CI harness (SURVEY.md §2.2 "Build/CI": the reference runs Maven/Jenkins
# pipelines; this is the equivalent single-command gate).
#
#   sh tools/ci.sh          # CPU: native build, tier-1 suite, dry runs
#   sh tools/ci.sh fast     # CPU: python suite only
#   sh tools/ci.sh chip     # on the chip machine: smoke + compiled-kernel
#                           # suite; no TPU, a failure or a timeout is fatal
#
# Exit nonzero on any failure.
set -e
cd "$(dirname "$0")/.."

if [ "$1" = "chip" ]; then
  # one process per chip: the two run one after the other
  echo "== chip smoke (train / serve / kernels at published widths)"
  timeout 1200 python chip_smoke.py
  echo "== compiled-kernel suite (errors, not skips, without a TPU)"
  timeout 900 python -m pytest tests_tpu/ -q
  echo "CI chip: all green"
  exit 0
fi

if [ "$1" != "fast" ]; then
  echo "== native build + C++ unit tests"
  sh native/build.sh test
fi

echo "== python test suite (8-device virtual CPU mesh)"
python -m pytest tests/ -q

if [ "$1" != "fast" ]; then
  echo "== multi-chip sharding dry-run"
  python __graft_entry__.py dryrun 8

  echo "== chip smoke, CPU rehearsal (toy sizes, interpreted kernels)"
  smoke_out=$(JAX_PLATFORMS=cpu python chip_smoke.py --dry-run-cpu)
  echo "$smoke_out" | tail -2

  echo "== benchmark artifact smoke (lstm row, cpu config)"
  # no pipe: POSIX sh has no pipefail, and `| tail` would mask a crash
  bench_out=$(JAX_PLATFORMS=cpu python bench.py measure lstm cpu)
  echo "$bench_out" | tail -1
fi

echo "CI: all green"
