"""``chip_smoke.py`` on the CPU: without a TPU and without the dry flag it
must refuse to run; a failing phase must reach the exit code; and with
``JAX_COMPILATION_CACHE_DIR`` unset the compile cache sits in
``<checkout>/.jax_cache``. Each case is a process of its own — the script
owns its process's JAX configuration — and they run side by side, since
this process only waits for them. The dry mode's full pass (and the cache
directory with the variable set) is ``test_zz_chip_smoke_dry_run.py``.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

_FAILING_PHASE = (
    "import sys, chip_smoke\n"
    "def boom(*a, **k):\n"
    "    import jax\n"
    "    raise RuntimeError('phase blew up; cache at '\n"
    "                       + jax.config.jax_compilation_cache_dir)\n"
    "chip_smoke.phase_train = boom\n"
    "sys.exit(chip_smoke.main(['--dry-run-cpu']))\n")


def start(argv, *, cache_dir=None, devices=1):
    """Start ``python *argv`` from the checkout on a CPU-only JAX with
    ``devices`` virtual devices and the given (or no) cache variable."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.Popen([sys.executable, *argv], env=env, cwd=ROOT,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def finish(proc, timeout=600):
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    return proc.returncode, stdout, stderr


@pytest.fixture(scope="module")
def runs():
    procs = {"no_flag": start([SMOKE]),
             "failing_phase": start(["-c", _FAILING_PHASE])}
    return {name: finish(proc) for name, proc in procs.items()}


def test_without_tpu_and_without_flag_it_fails_and_builds_nothing(runs):
    rc, stdout, stderr = runs["no_flag"]
    assert rc != 0
    assert stdout.strip() == ""  # no result line
    assert "no TPU" in stderr and "nothing was built" in stderr


def test_a_failing_phase_reaches_the_exit_code(runs):
    """...and, with JAX_COMPILATION_CACHE_DIR unset, the cache directory
    main() chose before its first phase is <checkout>/.jax_cache."""
    rc, stdout, stderr = runs["failing_phase"]
    assert rc != 0
    assert ("phase blew up; cache at " + os.path.join(ROOT, ".jax_cache")
            in stderr)
    assert stdout.strip() == ""  # nothing caught and summarised
