"""``sample_tokens`` alone on the chip: ms a call for each kind of batch.

No benchmark cell sends sampled traffic (the serve driver's requests are
greedy), so the sampler's other branches are timed by hand with this script
(PERF.md, PR 35). Run from the root of a checkout, so that two checkouts can
be compared in one call of the chip tool:

    python tools/bench_sampler.py [label]

A call is one iteration of a ``fori_loop`` of ``ITERS`` inside one program,
fenced once; each iteration's logits differ from the last's at one entry a
row (the row's last token), so no sort can be hoisted out of the loop. One
JSON line a case on stdout. Refuses to run without a TPU: a CPU's time is
not the sampler's.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.generate.sampling import sample_tokens

ROWS, ITERS, REPEATS = 128, 20, 5
VOCABS = (50257, 16384)
# name -> (greedy, temperature, top_k, top_p) of rows 1..127, and of row 0
SPECS = {
    "all-greedy": ((True, 1.0, 0, 1.0),) * 2,
    "temperature-only": ((False, 0.8, 0, 1.0),) * 2,
    "top-k-50": ((False, 0.8, 50, 1.0),) * 2,
    "top-p-0.9": ((False, 0.8, 0, 0.9),) * 2,
    "top-k-50-and-top-p-0.9": ((False, 0.8, 50, 0.9),) * 2,
    "one-sampling-row-of-128": ((True, 1.0, 0, 1.0), (False, 0.8, 50, 0.9)),
}


def _spec_vectors(rest, first):
    cols = list(zip(*([first] + [rest] * (ROWS - 1))))
    return tuple(jnp.asarray(c, d) for c, d in zip(
        cols, (bool, jnp.float32, jnp.int32, jnp.float32)))


@jax.jit
def _loop(logits, seeds, greedy, temp, k, p):
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)

    def body(i, toks):
        z = logits + jnp.where(col == toks[:, None], 1e-3, 0.0)
        return sample_tokens(z, seeds, jnp.full((ROWS,), i, jnp.int32),
                             greedy, temp, k, p)

    return jax.lax.fori_loop(0, ITERS, body, jnp.zeros((ROWS,), jnp.int32))


def main() -> int:
    label = sys.argv[1] if len(sys.argv) > 1 else "tree"
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing is timed",
              file=sys.stderr)
        return 2
    seeds = jnp.arange(ROWS, dtype=jnp.uint32)
    for vocab in VOCABS:
        logits = jnp.asarray(3.0 * np.random.default_rng(vocab).standard_normal(
            (ROWS, vocab)), jnp.float32)
        for name, (rest, first) in SPECS.items():
            spec = _spec_vectors(rest, first)
            _loop(logits, seeds, *spec).block_until_ready()  # compile, warm
            ms = []
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                _loop(logits, seeds, *spec).block_until_ready()
                ms.append(1e3 * (time.perf_counter() - t0) / ITERS)
            print(json.dumps({
                "label": label, "device": dev.device_kind, "vocab": vocab,
                "rows": ROWS, "spec": name, "ms_a_call": min(ms),
                "ms_a_call_all": ms}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
