#!/usr/bin/env python3
"""``benchmarks/limits.py`` for a served cell whose logits leave no room for
a second copy: the precision control in two passes.

    python3 tools/phi4f_limits.py --workload phi4f-reasoning-closed64 \\
        --seeds 4141100001,4141100002 [--control 1] [--seconds 15]

The control reads, at each served position, the token that the reference
computed in the configuration's ``control_quant`` puts first, and the
float32 reference's logit gap to it (``serve_driver.ServeRun.served_gaps``).
``served_gaps`` does both in one program, which holds the two logit arrays
at once: for Phi-4-mini-flash at 10,240 positions that is 2 x 8.2 GB beside
7.7 GB of weights. Here the control's tokens are taken first, in a program
of their own, and the float32 logits are read against them in a second:
the same numbers, one logit array at a time. Everything else is
``limits.py`` as it stands.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def two_pass_gaps(self, sample, quant=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights

    if quant is None:
        return one_pass(self, sample)
    cfg, dims = self.run.config, self.dims
    w = weights.make_weights(self.family, dims, self.run.seed, cfg["dtype"])
    length = int(cfg["engine"]["max_len"])
    logits_of = self.family.decoder_logits
    control = jax.jit(lambda w, ids: jnp.argmax(
        logits_of(w, ids[None], dims, quant=quant)[0], axis=-1))

    @jax.jit
    def gaps(w, ids, targets, mask):
        logits = logits_of(w, ids[None], dims)[0]
        at = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        gap = jnp.where(mask, jnp.max(logits, axis=-1) - at, 0.0)
        return jnp.max(gap), jnp.argmax(gap), jnp.sum(gap), jnp.sum(gap > 0)

    out = []
    for r in sample:
        n, toks = len(r["prompt"]), r["tokens"]
        seq = (r["prompt"] + toks)[:length]
        ids = np.zeros((length,), np.int32)
        ids[:len(seq)] = seq
        m = min(len(toks), length - (n - 1))
        mask = np.zeros((length,), bool)
        mask[n - 1:n - 1 + m] = True
        targets = control(w, jnp.asarray(ids))
        g, at, total, off = gaps(w, jnp.asarray(ids), targets,
                                 jnp.asarray(mask))
        out.append({"k": r["k"], "gap": float(g), "sum": float(total),
                    "off_best": int(off), "token_index": int(at) - (n - 1),
                    "tokens": m})
    return out


if __name__ == "__main__":
    from benchmarks import limits
    from benchmarks.harness import serve_driver

    one_pass = serve_driver.ServeRun.served_gaps
    serve_driver.ServeRun.served_gaps = two_pass_gaps
    limits.main()
