"""What every family's plain reference shares: the control's rounding and
the follower of the first training steps. The forward pass itself (float32,
"highest" matmul precision, no kernels, no cache, no batching tricks) is the
family's, ``families/<family>.py``; nothing here or there imports anything
of the program or takes anything the program made: the weights are
``weights.make_weights`` from the same seed.

``quant`` names the lower precision of the CONTROL (never of a benchmark
run): every matmul operand is rounded to it on the way forward, and the
gradient passes the rounding straight through (a gradient rounded to fp8
without scaling underflows to nought, which fails by default and shows
nothing). That is the mildest form of the step that would tempt a later PR.
A family's reference multiplies through ``mm`` so that the control reaches
every product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def q(x, quant):
    if quant is None:
        return x
    rounded = x.astype(quant).astype(F32)
    return x + jax.lax.stop_gradient(rounded - x)


def mm(a, b, quant=None):
    return jnp.matmul(q(a, quant), q(b, quant), precision="highest")


# ------------------------------------------------------------------ training
def leaf_norms(tree, stacked):
    """Per-leaf L2 norms; a key in ``stacked`` gives one norm a member."""
    def norm(key, a):
        a = a.astype(F32)
        axes = tuple(range(1, a.ndim)) if key in stacked else None
        return jnp.sqrt(jnp.sum(a * a, axis=axes))

    return {k: norm(k, a) for k, a in tree.items()}


def train_steps(loss_fn, w, batches, dims, adam, *, stacked, row_block,
                quant=None, half_batch=False, state_unchanged=False):
    """Follow ``len(batches)`` Adam steps from ``w`` under the family's
    ``loss_fn(w, ids, labels, dims, quant)``. Returns the losses,
    the per-leaf norms of the first gradient, and the per-leaf norms of the
    parameters' change after the last step. ``half_batch`` plants the fault
    "half of the batch left out, the mean taken over the rest", and
    ``state_unchanged`` the fault "a step that returns its state unchanged"
    (controls, never a benchmark run)."""
    lr, b1, b2, eps = (adam[k] for k in ("learning_rate", "beta1", "beta2",
                                         "epsilon"))
    if state_unchanged:
        lr = 0.0

    @jax.jit
    def loss_and_grads(w, ids, labels):
        n = ids.shape[0] // row_block
        ids = ids.reshape(n, row_block, -1)
        labels = labels.reshape(n, row_block, -1)

        def one(acc, xy):
            l, g = jax.value_and_grad(loss_fn)(w, xy[0], xy[1], dims, quant)
            return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

        zero = (jnp.zeros((), F32), jax.tree_util.tree_map(jnp.zeros_like, w))
        (l, g), _ = jax.lax.scan(one, zero, (ids, labels))
        return l / n, jax.tree_util.tree_map(lambda a: a / n, g)

    @jax.jit
    def adam_step(w, m, v, g, t):
        def upd(p, m, v, g):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
            return p - lr * mh / (jnp.sqrt(vh) + eps), m, v

        out = {k: upd(w[k], m[k], v[k], g[k]) for k in w}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    w0 = w
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, gnorm = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        if half_batch:
            ids, labels = ids[: ids.shape[0] // 2], labels[: ids.shape[0] // 2]
        loss, g = loss_and_grads(w, jnp.asarray(ids), jnp.asarray(labels))
        if gnorm is None:
            gnorm = jax.jit(lambda t: leaf_norms(t, stacked))(g)
        w, m, v = adam_step(w, m, v, g, jnp.asarray(float(t), F32))
        losses.append(float(loss))
    dnorm = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b), stacked))(w, w0)
    return losses, jax.device_get(gnorm), jax.device_get(dnorm)
