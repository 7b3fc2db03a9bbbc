"""The plain references: straightforward ``jax.numpy`` in float32 at
"highest" matmul precision, no kernels, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program made; its
weights are ``weights.make_weights`` from the same seed. Both zoo models are
the same pre-LN transformer trunk (no attention biases, tanh-GELU, learned
positions, LayerNorm eps 1e-5): the encoder attends in both directions and
is trained with the cross-entropy at EVERY position; the decoder is causal.

``quant`` names the lower precision of the CONTROL (never of a benchmark
run): every matmul operand is rounded to it on the way forward, and the
gradient passes the rounding straight through (a gradient rounded to fp8
without scaling underflows to nought, which fails by default and shows
nothing). That is the mildest form of the step that would tempt a later PR.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import BLOCK_KEYS

F32 = jnp.float32


def _q(x, quant):
    if quant is None:
        return x
    rounded = x.astype(quant).astype(F32)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision="highest")


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):  # the tanh form, which is jax.nn.gelu's default
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, n_heads, causal, quant):
    b, t, h = x.shape
    d = h // n_heads

    def heads(y):
        return y.reshape(b, t, n_heads, d).transpose(0, 2, 1, 3)

    y = _ln(x, w["ln1_g"], w["ln1_b"])
    q, k, v = (heads(_mm(y, w[n], quant)) for n in ("wq", "wk", "wv"))
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(d)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm(p, v, quant).transpose(0, 2, 1, 3).reshape(b, t, h)
    x = x + _mm(o, w["wo"], quant)
    y = _ln(x, w["ln2_g"], w["ln2_b"])
    y = _gelu(_mm(y, w["w1"], quant) + w["b1"])
    return x + _mm(y, w["w2"], quant) + w["b2"]


def trunk(w, ids, model, *, causal, quant=None, remat=False):
    """ids [b, t] -> the final LayerNorm's output [b, t, h]."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    t = ids.shape[1]
    x = w["tok_emb"][ids] + w["pos_emb"][:t][None]
    blocks = {k: w[k] for k in BLOCK_KEYS}

    def body(x, wb):
        return _block(x, wb, model["n_heads"], causal, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, blocks)
    return _ln(x, w["lnf_g"], w["lnf_b"]), w


def encoder_loss(w, ids, labels, model, quant=None):
    """Mean over every position of -log softmax(logits)[label]."""
    hid, w32 = trunk(w, ids, model, causal=False, quant=quant, remat=True)
    logits = _mm(hid, w32["head_w"], quant) + w32["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def decoder_logits(w, ids, model, quant=None):
    """ids [b, t] -> logits [b, t, vocab] of the full causal forward."""
    hid, w32 = trunk(w, ids, model, causal=True, quant=quant)
    return _mm(hid, w32["head_w"], quant) + w32["head_b"]


# ------------------------------------------------------------------ training
def leaf_norms(tree):
    """Per-leaf L2 norms; stacked keys give one norm a block."""
    def norm(key, a):
        a = a.astype(F32)
        axes = tuple(range(1, a.ndim)) if key in BLOCK_KEYS else None
        return jnp.sqrt(jnp.sum(a * a, axis=axes))

    return {k: norm(k, a) for k, a in tree.items()}


def train_steps(w, batches, model, adam, *, row_block, quant=None,
                half_batch=False, state_unchanged=False):
    """Follow ``len(batches)`` Adam steps from ``w``. Returns the losses,
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
            l, g = jax.value_and_grad(encoder_loss)(w, xy[0], xy[1], model,
                                                    quant)
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
            gnorm = jax.jit(leaf_norms)(g)
        w, m, v = adam_step(w, m, v, g, jnp.asarray(float(t), F32))
        losses.append(float(loss))
    dnorm = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
    return losses, jax.device_get(gnorm), jax.device_get(dnorm)
