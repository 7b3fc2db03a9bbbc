"""Family ``graves_lstm``: the zoo's ``TextGenerationLSTM``, a stack of
peephole LSTM layers (Graves 2013) over one-hot ids with a softmax head. A
test fixture: it shows that the harness takes a model whose tree and decode
state are not the transformer's from files under one data directory, with
no file of ``benchmarks/harness/`` knowing its keys.

Per layer: ``z = x W + h RW + b`` in the gate order [i, f, o, g]; the input
and forget gates see the old cell through the peepholes ``P[0]``, ``P[1]``,
the output gate the new cell through ``P[2]``; ``c' = f c + i tanh(g)``,
``h' = o tanh(c')``. The first layer's ``W`` is ``vocab x 4n`` and the
others' ``n x 4n``: two groups, each stacked on its own leading axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

LAYER_KEYS = ("w", "rw", "b", "p")


def dims(config: dict) -> dict:
    m = config["model"]
    return {k: int(m[k]) for k in ("vocab_size", "hidden", "layers")}


def groups(d: dict) -> dict:
    return {"first": 1, "rest": d["layers"] - 1}


def leaves(d: dict) -> dict:
    v, n = d["vocab_size"], d["hidden"]
    layer = {"rw": (n, 4 * n), "b": (4 * n,), "p": (3, n)}
    return {"head_w": (None, (n, v)), "head_b": (None, (v,)),
            "first_w": ("first", (v, 4 * n)), "w": ("rest", (n, 4 * n))} | \
        {"first_" + k: ("first", s) for k, s in layer.items()} | \
        {k: ("rest", s) for k, s in layer.items()}


def init_scale(key: str, shape: tuple) -> tuple:
    if len(shape) == 1 or key.endswith("p"):  # biases, peepholes
        return 0.0, 0.05
    return 0.0, math.sqrt(2.0 / (shape[-2] + shape[-1]))


def _layer(x, w, quant):
    """x [b, t, in] -> h [b, t, n] through one layer from a zero state."""
    n = w["rw"].shape[0]
    xp = mm(x, w["w"], quant) + w["b"]

    def step(carry, z):
        h, c = carry
        zi, zf, zo, zg = jnp.split(z + mm(h, w["rw"], quant), 4, axis=-1)
        i = jax.nn.sigmoid(zi + w["p"][0] * c)
        f = jax.nn.sigmoid(zf + w["p"][1] * c)
        c = f * c + i * jnp.tanh(zg)
        o = jax.nn.sigmoid(zo + w["p"][2] * c)
        h = o * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((x.shape[0], n), F32)
    _, hs = jax.lax.scan(step, (zero, zero), xp.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab]: the whole sequence from a zero
    state, one step at a time."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    x = jax.nn.one_hot(ids, d["vocab_size"], dtype=F32)
    x = _layer(x, {k: w["first_" + k][0] for k in LAYER_KEYS}, quant)
    for i in range(d["layers"] - 1):
        x = _layer(x, {k: w[k][i] for k in LAYER_KEYS}, quant)
    return mm(x, w["head_w"], quant) + w["head_b"]


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """``h`` and ``c`` a layer, whatever the position."""
    return 2 * d["layers"] * d["hidden"] * dtype_bytes


def flops_per_token(d: dict) -> float:
    """``h RW`` in every layer, ``x W`` in every layer but the first (a
    one-hot row times ``W`` is a look-up), and the head."""
    n = d["hidden"]
    return 2.0 * ((2 * d["layers"] - 1) * n * 4 * n + n * d["vocab_size"])


def lstm_serve_slice(s: dict):
    """Every token decoded in the slice and every position prefilled in it
    costs the same: no entry of a cache is attended."""
    tokens = sum(share for _, share in s["decode_attended"]) + \
        sum(n * share for n, share in s["prefill_lengths"])
    return tokens * flops_per_token(s["model"]), None


def lstm_emitted_slice(s: dict):
    """The same count from the tokens the program itself says it emitted in
    the slice (its counter, carried by ``slice_counters``): the decoded
    tokens only, whole, with no share for where the slice's edges fall."""
    emitted = sum(s["counters"]["dl4j_tpu_generate_tokens_total"].values())
    return emitted * flops_per_token(s["model"]), None
