"""Family ``preln_transformer``: the zoo's ``BertEncoder`` and
``TransformerLM``. One pre-LN trunk (no attention biases, tanh-GELU, learned
positions, LayerNorm eps 1e-5, an untied ``hidden x vocab`` head): the
encoder attends in both directions and is trained with the cross-entropy at
EVERY position; the decoder is causal.

Everything of the yardstick that depends on the architecture is here, under
the names ``run.py``'s docstring gives a family: the sizes, the canonical
weight tree, the plain reference (float32, "highest" matmul precision, no
kernels, no cache; it imports nothing of the program), the work counts and
the cache's bytes.

The work counts are MODEL operations: a multiply-add is 2 FLOPs; training
is 3x the forward matmul work (the backward does twice the forward's); what
the mathematics requires whatever implements it, so recomputation inside a
kernel never counts and a later kernel cannot make its own roofline stale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

BLOCK_KEYS = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
              "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``."""
    m = config["model"]
    return {k: int(m[k]) for k in ("vocab_size", "hidden", "n_layers",
                                   "n_heads", "ffn_size", "max_len")}


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    return {"block": d["n_layers"]}


def leaves(d: dict) -> dict:
    h, f, v, t = d["hidden"], d["ffn_size"], d["vocab_size"], d["max_len"]
    block = {"ln1_g": (h,), "ln1_b": (h,),
             "wq": (h, h), "wk": (h, h), "wv": (h, h), "wo": (h, h),
             "ln2_g": (h,), "ln2_b": (h,),
             "w1": (h, f), "b1": (f,), "w2": (f, h), "b2": (h,)}
    top = {"tok_emb": (v, h), "pos_emb": (t, h), "lnf_g": (h,), "lnf_b": (h,),
           "head_w": (h, v), "head_b": (v,)}
    return {k: (None, s) for k, s in top.items()} | \
        {k: ("block", block[k]) for k in BLOCK_KEYS}


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices are Xavier-normal as in the zoo models; gains and biases get
    a small random part (the zoo's are exactly 1 and 0), so that a dropped
    bias or gain shows in the comparison."""
    if key == "pos_emb":
        return 0.0, 0.02
    if key.endswith("_g"):
        return 1.0, 0.02
    if len(shape) == 1:
        return 0.0, 0.02
    return 0.0, math.sqrt(2.0 / (shape[-2] + shape[-1]))


# ---------------------------------------------------------- plain reference
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
    q, k, v = (heads(mm(y, w[n], quant)) for n in ("wq", "wk", "wv"))
    s = mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(d)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm(p, v, quant).transpose(0, 2, 1, 3).reshape(b, t, h)
    x = x + mm(o, w["wo"], quant)
    y = _ln(x, w["ln2_g"], w["ln2_b"])
    y = _gelu(mm(y, w["w1"], quant) + w["b1"])
    return x + mm(y, w["w2"], quant) + w["b2"]


def trunk(w, ids, d, *, causal, quant=None, remat=False):
    """ids [b, t] -> the final LayerNorm's output [b, t, h]."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    t = ids.shape[1]
    x = w["tok_emb"][ids] + w["pos_emb"][:t][None]
    blocks = {k: w[k] for k in BLOCK_KEYS}

    def body(x, wb):
        return _block(x, wb, d["n_heads"], causal, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, blocks)
    return _ln(x, w["lnf_g"], w["lnf_b"]), w


def loss(w, ids, labels, d, quant=None):
    """The encoder's: mean over every position of -log softmax(logits)[label]."""
    hid, w32 = trunk(w, ids, d, causal=False, quant=quant, remat=True)
    logits = mm(hid, w32["head_w"], quant) + w32["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab] of the full causal forward."""
    hid, w32 = trunk(w, ids, d, causal=True, quant=quant)
    return mm(hid, w32["head_w"], quant) + w32["head_b"]


# ------------------------------------------------------------ cache's bytes
def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds a key and a value of
    ``hidden`` numbers a layer for every position before it."""
    return position * 2 * d["n_layers"] * d["hidden"] * dtype_bytes


# ------------------------------------------------------------- work counts
def matmul_params(d: dict) -> int:
    """Weights the MXU multiplies per token: 4 h^2 attention projections and
    2 h f feed-forward per layer, plus the h x vocab head. Embedding look-ups
    are gathers and do not count."""
    h, L = d["hidden"], d["n_layers"]
    f, v = d["ffn_size"], d["vocab_size"]
    return L * (4 * h * h + 2 * h * f) + h * v


def encoder_train_flops_per_token(d: dict, seq: int) -> float:
    """6 N over the matmul parameters plus the attention scores' 12 L h T
    (2 FLOPs x 2 matmuls [QK^T, PV] x 3 for training x h T per layer)."""
    return 6.0 * matmul_params(d) + 12.0 * d["n_layers"] * d["hidden"] * seq


def decoder_flops_per_token(d: dict, attended: float) -> float:
    """Forward only: 2 N plus 4 L h per cache entry attended (QK^T and PV
    over ``attended`` keys, all heads together)."""
    return 2.0 * matmul_params(d) + \
        4.0 * d["n_layers"] * d["hidden"] * attended


def _heads(d: dict):
    return d["n_heads"], d["hidden"] // d["n_heads"]


def encoder_train_step_slice(s: dict):
    tokens = s["steps"] * s["batch"] * s["seq"]
    return tokens * encoder_train_flops_per_token(s["model"], s["seq"]), None


def decoder_serve_slice(s: dict):
    """Every token decoded in the slice attends its own position + 1
    entries; every prompt of length n prefilled in it attends 1..n. Both
    lists hold ``[entries or n, share]``: the share of the token's (or the
    prefill's) time that lies inside the slice."""
    m = s["model"]
    flops = sum(w * decoder_flops_per_token(m, a)
                for a, w in s["decode_attended"])
    for n, w in s["prefill_lengths"]:
        flops += w * n * decoder_flops_per_token(m, (n + 1) / 2.0)
    return flops, None


def flash_fwd_call(s: dict):
    """Non-causal attention forward over [b, heads, t, d]: QK^T and PV, 2 t^2
    d multiply-adds a head; reads q, k, v and writes o once, plus the f32
    log-sum-exp row the backward needs."""
    n, d = _heads(s["model"])
    b, t, w = s["batch"], s["seq"], s["dtype_bytes"]
    return 4.0 * b * n * t * t * d, 4.0 * b * n * t * d * w + 4.0 * b * n * t


def flash_bwd_call(s: dict):
    """Attention backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q,
    four t^2 d matmuls a head. Rebuilding P inside the kernels is
    recomputation and does not count. Reads q, k, v, o, dO and the lse row,
    writes dq, dk, dv."""
    n, d = _heads(s["model"])
    b, t, w = s["batch"], s["seq"], s["dtype_bytes"]
    return 8.0 * b * n * t * t * d, 8.0 * b * n * t * d * w + 4.0 * b * n * t


def flash_decode_call(s: dict):
    """One layer's single-query attention of one decode step, averaged over
    the slice's steps: each active row reads the K and V entries up to its
    own position (what the algorithm needs, not the blocks a kernel happens
    to fetch), 2 FLOPs x 2 products per entry and head dimension."""
    n, d = _heads(s["model"])
    steps = max(1, s["decode_steps"])
    entries = sum(a * w for a, w in s["decode_attended"]) / steps
    rows = sum(w for _, w in s["decode_attended"]) / steps
    w = s["dtype_bytes"]
    return (4.0 * entries * n * d,
            2.0 * entries * n * d * w + 2.0 * rows * n * d * w)
