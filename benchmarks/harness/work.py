"""Operations and bytes that the algorithm needs, from shapes alone.

A multiply-add is 2 FLOPs; training is 3x the forward matmul work (the
backward does twice the forward's). These are MODEL operations: what the
mathematics requires whatever implements it, so recomputation inside a
kernel never counts and a later kernel cannot make its own roofline stale.

Every ``*_slice`` function takes the description of the traced slice that a
driver records (see ``train_driver`` / ``serve_driver``) and returns
``(flops, bytes)`` of ONE call of the kernel, averaged over the slice's
calls, or of the whole slice for the step functions. ``bytes`` is ``None``
where a step's bytes are not modelled.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Weights the MXU multiplies per token: 4 h^2 attention projections and
    2 h f feed-forward per layer, plus the h x vocab head. Embedding look-ups
    are gathers and do not count."""
    h, L = model["hidden"], model["n_layers"]
    f, v = model["ffn_size"], model["vocab_size"]
    return L * (4 * h * h + 2 * h * f) + h * v


def encoder_train_flops_per_token(model: dict, seq: int) -> float:
    """6 N over the matmul parameters plus the attention scores' 12 L h T
    (2 FLOPs x 2 matmuls [QK^T, PV] x 3 for training x h T per layer)."""
    return 6.0 * matmul_params(model) + \
        12.0 * model["n_layers"] * model["hidden"] * seq


def decoder_flops_per_token(model: dict, attended: float) -> float:
    """Forward only: 2 N plus 4 L h per cache entry attended (QK^T and PV
    over ``attended`` keys, all heads together)."""
    return 2.0 * matmul_params(model) + \
        4.0 * model["n_layers"] * model["hidden"] * attended


def _heads(model: dict):
    return model["n_heads"], model["hidden"] // model["n_heads"]


# --------------------------------------------------------------- whole steps
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


# ------------------------------------------------------------------- kernels
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
