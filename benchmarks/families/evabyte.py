"""Family ``evabyte``: the zoo's ``EvaByteLM`` (EvaByte 6.5B, 2025-01; its
attention is EVA, Zheng et al. 2023, arXiv:2302.04542). A byte-level decoder:
RMSNorm with the unit offset, rotary positions, a gated SiLU feed-forward
without biases, an untied head of ``n_pred_heads`` x ``vocab_size`` columns,
and the EVA mixer: a query attends, UNDER ONE SOFTMAX, the singletons of its
own aligned window of ``window`` positions (causally) and one learned summary
for every chunk of ``chunk`` positions of every window closed before it.

    N(x; g)  = x / sqrt(mean(x^2) + eps) * (1 + g)
    block    : h = x + Attn(N(x; g1));  y = h + Wd (silu(Wg u) * (Wu u)),  u = N(h; g2)
    Attn     : q, k, v = heads(u Wq, u Wk, u Wv), rotary on q and k (rotate-half
               over the whole head, positions from 0); s = d^-1/2; per head two
               learned vectors mu, phi:
               chunk j:  k~_j = sum_t softmax_t(s mu.k_t) k_t
                         v~_j = sum_t softmax_t(s phi.k_t) v_t     (t in the chunk)
               query i in window W = i // window attends t in [window W, i]
               (logit s q_i.k_t, value v_t) and j < (window/chunk) W (logit
               s q_i.k~_j, value v~_j); o = merge(...) Wo
    logits   = N(x_L; gf) Wh,  Wh: hidden x (n_pred_heads vocab); head 0 is the
               next byte.

Everything of the yardstick that depends on the architecture is here, under
the names ``run.py``'s docstring gives a family. The reference is float32,
"highest", every product through ``harness.reference.mm``, no kernel, no
cache; it imports nothing of the program. It attends window by window
(``lax.map``), so that a sampled sequence of 32,768 positions fits one chip
once the engine is freed: the scores alive at a time are one window's.

Work counts are MODEL operations from shapes and positions (a multiply-add
is 2 FLOPs), whatever implements them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

BLOCK_KEYS = ("g1", "wq", "wk", "wv", "wo", "mu", "phi", "g2", "wg", "wu",
              "wd")
WINDOWS_CLOSED = "dl4j_tpu_decode_windows_closed_total"


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``
    (the zoo class's own arguments)."""
    m = config["model"]
    d = {k: int(m[k]) for k in ("vocab_size", "hidden", "n_layers", "n_heads",
                                "ffn_size", "window", "chunk", "n_pred_heads",
                                "max_len")}
    d["rope_theta"] = float(m["rope_theta"])
    d["eps"] = float(m.get("eps", 1e-5))
    return d


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    return {"block": d["n_layers"]}


def leaves(d: dict) -> dict:
    h, f, v, n = d["hidden"], d["ffn_size"], d["vocab_size"], d["n_heads"]
    block = {"g1": (h,), "wq": (h, h), "wk": (h, h), "wv": (h, h),
             "wo": (h, h), "mu": (n, h // n), "phi": (n, h // n), "g2": (h,),
             "wg": (h, f), "wu": (h, f), "wd": (f, h)}
    top = {"tok_emb": (v, h), "gf": (h,),
           "head_w": (h, d["n_pred_heads"] * v)}
    return {k: (None, s) for k, s in top.items()} | \
        {k: ("block", block[k]) for k in BLOCK_KEYS}


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices Xavier-normal; the gains enter as ``1 + g``, so they lie
    round 0 with a random part (a dropped gain shows); ``mu`` and ``phi``
    are normal with the standard deviation head_dim^-1/2."""
    if key in ("mu", "phi"):
        return 0.0, shape[-1] ** -0.5
    if len(shape) == 1:
        return 0.0, 0.02
    return 0.0, math.sqrt(2.0 / (shape[-2] + shape[-1]))


# ---------------------------------------------------------- plain reference
def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x [b, n, t, d], positions 0..t-1: rotate-half over the whole head."""
    t, d = x.shape[2], x.shape[3]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)        # [d/2]
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [t, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _attend(q, k, v, mu, phi, d, quant):
    """EVA over [b, n, t, dh], t a multiple of the window."""
    b, n, t, dh = q.shape
    w, c = d["window"], d["chunk"]
    m, nw = w // c, t // w
    s = dh ** -0.5
    kc = k.reshape(b, n, t // c, c, dh)
    vc = v.reshape(b, n, t // c, c, dh)
    # one summary a chunk: softmax over the chunk's own positions
    pk = jax.nn.softmax(
        s * mm(kc, mu[None, :, None, :, None], quant)[..., 0], axis=-1)
    pv = jax.nn.softmax(
        s * mm(kc, phi[None, :, None, :, None], quant)[..., 0], axis=-1)
    ks = mm(pk[..., None, :], kc, quant)[..., 0, :]             # [b,n,t/c,dh]
    vs = mm(pv[..., None, :], vc, quant)[..., 0, :]
    causal = jnp.tril(jnp.ones((w, w), bool))
    j = jnp.arange(t // c)

    def window(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * w, w, axis=2)
        ki = jax.lax.dynamic_slice_in_dim(k, i * w, w, axis=2)
        vi = jax.lax.dynamic_slice_in_dim(v, i * w, w, axis=2)
        own = jnp.where(causal, s * mm(qi, ki.transpose(0, 1, 3, 2), quant),
                        -jnp.inf)
        old = jnp.where(j < m * i, s * mm(qi, ks.transpose(0, 1, 3, 2), quant),
                        -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([own, old], axis=-1), axis=-1)
        return mm(p[..., :w], vi, quant) + mm(p[..., w:], vs, quant)

    o = jax.lax.map(window, jnp.arange(nw))                     # [nw,b,n,w,dh]
    return o.transpose(1, 2, 0, 3, 4).reshape(b, n, t, dh)


def _block(x, wb, d, quant):
    wb = {k: a.astype(F32) for k, a in wb.items()}
    b, t, h = x.shape
    n = d["n_heads"]

    def heads(y):
        return y.reshape(b, t, n, h // n).transpose(0, 2, 1, 3)

    u = _norm(x, wb["g1"], d["eps"])
    q, k, v = (heads(mm(u, wb[name], quant)) for name in ("wq", "wk", "wv"))
    q, k = _rope(q, d["rope_theta"]), _rope(k, d["rope_theta"])
    o = _attend(q, k, v, wb["mu"], wb["phi"], d, quant)
    x = x + mm(o.transpose(0, 2, 1, 3).reshape(b, t, h), wb["wo"], quant)
    u = _norm(x, wb["g2"], d["eps"])
    return x + mm(jax.nn.silu(mm(u, wb["wg"], quant)) * mm(u, wb["wu"], quant),
                  wb["wd"], quant)


def decoder_logits(w, ids, d, quant=None, pred_heads=1):
    """ids [b, t] -> logits [b, t, pred_heads * vocab] of the full forward:
    head 0 (the next byte, what is served) by default, all
    ``n_pred_heads`` heads side by side when asked. Each block's weights go
    to float32 inside the scan, a layer at a time."""
    t = ids.shape[1]
    pad = (-t) % d["window"]
    ids = jnp.pad(ids, ((0, 0), (0, pad)))  # causal: a pad moves nothing before it
    x = w["tok_emb"].astype(F32)[ids]

    def body(x, wb):
        return _block(x, wb, d, quant), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in BLOCK_KEYS})
    hid = _norm(x, w["gf"].astype(F32), d["eps"])[:, :t]
    head = w["head_w"][:, :pred_heads * d["vocab_size"]].astype(F32)
    return mm(hid, head, quant)


# ------------------------------------------------------------ state's bytes
def attended(d: dict, position: float) -> float:
    """Entries a query standing at ``position`` (that many positions before
    it) attends beside itself: the singletons of its own window and the
    summaries of every closed window."""
    w = d["window"]
    return position % w + (w // d["chunk"]) * (position // w)


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds a key and a value of
    ``hidden`` numbers a layer for every singleton of its open window and
    every summary of its closed ones: bounded by window + max_len / chunk
    entries, where a K/V cache holds ``position``."""
    return attended(d, position) * 2 * d["n_layers"] * d["hidden"] \
        * dtype_bytes


# ------------------------------------------------------------- work counts
def matmul_params(d: dict) -> int:
    """Weights the MXU multiplies per byte served: 4 h^2 of projections and
    3 h f of the gated feed-forward a layer, and head 0's h x vocab columns
    (the served path reads no other head). The embedding is a gather."""
    h, f = d["hidden"], d["ffn_size"]
    return d["n_layers"] * (4 * h * h + 3 * h * f) + h * d["vocab_size"]


def _attn_flops(d: dict, entries: float) -> float:
    """QK^T and PV over ``entries`` keys, all heads and layers together."""
    return 4.0 * d["n_layers"] * d["hidden"] * entries


def _summary_flops(d: dict, positions: float) -> float:
    """mu.k, phi.k and the two weighted sums: 8 multiply-adds' worth of
    FLOPs a head dimension and position, every layer."""
    return 8.0 * d["n_layers"] * d["hidden"] * positions


def _prefill_attended(d: dict, n: int) -> float:
    """Sum over the prompt's positions p < n of the entries position p
    attends (itself among them)."""
    w, m = d["window"], d["window"] // d["chunk"]
    full, rest = divmod(int(n), w)
    own = full * w * (w + 1) / 2.0 + rest * (rest + 1) / 2.0
    old = m * (w * full * (full - 1) / 2.0 + rest * full)
    return own + old


def eva_serve_slice(s: dict):
    """Every byte decoded in the slice attends what its position makes
    valid; every prompt of n bytes prefilled in it runs the trunk over n
    positions, the head at the last, and summarises its chunks. The windows
    that decoding closed (the program's counter) are summarised too."""
    d = s["model"]
    trunk = 2.0 * (matmul_params(d) - d["hidden"] * d["vocab_size"])
    head = 2.0 * d["hidden"] * d["vocab_size"]
    flops = sum(share * (trunk + head + _attn_flops(d, attended(d, a - 1) + 1))
                for a, share in s["decode_attended"])
    for n, share in s["prefill_lengths"]:
        flops += share * (n * trunk + head + _summary_flops(d, n)
                          + _attn_flops(d, _prefill_attended(d, n)))
    closed = sum(s.get("counters", {}).get(WINDOWS_CLOSED, {}).values())
    return flops + _summary_flops(d, closed * d["window"]), None


def eva_decode_call(s: dict):
    """One layer's single-query attention of one decode step, averaged over
    the slice's steps: each active row reads the keys and values of the
    singletons and summaries its position makes valid (not the blocks a
    kernel fetches), its query, and writes its output."""
    d = s["model"]
    steps = max(1, s["decode_steps"])
    entries = sum((attended(d, a - 1) + 1) * share
                  for a, share in s["decode_attended"]) / steps
    rows = sum(share for _, share in s["decode_attended"]) / steps
    h, wb = d["hidden"], s["dtype_bytes"]
    return 4.0 * entries * h, 2.0 * entries * h * wb + 2.0 * rows * h * wb
