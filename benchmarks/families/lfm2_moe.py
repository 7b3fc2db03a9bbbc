"""Family ``lfm2_moe``: the zoo's ``Lfm2MoeLM`` (LFM2-8B-A1B, LiquidAI,
2025-10; https://huggingface.co/LiquidAI/LFM2-8B-A1B), whole or as the
leading layers of it (a pipeline stage: ``layer_types`` is then the first
entries of the published list); every width is the published one. ``N`` is
an RMSNorm (``x / rms(x) * g``) with a gain of its own each time it is
written; no matrix has a bias.

    layer i : h = x + Op(N(x));  y = h + FF(N(h))
              Op = Conv or Attn by layer_types[i]; FF the dense gated FFN
              for i < n_dense_layers, MoE from there on
    Conv(u) : [B; C; X] = Win u (three blocks of hidden, in this order);
              z_t = sum_j wc[:, j] * (B * X)_{t - (L - 1) + j}, j = 0..L-1
              (causal, depthwise, zeros left of position 0; L =
              conv_L_cache); Conv(u)_t = Wout (C_t * z_t). No activation
    Attn(u) : q = Wq u (n_heads x d), k = Wk u, v = Wv u (n_kv_heads x d);
              every query and key head through N over its d numbers (ONE
              gain gq for the query heads, one gk for the key heads), then
              rotated (rotate-half over the whole head, positions from 0);
              query head h attends K/V head h // (n_heads / n_kv_heads);
              scores q . k d^-1/2, causal softmax; Attn(u) = Wo [heads x d]
    FFN(x)  : W2 (silu(W1 x) * W3 x); a routed expert is the same at its
              own width
    MoE(u)  : s = sigmoid(Wr u) over n_experts; the top_k largest of s + b
              are chosen (b the served expert_bias: it moves the choice,
              never the weight); w_e = routed_scaling_factor s_e / (the sum
              of s over the chosen + 1e-6); MoE(u) = sum_chosen w_e E_e(u)
    logits = N(x_L) E^T, E the embedding's matrix (the head is tied)

The reference is float32, "highest", every product through
``harness.reference.mm``; the convolution is L shifted products, attention
runs one K/V head (its group of query heads) at a time, the experts one at
a time under a mask; no kernel, no cache; it imports nothing of the
program. A matrix, or one expert, goes to float32 where it is used.

The canonical tree has no groups: a layer's leaves carry the layer's number
(``l03_wq``), because the layers differ in their parts; the 32 experts of a
layer are one leaf a matrix, stacked on a leading axis.

Work counts are MODEL operations (a multiply-add is 2 FLOPs).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

MOE_CHOICES = "dl4j_tpu_moe_choices_total"
NORM_TOPK_EPS = 1e-6


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``
    (the zoo class's own arguments)."""
    m = config["model"]
    d = {k: int(m[k]) for k in (
        "vocab_size", "hidden", "n_dense_layers", "n_heads", "n_kv_heads",
        "ffn_size", "expert_ffn_size", "n_experts", "top_k", "conv_L_cache",
        "max_len")}
    d["layer_types"] = list(m["layer_types"])
    d["n_layers"] = len(d["layer_types"])
    d["head_dim"] = d["hidden"] // d["n_heads"]
    d["norm_topk_prob"] = bool(m.get("norm_topk_prob", True))
    d["routed_scaling_factor"] = float(m.get("routed_scaling_factor", 1.0))
    d["rope_theta"] = float(m["rope_theta"])
    d["eps"] = float(m.get("eps", 1e-5))
    return d


def is_conv(d: dict, i: int) -> bool:
    return d["layer_types"][i] == "conv"


def layer_kinds(d: dict) -> tuple:
    """``(convolution layers, attention layers)`` of the layers held."""
    conv = sum(is_conv(d, i) for i in range(d["n_layers"]))
    return conv, d["n_layers"] - conv


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    return {}


def layer_leaves(d: dict, i: int) -> dict:
    """Layer ``i``'s keys (without the layer's number) and shapes."""
    h, hd = d["hidden"], d["head_dim"]
    out = {"g1": (h,), "g2": (h,)}
    if is_conv(d, i):
        out |= {"win": (h, 3 * h), "wc": (h, d["conv_L_cache"]),
                "wout": (h, h)}
    else:
        out |= {"wq": (h, d["n_heads"] * hd), "wk": (h, d["n_kv_heads"] * hd),
                "wv": (h, d["n_kv_heads"] * hd), "wo": (d["n_heads"] * hd, h),
                "gq": (hd,), "gk": (hd,)}
    if i < d["n_dense_layers"]:
        f = d["ffn_size"]
        out |= {"w1": (h, f), "w3": (h, f), "w2": (f, h)}
    else:
        e, f = d["n_experts"], d["expert_ffn_size"]
        out |= {"wr": (h, e), "br": (e,), "eg": (e, h, f), "eu": (e, h, f),
                "ed": (e, f, h)}
    return out


def leaves(d: dict) -> dict:
    out = {"tok_emb": (None, (d["vocab_size"], d["hidden"])),
           "gf": (None, (d["hidden"],))}
    for i in range(d["n_layers"]):
        out |= {f"l{i:02d}_{k}": (None, shape)
                for k, shape in layer_leaves(d, i).items()}
    return out


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices Xavier-normal over their last two dimensions; gains lie
    round 1 with a random part (a dropped gain shows), the two QK-norm
    gains with a larger one (0.25: a norm left out, or a gain taken from
    the wrong side, shows where Xavier's own scale would hide it). ASSUMED,
    since the published config fixes none of them: the embedding's rows
    are unit normal scaled by hidden^-1/2 like the head it is tied to; the
    convolution's taps have the standard deviation L^-1/2; the router's
    columns 1.5 hidden^-1/2, so that the scores of a normed input spread
    over (0.1, 0.9) and not round 0.5; the served ``expert_bias`` is normal
    with the standard deviation 0.055 (the four largest of 32 such scores
    lie within a few hundredths of each other), which makes the experts'
    loads uneven: by simulation at the published widths (32 logits of
    standard deviation 1.5, sigmoid, top-4 of score + bias, 8,192 tokens a
    seed) the hottest expert takes 1.50-2.70 times the mean over 200 seeds,
    1.94 at the median."""
    k = key.split("_", 1)[-1]
    if k == "br":
        return 0.0, 0.055
    if k in ("gq", "gk"):
        return 1.0, 0.25
    if len(shape) == 1:
        return 1.0, 0.02
    if k == "wr":
        return 0.0, 1.5 * shape[0] ** -0.5
    if k == "wc":
        return 0.0, shape[-1] ** -0.5
    if key == "tok_emb":
        return 0.0, shape[-1] ** -0.5
    return 0.0, math.sqrt(2.0 / (shape[-2] + shape[-1]))


# ---------------------------------------------------------- plain reference
def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, theta):
    """x [..., t, d], positions 0..t-1: rotate-half over the d numbers."""
    t, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)        # [d/2]
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [t, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _conv(u, w, d, quant):
    """u [b, t, h] (normed) -> the convolution mixer's output [b, t, h]."""
    h, taps = d["hidden"], d["conv_L_cache"]
    bcx = mm(u, w["win"].astype(F32), quant)
    bx = bcx[..., :h] * bcx[..., 2 * h:]
    t = bx.shape[1]
    ext = jnp.pad(bx, ((0, 0), (taps - 1, 0), (0, 0)))
    wc = w["wc"].astype(F32)
    z = sum(wc[:, j] * ext[:, j:j + t] for j in range(taps))
    return mm(bcx[..., h:2 * h] * z, w["wout"].astype(F32), quant)


def _attn(u, w, d, quant):
    """u [b, t, h] (normed) -> the attention's output [b, t, h]."""
    b, t, _ = u.shape
    n, nkv, hd, eps = d["n_heads"], d["n_kv_heads"], d["head_dim"], d["eps"]
    g = n // nkv

    def heads(a, count):            # [b, t, count * hd] -> [b, count, t, hd]
        return a.reshape(b, t, count, hd).transpose(0, 2, 1, 3)

    q = heads(mm(u, w["wq"].astype(F32), quant), n)
    k = heads(mm(u, w["wk"].astype(F32), quant), nkv)
    v = heads(mm(u, w["wv"].astype(F32), quant), nkv)
    q = _rope(_norm(q, w["gq"], eps), d["rope_theta"])
    k = _rope(_norm(k, w["gk"], eps), d["rope_theta"])
    causal = jnp.tril(jnp.ones((t, t), bool))

    def pair(qkv):                  # one K/V head and its group of queries
        qi, ki, vi = qkv            # [b, g, t, hd], [b, t, hd], [b, t, hd]
        s = jnp.where(causal, hd ** -0.5 * mm(
            qi, ki[:, None].transpose(0, 1, 3, 2), quant), -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vi[:, None], quant)

    # query head h reads K/V head h // g: the heads of a group lie together
    o = jax.lax.map(pair, (
        q.reshape(b, nkv, g, t, hd).transpose(1, 0, 2, 3, 4),
        k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))  # [nkv,b,g,t,hd]
    o = o.transpose(1, 3, 0, 2, 4).reshape(b, t, n * hd)
    return mm(o, w["wo"].astype(F32), quant)


def _ffn(u, w1, w3, w2, quant):
    return mm(jax.nn.silu(mm(u, w1.astype(F32), quant))
              * mm(u, w3.astype(F32), quant), w2.astype(F32), quant)


def route(u, wr, br, d, quant=None):
    """u [..., h] -> the weight every expert carries for every token,
    ``[..., n_experts]`` (nought where it is not chosen): sigmoid scores in
    float32, the choice by ``s + b``, the chosen weights renormalised."""
    s = jax.nn.sigmoid(mm(u, wr.astype(F32), quant))
    _, idx = jax.lax.top_k(s + br.astype(F32), d["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    w = s * chosen
    if d["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return d["routed_scaling_factor"] * w


def _moe(u, w, d, quant):
    """Every expert over every token, one expert in float32 at a time,
    under the mask of the router's weights."""
    gate = route(u, w["wr"], w["br"], d, quant)              # [b, t, E]

    def one(m, e):
        eg, eu, ed, ge = e
        return m + ge[..., None] * _ffn(u, eg, eu, ed, quant), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        w["eg"], w["eu"], w["ed"], jnp.moveaxis(gate, -1, 0)))
    return m


def layer_weights(w: dict, i: int) -> dict:
    pre = f"l{i:02d}_"
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


def _layer(x, w, i, d, quant):
    eps = d["eps"]
    op = _conv if is_conv(d, i) else _attn
    h1 = x + op(_norm(x, w["g1"], eps), w, d, quant)
    u = _norm(h1, w["g2"], eps)
    if i < d["n_dense_layers"]:
        return h1 + _ffn(u, w["w1"], w["w3"], w["w2"], quant)
    return h1 + _moe(u, w, d, quant)


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab_size] of the full causal forward
    of the layers held."""
    emb = w["tok_emb"].astype(F32)
    x = emb[ids]
    for i in range(d["n_layers"]):
        x = _layer(x, layer_weights(w, i), i, d, quant)
    return mm(_norm(x, w["gf"], d["eps"]), emb.T, quant)


# ------------------------------------------------------------ state's bytes
def kv_entry_bytes(d: dict, dtype_bytes: int) -> int:
    """A position's keys and values in ONE attention layer."""
    return 2 * d["n_kv_heads"] * d["head_dim"] * dtype_bytes


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds a key and a value of
    ``n_kv_heads`` heads a position in every attention layer, and the last
    ``conv_L_cache - 1`` columns of every convolution, whatever its
    position."""
    conv, attn = layer_kinds(d)
    return position * attn * kv_entry_bytes(d, dtype_bytes) \
        + conv * (d["conv_L_cache"] - 1) * d["hidden"] * dtype_bytes


# ------------------------------------------------------------- work counts
def matmul_params(d: dict) -> int:
    """Weights a token passes outside the experts, every layer held, and
    the head's columns. The embedding is a gather; the convolution's taps
    and the products of its gates are counted apart."""
    h, hd = d["hidden"], d["head_dim"]
    conv, attn = layer_kinds(d)
    total = conv * (3 * h * h + h * h) \
        + attn * 2 * (h * d["n_heads"] * hd + h * d["n_kv_heads"] * hd)
    dense = min(d["n_dense_layers"], d["n_layers"])
    total += dense * 3 * h * d["ffn_size"]
    total += (d["n_layers"] - dense) * h * d["n_experts"]
    return total + h * d["vocab_size"]


def expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["expert_ffn_size"]


def _attn_flops(d: dict, entries: float) -> float:
    """QK^T and PV over ``entries`` keys, every query head and every
    attention layer held."""
    return 4.0 * d["n_heads"] * d["head_dim"] * layer_kinds(d)[1] * entries


def _conv_flops(d: dict) -> float:
    """The taps of every convolution held, a token."""
    return 2.0 * d["conv_L_cache"] * d["hidden"] * layer_kinds(d)[0]


def held_pairs(s: dict) -> float:
    """Token-expert pairs the experts computed in the slice, every layer:
    the program's counter (every expert is held here)."""
    return sum(v for k, v in s.get("counters", {}).get(
        MOE_CHOICES, {}).items() if k.split(",")[-1] == "held")


def lfm2_serve_slice(s: dict):
    """Every token decoded in the slice passes the weights outside the
    experts and attends what its position makes valid; a prompt of n tokens
    runs the trunk over n positions and the head at the last. The experts'
    part is 2 x an expert's parameters a token-expert pair, from the
    program's counter: only the run knows where the router sent its
    tokens."""
    d = s["model"]
    head = 2.0 * d["hidden"] * d["vocab_size"]
    trunk = 2.0 * matmul_params(d) - head + _conv_flops(d)
    flops = sum(share * (trunk + head + _attn_flops(d, a))
                for a, share in s["decode_attended"])
    for n, share in s["prefill_lengths"]:
        flops += share * (n * trunk + head
                          + _attn_flops(d, n * (n + 1) / 2.0))
    return flops + 2.0 * expert_params(d) * held_pairs(s), None


def lfm2_gqa_decode_call(s: dict):
    """One attention layer's single-query attention of one decode step over
    its K/V planes, averaged over the slice's steps: each active row reads
    the keys and values its position makes valid ONCE for the query heads
    that share them (not the blocks a kernel fetches), its heads' queries,
    and writes their outputs."""
    d = s["model"]
    steps = max(1, s["decode_steps"])
    entries = sum(a * share for a, share in s["decode_attended"]) / steps
    rows = sum(share for _, share in s["decode_attended"]) / steps
    wb, wide = s["dtype_bytes"], d["n_heads"] * d["head_dim"]
    return 4.0 * wide * entries, \
        entries * kv_entry_bytes(d, wb) + rows * 2 * wide * wb
