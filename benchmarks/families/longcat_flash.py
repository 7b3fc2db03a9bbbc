"""Family ``longcat_flash``: the zoo's ``LongCatFlashLM`` (LongCat-Flash-Chat,
560B-A27B, 2025-09; https://huggingface.co/meituan-longcat/LongCat-Flash-Chat)
as ONE CHIP'S SHARE of a layer: of the ``n_routed_experts`` published it
holds ``n_held_experts`` from ``first_held_expert`` on, of the vocabulary
the rows ``vocab_size`` says; every width is the published one. ``N`` is an
RMSNorm with a gain of its own each time it is written.

    layer  : h1 = x  + MLA_0(N(x));   u = N(h1);   m = MoE(u)
             h2 = h1 + FFN_0(u)
             h3 = h2 + MLA_1(N(h2));  y = h3 + FFN_1(N(h3)) + m
    MLA(x) : q = Wqb (N(Wqa x) (hidden/q_lora_rank)^1/2): per head dn
             non-rotary then dr rotary numbers; [c; kr] = Wkva x (rkv + dr);
             c' = N(c) (hidden/kv_lora_rank)^1/2; kr rotated (rotate-half
             over its dr numbers, positions from 0), one for all heads;
             [k_nope; v] = Wkvb c' per head (dn + dv);
             scores (q_nope.k_nope + rot(q_rope).rot(kr)) (dn + dr)^-1/2,
             causal softmax; o = Wo [heads x dv]
    FFN(x) : Wd (silu(Wg x) * Wu x); an expert is the same at its own width
    MoE(u) : s = softmax(Wr u) over n_routed_experts + zero_expert_num; the
             moe_topk largest of s + b are chosen; a chosen e weighs
             routed_scaling_factor s_e (no renormalisation);
             m = sum over the chosen of weight_e E_e(u), E_e the expert's
             FFN for e < n_routed_experts and E_e(u) = u (a zero-compute
             expert) from there on. THE SHARE: a chosen routed expert that
             is not held here adds nothing (its chip would), here and in the
             program alike; the zero-compute experts live on every chip.
    logits = N(x_L) Wh over the held rows of the vocabulary.

The reference is the NON-absorbed form (per-head keys and values expanded
from the latent), float32, "highest", every product through
``harness.reference.mm``, no kernel, no cache; it imports nothing of the
program. A matrix, or one expert, goes to float32 where it is used, inside
the scan over the layers (a layer is 5 GB in float32 beside a tree of
10 GB), and attention runs a block of heads at a time.

Work counts are MODEL operations in the published form (a multiply-add is 2
FLOPs); what the absorbed decode form adds is the program's choice.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

ATTN_KEYS = ("gn", "wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo")
FFN_KEYS = ("gn", "wg", "wu", "wd")
MOE_KEYS = ("wr", "br", "eg", "eu", "ed")
MOE_CHOICES = "dl4j_tpu_moe_choices_total"
HEAD_BLOCK = 16  # heads attended at a time: [16, t, t] float32 scores


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``
    (the zoo class's own arguments): what is HELD beside what is published
    (``n_held_experts`` of ``n_routed_experts``; ``vocab_size`` is the
    slice)."""
    m = config["model"]
    d = {k: int(m[k]) for k in (
        "vocab_size", "hidden", "n_layers", "n_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "ffn_size", "expert_ffn_size", "n_routed_experts", "zero_expert_num",
        "n_held_experts", "moe_topk", "max_len")}
    d["first_held_expert"] = int(m.get("first_held_expert", 0))
    d["routed_scaling_factor"] = float(m["routed_scaling_factor"])
    d["rope_theta"] = float(m["rope_theta"])
    d["eps"] = float(m.get("eps", 1e-5))
    return d


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    """Two attention blocks and two FFNs a layer are groups of their own;
    the router and the held experts (stacked in one leaf a matrix) a
    fifth."""
    n = d["n_layers"]
    return {"attn0": n, "attn1": n, "ffn0": n, "ffn1": n, "moe": n}


def leaves(d: dict) -> dict:
    h, v, n = d["hidden"], d["vocab_size"], d["n_heads"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    f, fe, held = d["ffn_size"], d["expert_ffn_size"], d["n_held_experts"]
    width = d["n_routed_experts"] + d["zero_expert_num"]
    attn = {"gn": (h,), "wqa": (h, rq), "gq": (rq,),
            "wqb": (rq, n * (dn + dr)), "wkva": (h, rkv + dr), "gkv": (rkv,),
            "wkvb": (rkv, n * (dn + dv)), "wo": (n * dv, h)}
    ffn = {"gn": (h,), "wg": (h, f), "wu": (h, f), "wd": (f, h)}
    moe = {"wr": (h, width), "br": (width,), "eg": (held, h, fe),
           "eu": (held, h, fe), "ed": (held, fe, h)}
    out = {"tok_emb": (None, (v, h)), "gf": (None, (h,)),
           "head_w": (None, (h, v))}
    for j in (0, 1):
        out |= {f"a{j}_{k}": (f"attn{j}", attn[k]) for k in ATTN_KEYS}
        out |= {f"f{j}_{k}": (f"ffn{j}", ffn[k]) for k in FFN_KEYS}
    return out | {f"m_{k}": ("moe", moe[k]) for k in MOE_KEYS}


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices Xavier-normal over their last two dimensions. Gains lie
    round 1 with a random part (a dropped gain shows). ASSUMED, since the
    published config fixes neither: the router's columns have the standard
    deviation 1.5 hidden^-1/2, so that the scores of a normed input spread
    by 1.5 and the ``moe_topk`` chosen carry real weight (their
    ``routed_scaling_factor s_e`` sum to the order of 1: with near-uniform
    scores the whole MoE would hide inside the tolerance); the selection
    bias is normal with the standard deviation 3.072 / width, three mean
    scores (0.004 at the published 768 outputs), which makes the held
    experts' loads uneven (the hottest near three times their mean) and a
    bias that leaks into the weights show."""
    if key == "m_br":
        return 0.0, 3.072 / shape[0]
    if len(shape) == 1:
        return 1.0, 0.02
    if key == "m_wr":
        return 0.0, 1.5 * shape[0] ** -0.5
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


def _mla(x, wb, j, d, quant):
    """x [b, t, h] (normed) -> the attention's output [b, t, h]."""
    def w(k):
        return wb[f"a{j}_{k}"]

    b, t, h = x.shape
    n, eps = d["n_heads"], d["eps"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    cq = _norm(mm(x, w("wqa").astype(F32), quant), w("gq"), eps) \
        * math.sqrt(h / rq)
    q = mm(cq, w("wqb").astype(F32), quant).reshape(b, t, n, dn + dr)
    q = q.transpose(0, 2, 1, 3)                                 # [b,n,t,.]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], d["rope_theta"])],
                        axis=-1)
    ckr = mm(x, w("wkva").astype(F32), quant)                   # [b,t,rkv+dr]
    c = _norm(ckr[..., :rkv], w("gkv"), eps) * math.sqrt(h / rkv)
    kr = _rope(ckr[..., rkv:], d["rope_theta"])                 # [b, t, dr]
    kv = mm(c, w("wkvb").astype(F32), quant).reshape(b, t, n, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr[:, None], (b, n, t, dr))], axis=-1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (dn + dr) ** -0.5

    def heads(qkv):                     # a block of heads: [b, hb, t, .]
        qi, ki, vi = qkv
        s = jnp.where(causal, scale * mm(qi, ki.transpose(0, 1, 3, 2), quant),
                      -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vi, quant)

    hb = math.gcd(n, HEAD_BLOCK)

    def cut(a):                         # [b, n, t, .] -> [n/hb, b, hb, t, .]
        return a.reshape(b, n // hb, hb, t, -1).transpose(1, 0, 2, 3, 4)

    o = jax.lax.map(heads, (cut(q), cut(k), cut(v)))            # [.,b,hb,t,dv]
    o = o.transpose(1, 3, 0, 2, 4).reshape(b, t, n * dv)
    return mm(o, w("wo").astype(F32), quant)


def _ffn(u, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(u, wg.astype(F32), quant))
              * mm(u, wu.astype(F32), quant), wd.astype(F32), quant)


def route(u, wr, br, d, quant=None):
    """u [..., h] -> the weight every one of the router's outputs carries
    for every token, ``[..., n_routed_experts + zero_expert_num]`` (nought
    where it is not chosen): softmax in float32, the choice by ``s + b``,
    the weight ``routed_scaling_factor s`` unnormalised."""
    s = jax.nn.softmax(mm(u, wr.astype(F32), quant), axis=-1)
    _, idx = jax.lax.top_k(s + br.astype(F32), d["moe_topk"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    return d["routed_scaling_factor"] * s * chosen


def _moe(u, wb, d, quant):
    """The chip's share: the held experts' part and the zero-compute
    experts' part (they return their input); nothing for the absent."""
    w = route(u, wb["m_wr"], wb["m_br"], d, quant)
    first, held = d["first_held_expert"], d["n_held_experts"]
    m = jnp.sum(w[..., d["n_routed_experts"]:], axis=-1, keepdims=True) * u
    for e in range(held):  # one expert in float32 at a time
        m = m + w[..., first + e, None] * _ffn(
            u, wb["m_eg"][e], wb["m_eu"][e], wb["m_ed"][e], quant)
    return m


def _layer(x, wb, d, quant):
    eps = d["eps"]
    h1 = x + _mla(_norm(x, wb["a0_gn"], eps), wb, 0, d, quant)
    u = _norm(h1, wb["f0_gn"], eps)
    m = _moe(u, wb, d, quant)
    h2 = h1 + _ffn(u, wb["f0_wg"], wb["f0_wu"], wb["f0_wd"], quant)
    h3 = h2 + _mla(_norm(h2, wb["a1_gn"], eps), wb, 1, d, quant)
    return h3 + _ffn(_norm(h3, wb["f1_gn"], eps), wb["f1_wg"], wb["f1_wu"],
                     wb["f1_wd"], quant) + m


def layer_keys(d: dict) -> tuple:
    return tuple(k for k, (g, _) in sorted(leaves(d).items()) if g)


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab_size] of the full causal forward
    of the share."""
    x = w["tok_emb"].astype(F32)[ids]

    def body(x, wb):
        return _layer(x, wb, d, quant), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in layer_keys(d)})
    return mm(_norm(x, w["gf"], d["eps"]), w["head_w"].astype(F32), quant)


# ------------------------------------------------------------ state's bytes
def latent_width(d: dict) -> int:
    return d["kv_lora_rank"] + d["qk_rope_head_dim"]


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds one latent entry of
    ``kv_lora_rank + qk_rope_head_dim`` numbers a position for each of the
    two attention blocks of every layer, whatever the number of heads."""
    return position * 2 * d["n_layers"] * latent_width(d) * dtype_bytes


# ------------------------------------------------------------- work counts
def matmul_params(d: dict) -> int:
    """Weights a token passes outside the experts, every layer, and the
    head's held columns. The embedding is a gather."""
    h, n = d["hidden"], d["n_heads"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    mla = h * rq + rq * n * (dn + dr) + h * (rkv + dr) \
        + rkv * n * (dn + dv) + n * dv * h
    layer = 2 * mla + 2 * 3 * h * d["ffn_size"] \
        + h * (d["n_routed_experts"] + d["zero_expert_num"])
    return d["n_layers"] * layer + h * d["vocab_size"]


def expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["expert_ffn_size"]


def _attn_flops(d: dict, entries: float) -> float:
    """QK^T over dn + dr and PV over dv for ``entries`` keys, every head
    and both blocks of every layer: the published form."""
    return 2.0 * d["n_heads"] * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
                                 + d["v_head_dim"]) \
        * 2 * d["n_layers"] * entries


def held_pairs(s: dict) -> float:
    """Token-expert pairs the held experts computed in the slice, every
    layer: the program's counter."""
    return sum(v for k, v in s.get("counters", {}).get(
        MOE_CHOICES, {}).items() if k.split(",")[-1] == "held")


def longcat_serve_slice(s: dict):
    """Every token decoded in the slice passes the weights outside the
    experts and attends what its position makes valid; a prompt of n tokens
    runs the trunk over n positions and the head at the last. The experts'
    part is 2 x an expert's parameters a token-expert pair that the held
    experts computed, from the program's counter: only the run knows where
    the router sent its tokens."""
    d = s["model"]
    head = 2.0 * d["hidden"] * d["vocab_size"]
    trunk = 2.0 * matmul_params(d) - head
    flops = sum(share * (trunk + head + _attn_flops(d, a))
                for a, share in s["decode_attended"])
    for n, share in s["prefill_lengths"]:
        flops += share * (n * trunk + head
                          + _attn_flops(d, n * (n + 1) / 2.0))
    return flops + 2.0 * expert_params(d) * held_pairs(s), None


def mla_decode_call(s: dict):
    """One attention block's single-query attention of one decode step over
    the latent plane (the absorbed form a kernel runs), averaged over the
    slice's steps: each active row reads the latent entries its position
    makes valid once (not the blocks a kernel fetches), its heads' queries,
    and writes their outputs; a head's score takes the entry's whole width
    and its value the non-rotary part."""
    d = s["model"]
    steps = max(1, s["decode_steps"])
    entries = sum(a * share for a, share in s["decode_attended"]) / steps
    rows = sum(share for _, share in s["decode_attended"]) / steps
    wide, rkv, wb = latent_width(d), d["kv_lora_rank"], s["dtype_bytes"]
    return 2.0 * d["n_heads"] * (wide + rkv) * entries, \
        entries * wide * wb + rows * d["n_heads"] * (wide + rkv) * wb
