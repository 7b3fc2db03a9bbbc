"""Family ``pangu_ultra_moe``: the zoo's ``PanguUltraMoeLM``
(openPangu-Ultra-MoE-718B, 2025; the DeepSeek-V3 layout with sandwich
norms; https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B)
as ONE CHIP'S SHARE of a layer: of the ``n_routed_experts`` published it
holds ``n_held_experts`` from ``first_held_expert`` on, of the vocabulary
the rows ``vocab_size`` says; every width is the published one. ``N`` is an
RMSNorm (``x / rms(x) * g``) with a gain of its own each time it is
written; no matrix has a bias; the residual stream is float32.

    layer  : h = x + N(MLA(N(x)));  y = h + N(FF(N(h)))   (sandwich norms)
    MLA(x) : q = Wqb N(Wqa x): per head dn non-rotary then dr rotary
             numbers; [c; kr] = Wkva x (rkv + dr); c' = N(c); kr rotated
             (rotate-half over its dr numbers, positions from 0), one for
             all heads; [k_nope; v] = Wkvb c' per head (dn + dv); scores
             (q_nope.k_nope + rot(q_rope).rot(kr)) (dn + dr)^-1/2, causal
             softmax; o = Wo [heads x dv]. No LoRA scale factors.
    FF(x)  : Wd (silu(Wg x) * Wu x) at ffn_size in the first n_dense_layers
             layers; from there on an expert layer:
             S(u) + sum over the chosen e of w_e E_e(u), S the shared
             expert (the same FFN at n_shared_experts x expert_ffn_size)
             and E_e a routed expert at expert_ffn_size;
             s = sigmoid(Wr u) over n_routed_experts (float32); the top_k
             largest of s + b are chosen (b the served selection bias: it
             moves the choice, never the weight); w_e =
             routed_scaling_factor s_e / (the sum of s over the chosen +
             1e-6). THE SHARE: a chosen expert that is not held here adds
             nothing (its chip would), here and in the program alike; the
             shared expert is every chip's, for its own rows.
    logits = N(x_L) Wh over the held rows of the vocabulary.
    MTP    : (DeepSeek-V3, arXiv:2412.19437, 2.2) h'_i = M [N(x_L,i);
             N(E[t_{i+1}])]; g = an expert layer's block over h' (its own
             weights); the logits of t_{i+2} are N(g_i) Wh, the same E and
             Wh as the stack's (``mtp_logits``; the harness compares the
             served tokens only, so only the tests and
             ``tools/pangu_mtp_diag.py`` call it).

The reference is the NON-absorbed form of MLA, float32, "highest", every
product through ``harness.reference.mm``, no kernel, no cache; it imports
nothing of the program. A matrix, or one expert, goes to float32 where it is
used, inside the scan over the expert layers (an expert layer is 4 GB in
float32 beside a tree of 12 GB), and attention runs a block of heads at a
time.

The canonical tree: the leading dense layers are the group ``dense``, the
expert layers the group ``moe`` (a key of a group stacked on a leading axis,
so one ``layout`` of formulas serves any depth: the rehearsal's and the
cell's), the MTP module's keys ``mtp_*`` ungrouped. Work counts are MODEL
operations (a multiply-add is 2 FLOPs) in the published form.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

MOE_CHOICES = "dl4j_tpu_moe_choices_total"
SPEC_ACCEPTED = "dl4j_tpu_generate_spec_accepted_total"
NORM_TOPK_EPS = 1e-6
HEAD_BLOCK = 16  # heads attended at a time: [16, t, t] float32 scores


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``
    (the zoo class's own arguments): what is HELD beside what is published
    (``n_held_experts`` of ``n_routed_experts``; ``vocab_size`` is the
    slice)."""
    m = config["model"]
    d = {k: int(m[k]) for k in (
        "vocab_size", "hidden", "n_layers", "n_dense_layers", "n_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "ffn_size", "expert_ffn_size", "n_routed_experts",
        "n_held_experts", "n_shared_experts", "top_k", "max_len")}
    d["first_held_expert"] = int(m.get("first_held_expert", 0))
    d["routed_scaling_factor"] = float(m["routed_scaling_factor"])
    d["rope_theta"] = float(m["rope_theta"])
    d["eps"] = float(m.get("eps", 1e-5))
    return d


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    return {"dense": d["n_dense_layers"],
            "moe": d["n_layers"] - d["n_dense_layers"]}


def _attn_shapes(d: dict) -> dict:
    h, n = d["hidden"], d["n_heads"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    return {"g1": (h,), "wqa": (h, rq), "gq": (rq,),
            "wqb": (rq, n * (dn + dr)), "wkva": (h, rkv + dr),
            "gkv": (rkv,), "wkvb": (rkv, n * (dn + dv)), "wo": (n * dv, h),
            "g2": (h,)}


def _moe_shapes(d: dict) -> dict:
    h, fe, held = d["hidden"], d["expert_ffn_size"], d["n_held_experts"]
    fs = d["n_shared_experts"] * fe
    return {"g3": (h,), "wr": (h, d["n_routed_experts"]),
            "br": (d["n_routed_experts"],), "eg": (held, h, fe),
            "eu": (held, h, fe), "ed": (held, fe, h), "sg": (h, fs),
            "su": (h, fs), "sd": (fs, h), "g4": (h,)}


def leaves(d: dict) -> dict:
    h, v, f = d["hidden"], d["vocab_size"], d["ffn_size"]
    dense = {"g3": (h,), "wg": (h, f), "wu": (h, f), "wd": (f, h),
             "g4": (h,)}
    out = {"tok_emb": (None, (v, h)), "gf": (None, (h,)),
           "head_w": (None, (h, v))}
    for group, part in (("dense", dense), ("moe", _moe_shapes(d))):
        out |= {f"{group}_{k}": (group, s)
                for k, s in (_attn_shapes(d) | part).items()}
    mtp = _attn_shapes(d) | _moe_shapes(d) | {
        "gh": (h,), "ge": (h,), "m": (2 * h, h), "gm": (h,)}
    return out | {f"mtp_{k}": (None, s) for k, s in mtp.items()}


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices Xavier-normal over their last two dimensions; gains lie
    round 1 with a random part (a dropped gain shows). ASSUMED, since the
    published config fixes none of them: the embedding's rows have the
    standard deviation hidden^-1/2 (a normed input's scale: the MTP module
    norms them); the router's columns 1.5 hidden^-1/2, so that the sigmoid
    scores of a normed input spread over (0.05, 0.95) and the chosen carry
    real weight; the served selection bias is normal with the standard
    deviation 0.02 (about four spacings of the top scores of 256), which
    makes the held experts' loads uneven (PERF.md gives the simulation)."""
    k = key.split("_", 1)[-1]
    if k == "br":
        return 0.0, 0.02
    if len(shape) == 1:
        return 1.0, 0.02
    if k == "wr":
        return 0.0, 1.5 * shape[-2] ** -0.5
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


def _mla(x, w, d, quant):
    """x [b, t, h] (normed) -> the attention's output [b, t, h]. The heads'
    queries, keys and values are expanded a block of heads at a time, and
    the block's share of the output projection added up: what is alive is
    a block's, not 128 heads' keys and values over every position."""
    b, t, h = x.shape
    n, eps = d["n_heads"], d["eps"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    hb = math.gcd(n, HEAD_BLOCK)
    cq = _norm(mm(x, w["wqa"].astype(F32), quant), w["gq"], eps)
    ckr = mm(x, w["wkva"].astype(F32), quant)                   # [b,t,rkv+dr]
    c = _norm(ckr[..., :rkv], w["gkv"], eps)
    kr = _rope(ckr[..., rkv:], d["rope_theta"])                 # [b, t, dr]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (dn + dr) ** -0.5

    def blocks(m, width):   # [rows, n * width] -> [n/hb, rows, hb * width]
        return m.reshape(m.shape[0], n // hb, hb * width).transpose(1, 0, 2)

    def heads(acc, ws):                 # one block of heads
        wqb, wkvb, wo = ws
        q = mm(cq, wqb.astype(F32), quant).reshape(b, t, hb, dn + dr)
        q = q.transpose(0, 2, 1, 3)                             # [b,hb,t,.]
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:],
                                                d["rope_theta"])], axis=-1)
        kv = mm(c, wkvb.astype(F32), quant).reshape(b, t, hb, dn + dv)
        kv = kv.transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(kr[:, None], (b, hb, t, dr))],
            axis=-1)
        s = jnp.where(causal, scale * mm(q, k.transpose(0, 1, 3, 2), quant),
                      -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), kv[..., dn:], quant)  # [b,hb,t,dv]
        o = o.transpose(0, 2, 1, 3).reshape(b, t, hb * dv)
        return acc + mm(o, wo.astype(F32), quant), None

    out, _ = jax.lax.scan(
        heads, jnp.zeros((b, t, h), F32),
        (blocks(w["wqb"], dn + dr), blocks(w["wkvb"], dn + dv),
         w["wo"].reshape(n // hb, hb * dv, h)))
    return out


def _ffn(u, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(u, wg.astype(F32), quant))
              * mm(u, wu.astype(F32), quant), wd.astype(F32), quant)


def route(u, wr, br, d, quant=None):
    """u [..., h] -> the weight every routed expert carries for every token,
    ``[..., n_routed_experts]`` (nought where it is not chosen): sigmoid
    scores in float32, the choice by ``s + b``, the chosen weights
    renormalised over ALL the chosen and scaled."""
    s = jax.nn.sigmoid(mm(u, wr.astype(F32), quant))
    _, idx = jax.lax.top_k(s + br.astype(F32), d["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    w = s * chosen
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return d["routed_scaling_factor"] * w


def _moe(u, w, d, quant, shared=True):
    """The chip's share: the held experts' part, one expert in float32 at a
    time under the router's mask, and the shared expert."""
    gate = route(u, w["wr"], w["br"], d, quant)
    first, held = d["first_held_expert"], d["n_held_experts"]
    mine = jnp.moveaxis(gate[..., first:first + held], -1, 0)

    def one(m, e):
        eg, eu, ed, ge = e
        return m + ge[..., None] * _ffn(u, eg, eu, ed, quant), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (w["eg"], w["eu"], w["ed"], mine))
    if shared:
        m = m + _ffn(u, w["sg"], w["su"], w["sd"], quant)
    return m


def _block(x, w, d, quant, moe: bool):
    eps = d["eps"]
    h = x + _norm(_mla(_norm(x, w["g1"], eps), w, d, quant), w["g2"], eps)
    u = _norm(h, w["g3"], eps)
    f = _moe(u, w, d, quant) if moe else _ffn(u, w["wg"], w["wu"], w["wd"],
                                              quant)
    return h + _norm(f, w["g4"], eps)


def _group(w, group: str) -> dict:
    return {k[len(group) + 1:]: v for k, v in w.items()
            if k.startswith(group + "_")}


def hidden_states(w, ids, d, quant=None):
    """ids [b, t] -> the stack's last output [b, t, hidden], before the
    final norm."""
    x = w["tok_emb"].astype(F32)[ids]
    for group, moe in (("dense", False), ("moe", True)):
        if groups(d)[group]:
            x, _ = jax.lax.scan(
                lambda x, wb, moe=moe: (_block(x, wb, d, quant, moe), None),
                x, _group(w, group))
    return x


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab_size] of the full causal forward
    of the share."""
    x = hidden_states(w, ids, d, quant)
    return mm(_norm(x, w["gf"], d["eps"]), w["head_w"].astype(F32), quant)


def mtp_logits(w, ids, d, quant=None):
    """ids [b, t] -> the MTP module's logits [b, t - 1, vocab_size]:
    position ``i`` pairs the stack's output at ``i`` with the id at ``i +
    1`` and predicts the id at ``i + 2``."""
    x = hidden_states(w, ids, d, quant)[:, :-1]
    m, eps = _group(w, "mtp"), d["eps"]
    e = w["tok_emb"].astype(F32)[ids[:, 1:]]
    hp = mm(jnp.concatenate([_norm(x, m["gh"], eps), _norm(e, m["ge"], eps)],
                            axis=-1), m["m"].astype(F32), quant)
    g = _block(hp, m, d, quant, moe=True)
    return mm(_norm(g, m["gm"], eps), w["head_w"].astype(F32), quant)


# ------------------------------------------------------------ state's bytes
def latent_width(d: dict) -> int:
    return d["kv_lora_rank"] + d["qk_rope_head_dim"]


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds one latent entry a position
    in every layer and in the MTP module, whatever the number of heads."""
    return position * (d["n_layers"] + 1) * latent_width(d) * dtype_bytes


# ------------------------------------------------------------- work counts
def _mla_params(d: dict) -> int:
    h, n = d["hidden"], d["n_heads"]
    dn, dr, dv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    return h * rq + rq * n * (dn + dr) + h * (rkv + dr) \
        + rkv * n * (dn + dv) + n * dv * h


def expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["expert_ffn_size"]


def _moe_layer_params(d: dict) -> int:
    """An expert layer's weights a token passes outside the routed experts:
    attention, router, shared expert."""
    return _mla_params(d) + d["hidden"] * d["n_routed_experts"] \
        + d["n_shared_experts"] * expert_params(d)


def trunk_params(d: dict) -> int:
    """Weights a token passes in the stack outside the routed experts."""
    dense = _mla_params(d) + 3 * d["hidden"] * d["ffn_size"]
    return d["n_dense_layers"] * dense \
        + (d["n_layers"] - d["n_dense_layers"]) * _moe_layer_params(d)


def mtp_params(d: dict) -> int:
    """The MTP module's: its projection and its block outside the routed
    experts."""
    return 2 * d["hidden"] ** 2 + _moe_layer_params(d)


def _attn_flops(d: dict, entries: float, layers: int) -> float:
    """QK^T over dn + dr and PV over dv for ``entries`` keys, every head of
    ``layers`` layers: the published form."""
    return 2.0 * d["n_heads"] * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
                                 + d["v_head_dim"]) * layers * entries


def held_pairs(s: dict) -> float:
    """Token-expert pairs the held experts computed in the slice, every
    layer and the MTP module: the program's counter."""
    return sum(v for k, v in s.get("counters", {}).get(
        MOE_CHOICES, {}).items() if k.split(",")[-1] == "held")


def accepted_drafts(s: dict) -> float:
    """Drafts the slice's steps kept: the program's counter."""
    return sum(s.get("counters", {}).get(SPEC_ACCEPTED, {}).values())


def pangu_serve_slice(s: dict):
    """The model work of the slice. A decode step of a row (one per token
    served, less the drafts kept: a kept draft rides in the step that
    verified it) runs the stack over TWO positions (the verify of the last
    token and the draft), the head at both, and the MTP module over both
    and its head at the one drafted from; each position attends what it
    makes valid, in the stack and in the module. A prompt of n tokens runs
    the stack and the module over n positions and both heads at the last.
    The routed experts' part is 2 x an expert's parameters a token-expert
    pair that the held experts computed, stack and module, from the
    program's counter: only the run knows where the router sent its
    tokens."""
    d = s["model"]
    head = 2.0 * d["hidden"] * d["vocab_size"]
    trunk, mtp = 2.0 * trunk_params(d), 2.0 * mtp_params(d)
    layers = d["n_layers"] + 1
    tokens = sum(share for _, share in s["decode_attended"])
    row_steps = max(0.0, tokens - accepted_drafts(s))
    attended = sum(a * share for a, share in s["decode_attended"])
    mean = attended / tokens if tokens else 0.0
    flops = row_steps * (2 * (trunk + mtp) + 3 * head
                         + _attn_flops(d, 2 * mean + 1, layers))
    for n, share in s["prefill_lengths"]:
        flops += share * (n * (trunk + mtp) + 2 * head
                          + _attn_flops(d, n * (n + 1) / 2.0, layers))
    return flops + 2.0 * expert_params(d) * held_pairs(s), None


def mla_verify_call(s: dict):
    """One layer's verify window of one decode step over the latent plane
    (the absorbed form the ``mla_verify`` kernel runs), averaged over the
    slice's steps: each active row reads the latent entries its position
    makes valid ONCE for both query positions and all heads, its 2 x heads
    queries, and writes their outputs; a head's score takes the entry's
    whole width and its value the non-rotary part. The second position
    attends one entry more than the first."""
    d = s["model"]
    steps = max(1, s["decode_steps"])
    tokens = sum(share for _, share in s["decode_attended"])
    row_steps = max(0.0, tokens - accepted_drafts(s)) / steps
    entries = sum(a * share for a, share in s["decode_attended"]) \
        / max(tokens, 1e-9) * row_steps
    wide, rkv, wb = latent_width(d), d["kv_lora_rank"], s["dtype_bytes"]
    flops = 2.0 * d["n_heads"] * (wide + rkv) * (2 * entries + row_steps)
    nbytes = (entries + row_steps) * wide * wb \
        + row_steps * 2 * d["n_heads"] * (wide + rkv) * wb
    return flops, nbytes
