"""Family ``phi4_flash``: the zoo's ``Phi4FlashLM`` (Phi-4-mini-flash-
reasoning, Microsoft, 2025-07, ``phi4flash``;
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning; the SambaY
decoder-hybrid-decoder of Ren et al. 2025, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation"); every width is
the published one. ``LN`` is a LayerNorm with a gain and a bias of its own
each time it is written; no matrix has a bias but Mamba's convolution and
``dt``.

    layer i : h = x + Mix_i(LN(x));  y = h + W2 (silu(Wg u) * Wu u),
              u = LN(h); the residual stream float32. With half = L // 2
              and p = mb_per_layer (the published config class's rule):
      i < half, i % p == 0  : Mamba
      i < half, otherwise   : window differential attention (a window of
                              `sliding_window` positions, the query's own
                              among them: t sees t - W + 1 .. t)
      i == half             : Mamba, whose y (below) is the MEMORY m
      i == half + 1         : full differential attention: THE K/V cache
      i > half + 1, i%p == 0: GMU(u) = W2 (m * silu(W1 u))
      i > half + 1, other   : cross differential attention: queries (Wq,
                              Wo) alone, over layer half + 1's K and V
    Mamba(u): [xs; z] = Win u (two blocks of d_inner);
              xs = silu(conv(xs) + cb) (depthwise, d_conv taps, causal,
              zeros left of position 0); [r; B; C] = Wx xs (dt_rank,
              d_state, d_state); dt = softplus(Wdt r + dtb);
              A = -exp(A_log) [d_inner, d_state];
              s_t = exp(dt_t A) * s_{t-1} + (dt_t xs_t) B_t^T, s_{-1} = 0;
              y_t = s_t C_t + D * xs_t;  Mamba(u) = Wout (y * silu(z))
    DiffAttn: q = Wq u (n_heads x d), k = Wk u, v = Wv u (n_kv_heads x d);
              differential head j (n_heads / 2) has q1 = q[2j], q2 = q[2j+1];
              its K/V pair g = j // (n_heads / n_kv_heads) has k1 = k[2g],
              k2 = k[2g + 1] and v = v[2g] ++ v[2g + 1] (2d);
              o_j = softmax(q1 k1^T / sqrt(d)) v - lam softmax(q2 k2^T /
              sqrt(d)) v (causal; a window's band), lam = exp(lq1 . lk1) -
              exp(lq2 . lk2) + lam_init, lam_init = 0.8 - 0.6 exp(-0.3 i);
              each o_j through an RMSNorm over its 2d with the gain gs, times
              (1 - lam_init); DiffAttn(u) = Wo [o_0 ++ o_1 ++ ...]
    No positional encoding.  logits = LN_f(x_L) E^T, E the embedding (tied)

ASSUMED (the published config leaves them to its class's defaults or to
the paper): the Mamba sizes d_inner = 2 hidden, d_state 16, d_conv 4,
dt_rank = hidden / 16; no positional encoding (SambaY needs none); DIFF
attention in all 16 attention layers with the head pairing above;
lam_init's formula with i 0-based; the memory taken before the gate
silu(z), the D skip in it; no bias on any attention, Mamba or GMU
projection but the convolution's and dt's; the gate first in the MLP's
fc1 (Phi-3's order: here two leaves, ``ffg`` and ``ffu``); the window
counting the query's own position (512 entries); the weight draws of
:func:`init_scale`.

The reference is float32, "highest", every product through
``harness.reference.mm``; the scan a plain ``lax.scan`` over positions,
the window a mask, attention one differential head at a time; the head in
blocks of the vocabulary written into the logits (the logits of 10,240
positions are 8.2 GB beside 7.7 GB of weights: no second copy of either
may stand); no kernel, no cache; it imports nothing of the program. A
matrix goes to float32 where it is used.

The canonical tree: layers of one kind are a group (``sm`` the
self-decoder's Mamba layers, ``sw`` its window layers, ``xg`` the GMUs,
``xc`` the cross layers, member ``k`` the ``k``-th of its kind), the
memory Mamba (``mm_*``) and the full attention layer (``fa_*``) alone.

Work counts are MODEL operations (a multiply-add is 2 FLOPs).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import F32, mm

WINDOW_ENTRIES = "dl4j_tpu_decode_window_entries_attended_total"
GROUP_OF = {"mamba": "sm", "window": "sw", "gmu": "xg", "cross": "xc",
            "memory": "mm", "full": "fa"}
GROUPED = ("sm", "sw", "xg", "xc")
HEAD_COLUMNS = 8192


# --------------------------------------------------------------------- sizes
def dims(config: dict) -> dict:
    """The sizes the yardstick needs, from the configuration's ``model``
    (the zoo class's own arguments)."""
    m = config["model"]
    d = {k: int(m[k]) for k in (
        "vocab_size", "hidden", "n_layers", "mb_per_layer", "n_heads",
        "n_kv_heads", "ffn_size", "sliding_window", "d_inner", "d_state",
        "d_conv", "dt_rank", "max_len")}
    d["head_dim"] = d["hidden"] // d["n_heads"]
    d["half"] = d["n_layers"] // 2
    d["eps"] = float(m.get("eps", 1e-5))
    kinds = [kind(d, i) for i in range(d["n_layers"])]
    for g in GROUPED:
        d["n_" + g] = sum(GROUP_OF[k] == g for k in kinds)
    return d


def kind(d: dict, i: int) -> str:
    half, p = d["half"], d["mb_per_layer"]
    if i < half:
        return "mamba" if i % p == 0 else "window"
    if i <= half + 1:
        return "memory" if i == half else "full"
    return "gmu" if i % p == 0 else "cross"


def member(d: dict, i: int):
    """``(group or leaf prefix, member index or None)`` of layer ``i``."""
    g = GROUP_OF[kind(d, i)]
    if g not in GROUPED:
        return g, None
    return g, sum(GROUP_OF[kind(d, j)] == g for j in range(i))


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# ------------------------------------------------------ canonical weight tree
def groups(d: dict) -> dict:
    return {g: d["n_" + g] for g in GROUPED if d["n_" + g]}


def kind_leaves(d: dict, k: str) -> dict:
    """A layer of kind ``k``: its keys (without the group's prefix) and
    shapes."""
    h, f, hd = d["hidden"], d["ffn_size"], d["head_dim"]
    di, n, r = d["d_inner"], d["d_state"], d["dt_rank"]
    out = {"ln1g": (h,), "ln1b": (h,), "ln2g": (h,), "ln2b": (h,),
           "ffg": (h, f), "ffu": (h, f), "ffd": (f, h)}
    if k in ("mamba", "memory"):
        out |= {"in": (h, 2 * di), "cw": (di, d["d_conv"]), "cb": (di,),
                "xp": (di, r + 2 * n), "dtw": (r, di), "dtb": (di,),
                "alog": (di, n), "dd": (di,), "out": (di, h)}
    elif k == "gmu":
        out |= {"w1": (h, di), "w2": (di, h)}
    else:
        out |= {"wq": (h, d["n_heads"] * hd), "wo": (d["n_heads"] * hd, h),
                "lq1": (hd,), "lk1": (hd,), "lq2": (hd,), "lk2": (hd,),
                "gs": (2 * hd,)}
        if k != "cross":
            out |= {"wk": (h, d["n_kv_heads"] * hd),
                    "wv": (h, d["n_kv_heads"] * hd)}
    return out


def leaves(d: dict) -> dict:
    out = {"tok_emb": (None, (d["vocab_size"], d["hidden"])),
           "lnf_g": (None, (d["hidden"],)), "lnf_b": (None, (d["hidden"],))}
    for k in sorted({kind(d, i) for i in range(d["n_layers"])}):
        g = GROUP_OF[k]
        out |= {f"{g}_{name}": (g if g in GROUPED else None, shape)
                for name, shape in kind_leaves(d, k).items()}
    return out


def init_scale(key: str, shape: tuple) -> tuple:
    """Matrices Xavier-normal over their last two dimensions; LayerNorm
    gains 1 and biases 0, each with a random part of 0.1 (a dropped one
    shows), the heads' norm gain alike. ASSUMED, since the published config
    fixes none of them, and chosen so that the scan is stable, as Mamba's
    own initialisation makes it: ``A_log`` normal round ln 4 with the
    standard deviation 0.8 (Mamba's ``log 1 .. 16``, drawn: the harness
    draws every leaf from a normal), ``D`` 1 +- 0.1, ``dt``'s bias round
    softplus^-1(0.01) = -4.6 with 1.15 (Mamba's dt range [1e-3, 0.1] is
    -6.9 .. -2.25), the convolution's taps with d_conv^-1/2 and its bias
    with 0.1; the four lambda vectors with 0.1; the embedding's rows with
    hidden^-1/2 like the head it is tied to."""
    k = key.split("_", 1)[-1]
    if k == "alog":
        return math.log(4.0), 0.8
    if k == "dtb":
        return -4.6, 1.15
    if k == "cw":
        return 0.0, shape[-1] ** -0.5
    if k in ("lq1", "lk1", "lq2", "lk2", "cb") or k.endswith("b"):
        return 0.0, 0.1
    if len(shape) == 1:          # ln gains, gs, dd
        return 1.0, 0.1
    if key == "tok_emb":
        return 0.0, shape[-1] ** -0.5
    return 0.0, math.sqrt(2.0 / (shape[-2] + shape[-1]))


def layer_weights(w: dict, d: dict, i: int) -> dict:
    g, m = member(d, i)
    pre = g + "_"
    return {k[len(pre):]: (v if m is None else v[m])
            for k, v in w.items() if k.startswith(pre)}


# ---------------------------------------------------------- plain reference
def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _mamba(u, w, d, quant):
    """u [b, t, h] (normed) -> (Mamba(u) [b, t, h], y [b, t, d_inner])."""
    di, n, r, taps = d["d_inner"], d["d_state"], d["dt_rank"], d["d_conv"]
    b, t, _ = u.shape
    xz = mm(u, w["in"].astype(F32), quant)
    ext = jnp.pad(xz[..., :di], ((0, 0), (taps - 1, 0), (0, 0)))
    cw = w["cw"].astype(F32)
    xs = jax.nn.silu(sum(cw[:, j] * ext[:, j:j + t] for j in range(taps))
                     + w["cb"].astype(F32))
    rbc = mm(xs, w["xp"].astype(F32), quant)
    dt = jax.nn.softplus(mm(rbc[..., :r], w["dtw"].astype(F32), quant)
                         + w["dtb"].astype(F32))
    A = -jnp.exp(w["alog"].astype(F32))                          # [di, n]

    def step(s, inp):                                            # [b, di, n]
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return s, mm(s, c_t[:, :, None], quant)[..., 0]

    _, y = jax.lax.scan(step, jnp.zeros((b, di, n), F32), tuple(
        jnp.swapaxes(a, 0, 1) for a in (
            xs, dt, rbc[..., r:r + n], rbc[..., r + n:])))
    y = jnp.swapaxes(y, 0, 1) + w["dd"].astype(F32) * xs
    return mm(y * jax.nn.silu(xz[..., di:]), w["out"].astype(F32), quant), y


def _diff_attn(u, w, d, quant, i, kv=None, window=None):
    """u [b, t, h] (normed) -> (DiffAttn(u) [b, t, h], (k, v)); a cross
    layer is handed ``kv``, a window layer its ``window``."""
    b, t, _ = u.shape
    nh, nkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]

    def heads(a, count):            # [b, t, count * hd] -> [b, count, t, hd]
        return a.reshape(b, t, count, hd).transpose(0, 2, 1, 3)

    q = heads(mm(u, w["wq"].astype(F32), quant), nh)
    if kv is None:
        kv = (heads(mm(u, w["wk"].astype(F32), quant), nkv),
              heads(mm(u, w["wv"].astype(F32), quant), nkv))
    k, v = kv
    lam = jnp.exp(jnp.sum(w["lq1"].astype(F32) * w["lk1"].astype(F32))) \
        - jnp.exp(jnp.sum(w["lq2"].astype(F32) * w["lk2"].astype(F32))) \
        + lambda_init(i)
    at = jnp.arange(t)
    see = at[None, :] <= at[:, None]
    if window is not None:
        see = see & (at[None, :] > at[:, None] - window)
    per_pair = nh // nkv

    def one(j):                     # differential head j
        g = j // per_pair
        vv = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)

        def softmax_v(qm, km):
            s = jnp.where(see, hd ** -0.5 * mm(qm, jnp.swapaxes(km, 1, 2),
                                               quant), -jnp.inf)
            return mm(jax.nn.softmax(s, axis=-1), vv, quant)

        o = softmax_v(q[:, 2 * j], k[:, 2 * g]) \
            - lam * softmax_v(q[:, 2 * j + 1], k[:, 2 * g + 1])
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d["eps"])
        return o * w["gs"].astype(F32) * (1.0 - lambda_init(i))

    o = jax.lax.map(one, jnp.arange(nh // 2))           # [nh/2, b, t, 2hd]
    o = o.transpose(1, 2, 0, 3).reshape(b, t, nh * hd)
    return mm(o, w["wo"].astype(F32), quant), kv


def _head(xn, emb, quant):
    """xn [n, h] -> logits [n, vocab], a block of the vocabulary at a time
    written in place (the embedding is cast to float32 a block at once)."""
    V = emb.shape[0]
    cols = next(c for c in range(1, V + 1)
                if V % c == 0 and V // c <= HEAD_COLUMNS)
    width = V // cols

    def block(j, out):
        e = jax.lax.dynamic_slice_in_dim(emb, j * width, width, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, mm(xn, e.astype(F32).T, quant), j * width, axis=1)

    return jax.lax.fori_loop(0, cols, block,
                             jnp.zeros((xn.shape[0], V), F32))


def decoder_logits(w, ids, d, quant=None):
    """ids [b, t] -> logits [b, t, vocab_size] of the full causal
    forward."""
    eps = d["eps"]
    x = w["tok_emb"][ids].astype(F32)
    memory = kv = None
    for i in range(d["n_layers"]):
        lw, k = layer_weights(w, d, i), kind(d, i)
        u = _ln(x, lw["ln1g"], lw["ln1b"], eps)
        if k in ("mamba", "memory"):
            o, y = _mamba(u, lw, d, quant)
            memory = y if k == "memory" else memory
        elif k == "gmu":
            o = mm(memory * jax.nn.silu(mm(u, lw["w1"].astype(F32), quant)),
                   lw["w2"].astype(F32), quant)
        else:
            o, fresh = _diff_attn(
                u, lw, d, quant, i, kv=kv if k == "cross" else None,
                window=d["sliding_window"] if k == "window" else None)
            kv = fresh if k == "full" else kv
        h1 = x + o
        u = _ln(h1, lw["ln2g"], lw["ln2b"], eps)
        x = h1 + mm(jax.nn.silu(mm(u, lw["ffg"].astype(F32), quant))
                    * mm(u, lw["ffu"].astype(F32), quant),
                    lw["ffd"].astype(F32), quant)
    b, t, h = x.shape
    xn = _ln(x, w["lnf_g"], w["lnf_b"], eps).reshape(b * t, h)
    return _head(xn, w["tok_emb"], quant).reshape(b, t, -1)


# ------------------------------------------------------------ state's bytes
def layer_counts(d: dict) -> dict:
    kinds = [kind(d, i) for i in range(d["n_layers"])]
    return {k: kinds.count(k) for k in GROUP_OF}


def kv_entry_bytes(d: dict, dtype_bytes: int) -> int:
    """A position's keys and values in ONE cache (or ring)."""
    return 2 * d["n_kv_heads"] * d["head_dim"] * dtype_bytes


def scan_state_bytes(d: dict, dtype_bytes: int) -> int:
    """One Mamba layer's state a row: the float32 scan state and the
    convolution's ``d_conv - 1`` columns."""
    return d["d_state"] * d["d_inner"] * 4 \
        + (d["d_conv"] - 1) * d["d_inner"] * dtype_bytes


def cache_bytes(d: dict, position: float, dtype_bytes: int) -> float:
    """A request standing at ``position`` holds ``position`` entries of the
    one full-length cache, ``min(position, window)`` of every window's
    ring, and every Mamba layer's state, whatever its position."""
    c = layer_counts(d)
    entry = kv_entry_bytes(d, dtype_bytes)
    return position * c["full"] * entry \
        + c["window"] * min(position, d["sliding_window"]) * entry \
        + (c["mamba"] + c["memory"]) * scan_state_bytes(d, dtype_bytes)


# ------------------------------------------------------------- work counts
def matmul_params(d: dict) -> int:
    """Weights a token passes, every layer and the head's columns (the
    embedding is a gather)."""
    h, f, hd = d["hidden"], d["ffn_size"], d["head_dim"]
    di, n, r = d["d_inner"], d["d_state"], d["dt_rank"]
    q = d["n_heads"] * hd
    per = {"mamba": 2 * h * di + di * (r + 2 * n) + r * di + di * h,
           "window": 2 * h * q + 2 * h * d["n_kv_heads"] * hd,
           "cross": 2 * h * q, "gmu": 2 * h * di}
    per["memory"], per["full"] = per["mamba"], per["window"]
    c = layer_counts(d)
    return sum(c[k] * (per[k] + 3 * h * f) for k in c) + h * d["vocab_size"]


def _entry_flops(d: dict) -> float:
    """Both maps of every differential head over ONE attended entry: the
    scores 2 x n_heads x d, the values 2 x n_heads x 2d."""
    return 6.0 * d["n_heads"] * d["head_dim"]


def _scan_flops(d: dict) -> float:
    """Every Mamba layer's convolution and scan, a token: the taps, then
    exp(dt A) and the state's update (5 a state element) and its read by C
    (2)."""
    c = layer_counts(d)
    return (c["mamba"] + c["memory"]) * (
        2.0 * d["d_conv"] * d["d_inner"] + 7.0 * d["d_inner"] * d["d_state"])


def _window_sum(n: float, w: int) -> float:
    """Entries positions 1 .. n attend in a window of ``w``: sum of
    min(p, w)."""
    if n <= w:
        return n * (n + 1) / 2.0
    return w * (w + 1) / 2.0 + (n - w) * w


def phi4f_serve_slice(s: dict):
    """Every token decoded in the slice passes every matmul weight and the
    head, attends what its position makes valid in the one cache (read by
    the full layer and every cross layer) and in every window's ring, and
    runs every scan a step; a prompt of n tokens runs the trunk over n
    positions and the head at the last."""
    d = s["model"]
    c = layer_counts(d)
    reads, w = c["full"] + c["cross"], d["sliding_window"]
    head = 2.0 * d["hidden"] * d["vocab_size"]
    trunk = 2.0 * matmul_params(d) - head + _scan_flops(d)
    e = _entry_flops(d)
    flops = sum(share * (trunk + head + e * (
        reads * a + c["window"] * min(a, w)))
        for a, share in s["decode_attended"])
    for n, share in s["prefill_lengths"]:
        flops += share * (n * trunk + head + e * (
            reads * n * (n + 1) / 2.0 + c["window"] * _window_sum(n, w)))
    return flops, None


def _decode_call(s: dict, entries: float):
    d = s["model"]
    steps = max(1, s["decode_steps"])
    rows = sum(share for _, share in s["decode_attended"]) / steps
    wb = s["dtype_bytes"]
    return _entry_flops(d) * entries, \
        entries * kv_entry_bytes(d, wb) \
        + rows * 2 * d["n_heads"] * d["head_dim"] * wb


def phi4f_diff_decode_call(s: dict):
    """One single-query read of the one full-length cache (the full layer's
    or a cross layer's), averaged over the slice's steps: each active row
    reads the keys and values its position makes valid ONCE for both maps
    of every differential head, its queries, and writes its heads'
    outputs."""
    steps = max(1, s["decode_steps"])
    return _decode_call(s, sum(a * share for a, share in
                               s["decode_attended"]) / steps)


def phi4f_window_decode_call(s: dict):
    """One window layer's single-query read of its ring, averaged over the
    slice's steps: the entries the program's counter says the rows of the
    dispatched steps attended (min(position + 1, window) a row), read once
    for both maps of every head; (0, 0), which the reader reads as nothing,
    where the counter is missing."""
    children = s.get("counters", {}).get(WINDOW_ENTRIES)
    if not children:  # a program without the counter: nothing to read
        return 0.0, 0.0
    return _decode_call(s, sum(children.values())
                        / max(1, s["decode_steps"]))
