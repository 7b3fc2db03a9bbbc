"""Differential attention (DIFF Transformer, Ye et al. 2024,
arXiv:2410.05258) with no positional encoding, in the three forms a SambaY
decoder-hybrid-decoder uses (Ren et al. 2025):

* ``kind="window"``: over a sliding window of its own keys and values, the
  query's own position and the ``window - 1`` before it;
* ``kind="full"``: causal over all positions, in a K/V cache that the
  layer WRITES and the cross-decoder's other layers read;
* ``kind="cross"``: queries alone (``Wq``, ``Wo``), causal over the K/V
  cache a ``"full"`` layer wrote.

Heads (``d`` each; ``n_heads`` queries, ``n_kv_heads`` keys and values):
differential head ``j`` (``n_heads / 2`` of them) owns query heads ``2j``
and ``2j + 1`` as ``q1``, ``q2``; its K/V pair ``g = j // (n_heads /
n_kv_heads)`` key heads ``2g`` and ``2g + 1`` as ``k1``, ``k2`` and value
heads ``2g`` and ``2g + 1`` as ONE value of ``2 d``:

    o_j = softmax(q1 k1^T d^-1/2) v - lam softmax(q2 k2^T d^-1/2) v
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
    lam_init = 0.8 - 0.6 exp(-0.3 layer_index)
    Attn(x) = Wo [(1 - lam_init) N(o_j; gs) for every j]

``N`` an RMSNorm over a head's ``2 d`` with one gain ``gs`` for all heads.

Decode state. A window layer keeps a RING of ``window`` entries a row
(``ring_k``, ``ring_v`` ``[b, n_kv_heads, window, d]``, position ``p`` at
slot ``p mod window``: without positions the order of the entries does not
matter) and its rows' positions; a full layer a cache of ``max_len``
(``cache_k``, ``cache_v``); both are planes written in place. A decode
STEP writes its entry and attends the planes where they lie through
``ops.diff_decode`` (``diff_decode_window`` for a ring): one read of a K/V
block for both maps of every head. A multi-token call with a decode state
is a PREFILL of fresh rows (position 0): the tokens attend each other, a
window by blocks of ``window`` queries in the XLA spelling, a full or
cross layer through ``flash_fwd`` from 512 tokens on (once a map); a ring
takes the prompt's last ``window`` entries, a cache all of them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.config import register_config
from ..input_type import InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .attention import _merge_heads, _split_heads
from .base import Layer, LayerContext, Params, State, apply_input_dropout
from .norm import rms_norm

_F32 = jnp.float32
_NEG = -1e30
KINDS = ("window", "full", "cross")


def lambda_init(layer_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _maps(q, k, v):
    """``q [b, n_heads, t, d]``, ``k, v [b, n_kv, s, d]`` -> the two maps'
    operands, head for head: ``(q1, q2 [b, n_heads/2, t, d], k1, k2 [b,
    n_heads/2, s, d], v [b, n_heads/2, s, 2d])`` (a K/V pair repeated for
    the differential heads that share it, for the call only)."""
    b, nkv, s, d = k.shape
    r = q.shape[1] // nkv
    kp = k.reshape(b, nkv // 2, 2, s, d)
    vp = v.reshape(b, nkv // 2, 2, s, d).transpose(0, 1, 3, 2, 4).reshape(
        b, nkv // 2, s, 2 * d)

    def rep(a):
        return jnp.repeat(a, r, axis=1) if r > 1 else a

    return (q[:, 0::2], q[:, 1::2], rep(kp[:, :, 0]), rep(kp[:, :, 1]),
            rep(vp))


def band_attention(q, k, v, window: int, mask, scale: float):
    """Causal attention within a band: position ``t`` attends ``t - window
    + 1 .. t`` (and the keys ``mask [b, s]`` keeps). XLA spelling, a block
    of ``window`` queries at a time against its own block and the one
    before, so what stands is ``[b, h, window, 2 window]`` scores."""
    from ...ops import mha_attention_reference

    b, h, t, d = q.shape
    if t <= window:
        return mha_attention_reference(q, k, v, mask=mask, causal=True,
                                       scale=scale)
    w = window
    pad = (-t) % w
    nb = (t + pad) // w
    keep = jnp.ones((b, t), bool) if mask is None else mask > 0
    keep = jnp.pad(keep, ((0, 0), (w, pad)))             # a block before 0

    def blocks(a, before):
        a = jnp.pad(a, ((0, 0), (0, 0), (w if before else 0, pad), (0, 0)))
        return a

    qb = blocks(q, False).reshape(b, h, nb, w, d)
    kb = blocks(k, True)
    vb = blocks(v, True)
    qi = jnp.arange(w)[:, None] + w                      # in [block-1; block]
    ki = jnp.arange(2 * w)[None, :]
    band = (ki <= qi) & (ki > qi - w)                    # [w, 2w]

    def one(i):
        ks = jax.lax.dynamic_slice_in_dim(kb, i * w, 2 * w, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vb, i * w, 2 * w, axis=2)
        kp = jax.lax.dynamic_slice_in_dim(keep, i * w, 2 * w, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb[:, :, i], ks,
                       preferred_element_type=_F32) * scale
        ok = band[None, None] & kp[:, None, None, :]
        s = jnp.where(ok, s, _NEG)
        p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("bhqk,bhke->bhqe", p.astype(vs.dtype), vs,
                          preferred_element_type=_F32).astype(q.dtype)

    out = jax.lax.map(one, jnp.arange(nb))               # [nb, b, h, w, e]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, nb * w, -1)
    return out[:, :, :t]


def ring_of(a, lengths, window: int):
    """The ring a fresh row keeps after a prompt: ``a [b, h, t, d]`` at
    positions ``0 .. t - 1`` -> ``[b, h, window, d]``, slot ``s`` holding
    the prompt's last position ``p < length`` with ``p mod window == s``
    (slots no position reaches hold whatever lies there: the row's length
    keeps them out of every later read)."""
    b, h, t, d = a.shape
    if t < window:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, window - t), (0, 0)))
    s = jnp.arange(window, dtype=jnp.int32)[None, :]
    last = lengths.astype(jnp.int32)[:, None] - 1
    at = s + window * jnp.maximum((last - s) // window, 0)  # [b, window]
    return jnp.take_along_axis(a, at[:, None, :, None], axis=2)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class DifferentialAttentionLayer(Layer):
    """``Attn(x)`` as a sequential layer (input/output ``[b, n_in, t]``; no
    norm before it and no residual round it: a block adds those). Matmul
    operands take the parameters' type; softmax, ``lam`` and the head norm
    float32. A ``"cross"`` layer has no K/V of its own: it is called through
    ``mix(..., shared=...)`` with what a ``"full"`` layer published."""

    n_in: int = 0
    n_heads: int = 2
    n_kv_heads: int = 2
    kind: str = "full"
    window: int = 512
    layer_index: int = 0
    eps: float = 1e-5

    def __post_init__(self) -> None:
        if self.kind not in KINDS:
            raise ValueError(f"kind {self.kind!r} is not one of {KINDS}")
        if self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads over {self.n_kv_heads} K/V "
                "heads: K/V heads come in pairs, shared by whole heads")

    @property
    def head(self) -> int:
        return self.n_in // self.n_heads

    @property
    def lambda_init(self) -> float:
        return lambda_init(self.layer_index)

    def output_type(self, input_type: InputType) -> InputType:
        return RecurrentType(size=self.n_in, timesteps=input_type.timesteps)

    def with_input(self, input_type: InputType) -> "DifferentialAttentionLayer":
        if self.n_in:
            return self
        return dataclasses.replace(self, n_in=input_type.size)

    def has_params(self) -> bool:
        return True

    def _matrices(self) -> Tuple[str, ...]:
        return ("Wq", "Wo") if self.kind == "cross" else \
            ("Wq", "Wk", "Wv", "Wo")

    def trainable_param_names(self) -> Tuple[str, ...]:
        return self._matrices() + ("lq1", "lk1", "lq2", "lk2", "gs")

    def weight_param_names(self) -> Tuple[str, ...]:
        return self._matrices()

    def init(self, key: jax.Array, dtype: Any) -> Params:
        wi = self.weight_init or WeightInit.XAVIER
        h, d = self.n_in, self.head
        shapes = {"Wq": (h, self.n_heads * d), "Wk": (h, self.n_kv_heads * d),
                  "Wv": (h, self.n_kv_heads * d), "Wo": (self.n_heads * d, h)}
        ks = jax.random.split(key, 8)
        out = {n: init_weights(ks[i], shapes[n], wi, *shapes[n], None, dtype)
               for i, n in enumerate(self._matrices())}
        for i, n in enumerate(("lq1", "lk1", "lq2", "lk2")):
            out[n] = (0.1 * jax.random.normal(ks[4 + i], (d,), _F32)).astype(
                dtype)
        out["gs"] = jnp.ones((2 * d,), dtype)
        return out

    # ---- the decode state and what the layer declares of it ---------------
    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        if self.kind == "cross":
            return {}
        size = self.window if self.kind == "window" else max_len
        shape = (batch, self.n_kv_heads, size, self.head)
        keys = ("ring_k", "ring_v") if self.kind == "window" else \
            ("cache_k", "cache_v")
        return {keys[0]: jnp.zeros(shape, dtype),
                keys[1]: jnp.zeros(shape, dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def decode_planes(self) -> Tuple[str, ...]:
        return {"window": ("ring_k", "ring_v"),
                "full": ("cache_k", "cache_v")}.get(self.kind, ())

    def decode_ring(self) -> Optional[int]:
        return self.window if self.kind == "window" else None

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        entry = 2 * self.n_kv_heads * self.head * itemsize
        if self.kind == "window":
            return {"window": np.minimum(position, self.window) * entry}
        return {"kv": position * entry} if self.kind == "full" else {}

    # ---- the mixer ----------------------------------------------------------
    def _lam(self, params: Params) -> jax.Array:
        def dot(a, b):
            return jnp.sum(params[a].astype(_F32) * params[b].astype(_F32))

        return jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) \
            + self.lambda_init

    def _fresh(self, q, k, v, mask, lam):
        """The call's tokens attend each other (causal; within the band for
        a window) -> ``o [b, n_heads / 2, t, 2d]``."""
        from ...ops import mha_attention

        q1, q2, k1, k2, vv = _maps(q, k, v)
        scale = self.head ** -0.5
        if self.kind == "window":
            def att(qm, km):
                return band_attention(qm, km, vv, self.window, mask, scale)
        else:
            def att(qm, km):
                return mha_attention(qm, km, vv, mask=mask, causal=True,
                                     scale=scale)
        return att(q1, k1).astype(_F32) - lam * att(q2, k2).astype(_F32)

    def mix(self, params: Params, state: State, x: jax.Array, mask, *,
            shared: Optional[dict] = None):
        """x ``[b, t, n_in]`` in the parameters' type -> ``(Attn(x) [b, t,
        n_in], the new state)``, and for a ``"full"`` layer also what the
        cross layers read, ``{"k", "v"}`` (the call's own keys and values,
        or the caches with the rows' ``"pos"`` after a one-token step); a
        ``"cross"`` layer takes that as ``shared``. ``state`` may be empty
        (a whole sequence from position 0, no cache)."""
        scope = {"window": "swa_attn", "full": "diff_attn",
                 "cross": "cross_attn"}[self.kind]
        with jax.named_scope(scope):
            return self._mix(params, state, x, mask, shared)

    def _write(self, state, k, v, at, advance):
        """The call's keys and values into the layer's planes at ``at``
        (rows a ``write_mask`` keeps), the rows' positions ``advance``d."""
        from ...ops import masked_cache_write

        pos = state["pos"].astype(jnp.int32)
        keep = state.get("write_mask")
        if keep is None:
            keep = jnp.ones(pos.shape, bool)
        kn, vn = self.decode_planes()
        return {kn: masked_cache_write(state[kn], k, at, keep),
                vn: masked_cache_write(state[vn], v, at, keep),
                "pos": pos + advance}

    def _mix(self, params, state, x, mask, shared):
        from ...ops.diff_attention import diff_decode_attention

        b, t, _ = x.shape
        lam = self._lam(params)
        q = _split_heads(x @ params["Wq"], self.n_heads)
        new, publish = state, None
        if self.kind == "cross":
            k, v = shared["k"], shared["v"]
        else:
            k = _split_heads(x @ params["Wk"], self.n_kv_heads)
            v = _split_heads(x @ params["Wv"], self.n_kv_heads)
        if t == 1 and (shared or state).get("pos") is not None:  # a step
            pos = (shared or state)["pos"].astype(jnp.int32)
            if self.kind != "cross":
                at = pos % self.window if self.kind == "window" else pos
                new = self._write(state, k, v, at, 1)
                k, v = (new[n] for n in self.decode_planes())
            n = pos + 1
            if self.kind == "window":
                n = jnp.minimum(n, self.window)
            o = diff_decode_attention(
                q[:, :, 0], k, v, n, lam, self.head ** -0.5,
                name="diff_decode_window" if self.kind == "window"
                else "diff_decode")[:, :, None]          # [b, heads/2, 1, 2d]
            publish = {"k": k, "v": v, "pos": pos}
        else:
            o = self._fresh(q, k, v, mask, lam)
            publish = {"k": k, "v": v}
            if self.kind != "cross" and state:  # fresh rows, from position 0
                valid = (jnp.full((b,), t, jnp.int32) if mask is None
                         else jnp.sum(mask > 0, axis=1).astype(jnp.int32))
                if self.kind == "window":  # the prompt's last entries
                    new = {"ring_k": ring_of(k, valid, self.window).astype(
                               state["ring_k"].dtype),
                           "ring_v": ring_of(v, valid, self.window).astype(
                               state["ring_v"].dtype),
                           "pos": state["pos"].astype(jnp.int32) + valid}
                else:
                    new = self._write(state, k, v, state["pos"], valid)
        o = rms_norm(o.astype(_F32), params["gs"], self.eps) \
            * (1.0 - self.lambda_init)
        y = _merge_heads(o.astype(x.dtype)) @ params["Wo"]
        return (y, new, publish) if self.kind == "full" else (y, new)

    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        if self.kind == "cross":
            raise ValueError("a cross layer reads another layer's cache: it "
                             "is called through mix(..., shared=...)")
        x = apply_input_dropout(self, x, ctx)
        xt = x.transpose(0, 2, 1).astype(params["Wq"].dtype)
        y, new_state, *_ = self.mix(params, state, xt, ctx.mask)
        return y.astype(x.dtype).transpose(0, 2, 1), new_state
