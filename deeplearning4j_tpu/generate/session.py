"""GenerationSession — KV-cached autoregressive decode over a
MultiLayerNetwork.

The session turns any sequential model whose layers implement
``decode_state`` (causal attention blocks, LSTM/GRU/SimpleRnn, positional
embeddings) into an incremental generator:

* **carry** — one preallocated pytree ``{layer: layer.decode_state(B,
  max_len, dtype)}``: static-shape KV caches ``[B, H, max_len, d]`` with
  per-row position counters for attention layers, ``(h, c)`` for the
  recurrent ones. Threaded through ``forward_pure``'s ``rnn_state``
  channel, so the model code is the SAME code that trains — decode is a
  calling convention, not a fork of the forward.
* **prefill** — the prompt runs once at a BUCKETED length (powers of two,
  mirroring the serving engine's ``bucket_sizes()`` discipline) with a
  validity mask for the right-pad, writing every position's K/V into the
  cache; the first sampled token comes from the logits at each row's last
  valid position. One compile per bucket, ever.
* **decode** — each subsequent token is a ``[B, 1]`` forward against the
  cache (``lax.dynamic_update_slice`` write + single-query flash decode
  attention); ONE compiled shape for the whole generation regardless of
  position, so no request ever pays a recompile mid-stream.

Prefill/decode equivalence (greedy token-for-token identity with a full
re-forward at every position) is enforced in tier-1
``tests/test_generation.py``.
"""

from __future__ import annotations

import contextlib
import struct
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..nn.layers.base import fresh_rows
from ..nn.layers.output import BaseOutputLayer
from ..nn.activations import Activation
from .sampling import sample_tokens, speculative_accept

_NEG = -1e30


def bucket_length(n: int, limit: int) -> int:
    """Smallest power-of-two >= n, capped at ``limit`` (the prompt-length
    analog of ParallelInference._bucket: stable shapes, no recompiles)."""
    b = 1
    while b < n and b < limit:
        b <<= 1
    return min(b, limit)


CACHE_DTYPES = (None, "int8")

# the columns of a self-speculating row's device-side image
# (:meth:`GenerationSession.mtp_step`): the last committed token (the next
# input), the draft after it, the tokens emitted so far (the sampling key's
# step), and what the step that wrote it committed: how many, the stack's
# token at the first position and at the second, whether a draft was
# verified and whether it was kept
(SV_LAST, SV_DRAFT, SV_EMITTED, SV_N, SV_TOK0, SV_TOK1, SV_PROPOSED,
 SV_ACCEPTED) = range(8)
SV_WIDTH = 8


# one prompt's admission as one int32 vector: its length, the slot it is for,
# then the sampling law; the seed and the two floats ride as their bits
_ROW_SPEC = struct.Struct("<iiIifif")
ROW_SPEC_WORDS = _ROW_SPEC.size // 4


def pack_row_spec(length: int, slot: int, seed: int, greedy: bool,
                  temperature: float, top_k: int, top_p: float) -> np.ndarray:
    """The operand of :meth:`GenerationSession.prefill_row` beside the ids:
    ``int32[ROW_SPEC_WORDS]``, bit-exact for the seed and the floats."""
    return np.frombuffer(_ROW_SPEC.pack(
        length, slot, seed, greedy, temperature, top_k, top_p), np.int32)


def quantize_decode_state(st):
    """Convert one layer's decode carry to the int8 KV-cache layout:
    ``cache_k``/``cache_v`` become int8 with per-slot/per-head f32 scale
    planes ``cache_k_scale``/``cache_v_scale`` ([b, h, L]); everything
    else (``pos``, recurrent ``h``/``c``, input caches) keeps its dtype.
    The attention layers' ``_cached_attention`` detects the scale keys
    and runs the quantize-on-write / dequant-on-attend path."""
    if "cache_k" not in st or "cache_v" not in st:
        return st
    out = dict(st)
    for key in ("cache_k", "cache_v"):
        c = st[key]
        out[key] = jnp.zeros(c.shape, jnp.int8)
        out[key + "_scale"] = jnp.zeros(c.shape[:-1], jnp.float32)
    return out


class GenerationSession:
    def __init__(self, model, *, max_len: int = 256,
                 cache_dtype: Optional[str] = None) -> None:
        model._check_init()
        migrate = getattr(model, "migrate_state", None)
        if callable(migrate):
            migrate()
        if cache_dtype not in CACHE_DTYPES:
            raise ValueError(
                f"cache_dtype must be one of {CACHE_DTYPES}, got "
                f"{cache_dtype!r}")
        #: "int8" stores attention K/V caches quantized (per-slot/per-head
        #: absmax scales on the carry) — ~2× the resident sequences per
        #: fp16 HBM budget; None keeps the model dtype (exact).
        self.cache_dtype = cache_dtype
        self.model = model
        self.max_len = int(max_len)
        last = model.layers[-1]
        if not isinstance(last, BaseOutputLayer):
            raise ValueError("generation needs an output layer last")
        self.vocab_size = int(last.n_out)
        act = last.activation or Activation.SOFTMAX
        self._out_is_probs = act == Activation.SOFTMAX
        self._layer_names = model.layer_names()
        #: what each layer declares of its decode state
        #: (``Layer.decode_planes``): ``{layer name: names of the leaves
        #: that are planes written in place}``; the carry's masking,
        #: freezing and paging (generate/paged.py) work from it
        named = list(zip(self._layer_names, model.layers))
        self.planes: Dict[str, frozenset] = {
            name: frozenset(layer.decode_planes())
            for name, layer in named if layer.decode_planes()}
        #: what each layer counts of a call in its decode state
        #: (``Layer.decode_counts``): ``{layer name: {leaf: column names}}``
        self.counts: Dict[str, dict] = {
            name: layer.decode_counts()
            for name, layer in named if layer.decode_counts()}
        #: the layers among them whose planes the paged layout can page
        self.paged_layers = frozenset(
            name for name, layer in named
            if name in self.planes and layer.pages_decode_planes)
        #: the window after which the model's mixers fold their entries
        #: into summaries (``Layer.decode_window``; the shortest, if layers
        #: differ), or None: a longer prompt is then prefilled a window at
        #: a time (:meth:`prefill_logits`)
        self.window = min((l.decode_window() for l in model.layers
                           if l.decode_window()), default=None)
        # an output layer that knows its own next-token logits (several
        # prediction heads, of which decoding reads the first)
        self._head = getattr(last, "decode_logits", None)
        #: whether the output layer holds a multi-token-prediction module
        #: that drafts the token after next (``MtpOutputLayer``): the model
        #: can speculate with its own module (:meth:`mtp_step`)
        self.mtp = callable(getattr(last, "draft", None))
        self._fns: Dict = {}
        # at least one layer must expose decode state, otherwise "decode"
        # would silently re-run from scratch each step
        if not any(l.decode_state(1, 1, model.dtype) for l in model.layers):
            raise ValueError(
                "no layer exposes decode_state — model cannot be decoded "
                "incrementally (attention layers need causal=True)")

    # ----- carry ------------------------------------------------------
    def decode_state(self, batch: int):
        """Fresh per-sequence decode carry for ``batch`` rows (attention
        K/V caches quantized when ``cache_dtype="int8"``)."""
        out = {}
        for name, layer in zip(self._layer_names, self.model.layers):
            st = layer.decode_state(batch, self.max_len, self.model.dtype)
            if st:
                if self.cache_dtype == "int8":
                    st = quantize_decode_state(st)
                out[name] = st
        return out

    def cache_bytes(self, batch: int = 1) -> int:
        """Resident bytes of the decode carry for ``batch`` rows — the
        per-sequence HBM cost capacity planning divides the cache budget
        by (and the ``dl4j_tpu_generate_kv_cache_bytes`` gauge)."""
        leaves = jax.tree_util.tree_leaves(self.decode_state(batch))
        return int(sum(l.size * l.dtype.itemsize for l in leaves))

    def count_columns(self) -> Dict[str, tuple]:
        """``{leaf: column names}`` of everything the model's layers count
        (layers that declare a leaf of one name declare the same columns)."""
        out: Dict[str, tuple] = {}
        for leaves in self.counts.values():
            out.update(leaves)
        return out

    def summed_counts(self, carry, rows=None) -> Dict[str, jax.Array]:
        """The counting leaves of ``carry`` summed over its rows (those
        ``rows [b]`` marks, if given) and over the layers that declare
        them: ``{leaf: [columns] int32}``, ``{}`` for a model that counts
        nothing. Traced inside the step or prefill that made the carry, so
        the sums leave the device with that program's tokens."""
        out: Dict[str, jax.Array] = {}
        for name, leaves in self.counts.items():
            for leaf in leaves:
                c = carry[name][leaf]
                if rows is not None:
                    c = jnp.where(rows[:, None], c, 0)
                c = jnp.sum(c, axis=0)
                out[leaf] = out[leaf] + c if leaf in out else c
        return out

    def bucket_sizes(self, limit: Optional[int] = None) -> List[int]:
        """Prompt-length buckets a warmup should compile (powers of two up
        to ``limit``, default ``max_len``)."""
        limit = self.max_len if limit is None else min(limit, self.max_len)
        sizes: List[int] = []
        b = 1
        while b < limit:
            sizes.append(b)
            b <<= 1
        sizes.append(limit)
        return sizes

    # ----- model plumbing ---------------------------------------------
    def _prep(self, ids: jax.Array) -> jax.Array:
        """ids [b, t] -> model input: kept as int ids for embedding-first
        models, one-hot [b, V, t] otherwise (the char-RNN convention)."""
        ids = jnp.asarray(ids, jnp.int32)
        if self.model.keeps_int_input():
            return ids
        oh = jax.nn.one_hot(ids, self.vocab_size, dtype=self.model.dtype)
        return oh.transpose(0, 2, 1)

    def _forward(self, params, state, x, mask, carry):
        """The model's forward on the decode carry -> ``(out, new carry)``:
        the model's output, or, where the output layer knows its own
        next-token logits (``decode_logits``: several prediction heads),
        that layer's INPUT, for :meth:`_logits` to hand to it."""
        model = self.model
        out, _, new = model.forward_pure(
            params, state, x, train=False, rng=None, mask=mask,
            rnn_state=carry,
            upto=None if self._head is None else len(model.layers) - 1)
        head = self._layer_names[-1]
        if self._head is not None and head in carry:
            # the head's own decode state (an MTP module's plane) passes
            # through a forward that stops before the head
            new = {**new, head: {k: v for k, v in carry[head].items()
                                 if k != "write_mask"}}
        return out, new

    def _logits(self, out: jax.Array, params=None) -> jax.Array:
        """:meth:`_forward`'s output [b, ., t] -> per-position logits
        [b, V, t] (log of probs for softmax outputs — equivalent under
        temperature scaling, truncation and argmax; see sampling.py)."""
        if self._head is not None:
            if params is None:
                raise ValueError(
                    "an output layer with decode_logits reads its own "
                    "parameters: pass the model's params")
            params, _ = self.model._to_compute(params, out)
            return self._head(self.model.layer_params(
                params, len(self._layer_names) - 1), out)
        if self._out_is_probs:
            return jnp.log(jnp.maximum(out, 1e-30))
        return out

    def prefill_logits(self, params, state, carry, ids, lengths):
        """The prompts ``ids`` [b, t] (right-padded, ``lengths`` [b] valid)
        through the model on a fresh ``carry`` -> ``(new carry, logits at
        each row's last valid position [b, V])``. A model whose mixers fold
        windows (``self.window``) takes a prompt longer than one window a
        window at a time, through all its layers, up to the longest row's
        last window: what is alive is a window's, and the windows that are
        only padding are not computed."""
        t, w = ids.shape[1], self.window
        lengths = lengths.astype(jnp.int32)

        def piece(carry, ids, start):
            n = ids.shape[1]
            at = start + jnp.arange(n, dtype=jnp.int32)[None, :]
            mask = (at < lengths[:, None]).astype(self.model.dtype)
            # the first window stands on nothing; a later one on those
            # before it (its start is traced)
            with (fresh_rows() if isinstance(start, int) and start == 0
                  else contextlib.nullcontext()):
                out, new = self._forward(params, state, self._prep(ids),
                                         mask, carry)
            idx = jnp.clip(lengths - 1 - start, 0, n - 1)[:, None, None]
            if self._head is not None:  # the head at the one position read
                return new, self._logits(jnp.take_along_axis(
                    out, idx, axis=2), params)[:, :, 0]  # [b, V]
            logits = self._logits(out, params)  # [b, V, n]
            return new, jnp.take_along_axis(logits, idx, axis=2)[:, :, 0]

        if w is None or t <= w:
            return piece(carry, ids, 0)
        ids = jnp.pad(ids, ((0, 0), (0, (-t) % w)))

        def window(i, val):
            carry, last = val
            new, here = piece(carry, jax.lax.dynamic_slice_in_dim(
                ids, i * w, w, axis=1), i * w)
            mine = ((lengths - 1) // w == i)[:, None]
            return new, jnp.where(mine, here, last)

        first, last = piece(carry, ids[:, :w], 0)  # fixes the loop's types
        return jax.lax.fori_loop(
            1, (jnp.max(lengths) + w - 1) // w, window, (first, last))

    def prefill_row(self, params, state, ids, spec):
        """One prompt ``ids`` [1, t] (right-padded) under ``spec``
        (:func:`pack_row_spec`) -> ``(row, first token, counts)``: the
        prompt through the model on a fresh one-row carry, made here, its
        first token sampled at decode step 0, and what the layers counted
        of the prompt (``{}`` for a model that counts nothing). The one
        body of every tier that prefills: a serving engine installs the
        row inside the same program, a prefill tier ships it."""
        bits = jax.lax.bitcast_convert_type
        row, last = self.prefill_logits(
            params, state, self.decode_state(1), ids, spec[0:1])
        tok = sample_tokens(
            last, bits(spec[2:3], jnp.uint32), jnp.zeros((1,), jnp.int32),
            spec[3:4] != 0, bits(spec[4:5], jnp.float32), spec[5:6],
            bits(spec[6:7], jnp.float32))
        return row, tok[0], self.summed_counts(row)

    # ----- self-speculation: the model's own MTP module drafts ----------
    def _mtp_params(self, params, like):
        params, _ = self.model._to_compute(params, like)
        return self.model.layer_params(params, len(self._layer_names) - 1)

    def _mtp_draft(self, params, state, out, nxt, at, mask=None):
        """The MTP module over the main stack's outputs ``out [b, n_in, t]``
        and the next ids ``nxt [b, t]`` on the head's state -> ``(the
        draft's logits at position ``at [b]`` of the call [b, V], the
        module's new state)``."""
        head = self.model.layers[-1]
        hp = self._mtp_params(params, out)
        g, new = head.draft(hp, state, out, nxt, mask)
        g = jnp.take_along_axis(g, at[:, None, None], axis=1)
        return head.draft_logits(hp, g)[:, 0], new

    def mtp_prefill_row(self, params, state, ids, spec):
        """:meth:`prefill_row` for a model that drafts with its own MTP
        module -> ``(row, first token, draft, counts)``: the module runs
        over the prompt too (position ``i`` pairs the stack's output with
        the id at ``i + 1``, the last with the first token), so that the
        row's first step already verifies a draft, the token after the
        first."""
        bits = jax.lax.bitcast_convert_type
        n = spec[0:1]
        t = ids.shape[1]
        mask = (jnp.arange(t, dtype=jnp.int32)[None, :]
                < n[:, None]).astype(self.model.dtype)
        row = self.decode_state(1)
        with fresh_rows():
            out, row = self._forward(params, state, self._prep(ids), mask,
                                     row)
        at = jnp.clip(n - 1, 0, t - 1)
        last = self._logits(jnp.take_along_axis(
            out, at[:, None, None], axis=2), params)[:, :, 0]
        tok = sample_tokens(
            last, bits(spec[2:3], jnp.uint32), jnp.zeros((1,), jnp.int32),
            spec[3:4] != 0, bits(spec[4:5], jnp.float32), spec[5:6],
            bits(spec[6:7], jnp.float32))
        nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        nxt = jnp.where(jnp.arange(t)[None, :] == at[:, None],
                        tok[:, None], nxt)
        name = self._layer_names[-1]
        with fresh_rows():
            logits, row[name] = self._mtp_draft(params, row[name], out, nxt,
                                                at, mask)
        draft = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return row, tok[0], draft[0], self.summed_counts(row)

    def mtp_step(self, params, state, carry, sv, rows):
        """One self-speculative step at depth 1 over a batch ``carry``. ``sv
        [b, SV_WIDTH]`` is each row's device-side image (:data:`SV_WIDTH`:
        its last committed token, the draft, the tokens emitted so far...),
        ``rows`` the host's ``int32 [7, b]``: active, seed, greedy,
        temperature, top-k, top-p (the floats and the seed as their bits),
        and the row's limit of emitted tokens. The stack verifies ``[last,
        draft]`` at two positions; a greedy row with room for two keeps the
        draft where it is the stack's own next token, and then the token
        after (two committed), else the stack's token alone (one). The MTP
        module then runs over both positions with the tokens they commit,
        and drafts from the last one committed. Every plane advanced two
        positions and is rewound to the committed frontier by its ``pos``.
        -> ``(carry, sv, counts)``; ``sv`` says what the step committed."""
        from .paged import freeze_rows, mask_inactive_writes

        bits = jax.lax.bitcast_convert_type
        last, draft, emitted = (sv[:, SV_LAST], sv[:, SV_DRAFT],
                                sv[:, SV_EMITTED])
        room = rows[6] - emitted
        active = (rows[0] != 0) & (room > 0)
        gmask = rows[2] != 0
        spec = active & gmask & (room >= 2)
        fwd = mask_inactive_writes(carry, active, self.planes)
        with jax.named_scope("verify"):
            out, new = self._forward(
                params, state, self._prep(jnp.stack([last, draft], axis=1)),
                None, fwd)
            logits = self._logits(out, params)                 # [b, V, 2]
        with jax.named_scope("sample"):
            tok0 = sample_tokens(
                logits[:, :, 0], bits(rows[1], jnp.uint32), emitted,
                gmask | ~active, bits(rows[3], jnp.float32), rows[4],
                bits(rows[5], jnp.float32))
            tok1 = jnp.argmax(logits[:, :, 1], axis=-1).astype(jnp.int32)
        acc = spec & (tok0 == draft)
        n = jnp.where(active, 1 + acc.astype(jnp.int32), 0)
        name = self._layer_names[-1]
        dlogits, new[name] = self._mtp_draft(
            params, fwd[name], out, jnp.stack([tok0, tok1], axis=1),
            jnp.maximum(n - 1, 0))
        nxt_draft = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
        with jax.named_scope("freeze_rows"):
            counts = self.summed_counts(new, active)
            new = rewind_carry(freeze_rows(new, fwd, active, self.planes),
                               jnp.where(active, 2 - n, 0))
        sv = jnp.stack([
            jnp.where(active, jnp.where(acc, tok1, tok0), last),
            jnp.where(active, nxt_draft, draft), emitted + n, n, tok0,
            tok1, spec.astype(jnp.int32), acc.astype(jnp.int32)], axis=1)
        return new, sv.astype(jnp.int32), counts

    # ----- jitted steps -----------------------------------------------
    def _prefill_fn(self, t_bucket: int):
        key = ("prefill", t_bucket)
        if key not in self._fns:
            self._fns[key] = jax.jit(self.prefill_logits)
        return self._fns[key]

    def _decode_fn(self):
        if "decode" not in self._fns:
            def fn(params, state, carry, tokens):
                out, new_rnn = self._forward(
                    params, state, self._prep(tokens[:, None]), None, carry)
                return new_rnn, self._logits(out, params)[:, :, 0]

            self._fns["decode"] = jax.jit(fn)
        return self._fns["decode"]

    def _write_row_fn(self):
        """jit: scatter a 1-row carry (a fresh prefill) into slot ``i`` of
        a B-row carry — the continuous-batching slot install."""
        if "write_row" not in self._fns:
            def fn(carry, row, i):
                def put(c, r):
                    z = jnp.zeros((), i.dtype)
                    idx = (i,) + (z,) * (c.ndim - 1)
                    return jax.lax.dynamic_update_slice(
                        c, r.astype(c.dtype), idx)

                return jax.tree_util.tree_map(put, carry, row)

            self._fns["write_row"] = jax.jit(fn)
        return self._fns["write_row"]

    def _freeze_fn(self):
        """jit: keep carry rows where ``active`` is False unchanged (an
        idle slot must not advance its cache/positions). Paged-aware:
        shared block pools are kept wholesale (inactive writes went to
        the trash block) and block tables restored (paged.freeze_rows)."""
        if "freeze" not in self._fns:
            from .paged import freeze_rows

            self._fns["freeze"] = jax.jit(
                lambda new, old, active: freeze_rows(new, old, active,
                                                     self.planes))
        return self._fns["freeze"]

    # ----- host API ----------------------------------------------------
    def prefill(self, prompts: Sequence[Sequence[int]], *, batch: Optional[int] = None):
        """Run the (ragged) prompts through the model once, building the
        decode carry. Returns ``(carry, logits [b, V], lengths [b])`` with
        prompts right-padded to the shared bucket length."""
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        if lengths.min() < 1:
            raise ValueError("empty prompt")
        b = len(prompts) if batch is None else batch
        tb = bucket_length(int(lengths.max()), self.max_len)
        ids = np.zeros((b, tb), np.int32)
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = np.asarray(p, np.int32)
        lens = np.ones((b,), np.int32)
        lens[: len(prompts)] = lengths
        carry = self.decode_state(b)
        carry, logits = self._prefill_fn(tb)(
            self.model.params, self.model.state, carry,
            jnp.asarray(ids), jnp.asarray(lens))
        return carry, logits, lens

    def decode(self, carry, tokens):
        """One incremental step: ``tokens [b]`` -> (carry', logits [b, V])."""
        return self._decode_fn()(self.model.params, self.model.state, carry,
                                 jnp.asarray(tokens, jnp.int32))

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_tokens: int,
        *,
        greedy: bool = True,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
    ) -> List[List[int]]:
        """Convenience batch generation (the serving engine drives the
        prefill/decode primitives itself for continuous batching). Stops a
        row at ``eos_id`` or ``max_tokens``, never past ``max_len``."""
        b = len(prompts)
        carry, logits, lens = self.prefill(prompts)
        seeds = jnp.full((b,), seed, jnp.uint32) + jnp.arange(b, dtype=jnp.uint32)
        gmask = jnp.full((b,), bool(greedy))
        temps = jnp.full((b,), temperature, jnp.float32)
        ks = jnp.full((b,), top_k, jnp.int32)
        ps = jnp.full((b,), top_p, jnp.float32)
        out: List[List[int]] = [[] for _ in range(b)]
        done = [False] * b
        pos = lens.copy()
        tokens = None
        for step in range(max_tokens):
            if tokens is None:
                toks = sample_tokens(logits, seeds,
                                     jnp.zeros((b,), jnp.int32),
                                     gmask, temps, ks, ps)
            else:
                carry, logits = self.decode(carry, tokens)
                toks = sample_tokens(logits, seeds,
                                     jnp.full((b,), step, jnp.int32),
                                     gmask, temps, ks, ps)
            toks_h = np.asarray(toks)
            for i in range(b):
                if done[i]:
                    continue
                t = int(toks_h[i])
                out[i].append(t)
                pos[i] += 1
                if (eos_id is not None and t == eos_id) or pos[i] >= self.max_len:
                    done[i] = True
            if all(done):
                break
            tokens = toks
        return out


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

# ``latent``: a latent-attention plane (a window of drafted tokens attends
# it through ``mla_verify``); ``moe_choices``: the counts of the last call
_REWINDABLE_KEYS = frozenset({"cache_k", "cache_v", "pos",
                              "cache_k_scale", "cache_v_scale",
                              "block_table", "latent", "moe_choices"})


def _check_rewindable(session: GenerationSession, role: str) -> None:
    """Speculative decode writes ``k+1`` positions ahead and must be able
    to roll the uncommitted suffix back after a rejection. That is only
    possible when every decode-state leaf is position-indexed (K/V caches
    and latent planes masked by a ``pos`` counter): a recurrent ``h``/``c``
    carry, a rolling convolution or a scan state has no position to rewind,
    so those models are rejected up front."""
    for name, st in session.decode_state(1).items():
        keys = set(st.keys())
        if "pos" not in keys or not keys <= _REWINDABLE_KEYS:
            raise ValueError(
                f"speculative decoding requires position-indexed decode "
                f"caches (K/V or latent planes under a pos counter); {role} "
                f"layer {name!r} carries state {sorted(keys)}, which cannot "
                "be rewound past a rejected draft (recurrent and rolling "
                "states have no position counter)")


def rewind_carry(carry, delta):
    """Roll a decode carry back ``delta`` positions per row. Stale K/V
    entries past the committed frontier stay in the cache but are masked
    by ``pos`` (decode attention reads ``[0, pos)`` only) and are
    overwritten by the next forward — rewind is a per-row position
    subtraction, not a data copy."""
    out = {}
    for name, st in carry.items():
        out[name] = {
            kk: (jnp.maximum(v - delta.astype(v.dtype), 0) if kk == "pos"
                 else v)
            for kk, v in st.items()}
    return out


class SpeculativeGenerationSession:
    """Draft-model speculative decoding over a paired target+draft cache.

    Each speculative step runs the cheap draft model ``k+1`` times at
    ``[B, 1]`` (proposing ``k`` tokens and keeping its own cache aligned
    through the window), scores the proposals with ONE target forward at
    ``[B, k+1]`` — the tq>1 causal pass through the same cached-attention
    path prefill uses, writing into the target's KV cache — and commits
    tokens through :func:`~deeplearning4j_tpu.generate.sampling.
    speculative_accept` (exact accept-or-resample: the output law is the
    target's, byte-identical under the same ``(seed, step)`` keying;
    greedy streams are token-identical to plain decode). Both caches then
    REWIND to the committed frontier, so a rejected burst never leaks
    speculative state into the next step.

    The per-``k`` propose/verify programs are compiled once each — the
    static-shape discipline of :class:`GenerationSession` carries over
    (one propose + one verify program per speculation depth, ever)."""

    def __init__(self, model, draft_model, *, max_len: int = 256,
                 k: int = 4, cache_dtype: Optional[str] = None) -> None:
        if k < 1:
            raise ValueError("speculative k must be >= 1")
        # cache_dtype applies to BOTH caches: the rewind contract holds
        # for int8 caches too (scales are position-indexed, masked by pos)
        self.target = GenerationSession(model, max_len=max_len,
                                        cache_dtype=cache_dtype)
        self.draft = GenerationSession(draft_model, max_len=max_len,
                                       cache_dtype=cache_dtype)
        if self.draft.vocab_size != self.target.vocab_size:
            raise ValueError(
                f"draft vocab {self.draft.vocab_size} != target vocab "
                f"{self.target.vocab_size} — the acceptance ratio needs "
                "one shared token space")
        _check_rewindable(self.target, "target")
        _check_rewindable(self.draft, "draft")
        self.k = int(k)
        self.max_len = int(max_len)
        self._fns: Dict = {}
        self.last_stats: Optional[dict] = None

    # ----- jitted steps -----------------------------------------------
    def _step_fn(self, k: int):
        """jit (one per depth): the WHOLE speculative step fused into one
        dispatch — k+1 chained [B, 1] draft forwards (k proposals keyed
        ``(seed, step+i)`` plus one trailing feed so the draft cache
        covers the full window), the tq=k+1 causal target verify pass,
        exact accept-or-resample, inactive-row freeze, and the rewind of
        BOTH caches to the committed frontier. One host round-trip per
        speculative step, mirroring the plain path's one-dispatch decode."""
        key = ("step", k)
        if key not in self._fns:
            dsess, tsess = self.draft, self.target

            def fn(tparams, tstate, dparams, dstate, tcarry, dcarry, last,
                   steps, active, seeds, gmask, temps, ks, ps, spec_ks):
                from .paged import freeze_rows, redirect_inactive_writes

                # paged carries: inactive rows' writes go to the trash
                # block instead of their own live blocks (the fused step
                # writes every row; freeze_rows restores their tables)
                tfwd = redirect_inactive_writes(tcarry, active)
                # ---- propose: k draft tokens, draft cache kept aligned
                cur, feed = redirect_inactive_writes(dcarry, active), last
                toks, logits_list = [], []
                for i in range(k + 1):
                    out, cur = dsess._forward(
                        dparams, dstate, dsess._prep(feed[:, None]), None,
                        cur)
                    logits_i = dsess._logits(out, dparams)[:, :, 0]
                    if i < k:
                        tok = sample_tokens(logits_i, seeds, steps + i,
                                            gmask, temps, ks, ps)
                        toks.append(tok)
                        logits_list.append(logits_i)
                        feed = tok
                d_toks = jnp.stack(toks, axis=1)
                d_logits = jnp.stack(logits_list, axis=1)
                # ---- verify: ONE tq=k+1 target forward through the
                # cached-attention path (a window over the filled caches)
                tokens_in = jnp.concatenate([last[:, None], d_toks], axis=1)
                out, tnew = tsess._forward(
                    tparams, tstate, tsess._prep(tokens_in), None, tfwd)
                t_logits = tsess._logits(out, tparams).transpose(
                    0, 2, 1)                                     # [b,t,V]
                # ---- accept (exact), freeze idle rows, rewind both
                otoks, n_acc, n_emit = speculative_accept(
                    d_toks, d_logits, t_logits, seeds, steps, spec_ks,
                    gmask, temps, ks, ps)

                tnew = freeze_rows(tnew, tcarry, active, tsess.planes)
                dnew = freeze_rows(cur, dcarry, active, dsess.planes)
                delta = jnp.where(active, (k + 1) - n_emit, 0)
                return (rewind_carry(tnew, delta),
                        rewind_carry(dnew, delta), otoks, n_acc, n_emit)

            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    # ----- one batched speculative step --------------------------------
    def step(self, target_carry, draft_carry, last, steps, active, seeds,
             gmask, temps, ks, ps, spec_ks, *, k: Optional[int] = None):
        """Propose / verify / accept / rewind for one batch step.

        ``last`` [B] is each row's most recent committed token (not yet
        fed), ``steps`` [B] the decode-step index its NEXT token samples
        at, ``spec_ks`` [B] the per-row acceptance window (<= ``k``; 0
        degenerates to a plain decode step for that row). Rows where
        ``active`` is False are frozen. Returns ``(target_carry,
        draft_carry, tokens [B, k+1], n_accepted [B], n_emitted [B])`` —
        the caller commits ``tokens[i, :n_emitted[i]]`` per row; both
        carries are already rewound to the committed frontier."""
        kk = self.k if k is None else int(k)
        return self._step_fn(kk)(
            self.target.model.params, self.target.model.state,
            self.draft.model.params, self.draft.model.state,
            target_carry, draft_carry,
            jnp.asarray(last, jnp.int32), jnp.asarray(steps, jnp.int32),
            jnp.asarray(active, bool), jnp.asarray(seeds, jnp.uint32),
            jnp.asarray(gmask, bool), jnp.asarray(temps, jnp.float32),
            jnp.asarray(ks, jnp.int32), jnp.asarray(ps, jnp.float32),
            jnp.asarray(spec_ks, jnp.int32))

    # ----- host API ----------------------------------------------------
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_tokens: int,
        *,
        greedy: bool = True,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        k: Optional[int] = None,
    ) -> List[List[int]]:
        """Batch speculative generation with the same semantics (and, for
        greedy, the same token streams) as :meth:`GenerationSession.
        generate`. Near the cache limit, where a full ``k+1`` window no
        longer fits, the batch falls back to plain [B, 1] decode steps so
        no write ever lands past ``max_len``. Records acceptance counters
        in :attr:`last_stats`."""
        b = len(prompts)
        kk = self.k if k is None else int(k)
        tcarry, logits, lens = self.target.prefill(prompts)
        dcarry, _, _ = self.draft.prefill(prompts)
        seeds = jnp.full((b,), seed, jnp.uint32) + jnp.arange(
            b, dtype=jnp.uint32)
        gmask = jnp.full((b,), bool(greedy))
        temps = jnp.full((b,), temperature, jnp.float32)
        ks = jnp.full((b,), top_k, jnp.int32)
        ps = jnp.full((b,), top_p, jnp.float32)
        out: List[List[int]] = [[] for _ in range(b)]
        done = [False] * b
        comm = lens.copy().astype(np.int64)  # committed length per row
        first = sample_tokens(logits, seeds, jnp.zeros((b,), jnp.int32),
                              gmask, temps, ks, ps)
        last = np.asarray(first).astype(np.int32)
        for i in range(b):
            t = int(last[i])
            out[i].append(t)
            comm[i] += 1
            if ((eos_id is not None and t == eos_id)
                    or comm[i] >= self.max_len or max_tokens <= 1):
                done[i] = True
        steps_h = np.ones((b,), np.int32)
        spec_steps = proposed = accepted = 0
        while not all(done):
            active_rows = [i for i in range(b) if not done[i]]
            k_step = min(kk, min(self.max_len - int(comm[i])
                                 for i in active_rows))
            active = jnp.asarray([not d for d in done])
            if k_step >= 1:
                spec_ks_h = np.where([not d for d in done], k_step, 0)
                tcarry, dcarry, toks, n_acc, n_emit = self.step(
                    tcarry, dcarry, last, steps_h, active, seeds, gmask,
                    temps, ks, ps, spec_ks_h, k=k_step)
                toks_h = np.asarray(toks)
                acc_h, ne_h = np.asarray(n_acc), np.asarray(n_emit)
                spec_steps += 1
                for i in active_rows:
                    proposed += int(spec_ks_h[i])
                    accepted += int(acc_h[i])
                    for j in range(int(ne_h[i])):
                        t = int(toks_h[i, j])
                        out[i].append(t)
                        comm[i] += 1
                        steps_h[i] += 1
                        last[i] = t
                        if ((eos_id is not None and t == eos_id)
                                or len(out[i]) >= max_tokens
                                or comm[i] >= self.max_len):
                            done[i] = True
                            break
            else:
                # boundary fallback: plain decode (no speculative write
                # may straddle max_len)
                tcarry, step_logits = self.target.decode(tcarry, last)
                toks = sample_tokens(step_logits, seeds, steps_h, gmask,
                                     temps, ks, ps)
                toks_h = np.asarray(toks)
                for i in active_rows:
                    t = int(toks_h[i])
                    out[i].append(t)
                    comm[i] += 1
                    steps_h[i] += 1
                    last[i] = t
                    if ((eos_id is not None and t == eos_id)
                            or len(out[i]) >= max_tokens
                            or comm[i] >= self.max_len):
                        done[i] = True
        self.last_stats = {
            "spec_steps": spec_steps,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": (accepted / proposed) if proposed else None,
            "accepted_per_step": ((accepted + spec_steps) / spec_steps)
            if spec_steps else None,
        }
        return out
