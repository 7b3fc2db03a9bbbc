"""Sort-based MoE token dispatch — gather/scatter instead of one-hot einsums.

The GShard/Mesh-TF dense formulation (nn/layers/moe.py ``dispatch_mode=
"einsum"``) turns routing into two ``[tokens, E, capacity]`` one-hot
contractions. That keeps every shape static, but the dispatch einsum is
O(tokens · E · capacity · d) with capacity ≈ top_k·tokens·cf/E — quadratic
in the token count — and almost all of that "MXU work" multiplies zeros
(BENCH: 2.84× the grad-step cost of an equal-FLOPs dense FFN at
tokens=8192, E=8, top_k=2). GShard's successors (PAPERS.md: the MLPerf
TPU-pod scaling and cross-replica sharding reports) moved to gather/
scatter dispatch for exactly this reason.

This module keeps every shape static while replacing the contractions with
index arithmetic:

1. route with ONE ``jax.lax.top_k`` (``top_k_routing``);
2. assign capacity slots with a per-expert cumsum over the flat
   (round, token) assignment list (``make_dispatch_plan``) — round-major
   order reproduces the einsum path's first-come-first-served capacity
   contract bit-for-bit (round 0 of every token claims slots before
   round 1 of any token, tokens in batch order within a round);
3. permute tokens into the ``[E, C, d]`` expert buffer with one
   ``jnp.take`` (``gather_dispatch``) — the leading ``E`` dim is the same
   expert-parallel sharding axis the einsum path exposes, so
   ``DistributedTrainer`` expert sharding rules carry over unchanged;
4. combine expert outputs back to token order with a gate-weighted gather
   (``scatter_combine``; the name is the backward view — its transpose is
   the scatter).

Overflowing (token, round) assignments map to an out-of-range sentinel
slot, so the scatter drops them (``mode="drop"``) and the gathers fill
zeros (``mode="fill"``) — the exact GShard drop semantics: a dropped
assignment contributes nothing and the residual path carries the token.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def top_k_routing(gates: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """Route with a single ``jax.lax.top_k``.

    Returns ``(gate_vals [n, k], expert_idx [n, k])``, descending by gate
    with ties to the lower expert index — the same selection sequence as
    the legacy k-round argmax-and-mask loop, in one HLO op (and top_k's
    VJP scatters the gate gradient to the selected entries, matching the
    ``sum(gates * one_hot)`` gradient of the loop formulation).
    """
    return jax.lax.top_k(gates, top_k)


def biased_top_k_routing(scores: jax.Array, bias: jax.Array, top_k: int,
                          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """Route by ``scores + bias`` and weigh by the scores alone: the
    selection bias of a served router (a load balancer's constant at
    serving time) moves WHICH experts a token takes, never how much each
    counts. Returns ``(scale * scores at the chosen [n, k], expert_idx
    [n, k])``; the weights are not renormalised."""
    _, idx = top_k_routing(scores + bias, top_k)
    return scale * jnp.take_along_axis(scores, idx, axis=-1), idx


def held_expert_choices(expert_idx: jax.Array, first: int, held: int,
                        routed: int) -> Tuple[jax.Array, jax.Array]:
    """Where a chip that holds the routed experts ``first .. first + held``
    of ``routed`` (the router's outputs from ``routed`` on are zero-compute
    experts, which every chip has) finds each choice ``[n, k]``: ``(local
    [n, k], counts [n, held + 2])``. ``local`` is the held expert's index
    here, or ``held`` (no expert: :func:`make_dispatch_plan` gives such a
    choice no slot) for a choice that went elsewhere; ``counts`` are the
    token's choices by where they went: one column a held expert, then the
    ABSENT routed experts (another chip's), then the zero-compute ones."""
    local = expert_idx - first
    here = (local >= 0) & (local < held)
    zero = expert_idx >= routed
    local = jnp.where(here, local, held).astype(jnp.int32)
    kinds = jnp.where(zero, held + 1, local)  # absent stays at ``held``
    counts = jnp.sum(jax.nn.one_hot(kinds, held + 2, dtype=jnp.int32), axis=1)
    return local, counts


class DispatchPlan(NamedTuple):
    """Static-shape routing plan for one batch of ``n`` tokens.

    Flat ``[k*n]`` arrays index the round-major flattened assignment list:
    row ``r*n + t`` is round ``r``'s expert choice for token ``t``. ``E*C``
    in ``buffer_idx`` (resp. ``n`` in ``slot_token``) is the out-of-range
    sentinel for dropped assignments (resp. unfilled slots).
    """

    buffer_idx: jax.Array     # [k*n] int32: expert*C + slot; E*C = dropped
    keep: jax.Array           # [k*n] bool: assignment claimed a slot
    slot_token: jax.Array     # [E*C] int32: source token per slot; n = empty
    expert_tokens: jax.Array  # [E] int32: assignments kept per expert
    dropped_tokens: jax.Array  # [] int32: assignments dropped (overflow)


def make_dispatch_plan(
    expert_idx: jax.Array,
    num_experts: int,
    capacity: int,
    token_mask: Optional[jax.Array] = None,
) -> DispatchPlan:
    """Assign capacity slots: per-expert cumsum over the flat assignment
    list, first-come-first-served in (round, token) order.

    ``expert_idx`` is ``[n, k]`` int (from :func:`top_k_routing`).
    ``token_mask`` ``[n]`` (nonzero = real) excludes padding tokens
    entirely: they claim no capacity slot and appear in no expert buffer.
    """
    n, k = expert_idx.shape
    flat_expert = expert_idx.T.reshape(-1)  # [k*n], round-major
    onehot = (flat_expert[:, None]
              == jnp.arange(num_experts, dtype=flat_expert.dtype)[None, :]
              ).astype(jnp.int32)                              # [k*n, E]
    if token_mask is not None:
        valid = jnp.tile(token_mask > 0, k)                    # [k*n]
        onehot = onehot * valid[:, None].astype(jnp.int32)
    # running per-expert fill count at each flat row; invalid rows (masked
    # tokens) have an all-zero onehot row and land at -1 => never kept
    within = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    keep = (within >= 0) & (within < capacity)
    sentinel = num_experts * capacity
    buffer_idx = jnp.where(
        keep, flat_expert.astype(jnp.int32) * capacity + within.astype(jnp.int32),
        sentinel).astype(jnp.int32)
    flat_token = jnp.tile(jnp.arange(n, dtype=jnp.int32), k)
    # int scatter only — the inverse permutation; out-of-range (dropped)
    # rows vanish, kept rows hit distinct slots by construction
    slot_token = jnp.full((sentinel,), n, jnp.int32).at[buffer_idx].set(
        flat_token, mode="drop")
    kept = onehot * keep[:, None].astype(jnp.int32)
    expert_tokens = jnp.sum(kept, axis=0)
    dropped_tokens = jnp.sum(onehot) - jnp.sum(kept)
    return DispatchPlan(buffer_idx, keep, slot_token, expert_tokens,
                        dropped_tokens)


def gather_dispatch(x: jax.Array, plan: DispatchPlan, num_experts: int,
                    capacity: int) -> jax.Array:
    """Permute tokens ``[n, d]`` into the expert buffer ``[E, C, d]`` with
    one gather; unfilled slots read zeros (their combine weight is zero, so
    like the einsum path's zero rows they only feed the bias path, which
    the combine then discards)."""
    buf = jnp.take(x, plan.slot_token, axis=0, mode="fill", fill_value=0)
    return buf.reshape(num_experts, capacity, x.shape[-1])


def scatter_combine(out_e: jax.Array, gate_vals: jax.Array,
                    plan: DispatchPlan, *, renormalize: bool = True,
                    eps: float = 1e-9) -> jax.Array:
    """Combine expert outputs ``[E, C, o]`` back to token order ``[n, o]``.

    Each kept (round, token) assignment gathers its expert-buffer row and
    weights it by the (renormalized) gate; dropped assignments contribute
    zero. ``renormalize=True`` divides by the sum of KEPT gates per token,
    matching the einsum path: a token whose assignments all dropped gets
    exactly zero output (the residual path carries it).
    """
    e, c, o = out_e.shape
    rows = jnp.take(out_e.reshape(e * c, o), plan.buffer_idx, axis=0,
                    mode="fill", fill_value=0)                 # [k*n, o]
    return combine_rows(rows, gate_vals, plan.keep,
                        renormalize=renormalize, eps=eps)


def combine_rows(rows: jax.Array, gate_vals: jax.Array, keep: jax.Array,
                 *, renormalize: bool = True, eps: float = 1e-9) -> jax.Array:
    """Gate-weight per-assignment output rows ``[k*n, o]`` (round-major
    flat order) down to token order ``[n, o]`` — the combine arithmetic
    shared by every dispatch mode, factored out so ``"grouped"`` (which
    sources rows from the sorted grouped matmul instead of the ``[E, C]``
    buffer) is gate-math-identical to ``"sort"`` by construction."""
    n, k = gate_vals.shape
    o = rows.shape[-1]
    gate_flat = gate_vals.T.reshape(-1)                        # [k*n]
    kept_gate = jnp.where(keep, gate_flat, 0)
    if renormalize:
        denom = jnp.sum(kept_gate.reshape(k, n), axis=0)       # [n]
        kept_gate = kept_gate / jnp.tile(jnp.maximum(denom, eps), k)
    return jnp.sum((rows * kept_gate[:, None]).reshape(k, n, o), axis=0)
