"""Mixture-of-Experts layer with expert parallelism.

Beyond-reference capability (SURVEY §2.3 lists EP as absent upstream;
"on TPU the absent rows come nearly free from pjit"): a Switch/GShard-style
sparse FFN whose expert weights carry a leading expert dimension that
shards over a mesh axis via ``DistributedTrainer(param_sharding_rules=
moe_expert_parallel_rules())`` — XLA then partitions the expert MLP and
inserts the all-to-alls.

Three dispatch formulations, selected by ``dispatch_mode``:

* ``"sort"`` (default) — sort-based gather/scatter dispatch
  (ops/moe_dispatch.py): one ``lax.top_k`` route, capacity slots from a
  per-expert cumsum over the flat assignment list, one gather into the
  ``[E, C, d]`` expert buffer, gate-weighted gather back. Static shapes,
  no one-hot contractions; the routing cost is O(tokens·E) index math
  instead of the einsum path's O(tokens·E·capacity·d). The expert MLP
  still pays dense ``[E, C]`` MXU time over *capacity* slots.
* ``"grouped"`` — the fast path: the same ``DispatchPlan``, but the sort
  permutation (argsort of ``buffer_idx`` — already the by-expert order)
  feeds both expert MLP matmuls through ``ops.grouped_matmul``, grouped
  over the *actual* per-expert counts (``expert_tokens``), so padded
  capacity slots stop costing FLOPs (the Pallas kernel skips m-tiles past
  each group's frontier). The combine unsorts through the inverse
  permutation into the same gate arithmetic (``ops.combine_rows``).
* ``"einsum"`` — the classic dense Mesh-TF/GShard formulation (one-hot
  ``[tokens, E, capacity]`` dispatch/combine contractions). Kept for
  equivalence testing and as the reference semantics.

All modes implement the exact GShard capacity contract: slots are granted
first-come-first-served in (round, token) order and tokens over an
expert's capacity are dropped (their combine weight is 0 — the residual
path carries them), so outputs and gradients agree between modes up to
float reduction order.

Explicit expert parallelism: inside the ``DistributedTrainer`` explicit
shard_map path (``ctx.dist.ep_axis`` set and expert params sliced over
the mesh's model axis), each shard routes the full token set with the
replicated router, computes its local experts only — ``"sort"`` over the
local ``[E/n, C]`` buffer slice, ``"grouped"`` over the locally-sorted
rows — and combines with ``psum_scatter`` over the expert axis. Tensors
entering the local branch carry a psum-in-backward wrapper so replicated
params (router, upstream layers) receive the full cross-shard gradient.

Observability: every ``apply`` refreshes ``state["expert_tokens"]`` ([E]
kept assignments per expert) and ``state["dropped_tokens"]`` (overflow
drops), which ``obs.record_moe_metrics``/``MoEMetricsListener`` feed into
``dl4j_tpu_moe_expert_tokens_total{layer=,expert=}`` and
``dl4j_tpu_moe_dropped_tokens_total{layer=}``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.config import register_config
from ...ops.grouped_matmul import grouped_matmul
from ...ops.moe_dispatch import (
    biased_top_k_routing,
    combine_rows,
    gather_dispatch,
    held_expert_choices,
    make_dispatch_plan,
    scatter_combine,
    top_k_routing,
)
from ..activations import Activation
from ..input_type import FeedForwardType, InputType, RecurrentType
from ..weights import WeightInit, init_weights
from .base import Layer, LayerContext, Params, State, apply_input_dropout

_DISPATCH_MODES = ("sort", "einsum", "grouped")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_in_bwd(x, axis):
    """Identity forward; psums the cotangent over ``axis`` in backward.

    Under explicit expert parallelism a replicated tensor (tokens, gates)
    enters a per-shard local-expert branch; each shard backprops only its
    own experts' contribution, so the gradient flowing back to replicated
    producers (router, upstream layers) must be summed across expert
    shards to stay replicated-consistent."""
    return x


def _psum_in_bwd_fwd(x, axis):
    return x, None


def _psum_in_bwd_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_psum_in_bwd.defvjp(_psum_in_bwd_fwd, _psum_in_bwd_bwd)


def _ep_sum(y_local, axis, n_shards):
    if y_local.shape[0] % n_shards == 0:
        # reduce-scatter over tokens, gather back: the psum spelled so a
        # token-sharded consumer could elide the all_gather
        return jax.lax.all_gather(
            jax.lax.psum_scatter(y_local, axis, scatter_dimension=0,
                                 tiled=True),
            axis, axis=0, tiled=True)
    return jax.lax.psum(y_local, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _ep_combine(y_local, axis, n_shards):
    """Sum per-shard expert contributions over the expert axis, with an
    IDENTITY backward. Each shard seeds its own (replicated-identical)
    loss cotangent, so the correct per-loss cotangent of ``y_local`` is
    ``g`` unchanged; psum's default transpose would re-psum it and scale
    every expert-local gradient by the expert-axis size."""
    return _ep_sum(y_local, axis, n_shards)


def _ep_combine_fwd(y_local, axis, n_shards):
    return _ep_sum(y_local, axis, n_shards), None


def _ep_combine_bwd(axis, n_shards, _, g):
    return (g,)


_ep_combine.defvjp(_ep_combine_fwd, _ep_combine_bwd)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class MixtureOfExpertsLayer(Layer):
    """Sparse MoE FFN: router -> top-k experts (2-layer MLPs) -> combine.

    Params: router ``Wg [nIn, E]``; per-expert ``We1 [E, nIn, hidden]``,
    ``be1 [E, hidden]``, ``We2 [E, hidden, nOut]``, ``be2 [E, nOut]``.
    The leading ``E`` dim is the expert-parallel sharding axis.
    """

    n_in: int = 0
    n_out: int = 0
    num_experts: int = 4
    hidden: int = 0            # defaults to 4 * n_in
    top_k: int = 2
    capacity_factor: float = 1.5
    # GShard aux load-balance loss weight: when > 0, the training score
    # adds balance_loss_weight * (E * sum(frac_e * mass_e)) so the router
    # is PUSHED toward uniform expert load, not merely observed. 0 keeps
    # it diagnostic-only (read from state["aux_load_balance"]).
    balance_loss_weight: float = 0.0
    # "sort" (gather/scatter, default), "grouped" (sorted grouped expert
    # matmul over actual per-expert counts — the Pallas fast path), or
    # "einsum" (dense one-hot contractions — the legacy GShard
    # formulation, kept for equivalence testing). Identical capacity/drop
    # semantics in every mode.
    dispatch_mode: str = "sort"

    def __post_init__(self) -> None:
        if self.top_k < 1 or self.top_k > self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts="
                f"{self.num_experts}]")
        if self.dispatch_mode not in _DISPATCH_MODES:
            raise ValueError(
                f"dispatch_mode={self.dispatch_mode!r} must be one of "
                f"{_DISPATCH_MODES}")

    def output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentType):
            return RecurrentType(size=self.n_out,
                                 timesteps=input_type.timesteps)
        return FeedForwardType(size=self.n_out)

    def with_input(self, input_type: InputType) -> "MixtureOfExpertsLayer":
        if self.n_in:
            return self
        size = input_type.size if isinstance(
            input_type, (FeedForwardType, RecurrentType)) \
            else input_type.flat_size()
        return dataclasses.replace(self, n_in=size)

    def has_params(self) -> bool:
        return True

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wg", "We1", "be1", "We2", "be2")

    def _hidden(self) -> int:
        return self.hidden or 4 * self.n_in

    def init_state(self, dtype: Any) -> State:
        # declared up-front so the state pytree structure is stable across
        # jitted steps (apply refreshes the values every call). Counts live
        # in float32 regardless of the compute dtype: bf16 can't represent
        # integers above 256 exactly.
        return {"aux_load_balance": jnp.zeros((), dtype),
                "expert_tokens": jnp.zeros((self.num_experts,), jnp.float32),
                "dropped_tokens": jnp.zeros((), jnp.float32),
                "capacity_slots": jnp.zeros((), jnp.float32)}

    def init(self, key: jax.Array, dtype: Any) -> Params:
        e, d, h, o = self.num_experts, self.n_in, self._hidden(), self.n_out
        kg, k1, k2 = jax.random.split(key, 3)
        wi = self.weight_init or WeightInit.XAVIER
        return {
            "Wg": init_weights(kg, (d, e), wi, fan_in=d, fan_out=e,
                               distribution=self.weight_init_distribution,
                               dtype=dtype),
            "We1": init_weights(k1, (e, d, h), wi, fan_in=d, fan_out=h,
                                distribution=self.weight_init_distribution,
                                dtype=dtype),
            "be1": jnp.zeros((e, h), dtype),
            "We2": init_weights(k2, (e, h, o), wi, fan_in=h, fan_out=o,
                                distribution=self.weight_init_distribution,
                                dtype=dtype),
            "be2": jnp.zeros((e, o), dtype),
        }

    def _route(self, gates: jax.Array, capacity: int,
               token_mask: Optional[jax.Array] = None):
        """Dense top-k dispatch (``dispatch_mode="einsum"``): returns
        (dispatch [b, E, C] 0/1, combine [b, E, C] gate-weighted).
        Position assignment is first-come-first-served per expert in
        (round, batch) order (GShard). Routing is ONE ``lax.top_k`` —
        round ``r``'s selection is column ``r`` of its result, replacing
        the legacy k-round argmax-and-remask loop with identical
        semantics (descending gate, ties to the lower expert index).
        ``token_mask`` [b] excludes padding tokens entirely: they claim no
        capacity slot and contribute nothing to dispatch/combine."""
        b, e = gates.shape
        gate_vals, idx = top_k_routing(gates, self.top_k)        # [b, k]
        dispatch = jnp.zeros((b, e, capacity), gates.dtype)
        combine = jnp.zeros((b, e, capacity), gates.dtype)
        # running per-expert fill across the k rounds
        fill = jnp.zeros((1, e), gates.dtype)
        for r in range(self.top_k):
            sel = jax.nn.one_hot(idx[:, r], e, dtype=gates.dtype)  # [b, E]
            if token_mask is not None:
                sel = sel * token_mask[:, None]
            # position of each token within its chosen expert's buffer,
            # counting earlier rounds' fills
            pos = (jnp.cumsum(sel, axis=0) - 1.0 + fill) * sel   # [b, E]
            pos_idx = jnp.sum(pos, axis=-1).astype(jnp.int32)    # [b]
            keep = (pos_idx < capacity).astype(gates.dtype)
            slot = jax.nn.one_hot(pos_idx, capacity,
                                  dtype=gates.dtype)             # [b, C]
            d_i = sel[:, :, None] * slot[:, None, :] * keep[:, None, None]
            dispatch = dispatch + d_i
            combine = combine + d_i * gate_vals[:, r][:, None, None]
            fill = fill + jnp.sum(sel * keep[:, None], axis=0,
                                  keepdims=True)
        # renormalize combine weights over the k selected experts
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
        return dispatch, combine

    def _expert_kernel(self, params: Params,
                       name: str) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Expert weight-slab view hook: returns ``(weights, scale)``.

        The full-precision layer stores weights directly (``scale`` is
        None); ``QuantizedMixtureOfExpertsLayer`` overrides this to return
        the int8/fp8 slab plus its per-expert per-output-channel scale,
        which the matmul epilogues below fold in — so every dispatch mode
        (einsum buffer, sort buffer, grouped rows) serves quantized
        experts through the same code path."""
        return params[name], None

    def _experts(self, params: Params, expert_in: jax.Array) -> jax.Array:
        """Batched expert MLPs over the [E, C, d] buffer — the leading E
        dim is what expert-parallel sharding rules partition."""
        w1, s1 = self._expert_kernel(params, "We1")
        h = jnp.einsum("ecd,edh->ech", expert_in, w1.astype(expert_in.dtype))
        if s1 is not None:
            h = h * s1[:, None, :].astype(h.dtype)
        h = h + params["be1"][:, None, :]
        act = self.activation or Activation.RELU
        h = act(h)
        w2, s2 = self._expert_kernel(params, "We2")
        out = jnp.einsum("ech,eho->eco", h, w2.astype(h.dtype))
        if s2 is not None:
            out = out * s2[:, None, :].astype(out.dtype)
        return out + params["be2"][:, None, :]

    def _experts_grouped(self, params: Params, rows: jax.Array,
                         group_sizes: jax.Array, row_expert: jax.Array,
                         capacity: int) -> jax.Array:
        """Both expert MLP matmuls over rows pre-sorted by expert
        (``ops.grouped_matmul`` — compute proportional to actual
        per-expert counts, capacity only bounds the kernel tile).
        ``row_expert`` [N] (clipped to the local expert range) gathers
        per-row biases and quantization scales."""
        w1, s1 = self._expert_kernel(params, "We1")
        h = grouped_matmul(rows, group_sizes, w1.astype(rows.dtype),
                           max_group_size=capacity)
        if s1 is not None:
            h = h * jnp.take(s1, row_expert, axis=0).astype(h.dtype)
        h = h + jnp.take(params["be1"], row_expert, axis=0)
        act = self.activation or Activation.RELU
        h = act(h)
        w2, s2 = self._expert_kernel(params, "We2")
        out = grouped_matmul(h, group_sizes, w2.astype(h.dtype),
                             max_group_size=capacity)
        if s2 is not None:
            out = out * jnp.take(s2, row_expert, axis=0).astype(out.dtype)
        return out + jnp.take(params["be2"], row_expert, axis=0)

    def _grouped_rows(self, params: Params, x2: jax.Array,
                      buffer_idx: jax.Array, group_sizes: jax.Array,
                      n_local: int, capacity: int) -> jax.Array:
        """Sorted grouped expert compute returning per-assignment output
        rows [k*n, o] in round-major flat order (ready for
        ``ops.combine_rows``).

        ``buffer_idx`` sorts kept assignments by (expert, slot) with
        dropped/non-local assignments on a past-the-end sentinel, so its
        argsort IS the by-expert order and rows past
        ``sum(group_sizes)`` come back zero from the grouped matmul
        (their bias-path values are discarded by the combine's zero gate,
        exactly like the sort path's empty buffer slots)."""
        kn = buffer_idx.shape[0]
        n_tok = x2.shape[0]
        k = kn // n_tok
        order = jnp.argsort(buffer_idx)                     # by-expert order
        flat_token = jnp.tile(jnp.arange(n_tok, dtype=jnp.int32), k)
        rows_in = jnp.take(x2, flat_token[order], axis=0)   # [k*n, d]
        sizes = group_sizes.astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        row_expert = jnp.minimum(
            jnp.searchsorted(ends, jnp.arange(kn, dtype=ends.dtype),
                             side="right"),
            n_local - 1).astype(jnp.int32)
        out_rows = self._experts_grouped(params, rows_in, sizes, row_expert,
                                         capacity)
        # inverse permutation: back to round-major assignment order
        inv = jnp.zeros((kn,), jnp.int32).at[order].set(
            jnp.arange(kn, dtype=jnp.int32))
        return jnp.take(out_rows, inv, axis=0)

    def _ep_forward(self, params: Params, x2: jax.Array,
                    gate_vals: jax.Array, plan, capacity: int,
                    ep_axis: str) -> jax.Array:
        """Explicit expert parallelism inside shard_map: this shard holds
        ``E/n`` experts (params sliced over the model axis by the
        trainer), routes the full replicated token set, computes only the
        assignments its experts own, and combines with ``psum_scatter``
        over the expert axis. Replicated inputs to the local branch are
        wrapped so their gradients psum across shards (see
        ``_psum_in_bwd``)."""
        e = self.num_experts
        e_loc = self._expert_kernel(params, "We1")[0].shape[0]
        n_shards = e // e_loc
        x2w = _psum_in_bwd(x2, ep_axis)
        gate_w = _psum_in_bwd(gate_vals, ep_axis)
        shard = jax.lax.axis_index(ep_axis)
        first_slot = shard * (e_loc * capacity)
        local_idx = plan.buffer_idx - first_slot
        in_local = (local_idx >= 0) & (local_idx < e_loc * capacity)
        local_idx = jnp.where(in_local, local_idx,
                              e_loc * capacity).astype(jnp.int32)
        if self.dispatch_mode == "grouped":
            sizes_local = jax.lax.dynamic_slice_in_dim(
                plan.expert_tokens, shard * e_loc, e_loc)
            rows = self._grouped_rows(params, x2w, local_idx, sizes_local,
                                      e_loc, capacity)
            # non-local assignments carry real gates: their rows must be
            # exactly zero so only the owning shard contributes
            rows = rows * in_local[:, None].astype(rows.dtype)
        else:  # "sort" over the local [E/n, C] buffer slice
            slot_local = jax.lax.dynamic_slice_in_dim(
                plan.slot_token, first_slot, e_loc * capacity)
            expert_in = jnp.take(x2w, slot_local, axis=0, mode="fill",
                                 fill_value=0).reshape(e_loc, capacity,
                                                       x2.shape[-1])
            out_e = self._experts(params, expert_in)
            rows = jnp.take(out_e.reshape(e_loc * capacity, -1), local_idx,
                            axis=0, mode="fill", fill_value=0)
        y_local = combine_rows(rows, gate_w, plan.keep)
        return _ep_combine(y_local, ep_axis, n_shards)

    def apply(self, params: Params, state: State, x: jax.Array,
              ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        recurrent = x.ndim == 3
        if recurrent:  # [b, f, t] -> tokens [b*t, f]
            b_, f_, t_ = x.shape
            x2 = jnp.transpose(x, (0, 2, 1)).reshape(b_ * t_, f_)
        else:
            x2 = x
        n_tok = x2.shape[0]
        e = self.num_experts
        capacity = max(1, int(math.ceil(
            self.top_k * n_tok / e * self.capacity_factor)))

        token_mask = None
        if recurrent and ctx.mask is not None:  # [b, t] -> [b*t]
            token_mask = jnp.reshape(
                jnp.asarray(ctx.mask, x2.dtype), (b_ * t_,))

        gates = jax.nn.softmax(x2 @ params["Wg"], axis=-1)       # [b, E]

        e_loc = self._expert_kernel(params, "We1")[0].shape[0]
        ep_axis = getattr(ctx.dist, "ep_axis", None) if ctx.dist else None
        ep = ep_axis is not None and e_loc != e
        if ep:
            if self.dispatch_mode == "einsum":
                raise ValueError(
                    "dispatch_mode='einsum' has no explicit expert-parallel "
                    "spelling; use 'sort' or 'grouped'")
            if e % e_loc != 0:
                raise ValueError(
                    f"num_experts={e} must divide evenly over the expert-"
                    f"parallel axis (local shard holds {e_loc})")

        if self.dispatch_mode in ("sort", "grouped"):
            gate_vals, expert_idx = top_k_routing(gates, self.top_k)
            plan = make_dispatch_plan(expert_idx, e, capacity,
                                      token_mask=token_mask)
            if ep:
                y = self._ep_forward(params, x2, gate_vals, plan, capacity,
                                     ep_axis)
            elif self.dispatch_mode == "grouped":
                rows = self._grouped_rows(params, x2, plan.buffer_idx,
                                          plan.expert_tokens, e, capacity)
                y = combine_rows(rows, gate_vals, plan.keep)     # [b, o]
            else:
                expert_in = gather_dispatch(x2, plan, e, capacity)
                out_e = self._experts(params, expert_in)         # [E, C, o]
                y = scatter_combine(out_e, gate_vals, plan)      # [b, o]
            expert_tokens = plan.expert_tokens.astype(jnp.float32)
            dropped = plan.dropped_tokens.astype(jnp.float32)
        else:
            dispatch, combine = self._route(gates, capacity, token_mask)
            expert_in = jnp.einsum("bec,bd->ecd", dispatch, x2)  # [E, C, d]
            out_e = self._experts(params, expert_in)
            y = jnp.einsum("bec,eco->bo", combine, out_e)        # [b, o]
            # count in f32: a bf16 sum of 0/1s goes inexact past 256
            expert_tokens = jnp.sum(dispatch.astype(jnp.float32),
                                    axis=(0, 2))
            requested = self.top_k * (
                jnp.sum(token_mask.astype(jnp.float32))
                if token_mask is not None else jnp.float32(n_tok))
            dropped = requested - jnp.sum(expert_tokens)

        # load-balance aux (GShard): fraction routed per expert x mean gate
        # mass per expert, E-scaled. Exposed via state for listeners; added
        # to the training score iff balance_loss_weight > 0 (the loss paths
        # in sequential.py/graph.py read it back). Real tokens only.
        if token_mask is not None:
            denom_tok = jnp.maximum(jnp.sum(token_mask), 1.0)
            mass = jnp.sum(gates * token_mask[:, None], axis=0) / denom_tok
        else:
            denom_tok = jnp.asarray(n_tok, gates.dtype)
            mass = jnp.mean(gates, axis=0)
        frac = expert_tokens.astype(gates.dtype) / denom_tok
        new_state = dict(state)
        new_state["aux_load_balance"] = e * jnp.sum(frac * mass)
        new_state["expert_tokens"] = expert_tokens
        new_state["dropped_tokens"] = dropped
        # total granted capacity slots (E * C) this batch — lets listeners
        # derive occupancy/drop pressure without re-deriving the GShard
        # capacity formula client-side
        new_state["capacity_slots"] = jnp.asarray(e * capacity, jnp.float32)

        if recurrent:
            y = jnp.transpose(y.reshape(b_, t_, self.n_out), (0, 2, 1))
        return y, new_state


def _router_logits(x: jax.Array, wr: jax.Array) -> jax.Array:
    """``x @ wr`` in float32. Router weights served in a 16-bit type are
    exact in it, so a float32 activation goes through the MXU as its two
    16-bit halves (what the second half leaves is 2^-17 of the value) and
    the products accumulate in float32; a product of float32 by float32 at
    the highest precision otherwise. (At 128 rows the chip's compiler ran
    the latter on the vector unit: 0.54 ms a layer of a 23 ms step.)"""
    f32 = jnp.float32
    if wr.dtype == f32 or x.dtype != f32:
        return jnp.dot(x.astype(f32), wr.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
    hi = x.astype(wr.dtype)
    lo = (x - hi.astype(f32)).astype(wr.dtype)
    return jnp.dot(hi, wr, preferred_element_type=f32) \
        + jnp.dot(lo, wr, preferred_element_type=f32)


#: slots of the sorted form over an expert's mean load: a served selection
#: bias makes the loads uneven, and the hottest expert of a layer took up to
#: 2.7 times the mean over 200 simulated seeds at 32 experts top-4
#: (``benchmarks/families/lfm2_moe.py`` ``init_scale``)
SORTED_LOAD_FACTOR = 3.0


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class ExpertShareMoELayer(Layer):
    """ONE CHIP'S SHARE of an expert layer with modern routing, as expert
    parallelism divides it: the router keeps its published width, the layer
    is told which routed experts it holds (``n_held_experts`` from
    ``first_held_expert`` on, of ``n_routed_experts``) and computes their
    part of the result for EVERY token routed to them, whatever the load:
    no capacity, no dropped token. What the absent experts would add is
    another chip's; nothing here stands in for it or for the exchange.

        s = softmax_float32(Wr u) over n_routed_experts + zero_expert_num
        (``scoring="sigmoid"``: sigmoid_float32, each output on its own)
        chosen: the top_k largest of s + br (the served selection bias
        moves the choice, not the weight); weight routed_scaling_factor *
        s_e, NOT renormalised (``norm_topk_prob``: routed_scaling_factor *
        s_e / (the sum of s over ALL the chosen + 1e-6), whichever chip
        holds them, so that the shares of a layer still add up)
        y = sum over the chosen of weight_e E_e(u): E_e the gated expert
        ``Ed_e (silu(Eg_e u) * Eu_e u)`` (no bias) for a held e, nothing
        for an absent one, and ``E_e(u) = u`` for e >= n_routed_experts
        (a ZERO-COMPUTE expert: it holds no weights and lives on every
        chip)
        + S(u) with ``n_shared_experts``: the SHARED expert, a gated FFN of
        ``n_shared_experts * hidden`` that every token passes at weight 1,
        outside the router; every chip computes it for its own rows, so it
        is no part of a share (:meth:`shared`)

    Params: ``Wr [n_in, E + Z]``, ``br [E + Z]``, ``Eg``/``Eu`` ``[held,
    n_in, hidden]``, ``Ed [held, hidden, n_in]``; with a shared expert
    ``Sg``/``Su`` ``[n_in, n_shared_experts * hidden]``, ``Sd``. Shapes
    are static for the worst load (every token to one held expert). Up to ``expert_rows``
    tokens every held expert runs over every token and the weights (nought
    where a token did not choose it) fold into the down-projection: at a
    decode step's rows that product is under the time the expert weights
    take to read. Beyond, tokens are sorted into ``[held, rows]`` buffers
    with the dispatch plan ``MixtureOfExpertsLayer`` uses
    (``ops/moe_dispatch.py``), and a call in which some held expert has
    more than ``rows`` tokens runs the held experts one at a time over
    every token instead (``lax.cond``; what is alive is one expert's, not
    ``held`` times the call's tokens): slower, never lossy. ``rows`` follows
    the call's tokens
    (:meth:`sorted_rows`: three times an expert's mean load, in whole
    ``expert_rows``), so a long prompt's buffers hold its load and a
    short one's are not sized for it.

    ``state["choice_counts"]`` (``[held + 2]``: a column a held expert, the
    absent, the zero-compute) says where the call's choices went."""

    n_in: int = 0
    hidden: int = 0
    n_routed_experts: int = 8
    zero_expert_num: int = 0
    n_held_experts: int = 0          # 0: every routed expert is held
    first_held_expert: int = 0
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    expert_rows: int = 128
    scoring: str = "softmax"         # or "sigmoid"
    norm_topk_prob: bool = False
    n_shared_experts: int = 0

    def __post_init__(self) -> None:
        width = self.n_routed_experts + self.zero_expert_num
        if not 1 <= self.top_k <= width:
            raise ValueError(f"top_k={self.top_k} must be in [1, {width}]")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: softmax or sigmoid")
        if self.first_held_expert + self.held > self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_held_expert}..+{self.held} are not "
                f"among the {self.n_routed_experts} routed")

    @property
    def held(self) -> int:
        return self.n_held_experts or self.n_routed_experts

    def choice_columns(self) -> Tuple[str, ...]:
        """The names of the ``held + 2`` columns of the counts."""
        first = self.first_held_expert
        return tuple(f"expert:{first + e}" for e in range(self.held)) \
            + ("absent", "zero")

    def sorted_rows(self, n_tokens: int) -> int:
        """Slots an expert's buffer of the sorted form has in a call of
        ``n_tokens``: :data:`SORTED_LOAD_FACTOR` times the mean load of one
        of the router's outputs, rounded up to whole ``expert_rows``."""
        width = self.n_routed_experts + self.zero_expert_num
        mean = n_tokens * self.top_k / width
        return self.expert_rows * max(
            1, math.ceil(SORTED_LOAD_FACTOR * mean / self.expert_rows))

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def with_input(self, input_type: InputType) -> "ExpertShareMoELayer":
        out = self
        if not out.n_in:
            out = dataclasses.replace(out, n_in=input_type.size)
        if not out.hidden:
            out = dataclasses.replace(out, hidden=4 * out.n_in)
        return out

    def has_params(self) -> bool:
        return True

    @property
    def _shared_names(self) -> Tuple[str, ...]:
        return ("Sg", "Su", "Sd") if self.n_shared_experts else ()

    def trainable_param_names(self) -> Tuple[str, ...]:
        return ("Wr", "br", "Eg", "Eu", "Ed") + self._shared_names

    def weight_param_names(self) -> Tuple[str, ...]:
        return ("Wr", "Eg", "Eu", "Ed") + self._shared_names

    def init_state(self, dtype: Any) -> State:
        return {"choice_counts": jnp.zeros((self.held + 2,), jnp.float32)}

    def init(self, key: jax.Array, dtype: Any) -> Params:
        e, d, f = self.held, self.n_in, self.hidden
        width = self.n_routed_experts + self.zero_expert_num
        wi = self.weight_init or WeightInit.XAVIER
        kr, kg, ku, kd, ks = jax.random.split(key, 5)

        def mats(k, rows, cols):
            return init_weights(k, (e, rows, cols), wi, fan_in=rows,
                                fan_out=cols, dtype=dtype)

        out = {"Wr": init_weights(kr, (d, width), wi, fan_in=d,
                                  fan_out=width, dtype=dtype),
               "br": jnp.zeros((width,), dtype),
               "Eg": mats(kg, d, f), "Eu": mats(ku, d, f),
               "Ed": mats(kd, f, d)}
        if self.n_shared_experts:
            fs = self.n_shared_experts * f
            k1, k2, k3 = jax.random.split(ks, 3)
            out |= {"Sg": init_weights(k1, (d, fs), wi, d, fs, None, dtype),
                    "Su": init_weights(k2, (d, fs), wi, d, fs, None, dtype),
                    "Sd": init_weights(k3, (fs, d), wi, fs, d, None, dtype)}
        return out

    # ---- the share ----------------------------------------------------------
    def _hidden_act(self, params: Params, eq: str, x: jax.Array) -> jax.Array:
        f32 = jnp.float32
        return jax.nn.silu(jnp.einsum(eq, x, params["Eg"],
                                      preferred_element_type=f32)) \
            * jnp.einsum(eq, x, params["Eu"], preferred_element_type=f32)

    def _held_weights(self, vals, local) -> jax.Array:
        """``[n, held]``: a token's weight for every held expert, nought
        for one it did not choose."""
        return jnp.sum(jax.nn.one_hot(local, self.held, dtype=vals.dtype)
                       * vals[..., None], axis=1)

    def _held_dense(self, params: Params, x2, vals, local) -> jax.Array:
        """Every held expert over every token under the mask of
        :meth:`_held_weights`."""
        w = self._held_weights(vals, local)
        h = self._hidden_act(params, "nd,edf->enf", x2) * w.T[:, :, None]
        return jnp.einsum("enf,efd->nd", h.astype(x2.dtype), params["Ed"],
                          preferred_element_type=jnp.float32)

    def _held_each(self, params: Params, x2, vals, local) -> jax.Array:
        """:meth:`_held_dense` one held expert at a time (a scan): the
        sorted form's way out at a prompt's tokens, where every expert over
        every token at once would be ``held`` hidden activations alive."""
        f32 = jnp.float32

        def one(acc, e):
            eg, eu, ed, we = e
            h = jax.nn.silu(jnp.dot(x2, eg, preferred_element_type=f32)) \
                * jnp.dot(x2, eu, preferred_element_type=f32) * we[:, None]
            return acc + jnp.dot(h.astype(x2.dtype), ed,
                                 preferred_element_type=f32), None

        acc, _ = jax.lax.scan(
            one, jnp.zeros((x2.shape[0], self.n_in), f32),
            (params["Eg"], params["Eu"], params["Ed"],
             self._held_weights(vals, local).T))
        return acc

    def _held_sorted(self, params: Params, x2, vals, plan,
                     rows: int) -> jax.Array:
        """The tokens of each held expert gathered into its ``rows`` slots
        (the plan granted every choice one)."""
        xin = gather_dispatch(x2, plan, self.held, rows)
        h = self._hidden_act(params, "emd,edf->emf", xin)
        out = jnp.einsum("emf,efd->emd", h.astype(x2.dtype), params["Ed"],
                         preferred_element_type=jnp.float32)
        return scatter_combine(out, vals, plan, renormalize=False)

    def parts(self, params: Params, x2: jax.Array,
              token_mask: Optional[jax.Array] = None):
        """Tokens ``x2 [n, n_in]`` (``token_mask [n]`` marks the real ones)
        -> ``(the held experts' part [n, n_in], the zero-compute experts'
        part [n, n_in], counts [n, held + 2])``, the parts float32. The
        share's result is their sum; over all the shares of a layer the
        held parts and ONE zero-compute part add up to the uncut layer.
        The ROUTER is float32 end to end: it takes ``x2`` as it comes (a
        float32 activation stays one), because a score that moves in its
        third digit flips a choice; the experts take ``x2`` in their
        weights' type."""
        f32 = jnp.float32
        with jax.named_scope("moe_router"):
            logits = _router_logits(x2, params["Wr"])
            scores = jax.nn.softmax(logits, axis=-1) \
                if self.scoring == "softmax" else jax.nn.sigmoid(logits)
            # (two spellings of one call: the unnormalised one is the
            # layer as PR 34 served it, operation for operation)
            if self.norm_topk_prob:
                vals, idx = biased_top_k_routing(
                    scores, params["br"].astype(f32), self.top_k)
                vals = self.routed_scaling_factor * vals / (
                    jnp.sum(vals, axis=-1, keepdims=True) + 1e-6)
            else:
                vals, idx = biased_top_k_routing(
                    scores, params["br"].astype(f32), self.top_k,
                    self.routed_scaling_factor)
            local, counts = held_expert_choices(
                idx, self.first_held_expert, self.held,
                self.n_routed_experts)
            if token_mask is not None:
                counts = counts * (token_mask > 0)[:, None].astype(
                    counts.dtype)
            zero_w = jnp.sum(jnp.where(idx >= self.n_routed_experts, vals,
                                       0.0), axis=-1)
        zero = zero_w[:, None] * x2.astype(f32)
        x2 = x2.astype(params["Eg"].dtype)
        with jax.named_scope("moe_experts"):
            if x2.shape[0] <= self.expert_rows:
                held = self._held_dense(params, x2, vals, local)
            else:
                rows = self.sorted_rows(x2.shape[0])
                plan = make_dispatch_plan(local, self.held, rows,
                                          token_mask=token_mask)
                held = jax.lax.cond(
                    plan.dropped_tokens == 0,
                    lambda: self._held_sorted(params, x2, vals, plan, rows),
                    lambda: self._held_each(params, x2, vals, local))
        return held, zero, counts

    def shared(self, params: Params, x2: jax.Array) -> jax.Array:
        """The shared expert over the tokens ``x2 [n, n_in]`` -> float32
        ``[n, n_in]`` (nought without one): what every chip computes for
        its own rows, outside every share."""
        if not self.n_shared_experts:
            return jnp.zeros(x2.shape, jnp.float32)
        f32 = jnp.float32
        x2 = x2.astype(params["Sg"].dtype)
        with jax.named_scope("moe_shared"):
            h = jax.nn.silu(jnp.dot(x2, params["Sg"],
                                    preferred_element_type=f32)) \
                * jnp.dot(x2, params["Su"], preferred_element_type=f32)
            return jnp.dot(h.astype(x2.dtype), params["Sd"],
                           preferred_element_type=f32)

    def share(self, params: Params, x2: jax.Array,
              token_mask: Optional[jax.Array] = None):
        """``(y [n, n_in] float32, counts [n, held + 2])``: the held and
        zero-compute parts, and the shared expert."""
        held, zero, counts = self.parts(params, x2, token_mask)
        if self.n_shared_experts:
            return held + zero + self.shared(params, x2), counts
        return held + zero, counts

    def feed(self, params: Params, x2: jax.Array,
             token_mask: Optional[jax.Array] = None):
        """:meth:`share` under the name a block of parts calls its
        feed-forward by (``decoder_block.py``)."""
        return self.share(params, x2, token_mask)

    def apply(self, params: Params, state: State, x: jax.Array,
              ctx: LayerContext) -> Tuple[jax.Array, State]:
        x = apply_input_dropout(self, x, ctx)
        recurrent = x.ndim == 3
        token_mask = None
        if recurrent:  # [b, f, t] -> tokens [b*t, f]
            b_, f_, t_ = x.shape
            x2 = jnp.transpose(x, (0, 2, 1)).reshape(b_ * t_, f_)
            if ctx.mask is not None:
                token_mask = jnp.reshape(ctx.mask, (b_ * t_,))
        else:
            x2 = x
        y, counts = self.share(params, x2, token_mask)
        y = y.astype(x.dtype)
        if recurrent:
            y = jnp.transpose(y.reshape(b_, t_, f_), (0, 2, 1))
        return y, {**state, "choice_counts": jnp.sum(
            counts, axis=0).astype(jnp.float32)}
