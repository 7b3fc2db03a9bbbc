"""Paged decode-carry management: block allocator, paged state layout,
and the batch-step helpers (freeze / write-redirect) shared by the plain
and speculative decode paths.

Layout (see ops/paged_attention.py): each attention layer's cache keys
(``cache_k``/``cache_v`` and, for int8, their scale planes) become
shared pools ``[num_blocks, h, block_size, ...]``; the per-layer state
gains a ``block_table`` leaf ``[b, max_len // block_size]`` int32. Block
ids are GLOBAL across layers — one logical block id indexes every
layer's pool at the same slot, so the host-side allocator and the
per-row block list stay layer-agnostic (and a cache handoff ships one
block list, not one per layer). Block id 0 is the reserved trash block:
unallocated table entries point at it, and
:func:`redirect_inactive_writes` routes inactive rows' writes there so
fused batch steps never corrupt a neighbour's blocks.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

_POOL_KEYS = frozenset({"cache_k", "cache_v",
                        "cache_k_scale", "cache_v_scale"})
_PAGEABLE_KEYS = _POOL_KEYS | {"pos"}


class OutOfBlocksError(RuntimeError):
    """The shared KV block pool cannot satisfy an allocation. The engine
    requeues the admit (blocks free as sequences retire) or preempts the
    row when nothing can ever free."""


class BlockAllocator:
    """Host-side free-list allocator over ``num_blocks`` block ids.
    Block 0 is the trash block and is never handed out; allocation is
    all-or-nothing (a partial grant would leave a row half-backed)."""

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is trash)")
        self.num_blocks = int(num_blocks)
        # LIFO free list: low ids hand out first (stable tests)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))

    @property
    def total_blocks(self) -> int:
        """Usable blocks (the trash block is not allocatable)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n <= 0:
            return []
        if n > len(self._free):
            raise OutOfBlocksError(
                f"need {n} KV blocks, {len(self._free)} free "
                f"(pool of {self.total_blocks})")
        ids = [self._free.pop() for _ in range(n)]
        return ids

    def free(self, ids: Sequence[int]) -> None:
        for i in ids:
            i = int(i)
            if i <= 0 or i >= self.num_blocks:
                raise ValueError(f"freeing invalid block id {i}")
            self._free.append(i)


def blocks_needed(tokens: int, block_size: int) -> int:
    return math.ceil(max(0, int(tokens)) / int(block_size))


def paged_decode_state(session, batch: int, *, block_size: int,
                       num_blocks: int) -> Dict[str, dict]:
    """Paged decode carry for ``batch`` rows: the session's per-layer
    static carry with every cache plane replaced by a shared block pool
    and a zero (= all-trash) block table added. Layers whose carry is not
    position-indexed (recurrent ``h``/``c``, input caches) cannot be
    paged — their state has no block structure to page."""
    bs = int(block_size)
    if bs < 1:
        raise ValueError("block_size must be >= 1")
    if session.max_len % bs:
        raise ValueError(
            f"max_len {session.max_len} not divisible by block_size {bs}")
    base = session.decode_state(batch)
    out: Dict[str, dict] = {}
    for name, st in base.items():
        keys = set(st.keys())
        if "cache_k" not in keys:
            # no K/V planes (e.g. a position-counter-only carry): nothing
            # to page — keep the per-row state as-is
            if keys <= {"pos"}:
                out[name] = st
                continue
            raise ValueError(
                f"layer {name!r} carries state {sorted(keys)} which is not "
                "pageable — paged decode needs position-indexed K/V caches "
                "(recurrent h/c carries have no block structure)")
        if not keys <= _PAGEABLE_KEYS:
            raise ValueError(
                f"layer {name!r} mixes cache planes with unpageable state "
                f"{sorted(keys - _PAGEABLE_KEYS)}")
        new_st = {}
        for key in keys & _POOL_KEYS:
            c = st[key]  # [b, h, L, d] or [b, h, L]
            new_st[key] = jnp.zeros(
                (int(num_blocks), c.shape[1], bs) + c.shape[3:], c.dtype)
        new_st["pos"] = st["pos"]
        new_st["block_table"] = jnp.zeros(
            (batch, session.max_len // bs), jnp.int32)
        out[name] = new_st
    return out


def block_bytes(session, block_size: int) -> int:
    """Bytes ONE block occupies across every layer's pools — the unit
    the live ``kv_cache_bytes`` gauge and capacity planning multiply by
    allocated block count."""
    bs = int(block_size)
    total = 0
    for st in session.decode_state(1).values():
        for key in set(st.keys()) & _POOL_KEYS:
            c = st[key]
            per_pos = int(c.size // c.shape[2]) * c.dtype.itemsize
            total += per_pos * bs
    return total


def is_paged(carry) -> bool:
    return any(isinstance(st, dict) and "block_table" in st
               for st in carry.values())


def attach_block_table(carry, table):
    """Put the ONE shared ``[b, max_len // block_size]`` table under every
    pool-holding layer of a paged carry that is kept without it (the
    engine holds the table beside the carry, so that donating the carry
    gives each buffer once). ``table=None`` (a static carry) passes
    through."""
    if table is None:
        return carry
    return {name: ({**st, "block_table": table} if "cache_k" in st else st)
            for name, st in carry.items()}


def detach_block_table(carry):
    """The carry without its ``block_table`` leaves (the inverse of
    :func:`attach_block_table`; a static carry passes through)."""
    return {name: {k: v for k, v in st.items() if k != "block_table"}
            for name, st in carry.items()}


def redirect_inactive_writes(carry, active):
    """Point inactive rows' block tables at the trash block before a
    fused batch forward: the static-shape step writes EVERY row's K/V,
    and without redirection an inactive-but-allocated row's write would
    land inside its own live blocks (spec/plain row splits advance the
    two groups at different rates). Unpaged layers pass through — their
    per-row rows are restored by :func:`freeze_rows`."""
    out = {}
    for name, st in carry.items():
        if "block_table" in st:
            st = dict(st)
            st["block_table"] = jnp.where(
                active[:, None], st["block_table"], 0)
        out[name] = st
    return out


def mask_inactive_writes(carry, active):
    """:func:`redirect_inactive_writes`, and the static layout's
    counterpart of it: every unpaged K/V layer gets a ``write_mask``
    leaf (``active``), under which the layer's cache write drops the
    update of a row that is masked off (``ops.masked_cache_write``, through
    ``_cached_attention`` in nn/layers/attention.py). An inactive row then leaves its row of
    every cache plane as it was by what it writes, and
    :func:`freeze_rows` need not select over the planes. Layers without
    K/V planes (recurrent ``h``/``c``, ``cache_x``) pass through and keep
    the whole-leaf select."""
    out = {}
    for name, st in redirect_inactive_writes(carry, active).items():
        if "cache_k" in st and "block_table" not in st:
            st = {**st, "write_mask": active}
        out[name] = st
    return out


def freeze_rows(new, old, active):
    """Keep carry rows where ``active`` is False unchanged after a fused
    batch step. ``old`` is the state the step's forward ran on. Paged
    layers: pool planes take the step's result (the inactive rows' writes
    went to trash — nothing of theirs changed), ``block_table`` is taken
    from ``old``, and per-row leaves (``pos``) are where'd by the mask.
    Static K/V layers whose ``old`` state carries a ``write_mask``
    (:func:`mask_inactive_writes`): the cache planes take the step's
    result too (a masked row wrote nothing) and only the
    per-row leaves are where'd. Every other layer keeps the per-leaf
    where (shapes are per-row there, so a row-select is well defined on
    every leaf) — among them the K/V layers of a multi-token window
    that is rewound afterwards, which no caller masks."""
    def sel(n, o):
        a = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    out = {}
    for name, n_st in new.items():
        o_st = old[name]
        if "block_table" in o_st or "write_mask" in o_st:
            st = {}
            for k, v in n_st.items():
                if k in _POOL_KEYS:
                    st[k] = v
                elif k == "block_table":
                    st[k] = o_st[k]
                else:
                    st[k] = sel(v, o_st[k])
            out[name] = st
        else:
            out[name] = jax.tree_util.tree_map(sel, n_st, o_st)
    return out
