"""Paged decode-carry management: block allocator, paged state layout,
and the batch-step helpers (freeze / write-redirect) shared by the plain
and speculative decode paths.

Which leaves of a layer's decode state are planes written in place is the
layer's to say (``Layer.decode_planes``); the helpers here take that
declaration as ``planes``, ``{layer name: names of its planes}``
(``GenerationSession.planes``), and know no leaf by name but ``pos``,
``block_table`` and ``write_mask``.

Layout (see ops/paged_attention.py): each pageable attention layer's planes
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
    static carry with every plane of a layer that pages its planes
    (``Layer.pages_decode_planes``: K/V caches indexed by absolute position)
    replaced by a shared block pool and a zero (= all-trash) block table
    added. A layer whose carry is not of that kind (recurrent ``h``/``c``,
    input caches, a mixer's bounded state of windows and summaries) cannot
    be paged: its state has no block structure to page."""
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
        pools = keys & set(session.planes.get(name, ()))
        if not pools:
            # no planes (e.g. a position-counter-only carry): nothing
            # to page — keep the per-row state as-is
            if keys <= {"pos"}:
                out[name] = st
                continue
            raise ValueError(
                f"layer {name!r} carries state {sorted(keys)} which is not "
                "pageable — paged decode needs position-indexed K/V caches "
                "(recurrent h/c carries have no block structure)")
        if name not in session.paged_layers:
            raise ValueError(
                f"layer {name!r} keeps a bounded decode state of its own "
                f"({sorted(pools)}) which is not a K/V cache indexed by "
                "position and is not paged — serve this model with the "
                "static layout (block_size=None)")
        if not keys <= pools | {"pos"}:
            raise ValueError(
                f"layer {name!r} mixes cache planes with unpageable state "
                f"{sorted(keys - pools - {'pos'})}")
        new_st = {}
        for key in pools:
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
    for name, st in session.decode_state(1).items():
        for key in set(st.keys()) & set(session.planes.get(name, ())):
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
    gives each buffer once): the layers whose state is more than a
    position counter. ``table=None`` (a static carry) passes through."""
    if table is None:
        return carry
    return {name: ({**st, "block_table": table} if set(st) - {"pos"} else st)
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


def mask_inactive_writes(carry, active, planes=None):
    """:func:`redirect_inactive_writes`, and the static layout's
    counterpart of it: every unpaged layer that declares planes
    (``planes``: ``{layer name: names}``, ``GenerationSession.planes``)
    gets a ``write_mask`` leaf (``active``), under which the layer's own
    write drops the update of a row that is masked off
    (``ops.masked_cache_write``). An inactive row then leaves its row of
    every plane as it was by what it writes, and :func:`freeze_rows` need
    not select over the planes. Layers that declare none (recurrent
    ``h``/``c``, ``cache_x``) pass through and keep the whole-leaf
    select."""
    planes = planes or {}
    out = {}
    for name, st in redirect_inactive_writes(carry, active).items():
        if planes.get(name) and "block_table" not in st:
            st = {**st, "write_mask": active}
        out[name] = st
    return out


def freeze_rows(new, old, active, planes=None):
    """Keep carry rows where ``active`` is False unchanged after a fused
    batch step. ``old`` is the state the step's forward ran on. Paged
    layers: pool planes take the step's result (the inactive rows' writes
    went to trash — nothing of theirs changed), ``block_table`` is taken
    from ``old``, and per-row leaves (``pos``) are where'd by the mask.
    Static layers whose ``old`` state carries a ``write_mask``
    (:func:`mask_inactive_writes`): the planes the layer declares
    (``planes``) take the step's result too (a masked row wrote nothing)
    and only the per-row leaves are where'd. Every other layer keeps the
    per-leaf where (shapes are per-row there, so a row-select is well
    defined on every leaf) — among them the K/V layers of a multi-token
    window that is rewound afterwards, which no caller masks."""
    planes = planes or {}

    def sel(n, o):
        a = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    out = {}
    for name, n_st in new.items():
        o_st = old[name]
        if "block_table" in o_st or "write_mask" in o_st:
            mine = planes.get(name, ())
            st = {}
            for k, v in n_st.items():
                if k in mine:
                    st[k] = v
                elif k == "block_table":
                    st[k] = o_st[k]
                else:
                    st[k] = sel(v, o_st[k])
            out[name] = st
        else:
            out[name] = jax.tree_util.tree_map(sel, n_st, o_st)
    return out
