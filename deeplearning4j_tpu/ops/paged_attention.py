"""Paged KV-cache primitives (block pools + per-row block tables).

The static decode cache (``[b, h, max_len, d]`` per layer) charges every
resident sequence for ``max_len`` positions it may never use. The paged
layout (the vLLM idea) splits each layer's cache into a shared pool of
fixed-size blocks ``[num_blocks, h, block_size, d]`` plus one int32 block
table per row ``[b, max_len // block_size]``: a sequence only holds the
blocks that cover its *used* positions, so the same HBM pool multiplies
the concurrent sequences and a cache handoff becomes a block-list
transfer (serving/disagg.py).

Block id 0 is reserved as the TRASH block: unallocated table entries are
0, and engine-side write redirection points inactive rows there, so a
fused batch step can keep its static shape — stray writes land in trash
and are never read, because reads are masked to ``[0, pos]`` by
:func:`~deeplearning4j_tpu.ops.flash_attention.decode_attention` and the
positions a live row reads are always backed by its own blocks.

``paged_decode_attention`` is XLA-level: it gathers the row's blocks
into the contiguous ``[b, h, L, d]`` view and delegates to the existing
``decode_attention`` dispatch (flash kernel / int8 dequant reference
path). A Pallas kernel that walks the block table in-kernel (no
transient gather) is the obvious next seam; the contract here is the
reference semantics it would have to match.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import count_kv, decode_attention


def pack_row_blocks(x: jax.Array, block_size: int) -> jax.Array:
    """Reshape one row's contiguous cache plane ``[h, L, ...]`` into its
    per-block form ``[L // block_size, h, block_size, ...]`` — the layout
    a scatter into the shared pool (one slice per block id) expects."""
    h, L = x.shape[0], x.shape[1]
    if L % block_size:
        raise ValueError(f"cache length {L} not divisible by "
                         f"block_size {block_size}")
    blocked = x.reshape((h, L // block_size, block_size) + x.shape[2:])
    return jnp.moveaxis(blocked, 1, 0)


def paged_gather(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather each row's blocks into the contiguous cache view: pool
    ``[num_blocks, h, block_size, ...]`` + table ``[b, nbr]`` ->
    ``[b, h, nbr * block_size, ...]`` (K/V pools are 4-D, int8 scale
    pools 3-D — both layouts share this)."""
    g = pool[block_table]                       # [b, nbr, h, bs, ...]
    g = jnp.moveaxis(g, 2, 1)                   # [b, h, nbr, bs, ...]
    b, h, nbr, bs = g.shape[:4]
    return g.reshape((b, h, nbr * bs) + g.shape[4:])


def paged_cache_write(pool: jax.Array, new: jax.Array,
                      block_table: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` (``[b, h, t, d]`` K/V or ``[b, h, t]`` scales) into
    the shared pool at each row's positions ``pos + [0, t)``, routed
    through its block table — the paged counterpart of the static cache's
    ``dynamic_update_slice`` write. Positions past the table's capacity
    clamp to the last slot (the engine retires rows before that happens;
    the clamp only keeps indices in range for frozen/done rows)."""
    count_kv("separate")
    b, t = new.shape[0], new.shape[2]
    bs = pool.shape[2]
    cap = block_table.shape[1] * bs
    p = pos.astype(jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    p = jnp.minimum(p, cap - 1)                 # [b, t]
    blk = jnp.take_along_axis(block_table, p // bs, axis=1)  # [b, t]
    off = p % bs
    # advanced-index axes move to the front: values must be [b*t, h, ...]
    vals = jnp.moveaxis(new, 2, 1).reshape((b * t, pool.shape[1])
                                           + pool.shape[3:])
    return pool.at[blk.reshape(-1), :, off.reshape(-1)].set(
        vals.astype(pool.dtype))


def paged_decode_attention(
    q: jax.Array,                 # [b, h, tq, d]
    pool_k: jax.Array,            # [num_blocks, h, block_size, d]
    pool_v: jax.Array,            # [num_blocks, h, block_size, dv]
    block_table: jax.Array,       # [b, nbr] int32
    start_pos: jax.Array,         # [b] int32
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,   # [num_blocks, h, block_size] f32
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Decode attention against a paged cache: gather the row's blocks,
    then run the standard masked decode attention (which also handles the
    int8 dequant when scale pools ride along). Entries past ``pos`` —
    including anything a trash-redirected write left in block 0 — are
    masked out exactly as the static cache's pad garbage is."""
    k = paged_gather(pool_k, block_table)
    v = paged_gather(pool_v, block_table)
    return decode_attention(
        q, k, v, start_pos, scale=scale,
        k_scale=None if k_scale is None else paged_gather(k_scale,
                                                          block_table),
        v_scale=None if v_scale is None else paged_gather(v_scale,
                                                          block_table))
