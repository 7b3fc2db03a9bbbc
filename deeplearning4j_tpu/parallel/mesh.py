"""Device-mesh construction and multi-host bring-up.

Reference: the Aeron ``MeshOrganizer`` built a bounded-degree tree of UDP
peers and Spark supplied the control plane (SURVEY.md §2.4). On TPU both
jobs are already solved: the mesh is ``jax.sharding.Mesh`` over the ICI
torus, and the control plane is the JAX coordination service
(``jax.distributed.initialize``). This module is the thin, explicit entry
point for both, so user code never touches raw device lists.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named mesh axes and sizes, e.g. ``MeshSpec(data=4, model=2)``.

    Axis vocabulary (used by DistributedTrainer sharding rules):
      * ``data``  — batch (data parallel; DP)
      * ``model`` — hidden/feature (tensor parallel; TP)
      * ``seq``   — sequence/context (ring attention; SP/CP)
      * ``pipe``  — layer sequence (pipeline parallel; PP —
        PipelineParallelTrainer stages, e.g. ``MeshSpec(pipe=4, data=2)``)
    A size of -1 means "all remaining devices".
    """

    axes: Tuple[Tuple[str, int], ...]

    def __init__(self, axes: Optional[Dict[str, int]] = None, **kw: int) -> None:
        merged = dict(axes or {})
        merged.update(kw)
        if not merged:
            merged = {"data": -1}
        object.__setattr__(self, "axes", tuple(merged.items()))

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = int(np.prod([v for v in sizes.values() if v != -1])) if sizes else 1
        if wild:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} wants {fixed} devices, have {n_devices}")
        return sizes


def make_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    **axes: int,
) -> Mesh:
    """Build a ``Mesh``. ``make_mesh(data=4, model=2)`` or ``make_mesh()``
    for all-devices data parallel.

    On real TPU slices ``jax.make_mesh`` picks an ICI-friendly device order
    (collectives ride neighbor links, not hops); we delegate to it whenever
    we're using the full default device set.
    """
    spec = spec or MeshSpec(axes or None)
    devs = list(devices) if devices is not None else jax.devices()
    sizes = spec.resolve(len(devs))
    names = tuple(sizes)
    shape = tuple(sizes[n] for n in names)
    if devices is None:
        # Auto axis types: shardings propagate GSPMD-style and XLA
        # derives the collectives (jax.make_mesh defaults to Explicit,
        # which demands out_sharding annotations everywhere). A failure
        # here propagates: reshaping jax.devices() in list order instead
        # is how a mesh ends up with a bad ICI order silently.
        auto = (jax.sharding.AxisType.Auto,) * len(names)
        return jax.make_mesh(shape, names, axis_types=auto)
    # an explicit device list is the caller's order, taken as given
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names=names)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up (reference: Aeron media driver + MeshOrganizer
    handshake, SURVEY.md §3.4 — here it is one call into the JAX
    coordination service; on Cloud TPU the arguments are auto-detected).

    A no-op when already initialized. A failed initialize raises: a job
    that meant to span hosts must not carry on as one process.
    """
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def local_batch_slice(global_batch: int, mesh: Mesh, axis: str = "data") -> slice:
    """The slice of a global batch this process owns (multi-host input
    pipelines feed per-host shards; reference: Spark partitioned the RDD).

    Requires the shard count to divide evenly across processes and the batch
    across shards — a real constraint of SPMD input feeding, surfaced as an
    error instead of silently overlapping/dropping rows.
    """
    n = mesh.shape[axis]
    procs = jax.process_count()
    if global_batch % n:
        raise ValueError(f"global batch {global_batch} not divisible by {n} data shards")
    if n % procs:
        raise ValueError(f"{n} data shards not divisible across {procs} processes")
    per = global_batch // n
    shards_per_proc = n // procs
    start = jax.process_index() * shards_per_proc * per
    return slice(start, start + shards_per_proc * per)


def zero1_partition_spec(
    shape: Tuple[int, ...],
    n_shards: int,
    axis: str = "data",
    base: Optional[PartitionSpec] = None,
) -> PartitionSpec:
    """Updater-state sharding rule for ZeRO-1 cross-replica weight-update
    sharding ("Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training", PAPERS.md): shard dim 0 of a param-shaped
    updater leaf over the data axis when the axis divides it evenly,
    composing with an existing (tensor-parallel) ``base`` spec on the
    remaining dims. Falls back to ``base`` unchanged when

    * the leaf is scalar / zero-sized / dim 0 is not divisible, or
    * ``base`` already shards dim 0 (row-parallel TP) — never double-shard
      one dim over two axes here; XLA would need a 2D reshard for no
      memory win on the dominant leaves.
    """
    base = base if base is not None else PartitionSpec()
    if not shape or not shape[0] or shape[0] % max(n_shards, 1) or n_shards <= 1:
        return base
    existing = tuple(base)
    if existing and existing[0] is not None:
        return base
    if existing:
        return PartitionSpec(axis, *existing[1:])
    return PartitionSpec(axis)


_ENV_FLAG = "DL4J_TPU_FORCE_HOST_DEVICES"


def force_host_device_count(n: int) -> None:
    """Testing aid: simulate ``n`` devices on CPU (must run before first JAX
    use). Mirrors the reference's 'multi-node ≈ multi-thread + loopback'
    test strategy (SURVEY.md §4)."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    os.environ[_ENV_FLAG] = str(n)


def shmap(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off (the
    strategies mix replicated and per-shard values by construction)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
