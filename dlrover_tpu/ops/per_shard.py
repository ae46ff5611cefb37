"""Run a Pallas kernel once per shard of the mesh in scope.

GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
automatically partitioned"), so inside a jitted step whose operands are
sharded every ``pallas_call`` has to sit in a ``shard_map``.  The mesh is
the one the caller entered with ``jax.set_mesh`` (``accelerate`` does, for
tracing and for every step call); with no mesh in scope, or one whose axes
are all size 1 or already manual, the kernel is called bare — the same
kernel either way, never the jnp reference.

Layout contract (the canonical axis names of ``parallel.mesh``): the batch
dim rides ``('dp', 'fsdp')``, attention heads ride ``'tp'``, everything
else is replicated into the kernel.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("dp", "fsdp")
HEAD_AXIS = "tp"


def free_axes() -> Tuple[Tuple[str, ...], dict]:
    """``(axes, sizes)``: the axes of the mesh in scope that a new
    shard_map may still claim (not manual in an enclosing one), and every
    axis size.  ``axes`` is empty when no such axis is larger than 1 —
    "call the kernel bare"."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return (), {}
    manual = set(mesh.manual_axes)
    free = tuple(a for a in mesh.axis_names if a not in manual)
    if all(mesh.shape[a] == 1 for a in free):
        return (), {}
    return free, dict(mesh.shape)


def shard_axes(batch: int, heads: Tuple[int, ...] = ()):
    """``(free, batch_axes, head_axis)`` for operands with leading dim
    ``batch`` and head counts ``heads``.  Raises when the mesh shards a
    dim the kernel cannot split evenly: a silent pad or gather would
    hide a layout bug."""
    free, shape = free_axes()
    batch_axes = tuple(a for a in BATCH_AXES if a in free and shape[a] > 1)
    n_batch = math.prod(shape[a] for a in batch_axes)
    if batch % n_batch:
        raise ValueError(
            f"per-shard kernel: batch dim {batch} is not divisible by the "
            f"{n_batch} batch shards of mesh axes {batch_axes}"
        )
    head_axis: Optional[str] = None
    if heads and HEAD_AXIS in free and shape[HEAD_AXIS] > 1:
        head_axis = HEAD_AXIS
        for h in heads:
            if h % shape[HEAD_AXIS]:
                raise ValueError(
                    f"per-shard kernel: {h} heads cannot be split over "
                    f"tp={shape[HEAD_AXIS]} (GQA needs n_kv_head % tp == 0)"
                )
    return free, (batch_axes or None), head_axis


def per_shard(fn: Callable, free, in_specs, out_specs) -> Callable:
    """``fn`` under a shard_map over the ``free`` axes of the mesh in
    scope (``fn`` itself when ``free`` is empty)."""
    if not free:
        return fn
    return jax.shard_map(
        fn, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(free), check_vma=False,
    )


__all__ = ["P", "free_axes", "per_shard", "shard_axes"]
