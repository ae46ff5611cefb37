"""reshard — restart-free elasticity via live mesh-to-mesh state resharding.

Tenplex (arXiv:2312.05181) models training state as parallelizable tensor
collections that re-split when the world changes; ElasWave (arXiv:2510.00606)
shows elastic-native resizing without a global restart.  This package brings
that to the JAX/pjit stack:

- :mod:`plan` — a pure planner from (source layout, target layout) to a
  per-tensor transfer plan of ``(src_rank, dst_rank, tensor, byte_range)``
  segments, with a validator proving the segments tile every target shard
  exactly once.  Zero processes needed; the same plans drive the
  checkpoint engine's restore-to-any-mesh.
- :mod:`mover` — segment execution: intra-host segments are copied out
  of the rank's staged shards (the shm arena's tensors ``read()`` into
  arrays, or views of live host shards), cross-host segments ride a
  replica-ring-style RPC with CRC-32-verified payloads.
- :mod:`coordinator` — orchestration: quiesce at a step boundary, execute
  the plan, rebuild the mesh and re-jit on the new world without process
  teardown; any plan/move/verify failure falls back loudly to the
  checkpoint-restart ladder.

Imports are lazy (mirrors ``checkpoint/__init__``): :mod:`plan` is pure
numpy and must stay importable without jax.
"""

from __future__ import annotations

_LAZY = {
    "MeshLayout": ("dlrover_tpu.reshard.plan", "MeshLayout"),
    "ReshardPlan": ("dlrover_tpu.reshard.plan", "ReshardPlan"),
    "Segment": ("dlrover_tpu.reshard.plan", "Segment"),
    "PlanError": ("dlrover_tpu.reshard.plan", "PlanError"),
    "build_plan": ("dlrover_tpu.reshard.plan", "build_plan"),
    "build_layout": ("dlrover_tpu.reshard.plan", "build_layout"),
    "layout_from_tensors_info": (
        "dlrover_tpu.reshard.plan", "layout_from_tensors_info"
    ),
    "ranks_needed": ("dlrover_tpu.reshard.plan", "ranks_needed"),
    "SegmentMover": ("dlrover_tpu.reshard.mover", "SegmentMover"),
    "LocalShardSource": ("dlrover_tpu.reshard.mover", "LocalShardSource"),
    "ReshardPeer": ("dlrover_tpu.reshard.mover", "ReshardPeer"),
    "ReshardMoveError": ("dlrover_tpu.reshard.mover", "ReshardMoveError"),
    "ReshardError": ("dlrover_tpu.reshard.coordinator", "ReshardError"),
    "ReshardOutcome": (
        "dlrover_tpu.reshard.coordinator", "ReshardOutcome"
    ),
    "reshard_state": ("dlrover_tpu.reshard.coordinator", "reshard_state"),
    "target_placeholders": (
        "dlrover_tpu.reshard.coordinator", "target_placeholders"
    ),
}


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    return getattr(importlib.import_module(mod_name), attr)


def __dir__():
    return sorted(_LAZY)
