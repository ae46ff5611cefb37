"""What the routed block's per-layer readers share: device seconds under
its four scopes (``moe_router``, ``moe_permute``, ``moe_experts``,
``moe_combine``; ``dlrover_tpu/models/llama.py::_moe_swiglu``), of every
routed block of the step: those of the layer stack, where the four are the
outermost scopes, and the prediction block's (``mtp``), which nests them.

The trace's operations — an XLA instruction, and each CALL of a Mosaic
kernel (``obs_read.placed_ops``) — are joined to the two tables of the
``accelerate.program`` event: ``scopes`` (phase and outermost scope) and,
under ``mtp``, ``subscopes`` (innermost).  So the grouped matmuls (``gmm``,
``tgmm``) count under ``moe_experts``, ``gather_sum`` under ``moe_combine``
going forward or recomputed and under ``moe_permute`` going backward, the
router's norm under ``moe_router``: what the table says of each call.
Under ``mtp`` the innermost scope of a kernel's call is the kernel's own
name, so there the block's kernels (:data:`BLOCK_KERNELS`) are placed by
that name and, ``gather_sum``, by the call's phase.  A call of one of them
that the table does not name at all is ``unplaced``: in the block's total,
in no scope, and said in the ``MOE_KERNELS`` line.

A program without the scopes (a dense step, or the parent of the PR that
brought the routed block) yields None, and every reader built on this
returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

SCOPES = ("moe_router", "moe_permute", "moe_experts", "moe_combine")
#: the scope of the prediction block, whose routed branch nests the four
NESTING_SCOPE = "mtp"
#: the Mosaic kernels only a routed block calls (``ops/grouped_matmul.py``,
#: ``ops/gather_sum.py``): theirs by name where the table cannot say more
BLOCK_KERNELS = ("gmm", "tgmm", "gather_sum")


def _nested_scope(label: str, phase: str, innermost: str) -> str:
    """The ``moe_*`` scope of an operation under :data:`NESTING_SCOPE`."""
    if label == "gather_sum":
        return "moe_permute" if phase == "backward" else "moe_combine"
    return "moe_experts" if label in BLOCK_KERNELS else innermost


def scope_seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{scope: device seconds}`` of the routed blocks in the traced
    window, with ``unplaced`` (their kernels' calls under no scope),
    ``whole`` (the four scopes and ``unplaced``) and ``busy_s`` beside
    them."""
    program = obs_read.program_tables(obs_read.records(spans), trace)
    if program is None:
        return None
    scopes, inner = program["scopes"], program.get("subscopes") or {}
    secs = dict.fromkeys(SCOPES, 0.0)
    unplaced, found = 0.0, False
    for name, label, s in obs_read.placed_ops(trace):
        if name not in scopes:
            unplaced += s if label in BLOCK_KERNELS else 0.0
            continue
        phase, scope = scopes[name]
        if scope == NESTING_SCOPE:
            scope = _nested_scope(label, phase, inner.get(name, ""))
        if scope in secs:
            secs[scope] += s
            found = True
    if not found:
        return None
    return dict(secs, unplaced=unplaced, busy_s=trace["busy_s"],
                whole=sum(secs.values()) + unplaced)


def print_kernels(secs: dict, trace: dict) -> None:
    """``MOE_KERNELS``: the block's kernels' seconds by name, and what of
    them no scope of the table holds."""
    kernel_s = trace.get("kernel_s") or {}
    print("MOE_KERNELS " + " ".join(
        f"{k}={kernel_s.get(k, 0.0):.4f}s" for k in BLOCK_KERNELS)
        + f" unplaced={secs['unplaced']:.4f}s"
        + (" (in the block's total, in no scope)" if secs["unplaced"]
           else ""), flush=True)
