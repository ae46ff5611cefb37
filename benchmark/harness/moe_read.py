"""What the routed block's per-layer readers share: device seconds under
its four scopes (``moe_router``, ``moe_permute``, ``moe_experts``,
``moe_combine``; ``dlrover_tpu/models/llama.py::_moe_swiglu``).

``obs_read.scope_shares`` joins the trace's instruction names to the scope
table of the compiled step.  The grouped matmuls are Mosaic kernels that
are none of the six ``trace_reduce.PALLAS_KERNELS`` names: the reduction
files their device time under ``kernel_s["pallas_other"]`` and the join
cannot name their scope.  A routed step has no other unnamed kernel, so
those seconds are the expert matmuls' and go to ``moe_experts`` here.

A program without the scopes (a dense step, or the parent of the PR that
brought the routed block) yields None, and every reader built on this
returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

SCOPES = ("moe_router", "moe_permute", "moe_experts", "moe_combine")
#: ``trace_reduce.kernel_of``'s name for a Mosaic kernel it has no name for
UNNAMED_KERNELS = "pallas_other"


def scope_seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{scope: device seconds}`` of the routed block in the traced
    window, with ``busy_s`` beside them."""
    shares = obs_read.scope_shares(obs_read.records(spans), trace)
    if shares is None:
        return None
    busy = trace["busy_s"]
    secs = {s: 0.0 for s in SCOPES}
    found = False
    for (_, scope), pct in shares["by"].items():
        if scope in secs:
            secs[scope] += pct / 100.0 * busy
            found = True
    if not found:
        return None
    secs["moe_experts"] += trace.get("kernel_s", {}).get(UNNAMED_KERNELS, 0.0)
    return dict(secs, busy_s=busy)
