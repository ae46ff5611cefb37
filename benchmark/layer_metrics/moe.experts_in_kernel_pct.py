"""Kernels: of the device seconds under the routed blocks' ``moe_experts``
scope (``harness/moe_read.py``), the share spent in calls of the grouped
matmul kernels (``gmm``, ``tgmm``; ``ops/grouped_matmul.py``), each call
joined to the scope the program's table gives it.  0 says the experts'
width fell to ``lax.ragged_dot`` (XLA's own kernels over the groups), as the
program's ``moe_expert_backend`` fact says "reference"; the rest of a
positive share is the activation between the matmuls and the fall-back
buffer's branch.  A program without the scope reads nothing."""
from benchmark.harness import moe_read, obs_read

LAYER = "kernels"
SOURCE = "device_trace"

GROUPED_KERNELS = ("gmm", "tgmm")


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    if secs is None or not secs["moe_experts"]:
        return None
    scopes = obs_read.program_tables(
        obs_read.records(spans), trace)["scopes"]
    in_kernels = sum(
        s for name, label, s in obs_read.placed_ops(trace)
        if label in GROUPED_KERNELS and name in scopes
        and scopes[name][1] == "moe_experts")
    return 100.0 * in_kernels / secs["moe_experts"]
