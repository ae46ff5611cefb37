"""Model step: what of the routed block's device time is NOT expert matmul
— ``moe_router`` + ``moe_permute`` + ``moe_combine`` over all four ``moe_*``
scopes (``harness/moe_read.py``).  The sort, the two row gathers and their
transposes, the router and the weighted sum are memory-bound bookkeeping
around the FLOPs; lower is better."""
from benchmark.harness import moe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    if secs is None:
        return None
    whole = sum(secs[s] for s in moe_read.SCOPES)
    return 100.0 * (whole - secs["moe_experts"]) / whole if whole else None
