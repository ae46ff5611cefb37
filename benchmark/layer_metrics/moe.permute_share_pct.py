"""Model step: what of the routed block's device time is NOT expert matmul
— ``moe_router`` + ``moe_permute`` + ``moe_combine`` over the block's whole
(all four ``moe_*`` scopes, and whatever of its kernels no scope holds;
``harness/moe_read.py``).  The sort, the row gathers (``gather_sum``
forward under ``moe_combine``, backward under ``moe_permute``), the router
and the weighted sum are memory-bound bookkeeping around the FLOPs; lower
is better."""
from benchmark.harness import moe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    if secs is None:
        return None
    bookkeeping = sum(secs[s] for s in moe_read.SCOPES if s != "moe_experts")
    return 100.0 * bookkeeping / secs["whole"] if secs["whole"] else None
