"""Model step: device time of the routed block (the four ``moe_*`` scopes
of ``models/llama.py``: router with its norm and loss terms, sort and
gathers, the grouped expert matmuls, the weighted combine with the residual
add; forward, backward and recomputed; its Mosaic kernels — the grouped
matmuls, ``gather_sum``, the router's norm — each call under the scope it
runs in) over device busy time.  The join, and what becomes of a kernel's
call the program's table does not name: ``harness/moe_read.py``.  Also
prints the block's kernels' seconds (``MOE_KERNELS``)."""
from benchmark.harness import moe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    if secs is None:
        return None
    moe_read.print_kernels(secs, trace)
    return 100.0 * secs["whole"] / secs["busy_s"]
