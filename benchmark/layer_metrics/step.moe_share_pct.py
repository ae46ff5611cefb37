"""Model step: device time of the routed block (the four ``moe_*`` scopes
of ``models/llama.py``: router with its norm and loss terms, sort and
gathers, the grouped expert matmuls, the weighted combine with the residual
add; forward and backward) over device busy time.  The join and where the
grouped-matmul kernels' seconds come from: ``harness/moe_read.py``."""
from benchmark.harness import moe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * sum(secs[s] for s in moe_read.SCOPES) / secs["busy_s"]
