"""Model step: device self time of the operations under the ``gmu`` scope of
every Gated Memory Unit (its input norm, ``in_proj``, the gate's multiply
with the memory another layer's scan put out, ``out_proj`` and the residual
add; forward, backward and recomputed) over device busy time
(``harness/s6_read.py``): two matmuls of 2,560 x 5,120 and one elementwise
pass over ``[tokens, 5,120]`` where a Mamba-1 layer runs a scan."""
from benchmark.harness import s6_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = s6_read.seconds(spans, trace)
    if secs is None or not secs["gmu"]:
        return None
    return 100.0 * secs["gmu"] / secs["busy_s"]
