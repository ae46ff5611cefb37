"""Model step: device self time of the operations under the ``s6`` scope of
every Mamba-1 layer (its input norm, the mixer's projections, convolution,
step, selective scan, gate and the residual add; forward, backward and
recomputed) over device busy time (``harness/s6_read.py``).  Two layers in
six are of this kind in the Phi-4-mini-flash cell."""
from benchmark.harness import s6_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = s6_read.seconds(spans, trace)
    if secs is None or not secs["s6"]:
        return None
    return 100.0 * secs["s6"] / secs["busy_s"]
