"""Model step: device self time of the instructions under the ``ssm`` scope
of every state-space layer (its input norm's XLA part, the mixer's two
projections, convolution, scan, gated norm and the residual add; forward,
backward and recomputed) over device busy time (``harness/ssm_read.py``).
Nine layers in ten are of this kind in the Granite hybrid."""
from benchmark.harness import ssm_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = ssm_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["ssm"] / secs["busy_s"]
