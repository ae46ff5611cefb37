"""Model step: device self time of the instructions under the ``gdn`` scope
of every delta-rule layer (its input norm's XLA part, the mixer's three
projections, convolution, the gated delta rule, gated norm and the residual
add; forward, backward and recomputed) over device busy time
(``harness/gdn_read.py``).  Three layers in four are of this kind in the
Qwen3-Next hybrid."""
from benchmark.harness import gdn_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = gdn_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["gdn"] / secs["busy_s"]
