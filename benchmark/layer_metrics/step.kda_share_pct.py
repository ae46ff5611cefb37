"""Model step: device self time of the operations under the ``kda`` scope of
every KDA layer (its input norm, the mixer's projections and low-rank gates,
the three convolutions, the per-channel delta rule, the gated norm and the
residual add; forward, backward and recomputed; XLA instructions and Mosaic
kernels alike, each placed by its calling instruction) over device busy time
(``harness/kda_read.py``).  Four layers in five are of this kind in the Kimi
Linear cut."""
from benchmark.harness import kda_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = kda_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["kda"] / secs["busy_s"]
