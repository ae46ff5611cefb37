"""Model step: device time of attention as a whole over device busy time —
the XLA instructions under the ``attention`` scope of every block
application (norm, projections, rotary, transposes, output projection,
residual add; the prediction block's too) plus the three flash kernels,
forward, backward and recomputed (``harness/mla_read.py``).  With latent
attention at a head size of 256 this is where most of the matmul work is
(63 % at S 8,192)."""
from benchmark.harness import mla_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = mla_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * (secs["attention_ops"] + secs["flash"]) / secs["busy_s"]
