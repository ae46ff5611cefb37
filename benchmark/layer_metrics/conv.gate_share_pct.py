"""Model step: the part of the gated short-convolution mixer that is no
projection — device time under ``conv_gate`` (``B * X``, the causal
depthwise taps, ``C *``; elementwise, memory-bound) over all of ``conv``
(``step.conv_share_pct``'s numerator; ``harness/conv_read.py``), between the
matmul-bound ``conv_in`` and ``conv_out``."""
from benchmark.harness import conv_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = conv_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["conv_gate"] / secs["conv"]
