"""Model step: device self time of the instructions under the ``conv`` scope
of every gated short-convolution layer (its input norm's XLA part, the two
projections, the two gates with the taps, and the residual add; forward,
backward and recomputed) over device busy time (``harness/conv_read.py``).
Four layers in five are of this kind in the LFM2 cut."""
from benchmark.harness import conv_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = conv_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["conv"] / secs["busy_s"]
