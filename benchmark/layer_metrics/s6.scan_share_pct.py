"""Model step: the part of the Mamba-1 mixer that is no projection of the
stream — device time under ``s6_conv`` (the causal depthwise convolution and
its silu), ``s6_dt`` (``x_proj``, ``dt_proj`` and the softplus: two low-rank
products and an elementwise pass), ``s6_scan`` (the selective scan's kernels
and what XLA lays out around them) and ``s6_gate`` over all of ``s6``
(``step.s6_share_pct``'s numerator; ``harness/s6_read.py``): memory- and
latency-bound work between the matmul-bound ``s6_in`` and ``s6_out``."""
from benchmark.harness import s6_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = s6_read.seconds(spans, trace)
    if secs is None or not secs["s6"]:
        return None
    return 100.0 * (secs["s6_conv"] + secs["s6_dt"] + secs["s6_scan"]
                    + secs["s6_gate"]) / secs["s6"]
