"""Model step: the part of the state-space mixer that is no projection —
device time under ``ssm_conv`` (the causal depthwise convolution and its
silu), ``ssm_scan`` (softplus, the chunked scan) and ``ssm_gate`` (the gate
and the norm's XLA part) over all of ``ssm`` (``step.ssm_share_pct``'s
numerator; ``harness/ssm_read.py``): memory- and latency-bound work between
the matmul-bound ``ssm_in`` and ``ssm_out``."""
from benchmark.harness import ssm_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = ssm_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * (secs["ssm_conv"] + secs["ssm_scan"]
                    + secs["ssm_gate"]) / secs["ssm"]
