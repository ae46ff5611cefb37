"""Model step: the part of the state-space mixer that is the gate and the
gated norm — device time under ``ssm_gate`` (``y * silu(z)`` and, where the
norm runs in groups, each group's mean square, its ``rsqrt`` and the gain:
elementwise and short reductions in float32, memory-bound; with one group
the norm is the RMSNorm kernel, which the join leaves out) over all of
``ssm`` (``step.ssm_share_pct``'s numerator; ``harness/ssm_read.py``)."""
from benchmark.harness import ssm_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = ssm_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["ssm_gate"] / secs["ssm"]
