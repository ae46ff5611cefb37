"""Model step: the per-channel delta rule's part of the KDA mixer — device
time under ``kda_scan`` (beta, the decay, the L2 norms, the cumulative sums
and the rule's kernel pair ``kda_chunk_fwd`` / ``kda_chunk_bwd``) over all of
``kda`` (``step.kda_share_pct``'s numerator; ``harness/kda_read.py``)."""
from benchmark.harness import kda_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = kda_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["kda_scan"] / secs["kda"]
