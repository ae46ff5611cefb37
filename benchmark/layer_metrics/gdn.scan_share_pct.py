"""Model step: the gated delta rule's part of the delta-rule mixer — device
time under ``gdn_scan`` (beta, g, the L2 norms and the chunked rule: the
``[Q, Q]`` products, the inverse, the scan over the chunks) over all of
``gdn`` (``step.gdn_share_pct``'s numerator; ``harness/gdn_read.py``):
bandwidth- and latency-bound work between the matmul-bound ``gdn_in`` and
``gdn_out``."""
from benchmark.harness import gdn_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = gdn_read.seconds(spans, trace)
    if secs is None:
        return None
    return 100.0 * secs["gdn_scan"] / secs["gdn"]
