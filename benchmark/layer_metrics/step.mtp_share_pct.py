"""Model step: device time of the multi-token-prediction block over device
busy time — the XLA instructions under the ``mtp`` scope (both norms,
``w_eh``, the block's attention and routed branches; forward, backward and
recomputed) plus the block's share of the flash kernels, one application in
``block_applications`` of the same shapes (``harness/mla_read.py``).  The
block's grouped-matmul and RMSNorm kernels carry no scope in the trace and
are left out.  The head's second set of rows is ``lm_head_loss``'s."""
from benchmark.harness import mla_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = mla_read.seconds(spans, trace)
    if secs is None:
        return None
    applications = secs["block_applications"] or 0
    flash = secs["flash"] / applications if applications else 0.0
    return 100.0 * (secs["mtp_ops"] + flash) / secs["busy_s"]
