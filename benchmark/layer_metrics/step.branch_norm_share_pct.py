"""Model step: device self time under ``branch_norm`` — the RMSNorm on each
branch's OUTPUT of a sandwich-norm block (``ln1_out``, ``ln2_out``: two a
block application beside the two input norms; the ``rmsnorm_fwd`` kernel's
calls and the backward's XLA fusions, forward, backward and recomputed) —
over device busy time (``harness/afmoe_read.py``).  Memory-bound passes over
the stream's width: what the two further norms of a block cost a step."""
from benchmark.harness import afmoe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = afmoe_read.seconds(spans, trace)
    if secs is None or "branch_norm" not in secs or not secs["busy_s"]:
        return None
    return 100.0 * secs["branch_norm"] / secs["busy_s"]
