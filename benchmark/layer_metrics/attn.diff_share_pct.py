"""Model step: of the device seconds under the blocks' ``attention`` scope
(norm, projections with their biases, the reordering of paired heads, the
flash kernels, ``wo``, the residual add), the share under ``attn_diff``: what
differential attention adds behind the flash call — ``o1 - lambda o2``, the
RMSNorm per pair with ``subln`` and the ``1 - lambda_init`` — forward,
backward and recomputed (``harness/s6_read.py``).  Elementwise work over
``[tokens, 40 heads x 128]`` in float32 between the flash kernel and ``wo``:
what it reads is what XLA left of it outside the neighbouring fusions."""
from benchmark.harness import s6_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = s6_read.seconds(spans, trace)
    if secs is None or not secs["attn_diff"] or not secs["attention"]:
        return None
    return 100.0 * secs["attn_diff"] / secs["attention"]
