"""Model step: of the device seconds under the blocks' ``attention`` scope
(projections, norms, rotation, the flash kernels, the gate, ``wo``, the
output norm where there is one, the residual add), the share under
``attn_gate``: the sigmoid gate's multiply on the attention output, one
gate per element, forward, backward and recomputed
(``harness/afmoe_read.py``).  Elementwise work over ``[tokens, heads x
head_dim]`` in float32 between the flash kernel and ``wo``: what it reads is
what XLA left of it outside the neighbouring fusions."""
from benchmark.harness import afmoe_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = afmoe_read.seconds(spans, trace)
    if secs is None or "attn_gate" not in secs or not secs["attention"]:
        return None
    return 100.0 * secs["attn_gate"] / secs["attention"]
