"""Model step: what the latent path costs around the kernels — device time
under ``mla_q`` (down, norm, up, rotary of the queries) and ``mla_kv``
(down, norm, up, rotary, the one rotary key broadcast under every head and
concatenated) over attention as a whole (``step.attention_share_pct``'s
numerator: the ``attention`` scope and the flash kernels;
``harness/mla_read.py``).  The RMSNorm kernels of the two latents are Mosaic
calls the join cannot place and are left out (0.8 % of busy in all their
uses)."""
from benchmark.harness import mla_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = mla_read.seconds(spans, trace)
    whole = secs and secs["attention_ops"] + secs["flash"]
    if not whole:
        return None
    return 100.0 * (secs["mla_q"] + secs["mla_kv"]) / whole
