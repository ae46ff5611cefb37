"""Model step: of the device seconds under the two attention kinds' scopes
(``attn_window`` + ``attn_full``: each kind's flash kernels, forward and
backward, and the transposes around them; ``harness/window_read.py``), the
share under ``attn_window``.  At the cell's sizes six of eight layers and
18 % of the attended pairs are the window kind's: what this reads above
18 % is what the window path pays a pair over the full path."""
from benchmark.harness import window_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = window_read.seconds(spans, trace)
    if secs is None or not secs["attn_window"] + secs["attn_full"]:
        return None
    return 100.0 * secs["attn_window"] / (
        secs["attn_window"] + secs["attn_full"])
