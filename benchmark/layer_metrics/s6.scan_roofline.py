"""Kernels: the least time the chip could take for the selective scan of the
Mamba-1 layers, forward and backward, at the cell's shapes (the count of the
configuration's adapter, ``s6_least_seconds``: the larger of the recurrence's
FLOPs over 197 TFLOP/s and its operands' bytes over 819 GB/s) x the program's
own count of its Mamba-1 layers x traced steps, over the device seconds under
the ``s6_scan`` scope, every phase (``harness/s6_read.py``): the two kernels
``s6_scan_fwd`` and ``s6_scan_bwd`` and what XLA lays out around them.  The
least time counts no recomputation — the backward kernel's rebuilding of each
chunk's states is the program's — so the share cannot pass 100 %."""
from benchmark.harness import common, s6_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = s6_read.seconds(spans, trace)
    steps = counters.get("traced_steps")
    if (secs is None or not secs["s6_scan"] or not secs["s6_layers"]
            or not steps):
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "s6_least_seconds"):
        return None
    least = adapter.s6_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return 100.0 * least * secs["s6_layers"] * steps / secs["s6_scan"]
