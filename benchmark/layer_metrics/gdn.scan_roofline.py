"""Kernels: the least time the chip could take for the gated delta rule of
the delta-rule layers, forward and backward, at the cell's shapes (the count
of the configuration's adapter, ``gdn_least_seconds``: the larger of the
chunked rule's FLOPs over 197 TFLOP/s and its operands' bytes over 819 GB/s)
x the program's own count of its delta-rule layers x traced steps, over the
device seconds under the ``gdn_scan`` scope, every phase, the recomputations
included (``harness/gdn_read.py``).  The least time counts no recomputation,
so the share cannot pass 100 %."""
from benchmark.harness import common, gdn_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = gdn_read.seconds(spans, trace)
    steps = counters.get("traced_steps")
    if (secs is None or not secs["gdn_scan"] or not secs["gdn_layers"]
            or not steps):
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "gdn_least_seconds"):
        return None
    least = adapter.gdn_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return 100.0 * least * secs["gdn_layers"] * steps / secs["gdn_scan"]
