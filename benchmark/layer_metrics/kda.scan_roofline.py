"""Kernels: the least time the chip could take for the per-channel delta
rule of the KDA layers, forward and backward, at the cell's shapes (the count
of the configuration's adapter, ``kda_least_seconds``: the larger of the
chunked rule's FLOPs over 197 TFLOP/s and its operands' bytes — q, k, v, o,
beta, the float32 decay a key channel and its cotangent — over 819 GB/s) x
the program's own count of its KDA layers x traced steps, over the device
seconds under the ``kda_scan`` scope, every phase (``harness/kda_read.py``).
The least time counts no recomputation, so the share cannot pass 100 %."""
from benchmark.harness import common, kda_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = kda_read.seconds(spans, trace)
    steps = counters.get("traced_steps")
    if (secs is None or not secs["kda_scan"] or not secs["kda_layers"]
            or not steps):
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "kda_least_seconds"):
        return None
    least = adapter.kda_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return 100.0 * least * secs["kda_layers"] * steps / secs["kda_scan"]
