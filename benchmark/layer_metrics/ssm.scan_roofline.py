"""Kernels: the least time the chip could take for the scan of the
state-space layers, forward and backward, at the cell's shapes (the count of
the configuration's adapter, ``ssd_least_seconds``: the larger of the
recurrence's FLOPs over 197 TFLOP/s and its operands' bytes over 819 GB/s)
x the program's own count of its state-space layers x traced steps, over the
device seconds under the ``ssm_scan`` scope, every phase, block remat's
recomputation included (``harness/ssm_read.py``).  The least time counts no
recomputation, so the share cannot pass 100 %."""
from benchmark.harness import common, ssm_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = ssm_read.seconds(spans, trace)
    steps = counters.get("traced_steps")
    if (secs is None or not secs["ssm_scan"] or not secs["ssm_layers"]
            or not steps):
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "ssd_least_seconds"):
        return None
    least = adapter.ssd_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return 100.0 * least * secs["ssm_layers"] * steps / secs["ssm_scan"]
