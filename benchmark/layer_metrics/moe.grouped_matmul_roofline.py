"""Kernels: the least time the chip could take for the three grouped expert
matmuls, forward and backward, at the cell's shapes (the count of the
configuration's adapter, ``grouped_matmul_least_seconds``: the larger of
FLOPs over 197 TFLOP/s and bytes over 819 GB/s) x layers x traced steps,
over the device seconds under the ``moe_experts`` scope (the ``gmm`` and
``tgmm`` kernels' calls and ``silu(g) * u``; ``harness/moe_read.py``).  A
grouped matmul's call the program's table does not name is in no scope:
the reader then counts it here as well, so the share reads low, never
high."""
from benchmark.harness import common, moe_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = moe_read.scope_seconds(spans, trace)
    steps = counters.get("traced_steps")
    if secs is None or not secs["moe_experts"] or not steps:
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "grouped_matmul_least_seconds"):
        return None
    least = adapter.grouped_matmul_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return (100.0 * least * cell["config_data"]["num_hidden_layers"] * steps
            / (secs["moe_experts"] + secs["unplaced"]))
