"""Kernels: the least time the chip could take for the flash forward and
backward at the cell's shapes (the count of the configuration's adapter;
for the dense block benchmark/harness/flops.py: the larger of FLOPs over
197 TFLOP/s and bytes over 819 GB/s) over the summed device time of
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` in the trace."""
from benchmark.harness import common, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    spent = sum(trace.get("kernel_s", {}).get(k, 0.0)
                for k in trace_reduce.FLASH_KERNELS)
    steps = counters.get("traced_steps")
    if not spent or not steps:
        return None
    cell = counters["cell"]
    least = common.adapter_of(cell["config_data"]).flash_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return (100.0 * least * cell["config_data"]["num_hidden_layers"] * steps
            / spent)
