"""Kernels: the least time the chip could take for the flash forward and
backward of the WINDOW layers at the cell's shapes (the count of the
configuration's adapter, ``flash_window_least_seconds``: per layer the
larger of FLOPs over 197 TFLOP/s and bytes over 819 GB/s, the FLOPs those
of the pairs a window layer attends) x traced steps, over the summed device
time of the ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` calls
under ``attn_window`` (``harness/window_read.py``).  ``flash_roofline``
reads both kinds together."""
from benchmark.harness import common, window_read

LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    secs = window_read.seconds(spans, trace)
    steps = counters.get("traced_steps")
    if secs is None or not secs["flash_window"] or not steps:
        return None
    cell = counters["cell"]
    adapter = common.adapter_of(cell["config_data"])
    if not hasattr(adapter, "flash_window_least_seconds"):
        return None
    least = adapter.flash_window_least_seconds(
        cell["config_data"], cell["batch_sequences"],
        cell["traffic_data"]["seq_len"], counters["peaks"],
        shards=counters["chips"])["seconds"]
    return 100.0 * least * steps / secs["flash_window"]
