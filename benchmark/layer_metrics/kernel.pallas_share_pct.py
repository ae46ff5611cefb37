"""Kernels: device time in every Mosaic kernel of the step (the program's
named ones, ``trace_reduce.PALLAS_KERNELS``, and ``pallas_other``) over
device busy time, from the trace."""
LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    if not trace.get("busy_s"):
        return None
    return 100.0 * sum(trace["kernel_s"].values()) / trace["busy_s"]
