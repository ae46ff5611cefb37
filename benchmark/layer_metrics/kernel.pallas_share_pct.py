"""Kernels: device time in the repo's six named Pallas kernels over device
busy time, from the trace."""
LAYER = "kernels"
SOURCE = "device_trace"


def read(spans, trace, counters):
    if not trace.get("busy_s"):
        return None
    return 100.0 * sum(trace["kernel_s"].values()) / trace["busy_s"]
