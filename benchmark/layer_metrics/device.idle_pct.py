"""Device: 1 - union of device-op intervals over the traced window."""
LAYER = "device"
SOURCE = "device_trace"


def read(spans, trace, counters):
    if not trace.get("window_s"):
        return None
    return 100.0 * trace["idle_share"]
