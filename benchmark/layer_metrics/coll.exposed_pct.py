"""Collectives: time in which a collective runs on a device and no compute
op does, over the traced window, averaged over the devices."""
LAYER = "collectives"
SOURCE = "device_trace"


def read(spans, trace, counters):
    if not trace.get("window_s") or trace.get("n_devices", 1) < 2:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
