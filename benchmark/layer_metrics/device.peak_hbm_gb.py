"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window.  Guards against a change that fits only by luck; read beside
every claim."""
LAYER = "device"
SOURCE = "program_counter"


def read(spans, trace, counters):
    peak = counters.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
