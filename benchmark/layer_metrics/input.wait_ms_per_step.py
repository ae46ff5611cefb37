"""Data: worker clock around sampler + token build +
``make_array_from_process_local_data``, median per step over the window."""
import statistics

LAYER = "data"
SOURCE = "host_clock"


def read(spans, trace, counters):
    waits = spans.get("input_wait_s") or []
    return 1e3 * statistics.median(waits) if waits else None
