"""Flash checkpoint: ``cpu_s`` of the first save's ``ckpt.save.d2h`` span
(``obs.span(..., host=True)``): the CPU seconds of every thread of the
worker between the span's ends.  Far under ``ckpt.first_save_d2h_s`` the
process slept on the copy; near or above it the host was copying or
faulting pages in."""
from benchmark.harness import restart_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    _, d2h = restart_read.first_save_d2h(spans)
    return (d2h[0].get("args") or {}).get("cpu_s") if d2h else None
