"""Step builder: the per-device peak of the compiled step from XLA's buffer
assignment (``AcceleratedJob.memory["peak_bytes"]``): arguments, outputs and
temporaries of the one program.  ``memory_stats()`` on this backend does not
count a running program's temporaries, so this is the number that says how
close the step is to the chip's 16.9 GB."""
LAYER = "step builder"
SOURCE = "program_counter"


def read(spans, trace, counters):
    mem = counters.get("compiled_memory")
    return mem["peak_bytes"] / 1e9 if mem else None
