"""Step builder: the ``accelerate.create_state`` span
(``parallel/accelerate.py::AcceleratedJob.create_state``: the state's init
traced, lowered, compiled or read from the cache, and dispatched; it ends
when the call returns); in the elastic cell of the resumed incarnation."""
from benchmark.harness import obs_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = obs_read.last_incarnation(obs_read.records(spans))
    return obs_read.seconds(obs_read.named(recs, "accelerate.create_state"))
