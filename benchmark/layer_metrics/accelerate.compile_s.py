"""Step builder: the ``accelerate.compile`` span (XLA's compile of the
lowered step, or its read from the persistent cache) plus
``accelerate.first_call`` (the jitted step's own first call, which lowers
and compiles once more) — ``parallel/accelerate.py``; in the elastic cell
of the resumed incarnation.  What of ``accelerate.build_s`` is the
compiler or the cache, and not tracing, lowering and analysis."""
from benchmark.harness import obs_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = obs_read.last_incarnation(obs_read.records(spans))
    return obs_read.seconds(
        obs_read.named(recs, "accelerate.compile")
        + obs_read.named(recs, "accelerate.first_call"))
