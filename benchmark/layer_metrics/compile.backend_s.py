"""Step builder: seconds in JAX's ``backend_compile_duration`` — every
``jax.compile`` span (``common/jax_env.py``'s listener): XLA's compile of a
program, or its read from the persistent cache; in the elastic cell of the
resumed incarnation."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    return compile_read.covered_s(
        compile_read.stage_spans(spans, "jax.compile"))
