"""Step builder: seconds of ``jax.trace``, ``jax.lower`` and ``jax.compile``
spans (``common/jax_env.py``'s listener) that no ``accelerate.*`` span
encloses — the union of their intervals.  What JAX's stages cost for
programs other than the step's build, the state's init and the first call:
the harness's comparison against the reference, or a user's own jits; their
run time is not in it.  In the elastic cell of the resumed incarnation."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    outside = compile_read.outside_build(spans)
    return None if outside is None else (
        compile_read.covered_s(outside) or 0.0)
