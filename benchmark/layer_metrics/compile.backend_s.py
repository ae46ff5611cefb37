"""Step builder: seconds in JAX's ``backend_compile_duration`` — the
``jax.compile`` spans (``common/jax_env.py``'s listener) under an
``accelerate.*`` span: XLA's compile of the step's and the state's programs,
or their read from the persistent cache; the harness's comparison programs
are left out, as they are out of ``setup_s``; in the elastic cell of the
resumed incarnation."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    return compile_read.covered_s(
        compile_read.build_stages(spans, "jax.compile") or [])
