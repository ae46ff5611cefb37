"""Step builder: seconds the program's build spent tracing functions to
jaxprs and lowering them to MLIR — the union of the ``jax.trace`` and
``jax.lower`` spans (JAX's ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration``, recorded by ``common/jax_env.py``'s
listener) under an ``accelerate.*`` span; the harness's comparison programs
are left out, as they are out of ``setup_s``; in the elastic cell of the
resumed incarnation.  Host work that no cache saves."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    return compile_read.covered_s(
        compile_read.build_stages(spans, "jax.trace", "jax.lower") or [])
