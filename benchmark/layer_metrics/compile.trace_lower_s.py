"""Step builder: seconds the process spent tracing functions to jaxprs and
lowering them to MLIR — the union of its ``jax.trace`` and ``jax.lower``
spans (JAX's ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``,
recorded by ``common/jax_env.py``'s listener); in the elastic cell of the
resumed incarnation.  Host work that no cache saves."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    return compile_read.covered_s(
        compile_read.stage_spans(spans, "jax.trace", "jax.lower"))
