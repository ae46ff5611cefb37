"""Step builder: how many ``jax.compile`` spans have ``cache_hit`` False —
programs compiled and not read from the persistent cache
(``common/jax_env.py``'s listener); in the elastic cell of the resumed
incarnation."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    stages = compile_read.stage_spans(spans, "jax.compile")
    return len(compile_read.missed(stages)) if stages else None
