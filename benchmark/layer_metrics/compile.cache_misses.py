"""Step builder: how many ``jax.compile`` spans under an ``accelerate.*``
span have ``cache_hit`` False — programs of the step's build and the state's
init compiled and not read from the persistent cache (``common/jax_env.py``'s
listener); in the elastic cell of the resumed incarnation."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    stages = compile_read.build_stages(spans, "jax.compile")
    return None if stages is None else len(compile_read.missed(stages))
