"""Step builder: seconds of the ``jax.compile`` spans under an
``accelerate.*`` span whose ``cache_hit`` is False — programs of the step's
build and the state's init that XLA compiled because the persistent cache
did not have them (``common/jax_env.py``'s listener); 0 where every one was
read; in the elastic cell of the resumed incarnation.  What of ``setup_s``
judges the cache's contents and not the tree.  Also prints the ``COMPILES``
line over EVERY stage, the comparison's too: the seconds outside the build,
the five longest stages and every miss, by function."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    inside, outside = compile_read.by_cause(spans)
    if not any(s["name"] == "jax.compile" for s in inside + outside):
        return None
    compile_read.print_compiles(inside, outside)
    return compile_read.covered_s(compile_read.missed(inside)) or 0.0
