"""Step builder: seconds of the ``jax.compile`` spans whose ``cache_hit`` is
False — programs XLA compiled because the persistent cache did not have
them (``common/jax_env.py``'s listener); 0 where every program asked for was
read; in the elastic cell of the resumed incarnation.  What of ``setup_s``
judges the cache's contents and not the tree.  Also prints the ``COMPILES``
line: the five longest stages and every miss, by function."""
from benchmark.harness import compile_read

LAYER = "step builder"
SOURCE = "program_span"


def read(spans, trace, counters):
    stages = compile_read.stage_spans(spans)
    if not any(s["name"] == "jax.compile" for s in stages):
        return None
    compile_read.print_compiles(stages)
    return compile_read.covered_s(compile_read.missed(stages)) or 0.0
