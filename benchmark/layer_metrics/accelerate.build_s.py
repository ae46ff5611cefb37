"""Step builder: worker clock over the ``accelerate()`` call plus the first
``train_step`` to completion — trace, lower, compile or cache read (in the
elastic cell: of the resumed incarnation)."""
LAYER = "step builder"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("build_s")
