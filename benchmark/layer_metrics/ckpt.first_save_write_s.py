"""Flash checkpoint: the ``ckpt.save.arena_write`` span (``write_state``
into the shared-memory arena, which this first save also creates: first
touch of every page) of incarnation 0's set-up save."""
from benchmark.harness import obs_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = [r for r in obs_read.records(spans)
            if obs_read.incarnation(r) == 0]
    return obs_read.child_seconds(
        recs, obs_read.named(recs, "ckpt.save"), "ckpt.save.arena_write")
