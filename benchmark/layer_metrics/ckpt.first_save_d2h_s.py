"""Flash checkpoint: the ``ckpt.save.d2h`` span (device-to-host copies and
the flatten walk, ``checkpoint/engine.py::_stage``) of the job's first
save, taken by incarnation 0 in set-up."""
from benchmark.harness import obs_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = [r for r in obs_read.records(spans)
            if obs_read.incarnation(r) == 0]
    return obs_read.child_seconds(
        recs, obs_read.named(recs, "ckpt.save"), "ckpt.save.d2h")
