"""Flash checkpoint: the ``ckpt.save.d2h.issue`` span (the
``copy_to_host_async`` call on every leaf, ``checkpoint/engine.py::_stage``)
under the ``ckpt.save.d2h`` of the job's first save, taken by incarnation 0
in set-up; the rest of ``ckpt.first_save_d2h_s`` is ``.fetch``, the walk
that waits for each leaf's host array."""
from benchmark.harness import obs_read, restart_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    return obs_read.child_seconds(
        *restart_read.first_save_d2h(spans), "ckpt.save.d2h.issue")
