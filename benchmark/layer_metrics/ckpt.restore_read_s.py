"""Flash checkpoint: the ``ckpt.load.shm_read`` span (``reopen`` +
``read_state`` of the arena; under an agent it copies) of the resumed
incarnation's ``FlashCheckpointer.load()``."""
from benchmark.harness import obs_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = [r for r in obs_read.records(spans)
            if obs_read.incarnation(r) == 1]
    return obs_read.child_seconds(
        recs, obs_read.named(recs, "ckpt.load"), "ckpt.load.shm_read")
