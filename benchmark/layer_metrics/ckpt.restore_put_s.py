"""Flash checkpoint: the ``ckpt.load.device_put`` span
(``restore_to_target``, ended by ``block_until_ready`` on the restored
state) of the resumed incarnation's ``FlashCheckpointer.load()``."""
from benchmark.harness import obs_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = [r for r in obs_read.records(spans)
            if obs_read.incarnation(r) == 1]
    return obs_read.child_seconds(
        recs, obs_read.named(recs, "ckpt.load"), "ckpt.load.device_put")
