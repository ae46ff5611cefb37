"""Flash checkpoint: worker clock around ``FlashCheckpointer.load()`` in the
resumed incarnation (warm restore from shared memory)."""
LAYER = "flash checkpoint"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("restore_s")
