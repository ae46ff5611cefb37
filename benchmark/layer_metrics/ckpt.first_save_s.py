"""Flash checkpoint: the first save of a job, which touches every page of
the shared-memory arena for the first time (in set-up, first incarnation)."""
LAYER = "flash checkpoint"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("first_save_s")
