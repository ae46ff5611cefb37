"""Flash checkpoint: parent clock from the agent's "breakpoint save ...
persisting" line to its "stopped workers" line — the persist and commit of
the staged step to disk, which the agent runs before it restarts a killed
worker, so it sits inside ``agent.kill_to_step_s`` and ``setup_s``."""
LAYER = "flash checkpoint"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("persist_s")
