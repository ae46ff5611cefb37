"""Launcher, master, agent: the agent's ``agent.restart`` span (failure
seen by its monitor -> the new workers started) minus the ``ckpt.persist``
inside its ``agent.stop_workers``: SIGTERM grace and reaping, rendezvous
and spawn — what of the restart is the agent's own."""
from benchmark.harness import obs_read

LAYER = "launcher, master, agent"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = obs_read.records(spans)
    restarts = obs_read.named(recs, "agent.restart")
    if not restarts:
        return None
    persist = obs_read.seconds(
        obs_read.descendants(recs, restarts[0], "ckpt.persist")) or 0.0
    return obs_read.seconds(restarts[:1]) - persist
