"""Launcher, master, agent: ``busy_max_s`` of the first ``agent.monitor``
span that ended ``failed``: the longest ``poll + rpc`` of one turn of the
agent's loop — how long it was away from its sleep.  Seconds here say the
turn is slow (the polls, or ``num_nodes_waiting``); near 0 with a late
``failure_seen`` says the killed worker polled as alive."""
from benchmark.harness import restart_read

LAYER = "launcher, master, agent"
SOURCE = "program_span"


def read(spans, trace, counters):
    return restart_read.failed_watch(spans).get("busy_max_s")
