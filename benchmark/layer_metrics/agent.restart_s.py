"""Launcher, master, agent: the parent's clock from the SIGKILL it sends the
worker to the first line of the new worker (the agent's monitor interval,
its breakpoint persist of the staged step, and the restart itself)."""
LAYER = "launcher, master, agent"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("agent_restart_s")
