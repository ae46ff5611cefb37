"""Launcher, master, agent: the parent's clock from the SIGKILL it sends the
worker to the line in which the restarted worker reports its first completed
step after restoring, less that worker's ``backend_open_s`` — the agent's
notice of the death, its breakpoint persist, the restart, the new worker's
imports, build, state and restore.  End to end as ``resume_s`` until PR 50:
one kill a run spreads by 6-7 % of it on a shared one-chip host, over half
of the widest bound, so it is read here and the whole sequence is held by
``setup_s``, which contains it."""
LAYER = "launcher, master, agent"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("kill_to_step_s")
