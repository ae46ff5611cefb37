"""Launcher, master, agent: ``unseen_s`` of the first ``agent.monitor`` span
that ended ``failed`` (``agent/training.py::MonitorWatch``): from the end of
the newest poll pass in which no worker had a non-zero exit code to the end
of the pass that saw one — the most the agent itself can have sat on a
worker the kernel had already made waitable.  About one monitor interval
where the loop is sound; the ``RESTART`` line's ``failure_seen`` beside it
says what else lay between the kill and the agent's notice."""
from benchmark.harness import restart_read

LAYER = "launcher, master, agent"
SOURCE = "program_span"


def read(spans, trace, counters):
    return restart_read.failed_watch(spans).get("unseen_s")
