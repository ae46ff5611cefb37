"""Launcher, master, agent: ``unspanned_s`` of the first account of the
program's own ``obs.postmortem.restart_accounts`` (one implementation: the
operator's postmortem prints the same account): of the interval from the
first moment the agent could have known of the failure to the end of the
resumed worker's first step call, the seconds that lie under no span of the
agent or of that worker — what the program still cannot name."""
from benchmark.harness import restart_read

LAYER = "launcher, master, agent"
SOURCE = "program_span"


def read(spans, trace, counters):
    account = restart_read.first_account(spans)
    return account["unspanned_s"] if account else None
