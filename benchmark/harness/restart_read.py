"""Picks the spans of the kill-to-step path and of the first save's copy
out of the elastic job's journals (``harness/obs_read.py``), for the
``program_span`` metrics that read their arguments: the agent's watch on
its workers (``agent.monitor``), the two-process account of a restart
(``dlrover_tpu.obs.postmortem.restart_accounts``: the program's own
function, one implementation) and the first save's ``ckpt.save.d2h``.
JAX-free.  A program that records none of these (the parent of the PR that
added them) yields nothing, and every reader built on this returns None.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark.harness import obs_read


def failed_watch(spans: dict) -> dict:
    """The ``args`` of the first ``agent.monitor`` span that ended
    ``failed`` (``agent/training.py::MonitorWatch``); ``{}`` of none."""
    for watch in obs_read.named(obs_read.records(spans), "agent.monitor"):
        if (watch.get("args") or {}).get("result") == "failed":
            return watch["args"]
    return {}


def first_account(spans: dict) -> Optional[dict]:
    """The first restart's account, None where the program has no such
    function or its journals hold no restart."""
    recs = obs_read.records(spans)
    if not recs:
        return None
    from dlrover_tpu.obs import postmortem

    accounts = getattr(postmortem, "restart_accounts", None)
    if accounts is None:
        return None
    by = {}
    for rec in recs:
        by.setdefault(rec["_proc"], []).append(rec)
    found = accounts([{"meta": {"process": proc}, "events": events}
                      for proc, events in by.items()])
    return found[0] if found else None


def first_save_d2h(spans: dict) -> Tuple[List[dict], List[dict]]:
    """``(incarnation 0's records, the ckpt.save.d2h of its first
    ckpt.save)``: the job's first save, as ``ckpt.first_save_d2h_s`` picks
    it."""
    recs = [r for r in obs_read.records(spans)
            if obs_read.incarnation(r) == 0]
    saves = obs_read.named(recs, "ckpt.save")
    return recs, (obs_read.children(recs, saves[0], "ckpt.save.d2h")
                  if saves else [])
