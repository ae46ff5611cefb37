"""Flash checkpoint: the ``ckpt.persist.write`` span (the streamed shard
write) of the agent's breakpoint persist, recorded in the agent's process
(``agent/ckpt_saver.py``), plus its ``ckpt.persist.commit`` where that
ran inside the persist (the agent's saver commits on a pool thread, beside
the restart and not in its way: left out): what of ``ckpt.persist_s`` is
the write itself, without the SIGTERM grace and the reaping that the
outside stamp also holds."""
from benchmark.harness import obs_read

LAYER = "flash checkpoint"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = obs_read.records(spans)
    persists = [p for p in obs_read.named(recs, "ckpt.persist")
                if (p.get("args") or {}).get("reason") == "breakpoint"]
    if not persists:
        return None
    end = persists[0]["ts"] + persists[0]["dur"]
    return obs_read.seconds(
        k for k in obs_read.children(recs, persists[0])
        if k["name"] == "ckpt.persist.write"
        or (k["name"] == "ckpt.persist.commit" and k["ts"] + k["dur"] <= end))
