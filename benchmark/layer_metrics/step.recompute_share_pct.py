"""Model step: device self time of the recomputation block remat runs in
front of each block's backward (``jax.checkpoint``'s
``rematted_computation``) over device busy time — what the remat costs that
a looped model's passes x layers block applications force: the operations
whose phase is ``recompute`` in the scope table of the compiled step
(``obs_read.scope_shares``, every scope), a Mosaic kernel's recomputed calls
among them — the join places a kernel call by call, so ``flash_fwd``,
``ssd_chunk_fwd``, ``gmm`` or ``rmsnorm_fwd`` run again in front of a
backward count here and their forward calls do not.  A step without remat
reads 0."""
from benchmark.harness import obs_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    shares = obs_read.scope_shares(obs_read.records(spans), trace)
    if shares is None:
        return None
    return sum(pct for (phase, _), pct in shares["by"].items()
               if phase == "recompute")
