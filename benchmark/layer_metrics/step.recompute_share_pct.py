"""Model step: device self time of the recomputation block remat runs in
front of each block's backward (``jax.checkpoint``'s
``rematted_computation``) over device busy time — what the remat costs that
a looped model's passes x layers block applications force.  Two parts: the
instructions whose phase is ``recompute`` in the scope table of the compiled
step (``obs_read.scope_shares``, every scope), and the recomputed half of
``flash_fwd`` — a Mosaic kernel's label in the trace is its name, the same
forward and recomputed, so the join reads it ``mixed``; the program's own
``accelerate.program`` event says how many of its ``flash_fwd`` calls were
forward applications (``block_applications``) and how many there are
(``kernels``), and the kernel's seconds are split by that count (the calls
are the same shape).  ``rmsnorm_fwd`` is ``mixed`` the same way and is left
where it is (0.8 % of busy in all its uses).  A step without remat reads 0;
a program that journals no ``block_applications`` (before the PR that
brought it) contributes the first part alone."""
from benchmark.harness import obs_read

LAYER = "model step"
SOURCE = "device_trace"
KERNEL = "flash_fwd"


def read(spans, trace, counters):
    recs = obs_read.records(spans)
    shares = obs_read.scope_shares(recs, trace)
    if shares is None:
        return None
    pct = sum(pct for (phase, _), pct in shares["by"].items()
              if phase == "recompute")
    programs = [r for r in obs_read.last_incarnation(recs)
                if r.get("kind") == "accelerate.program"]
    calls = (programs[-1].get("kernels") or {}).get(KERNEL, 0)
    forward = programs[-1].get("block_applications")
    if forward is not None and calls > forward:
        pct += (100.0 * trace.get("kernel_s", {}).get(KERNEL, 0.0)
                * (calls - forward) / calls / trace["busy_s"])
    return pct
