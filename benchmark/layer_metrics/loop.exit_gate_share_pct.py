"""Model step: device self time of the instructions under the program's
``exit_gate`` scope (``models/llama.py``: the gate's logit on each pass's
normed stream, the exit distribution, the head's row weights and the
entropy term; forward and backward, every phase) over device busy time.
The trace's instruction names joined to the scope table of the compiled
step (``obs_read.scope_shares``).  A program without the scope (any model
that is not looped, or the parent of the PR that brought it) yields
None."""
from benchmark.harness import obs_read

LAYER = "model step"
SOURCE = "device_trace"
SCOPE = "exit_gate"


def read(spans, trace, counters):
    shares = obs_read.scope_shares(obs_read.records(spans), trace)
    if shares is None:
        return None
    found = [pct for (_, scope), pct in shares["by"].items()
             if scope == SCOPE]
    return sum(found) if found else None
