"""Model step: device self time of the instructions under the program's
``lm_head_loss`` scope (``models/llama.py::loss_fn``: the chunked head
matmul, the softmax cross-entropy, forward and backward) over device busy
time — the trace's instruction names joined to the scope table of the
compiled step (``accelerate.program_summary``).  Also prints the whole
join (``SCOPES``) with the share no scope names."""
from benchmark.harness import obs_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    shares = obs_read.scope_shares(obs_read.records(spans), trace)
    if shares is None:
        return None
    obs_read.print_scope_shares(shares)
    return sum(pct for (_, scope), pct in shares["by"].items()
               if scope == "lm_head_loss")
