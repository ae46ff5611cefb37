"""Model step: device self time of the instructions whose phase is
``optimizer`` (the ``optimizer`` scope around ``tx.update`` +
``apply_updates`` in ``parallel/accelerate.py::train_step``) over device
busy time, by the same join as ``step.lm_head_share_pct``.  A fusion
carries its root's name: where XLA fuses the AdamW update into a weight
gradient's fusion, that time is the backward's here, and this share is a
lower bound."""
from benchmark.harness import obs_read

LAYER = "model step"
SOURCE = "device_trace"


def read(spans, trace, counters):
    shares = obs_read.scope_shares(obs_read.records(spans), trace)
    if shares is None:
        return None
    return sum(pct for (phase, _), pct in shares["by"].items()
               if phase == "optimizer")
