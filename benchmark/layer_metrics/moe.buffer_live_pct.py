"""Model step: how full the sorted buffer of a routed block is where the
chip holds a SHARE of the experts and the block chooses its buffer's size
from the pairs it computes — the held pairs over the rows the buffer took,
in the fullest routed block, from the counters the jitted step returns
beside its loss (``moe_held_pairs`` over ``moe_buffer_rows``, both int32
``[routed blocks]``, of the window's last step;
``counters["step_metrics"]``).  The rows gathered, masked and passed
through ``silu(g) * u`` are the buffer's; the rows the grouped matmuls
compute are the held pairs.  ``held / experts`` under even routing where
the buffer holds every pick (25 % at 8 of 32), 80 % where the first size
(the even share with a quarter of slack) engages.  A program whose blocks
choose nothing returns no ``moe_buffer_rows``."""
LAYER = "model step"
SOURCE = "program_counter"


def read(spans, trace, counters):
    metrics = counters.get("step_metrics") or {}
    held, rows = (metrics.get("moe_held_pairs"),
                  metrics.get("moe_buffer_rows"))
    if not held or not rows:
        return None
    return max(100.0 * h / r for h, r in zip(held, rows))
