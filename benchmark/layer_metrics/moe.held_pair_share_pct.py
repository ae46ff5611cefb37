"""Model step: of the (token, expert) pairs a routed block's router chose,
the share routed to the experts THIS CHIP HOLDS and so computed here — in
the fullest routed block, from the counters the jitted step returns beside
its loss (``moe_held_pairs`` int32 ``[routed blocks]`` over the row sums of
``moe_tokens_per_expert`` ``[routed blocks, experts]``, of the window's last
step; ``counters["step_metrics"]``).  ``held / experts`` (12.5 % at 8 of
64) under even routing; the grouped matmuls' work is this share of N x K.
A program that holds every expert returns no ``moe_held_pairs``."""
LAYER = "model step"
SOURCE = "program_counter"


def read(spans, trace, counters):
    metrics = counters.get("step_metrics") or {}
    held, per_expert = (metrics.get("moe_held_pairs"),
                        metrics.get("moe_tokens_per_expert"))
    if not held or not per_expert:
        return None
    return max(100.0 * h / sum(row) for h, row in zip(held, per_expert)
               if sum(row))
