"""Model step: how unevenly the router loads the experts — the fullest
expert's (token, expert) pairs over the mean, in the worst routed layer, from
the counter the jitted step computes itself and returns beside its loss
(``moe_tokens_per_expert``, int32 ``[routed layers, experts]``, of the
window's last step; ``counters["step_metrics"]``).  1.0 is even; the
grouped matmuls' groups are this uneven."""
LAYER = "model step"
SOURCE = "program_counter"


def read(spans, trace, counters):
    per_layer = (counters.get("step_metrics") or {}).get(
        "moe_tokens_per_expert")
    if not per_layer:
        return None
    return max(max(row) * len(row) / sum(row) for row in per_layer
               if sum(row))
