"""Model step: required FLOPs per token (causal, windowed attention; no
embedding matmul; no recomputation — the count of the configuration's
adapter, benchmark/harness/flops.py for the dense block) times the tokens
per second of the window over chips times the bf16 peak."""
from benchmark.harness import common

LAYER = "model step"
SOURCE = "host_clock"


def read(spans, trace, counters):
    rate = counters.get("tokens_per_s")
    peak = counters["peaks"]["bf16_flops"]
    if not rate or peak != peak:  # NaN in a rehearsal: no device, no MFU
        return None
    cell = counters["cell"]
    need = common.adapter_of(cell["config_data"]).model_flops_per_token(
        cell["config_data"], cell["traffic_data"]["seq_len"])["total"]
    return 100.0 * need * rate / (counters["chips"] * peak)
