"""Load-bearing utilities (reference ``atorch/atorch/utils/`` subset that
the TPU build keeps: loss-spike detector ``loss_spike_utils.py``, per-op
runtime metrics and trace rollups, metrics endpoint — the IB-counter
monitor maps to host-interconnect stats surfaced via the same endpoint).
Spans and chrome traces are ``dlrover_tpu.obs``."""

from dlrover_tpu.utils.loss_spike import LossSpikeDetector

__all__ = [
    "LossSpikeDetector",
]
