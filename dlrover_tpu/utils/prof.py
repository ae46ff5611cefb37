"""Percentiles for the step statistics (``utils/op_metrics.py``) and the
trace rollups (``utils/trace_analysis.py``).  Spans are recorded by
``dlrover_tpu.obs`` (one recorder, bridged to ``jax.profiler``), not
here."""

from __future__ import annotations


def percentile(sorted_xs, p: float) -> float:
    """Nearest-rank percentile over a pre-sorted sequence."""
    n = len(sorted_xs)
    return sorted_xs[min(n - 1, int(p * n))]
