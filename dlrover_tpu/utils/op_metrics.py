"""Per-op runtime metrics feeding diagnosis: the xpu-timer analogue.

Parity target: the reference scrapes per-op Prometheus metrics from the
xpu-timer sidecar into its diagnosis chain
(``dlrover/python/diagnosis/datacollector/xpu_timer_metric_collector
.py:22`` — kernel-level hang/slow signals beyond heartbeats).  The
TPU-native shape: no CUDA hooks exist, so every ``capture_every`` steps
the collector wraps ONE training step in a ``jax.profiler`` capture,
parses the XLA trace with :mod:`dlrover_tpu.utils.trace_analysis`, and
classifies device time into collectives / matmuls / other.  The result
feeds three consumers:

- a :class:`~dlrover_tpu.agent.metrics.MetricsRegistry` (the agent's
  ``/metrics`` endpoint) — per-step p50/p90/p99 and per-class fractions,
- the worker's periodic diagnosis report (``diagnosis_data()`` JSON for
  ``MasterClient.report_diagnosis_data``) — the master's hang/straggler
  operators see WHERE time goes, not just that steps stopped,
- a metrics JSON file next to the logs (``metrics_path``) the agent's
  log collector can scrape without any RPC.

Collective share is the straggler tell: on a healthy step collectives
overlap compute; a sick peer shows up as this fraction exploding while
step wall time grows.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import tempfile
import time
from collections import deque
from typing import Deque, Dict, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.utils.prof import percentile

# XLA HLO name prefixes per class (TPU device tracks); the CPU test
# backend emits primitive names (dot_general, ...), covered too.
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
    "psum", "ppermute",
)
MATMUL_PREFIXES = ("dot", "dot_general", "convolution", "fusion.matmul")


def classify_op(name: str) -> str:
    n = name.lower()
    if n.startswith("end:"):
        n = n[4:].strip()
    for p in COLLECTIVE_PREFIXES:
        if n.startswith(p):
            return "collective"
    for p in MATMUL_PREFIXES:
        if n.startswith(p):
            return "matmul"
    return "other"


class _StepTimes:
    """Per-step wall-time stats over a rolling window.  :meth:`step` is
    called once per training step; the first call is counted apart as
    warmup (XLA compile)."""

    def __init__(self, window: int = 200):
        self._times: Deque[float] = deque(maxlen=window)
        self._last: Optional[float] = None
        self.warmup_s: Optional[float] = None
        self._created = time.perf_counter()
        self.total_steps = 0

    def step(self) -> None:
        now = time.perf_counter()
        if self._last is None:
            self.warmup_s = now - self._created
        else:
            self._times.append(now - self._last)
        self._last = now
        self.total_steps += 1

    def summary(self) -> Dict[str, float]:
        if not self._times:
            return {"steps": float(self.total_steps)}
        xs = sorted(self._times)
        return {
            "steps": float(self.total_steps),
            "mean_s": sum(xs) / len(xs),
            "p50_s": percentile(xs, 0.5),
            "p90_s": percentile(xs, 0.9),
            "p99_s": percentile(xs, 0.99),
            "max_s": xs[-1],
            "warmup_s": self.warmup_s or 0.0,
            "steps_per_s": len(xs) / sum(xs) if sum(xs) > 0 else 0.0,
        }


class OpMetricsCollector:
    """Rolling step stats + periodic per-op capture.

    Wrap the training loop::

        col = OpMetricsCollector(capture_every=200)
        for step in ...:
            col.step_begin(step)
            run_one_step()          # must block until the step finishes
            col.step_end(step)
        ... col.metrics() / col.diagnosis_data()
    """

    def __init__(
        self,
        *,
        capture_every: int = 0,  # 0 = step stats only, no traces
        registry=None,
        metrics_path: str = "",
        window: int = 200,
        top_k: int = 5,
        publish_every: int = 20,
    ):
        self.prof = _StepTimes(window)
        self.capture_every = int(capture_every)
        self.registry = registry
        self.metrics_path = metrics_path
        self.top_k = top_k
        self.publish_every = max(1, int(publish_every))
        self._trace_dir: Optional[str] = None
        self._capturing = False
        self._op_fracs: Dict[str, float] = {}
        self._top_ops: list = []
        self._last_capture_step = -1
        self._last_capture_ts = 0.0

    # -- loop hooks ---------------------------------------------------------
    def step_begin(self, step: int) -> None:
        if (
            self.capture_every > 0
            and step > 0  # step 0 is compile; its trace is misleading
            and step % self.capture_every == 0
            and not self._capturing
        ):
            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="dlrtpu_optrace_")
            try:
                jax.profiler.start_trace(self._trace_dir)
                self._capturing = True
                self._last_capture_step = step
            except Exception as e:  # noqa: BLE001 - profiling is advisory
                logger.warning("op-metrics capture failed to start: %s", e)
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None

    def step_end(self, step: int) -> None:
        self.prof.step()
        captured = self._capturing
        if captured:
            self._finish_capture()
        # Publishing does registry sweeps + a file rename: cadence it
        # (consumers scrape every tens of steps anyway), plus right
        # after every capture so fresh op fractions land immediately.
        if captured or step % self.publish_every == 0:
            self._publish()

    def _finish_capture(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            logger.warning("op-metrics stop_trace failed: %s", e)
            self._capturing = False
            if self._trace_dir:  # don't leak the partial trace dir
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None
            return
        self._capturing = False
        try:
            files = glob.glob(
                os.path.join(self._trace_dir or "", "**",
                             "*.trace.json.gz"),
                recursive=True,
            )
            if files and self._analyze(files):
                self._last_capture_ts = time.time()
        except Exception as e:  # noqa: BLE001
            logger.warning("op-metrics trace analysis failed: %s", e)
        finally:
            if self._trace_dir:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None

    def _analyze(self, paths) -> bool:
        """Aggregate op durations over ALL trace files of the capture —
        multi-device/multi-track captures emit one .trace.json.gz per
        track; analyzing only the first skews the fractions the
        straggler operator consumes.  Returns False (keeping the
        previously published fractions intact) when no file yielded any
        events, so an all-corrupt capture doesn't wipe good data."""
        from dlrover_tpu.utils.trace_analysis import TraceAnalysis

        if isinstance(paths, str):
            paths = [paths]
        by_class: Dict[str, float] = {}
        per_op: Dict[str, float] = {}
        for path in paths:
            try:
                ta = TraceAnalysis.from_file(path)
            except Exception as e:  # noqa: BLE001 - skip a bad track
                logger.warning("op-metrics: unreadable trace %s: %s",
                               path, e)
                continue
            for ev in ta.events:
                # Framework/bookkeeping events pollute fractions: keep
                # only op-shaped events (no '::' and not $-internal).
                if "::" in ev.name or ev.name.startswith("$"):
                    continue
                cls = classify_op(ev.name)
                by_class[cls] = by_class.get(cls, 0.0) + ev.dur_us
                key = ev.name.split(".")[0]
                per_op[key] = per_op.get(key, 0.0) + ev.dur_us
        total = sum(by_class.values())
        if total <= 0:
            return False
        self._op_fracs = {k: v / total for k, v in by_class.items()}
        self._top_ops = sorted(
            per_op.items(), key=lambda kv: -kv[1]
        )[: self.top_k]
        return True

    # -- outputs ------------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        out = {
            f"step_{k}": v for k, v in self.prof.summary().items()
        }
        for cls in ("collective", "matmul", "other"):
            out[f"optime_{cls}_frac"] = self._op_fracs.get(cls, 0.0)
        out["last_capture_step"] = float(self._last_capture_step)
        return out

    def diagnosis_data(self) -> str:
        """JSON blob for MasterClient.report_diagnosis_data("op_metrics",
        ...) — consumed by the master's hang/straggler operators."""
        return json.dumps(
            {
                "metrics": self.metrics(),
                "top_ops": [
                    {"name": n, "total_us": round(us, 1)}
                    for n, us in self._top_ops
                ],
                "ts": time.time(),
            }
        )

    def _publish(self) -> None:
        m = self.metrics()
        if self.registry is not None:
            for k, v in m.items():
                try:
                    self.registry.set(f"worker_{k}", float(v))
                # graftcheck: disable=CC104 -- metrics publish is
                # advisory; a registry closing mid-shutdown races this
                # publisher by design
                except Exception:  # noqa: BLE001
                    pass
        if self.metrics_path:
            tmp = f"{self.metrics_path}.tmp.{os.getpid()}"
            try:
                with open(tmp, "w") as f:
                    f.write(self.diagnosis_data())
                os.replace(tmp, self.metrics_path)
            except OSError:
                pass


class OpMetricsCallback:
    """Trainer callback wiring an :class:`OpMetricsCollector` into the
    loop and the master's diagnosis chain.

    Because the Trainer's hook surface fires at step END, a capture is
    armed one step ahead: ``step_begin(step+1)`` from ``on_step_end`` —
    so the profiled window covers exactly one full subsequent step.
    Every ``report_every`` steps the collector's JSON lands on the
    master as ``DiagnosisDataType.OP_METRICS`` (feeding
    ``CheckStragglerOperator``)."""

    def __init__(
        self,
        *,
        capture_every: int = 0,
        report_every: int = 50,
        master_client=None,
        registry=None,
        metrics_path: str = "",
    ):
        self.collector = OpMetricsCollector(
            capture_every=capture_every,
            registry=registry,
            metrics_path=metrics_path,
        )
        self.report_every = int(report_every)
        self.client = master_client

    # TrainerCallback surface (duck-typed; see trainer.TrainerCallback).
    def on_train_begin(self, args, state, control) -> None: ...

    def on_step_end(self, args, state, control, metrics) -> None:
        self.collector.step_end(state.step)
        if (
            self.client is not None
            and self.report_every > 0
            and state.step % self.report_every == 0
        ):
            try:
                self.client.report_diagnosis_data(
                    "op_metrics", self.collector.diagnosis_data()
                )
            except Exception as e:  # noqa: BLE001 - advisory path
                logger.debug("op-metrics report failed: %s", e)
        self.collector.step_begin(state.step + 1)

    def on_log(self, args, state, control, logs) -> None: ...

    def on_evaluate(self, args, state, control, metrics) -> None: ...

    def on_save(self, args, state, control) -> None: ...

    def on_epoch_end(self, args, state, control) -> None: ...

    def on_train_end(self, args, state, control) -> None:
        if self.collector._capturing:  # close a dangling capture
            self.collector._finish_capture()
