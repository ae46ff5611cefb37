"""Chrome-trace analysis: turn a trace into an actionable breakdown.

Parity with the reference's trace tooling
(``atorch/utils/trace/`` timeline parsing, the xpu-timer's per-kernel
aggregation, and ``analyse``-stage reporting): given a chrome-trace JSON
— the flight recorder's merged dumps
(:func:`dlrover_tpu.obs.collect.write_chrome_trace`), ``jax.profiler``'s
trace-viewer export, or any Perfetto-compatible producer — compute
per-op/per-category time rollups, top-k hotspots, concurrency-corrected
busy time, and step statistics, and render a text report.  Pure host
code: no jax import, usable offline on collected traces.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class TraceEvent:
    name: str
    category: str
    start_us: float
    dur_us: float
    tid: int = 0
    pid: int = 0
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us


def load_trace(path: str) -> List[TraceEvent]:
    """Read a chrome trace (.json or .json.gz; bare list or
    {"traceEvents": [...]}), keeping complete ('X') duration events."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    raw = data["traceEvents"] if isinstance(data, dict) else data
    def as_int(v) -> int:
        """Some producers (viztracer, py-spy) emit string tids like
        "MainThread"; hash those instead of failing the whole load."""
        try:
            return int(v or 0)
        except (TypeError, ValueError):
            return hash(str(v)) & 0x7FFFFFFF

    out = []
    for ev in raw:
        if ev.get("ph") != "X":
            continue
        out.append(
            TraceEvent(
                name=str(ev.get("name", "")),
                category=str(ev.get("cat", "")),
                start_us=float(ev.get("ts", 0.0)),
                dur_us=float(ev.get("dur", 0.0)),
                tid=as_int(ev.get("tid")),
                pid=as_int(ev.get("pid")),
                args=ev.get("args", {}) or {},
            )
        )
    out.sort(key=lambda e: e.start_us)
    return out


@dataclasses.dataclass
class OpStat:
    name: str
    count: int
    total_us: float
    mean_us: float
    max_us: float
    pct_of_busy: float


def _merge_busy(intervals: List[Tuple[float, float]]) -> float:
    """Union length of [start, end) intervals — wall-clock busy time
    with overlapping (concurrent) events counted once."""
    if not intervals:
        return 0.0
    intervals.sort()
    busy = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return busy + (cur_e - cur_s)


class TraceAnalysis:
    """Aggregations over one loaded trace."""

    def __init__(self, events: Sequence[TraceEvent]):
        self.events = list(events)

    @classmethod
    def from_file(cls, path: str) -> "TraceAnalysis":
        return cls(load_trace(path))

    # -- rollups -------------------------------------------------------------
    def span_us(self) -> float:
        if not self.events:
            return 0.0
        return max(e.end_us for e in self.events) - min(
            e.start_us for e in self.events
        )

    def busy_us(self) -> float:
        return _merge_busy([(e.start_us, e.end_us) for e in self.events])

    def by_category(self) -> Dict[str, float]:
        """category -> summed duration (overlap NOT deduplicated: this is
        'work attributed', matching per-op rollups)."""
        out: Dict[str, float] = defaultdict(float)
        for e in self.events:
            out[e.category or "(none)"] += e.dur_us
        return dict(out)

    def top_ops(self, k: int = 20) -> List[OpStat]:
        total: Dict[str, List[float]] = defaultdict(list)
        for e in self.events:
            total[e.name].append(e.dur_us)
        busy = self.busy_us() or 1.0
        stats = [
            OpStat(
                name=name,
                count=len(durs),
                total_us=sum(durs),
                mean_us=sum(durs) / len(durs),
                max_us=max(durs),
                pct_of_busy=100.0 * sum(durs) / busy,
            )
            for name, durs in total.items()
        ]
        stats.sort(key=lambda s: -s.total_us)
        return stats[:k]

    def steps(
        self, step_event: str = "train_step"
    ) -> List[Tuple[float, float]]:
        """(start, dur) of every event named ``step_event`` — the step
        markers the trainer's spans carry."""
        return [
            (e.start_us, e.dur_us)
            for e in self.events
            if e.name == step_event
        ]

    def step_stats(
        self, step_event: str = "train_step"
    ) -> Optional[Dict[str, float]]:
        durs = sorted(d for _, d in self.steps(step_event))
        if not durs:
            return None
        from dlrover_tpu.utils.prof import percentile

        def pct(p: float) -> float:
            return percentile(durs, p)

        return {
            "count": float(len(durs)),
            "mean_us": sum(durs) / len(durs),
            "p50_us": pct(0.50),
            "p90_us": pct(0.90),
            "p99_us": pct(0.99),
            "max_us": durs[-1],
        }

    def gaps(
        self, threshold_us: float = 1000.0
    ) -> List[Tuple[float, float]]:
        """Idle windows longer than ``threshold_us`` between busy spans —
        the input-pipeline/host-stall hunting ground."""
        iv = sorted((e.start_us, e.end_us) for e in self.events)
        out = []
        if not iv:
            return out
        cur_end = iv[0][1]
        for s, e in iv[1:]:
            if s - cur_end > threshold_us:
                out.append((cur_end, s - cur_end))
            cur_end = max(cur_end, e)
        return out

    # -- report --------------------------------------------------------------
    def report(self, k: int = 12, step_event: str = "train_step") -> str:
        lines = []
        span = self.span_us()
        busy = self.busy_us()
        lines.append(
            f"trace: {len(self.events)} events, span {span/1e3:.2f} ms, "
            f"busy {busy/1e3:.2f} ms "
            f"({100.0 * busy / span if span else 0.0:.1f}%)"
        )
        ss = self.step_stats(step_event)
        if ss:
            lines.append(
                f"steps ({step_event}): n={int(ss['count'])} "
                f"mean={ss['mean_us']/1e3:.2f}ms "
                f"p50={ss['p50_us']/1e3:.2f}ms "
                f"p90={ss['p90_us']/1e3:.2f}ms "
                f"p99={ss['p99_us']/1e3:.2f}ms"
            )
        cats = sorted(self.by_category().items(), key=lambda kv: -kv[1])
        lines.append("by category:")
        for cat, us in cats[:8]:
            lines.append(f"  {cat:<24} {us/1e3:10.2f} ms")
        lines.append(f"top {k} ops by total time:")
        for s in self.top_ops(k):
            lines.append(
                f"  {s.name[:48]:<48} n={s.count:<6} "
                f"total={s.total_us/1e3:9.2f}ms "
                f"mean={s.mean_us:8.1f}us  {s.pct_of_busy:5.1f}%"
            )
        gaps = self.gaps()
        if gaps:
            worst = max(gaps, key=lambda g: g[1])
            lines.append(
                f"idle gaps >1ms: {len(gaps)} "
                f"(worst {worst[1]/1e3:.2f} ms at t={worst[0]/1e3:.2f} ms)"
            )
        return "\n".join(lines)


def main(argv=None) -> int:  # pragma: no cover - thin CLI shell
    import argparse

    p = argparse.ArgumentParser("dlrover-tpu-trace")
    p.add_argument("trace", help="chrome trace .json/.json.gz")
    p.add_argument("--top", type=int, default=12)
    p.add_argument("--step_event", default="train_step")
    args = p.parse_args(argv)
    print(
        TraceAnalysis.from_file(args.trace).report(
            args.top, args.step_event
        )
    )
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
