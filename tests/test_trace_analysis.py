"""Trace-analysis tests: synthetic traces with known answers, plus a
real round trip through the flight recorder (test model: the reference's trace
tooling unit tests)."""

import gzip
import json

import pytest

from dlrover_tpu import obs
from dlrover_tpu.obs.collect import write_chrome_trace
from dlrover_tpu.utils.trace_analysis import (
    TraceAnalysis,
    TraceEvent,
    load_trace,
)


def _ev(name, cat, ts, dur, tid=0):
    return TraceEvent(
        name=name, category=cat, start_us=ts, dur_us=dur, tid=tid
    )


def _synthetic():
    # Two 10ms steps: matmul 6ms + allreduce 2ms inside each, on two
    # "threads" (the second matmul overlaps the first step's allreduce).
    return [
        _ev("train_step", "step", 0, 10_000),
        _ev("matmul", "compute", 0, 6_000),
        _ev("allreduce", "comm", 6_000, 2_000),
        _ev("matmul", "compute", 7_000, 6_000, tid=1),  # overlaps
        _ev("train_step", "step", 12_000, 10_000),
        _ev("allreduce", "comm", 13_000, 2_000),
    ]


class TestAnalysis:
    def test_busy_merges_overlap(self):
        ta = TraceAnalysis(_synthetic())
        # Union of [0,13000) and [13000,15000) and the steps... steps
        # cover [0,10000) and [12000,22000); everything unions to
        # [0,10000) + [12000,22000) + the 7..13k matmul bridges 10..12k:
        # [0,13000) U [12000,22000) = [0,22000) minus [10000,12000)?
        # matmul tid=1 spans 7000..13000 -> union = [0,13000)+[12000,
        # 22000) = 22000 total (they overlap at 12000..13000).
        assert ta.busy_us() == 22_000
        assert ta.span_us() == 22_000

    def test_by_category_and_top_ops(self):
        ta = TraceAnalysis(_synthetic())
        cats = ta.by_category()
        assert cats["compute"] == 12_000
        assert cats["comm"] == 4_000
        top = ta.top_ops(2)
        assert top[0].name == "train_step" and top[0].total_us == 20_000
        assert top[1].name == "matmul"
        assert top[1].count == 2
        assert top[1].mean_us == pytest.approx(6_000)

    def test_step_stats(self):
        ta = TraceAnalysis(_synthetic())
        ss = ta.step_stats("train_step")
        assert ss["count"] == 2
        assert ss["mean_us"] == pytest.approx(10_000)
        assert ta.step_stats("missing") is None

    def test_gaps(self):
        events = [
            _ev("a", "c", 0, 1_000),
            _ev("b", "c", 5_000, 1_000),  # 4ms idle before it
        ]
        gaps = TraceAnalysis(events).gaps(threshold_us=1_000)
        assert gaps == [(1_000, 4_000)]

    def test_report_renders(self):
        rep = TraceAnalysis(_synthetic()).report()
        assert "by category" in rep
        assert "train_step" in rep
        assert "busy" in rep


class TestLoadTrace:
    def test_json_and_gz_and_shapes(self, tmp_path):
        events = {
            "traceEvents": [
                {"name": "x", "cat": "c", "ph": "X", "ts": 1, "dur": 2},
                {"name": "m", "ph": "i", "ts": 5},  # non-X dropped
            ]
        }
        p = tmp_path / "t.json"
        p.write_text(json.dumps(events))
        evs = load_trace(str(p))
        assert len(evs) == 1 and evs[0].name == "x"
        # bare-list form, gzipped
        pz = tmp_path / "t2.json.gz"
        with gzip.open(pz, "wt") as f:
            json.dump(events["traceEvents"], f)
        assert len(load_trace(str(pz))) == 1

    def test_round_trip_through_the_flight_recorder(self, tmp_path):
        """obs.span -> dump -> merged chrome trace -> TraceAnalysis."""
        rec = obs.configure(out_dir=str(tmp_path), process="t")
        try:
            with obs.span("train_step", "step"):
                with obs.span("fwd", "compute"):
                    pass
            obs.journal("ckpt", step=3)
            rec.dump()
        finally:
            obs.reset()
        path = write_chrome_trace(str(tmp_path), str(tmp_path / "t.json"))
        ta = TraceAnalysis.from_file(path)
        names = {e.name for e in ta.events}
        assert names == {"train_step", "fwd"}
        assert ta.step_stats("train_step")["count"] == 1
        assert "fwd" in ta.report()
