"""The elastic runner: its clean-up finds this run's arenas and no other
run's, its two clocks of set-up are the parent's less each worker's own
stamp of the backend's start-up, and its rate is the window's tokens over the
window's seconds."""

import os
import re
import types

import pytest

from benchmark.harness import common


def test_arenas_match_this_job_only():
    elastic = common.load_module("runners", "elastic")
    mine = ["dlrtpu_bench-12-ab12cd34_ckpt_0", "dlrtpu_bench-12_ckpt_0"]
    others = ["dlrtpu_bench-123-ab12cd34_ckpt_0", "dlrtpu_ckpt-bench-12_x_0"]
    paths = [os.path.join("/dev/shm", n) for n in mine + others]
    try:
        for p in paths:
            open(p, "w").close()
        assert sorted(elastic._arenas("bench-12")) == sorted(paths[:2])
    finally:
        for p in paths:
            os.unlink(p)


CPU = {"platform": "cpu", "kind": "cpu", "count": 4}
T_START, T_KILL = 1000.0, 1100.0
#: what two worker incarnations print, stamped by the parent's clock as the
#: lines arrive (``t``); the parent started at T_START and kills at T_KILL
WORKER_LINES = [
    {"kind": "start", "restart_count": 0, "pid": 11, "t": 1010.0},
    {"kind": "device", "summary": CPU, "device_open_s": 12.0,
     "backend_open_s": 9.0, "t": 1022.0},
    {"kind": "step", "n": 1, "loss": 8.0, "first": True, "t": 1030.0},
    {"kind": "step", "n": 2, "loss": 7.5, "t": 1031.0},
    {"kind": "save", "step": 2, "stall_s": 9.0, "setup": True, "t": 1040.0},
    {"kind": "step", "n": 3, "loss": 7.0, "t": 1041.0},
    {"kind": "step", "n": 4, "loss": 6.5, "t": 1042.0},
    {"kind": "start", "restart_count": 1, "pid": 12, "t": 1112.0},
    {"kind": "device", "summary": CPU, "device_open_s": 13.0,
     "backend_open_s": 10.5, "t": 1125.0},
    {"kind": "restored", "step": 2, "restore_s": 1.5, "t": 1130.0},
    {"kind": "step", "n": 3, "loss": 7.0, "first": True, "t": 1131.5},
    {"kind": "step", "n": 4, "loss": 6.5, "t": 1132.0},
    {"kind": "window_open", "t": 1133.0},
    {"kind": "result", "t": 1160.0,
     "spans": {"save_stall_s": [5.5], "build_s": 3.7, "restore_s": 1.5,
               "device_open_s": 13.0, "backend_open_s": 10.5,
               "step_s": [0.5, 0.75, 0.5, 0.5]},
     "losses": [6.0, 5.5, 5.0, 4.5], "steps": 4, "tokens_per_step": 16384,
     "window_s": 2.5, "traced_steps": [1], "save_stall_s": 5.5,
     "compiles_in_window": 0, "memory_peak_bytes": 0,
     "engine_stall_ms_last": 5500.0, "engine_staged_mbps_last": None,
     "program": {}, "memory": {}},
]


class RecordedLines:
    """``elastic.Lines`` over lines that have already arrived."""

    def __init__(self, events):
        self.seen = list(events)
        self._left = list(events)

    def expect(self, kind, timeout, **match):
        while self._left:
            ev = self._left.pop(0)
            if ev["kind"] == kind and all(
                    ev.get(k) == v for k, v in match.items()):
                return ev
        raise RuntimeError(f"no {kind!r} line")

    #: the agent's own log lines, by the parent's clock
    LOG = [(1101.5, "worker failure(s): [(0, -9)]"),
           (1101.6, "breakpoint save (step 2): persisting"),
           (1105.6, "stopped workers (worker failure; re-rendezvous)"),
           (1106.0, "started 1 worker(s): pids=[12]")]

    def first_time(self, pattern, after=0.0):
        return next((t for t, line in self.LOG
                     if t >= after and re.search(pattern, line)), None)


def test_set_up_leaves_out_each_workers_backend_and_the_rate_is_the_windows(tmp_path, monkeypatch):
    elastic = common.load_module("runners", "elastic")
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))
    monkeypatch.setattr(elastic.os, "kill", lambda pid, sig: None)
    monkeypatch.setattr(elastic, "_marked_pids", lambda mark: [])
    monkeypatch.setattr(elastic, "_holds_device", lambda pid: False)
    monkeypatch.setattr(
        elastic, "_watch_exit",
        lambda pid, t_kill, seen: seen.update(worker_exit="0.250(Z)"))
    monkeypatch.setattr(
        elastic, "time", types.SimpleNamespace(monotonic=lambda: T_KILL))
    cell = {"name": "fake.elastic", "chips": 4, "traffic_data": {
        "setup_save_step": 2, "kill_steps_after_save": 2}}
    args = types.SimpleNamespace(seconds=20.0, trace=0, rehearse=True,
                                 dump_trace="")
    out = elastic._drive(cell, args, T_START, RecordedLines(WORKER_LINES),
                         "mark")
    assert all(out["checks"].values()), out["checks"]
    # kill -> first step 31.5 s, of which the restarted worker's backend
    # 10.5; start -> window 133 s, of which the two backends 9 + 10.5
    assert out["spans"]["kill_to_step_s"] == pytest.approx(21.0)
    # the window: four steps of 16,384 tokens in 2.5 s, all of them counted;
    # what a traced run's readers get leaves the traced step out
    assert out["end_to_end"] == {
        "train_tokens_per_s": pytest.approx(26214.4),
        "setup_s": pytest.approx(113.5)}
    assert out["counters"]["tokens_per_s"] == pytest.approx(32768.0)
    assert out["attempted"] == 6 and out["failed"] == 0
    notes = "\n".join(out["notes"])
    assert ("SETUP_S 113.500000 total=133.000000 backend_open_s_0=9.000000"
            " backend_open_s_1=10.500000\n") in notes
    assert re.search(r"^RESUME 21\.000000 total=31\.500000 "
                     r"backend_open_s=10\.500000 agent_restart_s=12\.000 "
                     r"persist_s=(4\.0|3\.9999)", notes, re.M)
    # where the seconds between the kill and the new worker's first line
    # went, by the agent's own log lines
    assert ("RESTART seconds after the kill: worker_exit=0.250(Z) "
            "failure_seen=1.5 persisting=1.6 stopped=5.6 started=6.0 "
            "first_line=12.000\n") in notes
    assert out["spans"]["persist_s"] == pytest.approx(4.0)
    # the resumed worker's two stamps reach the reader of what is left
    reader = common.load_module("layer_metrics", "bootstrap.device_open_s")
    assert reader.read(out["spans"], {}, {}) == pytest.approx(2.5)
    # what was ``resume_s`` end to end until PR 50 reaches its reader
    reader = common.load_module("layer_metrics", "agent.kill_to_step_s")
    assert reader.read(out["spans"], {}, {}) == pytest.approx(21.0)
    assert reader.read({}, {}, {}) is None
