"""The steady runner's account of ``setup_s`` on a session that computes
nothing: the host clock's total, less the backend's start-up as the session
stamped it, less the comparison with the reference."""

import re
import time
import types

import pytest

from benchmark.harness import common

BACKEND_S, CHECK_S, STEP_S = 0.12, 0.08, 0.005


class FakeSession:
    tokens_per_step = 8

    def __init__(self, cell, seed, t_proc_start):
        self.t_proc_start = t_proc_start
        self.spans = {"input_wait_s": [], "step_s": [], "save_stall_s": []}
        self.losses = []
        self.state = {"params": None}
        self.model_config = None
        self.job = types.SimpleNamespace(program={}, memory={})

    def open_device(self):
        t0 = time.monotonic()
        time.sleep(BACKEND_S)
        t1 = time.monotonic()
        self.spans["backend_open_s"] = t1 - t0
        self.spans["device_open_s"] = t1 - self.t_proc_start
        return {"platform": "cpu", "kind": "cpu", "count": 1}

    def build(self):
        self.spans["accelerate_s"] = 0.0

    def create_state(self):
        self.spans["create_state_s"] = 0.0

    def start_sampler(self):
        pass

    def first_step(self):
        return self.step(record=False)

    def step(self, record=True):
        time.sleep(STEP_S)
        if record:
            self.spans["input_wait_s"].append(0.0)
            self.spans["step_s"].append(STEP_S)
            self.losses.append(1.0)
        return 1.0

    def step_metrics(self):
        return {}


def test_less_parts_prints_the_whole_account():
    value, note = common.less_parts(
        "SETUP_S", 22.970113, backend_open_s=7.860001, check_s=5.1797)
    assert value == pytest.approx(9.930412)
    assert note == ("SETUP_S 9.930412 total=22.970113 "
                    "backend_open_s=7.860001 check_s=5.179700")
    assert common.less_parts("RESUME", 3.0) == (
        3.0, "RESUME 3.000000 total=3.000000")


def test_setup_s_is_the_total_less_backend_and_check(tmp_path, monkeypatch):
    from benchmark.harness import model

    runner = common.load_module("runners", "train_steady")
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))
    monkeypatch.setattr(runner, "TrainSession", FakeSession)

    def check(job, model_config, cell, params, seed):
        time.sleep(CHECK_S)
        return {"ok": True}

    monkeypatch.setattr(model, "check_against_reference", check)
    cell = {"name": "fake.steady", "chips": 1, "traffic_data": {
        "warmup_steps": 2, "trace_skip_steps": 1, "trace_steps": 1}}
    args = types.SimpleNamespace(seed=7, seconds=0.05, trace=0,
                                 rehearse=True, dump_trace="")
    t_start = time.monotonic()
    out = runner.run(cell, args, t_start)
    assert out["correct"]
    setup_s = out["end_to_end"]["setup_s"]
    (note,) = [ln for ln in out["notes"] if ln.startswith("SETUP_S ")]
    m = re.fullmatch(r"SETUP_S (\S+) total=(\S+) backend_open_s=(\S+) "
                     r"check_s=(\S+)", note)
    shown, total, backend, checked = map(float, m.groups())
    # the note carries all four, and the metric is the total less the parts
    assert shown == pytest.approx(setup_s, abs=1e-6)
    assert total - backend - checked == pytest.approx(setup_s, abs=2e-6)
    assert backend == pytest.approx(out["spans"]["backend_open_s"], abs=1e-6)
    assert backend >= BACKEND_S and checked >= CHECK_S
    # what is left is what the session did besides: two warm-up steps
    assert 2 * STEP_S <= setup_s < total - BACKEND_S - CHECK_S + 1e-6
