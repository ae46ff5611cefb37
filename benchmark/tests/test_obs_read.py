"""The readers of the program's own spans (``harness/obs_read.py`` and the
``program_span`` metrics built on it) on a small recorded journal: the
files an elastic rehearsal's launcher, agent and two worker incarnations
wrote (``data/obs_journal/``, durations edited to round numbers), and a
hand-made device trace against a hand-made scope table."""

import json
import os
import shutil
import tempfile

import pytest

from benchmark.harness import common, obs_read

DATA = os.path.join(os.path.dirname(__file__), "data", "obs_journal")
#: what the runner hands a reader when a run happened (any stamp will do)
RAN = {"device_open_s": 1.0}


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """The recorded journal where the elastic cell's launcher would have
    put it for this process."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    job = tmp_path / "dlrover_tpu_obs" / f"bench-{os.getpid()}-ab12cd34"
    shutil.copytree(DATA, job)
    # another run's directory, whose pid only starts like ours
    other = tmp_path / "dlrover_tpu_obs" / f"bench-{os.getpid()}7-ffff0000"
    shutil.copytree(DATA, other)
    monkeypatch.setattr(obs_read, "_removed_at_exit", set())
    monkeypatch.setattr(obs_read.atexit, "register", lambda *a, **k: None)
    return job


def _read(metric: str, spans=RAN, trace=None, counters=None):
    reader = common.load_module("layer_metrics", metric)
    return reader.read(spans, trace or {}, counters or {})


def test_records_come_from_this_runs_directory_only(journal):
    recs = obs_read.records(RAN)
    assert {r["_proc"] for r in recs} == {
        "agent-n0", "worker-r0-i0", "worker-r0-i1"}
    assert len(obs_read.named(recs, "agent.restart")) == 1
    assert obs_read.records({}) == []  # no run, nothing read


def test_an_untraced_run_removes_its_directory_itself(journal):
    """What the elastic runner's ``finally`` calls, and what its check
    "no arena, process or journal outlives the run" then looks for."""
    assert obs_read.job_dirs() == [str(journal)]
    obs_read.remove_job_dirs()
    assert obs_read.job_dirs() == []
    # another run's directory, whose pid only starts like ours, stays
    assert os.listdir(journal.parent) == [
        f"bench-{os.getpid()}7-ffff0000"]


def test_a_line_cut_by_the_kill_is_skipped(journal):
    with open(journal / "flight-worker-r0-i0-101.jsonl", "a") as f:
        f.write('{"k": "span", "name": "ckpt.save", "ts": 1, "du')
    assert len(obs_read.named(obs_read.records(RAN), "ckpt.save")) == 3


@pytest.mark.parametrize("metric,want", [
    ("ckpt.first_save_d2h_s", 2.0),
    ("ckpt.first_save_write_s", 30.0),
    ("ckpt.restore_read_s", 20.0),
    ("ckpt.restore_put_s", 35.0),
    ("ckpt.persist_write_s", 50.0),  # the commit ran beside the restart
    ("agent.restart_overhead_s", 8.0),  # restart 60 - persist 52
    ("accelerate.compile_s", 3.5),  # compile 1.5 + first call 2, inc. 1
])
def test_span_metrics_on_the_recorded_journal(journal, metric, want):
    assert _read(metric) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "ckpt.first_save_d2h_s", "ckpt.first_save_write_s",
    "ckpt.restore_read_s", "ckpt.restore_put_s", "ckpt.persist_write_s",
    "agent.restart_overhead_s", "accelerate.compile_s",
    "step.lm_head_share_pct", "step.optimizer_share_pct"])
def test_nothing_recorded_reads_as_nothing(tmp_path, monkeypatch, metric):
    """The parent of the PR that added the spans: no journal directory,
    nothing in the ring."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert _read(metric, trace={"busy_s": 1.0,
                                "op_self_s": {"fusion.1 f32[4]": 1.0}}) is None
    assert _read(metric, spans={}) is None


def test_one_process_run_reads_the_ring(tmp_path, monkeypatch):
    """A steady cell: no launcher, no directory; the spans are in this
    process's recorder."""
    from dlrover_tpu import obs

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    obs.configure()
    try:
        obs.get_recorder().span("accelerate.create_state", "accelerate",
                                10.0, 14.0)
        obs.get_recorder().span("accelerate.compile", "accelerate",
                                30.0, 31.0)
        assert _read("state.create_s") == pytest.approx(4.0)
        assert _read("accelerate.compile_s") == pytest.approx(1.0)
        assert _read("ckpt.restore_read_s") is None
    finally:
        obs.reset()


def test_device_open_is_what_precedes_the_backend():
    """``bootstrap.device_open_s`` moves ``setup_s``, which like the elastic
    cell's kill-to-step seconds leaves the first ``jax.devices()`` out: it
    reads the stamp less that call (imports, the compile cache's set-up),
    and nothing where a runner handed over only one of the two."""
    assert _read("bootstrap.device_open_s", spans={
        "device_open_s": 10.865, "backend_open_s": 7.857}) == (
        pytest.approx(3.008))
    assert _read("bootstrap.device_open_s", spans=RAN) is None
    assert _read("bootstrap.device_open_s",
                 spans={"backend_open_s": 7.857}) is None


TRACE = {"busy_s": 10.0, "op_self_s": {
    "fusion.1 f32[4096,32000]": 2.0,       # forward lm_head_loss
    "all-reduce.3 f32[1024,16000]": 1.0,   # backward lm_head_loss
    "fusion.2 (f32[4096,14336], f32[])": 3.0,  # optimizer
    "flash_fwd": 1.5,                      # kernel: forward + recompute
    "rmsnorm_fwd": 0.5,                    # kernel: two scopes
    "pallas_other": 0.2,                   # a kernel nobody can place
    "fusion.9 f32[8]": 1.7,                # backward mlp
    "copy.77 f32[2]": 0.1,                 # in no scope
}, "kernel_s": {"flash_fwd": 1.5, "rmsnorm_fwd": 0.5, "pallas_other": 0.2},
    # a kernel's seconds by the calling instruction: what the table names
    "kernel_call_s": {
        "flash_fwd": {"flash_fwd.2": 1.0, "flash_fwd.5": 0.5},
        "rmsnorm_fwd": {"rmsnorm_fwd.1": 0.3, "rmsnorm_fwd.2": 0.2},
        "pallas_other": {"custom-call.9": 0.2}}}
PROGRAM = {"k": "ev", "kind": "accelerate.program", "ts": 1.0, "scopes": {
    "fusion.1": ["forward", "lm_head_loss"],
    "all-reduce.3": ["backward", "lm_head_loss"],
    "fusion.2": ["optimizer", "optimizer"],
    "flash_fwd.2": ["forward", "attention"],
    "flash_fwd.5": ["recompute", "attention"],
    "rmsnorm_fwd.1": ["forward", "attention"],
    "rmsnorm_fwd.2": ["forward", "mlp"],
    "fusion.9": ["backward", "mlp"],
}}


def test_scope_join_against_a_device_trace(capsys):
    shares = obs_read.scope_shares([dict(PROGRAM, _proc="")], TRACE)
    # the copy, and the kernel no row of the table names: reported
    assert shares["unphased_pct"] == pytest.approx(3.0)
    assert shares["unplaced_kernel_s"] == {"pallas_other": pytest.approx(0.2)}
    by = shares["by"]
    assert by[("forward", "lm_head_loss")] == pytest.approx(20.0)
    assert by[("optimizer", "optimizer")] == pytest.approx(30.0)
    # a kernel is placed call by call: the same name, two phases, two scopes
    assert by[("forward", "attention")] == pytest.approx(10.0 + 3.0)
    assert by[("recompute", "attention")] == pytest.approx(5.0)
    assert by[("forward", "mlp")] == pytest.approx(2.0)
    assert sum(by.values()) == pytest.approx(100.0)
    obs_read.print_scope_shares(shares)
    line = capsys.readouterr().out
    assert line.startswith("SCOPES pct_of_busy optimizer/optimizer=30.00")
    assert line.rstrip().endswith(
        "unphased_pct=3.000 unplaced_kernels=pallas_other:0.2000s")


def test_share_metrics_read_the_ring_and_the_trace(tmp_path, monkeypatch,
                                                   capsys):
    from dlrover_tpu import obs

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    obs.configure()
    try:
        obs.journal("accelerate.program", scopes=PROGRAM["scopes"])
        assert _read("step.lm_head_share_pct", trace=TRACE) == (
            pytest.approx(30.0))
        assert "SCOPES " in capsys.readouterr().out
        assert _read("step.optimizer_share_pct", trace=TRACE) == (
            pytest.approx(30.0))
        assert _read("step.optimizer_share_pct", trace={}) is None
    finally:
        obs.reset()


def test_the_directory_goes_when_the_process_exits(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    root = tmp_path / "dlrover_tpu_obs"
    job = root / f"bench-{os.getpid()}-0011aabb"
    shutil.copytree(DATA, job)
    registered = []
    monkeypatch.setattr(obs_read, "_removed_at_exit", set())
    monkeypatch.setattr(obs_read.atexit, "register",
                        lambda fn, *a: registered.append((fn, a)))
    assert obs_read.records(RAN) and obs_read.records(RAN)
    assert len(registered) == 1  # once, however many readers asked
    fn, args = registered[0]
    fn(*args)
    assert not root.exists()


def test_fixture_is_what_the_program_writes():
    """The recorded files keep the recorder's format: a meta line, then
    spans with k, name, cat, ts, dur, sid (psid, args)."""
    for name in sorted(os.listdir(DATA)):
        with open(os.path.join(DATA, name)) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines[0]["k"] == "meta" and lines[0]["reason"] == "journal"
        for rec in lines[1:]:
            if rec["k"] == "span":
                assert {"name", "cat", "ts", "dur", "sid"} <= set(rec)
