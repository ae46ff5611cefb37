"""The six readers of the kill-to-step path and of the first save's copy
(``harness/restart_read.py`` and the ``program_span`` metrics built on it)
on a three-process journal written by the program's own recorder on a clock
of round seconds, and on a journal of the parent's shape
(``data/obs_journal/``: no ``agent.monitor``, no children under
``ckpt.save.d2h``, no ``psid`` on the worker's start), where each
reads None but the one whose event that program already journals."""

import os
import shutil
import tempfile

import pytest

from benchmark.harness import common, obs_read, restart_read

PARENT_SHAPE = os.path.join(os.path.dirname(__file__), "data", "obs_journal")
RAN = {"device_open_s": 1.0}
METRICS = {
    "agent.failure_unseen_s": 1.0,
    "agent.monitor_busy_max_s": 0.02,
    # 109 (the watch's end less unseen_s) to 152, less the union of the
    # agent's and the resumed worker's spans: holes 124-125, 128-131,
    # 140-142, 150-151
    "agent.restart_unspanned_s": 7.0,
    "bootstrap.interpreter_s": 3.5,
    "ckpt.first_save_d2h_issue_s": 0.5,
    "ckpt.first_save_d2h_cpu_s": 1.25,
}


def _write(job_dir) -> None:
    from dlrover_tpu.obs import FlightRecorder

    def recorder(process, pid):
        rec = FlightRecorder(process=process, out_dir=str(job_dir))
        rec.dump_path = lambda: os.path.join(
            str(job_dir), f"flight-{process}-{pid}.jsonl")
        return rec

    def span(rec, name, start, end, sid="", parent="", **args):
        rec.span(name, name.split(".")[0], start, end, span_id=sid or None,
                 parent=parent, args=args or None, durable=True)

    agent = recorder("agent-n0", 100)
    first = recorder("worker-r0-i0", 101)
    second = recorder("worker-r0-i1", 102)
    # incarnation 0: the first save, then a second one a reader must skip
    span(first, "ckpt.save.d2h.issue", 50.0, 50.5, parent="d2h")
    span(first, "ckpt.save.d2h.fetch", 50.5, 54.5, parent="d2h", leaves=9,
         first_leaf_s=3.9, largest=[[4096, 0.2]])
    span(first, "ckpt.save.d2h", 50.0, 54.5, sid="d2h", parent="save",
         bytes=8192, tensors=9, cpu_s=1.25)
    span(first, "ckpt.save", 50.0, 60.0, sid="save", step=2)
    span(first, "ckpt.save.d2h.issue", 70.0, 70.1, parent="d2h2")
    span(first, "ckpt.save.d2h", 70.0, 71.0, sid="d2h2", parent="save2",
         cpu_s=0.1)
    span(first, "ckpt.save", 70.0, 72.0, sid="save2", step=4)
    # the agent: a watch that succeeded is not the one; the one that failed
    span(agent, "agent.monitor", 10.0, 20.0, result="membership_changed",
         turns=10, busy_max_s=9.0)
    span(agent, "agent.monitor", 100.0, 110.0, result="failed", turns=10,
         sleep_s=9.9, poll_s=0.05, rpc_s=0.05, busy_max_s=0.02,
         unseen_s=1.0)
    span(agent, "ckpt.persist", 111.0, 117.0, parent="stop",
         reason="breakpoint")
    span(agent, "agent.stop_workers", 110.5, 118.0, sid="stop",
         parent="restart")
    span(agent, "agent.rendezvous", 118.0, 119.0, parent="restart")
    span(agent, "agent.start_workers", 119.0, 120.0, sid="spawn",
         parent="restart")
    span(agent, "agent.restart", 110.0, 120.0, sid="restart",
         reason="failed")
    # incarnation 1, started by that restart
    second._clock = lambda: 123.0
    second.event("bootstrap.process_start", durable=True,
                 since_process_start_s=3.5, psid="spawn", restart_count=1)
    span(second, "bootstrap.init", 123.0, 124.0, parent="spawn")
    span(second, "bootstrap.backend_init", 125.0, 128.0)
    span(second, "accelerate.build", 131.0, 140.0)
    span(second, "accelerate.create_state", 142.0, 144.0)
    span(second, "ckpt.load", 144.0, 150.0)
    span(second, "accelerate.first_call", 151.0, 152.0)
    for rec in (agent, first, second):
        rec.close()


@pytest.fixture
def here(tmp_path, monkeypatch):
    """Where the elastic cell's launcher puts this process's job."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(obs_read, "_removed_at_exit", set())
    monkeypatch.setattr(obs_read.atexit, "register", lambda *a, **k: None)
    return tmp_path / "dlrover_tpu_obs" / f"bench-{os.getpid()}-ab12cd34"


def _read(metric: str, spans=RAN):
    return common.load_module("layer_metrics", metric).read(spans, {}, {})


@pytest.mark.parametrize("metric,want", sorted(METRICS.items()))
def test_reader_on_the_written_journal(here, metric, want):
    os.makedirs(here)
    _write(here)
    assert _read(metric) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_on_a_journal_of_the_parents_shape(here, metric):
    shutil.copytree(PARENT_SHAPE, here)
    assert obs_read.records(RAN)  # there is a journal, of the old shape
    # the interpreter's event is older than its reader (PR 23 journals it)
    want = 0.7 if metric == "bootstrap.interpreter_s" else None
    assert _read(metric) == want
    assert _read(metric, spans={}) is None  # and no run at all


def test_a_program_without_the_account_reads_as_nothing(here, monkeypatch):
    """The new files laid over the parent's checkout: ``obs.postmortem``
    has no ``restart_accounts``."""
    from dlrover_tpu.obs import postmortem

    os.makedirs(here)
    _write(here)
    assert restart_read.first_account(RAN)["interval_s"] == pytest.approx(43)
    monkeypatch.delattr(postmortem, "restart_accounts")
    assert restart_read.first_account(RAN) is None
    assert _read("agent.restart_unspanned_s") is None


def test_every_new_metric_is_declared_for_the_elastic_cell_alone():
    spec = common.load_spec()
    by = {m["name"]: m for m in spec["per_layer"]}
    for name in METRICS:
        m = by[name]
        assert m["workloads"] == ["mistral7b-l1.elastic"], name
        assert (m["source"], m["moves"], m["better"], m["unit"]) == (
            "program_span", "setup_s", "lower", "s"), name
