"""The training path's flight recorder (ISSUE 23): the span primitive, its
SIGKILL-proof journal, the spans inside save / load / persist / build, and
the scope table of the compiled step.  CPU, fast."""

import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.obs.collect import load_dir, load_dump

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorder(tmp_path):
    rec = obs.configure(out_dir=str(tmp_path / "obs"), process="ut")
    yield rec
    obs.reset()


def _spans(rec=None, prefix=""):
    evs, _, _ = (rec or obs.get_recorder()).snapshot()
    return [e for e in evs
            if e["k"] == "span" and e["name"].startswith(prefix)]


def _inside(child, parent) -> bool:
    return (child["ts"] >= parent["ts"] - 0.2
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 0.2)


#: what ``obs.span(..., host=True)`` adds to a span's ``args``
HOST_ARGS = ("cpu_s", "minflt", "majflt")


def _unlink_arena(job: str) -> None:
    from dlrover_tpu.common.shm import arena_name

    try:
        os.unlink(f"/dev/shm/{arena_name(job, 0)}")
    except FileNotFoundError:
        pass


def _run_python(code: str, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env))


class TestSpan:
    def test_child_names_its_parent_per_thread(self, recorder):
        other = {}

        def elsewhere():
            with obs.span("t.other", "t") as sp:
                other["psid"] = sp.psid

        with obs.span("t.outer", "t", step=3) as outer:
            with obs.span("t.inner", "t") as inner:
                assert inner.psid == outer.sid
            th = threading.Thread(target=elsewhere)
            th.start()
            th.join()
        by = {s["name"]: s for s in _spans(recorder, "t.")}
        assert by["t.inner"]["psid"] == outer.sid == by["t.outer"]["sid"]
        assert "psid" not in by["t.outer"]
        # another thread's span is no child of this thread's open span
        assert other["psid"] == "" and "psid" not in by["t.other"]
        assert _inside(by["t.inner"], by["t.outer"])
        with obs.span("t.after", "t") as after:  # the stack is empty again
            assert after.psid == ""

    def test_args_set_inside_and_errors_are_recorded(self, recorder):
        with pytest.raises(KeyError):
            with obs.span("t.args", "t", step=7, rank=0) as sp:
                sp.set(bytes=1024)
                raise KeyError("x")
        (rec,) = _spans(recorder, "t.args")
        assert rec["args"] == {"step": 7, "rank": 0, "bytes": 1024,
                               "error": "KeyError"}
        assert rec["cat"] == "t" and rec["dur"] >= 0

    def test_start_end_across_blocks_and_explicit_parent(self, recorder):
        restart = obs.span("t.restart", "t", reason="failed").start()
        with obs.span("t.stop", "t"):
            pass
        restart.end(round=2)
        restart.end()  # a second end records nothing
        with obs.span("t.commit", "t", parent=restart.sid):
            pass
        by = {s["name"]: s for s in _spans(recorder, "t.")}
        assert len(_spans(recorder, "t.restart")) == 1
        assert by["t.stop"]["psid"] == restart.sid
        assert by["t.commit"]["psid"] == restart.sid
        assert by["t.restart"]["args"] == {"reason": "failed", "round": 2}

    def test_never_imports_jax(self):
        res = _run_python(
            "import sys\n"
            "from dlrover_tpu import obs\n"
            "with obs.span('a.b', 'a', n=1):\n"
            "    with obs.span('a.c', 'a'):\n"
            "        pass\n"
            "obs.journal('e', durable=True)\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n"
            "print(len(obs.get_recorder().snapshot()[0]))\n")
        assert res.returncode == 0, res.stderr
        assert res.stdout.strip() == "3"

    def test_bridges_to_trace_annotation_only_when_jax_is_loaded(
            self, recorder, monkeypatch):
        import jax.profiler

        seen = []

        class FakeAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
        with obs.span("t.bridged", "t"):
            assert seen == [("enter", "t.bridged")]
        assert seen == [("enter", "t.bridged"), ("exit", "t.bridged")]
        # a process that has not loaded JAX is never made to
        monkeypatch.delitem(sys.modules, "jax")
        with obs.span("t.plain", "t"):
            pass
        assert len(seen) == 2
        assert {s["name"] for s in _spans(recorder, "t.")} == {
            "t.bridged", "t.plain"}


class TestJournalSurvivesSigkill:
    def test_low_rate_spans_are_on_disk_when_the_process_is_killed(
            self, tmp_path):
        res = _run_python(
            "import os, signal\n"
            "from dlrover_tpu import obs\n"
            "with obs.span('ckpt.save', 'ckpt', step=2, bytes=10):\n"
            "    with obs.span('ckpt.save.d2h', 'ckpt'):\n"
            "        pass\n"
            "with obs.span('trainer.report_step', 'trainer',\n"
            "              ring_only=True, step=3):\n"
            "    pass\n"
            "obs.journal('bootstrap.process_start', durable=True, x=1)\n"
            "obs.journal('ring.only', x=2)\n"
            "os.kill(os.getpid(), signal.SIGKILL)\n",
            DLROVER_TPU_OBS_DIR=str(tmp_path),
            DLROVER_TPU_OBS_PROCESS="worker-r0-i0")
        assert res.returncode == -signal.SIGKILL
        (dump,) = load_dir(str(tmp_path))
        assert dump["meta"]["process"] == "worker-r0-i0"
        assert dump["meta"]["reason"] == "journal"  # no hook ever ran
        names = [e.get("name") or e.get("kind") for e in dump["events"]]
        assert names == ["ckpt.save.d2h", "ckpt.save",
                         "bootstrap.process_start"]
        save = dump["events"][1]
        assert save["args"] == {"step": 2, "bytes": 10}
        assert dump["events"][0]["psid"] == save["sid"]
        assert set(save) >= {"k", "name", "cat", "ts", "dur", "sid"}

    def test_exit_dump_keeps_what_the_ring_has_evicted(self, tmp_path):
        rec = obs.configure(out_dir=str(tmp_path), process="p", capacity=4)
        try:
            with obs.span("ckpt.load", "ckpt", step=1):
                pass
            for n in range(10):  # per-step spans push it out of the ring
                with obs.span("trainer.report_step", "trainer",
                              ring_only=True, step=n):
                    pass
            assert not _spans(rec, "ckpt.load")
            path = rec.dump(reason="exit")
            dump = load_dump(path)
            names = [e["name"] for e in dump["events"]]
            assert names[0] == "ckpt.load" and len(names) == 5
            assert dump["meta"]["reason"] == "exit"
            # the journal goes on after a dump, in the file the dump left
            with obs.span("ckpt.save", "ckpt", step=2):
                pass
            again = load_dump(path)
            assert [e["name"] for e in again["events"]][-1] == "ckpt.save"
        finally:
            obs.reset()

    def test_ring_only_without_a_dump_directory(self):
        rec = obs.configure()
        try:
            with obs.span("ckpt.save", "ckpt"):
                pass
            assert rec.dump_path() is None and _spans(rec, "ckpt.save")
        finally:
            obs.reset()


class TestCheckpointSpans:
    CHILDREN = {
        "ckpt.save": {"ckpt.save.d2h", "ckpt.save.lock_wait",
                      "ckpt.save.arena_write", "ckpt.save.report"},
        "ckpt.load": {"ckpt.load.shm_read", "ckpt.load.agree",
                      "ckpt.load.device_put"},
    }

    def test_save_and_load_leave_their_spans(self, tmp_path, monkeypatch,
                                             recorder):
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        job = f"obs-ckpt-{os.getpid()}"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        ckpt = FlashCheckpointer(str(tmp_path / "ckpt"), job_name=job)
        state = {"params": {"w": jnp.arange(4096.0).reshape(64, 64)},
                 "count": jnp.array(3)}
        nbytes = 64 * 64 * 4 + np.asarray(state["count"]).nbytes
        try:
            ckpt.save(state, meta={"step": 5})
            ckpt.save(state, meta={"step": 6})
            restored, meta = ckpt.load(target=state)
            assert meta["step"] == 6
        finally:
            ckpt.close()
            _unlink_arena(job)
        spans = _spans(recorder, "ckpt.")
        for parent_name, want in self.CHILDREN.items():
            parents = [s for s in spans if s["name"] == parent_name]
            assert parents, parent_name
            for parent in parents:
                kids = [s for s in spans
                        if s.get("psid") == parent["sid"]]
                assert {k["name"] for k in kids} == want
                assert all(_inside(k, parent) for k in kids)
                assert sum(k["dur"] for k in kids) <= parent["dur"] + 1
                assert parent["args"]["bytes"] == nbytes
        first, second = [s for s in spans if s["name"] == "ckpt.save"]
        assert first["args"]["step"] == 5 and second["args"]["step"] == 6
        assert first["args"]["first_touch"] is True
        assert second["args"]["first_touch"] is False
        assert {"stall_ms", "mbps", "rank"} <= set(first["args"])
        d2h = [s for s in spans if s["name"] == "ckpt.save.d2h"][0]
        assert {k: v for k, v in d2h["args"].items()
                if k not in HOST_ARGS} == {"bytes": nbytes, "tensors": 2}
        load = [s for s in spans if s["name"] == "ckpt.load"][0]
        assert load["args"]["source"] == "shm"
        assert load["args"]["step"] == 6
        read = [s for s in spans if s["name"] == "ckpt.load.shm_read"][0]
        assert read["args"] == {"copy": False, "bytes": nbytes}
        # the one record that replaced the ckpt.stage event
        evs, _, _ = recorder.snapshot()
        assert not [e for e in evs if e.get("kind") == "ckpt.stage"]

    def test_storage_save_and_cold_load(self, tmp_path, monkeypatch,
                                        recorder):
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        job = f"obs-cold-{os.getpid()}"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        ckpt = FlashCheckpointer(str(tmp_path / "ckpt"), job_name=job)
        state = {"w": jnp.ones((32, 32)) * 2.5}
        ckpt.save(state, meta={"step": 9}, storage=True)
        assert ckpt.wait(timeout=60)
        ckpt.close()
        _unlink_arena(job)  # the host restarted: shared memory is gone
        ckpt2 = FlashCheckpointer(str(tmp_path / "ckpt"), job_name=job)
        try:
            _, meta = ckpt2.load(target={"w": jnp.zeros((32, 32))})
            assert meta["step"] == 9
        finally:
            ckpt2.close()
        spans = _spans(recorder, "ckpt.")
        persist = [s for s in spans if s["name"] == "ckpt.persist"][0]
        assert persist["args"]["reason"] == "save"
        kids = {s["name"]: s for s in spans
                if s.get("psid") == persist["sid"]}
        assert set(kids) == {"ckpt.persist.lock_wait",
                             "ckpt.persist.write", "ckpt.persist.commit"}
        assert kids["ckpt.persist.write"]["args"]["bytes"] > 0
        assert kids["ckpt.persist.commit"]["args"]["ok"] is True
        load = [s for s in spans if s["name"] == "ckpt.load"][-1]
        assert load["args"]["source"] == "storage"
        assert [s for s in spans if s["name"] == "ckpt.load.storage_read"
                and s.get("psid") == load["sid"]]


class TestAgentSaverSpans:
    def test_breakpoint_persist_in_the_agent_process(self, tmp_path,
                                                     monkeypatch,
                                                     recorder):
        import jax.numpy as jnp

        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        job = f"obs-agent-{os.getpid()}"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)
        saver.start()
        try:
            ckpt = FlashCheckpointer(str(tmp_path), job_name=job)
            ckpt.save({"w": jnp.full((8, 8), 1.5)}, meta={"step": 8})
            with obs.span("agent.stop_workers", "agent") as stop:
                saver.save_shm_to_storage("test-breakpoint")
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not _spans(
                    recorder, "ckpt.persist.commit"):
                time.sleep(0.1)
            ckpt.close()
        finally:
            saver.stop()
            _unlink_arena(job)
        spans = _spans(recorder, "ckpt.persist")
        persist = [s for s in spans if s["name"] == "ckpt.persist"][0]
        assert persist["psid"] == stop.sid
        assert persist["args"]["reason"] == "breakpoint"
        assert persist["args"]["step"] == 8
        kids = {s["name"] for s in spans
                if s.get("psid") == persist["sid"]}
        # the commit runs on a pool thread and still names its persist
        assert kids == {"ckpt.persist.lock_wait", "ckpt.persist.write",
                        "ckpt.persist.commit"}


class TestHostCost:
    """``obs.span(..., host=True)``: the process's CPU seconds and page
    faults between the span's ends."""

    def test_host_adds_cpu_seconds_of_the_whole_process(self, recorder,
                                                        monkeypatch):
        from dlrover_tpu.obs import recorder as rec_mod

        with obs.span("t.copy", "t", host=True, bytes=8):
            burnt = time.process_time() + 0.02
            while time.process_time() < burnt:  # the host is busy
                pass
        with obs.span("t.wait", "t", host=True):
            time.sleep(0.01)  # the process sleeps, as on a DMA
        # what the span does with two readings, on readings the test owns:
        # the machine's other load is in neither
        readings = iter([(1.0, 10, 1), (1.25, 14, 1)])
        monkeypatch.setattr(rec_mod, "_host_cost", lambda: next(readings))
        with obs.span("t.owned", "t", host=True):
            pass
        by = {s["name"]: s["args"] for s in _spans(recorder, "t.")}
        copy, wait = by["t.copy"], by["t.wait"]
        assert copy["bytes"] == 8
        # the process's own CPU clock, read at both ends: no less than the
        # loop burnt by that clock, and no sign the other way
        assert copy["cpu_s"] >= 0.02 and wait["cpu_s"] >= 0
        assert by["t.owned"] == {"cpu_s": 0.25, "minflt": 4, "majflt": 0}
        # the fault counts come together, and only where the platform
        # counts them at all
        for args in (copy, wait):
            assert ("minflt" in args) == ("majflt" in args)
            assert set(args) <= {"bytes", *HOST_ARGS}

    def test_fault_counts_are_left_out_where_the_platform_counts_none(
            self, recorder, monkeypatch):
        from dlrover_tpu.obs import recorder as rec_mod

        monkeypatch.setattr(rec_mod, "_host_cost",
                            lambda: (time.process_time(), 0, 0))
        with obs.span("t.nofaults", "t", host=True):
            pass
        (rec,) = _spans(recorder, "t.nofaults")
        assert set(rec["args"]) == {"cpu_s"}

    def test_a_span_without_it_is_recorded_as_before(self, recorder,
                                                      monkeypatch):
        from dlrover_tpu.obs import recorder as rec_mod

        def never():
            raise AssertionError("a plain span read the host's cost")

        monkeypatch.setattr(rec_mod, "_host_cost", never)
        with obs.span("t.plain", "t", n=1):
            pass
        with obs.span("t.bare", "t"):
            pass
        by = {s["name"]: s for s in _spans(recorder, "t.")}
        assert by["t.plain"]["args"] == {"n": 1}
        # byte for byte the record a span has always left
        assert list(by["t.plain"]) == [
            "k", "name", "cat", "ts", "dur", "tid", "sid", "args", "seq"]
        assert list(by["t.bare"]) == [
            "k", "name", "cat", "ts", "dur", "tid", "sid", "seq"]


class TestSaveCopySpans:
    """Under ``ckpt.save.d2h``: the issue of the copies and the walk that
    waits for them, and the host's cost on the four spans whose seconds
    are bytes moving."""

    @pytest.fixture
    def spans(self, tmp_path, monkeypatch, recorder):
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        job = f"obs-copy-{os.getpid()}"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        ckpt = FlashCheckpointer(str(tmp_path / "ckpt"), job_name=job)
        state = {"w": [jnp.ones((n, 64)) for n in (8, 64, 2, 32, 16)],
                 "host": np.arange(4), "count": jnp.array(3)}
        try:
            ckpt.save(state, meta={"step": 1}, storage=True)
            assert ckpt.wait(timeout=60)
            ckpt.load(target=state)
        finally:
            ckpt.close()
            _unlink_arena(job)
        return _spans(recorder, "ckpt.")

    def test_d2h_has_two_children_that_sum_to_it(self, spans):
        (d2h,) = [s for s in spans if s["name"] == "ckpt.save.d2h"]
        kids = {s["name"]: s for s in spans if s.get("psid") == d2h["sid"]}
        assert set(kids) == {"ckpt.save.d2h.issue", "ckpt.save.d2h.fetch"}
        assert all(_inside(k, d2h) for k in kids.values())
        issue, fetch = kids["ckpt.save.d2h.issue"], kids["ckpt.save.d2h.fetch"]
        assert issue["ts"] + issue["dur"] <= fetch["ts"] + 0.2
        # within a millisecond (the spans are in microseconds)
        assert 0 <= d2h["dur"] - issue["dur"] - fetch["dur"] < 1000
        assert "args" not in issue

    def test_the_walk_says_where_it_waited(self, spans):
        (fetch,) = [s for s in spans if s["name"] == "ckpt.save.d2h.fetch"]
        args = fetch["args"]
        assert args["leaves"] == 6  # the device leaves; "host" is none
        assert [n for n, _ in args["largest"]] == [
            64 * 64 * 4, 32 * 64 * 4, 16 * 64 * 4]  # three, largest first
        assert all(0 <= secs <= fetch["dur"] * 1e-6
                   for _, secs in args["largest"])
        assert 0 <= args["first_leaf_s"] <= fetch["dur"] * 1e-6
        assert (sum(secs for _, secs in args["largest"]) - 1e-5
                <= args["asarray_s"] <= fetch["dur"] * 1e-6)
        json.dumps(args)

    @pytest.mark.parametrize("name", [
        "ckpt.save.d2h", "ckpt.save.arena_write", "ckpt.load.device_put",
        "ckpt.persist.write"])
    def test_spans_whose_seconds_are_bytes_moving_carry_the_hosts_cost(
            self, spans, name):
        found = [s for s in spans if s["name"] == name]
        assert found
        for s in found:
            assert s["args"]["cpu_s"] >= 0
        others = [s for s in spans if s["name"] not in (
            "ckpt.save.d2h", "ckpt.save.arena_write",
            "ckpt.load.device_put", "ckpt.persist.write")]
        assert others and not [
            s for s in others if "cpu_s" in (s.get("args") or {})]

    def test_flatten_stamps_only_when_asked(self):
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint import tree_utils

        state = {"a": jnp.ones((4, 4)), "b": np.ones(3)}
        fetched = []
        stamped = tree_utils.flatten_to_shards(state, fetched)
        plain = tree_utils.flatten_to_shards(state)
        assert [(n, t1 >= t0) for n, t0, t1 in fetched] == [(64, True)]
        assert stamped[1] == plain[1]
        assert all((stamped[0][k] == plain[0][k]).all() for k in plain[0])


class FakeWorker:
    """Polls as alive until turn ``exits_on`` of the agent's loop, then
    as exited with ``code``."""

    def __init__(self, exits_on=None, code=1, local_rank=0,
                 on_poll=lambda: None):
        self.local_rank, self.exits_on, self.code = local_rank, exits_on, code
        self.polls, self.on_poll = 0, on_poll

    def poll(self):
        self.polls += 1
        self.on_poll()
        if self.exits_on is not None and self.polls >= self.exits_on:
            return self.code
        return None


class FakeClient:
    """``num_nodes_waiting`` answers from ``waiting`` turn by turn (the
    last answer for ever; an exception is raised), ``delay`` seconds
    late."""

    def __init__(self, waiting=(0,), delay=0.0):
        self.waiting, self.delay, self.calls = list(waiting), delay, 0

    def num_nodes_waiting(self, name):
        self.calls += 1
        time.sleep(self.delay)
        answer = self.waiting[min(self.calls, len(self.waiting)) - 1]
        if isinstance(answer, Exception):
            raise answer
        return answer


class OwnedClock:
    """Stands in for the ``time`` module where the code under test reads
    it: ``monotonic`` is the test's and ``sleep`` moves it and returns at
    once, so what a span says of its seconds is arithmetic and the
    machine's load is in none of it.  The rest is the real module's."""

    def __init__(self):
        self.now = time.monotonic()

    def monotonic(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


def _agent(monkeypatch, workers, client=None, interval=0.05):
    from dlrover_tpu.agent import config_tuner
    from dlrover_tpu.agent.training import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )

    # the tuner's constructor exports its path; not this test's to leave
    monkeypatch.setenv(config_tuner.CONFIG_PATH_ENV, "")
    agent = ElasticTrainingAgent(
        ElasticLaunchConfig(monitor_interval=interval), ["true"], "",
        client=client or FakeClient())
    agent._workers = workers
    return agent


class TestAgentMonitorSpan:
    """``agent.monitor``: one durable span a call of ``_monitor``, whose
    ``args`` say what the loop did with its time."""

    INTERVAL = 0.05

    def _monitor(self, recorder, agent):
        result = agent._monitor()
        (rec,) = _spans(recorder, "agent.monitor")
        assert rec["args"]["result"] == result
        assert rec["args"]["interval"] == agent.config.monitor_interval
        return rec, rec["args"], rec["dur"] * 1e-6

    @pytest.mark.parametrize("turn", [1, 3])
    def test_a_worker_that_exits_nonzero_on_turn_n(self, recorder,
                                                   monkeypatch, turn):
        from dlrover_tpu.agent import training
        from dlrover_tpu.obs import recorder as rec_mod

        clock, poll = OwnedClock(), 0.002
        monkeypatch.setattr(training, "time", clock)
        monkeypatch.setattr(rec_mod, "time", clock)
        agent = _agent(monkeypatch, [FakeWorker(
            exits_on=turn, code=-9, on_poll=lambda: clock.sleep(poll))])
        _, args, dur = self._monitor(recorder, agent)
        assert args["result"] == "failed" and args["turns"] == turn
        assert agent._last_failures == [(0, -9)]
        # on the test's clock a turn is its sleep and a poll of ``poll``
        # seconds, and the master answers in no time
        a_turn = self.INTERVAL + poll

        def near(seconds):  # the recorder rounds to a microsecond
            return pytest.approx(seconds, abs=1e-6)

        assert dur == near(turn * a_turn)
        # from the last clean pass (the entry, on turn 1) to the pass that
        # saw the exit code: one sleep and the poll after it
        assert args["unseen_s"] == near(a_turn)
        parts = args["sleep_s"] + args["poll_s"] + args["rpc_s"]
        assert parts == near(dur)
        assert args["sleep_s"] == near(turn * self.INTERVAL)
        assert args["poll_s"] == near(turn * poll)
        assert args["rpc_s"] == 0.0
        assert args["turn_max_s"] == near(a_turn)
        assert args["busy_max_s"] == near(poll)
        assert args["rpc_errors"] == 0
        # the last turns (at most four), each [sleep, poll, rpc]; the one
        # that saw the failure never asked the master
        assert args["last_turns"] == [
            near([self.INTERVAL, poll, 0.0])] * min(turn, 4)

    def test_last_turns_keeps_four(self, recorder, monkeypatch):
        agent = _agent(monkeypatch, [FakeWorker(exits_on=6)], interval=0.01)
        _, args, _ = self._monitor(recorder, agent)
        assert args["turns"] == 6 and len(args["last_turns"]) == 4

    def test_a_slow_question_to_the_master_shows(self, recorder,
                                                 monkeypatch):
        client = FakeClient(delay=0.08)
        agent = _agent(monkeypatch, [FakeWorker(exits_on=3)], client)
        _, args, dur = self._monitor(recorder, agent)
        assert client.calls == 2 and args["turns"] == 3
        assert args["rpc_s"] >= 0.16 and args["busy_max_s"] >= 0.08
        assert args["turn_max_s"] >= self.INTERVAL + 0.08
        # the loop was away from its polls for the RPC too
        assert args["unseen_s"] >= self.INTERVAL + 0.08
        assert args["unseen_s"] <= dur

    def test_a_failed_question_is_counted_and_the_loop_goes_on(
            self, recorder, monkeypatch):
        client = FakeClient(waiting=[RuntimeError("master away"), 0])
        agent = _agent(monkeypatch, [FakeWorker(exits_on=3)], client)
        _, args, _ = self._monitor(recorder, agent)
        assert args["result"] == "failed" and args["rpc_errors"] == 1

    def test_success_closes_the_span(self, recorder, monkeypatch):
        agent = _agent(monkeypatch, [FakeWorker(exits_on=2, code=0)])
        _, args, dur = self._monitor(recorder, agent)
        assert args["result"] == "succeeded" and args["turns"] == 2
        assert "unseen_s" not in args  # nothing failed
        assert (args["sleep_s"] + args["poll_s"] + args["rpc_s"]
                == pytest.approx(dur, abs=1e-3))

    def test_membership_change_closes_the_span(self, recorder, monkeypatch):
        agent = _agent(monkeypatch, [FakeWorker()], FakeClient([0, 1]))
        _, args, _ = self._monitor(recorder, agent)
        assert args["result"] == "membership_changed"
        assert args["turns"] == 2 and "unseen_s" not in args
        assert args["last_turns"][-1][2] > 0  # it asked, and was told

    def test_a_pushed_action_closes_the_span_before_any_poll(
            self, recorder, monkeypatch):
        from dlrover_tpu.common.constants import DiagnosisActionType

        worker = FakeWorker()
        agent = _agent(monkeypatch, [worker])
        agent._pending_action = DiagnosisActionType.RESTART_WORKER
        _, args, _ = self._monitor(recorder, agent)
        assert args["result"] == "restart_requested" and worker.polls == 0
        assert args["turns"] == 1
        assert args["last_turns"][0][1:] == [0.0, 0.0]

    def test_the_span_is_on_disk_when_the_agent_is_killed(self, tmp_path):
        res = _run_python(
            "import os, signal\n"
            "from unittest import mock\n"
            "from dlrover_tpu.agent.training import (\n"
            "    ElasticLaunchConfig, ElasticTrainingAgent, WorkerProcess)\n"
            "client = mock.Mock()\n"
            "client.num_nodes_waiting.return_value = 0\n"
            "agent = ElasticTrainingAgent(\n"
            "    ElasticLaunchConfig(monitor_interval=0.02), ['true'], '',\n"
            "    client=client)\n"
            "proc = mock.Mock()\n"
            "proc.poll.side_effect = [None, -9]\n"
            "agent._workers = [WorkerProcess(0, proc)]\n"
            "assert agent._monitor() == 'failed'\n"
            "os.kill(os.getpid(), signal.SIGKILL)\n",
            DLROVER_TPU_OBS_DIR=str(tmp_path),
            DLROVER_TPU_OBS_PROCESS="agent-n0")
        assert res.returncode == -signal.SIGKILL, res.stderr
        (dump,) = load_dir(str(tmp_path))
        assert dump["meta"]["reason"] == "journal"  # no hook ever ran
        (rec,) = [e for e in dump["events"]
                  if e.get("name") == "agent.monitor"]
        assert rec["args"]["result"] == "failed"
        assert rec["args"]["turns"] == 2 and rec["args"]["unseen_s"] > 0


class TestRestartIsOneTree:
    """The agent hands its worker the ``sid`` of the span that starts it;
    the worker's bootstrap names it."""

    def test_spawn_hands_the_worker_the_open_spans_id(
            self, recorder, monkeypatch, tmp_path):
        out = tmp_path / "parent.txt"
        agent = _agent(monkeypatch, [])
        agent.entrypoint = [
            sys.executable, "-c",
            "import os; open(%r, 'w').write(os.environ.get("
            "'DLROVER_TPU_OBS_PARENT', 'unset') + ' ' + "
            "os.environ['DLROVER_TPU_OBS_PROCESS'])" % str(out)]
        agent._start_workers({
            "round": 1, "my_rank": 0, "coordinator": "localhost:1",
            "num_processes": 1,
            "world": {0: {"node_id": 0, "process_id_base": 0,
                          "local_world_size": 1}}})
        try:
            assert agent._workers[0].proc.wait(timeout=60) == 0
        finally:
            agent._stop_workers("test over", grace=1.0)
        (start,) = _spans(recorder, "agent.start_workers")
        assert out.read_text() == f"{start['sid']} worker-r0-i0"

    def test_the_worker_journals_it_and_its_init_names_it(self, tmp_path):
        res = _run_python(
            "import dlrover_tpu.trainer as t\n"
            "t.init(connect_master=False)\n",
            DLROVER_TPU_OBS_DIR=str(tmp_path),
            DLROVER_TPU_OBS_PROCESS="worker-r0-i1",
            DLROVER_TPU_OBS_PARENT="feedfacefeedface",
            DLROVER_TPU_RESTART_COUNT="1")
        assert res.returncode == 0, res.stderr
        (dump,) = load_dir(str(tmp_path))
        by = {e.get("name") or e.get("kind"): e for e in dump["events"]}
        assert by["bootstrap.process_start"]["psid"] == "feedfacefeedface"
        assert by["bootstrap.init"]["psid"] == "feedfacefeedface"

    def test_a_worker_nobody_started_names_no_parent(self, tmp_path):
        res = _run_python(
            "import dlrover_tpu.trainer as t\n"
            "t.init(connect_master=False)\n",
            DLROVER_TPU_OBS_DIR=str(tmp_path),
            DLROVER_TPU_OBS_PROCESS="worker-r0-i0")
        assert res.returncode == 0, res.stderr
        (dump,) = load_dir(str(tmp_path))
        by = {e.get("name") or e.get("kind"): e for e in dump["events"]}
        assert "psid" not in by["bootstrap.process_start"]
        assert "psid" not in by["bootstrap.init"]


def write_restart_journal(out_dir, unseen_s=1.0, monitor_result="failed"):
    """A two-process journal of one restart on a clock of round seconds,
    written by the recorder itself (``benchmark/tests/
    test_restart_readers.py`` reads the same one): the agent's watch ends
    at 110 having seen the failure at most ``unseen_s`` late; the restart
    runs 110-120 with a persist 111-117 inside its stop; the new worker's
    interpreter starts at 119.5, inside ``agent.start_workers``; its spans
    overlap (build 131-140 under an outer 130-141) and leave holes
    (124-125, 128-130, 141-142, 150-151)."""
    from dlrover_tpu.obs import FlightRecorder

    agent = FlightRecorder(process="agent-n0", out_dir=str(out_dir))
    worker = FlightRecorder(process="worker-r0-i1", out_dir=str(out_dir))
    # both files would be named by this process's pid
    agent.dump_path = lambda: os.path.join(str(out_dir),
                                           "flight-agent-n0-1.jsonl")
    worker.dump_path = lambda: os.path.join(str(out_dir),
                                            "flight-worker-r0-i1-2.jsonl")

    def span(rec, name, start, end, sid="", parent="", **args):
        return rec.span(name, name.split(".")[0], start, end,
                        span_id=sid or None, parent=parent,
                        args=args or None, durable=True)

    watch_args = {"result": monitor_result, "turns": 10, "sleep_s": 9.9,
                  "poll_s": 0.05, "rpc_s": 0.05, "busy_max_s": 0.02}
    if monitor_result == "failed":
        watch_args["unseen_s"] = unseen_s
    span(agent, "agent.monitor", 100.0, 110.0, **watch_args)
    span(agent, "ckpt.persist.write", 112.0, 116.0, parent="persist")
    span(agent, "ckpt.persist", 111.0, 117.0, sid="persist",
         parent="stop", reason="breakpoint")
    span(agent, "agent.stop_workers", 110.5, 118.0, sid="stop",
         parent="restart")
    span(agent, "agent.rendezvous", 118.0, 119.0, parent="restart")
    span(agent, "agent.start_workers", 119.0, 120.0, sid="spawn",
         parent="restart")
    span(agent, "agent.restart", 110.0, 120.0, sid="restart",
         reason=monitor_result)
    # the next watch, on the new workers, which succeed
    span(agent, "agent.monitor", 120.0, 160.0, result="succeeded", turns=40)
    worker._clock = lambda: 123.0
    worker.event("bootstrap.process_start", durable=True,
                 since_process_start_s=3.5, psid="spawn", restart_count=1)
    span(worker, "bootstrap.init", 123.0, 124.0, parent="spawn")
    span(worker, "bootstrap.backend_init", 125.0, 128.0, first=True)
    span(worker, "accelerate.compile", 132.0, 139.0, parent="build")
    span(worker, "accelerate.build", 131.0, 140.0, sid="build")
    span(worker, "user.outer", 130.0, 141.0)
    span(worker, "accelerate.create_state", 142.0, 144.0)
    span(worker, "ckpt.load", 144.0, 150.0, source="shm")
    span(worker, "accelerate.first_call", 151.0, 152.0)
    span(worker, "accelerate.first_call", 170.0, 171.0)  # a later program
    agent.close()
    worker.close()


class TestRestartAccounts:
    def test_parts_and_the_unspanned_rest(self, tmp_path):
        from dlrover_tpu.obs import postmortem

        write_restart_journal(tmp_path)
        (acct,) = postmortem.restart_accounts(load_dir(str(tmp_path)))
        assert (acct["agent"], acct["worker"]) == ("agent-n0", "worker-r0-i1")
        assert acct["result"] == "failed"
        # from 110 - 1 (the first moment the agent could have known) to
        # the end of the first first_call, 152
        assert acct["interval_s"] == pytest.approx(43.0)
        assert acct["parts"] == pytest.approx({
            "unseen": 1.0, "ckpt.persist": 6.0,
            "agent.restart": 4.0,  # 10 less the persist
            "interpreter": 3.5, "bootstrap.init": 1.0,
            "bootstrap.backend_init": 3.0, "accelerate.build": 9.0,
            "accelerate.create_state": 2.0, "ckpt.load": 6.0,
            "accelerate.first_call": 1.0})
        # the union covers 109-124 (watch, restart, the interpreter from
        # 119.5 on, init), 125-128, 130-141, 142-150, 151-152: holes
        # 124-125, 128-130, 141-142, 150-151
        assert acct["unspanned_s"] == pytest.approx(5.0)
        # the three longest, in order: seconds into the interval, seconds,
        # and the span that ended last before each
        assert acct["holes"] == [
            [15.0, 1.0, "bootstrap.init"],
            [19.0, 2.0, "bootstrap.backend_init"],
            [32.0, 1.0, "user.outer"]]

    def test_unseen_moves_the_start_and_nothing_else(self, tmp_path):
        from dlrover_tpu.obs import postmortem

        write_restart_journal(tmp_path, unseen_s=0.25)
        (acct,) = postmortem.restart_accounts(load_dir(str(tmp_path)))
        assert acct["interval_s"] == pytest.approx(42.25)
        assert acct["parts"]["unseen"] == pytest.approx(0.25)
        assert acct["unspanned_s"] == pytest.approx(5.0)

    def test_a_membership_change_has_its_account_too(self, tmp_path):
        from dlrover_tpu.obs import postmortem

        write_restart_journal(tmp_path, monitor_result="membership_changed")
        (acct,) = postmortem.restart_accounts(load_dir(str(tmp_path)))
        assert acct["result"] == "membership_changed"
        assert acct["parts"]["unseen"] == 0.0
        assert acct["interval_s"] == pytest.approx(42.0)

    def test_gaps_of_overlapping_and_nested_intervals(self):
        from dlrover_tpu.obs.postmortem import _gaps

        spans = [(0, 10), (2, 5), (8, 14), (20, 30), (25, 27), (40, 60)]
        assert _gaps(spans, 1, 50) == [(14, 20), (30, 40)]
        assert _gaps(spans, 12, 35) == [(14, 20), (30, 35)]
        assert _gaps([], 1, 50) == [(1, 50)]
        assert _gaps([(5, 6)], 1, 50) == [(1, 5), (6, 50)]
        assert _gaps([(60, 70)], 1, 50) == [(1, 50)]

    def test_no_restart_no_account(self, tmp_path):
        from dlrover_tpu.obs import FlightRecorder, postmortem

        rec = FlightRecorder(process="agent-n0", out_dir=str(tmp_path))
        rec.span("agent.monitor", "agent", 1.0, 5.0, durable=True,
                 args={"result": "succeeded", "turns": 4})
        # a failure the agent gave up on: a restart that started no worker
        rec.span("agent.monitor", "agent", 6.0, 9.0, durable=True,
                 args={"result": "failed", "turns": 3, "unseen_s": 1.0})
        rec.span("agent.restart", "agent", 9.0, 9.5, durable=True,
                 args={"gave_up": True})
        rec.close()
        assert postmortem.restart_accounts(load_dir(str(tmp_path))) == []

    def test_the_cli_prints_one_line_a_restart(self, tmp_path, capsys):
        from dlrover_tpu.obs import postmortem

        write_restart_journal(tmp_path)
        assert postmortem.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        (line,) = [ln for ln in out.splitlines() if "->" in ln
                   and "worker-r0-i1" in ln]
        assert line.strip() == (
            "agent-n0 -> worker-r0-i1 (failed): 43.000 to the first step = "
            "unseen <= 1.000 + ckpt.persist 6.000 + agent.restart 4.000 + "
            "interpreter 3.500 + bootstrap.init 1.000 + "
            "bootstrap.backend_init 3.000 + accelerate.build 9.000 + "
            "accelerate.create_state 2.000 + ckpt.load 6.000 + "
            "accelerate.first_call 1.000; unspanned 5.000, 1.000 after "
            "bootstrap.init, 2.000 after bootstrap.backend_init, 1.000 "
            "after user.outer")
        assert "what each restart cost" in out
        # a serving fleet's report has no such section
        assert postmortem.render(dict(
            postmortem.analyze(str(tmp_path)), restarts=[])).count(
                "restart cost") == 0


class TestBuildSpansAndScopes:
    @pytest.fixture(scope="class")
    def built(self):
        import jax
        import optax

        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel.accelerate import Strategy, accelerate
        from dlrover_tpu.parallel.mesh import MeshSpec

        rec = obs.configure()
        cfg = llama.LlamaConfig.tiny(vocab_size=4096, remat_block=True)
        job = accelerate(
            loss_fn=functools.partial(llama.loss_fn, cfg=cfg),
            init_fn=functools.partial(llama.init_params, cfg=cfg),
            optimizer=optax.adamw(1e-3),
            sample_batch={"tokens": np.zeros((2, 33), np.int32)},
            strategy=Strategy(mesh=MeshSpec(dp=1)),
            devices=jax.devices()[:1])
        state = job.create_state(jax.random.PRNGKey(0))
        batch = {"tokens": jax.device_put(
            np.zeros((2, 33), np.int32), job.batch_sharding["tokens"])}
        state, _ = job.train_step(state, batch)
        job.train_step(state, batch)
        evs, _, _ = rec.snapshot()
        obs.reset()
        return job, evs

    def test_build_has_its_children_and_one_first_call(self, built):
        _, evs = built
        spans = {e["name"]: e for e in evs if e["k"] == "span"}
        build = spans["accelerate.build"]
        for child in ("accelerate.lower", "accelerate.compile",
                      "accelerate.analyze"):
            assert spans[child]["psid"] == build["sid"], child
            assert _inside(spans[child], build)
        assert "cache_hit" in spans["accelerate.compile"]["args"]
        assert "strategy" in build["args"]
        firsts = [e for e in evs if e.get("name") == "accelerate.first_call"]
        assert len(firsts) == 1  # the second train_step call records none
        assert "psid" not in firsts[0]

    def test_create_state_has_its_span_and_its_init_is_its_child(
            self, built):
        _, evs = built
        (made,) = [e for e in evs
                   if e.get("name") == "accelerate.create_state"]
        # fp32 params + two AdamW moments, from the shapes
        assert made["args"]["bytes"] > 3 * 4 * 4096 * 64
        assert made["args"]["frozen"] == "none"
        assert "psid" not in made
        kids = [e for e in evs if e.get("psid") == made["sid"]]
        compiled = [k for k in kids if k["name"] == "jax.compile"]
        assert [k["args"]["fun_name"] for k in compiled] == ["jit(mk)"]
        assert {"jax.trace", "jax.lower"} <= {k["name"] for k in kids}
        assert all(_inside(k, made) for k in kids)

    def test_compile_and_first_call_say_what_their_compiles_said(
            self, built):
        """``cache_hit`` of ``accelerate.compile`` and ``.first_call``
        comes from the ``jax.compile`` spans under them: one listener."""
        _, evs = built
        for name in ("accelerate.compile", "accelerate.first_call"):
            (sp,) = [e for e in evs if e.get("name") == name]
            verdicts = [e["args"]["cache_hit"] for e in evs
                        if e.get("name") == "jax.compile"
                        and e.get("psid") == sp["sid"]]
            # the first call's executable may come from JAX's in-memory
            # cache of compilations: no compile, no verdict, None
            assert verdicts or name == "accelerate.first_call"
            asked = [v for v in verdicts if v is not None]
            assert sp["args"]["cache_hit"] == (
                all(asked) if asked else None), name

    def test_program_event_carries_the_scope_table(self, built):
        job, evs = built
        (ev,) = [e for e in evs if e.get("kind") == "accelerate.program"]
        assert ev["scopes"] == job.program["scopes"]
        assert set(ev) >= {"kernels", "collectives", "strategy"}
        json.dumps(ev)  # the journal's line

    def test_scope_table_names_phase_and_scope(self, built):
        job, _ = built
        table = job.program["scopes"]
        verdicts = {tuple(v) for v in table.values()}
        # No ("backward", "lm_head_loss"): the reduced lm-head loss forms
        # dx and dw in its forward scan, and what its backward rule adds
        # (a scaling by the cotangent, 1.0 here) XLA folds away — the
        # head's gradient matmuls read phase "forward".
        for want in (("forward", "lm_head_loss"),
                     ("backward", "embed"),
                     ("optimizer", "optimizer"),
                     ("forward", "attention"), ("backward", "attention"),
                     ("recompute", "attention"), ("backward", "mlp"),
                     ("forward", "embed"), ("other", "grad_norm")):
            assert want in verdicts, want
        assert {p for p, _ in verdicts} <= {
            "forward", "backward", "recompute", "optimizer", "other"}

    @pytest.mark.parametrize("op_name,want", [
        ("jit(train_step)/jvp(attention)/dot_general",
         ["forward", "attention"]),
        ("jit(train_step)/transpose(jvp(lm_head_loss))/while/body/"
         "closed_call/dot_general", ["backward", "lm_head_loss"]),
        ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
         "rematted_computation/mlp/jit(silu)/logistic",
         ["recompute", "mlp"]),
        ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/attention/"
         "bhqk,bhkd->bhqd/dot_general", ["backward", "attention"]),
        ("jit(train_step)/optimizer/jit(_where)/select_n",
         ["optimizer", "optimizer"]),
        ("jit(train_step)/grad_norm/reduce_sum", ["other", "grad_norm"]),
        ("jit(train_step)/add", None),
        ("reduce_sum", None),
    ])
    def test_phase_and_scope_of_an_op_name(self, op_name, want):
        from dlrover_tpu.parallel.accelerate import phase_and_scope

        assert phase_and_scope(op_name) == want

    def test_scope_table_of_a_text(self):
        from dlrover_tpu.parallel.accelerate import scope_table

        text = """\
HloModule jit_train_step

%fused_computation (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(train_step)/optimizer/mul"}
}

%body (arg: f32[4]) -> f32[4] {
  %arg = f32[4]{0} parameter(0)
  ROOT %dot.7 = f32[4]{0} add(%arg, %arg), metadata={op_name="jit(train_step)/transpose(jvp(lm_head_loss))/while/body/closed_call/dot_general"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  %copy.2 = f32[4]{0} copy(%fusion.3)
  %loose.1 = f32[4]{0} negate(%a)
  ROOT %while.4 = f32[4]{0} while(%copy.2), body=%body, metadata={op_name="jit(train_step)/transpose(jvp(lm_head_loss))/while"}
}
"""
        assert scope_table(text) == {
            "fusion.3": ["optimizer", "optimizer"],  # voted by its body
            "copy.2": ["optimizer", "optimizer"],  # its operand's
            "dot.7": ["backward", "lm_head_loss"],
            "while.4": ["backward", "lm_head_loss"],
        }

    def test_program_summary_of_a_remat_that_keeps_the_kernels_outputs(self):
        """The op_names of two block applications as the chip's compiler
        writes them (``tests/test_aot_compile.py`` compiles the real
        thing) where block remat keeps the flash kernel's output and
        log-sum-exp: no ``flash_fwd`` sits in ``rematted_computation``, and
        ``kernels.flash_fwd == block_applications`` — the condition under
        which ``step.recompute_share_pct``'s reader adds nothing for the
        kernel (``tests/test_llama_looped.py`` has the remat that runs it
        twice)."""
        from dlrover_tpu.parallel.accelerate import (
            phase_and_scope,
            program_summary,
        )

        call = ('  %k.{n} = bf16[8] custom-call(%a), custom_call_target='
                '"tpu_custom_call", metadata={{op_name="jit(train_step)/'
                '{path}/pallas_call"}}')
        paths = ["jvp(attention)/flash_fwd"] * 2 + [
            "transpose(jvp(jvp()))/checkpoint/attention/" + bwd
            for bwd in ("flash_bwd_dq", "flash_bwd_dkv") * 2]
        got = program_summary("\n".join(
            call.format(n=n, path=path) for n, path in enumerate(paths)))
        assert got["kernels"] == {
            "flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}
        assert got["block_applications"] == got["kernels"]["flash_fwd"]
        assert [phase_and_scope(f"jit(train_step)/{path}/pallas_call")[0]
                for path in paths] == ["forward"] * 2 + ["backward"] * 4


class TestJaxStageSpans:
    """JAX's own trace, lower and compile events as spans
    (``common/jax_env.py::install_compile_listener``), in a process of
    its own with a fresh persistent cache."""

    CODE = (
        "import json, jax, jax.numpy as jnp\n"
        "from dlrover_tpu import obs\n"
        "from dlrover_tpu.common import jax_env\n"
        "assert jax_env.enable_compilation_cache()\n"
        "jax_env.device_summary()\n"
        "jax_env.install_compile_listener()\n"  # a third time
        "@jax.jit\n"
        "def stepfn(x):\n"
        "    for i in range(60):\n"
        "        x = jnp.tanh(x * (i + 1.5)) + jnp.cumsum(x)\n"
        "    return x\n"
        "x = jnp.ones(8)\n"
        "jax.block_until_ready(x)\n"
        "with obs.span('outer', 'ut'):\n"
        "    stepfn(x)\n"
        "jax.clear_caches()\n"
        "stepfn(x)\n"
        "before = obs.get_recorder().stats()['spans']\n"
        "for _ in range(10):\n"
        "    x = stepfn(x)\n"
        "jax.block_until_ready(x)\n"
        "fired = obs.get_recorder().stats()['spans'] - before\n"
        "evs, dropped, _ = obs.get_recorder().snapshot()\n"
        "print(json.dumps({'events': evs, 'dropped': dropped, "
        "'fired_warm': fired}))\n"
    )

    @pytest.fixture(scope="class")
    def ran(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("stages")
        res = _run_python(
            self.CODE, JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
            DLROVER_TPU_COMPILE_CACHE="1",
            DLROVER_TPU_OBS_DIR=str(tmp / "obs"),
            DLROVER_TPU_OBS_PROCESS="ut")
        assert res.returncode == 0, res.stderr[-2000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        (dump,) = load_dir(str(tmp / "obs"))
        return out, dump

    @staticmethod
    def _of(evs, stage, fun):
        return [e for e in evs if e.get("name") == stage
                and e["args"]["fun_name"] in (fun, f"jit({fun})")]

    def test_first_call_records_three_stages_and_a_miss(self, ran):
        evs = ran[0]["events"]
        traced, again = self._of(evs, "jax.trace", "stepfn")
        lowered, _ = self._of(evs, "jax.lower", "stepfn")
        compiled, _ = self._of(evs, "jax.compile", "stepfn")
        assert traced["cat"] == lowered["cat"] == compiled["cat"] == "jax"
        assert compiled["args"]["cache_hit"] is False
        assert "retrieval_s" not in compiled["args"]
        # in the order JAX runs them, none inside another
        assert (traced["ts"] + traced["dur"] <= lowered["ts"] + 0.2
                and lowered["ts"] + lowered["dur"] <= compiled["ts"] + 0.2)

    def test_after_clear_caches_the_compile_is_a_cache_read(self, ran):
        _, hit = self._of(ran[0]["events"], "jax.compile", "stepfn")
        assert hit["args"]["cache_hit"] is True
        assert hit["args"]["retrieval_s"] > 0
        assert "saved_s" in hit["args"]

    def test_a_compile_inside_an_open_span_is_its_child(self, ran):
        evs = ran[0]["events"]
        (outer,) = [e for e in evs if e.get("name") == "outer"]
        for stage in ("jax.trace", "jax.lower", "jax.compile"):
            first, second = self._of(evs, stage, "stepfn")
            assert first["psid"] == outer["sid"], stage
            assert "psid" not in second, stage

    def test_installing_again_records_each_event_once(self, ran):
        """``enable_compilation_cache``, ``device_summary`` and a direct
        call all install: still one span a stage and call."""
        evs = ran[0]["events"]
        for stage in ("jax.trace", "jax.lower", "jax.compile"):
            assert len(self._of(evs, stage, "stepfn")) == 2, stage

    def test_ten_warm_calls_fire_the_listener_zero_times(self, ran):
        assert ran[0]["fired_warm"] == 0
        assert ran[0]["dropped"] == 0

    def test_durations_are_spans_on_the_monotonic_clock(self, ran):
        evs = ran[0]["events"]
        (outer,) = [e for e in evs if e.get("name") == "outer"]
        stages = [e for e in evs if e.get("cat") == "jax"]
        assert stages and all(e["dur"] > 0 for e in stages)
        for e in stages:
            if e.get("psid") == outer["sid"]:
                assert _inside(e, outer), e
        # a trace holds the traces of what it called: they nest
        outer_trace = self._of(evs, "jax.trace", "stepfn")[0]
        nested = [e for e in stages if e["name"] == "jax.trace"
                  and e is not outer_trace and _inside(e, outer_trace)]
        assert all(e["dur"] < outer_trace["dur"] for e in nested)

    def test_short_traces_are_not_recorded(self, ran):
        from dlrover_tpu.common.jax_env import MIN_TRACE_SPAN_S

        traces = [e for e in ran[0]["events"]
                  if e.get("name") == "jax.trace"]
        assert all(e["dur"] >= MIN_TRACE_SPAN_S * 1e6 - 1 for e in traces)

    def test_stage_spans_are_journalled_as_they_end(self, ran):
        out, dump = ran
        on_disk = [e["sid"] for e in dump["events"]
                   if e.get("cat") == "jax"]
        assert on_disk == [e["sid"] for e in out["events"]
                           if e.get("cat") == "jax"]


class TestBootstrapSpans:
    def test_backend_init_and_process_age(self, recorder):
        from dlrover_tpu.common.jax_env import (
            device_summary,
            process_age_s,
        )

        summary = device_summary()
        (span,) = _spans(recorder, "bootstrap.backend_init")
        assert span["args"]["platform"] == summary["platform"] == "cpu"
        assert span["args"]["count"] == summary["count"]
        assert 0 <= process_age_s() < 24 * 3600

    def test_init_journals_the_interpreter_start(self, tmp_path):
        res = _run_python(
            "import dlrover_tpu.trainer as t\n"
            "ctx = t.init(connect_master=False)\n"
            "ctx.report_step(1)\n",
            DLROVER_TPU_OBS_DIR=str(tmp_path),
            DLROVER_TPU_OBS_PROCESS="worker-r0-i1",
            DLROVER_TPU_RESTART_COUNT="1")
        assert res.returncode == 0, res.stderr
        (dump,) = load_dir(str(tmp_path))
        by = {e.get("name") or e.get("kind"): e for e in dump["events"]}
        start = by["bootstrap.process_start"]
        assert start["restart_count"] == 1
        assert 0 < start["since_process_start_s"] < 120
        assert by["bootstrap.init"]["args"]["restart_count"] == 1


class TestLauncherJournalDirectory:
    def test_job_dir_follows_the_temp_dir(self, tmp_path, monkeypatch):
        import tempfile

        monkeypatch.setenv("TMPDIR", str(tmp_path))
        monkeypatch.setattr(tempfile, "tempdir", None)
        assert obs.job_dir("bench-12", "ab12") == str(
            tmp_path / "dlrover_tpu_obs" / "bench-12-ab12")
        assert obs.job_dir("a/b") == str(
            tmp_path / "dlrover_tpu_obs" / "a_b")

    def test_retention_removes_only_what_is_old(self, tmp_path,
                                                 monkeypatch):
        import tempfile

        monkeypatch.setenv("TMPDIR", str(tmp_path))
        monkeypatch.setattr(tempfile, "tempdir", None)
        old, new = obs.job_dir("old", "1"), obs.job_dir("new", "2")
        for d in (old, new):
            os.makedirs(d)
            with open(os.path.join(d, "flight-agent-n0-1.jsonl"), "w"):
                pass
        stale = time.time() - 30 * 86400
        os.utime(old, (stale, stale))
        os.utime(os.path.join(old, "flight-agent-n0-1.jsonl"),
                 (stale, stale))
        obs.gc_job_dirs()
        assert not os.path.exists(old) and os.path.exists(new)
