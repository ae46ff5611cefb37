"""L0 substrate tests: messages, RPC, node model, storage, context."""

import os
import threading

import grpc
import pytest

from dlrover_tpu.common import messages as msgs
from dlrover_tpu.common.constants import NodeStatus
from dlrover_tpu.common.global_context import get_context
from dlrover_tpu.common.node import Node, NodeResource, NodeStatusFlow
from dlrover_tpu.common.rpc import (
    ChaosRpcError,
    RpcClient,
    RpcServer,
    addr_connectable,
)


class TestMessages:
    def test_roundtrip_simple(self):
        m = msgs.JoinRendezvous(node_id=3, node_rank=1, local_world_size=4)
        out = msgs.deserialize(msgs.serialize(m))
        assert out == m

    def test_roundtrip_nested(self):
        hb = msgs.HeartbeatResponse(
            actions=[
                msgs.DiagnosisAction(action_type="restart_worker", reason="hang"),
                msgs.DiagnosisAction(action_type="no_action"),
            ]
        )
        out = msgs.deserialize(msgs.serialize(hb))
        assert isinstance(out, msgs.HeartbeatResponse)
        assert out.actions[0].action_type == "restart_worker"
        assert len(out.actions) == 2

    def test_roundtrip_bytes_and_dict(self):
        m = msgs.KVStoreSet(key="store/rank0", value=b"\x00\x01binary")
        out = msgs.deserialize(msgs.serialize(m))
        assert out.value == b"\x00\x01binary"
        w = msgs.CommWorld(round=2, world={0: {"id": 0}, 1: {"id": 1}})
        out2 = msgs.deserialize(msgs.serialize(w))
        assert out2.world[1]["id"] == 1


class TestRpc:
    def test_server_dispatch_and_retry(self):
        calls = []

        def handler(msg):
            calls.append(msg)
            if isinstance(msg, msgs.TaskRequest):
                return msgs.Task(task_id=7, start=0, end=10)
            return None

        server = RpcServer(0, handler)
        server.start()
        try:
            addr = f"127.0.0.1:{server.port}"
            assert addr_connectable(addr)
            client = RpcClient(addr)
            task = client.call(msgs.TaskRequest(dataset_name="d", worker_id=1))
            assert isinstance(task, msgs.Task)
            assert task.task_id == 7
            # Unknown-handled message -> default success response.
            resp = client.call(msgs.Heartbeat(node_id=1))
            assert isinstance(resp, msgs.BaseResponse) and resp.success
            client.close()
        finally:
            server.stop()
        assert len(calls) == 2

    def test_handler_exception_returns_failure(self):
        def handler(msg):
            raise ValueError("boom")

        server = RpcServer(0, handler)
        server.start()
        try:
            client = RpcClient(f"127.0.0.1:{server.port}")
            resp = client.call(msgs.Heartbeat())
            assert isinstance(resp, msgs.BaseResponse)
            assert not resp.success and "boom" in resp.reason
            client.close()
        finally:
            server.stop()

    def test_concurrent_calls(self):
        lock = threading.Lock()
        count = [0]

        def handler(msg):
            with lock:
                count[0] += 1
            return msgs.KVStoreCount(value=count[0])

        server = RpcServer(0, handler)
        server.start()
        try:
            client = RpcClient(f"127.0.0.1:{server.port}")
            threads = [
                threading.Thread(target=lambda: client.call(msgs.Empty()))
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert count[0] == 8
            client.close()
        finally:
            server.stop()


def _fake_client(responses):
    """An RpcClient whose channel is scripted: each entry in ``responses``
    is either an exception to raise or bytes to return.  No real server."""
    client = RpcClient("127.0.0.1:1")
    attempts = []

    def fake_call(data, timeout=None):
        attempts.append(timeout)
        item = responses.pop(0)
        if isinstance(item, Exception):
            raise item
        return item

    client._call = fake_call
    return client, attempts


class TestRpcRetryPolicy:
    """The retry contract itself, against a scripted channel: UNAVAILABLE
    retried under jittered-bounded backoff, DEADLINE_EXCEEDED only for
    idempotent calls, exhausted retries re-raise the LAST error, and the
    total deadline budget caps the loop."""

    def _unavailable(self):
        return ChaosRpcError(grpc.StatusCode.UNAVAILABLE, "test")

    def _deadline(self):
        return ChaosRpcError(grpc.StatusCode.DEADLINE_EXCEEDED, "test")

    def test_unavailable_retried_with_bounded_backoff(self, monkeypatch):
        ok = msgs.serialize(msgs.BaseResponse(success=True))
        client, attempts = _fake_client(
            [self._unavailable(), self._unavailable(),
             self._unavailable(), ok]
        )
        sleeps = []
        monkeypatch.setattr(
            "dlrover_tpu.common.rpc.time.sleep", sleeps.append
        )
        resp = client.call(msgs.Heartbeat(), retries=5, backoff=0.5)
        assert isinstance(resp, msgs.BaseResponse) and resp.success
        assert len(attempts) == 4
        assert len(sleeps) == 3
        for i, s in enumerate(sleeps):
            base = min(0.5 * (2**i), 8.0)
            # Half-jittered exponential: within [base/2, base], capped.
            assert 0.5 * base <= s <= base

    def test_deadline_exceeded_not_retried(self, monkeypatch):
        client, attempts = _fake_client([self._deadline()])
        monkeypatch.setattr(
            "dlrover_tpu.common.rpc.time.sleep", lambda s: None
        )
        with pytest.raises(grpc.RpcError):
            client.call(msgs.KVStoreSet(key="k", value=b"v"), retries=5)
        assert len(attempts) == 1  # the request may have executed: no resend

    def test_deadline_exceeded_retried_when_idempotent(self, monkeypatch):
        ok = msgs.serialize(msgs.BaseResponse(success=True))
        client, attempts = _fake_client([self._deadline(), ok])
        monkeypatch.setattr(
            "dlrover_tpu.common.rpc.time.sleep", lambda s: None
        )
        resp = client.call(
            msgs.KVStoreGet(key="k"), retries=5, idempotent=True
        )
        assert isinstance(resp, msgs.BaseResponse)
        assert len(attempts) == 2

    def test_exhausted_retries_reraise_last_error(self, monkeypatch):
        errs = [self._unavailable() for _ in range(3)]
        client, attempts = _fake_client(list(errs))
        monkeypatch.setattr(
            "dlrover_tpu.common.rpc.time.sleep", lambda s: None
        )
        with pytest.raises(grpc.RpcError) as ei:
            client.call(msgs.Heartbeat(), retries=3, backoff=0.001)
        assert ei.value is errs[-1]
        assert len(attempts) == 3

    def test_other_codes_raise_immediately(self, monkeypatch):
        err = ChaosRpcError(grpc.StatusCode.INTERNAL, "boom")
        client, attempts = _fake_client([err])
        with pytest.raises(grpc.RpcError):
            client.call(msgs.Heartbeat(), retries=5)
        assert len(attempts) == 1

    def test_deadline_budget_caps_retries(self, monkeypatch):
        """With a tiny total budget the loop stops early even though
        ``retries`` remain — and still raises the transport error."""
        client, attempts = _fake_client(
            [self._unavailable() for _ in range(10)]
        )
        with pytest.raises(grpc.RpcError):
            client.call(
                msgs.Heartbeat(), retries=10, backoff=0.05, deadline=0.08
            )
        assert len(attempts) < 10

    def test_per_attempt_timeout_clamped_to_budget(self):
        ok = msgs.serialize(msgs.BaseResponse(success=True))
        client, attempts = _fake_client([ok])
        client.call(msgs.Heartbeat(), timeout=500.0, deadline=2.0)
        assert attempts[0] <= 2.0

    def test_default_budget_never_shortens_explicit_timeout(self):
        """A caller-configured timeout beyond DEFAULT_DEADLINE must get
        its full window (the default budget stretches to cover it)."""
        ok = msgs.serialize(msgs.BaseResponse(success=True))
        client, attempts = _fake_client([ok])
        client.call(msgs.Heartbeat(), timeout=120.0)
        assert attempts[0] > 60.0


class TestRpcReconnect:
    def test_reconnect_survives_server_restart_on_same_port(self):
        from dlrover_tpu.common.rpc import find_free_port

        port = find_free_port()
        s1 = RpcServer(port, lambda m: msgs.BaseResponse(success=True))
        s1.start()
        client = RpcClient(f"127.0.0.1:{port}")
        try:
            assert client.call(msgs.Heartbeat()).success
            s1.stop(grace=0.1)
            s2 = RpcServer(port, lambda m: msgs.BaseResponse(success=True))
            s2.start()
            try:
                # A rebuilt channel must reach the new incarnation even if
                # the old one is sulking in reconnect backoff.
                client.reconnect(force=True)
                resp = client.call(msgs.Heartbeat(), backoff=0.05)
                assert resp.success
            finally:
                s2.stop()
        finally:
            client.close()


class _VirtualTime:
    """Stands in for the ``time`` module of the code under test: a
    sleep advances the clock by what was asked and is recorded, so a
    deadline test counts sleeps and reads no clock of the host."""

    def __init__(self):
        self.now = 100.0
        self.sleeps = []

    def monotonic(self):
        return self.now

    def sleep(self, dt):
        self.sleeps.append(round(dt, 6))
        self.now += dt


class TestDeadlineClamps:
    def test_addr_connectable_respects_deadline(self, monkeypatch):
        from dlrover_tpu.common import rpc

        vt = _VirtualTime()
        monkeypatch.setattr(rpc, "time", vt)

        def refused(*_a, **_k):  # nothing listens: instant refusal
            raise ConnectionRefusedError

        monkeypatch.setattr(rpc.socket, "create_connection", refused)
        assert not addr_connectable("127.0.0.1:9", timeout=0.6)
        # The old loop slept a fixed 0.5s past the deadline; the clamp
        # cuts the last sleep to what is left of the budget.
        assert vt.sleeps == [0.5, 0.1]

    def test_barrier_poll_clamped(self, monkeypatch):
        from dlrover_tpu.agent import master_client
        from dlrover_tpu.agent.master_client import MasterClient

        vt = _VirtualTime()
        monkeypatch.setattr(master_client, "time", vt)
        client = MasterClient.__new__(MasterClient)
        monkeypatch.setattr(
            client, "join_sync", lambda *a, **k: None, raising=False
        )
        monkeypatch.setattr(
            client, "sync_finished", lambda *a, **k: False, raising=False
        )
        assert client.barrier("b", timeout=0.3) is False
        assert vt.sleeps == [0.2, 0.1]

    def test_kv_wait_get_clamped(self, monkeypatch):
        from dlrover_tpu.agent import master_client
        from dlrover_tpu.agent.master_client import MasterClient

        vt = _VirtualTime()
        monkeypatch.setattr(master_client, "time", vt)
        client = MasterClient.__new__(MasterClient)
        monkeypatch.setattr(
            client, "kv_store_get", lambda *a, **k: None, raising=False
        )
        assert client.kv_store_wait_get("k", timeout=0.3, poll=0.2) is None
        assert vt.sleeps == [0.2, 0.1]


class TestNode:
    def test_status_flow(self):
        n = Node("worker", 0)
        n.update_status(NodeStatus.PENDING)
        n.update_status(NodeStatus.RUNNING)
        assert n.status == NodeStatus.RUNNING
        # Illegal transition ignored.
        n.update_status(NodeStatus.PENDING)
        assert n.status == NodeStatus.RUNNING
        n.update_status(NodeStatus.SUCCEEDED)
        assert n.status == NodeStatus.SUCCEEDED
        assert n.finish_time is not None

    def test_status_flow_rules(self):
        assert NodeStatusFlow.is_allowed(NodeStatus.FAILED, NodeStatus.RUNNING)
        assert not NodeStatusFlow.is_allowed(NodeStatus.DELETED, NodeStatus.RUNNING)
        assert not NodeStatusFlow.is_allowed(NodeStatus.RUNNING, NodeStatus.RUNNING)

    def test_relaunch_accounting(self):
        n = Node("worker", 0, max_relaunch_count=2)
        assert not n.is_unrecoverable_failure()
        n.inc_relaunch_count()
        n.inc_relaunch_count()
        assert n.is_unrecoverable_failure()
        succ = n.get_relaunch_node(new_id=5)
        assert succ.id == 5 and succ.rank_index == n.rank_index
        assert succ.relaunch_count == 3

    def test_resource_parse(self):
        r = NodeResource.resource_str_to_node_resource("cpu=4,memory=8192Mi,tpu=8")
        assert r.cpu == 4 and r.memory_mb == 8192 and r.tpu_chips == 8


class TestStorageAndContext:
    def test_posix_storage(self, tmp_path):
        from dlrover_tpu.common.storage import ClassMeta, PosixDiskStorage

        s = PosixDiskStorage()
        p = str(tmp_path / "a" / "f.bin")
        s.safe_makedirs(str(tmp_path / "a"))
        s.write(b"hello", p)
        assert s.read(p) == b"hello"
        assert s.exists(p)
        assert "f.bin" in s.listdir(str(tmp_path / "a"))
        s.safe_remove(p)
        assert not s.exists(p)
        # ClassMeta round-trip builds the same backend.
        built = ClassMeta().build()
        assert isinstance(built, PosixDiskStorage)

    def test_context_singleton_and_update(self):
        ctx = get_context()
        assert ctx is get_context()
        old = ctx.rdzv_timeout
        ctx.update(rdzv_timeout=123.0)
        assert get_context().rdzv_timeout == 123.0
        ctx.update(rdzv_timeout=old)


class TestPublicAPI:
    def test_every_lazy_export_resolves(self):
        """dt.<name> must import for every advertised top-level symbol
        (regression: a stale module path made dt.ElasticTrainer raise
        ModuleNotFoundError)."""
        import dlrover_tpu as dt

        for name in dt._LAZY:
            obj = getattr(dt, name)
            assert obj is not None, name

    def test_unknown_attribute_raises(self):
        import pytest

        import dlrover_tpu as dt

        with pytest.raises(AttributeError):
            dt.does_not_exist


class TestCompilationCache:
    """One way to place the compile cache, JAX's own: where
    ``JAX_COMPILATION_CACHE_DIR`` is set the code sets no directory; unset,
    one fixed path inside the checkout."""

    def test_off_switch(self, monkeypatch):
        from dlrover_tpu.common.jax_env import enable_compilation_cache

        monkeypatch.setenv("DLROVER_TPU_COMPILE_CACHE", "0")
        assert enable_compilation_cache() is False

    def test_unset_env_uses_the_fixed_in_checkout_dir(self, monkeypatch):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from conftest import REPO_ROOT
        from dlrover_tpu.common import jax_env

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("DLROVER_TPU_COMPILE_CACHE", raising=False)
        d = jax_env.compilation_cache_dir()
        # fixed: not $HOME, not a temp name, a pid or a time
        assert d == os.path.join(REPO_ROOT, ".jax_cache")
        assert d == jax_env.DEFAULT_COMPILE_CACHE_DIR
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert jax_env.enable_compilation_cache() is True
            assert jax.config.jax_compilation_cache_dir == d
            assert os.path.isdir(d)
        finally:
            # the cache backend latches its directory: hand the next
            # test the setting it had
            jax.config.update("jax_compilation_cache_dir", prev)
            compilation_cache.reset_cache()

    def test_env_places_the_cache_and_the_code_sets_no_dir(self, tmp_path):
        """A fresh process, as every entry point is: the cache is written
        under ``JAX_COMPILATION_CACHE_DIR`` and ``enable_compilation_cache``
        never touches ``jax_compilation_cache_dir``."""
        import json
        import subprocess
        import sys

        from conftest import REPO_ROOT

        code = (
            "import json, os, jax\n"
            "seen = []\n"
            "real = jax.config.update\n"
            "jax.config.update = lambda k, v: (seen.append(k), "
            "real(k, v))[1]\n"
            "from dlrover_tpu.common import jax_env\n"
            "assert jax_env.enable_compilation_cache() is True\n"
            "jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones((37,)))"
            ".block_until_ready()\n"
            "print(json.dumps({'seen': seen, "
            "'dir': jax.config.jax_compilation_cache_dir, "
            "'where': jax_env.compilation_cache_dir()}))\n"
        )
        want = str(tmp_path / "x")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=want,
                   JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
        env.pop("DLROVER_TPU_COMPILE_CACHE", None)
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert "jax_compilation_cache_dir" not in out["seen"]
        assert out["dir"] == want and out["where"] == want
        assert any((tmp_path / "x").iterdir())

    def test_exactly_one_site_names_the_cache_dir_option(self):
        """``grep -rn jax_compilation_cache_dir dlrover_tpu examples``:
        one guarded site."""
        import glob

        from conftest import REPO_ROOT

        files = []
        for top in ("dlrover_tpu", "examples"):
            files += glob.glob(
                os.path.join(REPO_ROOT, top, "**", "*.py"), recursive=True)
        hits = [
            (os.path.relpath(f, REPO_ROOT), n)
            for f in files
            for n, line in enumerate(open(f, encoding="utf-8"), 1)
            if "jax_compilation_cache_dir" in line
        ]
        assert [f for f, _ in hits] == ["dlrover_tpu/common/jax_env.py"]


class TestCompileListener:
    """JAX's compile events reach the flight recorder through one
    listener pair (``jax_env.install_compile_listener``), and whoever
    asks what the cache said asks that listener."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

    @pytest.fixture
    def recorder(self):
        from dlrover_tpu import obs

        rec = obs.configure()
        yield rec
        obs.reset()

    @staticmethod
    def _compiles(rec):
        return [e for e in rec.snapshot()[0]
                if e.get("name") == "jax.compile"]

    def test_one_pair_however_often_it_is_installed(self):
        from jax._src import monitoring

        from dlrover_tpu.common import jax_env

        jax_env.install_compile_listener()
        before = (len(monitoring._event_listeners),
                  len(monitoring._event_duration_secs_listeners))
        jax_env.install_compile_listener()
        jax_env.device_summary()
        jax_env.CompileWatch()
        assert (len(monitoring._event_listeners),
                len(monitoring._event_duration_secs_listeners)) == before
        assert monitoring._event_listeners.count(
            jax_env._on_cache_event) == 1
        assert monitoring._event_duration_secs_listeners.count(
            jax_env._on_stage_duration) == 1

    def test_exactly_one_site_registers_listeners(self):
        """``grep -rn 'register_event.*listener' dlrover_tpu``: the two
        calls of ``install_compile_listener``."""
        import glob

        from conftest import REPO_ROOT

        hits = [
            os.path.relpath(f, REPO_ROOT)
            for f in glob.glob(os.path.join(
                REPO_ROOT, "dlrover_tpu", "**", "*.py"), recursive=True)
            for line in open(f, encoding="utf-8")
            if "monitoring.register_" in line
        ]
        assert hits == ["dlrover_tpu/common/jax_env.py"] * 2

    def test_the_verdict_rides_on_the_compile_that_asked(self, recorder):
        from dlrover_tpu.common import jax_env

        watch = jax_env.CompileWatch()
        assert watch.cache_hit is None  # nothing compiled
        jax_env._on_stage_duration(self.COMPILE, 0.25, fun_name="jit(a)")
        assert watch.cache_hit is None  # compiled, the cache not asked
        jax_env._on_cache_event(self.HIT)
        jax_env._on_stage_duration(self.RETRIEVAL, 0.125)
        jax_env._on_stage_duration(self.COMPILE, 0.5, fun_name="jit(b)")
        assert watch.cache_hit is True
        jax_env._on_cache_event(self.MISS)
        jax_env._on_stage_duration(self.COMPILE, 2.0, fun_name="jit(c)")
        assert watch.cache_hit is False  # one was compiled
        assert jax_env.CompileWatch().cache_hit is None  # a later watch
        a, b, c = self._compiles(recorder)
        assert a["args"] == {"fun_name": "jit(a)", "cache_hit": None}
        assert b["args"] == {"fun_name": "jit(b)", "cache_hit": True,
                             "retrieval_s": 0.125}
        # the hit's retrieval time does not leak into the next compile
        assert c["args"] == {"fun_name": "jit(c)", "cache_hit": False}
        assert c["dur"] == pytest.approx(2.0e6)

    def test_a_verdict_stays_on_its_thread(self, recorder):
        import threading

        from dlrover_tpu.common import jax_env

        watch = jax_env.CompileWatch()
        t = threading.Thread(target=lambda: (
            jax_env._on_cache_event(self.MISS),
            jax_env._on_stage_duration(self.COMPILE, 1.0, fun_name="x")))
        t.start()
        t.join()
        assert watch.cache_hit is None
        jax_env._on_stage_duration(self.COMPILE, 1.0, fun_name="y")
        by = {e["args"]["fun_name"]: e["args"]["cache_hit"]
              for e in self._compiles(recorder)}
        assert by == {"x": False, "y": None}

    def test_a_stage_span_ends_now_and_names_the_open_span(self, recorder):
        import time

        from dlrover_tpu import obs
        from dlrover_tpu.common import jax_env
        from dlrover_tpu.obs.span import anchored_us

        with obs.span("outer", "ut") as outer:
            jax_env._on_stage_duration(self.TRACE, 0.5, fun_name="f")
            # under the threshold: thousands of these a build, unrecorded
            jax_env._on_stage_duration(
                self.TRACE, jax_env.MIN_TRACE_SPAN_S / 2, fun_name="g")
            jax_env._on_stage_duration("/jax/other/duration", 9.0)
        now = anchored_us(time.monotonic())
        (traced,) = [e for e in recorder.snapshot()[0]
                     if e.get("cat") == "jax"]
        assert traced["name"] == "jax.trace"
        assert traced["psid"] == outer.sid
        assert traced["dur"] == pytest.approx(0.5e6)
        assert now - 1e6 < traced["ts"] + traced["dur"] <= now
