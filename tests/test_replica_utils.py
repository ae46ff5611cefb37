"""Checkpoint replica + utils tests: ring backup over real RPC,
loss-spike detection, metrics endpoint."""

import math
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.checkpoint.replica import (
    CkptReplicaManager,
    ReplicaServicer,
    ReplicaStore,
)
from dlrover_tpu.utils.loss_spike import LossSpikeDetector


class TestReplicaStore:
    def test_put_get_monotonic_steps(self):
        st = ReplicaStore()
        assert st.put(0, 10, b"a")
        assert not st.put(0, 9, b"b")  # stale step rejected
        assert st.get(0) == (10, b"a")
        assert st.get(0, min_step=11) is None

    def test_capacity_guard(self):
        st = ReplicaStore(max_bytes=10)
        assert st.put(0, 1, b"x" * 8)
        assert not st.put(1, 1, b"y" * 8)  # would exceed cap
        assert st.put(0, 2, b"z" * 9)  # replacing own entry is fine


class _KVStub:
    """Master-KV stand-in shared by both 'nodes'."""

    def __init__(self):
        self.kv = {}

    def kv_store_set(self, k, v):
        self.kv[k] = v

    def kv_store_get(self, k):
        return self.kv.get(k)


class TestReplicaRing:
    def test_backup_and_fetch_between_nodes(self):
        kv = _KVStub()
        m0 = CkptReplicaManager(kv, node_rank=0, world_size=2,
                                push_interval_s=0.0)
        m1 = CkptReplicaManager(kv, node_rank=1, world_size=2,
                                push_interval_s=0.0)
        try:
            tensors = {"w|0": np.arange(6, dtype=np.float32)}
            # Push verification (ISSUE 3) rejects payloads that could
            # never seed a restore: carry a real layout.
            extra = {
                "step": 7,
                "process_id": 0,
                "num_processes": 2,
                "tensors_info": {
                    "w|0": {
                        "path": "w", "global_shape": [6], "index": [[0, 6]]
                    }
                },
            }
            # Node 0 backs its proc 0 shard onto node 1 (ring successor).
            assert m0.backup_shard(0, 7, tensors, extra, force=True)
            assert m1.store.get(0)[0] == 7
            # A "replaced" node 0 fetches it back from node 1.
            got = m0.fetch_replica(0)
            assert got is not None
            step, t2, e2 = got
            assert step == 7
            np.testing.assert_array_equal(t2["w|0"], tensors["w|0"])
            assert e2["num_processes"] == 2
        finally:
            m0.stop()
            m1.stop()

    def test_throttle(self):
        kv = _KVStub()
        m0 = CkptReplicaManager(kv, node_rank=0, world_size=2,
                                push_interval_s=3600.0)
        m1 = CkptReplicaManager(kv, node_rank=1, world_size=2)
        try:
            t = {"w|0": np.zeros(1, np.float32)}

            def e(step):
                return {
                    "step": step,
                    "process_id": 0,
                    "num_processes": 2,
                    "tensors_info": {
                        "w|0": {
                            "path": "w",
                            "global_shape": [1],
                            "index": [[0, 1]],
                        }
                    },
                }

            assert m0.backup_shard(0, 1, t, e(1))   # first push goes out
            assert not m0.backup_shard(0, 2, t, e(2))  # throttled
            assert m0.backup_shard(0, 3, t, e(3), force=True)
        finally:
            m0.stop()
            m1.stop()

    def test_single_node_noop(self):
        kv = _KVStub()
        m0 = CkptReplicaManager(kv, node_rank=0, world_size=1)
        try:
            assert not m0.backup_shard(0, 1, {}, {}, force=True)
            assert m0.fetch_replica(0) is None
        finally:
            m0.stop()


class TestSaverSeeding:
    def test_seed_arena_from_peer_replica(self, monkeypatch):
        """A replaced node's saver seeds its empty local arena from the
        ring successor's replica store before workers start."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.common.global_context import get_context
        from dlrover_tpu.common.shm import SharedMemoryArena, arena_name

        monkeypatch.setenv("DLROVER_TPU_RUN_ID", "seedtest")
        monkeypatch.setattr(get_context(), "ckpt_replica", True)
        kv = _KVStub()
        peer = CkptReplicaManager(kv, node_rank=1, world_size=2)
        saver = None
        try:
            saver = AsyncCheckpointSaver(
                "seed-job", 1, master_client=kv
            )
            assert saver.replica is not None
            saver.update_world(0, 2)
            # Peer (node 1) holds the replica of proc 0 at step 42.
            tensors = {"w|0": np.full(4, 3.0, np.float32)}
            extra = {
                "step": 42,
                "tensors_info": {
                    "w|0": {
                        "path": "w",
                        "global_shape": [4],
                        "index": [[0, 4]],
                    }
                },
                "num_processes": 2,
                "process_id": 0,
            }
            import dlrover_tpu.checkpoint.shard_file as sf

            peer.store.put(0, 42, sf.pack_shard(tensors, extra))
            seeded = saver.seed_from_replicas({0: 0}, num_processes=2)
            assert seeded == 1
            arena = SharedMemoryArena(arena_name("seed-job", 0))
            try:
                got = arena.read_state()
                assert got is not None
                t2, e2 = got
                assert e2["step"] == 42
                np.testing.assert_array_equal(t2["w|0"], tensors["w|0"])
            finally:
                arena.close(unlink=True)
        finally:
            peer.stop()
            if saver is not None:
                saver.stop()


class TestLossSpike:
    def test_nan_always_spikes(self):
        d = LossSpikeDetector(min_samples=5)
        assert d.update(1, float("nan"))

    def test_spike_detection(self, tmp_path):
        d = LossSpikeDetector(
            min_samples=10, zscore_threshold=4.0,
            ratio_threshold=1.5, spike_log_dir=str(tmp_path),
        )
        for i in range(20):
            assert not d.update(i, 2.0 + 0.01 * (i % 3))
        assert d.update(20, 10.0)
        # Spike not added to the window: next normal loss is not flagged.
        assert not d.update(21, 2.0)
        log = (tmp_path / "loss_spikes.jsonl").read_text()
        assert '"step": 20' in log


class TestMetricsEndpoint:
    def test_scrape(self):
        from dlrover_tpu.agent.metrics import (
            MetricsRegistry,
            MetricsServer,
        )

        reg = MetricsRegistry()
        reg.gauge("restart_count", lambda: 2.0)
        srv = MetricsServer(reg, 0)
        srv.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
            assert "dlrover_tpu_restart_count 2.0" in body
        finally:
            srv.stop()
