"""Flash-checkpoint tests: flatten/assemble (resharding), engine save/load,
shard-file commit protocol, agent saver breakpoint save."""

import dataclasses
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint import shard_file, tree_utils
from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer
from dlrover_tpu.common.shm import SharedMemoryArena, arena_name
from dlrover_tpu.common.storage import PosixDiskStorage


@pytest.fixture()
def mesh(cpu_mesh_devices):
    return Mesh(np.array(cpu_mesh_devices[:8]).reshape(4, 2), ("dp", "tp"))


class TestTreeUtils:
    def test_flatten_replicated_and_sharded(self, mesh):
        repl = NamedSharding(mesh, P())
        sharded = NamedSharding(mesh, P("dp", "tp"))
        state = {
            "w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sharded),
            "b": jax.device_put(jnp.ones(4), repl),
            "step": np.int64(7),
        }
        tensors, info = tree_utils.flatten_to_shards(state)
        # Replicated leaf -> 1 shard; (4,2)-sharded 8x8 -> 8 unique shards.
        w_keys = [k for k in tensors if "'w'" in k]
        b_keys = [k for k in tensors if "'b'" in k]
        assert len(w_keys) == 8 and len(b_keys) == 1
        assert info[b_keys[0]]["global_shape"] == [4]

    def test_assemble_exact_and_reshard(self, mesh):
        sharded = NamedSharding(mesh, P("dp", None))
        x = jax.device_put(jnp.arange(32.0).reshape(8, 4), sharded)
        tensors, info = tree_utils.flatten_to_shards({"x": x})
        source = tree_utils.ShardSource()
        source.add(tensors, info)
        path = next(iter(source.pieces))
        # Exact shard.
        got = source.assemble(path, ((0, 2), (0, 4)))
        np.testing.assert_array_equal(got, np.arange(8.0).reshape(2, 4))
        # Resharded region spanning two original shards.
        got2 = source.assemble(path, ((1, 3), (0, 4)))
        np.testing.assert_array_equal(
            got2, np.arange(32.0).reshape(8, 4)[1:3]
        )
        # Full array.
        got3 = source.assemble(path, ((0, 8), (0, 4)))
        np.testing.assert_array_equal(got3, np.arange(32.0).reshape(8, 4))
        # Uncovered region -> None.
        assert source.assemble(path, ((0, 9), (0, 4))) is None

    def test_restore_to_new_sharding(self, mesh):
        """Save under (dp)-sharding, restore under (tp)-style sharding —
        the Tenplex-style reshard-on-restore."""
        s1 = NamedSharding(mesh, P("dp", None))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8), s1)
        tensors, info = tree_utils.flatten_to_shards({"x": x})
        source = tree_utils.ShardSource()
        source.add(tensors, info)
        s2 = NamedSharding(mesh, P("tp", "dp"))
        target = {"x": jax.device_put(jnp.zeros((8, 8)), s2)}
        restored = tree_utils.restore_to_target(target, source)
        np.testing.assert_array_equal(
            np.asarray(restored["x"]), np.arange(64.0).reshape(8, 8)
        )
        assert restored["x"].sharding == s2


class TestShardFile:
    def test_pack_unpack(self):
        tensors = {
            "a|0": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b|0": np.array([True, False]),
        }
        blob = shard_file.pack_shard(tensors, {"step": 3})
        out, extra = shard_file.unpack_shard(blob)
        assert extra["step"] == 3
        np.testing.assert_array_equal(out["a|0"], tensors["a|0"])
        np.testing.assert_array_equal(out["b|0"], tensors["b|0"])

    def test_uncommitted_step_restorable_when_covered(self, tmp_path, monkeypatch):
        """A breakpoint save from a partial world (no commit) must still
        restore when its shards cover the target (replicated layout)."""
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-unc")
        monkeypatch.setenv("DLROVER_TPU_RUN_ID", "unc1")
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        # Committed step 10 and an uncommitted (newer) step 20 whose one
        # shard fully covers the replicated tensor.
        for step, val, commit_it in ((10, 1.0, True), (20, 2.0, False)):
            tensors = {"['w']|0": np.full(4, val, np.float32)}
            extra = {
                "step": step,
                "meta": {"step": step},
                "tensors_info": {
                    "['w']|0": {
                        "path": "['w']",
                        "global_shape": [4],
                        "index": [[0, 4]],
                    }
                },
                "num_processes": 1,
                "process_id": 0,
            }
            shard_file.write_shard(
                PosixDiskStorage(), str(tmp_path), step, 0, tensors, extra
            )
            if commit_it:
                shard_file.commit(PosixDiskStorage(), str(tmp_path), step)
        eng = CheckpointEngine(str(tmp_path), job_name="ckpt-unc")
        try:
            got = eng.load(target={"w": np.zeros(4, np.float32)})
            assert got is not None
            state, meta = got
            # Committed step wins (deterministic across ranks) ...
            assert meta["step"] == 10
            np.testing.assert_array_equal(state["w"], np.full(4, 1.0))
            # ... but with no tracker at all, the newest covered step is
            # used.
            import os as _os

            _os.unlink(shard_file.tracker_path(str(tmp_path)))
            got2 = eng.load(target={"w": np.zeros(4, np.float32)})
            assert got2[1]["step"] == 20
            np.testing.assert_array_equal(got2[0]["w"], np.full(4, 2.0))
        finally:
            eng.close()

    def test_truncated_shard_raises_typed_error(self, tmp_path):
        """Regression (ISSUE 3): a truncated on-disk shard used to
        surface as raw struct.error/ValueError from unpack; every damage
        mode is now one typed ShardCorruptionError."""
        storage = PosixDiskStorage()
        d = str(tmp_path)
        shard_file.write_shard(storage, d, 10, 0, {"x|0": np.ones(3)}, {})
        path = shard_file.shard_path(d, 10, 0)
        with open(path, "rb") as f:
            raw = f.read()
        for cut in (0, 7, 18, len(raw) - 2):
            with open(path, "wb") as f:
                f.write(raw[:cut])
            with pytest.raises(shard_file.ShardCorruptionError):
                shard_file.read_shard(storage, d, 10, 0)

    def test_pack_unpack_zero_d(self):
        # Regression: np.ascontiguousarray promotes 0-d to (1,); a restored
        # scalar (e.g. optimizer step count) must stay 0-d or
        # make_array_from_single_device_arrays rejects the shard.
        tensors = {"count|0": np.asarray(np.int32(7))}
        out, _ = shard_file.unpack_shard(shard_file.pack_shard(tensors, {}))
        assert out["count|0"].shape == ()
        assert out["count|0"] == 7

    def test_commit_protocol(self, tmp_path):
        storage = PosixDiskStorage()
        d = str(tmp_path)
        shard_file.write_shard(storage, d, 10, 0, {"x|0": np.ones(3)}, {})
        assert not shard_file.all_shards_done(storage, d, 10, 2)
        assert shard_file.latest_step(storage, d) is None  # not committed
        shard_file.write_shard(storage, d, 10, 1, {"x|1": np.ones(3)}, {})
        assert shard_file.all_shards_done(storage, d, 10, 2)
        shard_file.commit(storage, d, 10)
        assert shard_file.latest_step(storage, d) == 10
        assert shard_file.list_shard_ids(storage, d, 10) == [0, 1]

    def test_gc_keeps_last(self, tmp_path):
        storage = PosixDiskStorage()
        d = str(tmp_path)
        for step in (1, 2, 3, 4, 5):
            shard_file.write_shard(storage, d, step, 0, {"x|0": np.ones(2)}, {})
            shard_file.commit(storage, d, step, keep_last=2)
        remaining = [n for n in os.listdir(d) if n.startswith("step_")]
        assert len(remaining) == 2


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class _History:
    """A registered pytree class, as a train state may hold one beside its
    dicts (an optimizer's own state, a caller's counters)."""

    seen: jax.Array

    def tree_flatten(self):
        return (self.seen,), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


class TestEngineStandalone:
    @pytest.mark.parametrize("holder", [dict, _History],
                             ids=["dict", "pytree class"])
    def test_save_load_memory_and_storage(
            self, tmp_path, monkeypatch, holder):
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-ut")
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        ckpt = FlashCheckpointer(str(tmp_path), job_name="ckpt-ut")
        state = {
            "params": {"w": jnp.arange(16.0).reshape(4, 4)},
            "count": jnp.array(3),
            "history": holder(seen=jnp.arange(4.0)),
        }
        ckpt.save(state, meta={"step": 5})  # memory only
        # load(target=) rebuilds the target's own containers around the
        # saved leaves
        restored, meta = ckpt.load(
            target=jax.tree_util.tree_map(jnp.zeros_like, state))
        assert meta["step"] == 5
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.arange(16.0).reshape(4, 4)
        )
        assert type(restored["history"]) is holder
        np.testing.assert_array_equal(
            np.asarray(jax.tree_util.tree_leaves(restored["history"])[0]),
            np.arange(4.0))
        # Storage save + wait -> tracker advanced.
        ckpt.save(state, meta={"step": 6}, storage=True)
        assert ckpt.wait(timeout=60)
        assert shard_file.latest_step(PosixDiskStorage(), str(tmp_path)) == 6
        ckpt.close()
        # What the engine writes is fsck-clean (CRCs, done votes,
        # tracker, coverage).
        from dlrover_tpu.checkpoint import fsck

        report = fsck.fsck(str(tmp_path))
        assert not report.damaged, report.findings

    def test_cold_restore_from_storage(self, tmp_path, monkeypatch):
        """Simulates full host restart: shm gone, restore reads shard files."""
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-cold")
        ckpt = FlashCheckpointer(str(tmp_path), job_name="ckpt-cold")
        state = {"w": jnp.ones((4, 4)) * 2.5}
        ckpt.save(state, meta={"step": 9}, storage=True)
        assert ckpt.wait(timeout=60)
        ckpt.close()
        # Wipe the shm arena (simulate reboot).
        arena = SharedMemoryArena(arena_name("ckpt-cold", 0))
        arena.close(unlink=True)
        ckpt2 = FlashCheckpointer(str(tmp_path), job_name="ckpt-cold")
        restored, meta = ckpt2.load(target={"w": jnp.zeros((4, 4))})
        assert meta["step"] == 9
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.full((4, 4), 2.5)
        )
        ckpt2.close()


class TestAgentSaver:
    def test_event_persist_and_breakpoint_save(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        job = "ckpt-agent"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)
        saver.start()
        try:
            # Engine must auto-detect agent mode now.
            ckpt = FlashCheckpointer(str(tmp_path), job_name=job)
            assert ckpt.engine.agent_mode
            state = {"w": jnp.full((8, 8), 1.5)}
            ckpt.save(state, meta={"step": 4}, storage=True)
            assert ckpt.wait(timeout=60)
            assert shard_file.latest_step(
                PosixDiskStorage(), str(tmp_path)
            ) == 4
            # Stage step 8 in shm only, then breakpoint-save persists it.
            ckpt.save(state, meta={"step": 8})
            saver.save_shm_to_storage("test-breakpoint")
            deadline = time.time() + 60
            while time.time() < deadline:
                if shard_file.latest_step(
                    PosixDiskStorage(), str(tmp_path)
                ) == 8:
                    break
                time.sleep(0.5)
            assert shard_file.latest_step(
                PosixDiskStorage(), str(tmp_path)
            ) == 8
            ckpt.close()
        finally:
            saver.stop()
