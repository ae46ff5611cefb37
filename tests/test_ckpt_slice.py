"""Scale-out checkpoint tests (ISSUE 7): cross-replica sliced persist,
dirty-fence incremental saves, the reused tiling proof gating commit, and
plan-driven restore of sliced checkpoints onto any mesh."""

import os
import shutil

import numpy as np
import pytest

from dlrover_tpu.checkpoint import shard_file, slicer
from dlrover_tpu.checkpoint.tree_utils import ShardSource
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.parallel.mesh import MeshSpec


def _info_for(state, world, owners=None):
    return {
        k: {
            "path": k.rsplit("|", 1)[0],
            "global_shape": list(np.shape(v)),
            "index": [[0, d] for d in np.shape(v)],
            "owners": owners if owners is not None else list(range(world)),
        }
        for k, v in state.items()
    }


def _extra_for(state, step, pid, world, owners=None):
    return {
        "step": step,
        "meta": {},
        "tensors_info": _info_for(state, world, owners),
        "process_id": pid,
        "num_processes": world,
        "tree_paths": sorted({k.rsplit("|", 1)[0] for k in state}),
    }


def _save_sliced_world(storage, ckpt_dir, state, step, world,
                       trackers=None, commit=True):
    """Persist one replicated state as ``world`` sliced ranks would;
    returns each rank's plan."""
    plans = []
    for pid in range(world):
        plan = slicer.plan_persist(
            state, _extra_for(state, step, pid, world),
            process_id=pid, num_processes=world,
            tracker=trackers[pid] if trackers else None,
            holder_exists=lambda s, p=pid: storage.exists(
                shard_file.shard_path(ckpt_dir, s, p)
            ),
        )
        stats = shard_file.write_shard_from_views(
            storage, ckpt_dir, step, pid, plan.tensors, plan.extra,
            meta_extra=plan.meta_extra,
        )
        if trackers:
            trackers[pid].note_plan(plan, step, stats["crcs"])
        plans.append(plan)
    if commit:
        assert slicer.commit_gate(storage, ckpt_dir, step)
        shard_file.commit(storage, ckpt_dir, step, keep_last=0)
    return plans


class TestSlicePartitionProperties:
    """The assignment itself: disjoint + fully covering + byte-balanced,
    across world sizes 1/2/3/4, including non-divisible element counts,
    empty and 0-d tensors."""

    @pytest.mark.parametrize("world", [1, 2, 3, 4])
    def test_bounds_tile_exactly(self, world):
        for n_elems, isz in [(0, 4), (1, 4), (2, 8), (5, 4), (7, 2),
                             (1024, 4), (1025, 4), (999, 1)]:
            n = n_elems * isz
            ranges = [
                slicer.slice_bounds(n, isz, world, i) for i in range(world)
            ]
            pos = 0
            for lo, hi in ranges:  # contiguous => disjoint + covering
                assert lo == pos and hi >= lo
                assert lo % isz == 0  # element-aligned
                pos = hi
            assert pos == n
            sizes = [hi - lo for lo, hi in ranges]
            assert max(sizes) - min(sizes) <= isz  # byte-balanced

    @pytest.mark.parametrize("world", [1, 2, 3, 4])
    def test_plans_are_disjoint_covering_balanced(self, world):
        state = {
            "big|0": np.arange(50001, dtype=np.float32),  # non-divisible
            "small|0": np.arange(7, dtype=np.float64),  # single-owner
            "scalar|0": np.float32(2.5),  # 0-d
            "empty|0": np.zeros((0, 3), dtype=np.float32),  # empty
        }
        plans = [
            slicer.plan_persist(
                state, _extra_for(state, 1, pid, world),
                process_id=pid, num_processes=world,
            )
            for pid in range(world)
        ]
        for key, arr in state.items():
            n = int(np.asarray(arr).nbytes)
            covered = np.zeros(n, dtype=bool)
            for plan in plans:
                lo, hi, full = plan.layout[key]
                assert full == n
                assert not covered[lo:hi].any(), "overlapping slices"
                covered[lo:hi] = True
            assert covered.all(), f"{key}: uncovered bytes"
        # big tensors byte-balanced across ranks
        big = [p.layout["big|0"] for p in plans]
        sizes = [hi - lo for lo, hi, _ in big]
        assert max(sizes) - min(sizes) <= 4
        # determinism: replanning yields identical layouts
        replans = [
            slicer.plan_persist(
                state, _extra_for(state, 1, pid, world),
                process_id=pid, num_processes=world,
            )
            for pid in range(world)
        ]
        assert [p.layout for p in plans] == [p.layout for p in replans]

    def test_partial_replication_slices_within_owner_group(self):
        """A box owned by ranks {1, 3} of a 4-world splits between those
        two only; non-owners write nothing for it."""
        state = {"w|0": np.arange(40000, dtype=np.float32)}
        n = state["w|0"].nbytes
        layouts = {}
        for pid in range(4):
            plan = slicer.plan_persist(
                state, _extra_for(state, 1, pid, 4, owners=[1, 3]),
                process_id=pid, num_processes=4,
            )
            layouts[pid] = plan.layout["w|0"]
        assert layouts[1] == (0, n // 2, n)
        assert layouts[3] == (n // 2, n, n)
        # non-owners keep the full entry (their staged copy is written
        # whole — they are not in the owner set, nothing is saved by
        # slicing a box the plan says they do not hold)
        assert layouts[0] == (0, n, n) and layouts[2] == (0, n, n)


class TestCoverageProof:
    """Commit requires the reshard planner's tiling proof over the slice
    set — reused, not reimplemented."""

    def test_full_slice_set_proves_and_missing_rank_fails(self, tmp_path):
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = {"w|0": np.arange(30000, dtype=np.float32),
                 "b|0": np.arange(100, dtype=np.float32)}
        _save_sliced_world(storage, d, state, 1, 3, commit=False)
        ok, why = slicer.step_covers(storage, d, 1)
        assert ok, why
        os.remove(shard_file.shard_path(d, 1, 1))
        ok, why = slicer.step_covers(storage, d, 1)
        assert not ok and "uncovered" in why

    def test_missing_exclusive_tensor_path_detected(self, tmp_path):
        """tree_paths lets the proof see a dead rank's EXCLUSIVE tensors
        are gone entirely, not just torn slices of shared ones."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = {"b|0": np.arange(100, dtype=np.float32)}
        extra = _extra_for(state, 1, 0, 2)
        extra["tree_paths"] = ["b", "only_on_rank1"]
        plan = slicer.plan_persist(state, extra, process_id=0,
                                   num_processes=2)
        shard_file.write_shard_from_views(
            storage, d, 1, 0, plan.tensors, plan.extra,
            meta_extra=plan.meta_extra,
        )
        ok, why = slicer.step_covers(storage, d, 1)
        assert not ok and "only_on_rank1" in why

    def test_commit_gate_blocks_even_with_lying_done_votes(self, tmp_path):
        """Done votes are necessary but no longer sufficient: a vote
        without the bytes (torn write, lying filesystem) must not
        produce a committed-but-unrestorable step."""
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = {"w|0": np.arange(30000, dtype=np.float32)}
        _save_sliced_world(storage, d, state, 1, 2, commit=False)
        os.remove(shard_file.shard_path(d, 1, 1))  # bytes gone ...
        storage.write("1", shard_file.done_path(d, 1, 1))  # ... vote says ok
        eng = CheckpointEngine(d, job_name="slice-gate-test")
        eng.num_processes = 2
        assert eng._commit_when_ready(1, timeout=2.0) is False
        assert shard_file.latest_step(storage, d) is None
        eng.close()


class TestSlicedRestore:
    """Slice-persisted checkpoints restore byte-exactly — including onto
    larger/smaller/equal target meshes via the engine's plan-driven
    parallel reads."""

    def _save_mixed_world(self, tmp_path, world=4):
        """w: dp-sharded (exclusive boxes); b: replicated (sliced)."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "ckpt")
        W = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
        B = np.linspace(0, 1, 20000).astype(np.float32)
        step = 3
        per = 64 // world
        for pid in range(world):
            lo, hi = pid * per, (pid + 1) * per
            tensors = {"['w']|0": np.ascontiguousarray(W[lo:hi]),
                       "['b']|0": B}
            info = {
                "['w']|0": {
                    "path": "['w']", "global_shape": [64, 4],
                    "index": [[lo, hi], [0, 4]], "owners": [pid],
                },
                "['b']|0": {
                    "path": "['b']", "global_shape": [20000],
                    "index": [[0, 20000]],
                    "owners": list(range(world)),
                },
            }
            extra = {
                "step": step, "meta": {}, "tensors_info": info,
                "process_id": pid, "num_processes": world,
                "tree_paths": ["['b']", "['w']"],
            }
            plan = slicer.plan_persist(
                tensors, extra, process_id=pid, num_processes=world
            )
            shard_file.write_shard_from_views(
                storage, d, step, pid, plan.tensors, plan.extra,
                meta_extra=plan.meta_extra,
            )
        assert slicer.commit_gate(storage, d, step)
        shard_file.commit(storage, d, step)
        # the replicated tensor moved once across the fleet, not world x
        total_b_bytes = 0
        for pid in range(world):
            man = shard_file.read_shard_manifest(storage, d, step, pid)
            tm = man.tensors["['b']|0"]
            total_b_bytes += int(tm["nbytes"])
        assert total_b_bytes == B.nbytes
        return d, W, B, step

    @pytest.mark.parametrize("target_dp", [1, 2, 4, 8])
    def test_restore_equality_across_target_meshes(
        self, tmp_path, cpu_mesh_devices, target_dp
    ):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.parallel.mesh import build_mesh

        d, W, B, step = self._save_mixed_world(tmp_path, world=4)
        mesh = build_mesh(
            MeshSpec(dp=target_dp), cpu_mesh_devices[:target_dp]
        )
        target = {
            "w": jax.ShapeDtypeStruct(
                W.shape, W.dtype, sharding=NamedSharding(mesh, P("dp"))
            ),
            "b": jax.ShapeDtypeStruct(
                B.shape, B.dtype, sharding=NamedSharding(mesh, P())
            ),
        }
        eng = CheckpointEngine(d, job_name=f"slice-rt-{target_dp}")
        got = eng.load(target)
        assert got is not None
        restored, meta = got
        assert meta["step"] == step
        np.testing.assert_array_equal(np.asarray(restored["w"]), W)
        np.testing.assert_array_equal(np.asarray(restored["b"]), B)
        eng.close()

    def test_shardsource_slice_reassembly_paths(self, tmp_path):
        """Slices accumulate per (path, box) and only a complete tiling
        materializes; incomplete tilings leave the region uncovered."""
        B = np.arange(1000, dtype=np.float64)
        sl_meta = lambda lo, hi: {  # noqa: E731
            "slice": [lo, hi], "full_nbytes": B.nbytes,
            "dtype": "float64", "shape": [1000],
        }
        info = {"b|0": {"path": "b", "global_shape": [1000],
                        "index": [[0, 1000]]}}
        raw = B.view(np.uint8)
        src = ShardSource()
        src.add({"b|0": raw[:4000]}, info, {"b|0": sl_meta(0, 4000)})
        assert src.assemble("b", ((0, 1000),)) is None  # gap
        src.add({"b|0": raw[4000:]}, info, {"b|0": sl_meta(4000, 8000)})
        np.testing.assert_array_equal(src.assemble("b", ((0, 1000),)), B)


class TestIncrementalSaves:
    """Dirty-fence refs: unchanged tensors are referenced, not
    rewritten; chains restore byte-exactly; rotation keeps holders."""

    def _std_engine(self, tmp_path):
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        return CheckpointEngine(
            str(tmp_path / "ckpt"), job_name="inc-test", max_to_keep=2
        )

    def test_unchanged_tensors_become_refs_and_restore(self, tmp_path):
        eng = self._std_engine(tmp_path)
        state = {f"t{i}": np.arange(5000, dtype=np.float32) + i
                 for i in range(10)}
        eng.save_to_storage(1, dict(state))
        assert eng.wait(timeout=60)
        state["t3"] = state["t3"] + 1.0
        eng.save_to_storage(2, dict(state))
        assert eng.wait(timeout=60)
        man = shard_file.read_shard_manifest(eng.storage, eng.ckpt_dir, 2, 0)
        refs = [k for k, tm in man.tensors.items()
                if isinstance(tm.get("ref"), dict)]
        assert len(refs) == 9 and "['t3']|0" not in refs
        assert man.extra["ref_steps"] == [1]
        got = eng.load({k: np.zeros_like(v) for k, v in state.items()})
        assert got is not None
        restored, meta = got
        assert meta["step"] == 2
        for k, v in state.items():
            np.testing.assert_array_equal(np.asarray(restored[k]), v)
        from dlrover_tpu.checkpoint import fsck as fsck_mod

        assert not fsck_mod.fsck(eng.ckpt_dir, eng.storage).damaged
        eng.close()

    def test_same_step_persisted_twice_never_refs_itself(self, tmp_path):
        """A breakpoint save can race the event-loop persist of the SAME
        step (seen on the chip at a 2.6 GB state): the rewrite found
        every fence untripped against the holder it was replacing and
        wrote a shard of refs to itself — the only copy of the bytes
        gone, fsck 'ref chain exceeds depth'."""
        from dlrover_tpu.checkpoint import fsck as fsck_mod

        eng = self._std_engine(tmp_path)
        state = {f"t{i}": np.arange(5000, dtype=np.float32) + i
                 for i in range(4)}
        for _ in range(2):
            eng.save_to_storage(1, dict(state))
            assert eng.wait(timeout=60)
        man = shard_file.read_shard_manifest(eng.storage, eng.ckpt_dir, 1, 0)
        assert not [k for k, tm in man.tensors.items()
                    if isinstance(tm.get("ref"), dict)]
        assert not fsck_mod.fsck(eng.ckpt_dir, eng.storage).damaged
        restored, meta = eng.load(
            {k: np.zeros_like(v) for k, v in state.items()})
        assert meta["step"] == 1
        for k, v in state.items():
            np.testing.assert_array_equal(np.asarray(restored[k]), v)
        eng.close()

    def test_rotation_protects_holder_steps(self, tmp_path):
        """max_to_keep=2 would GC step 1 after steps 2 and 3 commit —
        unless live steps still reference its bytes."""
        eng = self._std_engine(tmp_path)
        state = {"frozen": np.arange(20000, dtype=np.float32),
                 "hot": np.arange(100, dtype=np.float32)}
        for step in (1, 2, 3):
            state["hot"] = state["hot"] + 1.0
            eng.save_to_storage(step, dict(state))
            assert eng.wait(timeout=60)
        steps = sorted(shard_file.list_steps(eng.storage, eng.ckpt_dir))
        assert 1 in steps, "holder step GC'd while still referenced"
        man = shard_file.read_shard_manifest(eng.storage, eng.ckpt_dir, 3, 0)
        assert man.tensors["['frozen']|0"]["ref"]["step"] == 1
        # and the chain still restores byte-exactly
        got = eng.load({k: np.zeros_like(v) for k, v in state.items()})
        restored, meta = got
        assert meta["step"] == 3
        np.testing.assert_array_equal(
            np.asarray(restored["frozen"]), state["frozen"]
        )
        eng.close()

    def test_fsck_flags_broken_ref_chain(self, tmp_path):
        from dlrover_tpu.checkpoint import fsck as fsck_mod

        eng = self._std_engine(tmp_path)
        state = {"w": np.arange(5000, dtype=np.float32)}
        eng.save_to_storage(1, dict(state))
        assert eng.wait(timeout=60)
        eng.save_to_storage(2, dict(state))
        assert eng.wait(timeout=60)
        # break the chain: delete the holder's step dir wholesale
        shutil.rmtree(shard_file.step_dir(eng.ckpt_dir, 1))
        report = fsck_mod.fsck(eng.ckpt_dir, eng.storage)
        assert report.damaged
        assert any("ref" in f.reason for f in report.findings)
        eng.close()


class TestFleetSaveByteCounts:
    """What a fleet save streams, counted in bytes: every byte of a
    replicated state once across the ranks, and after a partial update
    only what changed."""

    def _state(self, n=10, per=25_000):
        return {f"w{i}|0": np.arange(per, dtype=np.float32) * float(i + 1)
                for i in range(n)}

    @pytest.mark.parametrize("world", [2, 4])
    def test_each_byte_streamed_once_and_shared_evenly(self, tmp_path, world):
        storage = PosixDiskStorage()
        state = self._state()
        logical = sum(a.nbytes for a in state.values())
        plans = _save_sliced_world(
            storage, str(tmp_path / "c"), state, 1, world)
        assert sum(p.written_bytes for p in plans) == logical
        # a 4-byte item may fall either side of each tensor's cut
        slack = 4 * len(state)
        for plan in plans:
            assert plan.written_bytes <= logical // world + slack
            assert plan.skipped == 0

    def test_incremental_save_streams_only_the_dirty_bytes(self, tmp_path):
        """One tensor of ten changes between two saves of two ranks:
        the second save streams at most 1.5x the changed bytes, skips
        the rest, and the step reassembles byte-exactly across ranks
        and across the refs into step 1."""
        from dlrover_tpu.checkpoint import fsck as fsck_mod

        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = self._state()
        trackers = [slicer.DirtyTracker() for _ in range(2)]
        _save_sliced_world(storage, d, state, 1, 2, trackers=trackers)
        state["w3|0"] = state["w3|0"] + 1.0
        dirty = state["w3|0"].nbytes
        plans = _save_sliced_world(storage, d, state, 2, 2,
                                   trackers=trackers)
        written = sum(p.written_bytes for p in plans)
        assert 0 < written <= 1.5 * dirty
        assert sum(p.skipped for p in plans) >= len(state) - 1
        src = ShardSource()
        for pid in range(2):
            tensors, slices, extra = shard_file.read_shard_pieces(
                storage, d, 2, pid)
            src.add(tensors, extra["tensors_info"], slices)
        for key, want in state.items():
            got = src.assemble(key.rsplit("|", 1)[0],
                               tuple((0, n) for n in want.shape),
                               dtype=want.dtype)
            np.testing.assert_array_equal(got, want)
        assert not fsck_mod.fsck(d, storage).damaged


class TestSliceCrashChaos:
    """Chaos site ``storage.slice_crash``: a rank dies with its slice
    streamed but unpublished — the coverage proof blocks commit, restore
    falls back to the previous committed step, fsck stays clean."""

    CODE = r"""
import numpy as np
from dlrover_tpu.checkpoint import shard_file, slicer
from dlrover_tpu.common.storage import PosixDiskStorage

storage = PosixDiskStorage()
d = {ckpt_dir!r}
state = {{"['w']|0": np.arange(30000, dtype=np.float32)}}


def extra_for(step, pid):
    info = {{"['w']|0": {{"path": "['w']", "global_shape": [30000],
                          "index": [[0, 30000]], "owners": [0, 1]}}}}
    return {{"step": step, "meta": {{}}, "tensors_info": info,
             "process_id": pid, "num_processes": 2,
             "tree_paths": ["['w']"]}}


for step in (1, 2):
    if step == 2:
        state["['w']|0"] = state["['w']|0"] + 1.0
    for pid in (0, 1):
        plan = slicer.plan_persist(
            state, extra_for(step, pid), process_id=pid, num_processes=2
        )
        # step 2 / rank 1 crashes inside the streamed write (before the
        # atomic publish + done vote) via DLROVER_TPU_FAULTS
        shard_file.write_shard_from_views(
            storage, d, step, pid, plan.tensors, plan.extra,
            meta_extra=plan.meta_extra,
        )
    assert slicer.commit_gate(storage, d, step)
    shard_file.commit(storage, d, step, keep_last=0)
print("UNREACHABLE: chaos site did not fire")
raise SystemExit(3)
"""

    @pytest.mark.chaos
    def test_partial_slice_blocks_commit_and_ladder_falls_back(
        self, tmp_path, cpu_mesh_subprocess
    ):
        from dlrover_tpu.chaos.plan import EXIT_SLICE_CRASH
        from dlrover_tpu.checkpoint import fsck as fsck_mod
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        d = str(tmp_path / "ckpt")
        proc = cpu_mesh_subprocess(
            self.CODE.format(ckpt_dir=d),
            devices=1,
            env_extra={
                "DLROVER_TPU_FAULTS": "storage.slice_crash:step=2,rank=1",
            },
            timeout=120,
        )
        assert proc.returncode == EXIT_SLICE_CRASH, (
            proc.stdout[-1000:], proc.stderr[-1000:]
        )
        storage = PosixDiskStorage()
        # step 1 committed; step 2 has rank0's slice only (rank1 died
        # pre-publish: at most a .tmp widow, no shard, no done vote)
        assert shard_file.latest_step(storage, d) == 1
        assert not storage.exists(shard_file.shard_path(d, 2, 1))
        assert not storage.exists(shard_file.done_path(d, 2, 1))
        ok, why = slicer.step_covers(storage, d, 2)
        assert not ok and "uncovered" in why
        # the coverage proof blocks commit even if a vote lies
        storage.write("1", shard_file.done_path(d, 2, 1))
        eng = CheckpointEngine(d, job_name="slice-crash-test")
        eng.num_processes = 2
        assert eng._commit_when_ready(2, timeout=2.0) is False
        assert shard_file.latest_step(storage, d) == 1
        storage.safe_remove(shard_file.done_path(d, 2, 1))
        # restore falls back to the previous committed step's content
        W1 = np.arange(30000, dtype=np.float32)
        got = eng.load({"w": np.zeros(30000, dtype=np.float32)})
        assert got is not None
        restored, meta = got
        assert meta["step"] == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]), W1)
        eng.close()
        assert not fsck_mod.fsck(d, storage).damaged


class TestCoverageProofShardedLayouts:
    """The proof must be sound for SHARDED (non-replicated) layouts too:
    pieces are identified by (path, box), never by the per-rank local
    key — which collides across ranks for sharded leaves."""

    def _save_sharded(self, storage, d, world, rows, step=1, drop=None):
        """Each rank owns an exclusive row-slice of one 2-d tensor;
        uneven when ``world`` does not divide ``rows``."""
        per = -(-rows // world)  # ceil: jax-style uneven chunks
        for pid in range(world):
            if drop is not None and pid == drop:
                continue
            lo, hi = min(pid * per, rows), min((pid + 1) * per, rows)
            arr = np.arange(lo * 4, hi * 4, dtype=np.float32).reshape(
                max(0, hi - lo), 4
            )
            tensors = {"['w']|0": arr}
            info = {"['w']|0": {
                "path": "['w']", "global_shape": [rows, 4],
                "index": [[lo, hi], [0, 4]], "owners": [pid],
            }}
            extra = {
                "step": step, "meta": {}, "tensors_info": info,
                "process_id": pid, "num_processes": world,
                "tree_paths": ["['w']"],
            }
            plan = slicer.plan_persist(
                tensors, extra, process_id=pid, num_processes=world
            )
            shard_file.write_shard_from_views(
                storage, d, step, pid, plan.tensors, plan.extra,
                meta_extra=plan.meta_extra,
            )

    def test_uneven_sharding_commits(self, tmp_path):
        """10 rows over 4 ranks (3/3/3/1): every rank's local key is
        "['w']|0" with DIFFERENT sizes — must still prove coverage."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        self._save_sharded(storage, d, world=4, rows=10)
        ok, why = slicer.step_covers(storage, d, 1)
        assert ok, why

    def test_missing_exclusive_box_blocks_commit(self, tmp_path):
        """EVEN sharding, one rank's exclusive box gone: same-key
        conflation must not let the other ranks' boxes stand in."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        self._save_sharded(storage, d, world=4, rows=16, drop=2)
        ok, why = slicer.step_covers(storage, d, 1)
        assert not ok and "box coverage" in why, why

    def test_scalar_and_empty_tensors_commit(self, tmp_path):
        """0-d boxes (index []) and 0-size tensors must pass both proofs
        — trainer states carry scalar step counters."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = {
            "w|0": np.arange(30000, dtype=np.float32),
            "step|0": np.int64(7),  # 0-d
            "empty|0": np.zeros((0, 3), dtype=np.float32),
        }
        _save_sliced_world(storage, d, state, 1, 2)
        assert shard_file.latest_step(storage, d) == 1

    def test_incremental_refs_of_small_replicated_tensors_commit(
        self, tmp_path
    ):
        """An unsliced ref writes an EMPTY payload; the proof must read
        the covered range from the ref meta's full_nbytes, or every
        incremental save of a model with small replicated tensors (all
        of them) blocks commit from the second step on."""
        storage = PosixDiskStorage()
        d = str(tmp_path / "c")
        state = {
            "big|0": np.arange(50000, dtype=np.float32),
            "bias|0": np.arange(16, dtype=np.float32),  # < SLICE_MIN
        }
        trackers = [slicer.DirtyTracker() for _ in range(2)]
        _save_sliced_world(storage, d, state, 1, 2, trackers=trackers)
        _save_sliced_world(storage, d, state, 2, 2, trackers=trackers)
        ok, why = slicer.step_covers(storage, d, 2)
        assert ok, why
        # and the step actually committed (gate inside the helper)
        assert shard_file.latest_step(storage, d) == 2


class TestScaleoutObservability:
    def test_speed_monitor_scaleout_gauges(self):
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        sm.record_ckpt_stall(
            0.0, step=5, persist_mbps=80.0, agg_persist_mbps=320.0,
            tensors_skipped=14,
        )
        assert sm.ckpt_agg_persist_mbps == 320.0
        assert sm.ckpt_tensors_skipped == 14
        # multi-node: the fleet aggregate SUMS each node's last report
        # (never one node's sum masquerading as the fleet's)
        sm.record_ckpt_stall(
            0.0, agg_persist_mbps=80.0, tensors_skipped=2, node_id=1
        )
        assert sm.ckpt_agg_persist_mbps == 400.0
        assert sm.ckpt_tensors_skipped == 16
        # a node's newer report replaces its own older one
        sm.record_ckpt_stall(
            0.0, agg_persist_mbps=100.0, tensors_skipped=0, node_id=0
        )
        assert sm.ckpt_agg_persist_mbps == 180.0
        assert sm.ckpt_tensors_skipped == 2
        # throughput-only reports never touch stall bookkeeping
        assert sm.ckpt_stall_total == 0.0

    def test_diagnosis_surfaces_ckpt_perf_once_per_change(self):
        from dlrover_tpu.diagnosis.manager import DiagnosisManager
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        mgr = DiagnosisManager(speed_monitor=sm)
        mgr._surface_ckpt_perf()  # zero: nothing surfaced
        assert mgr._ckpt_perf_seen == (0.0, 0)
        sm.record_ckpt_stall(0.0, agg_persist_mbps=150.0,
                             tensors_skipped=3)
        mgr._surface_ckpt_perf()
        assert mgr._ckpt_perf_seen == (150.0, 3)

    def test_saver_aggregate_sums_rank_rows(self, monkeypatch):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        snap = {
            "persist_mbps_0": 80.0, "persist_mbps_1": 75.5,
            "tensors_skipped_0": 3, "tensors_skipped_1": 4,
            "stall_ms_0": 1.0,
        }
        monkeypatch.setattr(
            AsyncCheckpointSaver, "worker_perf", lambda self: snap
        )
        saver = AsyncCheckpointSaver.__new__(AsyncCheckpointSaver)
        assert saver.agg_persist_mbps() == pytest.approx(155.5)
        assert saver.tensors_skipped_total() == 7
