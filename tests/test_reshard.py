"""Live-reshard tests (ISSUE 6): planner proofs, mover substrates,
coordinator/trainer orchestration, master epoch machine, restore-to-any-
mesh.

Everything in this file is tier-1 (sub-second to a-few-seconds, virtual
CPU mesh from conftest); the cross-process chaos e2e lives in
``test_chaos_e2e.py`` (marker ``reshard+chaos+slow``).
"""

import numpy as np
import pytest

from dlrover_tpu.parallel.mesh import MeshSpec
from dlrover_tpu.reshard import plan as rp
from dlrover_tpu.reshard.mover import (
    LocalShardSource,
    ReshardMoveError,
    ReshardPeer,
    SegmentMover,
    check_segment_payload,
)

pytestmark = pytest.mark.reshard


# ---------------------------------------------------------------------------
# planner: pure-function proofs (zero processes, zero jax)
# ---------------------------------------------------------------------------


class TestBoxMath:
    def test_axis_chunks_even_uneven_empty(self):
        assert rp.axis_chunks(8, 2) == [(0, 4), (4, 8)]
        assert rp.axis_chunks(7, 3) == [(0, 3), (3, 6), (6, 7)]
        # dim smaller than parts: trailing chunks are empty
        assert rp.axis_chunks(5, 4) == [(0, 2), (2, 4), (4, 5), (5, 5)]
        assert rp.axis_chunks(3, 8)[-1] == (3, 3)
        assert rp.axis_chunks(6, 1) == [(0, 6)]

    def test_intersect_and_subtract_partition(self):
        box = ((0, 8), (0, 6))
        hole = ((2, 5), (1, 4))
        inter = rp.box_intersect(box, hole)
        assert inter == hole
        rest = rp.box_subtract(box, hole)
        # hole + remainders partition the box exactly
        assert rp.box_volume(hole) + sum(
            rp.box_volume(r) for r in rest
        ) == rp.box_volume(box)
        for i in range(len(rest)):
            assert rp.box_intersect(rest[i], hole) is None
            for j in range(i + 1, len(rest)):
                assert rp.box_intersect(rest[i], rest[j]) is None

    def test_zero_d_boxes(self):
        assert rp.box_volume(()) == 1
        assert rp.box_intersect((), ()) == ()
        assert rp.box_subtract((), ()) == []

    def test_disjoint_intersect_none(self):
        assert rp.box_intersect(((0, 2),), ((2, 4),)) is None


class TestShardBoxesVsJax:
    """Pin the planner's sharding semantics against jax's own
    ``addressable_devices_indices_map`` — the equivalence the whole plan
    correctness rests on."""

    CASES = [
        (MeshSpec(dp=2, tp=2), ("dp", "tp"), (6, 8)),
        (MeshSpec(dp=2, tp=2), (("dp", "tp"),), (12,)),
        (MeshSpec(fsdp=4), ("fsdp",), (8, 3)),
        (MeshSpec(dp=2, tp=2), (), (4, 4)),
        (MeshSpec(dp=4), (None, "dp"), (2, 12)),
        (MeshSpec(dp=2, tp=2), None, ()),
        (MeshSpec(pp=2, dp=2, tp=2), ("tp", "dp"), (4, 6)),
    ]

    def test_matches_indices_maps(self, cpu_mesh_devices):
        import jax  # noqa: F401
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.parallel.mesh import build_mesh

        for spec, pspec, shape in self.CASES:
            mesh = build_mesh(spec, cpu_mesh_devices[: spec.num_devices])
            jspec = P(*pspec) if pspec is not None else P()
            imap = NamedSharding(
                mesh, jspec
            ).addressable_devices_indices_map(shape)
            mine = rp.shard_boxes(shape, pspec, spec)
            for flat, dev in enumerate(mesh.devices.flat):
                sls = imap[dev]
                jbox = tuple(
                    (
                        0 if sl.start is None else sl.start,
                        dim if sl.stop is None else sl.stop,
                    )
                    for sl, dim in zip(sls, shape)
                )
                assert jbox == mine[flat], (spec, pspec, shape, flat)

    def test_layout_keys_match_flatten_to_shards(self, cpu_mesh_devices):
        """build_layout must key shards exactly like the checkpoint
        stager, or plans would not line up with arena/shard-file keys."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.checkpoint.tree_utils import flatten_to_shards
        from dlrover_tpu.parallel.mesh import build_mesh

        spec = MeshSpec(dp=2, tp=2)
        mesh = build_mesh(spec, cpu_mesh_devices[:4])
        state = {
            "a": jax.device_put(
                np.arange(48, dtype=np.float32).reshape(8, 6),
                NamedSharding(mesh, P("dp", "tp")),
            ),
            "b": jax.device_put(
                np.ones(5, np.float32), NamedSharding(mesh, P())
            ),
        }
        _tensors, infos = flatten_to_shards(state)
        shapes = {"['a']": (8, 6), "['b']": (5,)}
        layout = rp.build_layout(
            spec,
            {"['a']": ("dp", "tp"), "['b']": ()},
            shapes,
            ranks=[0],
        )
        expect = {
            key: tuple(tuple(p) for p in meta["index"])
            for key, meta in infos.items()
        }
        assert layout.shards[0] == expect


class TestPlanValidator:
    def _layouts(self, src_spec, src_p, dst_spec, dst_p, shape=(8, 4),
                 src_ranks=(0, 1), dst_ranks=(0, 1)):
        shapes = {"w": shape}
        dt = {"w": "float32"}
        src = rp.build_layout(
            src_spec, {"w": src_p}, shapes, dt, ranks=list(src_ranks)
        )
        dst = rp.build_layout(
            dst_spec, {"w": dst_p}, shapes, dt, ranks=list(dst_ranks)
        )
        return src, dst

    def test_exact_tiling_across_factorizations(self):
        cases = [
            (MeshSpec(dp=2), ("dp",), MeshSpec(dp=4), ("dp",), (0, 1),
             (0, 1, 2, 3)),
            (MeshSpec(dp=4), ("dp",), MeshSpec(dp=2), ("dp",),
             (0, 1, 2, 3), (0, 1)),
            (MeshSpec(dp=2, tp=2), ("dp", "tp"), MeshSpec(tp=4),
             (None, "tp"), (0, 1), (0,)),
            (MeshSpec(fsdp=2), ("fsdp",), MeshSpec(dp=2, tp=2),
             ("tp", "dp"), (0, 1), (0, 1, 2, 3)),
        ]
        for src_spec, sp, dst_spec, dp, sr, dr in cases:
            src, dst = self._layouts(
                src_spec, sp, dst_spec, dp, src_ranks=sr, dst_ranks=dr
            )
            plan = rp.build_plan(src, dst)  # validate=True inside
            st = plan.stats()
            assert st["segments"] > 0

    def test_replicated_leaf_moves_zero_cross_bytes(self):
        src, dst = self._layouts(
            MeshSpec(dp=2), (), MeshSpec(dp=2), ("dp",)
        )
        plan = rp.build_plan(src, dst)
        assert plan.stats()["cross_bytes"] == 0

    def test_uneven_to_even_split(self):
        src = rp.layout_from_tensors_info(
            {
                0: {"w|0": {"path": "w", "global_shape": [7],
                            "index": [[0, 5]], "dtype": "float32"}},
                1: {"w|0": {"path": "w", "global_shape": [7],
                            "index": [[5, 7]], "dtype": "float32"}},
            }
        )
        dst = rp.build_layout(
            MeshSpec(dp=1), {"w": ()}, {"w": (7,)}, {"w": "float32"},
            ranks=[0],
        )
        plan = rp.build_plan(src, dst)
        assert sum(s.nbytes for s in plan.segments) == 7 * 4

    def test_empty_and_scalar_tensors(self):
        shapes = {"e": (0, 4), "s": ()}
        specs = {"e": (), "s": ()}
        dt = {"e": "float32", "s": "int64"}
        src = rp.build_layout(MeshSpec(dp=2), specs, shapes, dt,
                              ranks=[0, 1])
        dst = rp.build_layout(MeshSpec(dp=4), specs, shapes, dt,
                              ranks=[0, 1, 2, 3])
        plan = rp.build_plan(src, dst)
        # scalar: one segment per dst rank; empty tensor: none at all
        assert all(s.path == "s" for s in plan.segments)

    def test_uncovered_target_raises(self):
        src = rp.layout_from_tensors_info(
            {0: {"w|0": {"path": "w", "global_shape": [8],
                         "index": [[0, 4]], "dtype": "float32"}}}
        )
        dst = rp.build_layout(
            MeshSpec(dp=1), {"w": ()}, {"w": (8,)}, {"w": "float32"},
            ranks=[0],
        )
        with pytest.raises(rp.PlanError, match="uncovered"):
            rp.build_plan(src, dst)

    def test_validator_rejects_overlap_and_bad_source(self):
        src, dst = self._layouts(
            MeshSpec(dp=2), ("dp",), MeshSpec(dp=2), ("dp",)
        )
        plan = rp.build_plan(src, dst)
        seg = plan.segments[0]
        # duplicate segment -> covered twice
        bad = rp.ReshardPlan(
            src=src, dst=dst, segments=plan.segments + [seg]
        )
        with pytest.raises(rp.PlanError):
            bad.validate()
        # segment pointing at a shard its rank does not hold
        import dataclasses

        rogue = dataclasses.replace(seg, src_rank=max(src.ranks()) + 7)
        with pytest.raises(rp.PlanError, match="does not hold"):
            rp.ReshardPlan(
                src=src, dst=dst,
                segments=[rogue] + plan.segments[1:],
            ).validate()

    def test_dtype_change_rejected(self):
        src = rp.layout_from_tensors_info(
            {0: {"w|0": {"path": "w", "global_shape": [4],
                         "index": [[0, 4]], "dtype": "float32"}}}
        )
        dst = rp.build_layout(
            MeshSpec(dp=1), {"w": ()}, {"w": (4,)}, {"w": "int32"},
            ranks=[0],
        )
        with pytest.raises(rp.PlanError, match="dtype"):
            rp.build_plan(src, dst)

    def test_byte_range_fast_path_matches_buffer(self):
        """Contiguous segments' (offset, length) must address exactly the
        right bytes of the source shard's C-order buffer."""
        W = np.arange(48, dtype=np.float32).reshape(8, 6)
        src = rp.build_layout(
            MeshSpec(dp=2), {"w": ("dp",)}, {"w": (8, 6)},
            {"w": "float32"}, ranks=[0, 1],
        )
        dst = rp.build_layout(
            MeshSpec(dp=4), {"w": ("dp",)}, {"w": (8, 6)},
            {"w": "float32"}, ranks=[0, 1, 2, 3],
        )
        plan = rp.build_plan(src, dst)
        assert plan.stats()["contiguous_segments"] == len(plan.segments)
        for seg in plan.segments:
            sls = tuple(slice(s, e) for s, e in seg.src_box)
            shard_bytes = np.ascontiguousarray(W[sls]).tobytes()
            off, ln = seg.byte_range
            want = np.ascontiguousarray(
                W[tuple(slice(s, e) for s, e in seg.box)]
            ).tobytes()
            assert shard_bytes[off:off + ln] == want

    def test_strided_segment_has_no_byte_range(self):
        # tp split of dim1: the overlap is strided in the source buffer
        src = rp.build_layout(
            MeshSpec(dp=2), {"w": ("dp",)}, {"w": (4, 8)},
            {"w": "float32"}, ranks=[0],
        )
        dst = rp.build_layout(
            MeshSpec(tp=2), {"w": (None, "tp")}, {"w": (4, 8)},
            {"w": "float32"}, ranks=[0],
        )
        plan = rp.build_plan(src, dst)
        strided = [s for s in plan.segments if s.byte_range is None]
        assert strided, "expected at least one strided segment"

    def test_ranks_needed_selects_subset(self):
        infos = {
            r: {
                "w|0": {
                    "path": "w", "global_shape": [16],
                    "index": [[r * 4, r * 4 + 4]], "dtype": "float32",
                }
            }
            for r in range(4)
        }
        # target wants rows 0..8 -> ranks 0 and 1 only
        need = rp.ranks_needed(infos, {"w": [((0, 8),)]})
        assert need == [0, 1]
        # replicated source: everyone holds everything -> one rank
        rep = {
            r: {"w|0": {"path": "w", "global_shape": [16],
                        "index": [[0, 16]], "dtype": "float32"}}
            for r in range(4)
        }
        need = rp.ranks_needed(rep, {"w": [((0, 16),)]}, dst_rank=2)
        assert need == [2]  # prefer-local picks the asking rank's copy


# ---------------------------------------------------------------------------
# property suite: resharded tree == fresh device_put reference
# ---------------------------------------------------------------------------


class TestReshardByteIdentity:
    """ISSUE 6 acceptance: across dp/tp factorizations, uneven->even
    splits, replicated leaves and empty/0-d tensors, the resharded tree
    is byte-identical to placing the original host arrays directly onto
    the target mesh."""

    PAIRS = [
        (MeshSpec(dp=2), MeshSpec(dp=4)),
        (MeshSpec(dp=4), MeshSpec(dp=2)),
        (MeshSpec(fsdp=2), MeshSpec(fsdp=8)),
        (MeshSpec(dp=2, tp=2), MeshSpec(dp=4, tp=2)),
        (MeshSpec(dp=2, tp=2), MeshSpec(tp=2)),
        (MeshSpec(tp=4), MeshSpec(dp=2, tp=2)),
    ]

    def _state(self, mesh, spec):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(arr, pspec):
            return jax.device_put(arr, NamedSharding(mesh, pspec))

        dpax = "dp" if spec.dp > 1 else (
            "fsdp" if spec.fsdp > 1 else None
        )
        tpax = "tp" if spec.tp > 1 else None
        host = {
            "w": np.arange(16 * 8, dtype=np.float32).reshape(16, 8),
            "v": np.arange(32, dtype=np.int32),
            "rep": np.linspace(0, 1, 24, dtype=np.float32).reshape(6, 4),
            "scalar": np.float32(3.5),
            "empty": np.zeros((0, 3), np.float32),
        }
        specs = {
            "w": P(dpax, tpax),
            "v": P(tpax) if tpax else P(dpax),
            "rep": P(),
            "scalar": P(),
            "empty": P(),
        }
        state = {k: put(host[k], specs[k]) for k in host}
        return host, specs, state

    def test_byte_identity_across_mesh_pairs(self, cpu_mesh_devices):
        import jax
        from jax.sharding import NamedSharding

        from dlrover_tpu.parallel.mesh import build_mesh
        from dlrover_tpu.reshard.coordinator import reshard_state

        for src_spec, dst_spec in self.PAIRS:
            src_mesh = build_mesh(
                src_spec, cpu_mesh_devices[: src_spec.num_devices]
            )
            dst_mesh = build_mesh(
                dst_spec, cpu_mesh_devices[: dst_spec.num_devices]
            )
            host, specs, state = self._state(src_mesh, src_spec)
            new_state, outcome = reshard_state(state, dst_mesh)
            assert outcome.ok and outcome.segments > 0
            for k, arr in new_state.items():
                np.testing.assert_array_equal(
                    np.asarray(arr), host[k],
                    err_msg=f"{src_spec}->{dst_spec}:{k}",
                )
                # shard-for-shard identical to a fresh device_put with
                # the leaf's spec re-expressed on the target mesh
                ref = jax.device_put(
                    host[k],
                    NamedSharding(dst_mesh, new_state[k].sharding.spec),
                )
                for got, want in zip(
                    arr.addressable_shards, ref.addressable_shards
                ):
                    assert got.device == want.device
                    np.testing.assert_array_equal(
                        np.asarray(got.data), np.asarray(want.data)
                    )


    @pytest.mark.parametrize("src_dp,dst_dp", [(2, 4), (4, 2)])
    def test_a_resize_moves_each_byte_of_the_state_once(
        self, cpu_mesh_devices, src_dp, dst_dp
    ):
        """2->4 and 4->2: the plan tiles every distinct target box
        exactly once, so what the move reports moved is the byte size
        of the state, a replicated leaf once; nothing crosses a process
        here; and the state is equal after the move."""
        from dlrover_tpu.parallel.mesh import build_mesh
        from dlrover_tpu.reshard.coordinator import reshard_state

        src_spec, dst_spec = MeshSpec(dp=src_dp), MeshSpec(dp=dst_dp)
        src_mesh = build_mesh(src_spec, cpu_mesh_devices[:src_dp])
        dst_mesh = build_mesh(dst_spec, cpu_mesh_devices[:dst_dp])
        host, _, state = self._state(src_mesh, src_spec)
        new_state, outcome = reshard_state(state, dst_mesh)
        assert outcome.ok and outcome.segments > 0
        state_bytes = sum(a.nbytes for a in host.values())
        assert round(outcome.moved_mb * (1 << 20)) == state_bytes > 0
        assert outcome.moved_cross_mb == 0  # one process holds it all
        for k, arr in new_state.items():
            np.testing.assert_array_equal(np.asarray(arr), host[k])


def spec_size(spec, axis):
    return getattr(spec, axis, 1)


# ---------------------------------------------------------------------------
# mover: substrates + verification + chaos
# ---------------------------------------------------------------------------


def _split_state(W, layout, rank, path="w"):
    tensors, infos = {}, {}
    for key, box in layout.shards[rank].items():
        sls = tuple(slice(s, e) for s, e in box)
        tensors[key] = W[sls]
        infos[key] = {
            "path": path,
            "global_shape": list(W.shape),
            "index": [list(p) for p in box],
        }
    return tensors, infos


class TestMover:
    def _plan(self):
        W = np.arange(64, dtype=np.float32).reshape(16, 4)
        src = rp.build_layout(
            MeshSpec(dp=2), {"w": ("dp",)}, {"w": W.shape},
            {"w": "float32"}, ranks=[0, 1],
        )
        dst = rp.build_layout(
            MeshSpec(dp=4), {"w": ("dp",)}, {"w": W.shape},
            {"w": "float32"}, ranks=[0, 1, 2, 3],
        )
        return W, src, dst, rp.build_plan(src, dst)

    def test_local_equivalence_every_dst_rank(self):
        W, src, dst, plan = self._plan()
        sources = {
            r: LocalShardSource(*_split_state(W, src, r))
            for r in src.ranks()
        }
        for r in dst.ranks():
            tensors, infos, stats = SegmentMover(r, sources).execute(plan)
            for key, box in dst.shards[r].items():
                sls = tuple(slice(s, e) for s, e in box)
                np.testing.assert_array_equal(tensors[key], W[sls])
            assert stats["cross_bytes"] == 0  # all sources local here

    def test_missing_rank_without_fetch_raises(self):
        W, src, dst, plan = self._plan()
        only0 = {0: LocalShardSource(*_split_state(W, src, 0))}
        with pytest.raises(ReshardMoveError, match="unreachable"):
            SegmentMover(3, only0).execute(plan)

    def test_rpc_pull_with_crc(self):
        W, src, dst, plan = self._plan()
        server = ReshardPeer(rank=1)
        puller = ReshardPeer(rank=3)
        try:
            t1, i1 = _split_state(W, src, 1)
            server.publish(epoch=5, step=20, tensors=t1, infos=i1)
            mover = SegmentMover(
                3,
                {0: LocalShardSource(*_split_state(W, src, 0))},
                fetch=lambda seg: puller.fetch_segment(
                    seg, epoch=5, step=20, addr=server.addr
                ),
            )
            tensors, infos, stats = mover.execute(plan)
            for key, box in dst.shards[3].items():
                sls = tuple(slice(s, e) for s, e in box)
                np.testing.assert_array_equal(tensors[key], W[sls])
            assert stats["cross_bytes"] > 0
            # epoch mismatch is refused, not served stale
            with pytest.raises(ReshardMoveError, match="lost in flight"):
                puller.fetch_segment(
                    plan.for_dst_rank(3)[0], epoch=6, step=20,
                    addr=server.addr,
                )
        finally:
            server.stop()
            puller.stop()

    def test_torn_payload_rejected(self):
        from dlrover_tpu.common import messages as m

        _W, _src, _dst, plan = self._plan()
        seg = next(s for s in plan.segments if s.nbytes > 0)
        good = np.zeros(
            tuple(e - s for s, e in seg.box), np.float32
        ).tobytes()
        resp = m.ReshardSegment(
            found=True, payload=good, crc32=12345,  # wrong CRC
            dtype="float32", shape=[e - s for s, e in seg.box],
        )
        with pytest.raises(ReshardMoveError, match="CRC"):
            check_segment_payload(resp, seg)
        # wrong shape is a mismatch even with a valid CRC
        from dlrover_tpu.checkpoint.shard_file import crc32_bytes

        resp2 = m.ReshardSegment(
            found=True, payload=good, crc32=crc32_bytes(good),
            dtype="float32", shape=[1, 1],
        )
        with pytest.raises(ReshardMoveError, match="shape"):
            check_segment_payload(resp2, seg)


class TestReshardChaos:
    """Seeded-determinism units for the three reshard chaos sites."""

    def setup_method(self):
        from dlrover_tpu import chaos

        chaos.reset()

    def teardown_method(self):
        from dlrover_tpu import chaos

        chaos.reset()

    def test_drop_segment_fails_the_move(self):
        from dlrover_tpu import chaos

        W = np.arange(64, dtype=np.float32).reshape(16, 4)
        src = rp.build_layout(
            MeshSpec(dp=2), {"w": ("dp",)}, {"w": W.shape},
            {"w": "float32"}, ranks=[0, 1],
        )
        dst = rp.build_layout(
            MeshSpec(dp=1), {"w": ("dp",)}, {"w": W.shape},
            {"w": "float32"}, ranks=[0],
        )
        plan = rp.build_plan(src, dst)
        server = ReshardPeer(rank=1)
        puller = ReshardPeer(rank=0)
        try:
            server.publish(3, 1, *_split_state(W, src, 1))
            mover = SegmentMover(
                0,
                {0: LocalShardSource(*_split_state(W, src, 0))},
                fetch=lambda seg: puller.fetch_segment(
                    seg, epoch=3, step=1, addr=server.addr
                ),
            )
            chaos.configure("reshard.drop_segment:times=1")
            with pytest.raises(ReshardMoveError, match="dropped"):
                mover.execute(plan)
            assert chaos.active_plan().stats()[
                "reshard.drop_segment"
            ] == 1
            # one-shot: the retry succeeds (fall back then retry works)
            tensors, _infos, _stats = mover.execute(plan)
            np.testing.assert_array_equal(tensors["w|0"], W)
        finally:
            server.stop()
            puller.stop()

    def test_stall_peer_delays_but_completes(self):
        from dlrover_tpu import chaos

        W = np.arange(16, dtype=np.float32).reshape(4, 4)
        src = rp.build_layout(
            MeshSpec(dp=2), {"w": ("dp",)}, {"w": W.shape},
            {"w": "float32"}, ranks=[0, 1],
        )
        dst = rp.build_layout(
            MeshSpec(dp=1), {"w": ()}, {"w": W.shape},
            {"w": "float32"}, ranks=[0],
        )
        plan = rp.build_plan(src, dst)
        server = ReshardPeer(rank=1)
        puller = ReshardPeer(rank=0)
        try:
            server.publish(1, -1, *_split_state(W, src, 1))
            chaos.configure("reshard.stall_peer:delay=300ms,times=1")
            mover = SegmentMover(
                0,
                {0: LocalShardSource(*_split_state(W, src, 0))},
                fetch=lambda seg: puller.fetch_segment(
                    seg, epoch=1, addr=server.addr
                ),
            )
            tensors, _i, _s = mover.execute(plan)
            assert chaos.active_plan().stats() == {
                "reshard.stall_peer": 1}
            np.testing.assert_array_equal(tensors["w|0"], W)
        finally:
            server.stop()
            puller.stop()

    def test_decisions_deterministic_under_seed(self):
        from dlrover_tpu.chaos.plan import FaultPlan

        def firing_pattern(seed):
            plan = FaultPlan.parse(
                f"reshard.drop_segment:p=0.4,times=-1,seed={seed}"
            )
            return [
                plan.fire("reshard.drop_segment") is not None
                for _ in range(40)
            ]

        assert firing_pattern(11) == firing_pattern(11)
        assert firing_pattern(11) != firing_pattern(12)

    def test_crash_mid_move_kills_process(self, cpu_mesh_subprocess):
        """The crash site hard-exits with the reshard exit code — proven
        in a throwaway subprocess via the shared cpu-mesh helper."""
        code = (
            "import numpy as np\n"
            "from dlrover_tpu.parallel.mesh import MeshSpec\n"
            "from dlrover_tpu.reshard import plan as rp\n"
            "from dlrover_tpu.reshard.mover import (LocalShardSource,"
            " SegmentMover)\n"
            "W = np.arange(16, dtype=np.float32)\n"
            "src = rp.build_layout(MeshSpec(dp=2), {'w': ('dp',)},"
            " {'w': (16,)}, {'w': 'float32'}, ranks=[0, 1])\n"
            "dst = rp.build_layout(MeshSpec(dp=1), {'w': ()},"
            " {'w': (16,)}, {'w': 'float32'}, ranks=[0])\n"
            "plan = rp.build_plan(src, dst)\n"
            "tensors = {'w|0': W[:8], 'w|1': W[8:]}\n"
            "infos = {'w|0': {'path': 'w', 'global_shape': [16],"
            " 'index': [[0, 8]]}, 'w|1': {'path': 'w',"
            " 'global_shape': [16], 'index': [[8, 16]]}}\n"
            "srcs = {0: LocalShardSource({'w|0': W[:8]},"
            " {'w|0': infos['w|0']}), 1: LocalShardSource("
            "{'w|1': W[8:]}, {'w|1': infos['w|1']})}\n"
            "SegmentMover(0, srcs).execute(plan)\n"
            "print('UNREACHABLE')\n"
        )
        proc = cpu_mesh_subprocess(
            code, devices=2,
            env_extra={"DLROVER_TPU_FAULTS": "reshard.crash_mid_move:step=1"},
            timeout=120,
        )
        from dlrover_tpu.chaos.plan import EXIT_RESHARD_CRASH

        assert proc.returncode == EXIT_RESHARD_CRASH, (
            proc.stdout, proc.stderr
        )
        assert "UNREACHABLE" not in proc.stdout


# ---------------------------------------------------------------------------
# coordinator + trainer orchestration
# ---------------------------------------------------------------------------


class TestCoordinator:
    def test_reshard_state_roundtrip(self, cpu_mesh_devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.parallel.mesh import build_mesh
        from dlrover_tpu.reshard.coordinator import reshard_state

        mesh2 = build_mesh(MeshSpec(fsdp=2), cpu_mesh_devices[:2])
        mesh4 = build_mesh(MeshSpec(fsdp=4), cpu_mesh_devices[:4])
        host = np.arange(32, dtype=np.float32).reshape(8, 4)
        state = {
            "w": jax.device_put(host, NamedSharding(mesh2, P("fsdp"))),
            "step": jax.device_put(
                np.int64(9), NamedSharding(mesh2, P())
            ),
        }
        up, o1 = reshard_state(state, mesh4, epoch=1)
        down, o2 = reshard_state(up, mesh2, epoch=2)
        np.testing.assert_array_equal(np.asarray(down["w"]), host)
        assert int(np.asarray(down["step"])) == 9
        assert o1.ok and o2.ok and o1.epoch == 1

    def test_failure_raises_reshard_error(self, cpu_mesh_devices):
        """A source that cannot cover the target must surface as
        ReshardError (the restart-ladder trigger), not silently corrupt."""
        from dlrover_tpu.reshard.coordinator import (
            ReshardError,
            reshard_shards,
        )

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(MeshSpec(dp=2), cpu_mesh_devices[:2])
        target = {
            "w": jax.ShapeDtypeStruct(
                (8,), np.float32, sharding=NamedSharding(mesh, P())
            )
        }
        tensors = {"['w']|0": np.zeros(4, np.float32)}
        infos = {
            "['w']|0": {
                "path": "['w']", "global_shape": [8], "index": [[0, 4]],
            }
        }
        with pytest.raises(ReshardError, match="plan failed"):
            reshard_shards(tensors, infos, target)

    def test_trainer_reshard_live(self, cpu_mesh_devices):
        """ElasticTrainer.reshard_live carries state across a 4->2
        rebuild through the plan/mover path and keeps training."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).parent))
        from test_trainer import _quadratic_trainer

        from dlrover_tpu.parallel.accelerate import Strategy

        trainer = _quadratic_trainer(
            cpu_mesh_devices[:4], global_batch=16, max_micro=16
        )
        trainer.build(num_processes=1, process_id=0)
        for _, _m in zip(range(3), trainer.epoch()):
            pass
        step_before = trainer.step
        w_before = np.asarray(trainer.state["params"]["w"]).copy()

        trainer.devices = cpu_mesh_devices[:2]
        trainer.base_strategy = Strategy(mesh=MeshSpec(dp=2))
        outcome = trainer.reshard_live(num_processes=1, process_id=0)
        assert outcome.ok
        assert trainer.step == step_before
        np.testing.assert_array_equal(
            np.asarray(trainer.state["params"]["w"]), w_before
        )
        for _, _m in zip(range(2), trainer.epoch()):
            pass
        assert trainer.step == step_before + 2

    def test_trainer_reshard_live_falls_to_ladder_on_chaos(
        self, cpu_mesh_devices, tmp_path
    ):
        """Tier-1 version of the chaos acceptance path: a dropped segment
        mid-move fails the live reshard loudly; the caller falls back to
        the checkpoint-restart ladder (build + engine restore) and the
        restored state is the checkpointed one with fsck-clean storage."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).parent))
        from test_trainer import _quadratic_trainer

        import jax

        from dlrover_tpu import chaos
        from dlrover_tpu.checkpoint import fsck as fsck_mod
        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.parallel.accelerate import Strategy
        from dlrover_tpu.reshard import coordinator as coord
        from dlrover_tpu.reshard.coordinator import ReshardError

        trainer = _quadratic_trainer(
            cpu_mesh_devices[:4], global_batch=16, max_micro=16
        )
        trainer.build(num_processes=1, process_id=0)
        for _, _m in zip(range(3), trainer.epoch()):
            pass
        ckpt_dir = str(tmp_path / "ckpt")
        eng = CheckpointEngine(ckpt_dir, job_name="rsfallback")
        eng.save_to_storage(trainer.step, trainer.state)
        assert eng.wait(120)

        # Make the live path fail deterministically: reshard_shards
        # raises (simulating a lost segment mid-move).
        real = coord.reshard_shards

        def boom(*a, **k):
            raise ReshardError("reshard move failed: chaos: segment "
                               "dropped")

        coord.reshard_shards = boom
        try:
            trainer.devices = cpu_mesh_devices[:2]
            trainer.base_strategy = Strategy(mesh=MeshSpec(dp=2))
            with pytest.raises(ReshardError, match="segment"):
                trainer.reshard_live(num_processes=1, process_id=0)
        finally:
            coord.reshard_shards = real
        # The ladder: rebuild fresh + restore from the committed step.
        trainer.state = None
        trainer.build(num_processes=1, process_id=0)
        target = jax.tree_util.tree_map(lambda x: x, trainer.state)
        got = eng.load(target)
        assert got is not None
        trainer.state, _meta = got
        assert trainer.step == 3
        for _, _m in zip(range(2), trainer.epoch()):
            pass
        assert trainer.step == 5
        # No torn state escaped: storage verifies end to end.
        assert fsck_mod.main([ckpt_dir]) == 0
        eng.close()
        chaos.reset()


# ---------------------------------------------------------------------------
# master epoch machine + control plane
# ---------------------------------------------------------------------------


class TestReshardManager:
    def _mgr(self):
        from dlrover_tpu.master.reshard import ReshardManager

        clock = {"t": 100.0}
        mgr = ReshardManager(clock=lambda: clock["t"])
        return mgr, clock

    def test_announce_report_done(self):
        mgr, _clock = self._mgr()
        from dlrover_tpu.common import messages as m
        from dlrover_tpu.master import reshard as rs

        epoch = mgr.announce(4, {"fsdp": 4}, expected_reports=2)
        info = mgr.info()
        assert info.status == rs.PREPARING
        assert info.target_num_processes == 4
        assert info.target_spec == {"fsdp": 4}
        for node in (0, 1):
            resp = mgr.report(
                m.ReshardReport(node_id=node, epoch=epoch, ok=True)
            )
            assert resp.success
        assert mgr.status == rs.DONE

    def test_any_failure_aborts(self):
        mgr, _clock = self._mgr()
        from dlrover_tpu.common import messages as m
        from dlrover_tpu.master import reshard as rs

        epoch = mgr.announce(2, expected_reports=2)
        mgr.report(m.ReshardReport(node_id=0, epoch=epoch, ok=True))
        mgr.report(
            m.ReshardReport(
                node_id=1, epoch=epoch, ok=False, reason="move failed"
            )
        )
        assert mgr.status == rs.ABORTED

    def test_deadline_lapse_aborts(self):
        mgr, clock = self._mgr()
        from dlrover_tpu.master import reshard as rs

        mgr.announce(2, expected_reports=2, deadline_s=30.0)
        assert mgr.status == rs.PREPARING
        clock["t"] += 31.0
        assert mgr.status == rs.ABORTED

    def test_stale_epoch_report_rejected(self):
        mgr, _clock = self._mgr()
        from dlrover_tpu.common import messages as m

        mgr.announce(2, expected_reports=1)
        epoch2 = mgr.announce(4, expected_reports=1)
        resp = mgr.report(
            m.ReshardReport(node_id=0, epoch=epoch2 - 1, ok=True)
        )
        assert not resp.success and "stale" in resp.reason

    def test_servicer_dispatch(self):
        from dlrover_tpu.common import messages as m
        from dlrover_tpu.master.reshard import ReshardManager
        from dlrover_tpu.master.servicer import MasterServicer

        mgr = ReshardManager()
        servicer = MasterServicer(reshard_manager=mgr)
        info = servicer(m.ReshardEpochRequest(node_id=0))
        assert isinstance(info, m.ReshardEpochInfo)
        assert info.status == "idle"
        epoch = mgr.announce(2, expected_reports=1)
        info = servicer(m.ReshardEpochRequest(node_id=0))
        assert info.status == "preparing" and info.epoch == epoch
        resp = servicer(
            m.ReshardReport(node_id=0, epoch=epoch, ok=True,
                            downtime_ms=12.0)
        )
        assert resp.success
        # a master without the manager answers idle / refuses reports
        bare = MasterServicer()
        assert bare(m.ReshardEpochRequest()).epoch == -1
        assert not bare(m.ReshardReport(epoch=1)).success


class TestAutoScalerLiveResize:
    """The two-phase resize hold in AllreduceTrainingAutoScaler."""

    class _FakeManager:
        def __init__(self):
            self.scaled_to = []

        def alive_workers(self):
            return [0, 1]

        def pending_workers(self):
            return []

        def scale_workers_to(self, n):
            self.scaled_to.append(n)
            return n

    def _scaler(self, reshard_mgr):
        from dlrover_tpu.master.job_auto_scaler import (
            AllreduceTrainingAutoScaler,
        )
        from dlrover_tpu.scheduler.job import JobArgs

        job_args = JobArgs(job_name="rs-test")
        job_args.workers.count = 2
        job_args.workers.min_count = 1
        job_args.workers.max_count = 8

        class _Speed:
            def running_speed(self):
                return 0.0

        jm = self._FakeManager()
        scaler = AllreduceTrainingAutoScaler(
            job_args, jm, _Speed(), None, interval=3600,
            reshard_manager=reshard_mgr,
        )
        return scaler, jm

    def test_shrink_announces_holds_then_releases_surplus(self):
        from dlrover_tpu.master.reshard import ReshardManager
        from dlrover_tpu.common import messages as m

        mgr = ReshardManager()
        mgr.info()  # a worker is polling -> live path is armed
        scaler, jm = self._scaler(mgr)
        assert scaler._resize(alive=2, target=1) == 0
        assert mgr.status == "preparing"
        assert jm.scaled_to == []  # held: no process-level scaling yet
        assert scaler.scale_once() == 0  # still preparing -> hold
        for node in (0, 1):
            mgr.report(
                m.ReshardReport(node_id=node, epoch=mgr.epoch, ok=True)
            )
        # DONE: survivors resharded live; the now-state-free surplus
        # worker is released (that release is not a restart of anyone).
        assert scaler.scale_once() == 1
        assert jm.scaled_to == [1]
        assert scaler._pending_resize is None

    def test_resize_falls_back_on_abort(self):
        from dlrover_tpu.master.reshard import ReshardManager
        from dlrover_tpu.common import messages as m

        mgr = ReshardManager()
        mgr.info()
        scaler, jm = self._scaler(mgr)
        scaler._resize(alive=2, target=1)
        mgr.report(
            m.ReshardReport(
                node_id=0, epoch=mgr.epoch, ok=False, reason="nope"
            )
        )
        assert scaler.scale_once() == 1  # restart ladder applied
        assert jm.scaled_to == [1]

    def test_grow_always_restart_scales(self):
        """New processes must be provisioned + rendezvous'd before bytes
        could move into them — grow never takes the live path."""
        from dlrover_tpu.master.reshard import ReshardManager

        mgr = ReshardManager()
        mgr.info()
        scaler, jm = self._scaler(mgr)
        assert scaler._resize(alive=2, target=4) == 4
        assert jm.scaled_to == [4]
        assert scaler._pending_resize is None

    def test_no_observers_scales_directly(self):
        """A job whose training loop never polls the epoch must not pay
        the announce deadline on every resize."""
        from dlrover_tpu.master.reshard import ReshardManager

        mgr = ReshardManager()  # nobody ever called info()
        scaler, jm = self._scaler(mgr)
        assert scaler._resize(alive=2, target=1) == 1
        assert jm.scaled_to == [1]

    def test_knob_off_scales_directly(self, monkeypatch):
        from dlrover_tpu.common.global_context import get_context
        from dlrover_tpu.master.reshard import ReshardManager

        ctx = get_context()
        old = ctx.live_reshard
        try:
            ctx.update(live_reshard=False)
            scaler, jm = self._scaler(ReshardManager())
            assert scaler._resize(alive=2, target=4) == 4
            assert jm.scaled_to == [4]
        finally:
            ctx.update(live_reshard=old)


class TestBootstrapPoll:
    class _FakeClient:
        def __init__(self):
            from dlrover_tpu.common import messages as m

            self.info = m.ReshardEpochInfo(
                epoch=3, status="preparing", target_num_processes=4
            )
            self.reports = []

        def get_reshard_epoch(self):
            return self.info

        def report_reshard(self, epoch, ok, reason="", downtime_ms=0.0,
                           moved_mb=0.0):
            self.reports.append((epoch, ok, reason))
            return True

    def _ctx(self):
        from dlrover_tpu.trainer.bootstrap import ElasticContext

        ctx = ElasticContext.__new__(ElasticContext)
        ctx.client = self._FakeClient()
        ctx._last_reshard_poll = 0.0
        ctx._last_reshard_epoch = -1
        return ctx

    def test_poll_fires_once_per_epoch_and_throttles(self):
        ctx = self._ctx()
        info = ctx.poll_reshard()
        assert info is not None and info.epoch == 3
        # same epoch again: observed already
        ctx._last_reshard_poll = 0.0
        assert ctx.poll_reshard() is None
        # throttle: a fresh epoch inside the poll interval is not seen
        ctx.client.info.epoch = 4
        assert ctx.poll_reshard() is None
        ctx._last_reshard_poll = 0.0
        assert ctx.poll_reshard().epoch == 4

    def test_poll_ignores_idle_and_aborted(self):
        ctx = self._ctx()
        ctx.client.info.status = "aborted"
        assert ctx.poll_reshard() is None
        ctx._last_reshard_poll = 0.0
        ctx.client.info.status = "idle"
        assert ctx.poll_reshard() is None

    def test_report_paths(self):
        from dlrover_tpu.reshard.coordinator import ReshardOutcome

        ctx = self._ctx()
        ctx.report_reshard(
            3, ReshardOutcome(ok=True, downtime_s=0.5, segments=4)
        )
        ctx.report_reshard(3, None, error="segment lost")
        assert ctx.client.reports[0][:2] == (3, True)
        assert ctx.client.reports[1] == (3, False, "segment lost")


# ---------------------------------------------------------------------------
# restore-to-any-mesh (the checkpoint engine's reuse of the plans)
# ---------------------------------------------------------------------------


class TestRestoreToAnyMesh:
    def _save_multirank_ckpt(self, tmp_path, world=4, dim=16):
        """Write a committed step as ``world`` ranks would: each rank's
        shard holds its dp-slice of ``w`` plus the replicated ``b``."""
        from dlrover_tpu.checkpoint import shard_file
        from dlrover_tpu.common.storage import PosixDiskStorage

        storage = PosixDiskStorage()
        ckpt_dir = str(tmp_path / "ckpt")
        W = np.arange(dim * 4, dtype=np.float32).reshape(dim, 4)
        B = np.linspace(0, 1, 8).astype(np.float32)
        step = 7
        per = dim // world
        for pid in range(world):
            lo, hi = pid * per, (pid + 1) * per
            tensors = {"['w']|0": W[lo:hi], "['b']|0": B}
            info = {
                "['w']|0": {
                    "path": "['w']", "global_shape": [dim, 4],
                    "index": [[lo, hi], [0, 4]],
                },
                "['b']|0": {
                    "path": "['b']", "global_shape": [8],
                    "index": [[0, 8]],
                },
            }
            extra = {
                "step": step, "meta": {}, "tensors_info": info,
                "process_id": pid, "num_processes": world,
            }
            shard_file.write_shard(
                storage, ckpt_dir, step, pid, tensors, extra
            )
            storage.write(b"", shard_file.done_path(ckpt_dir, step, pid))
        shard_file.commit(storage, ckpt_dir, step, keep_last=3)
        return ckpt_dir, W, B, step

    def test_engine_load_target_mesh(self, tmp_path, cpu_mesh_devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.parallel.mesh import build_mesh

        ckpt_dir, W, B, step = self._save_multirank_ckpt(tmp_path)
        mesh2 = build_mesh(MeshSpec(dp=2), cpu_mesh_devices[:2])
        mesh4 = build_mesh(MeshSpec(dp=4), cpu_mesh_devices[:4])
        # the target describes the OLD mesh; target_mesh re-homes it
        target = {
            "w": jax.ShapeDtypeStruct(
                W.shape, W.dtype, sharding=NamedSharding(mesh2, P("dp"))
            ),
            "b": jax.ShapeDtypeStruct(
                B.shape, B.dtype, sharding=NamedSharding(mesh2, P())
            ),
        }
        eng = CheckpointEngine(ckpt_dir, job_name="rt-mesh-test")
        got = eng.load(target, target_mesh=mesh4)
        assert got is not None
        restored, meta = got
        assert meta["step"] == step
        np.testing.assert_array_equal(np.asarray(restored["w"]), W)
        np.testing.assert_array_equal(np.asarray(restored["b"]), B)
        assert restored["w"].sharding.mesh.shape["dp"] == 4
        eng.close()

    def test_selective_shard_read(self, tmp_path, cpu_mesh_devices,
                                  monkeypatch):
        """The plan decides which ranks' shards to read: a target needing
        rows 0..8 of a 4-way-split tensor must read 2 shards, not 4."""
        from dlrover_tpu.checkpoint import shard_file
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        ckpt_dir, W, B, step = self._save_multirank_ckpt(tmp_path)
        # A target needing only the TOP half of w (+ replicated b),
        # expressed as raw boxes through the private selector (the same
        # shape load() derives from a real placeholder tree).
        eng = CheckpointEngine(ckpt_dir, job_name="rt-select-test")
        eng._restore_boxes = {
            "['w']": [((0, 8), (0, 4))],
            "['b']": [((0, 8),)],
        }
        piece_reads = []
        meta_reads = []
        real_pieces = shard_file.read_shard_pieces
        real_manifest = shard_file.read_shard_manifest

        def counting_pieces(storage, d, s, pid, **kw):
            piece_reads.append(pid)
            return real_pieces(storage, d, s, pid, **kw)

        def counting_manifest(storage, d, s, pid):
            meta_reads.append(pid)
            return real_manifest(storage, d, s, pid)

        monkeypatch.setattr(shard_file, "read_shard_pieces", counting_pieces)
        monkeypatch.setattr(
            shard_file, "read_shard_manifest", counting_manifest
        )
        pids = shard_file.list_shard_ids(eng.storage, ckpt_dir, step)
        chosen = eng._select_pids(step, pids)
        assert chosen == [0, 1]  # rows 0..8 live on ranks 0 and 1
        # and the full candidate walk reads data from only those two
        for _src, _extra, _sel in eng._storage_candidates():
            break
        assert set(piece_reads) == {0, 1}
        # the metas fetched during selection are REUSED on the read path:
        # exactly one header+meta read per shard, never two (the PR 6
        # double read is retired).
        assert sorted(meta_reads) == pids
        eng.close()

    def test_selection_falls_back_when_chosen_shard_corrupt(
        self, tmp_path, cpu_mesh_devices
    ):
        """Selection is bandwidth, never correctness: when the one chosen
        shard of a replicated tensor is rotten, the unselected replicas
        still restore the step."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.checkpoint import shard_file
        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.common.storage import PosixDiskStorage
        from dlrover_tpu.parallel.mesh import build_mesh

        storage = PosixDiskStorage()
        ckpt_dir = str(tmp_path / "ckpt")
        B = np.arange(32, dtype=np.float32)
        step = 3
        world = 3
        for pid in range(world):
            tensors = {"['b']|0": B}
            info = {
                "['b']|0": {
                    "path": "['b']", "global_shape": [32],
                    "index": [[0, 32]],
                }
            }
            shard_file.write_shard(
                storage, ckpt_dir, step, pid, tensors,
                {"step": step, "meta": {}, "tensors_info": info,
                 "process_id": pid, "num_processes": world},
            )
            storage.write(b"", shard_file.done_path(ckpt_dir, step, pid))
        shard_file.commit(storage, ckpt_dir, step, keep_last=3)

        eng = CheckpointEngine(ckpt_dir, job_name="rt-corrupt-sel")
        mesh1 = build_mesh(MeshSpec(dp=1), cpu_mesh_devices[:1])
        target = {
            "b": jax.ShapeDtypeStruct(
                B.shape, B.dtype, sharding=NamedSharding(mesh1, P())
            )
        }
        eng._restore_boxes = eng._target_boxes(target)
        pids = shard_file.list_shard_ids(storage, ckpt_dir, step)
        chosen = eng._select_pids(step, pids)
        assert len(chosen) == 1  # replicated: plan wants exactly one
        # rot exactly that shard
        path = shard_file.shard_path(ckpt_dir, step, chosen[0])
        raw = bytearray(storage.read(path))
        raw[-3] ^= 0xFF
        storage.write(bytes(raw), path)
        got = eng.load(target)
        assert got is not None
        restored, _meta = got
        np.testing.assert_array_equal(np.asarray(restored["b"]), B)
        eng.close()

    def test_read_shard_meta_roundtrip_and_damage(self, tmp_path):
        from dlrover_tpu.checkpoint import shard_file
        from dlrover_tpu.common.storage import PosixDiskStorage

        storage = PosixDiskStorage()
        ckpt_dir = str(tmp_path / "c")
        tensors = {"x|0": np.arange(6, dtype=np.float32)}
        info = {"x|0": {"path": "x", "global_shape": [6],
                        "index": [[0, 6]]}}
        shard_file.write_shard(
            storage, ckpt_dir, 1, 0, tensors,
            {"step": 1, "tensors_info": info, "process_id": 0,
             "num_processes": 1},
        )
        extra = shard_file.read_shard_meta(storage, ckpt_dir, 1, 0)
        assert extra["step"] == 1
        assert extra["tensors_info"] == info
        assert shard_file.read_shard_meta(storage, ckpt_dir, 1, 9) is None
        # meta damage raises the typed corruption error
        path = shard_file.shard_path(ckpt_dir, 1, 0)
        raw = bytearray(storage.read(path))
        raw[14] ^= 0xFF  # inside the meta region
        storage.write(bytes(raw), path)
        with pytest.raises(shard_file.ShardCorruptionError):
            shard_file.read_shard_meta(storage, ckpt_dir, 1, 0)


class TestArenaSource:
    """The intra-host substrate: the mover's source over a staged shm
    arena holds the tensors ``read()`` off the arena's file into arrays
    of its own (ISSUE 28: no bulk consumer walks the mapping)."""

    def test_from_arena_feeds_the_mover(self):
        from dlrover_tpu.common.shm import SharedMemoryArena

        W = np.arange(64, dtype=np.float32).reshape(16, 4)
        infos = {
            "w|0": {"path": "w", "global_shape": [16, 4],
                    "index": [[0, 16], [0, 4]]},
        }
        arena = SharedMemoryArena(
            f"rs_arena_test_{np.random.randint(1 << 30)}"
        )
        try:
            arena.write_state({"w|0": W}, extra={"tensors_info": infos,
                                                 "step": 2})
            src = LocalShardSource.from_arena(arena)
            # arrays of its own: the source outlives the arena's hold
            assert isinstance(src.tensors["w|0"], np.ndarray)
            assert src.tensors["w|0"].flags.owndata
            dst = rp.build_layout(
                MeshSpec(dp=2), {"w": ("dp",)}, {"w": (16, 4)},
                {"w": "float32"}, ranks=[0],
            )
            src_layout = rp.layout_from_tensors_info(
                {0: infos}, {"w": "float32"}
            )
            plan = rp.build_plan(src_layout, dst)
            tensors, _i, _s = SegmentMover(0, {0: src}).execute(plan)
            np.testing.assert_array_equal(tensors["w|0"], W[:8])
            np.testing.assert_array_equal(tensors["w|1"], W[8:])
            # the mover's outputs OWN their bytes (fresh buffers): a
            # later arena rewrite must not reach the resharded state
            arena.write_state(
                {"w|0": np.zeros_like(W)},
                extra={"tensors_info": infos, "step": 3},
            )
            np.testing.assert_array_equal(tensors["w|0"], W[:8])
            np.testing.assert_array_equal(src.tensors["w|0"], W)
        finally:
            arena.close(unlink=True)

    def test_from_arena_rejects_torn_state(self):
        from dlrover_tpu import chaos
        from dlrover_tpu.common.shm import SharedMemoryArena

        arena = SharedMemoryArena(
            f"rs_arena_torn_{np.random.randint(1 << 30)}"
        )
        try:
            arena.write_state(
                {"x|0": np.ones(4, np.float32)},
                extra={"tensors_info": {
                    "x|0": {"path": "x", "global_shape": [4],
                            "index": [[0, 4]]}}},
            )
            chaos.configure("shm.torn_read:times=1")
            with pytest.raises(ReshardMoveError, match="no staged"):
                LocalShardSource.from_arena(arena)
        finally:
            chaos.reset()
            arena.close(unlink=True)
