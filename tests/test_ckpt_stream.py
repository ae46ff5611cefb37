"""Flash-checkpoint fast path (ISSUE 4): streamed shard writer interop.

The streaming writer must be invisible to every consumer: byte-identical
v2 shards (``pack_shard`` is the reference implementation), fsck/verify/
unpack acceptance, chaos damage sites still firing, and — the acceptance
criterion — exactly one pass over the state bytes with zero intermediate
full-state copies, counted by the byte-audit test hook.
"""

import contextlib
import io
import os

import numpy as np
import pytest

from dlrover_tpu import chaos, obs
from dlrover_tpu.checkpoint import fsck, shard_file
from dlrover_tpu.common.byte_audit import audit
from dlrover_tpu.common.shm import SharedMemoryArena
from dlrover_tpu.common.storage import (
    CheckpointStorage,
    PosixDiskStorage,
    _BufferShardSink as _BufferShardSinkBase,
)


def _mixed_tensors():
    tensors = {
        "a|0": np.arange(3000, dtype=np.float32).reshape(50, 60),
        "b|0": np.array([True, False, True]),
        "c|0": np.asarray(np.int32(7)),  # 0-d scalar
        "d|0": np.zeros((0, 3), np.float64),  # empty
        "e|0": np.arange(64, dtype=np.int8)[::2],  # non-contiguous
        "f|0": (np.arange(257, dtype=np.uint16)),  # odd byte count
    }
    try:
        import ml_dtypes

        tensors["g|0"] = np.arange(128, dtype=np.float32).astype(
            ml_dtypes.bfloat16
        )
    except ImportError:
        pass
    return tensors


def _extra(step=3):
    return {
        "step": step,
        "meta": {"step": step},
        "tensors_info": {"a": 1},
        "process_id": 0,
        "num_processes": 1,
    }


def _stream_bytes(tmp_path, tensors, extra, **kw):
    st = PosixDiskStorage()
    path = str(tmp_path / "stream.ckpt")
    shard_file.ShardStreamWriter(st, path, tensors, extra, **kw).write()
    with open(path, "rb") as f:
        return f.read()


class TestByteIdentity:
    def test_mixed_dtypes_identical_to_pack_shard(self, tmp_path):
        tensors, extra = _mixed_tensors(), _extra()
        assert _stream_bytes(tmp_path, tensors, extra) == shard_file.pack_shard(
            tensors, extra
        )

    def test_parallel_workers_identical(self, tmp_path):
        tensors, extra = _mixed_tensors(), _extra()
        for w in (2, 4, 16):
            assert _stream_bytes(
                tmp_path, tensors, extra, workers=w
            ) == shard_file.pack_shard(tensors, extra)

    def test_tiny_chunks_identical(self, tmp_path):
        tensors, extra = _mixed_tensors(), _extra()
        # chunk floor is 64KB; exercise chunking with a tensor bigger
        # than one chunk.
        tensors["big|0"] = np.arange(100_000, dtype=np.float32)
        assert _stream_bytes(
            tmp_path, tensors, extra, chunk_bytes=1
        ) == shard_file.pack_shard(tensors, extra)

    def test_relayout_fallback_identical(self, tmp_path, monkeypatch):
        """A tensor CRC below 65536 narrows the msgpack meta, forcing the
        rare re-layout second pass.  Force it for every tensor by
        shrinking the placeholder and assert the fallback still lands
        byte-identical output."""
        tensors, extra = _mixed_tensors(), _extra()
        monkeypatch.setattr(shard_file, "_CRC_PLACEHOLDER", 1)
        audit.enable()
        data = _stream_bytes(tmp_path, tensors, extra)
        snap = audit.snapshot()
        audit.disable()
        assert data == shard_file.pack_shard(tensors, extra)
        assert snap["passes"].get("stream_relayout") == 1

    def test_empty_state_identical(self, tmp_path):
        assert _stream_bytes(tmp_path, {}, _extra()) == shard_file.pack_shard(
            {}, _extra()
        )

    def test_streamed_accepted_by_unpack_and_verify(self, tmp_path):
        tensors, extra = _mixed_tensors(), _extra()
        data = _stream_bytes(tmp_path, tensors, extra)
        assert shard_file.verify_shard(data) == extra
        out, ex = shard_file.unpack_shard(data)
        assert ex == extra
        for k, v in tensors.items():
            np.testing.assert_array_equal(out[k], np.asarray(v))
            assert out[k].shape == np.shape(v)


class TestSinglePassZeroCopy:
    """The acceptance hook: copies counted, passes counted."""

    def test_stream_is_single_pass_zero_copy(self, tmp_path):
        # All-contiguous tensors (the shm-arena case: views are always
        # contiguous) — the streamed write must materialize nothing.
        tensors = {
            f"w{i}|0": np.arange(50_000, dtype=np.float32) for i in range(4)
        }
        nbytes = sum(a.nbytes for a in tensors.values())
        audit.enable()
        _stream_bytes(tmp_path, tensors, _extra(), workers=2)
        snap = audit.snapshot()
        audit.disable()
        assert snap["copied_bytes"] == 0
        assert snap["written_bytes"] == nbytes  # exactly one write pass
        assert snap["passes"] == {"stream_data": 1}

    def test_legacy_pack_path_copies_three_times(self, tmp_path):
        tensors = {
            f"w{i}|0": np.arange(50_000, dtype=np.float32) for i in range(4)
        }
        nbytes = sum(a.nbytes for a in tensors.values())
        audit.enable()
        shard_file.pack_shard(tensors, _extra())
        snap = audit.snapshot()
        audit.disable()
        # tobytes + join; the arena read copy is the third (counted in
        # the arena test below).
        assert snap["copied_bytes"] == 2 * nbytes

    def test_arena_views_stream_zero_copy(self, tmp_path):
        """End-to-end: stage into a real shm arena, stream its
        copy=False views to a file — byte-identical to the pack path and
        zero copies."""
        arena = SharedMemoryArena(
            f"tckpt-stream-{os.getpid()}", create=True, size=1 << 22
        )
        try:
            staged = {
                "x|0": np.arange(30_000, dtype=np.float32),
                "c|0": np.asarray(np.int64(5)),
            }
            arena.write_state(staged, extra=_extra())
            copies, extra = arena.read_state(copy=True)
            audit.enable()
            views, extra2 = arena.read_state(copy=False)
            data = _stream_bytes(tmp_path, views, extra2)
            snap = audit.snapshot()
            audit.disable()
            assert data == shard_file.pack_shard(copies, extra)
            # Zero copies — the 0-d scalar's ascontiguousarray promotion
            # is a view, and the audit must not count it as a copy.
            assert snap["copied_bytes"] == 0
        finally:
            arena.close(unlink=True)


class _CheckingSink(_BufferShardSinkBase):
    """A sink that looks at every chunk AT ``write_at`` time: the bytes
    it is handed must be the file's bytes at that offset right now —
    which a reused buffer refilled before it was written would not be.
    Concurrent ``write_at`` is allowed, so range workers really fan
    out.  Also notes which buffers the chunks came from."""

    parallel_safe = True

    def __init__(self, want: bytes, seen_buffers: set):
        super().__init__()
        self._want = want
        self._seen = seen_buffers
        self.bad = []

    def write_at(self, data, offset):
        view = memoryview(data)
        if bytes(view) != self._want[offset : offset + len(view)]:
            self.bad.append(offset)
        if len(view) and isinstance(view.obj, np.ndarray):
            root = view.obj
            while root.base is not None and isinstance(root.base, np.ndarray):
                root = root.base
            self._seen.add((root.ctypes.data, root.nbytes))
        return super().write_at(data, offset)


class _CheckingStorage(PosixDiskStorage):
    def __init__(self, want: bytes):
        self.want = want
        self.buffers = set()
        self.sinks = []
        self.out = None

    @contextlib.contextmanager
    def stream_writer(self, path):
        sink = _CheckingSink(self.want, self.buffers)
        self.sinks.append(sink)
        yield sink
        self.out = sink.getvalue()


class TestArenaFedPersist:
    """ISSUE 28: tensors still in the shm arena reach the shard by
    ``read()`` into one reused chunk buffer a range worker — same file
    bytes as ``pack_shard``, same CRCs, one data pass, no state-sized
    buffer."""

    CHUNK = 1 << 16  # the writer's floor: the big tensors take 19 chunks

    @pytest.fixture
    def staged(self):
        tensors = {k: np.ascontiguousarray(v)
                   for k, v in _mixed_tensors().items()}
        rng = np.random.RandomState(28)
        for i in range(5):
            tensors[f"big{i}|0"] = rng.standard_normal(
                300_001 + i).astype(np.float32)
        info = {k: {"path": k.split("|")[0],
                    "global_shape": list(np.shape(v)),
                    "index": [[0, d] for d in np.shape(v)],
                    "owners": [0, 1]} for k, v in tensors.items()}
        extra = dict(_extra(), tensors_info=info, num_processes=2,
                     process_id=1)
        arena = SharedMemoryArena(f"tckpt-arenafed-{os.getpid()}")
        reader = SharedMemoryArena(arena.name)
        try:
            arena.write_state(tensors, extra=extra)
            copies, _ = reader.read_state(copy=True)
            handles, extra2 = reader.read_state(copy=False)
            assert extra2 == extra
            yield copies, handles, extra
        finally:
            reader.close()
            arena.close(unlink=True)

    @pytest.mark.parametrize("workers", [1, 4])
    @pytest.mark.parametrize("variant", ["whole", "sliced", "relayout"])
    def test_identical_to_pack_shard(self, tmp_path, monkeypatch, staged,
                                     variant, workers):
        from dlrover_tpu.checkpoint import slicer

        copies, handles, extra = staged
        meta_extra = None
        if variant == "sliced":
            plan = slicer.plan_persist(
                handles, extra, process_id=1, num_processes=2)
            ref = slicer.plan_persist(
                copies, extra, process_id=1, num_processes=2)
            assert plan.meta_extra == ref.meta_extra
            assert plan.layout == ref.layout and plan.extra == ref.extra
            assert 0 < plan.written_bytes == ref.written_bytes < (
                plan.logical_bytes)
            handles, copies, extra = plan.tensors, ref.tensors, plan.extra
            meta_extra = plan.meta_extra
        if variant == "relayout":
            monkeypatch.setattr(shard_file, "_CRC_PLACEHOLDER", 1)
        nbytes = sum(int(v.nbytes) for v in copies.values())
        st = PosixDiskStorage()
        path = str(tmp_path / "s.ckpt")
        audit.enable()
        try:
            stats = shard_file.ShardStreamWriter(
                st, path, handles, extra, workers=workers,
                chunk_bytes=self.CHUNK, meta_extra=meta_extra).write()
            snap = audit.snapshot()
        finally:
            audit.disable()
        assert open(path, "rb").read() == shard_file.pack_shard(
            copies, extra, meta_extra)
        assert stats["crcs"] == {
            k: shard_file.crc32_bytes(
                np.ascontiguousarray(v).reshape(-1).view(np.uint8))
            for k, v in copies.items()}
        passes = 2 if variant == "relayout" else 1
        assert stats["passes"] == passes
        assert stats["read_bytes"] == passes * nbytes
        # one data pass (two through the rare relayout), and no buffer
        # the size of the state anywhere
        assert snap["copied_bytes"] == 0
        assert snap["written_bytes"] == passes * nbytes
        assert snap["passes"] == dict(
            stream_data=1, **({"stream_relayout": 1} if passes == 2 else {}))

    @pytest.mark.parametrize("workers", [1, 4])
    def test_a_chunk_is_written_before_its_buffer_is_refilled(
            self, staged, workers):
        copies, handles, extra = staged
        st = _CheckingStorage(shard_file.pack_shard(copies, extra))
        stats = shard_file.ShardStreamWriter(
            st, "/ck/s.ckpt", handles, extra, workers=workers,
            chunk_bytes=self.CHUNK).write()
        assert st.out == st.want
        assert [s.bad for s in st.sinks] == [[]]
        # every tensor byte came through a range worker's one buffer,
        # none of them larger than a chunk
        assert (stats["workers"] > 1) == (workers > 1)
        assert 1 <= len(st.buffers) <= stats["workers"]
        assert all(n <= self.CHUNK for _addr, n in st.buffers)

    def test_dirty_probe_reads_through_the_primitive(self, staged):
        """An incremental save's fence probe CRCs a tensor that is still
        in the arena: same verdict as on arrays, for the whole tensor
        and for this rank's slice of it."""
        from dlrover_tpu.checkpoint import slicer

        copies, handles, extra = staged
        for sliced in (False, True):
            tracker = slicer.DirtyTracker()
            first = slicer.plan_persist(
                copies, extra, process_id=1, num_processes=2, sliced=sliced)
            crcs = {k: shard_file.crc32_bytes(
                np.ascontiguousarray(v).reshape(-1).view(np.uint8))
                for k, v in first.tensors.items()}
            tracker.note_plan(first, 3, crcs)
            later = dict(extra, step=4)
            plans = [
                slicer.plan_persist(
                    src, later, process_id=1, num_processes=2,
                    sliced=sliced, tracker=tracker,
                    holder_exists=lambda step: True)
                for src in (copies, handles)]
            assert plans[0].refs == plans[1].refs
            assert plans[1].skipped == len(
                [k for k, (lo, hi, _n) in first.layout.items() if hi > lo])
            assert plans[0].meta_extra == plans[1].meta_extra
            assert plans[1].written_bytes == 0


class TestChaosSitesOnStreamedPath:
    def test_corrupt_shard_fires(self, tmp_path):
        st = PosixDiskStorage()
        chaos.configure("storage.corrupt_shard:step=6")
        try:
            shard_file.write_shard_from_views(
                st, str(tmp_path), 6, 0, _mixed_tensors(), _extra(6)
            )
        finally:
            chaos.reset()
        with open(shard_file.shard_path(str(tmp_path), 6, 0), "rb") as f:
            with pytest.raises(shard_file.ShardCorruptionError):
                shard_file.verify_shard_file(f)
        # Done vote still lands (silent-rot scenario).
        assert os.path.exists(shard_file.done_path(str(tmp_path), 6, 0))

    def test_truncate_shard_fires(self, tmp_path):
        st = PosixDiskStorage()
        intact = len(
            shard_file.pack_shard(_mixed_tensors(), _extra(7))
        )
        chaos.configure("storage.truncate_shard:step=7")
        try:
            shard_file.write_shard_from_views(
                st, str(tmp_path), 7, 0, _mixed_tensors(), _extra(7)
            )
        finally:
            chaos.reset()
        path = shard_file.shard_path(str(tmp_path), 7, 0)
        assert os.path.getsize(path) == max(1, intact // 2)
        with pytest.raises(shard_file.ShardCorruptionError):
            shard_file.read_shard(st, str(tmp_path), 7, 0)


class TestChunkedVerify:
    def test_verify_shard_file_small_chunks(self, tmp_path):
        tensors, extra = _mixed_tensors(), _extra()
        data = _stream_bytes(tmp_path, tensors, extra)
        extra2, version = shard_file.verify_shard_file(
            io.BytesIO(data), chunk_bytes=64
        )
        assert extra2 == extra and version == 2

    def test_verify_shard_file_detects_bit_rot(self, tmp_path):
        data = bytearray(_stream_bytes(tmp_path, _mixed_tensors(), _extra()))
        data[-5] ^= 0xFF  # tensor data region
        with pytest.raises(shard_file.ShardCorruptionError) as ei:
            shard_file.verify_shard_file(io.BytesIO(bytes(data)))
        assert "CRC mismatch" in str(ei.value)

    def test_verify_shard_file_damage_modes_match_bytes_verifier(
        self, tmp_path
    ):
        """Both verifiers must classify the same damage the same way."""
        raw = _stream_bytes(tmp_path, _mixed_tensors(), _extra())
        for mutate in (
            lambda b: b[:10],  # header truncated
            lambda b: b"XXXXXXXX" + b[8:],  # bad magic
            lambda b: b[: len(b) // 2],  # torn write
            lambda b: b[:30] + b"\x00" * 8 + b[38:],  # garbage meta bytes
        ):
            damaged = mutate(raw)
            with pytest.raises(shard_file.ShardCorruptionError):
                shard_file.verify_shard(damaged)
            with pytest.raises(shard_file.ShardCorruptionError):
                shard_file.verify_shard_file(io.BytesIO(damaged))

    def test_verify_shard_file_caps_bogus_meta_len(self, tmp_path):
        """A bit-flipped meta_len must raise, not materialize gigabytes
        (the bounded-memory guarantee on the damaged-header case)."""
        import struct

        head = bytearray(
            _stream_bytes(tmp_path, _mixed_tensors(), _extra())[:20]
        )
        head[8:16] = struct.pack("<Q", 300 << 20)

        class FakeBigFile:
            """Serves a damaged 20B header over a pretend-huge file so
            the test needn't allocate 300MB to prove we won't."""

            def __init__(self):
                self.pos = 0
                self.size = 400 << 20

            def seek(self, off, whence=0):
                self.pos = self.size if whence == os.SEEK_END else off

            def tell(self):
                return self.pos

            def read(self, n):
                chunk = bytes(head[self.pos : self.pos + n])
                self.pos += len(chunk)
                return chunk

        with pytest.raises(shard_file.ShardCorruptionError) as ei:
            shard_file.verify_shard_file(FakeBigFile())
        assert "implausibly large" in str(ei.value)

    def test_fsck_clean_on_streamed_checkpoint(self, tmp_path):
        """A checkpoint written entirely via the streaming path (two
        ranks + commit) passes fsck — which itself now verifies in
        bounded chunks."""
        st = PosixDiskStorage()
        d = str(tmp_path)
        for pid in (0, 1):
            extra = dict(_extra(9), process_id=pid, num_processes=2)
            shard_file.write_shard_from_views(
                st, d, 9, pid, _mixed_tensors(), extra, workers=2
            )
        shard_file.commit(st, d, 9)
        report = fsck.fsck(d, st)
        assert not report.damaged, report.findings
        assert report.shards_checked == 2

    def test_fsck_unreadable_committed_shard_is_damage(self, tmp_path):
        """A committed step whose only shard can't be read (failing
        disk) must exit damaged, not 'clean' — the coverage check can't
        rely on verified shards to learn the world size there."""
        st = PosixDiskStorage()
        d = str(tmp_path)
        shard_file.write_shard_from_views(
            st, d, 8, 0, _mixed_tensors(), _extra(8)
        )
        shard_file.commit(st, d, 8)

        class EIOStorage(PosixDiskStorage):
            def open_read(self, path):
                if path.endswith(".ckpt"):
                    return None  # EIO-shaped: listed but unreadable
                return super().open_read(path)

        report = fsck.fsck(d, EIOStorage())
        assert report.damaged
        assert any("unreadable" in f.reason for f in report.findings)
        st = PosixDiskStorage()
        d = str(tmp_path)
        shard_file.write_shard_from_views(
            st, d, 4, 0, _mixed_tensors(), _extra(4)
        )
        shard_file.commit(st, d, 4)
        path = shard_file.shard_path(d, 4, 0)
        with open(path, "r+b") as f:
            f.seek(-3, os.SEEK_END)
            b = f.read(1)
            f.seek(-3, os.SEEK_END)
            f.write(bytes([b[0] ^ 0xFF]))
        report = fsck.fsck(d, st)
        assert report.damaged
        assert any(
            "shard_00000.ckpt" in f.path and f.severity == fsck.SEV_DAMAGE
            for f in report.findings
        )


class _MemStorage(CheckpointStorage):
    """Minimal non-POSIX backend: exercises the sequential buffered
    stream fallback (object-store shape)."""

    def __init__(self):
        self.blobs = {}

    def write(self, content, path):
        self.blobs[path] = (
            content if isinstance(content, bytes) else content.encode()
        )

    def read(self, path, mode="rb"):
        raw = self.blobs.get(path)
        if raw is None:
            return None
        return raw if "b" in mode else raw.decode()

    def safe_rmtree(self, dirpath):
        for k in [k for k in self.blobs if k.startswith(dirpath)]:
            del self.blobs[k]

    def safe_remove(self, path):
        self.blobs.pop(path, None)

    def safe_makedirs(self, dirpath):
        pass

    def commit(self, step, success):
        pass

    def exists(self, path):
        return path in self.blobs or any(
            k.startswith(path.rstrip("/") + "/") for k in self.blobs
        )

    def listdir(self, path):
        prefix = path.rstrip("/") + "/"
        return sorted(
            {
                k[len(prefix):].split("/", 1)[0]
                for k in self.blobs
                if k.startswith(prefix)
            }
        )


class TestWriteShardRanges:
    RANGES = [
        (0, [b"ab", b"cd"]),
        (4, [b"efgh"]),
        (8, [b"ij"]),
    ]

    def test_posix_parallel(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "ranges.bin")
        st.write_shard_ranges(path, 10, list(self.RANGES), workers=3)
        assert open(path, "rb").read() == b"abcdefghij"

    def test_buffer_fallback_matches_posix(self, tmp_path):
        mem = _MemStorage()
        mem.write_shard_ranges("/k/ranges.bin", 10, list(self.RANGES),
                               workers=3)
        assert mem.blobs["/k/ranges.bin"] == b"abcdefghij"

    def test_finalize_patches_before_publish(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "fin.bin")
        st.write_shard_ranges(
            path, 10, list(self.RANGES),
            finalize=lambda sink: sink.write_at(b"XY", 0),
        )
        assert open(path, "rb").read() == b"XYcdefghij"

    def test_streamed_shard_identical_on_buffer_fallback(self, tmp_path):
        """Object-store shape storage still produces byte-identical
        shards via the sequential in-memory sink."""
        tensors, extra = _mixed_tensors(), _extra()
        mem = _MemStorage()
        shard_file.ShardStreamWriter(
            mem, "/ck/s.ckpt", tensors, extra, workers=4
        ).write()
        assert mem.blobs["/ck/s.ckpt"] == shard_file.pack_shard(
            tensors, extra
        )


class TestEngineAndSaverFastPath:
    def test_agent_saver_streams_zero_copy(self, tmp_path, monkeypatch):
        """Full agent-mode round trip: the saver persists straight from
        the arena's copy=False views under its locks — file identical to
        packing the arena state, perf gauges populated, fsck clean."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.agent.metrics import perf_stats
        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        job = "ckpt-stream-agent"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)
        saver.start()
        try:
            ckpt = FlashCheckpointer(str(tmp_path), job_name=job)
            assert ckpt.engine.agent_mode
            state = {"w": np.full((64, 64), 1.5, np.float32)}
            ckpt.save(state, meta={"step": 4}, storage=True)
            assert ckpt.wait(timeout=60)
            assert shard_file.latest_step(
                PosixDiskStorage(), str(tmp_path)
            ) == 4
            # The streamed shard equals packing the arena state directly.
            read = ckpt.engine._arena.read_state(copy=True)
            assert read is not None
            tensors, extra = read
            on_disk = open(
                shard_file.shard_path(str(tmp_path), 4, 0), "rb"
            ).read()
            assert on_disk == shard_file.pack_shard(tensors, extra)
            # the write span says how many bytes the writer read() off
            # the arena: every tensor byte, once
            evs, _, _ = obs.get_recorder().snapshot()
            writes = [e["args"] for e in evs if e["k"] == "span"
                      and e["name"] == "ckpt.persist.write"
                      and e["args"].get("step") == 4]
            assert writes and writes[-1]["read_bytes"] == sum(
                int(v.nbytes) for v in tensors.values())
            # Observability: persist throughput + the worker's stall
            # reached the agent-side surfaces.
            assert perf_stats.get("ckpt_persist_mbps") > 0
            assert saver.last_stall_ms() > 0
            assert saver.staged_mbps() > 0
            assert ckpt.engine.last_stall_ms > 0
            assert not fsck.fsck(str(tmp_path)).damaged
            ckpt.close()
        finally:
            saver.stop()

    def test_engine_reports_ckpt_perf_to_master(self, tmp_path, monkeypatch):
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-perf-rep")

        class FakeClient:
            def __init__(self):
                self.calls = []

            def report_ckpt_perf(self, **kw):
                self.calls.append(kw)

        client = FakeClient()
        eng = CheckpointEngine(
            str(tmp_path), job_name="ckpt-perf-rep", master_client=client
        )
        try:
            eng.save_to_memory(5, {"w": np.ones((16, 16), np.float32)})
            assert client.calls and client.calls[-1]["step"] == 5
            assert client.calls[-1]["stall_ms"] > 0
            assert client.calls[-1]["staged_mbps"] > 0
        finally:
            eng.close()

    def test_load_with_target_not_aliased_to_arena(
        self, tmp_path, monkeypatch
    ):
        """The zero-copy shm restore must not leak live-arena views into
        the restored tree: a later save_to_memory rewrites the arena and
        an aliased 'restored' array would change underfoot."""
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-alias")
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        eng = CheckpointEngine(str(tmp_path), job_name="ckpt-alias")
        try:
            eng.save_to_memory(5, {"w": np.full(64, 1.0, np.float32)})
            got = eng.load(target={"w": np.zeros(64, np.float32)})
            assert got is not None
            state, meta = got
            assert meta["step"] == 5
            eng.save_to_memory(6, {"w": np.full(64, 9.0, np.float32)})
            np.testing.assert_array_equal(
                state["w"], np.full(64, 1.0, np.float32)
            )
        finally:
            eng.close()

    def test_copy_mode_knob_persists_identically(self, tmp_path, monkeypatch):
        """ckpt_zero_copy=False restores the old bounded-stall shape
        (copy under the lock, persist from the copy) — the shard bytes
        must be indistinguishable from the zero-copy path's."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer
        from dlrover_tpu.common.global_context import get_context

        job = "ckpt-copy-knob"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        ctx = get_context()
        monkeypatch.setattr(ctx, "ckpt_zero_copy", False)
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)
        saver.start()
        try:
            ckpt = FlashCheckpointer(str(tmp_path), job_name=job)
            ckpt.save(
                {"w": np.full((32, 32), 2.5, np.float32)},
                meta={"step": 3}, storage=True,
            )
            assert ckpt.wait(timeout=60)
            tensors, extra = ckpt.engine._arena.read_state(copy=True)
            on_disk = open(
                shard_file.shard_path(str(tmp_path), 3, 0), "rb"
            ).read()
            assert on_disk == shard_file.pack_shard(tensors, extra)
            ckpt.close()
        finally:
            saver.stop()

    def test_load_jax_target_not_aliased_to_arena(
        self, tmp_path, monkeypatch
    ):
        """jax.device_put on the CPU backend may zero-copy an aligned
        numpy buffer — a restored jax leaf must still be independent of
        the live arena (the _owned guard in restore_to_target)."""
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-jax-alias")
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        eng = CheckpointEngine(str(tmp_path), job_name="ckpt-jax-alias")
        try:
            eng.save_to_memory(5, {"w": np.full(256, 1.0, np.float32)})
            got = eng.load(target={"w": jnp.zeros(256, jnp.float32)})
            assert got is not None
            state, meta = got
            assert meta["step"] == 5
            eng.save_to_memory(6, {"w": np.full(256, 9.0, np.float32)})
            np.testing.assert_array_equal(
                np.asarray(state["w"]), np.full(256, 1.0, np.float32)
            )
            # the spans say how: header and meta under shm_read, the
            # tensor read() into an array of its own under device_put
            evs, _, _ = obs.get_recorder().snapshot()
            args = {e["name"]: e.get("args", {}) for e in evs
                    if e["k"] == "span"
                    and e["name"].startswith("ckpt.load.")}
            assert args["ckpt.load.shm_read"]["copy"] is False
            assert args["ckpt.load.shm_read"]["bytes"] == 1024
            put = args["ckpt.load.device_put"]
            assert (put["staged_bytes"], put["copied_bytes"]) == (0, 1024)
            assert "in_place_bytes" not in put
        finally:
            eng.close()

    def test_load_without_target_survives_arena_close(
        self, tmp_path, monkeypatch
    ):
        """Without a target the ShardSource escapes to the caller with
        unbounded lifetime — it must hold copies, not views."""
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "ckpt-escape")
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        eng = CheckpointEngine(str(tmp_path), job_name="ckpt-escape")
        try:
            eng.save_to_memory(7, {"w": np.full(32, 3.0, np.float32)})
            got = eng.load()
            assert got is not None
            source, meta = got
        finally:
            eng.close()
        # Arena closed: the escaped source must still assemble correctly.
        piece = source.assemble("['w']", ((0, 32),))
        np.testing.assert_array_equal(piece, np.full(32, 3.0, np.float32))


class _StandInDevice:
    """All ``restore_to_target`` asks of a destination: its platform."""

    def __init__(self, platform):
        self.platform = platform


class _StandInSharding:
    def __init__(self, device):
        self._device = device

    def addressable_devices_indices_map(self, gshape):
        return {self._device: tuple(slice(None) for _ in gshape)}


class _StandInLeaf:
    """A sharding-bearing target leaf on a device this host has not."""

    def __init__(self, like, device):
        self.shape, self.dtype = like.shape, like.dtype
        self.sharding = _StandInSharding(device)


@pytest.fixture
def arena_pieces():
    """Stage arrays into a real shm arena and hand back their
    ``ArenaTensor`` handles, as a warm restore gets them."""
    arenas = []

    def stage(flat):
        w = SharedMemoryArena(
            f"tckpt-pieces-{os.getpid()}-{len(arenas)}")
        r = SharedMemoryArena(w.name)
        arenas.append((w, r))
        w.write_state(flat, extra={"step": 1})
        handles, _ = r.read_state(copy=False)
        return handles, w

    yield stage
    for w, r in arenas:
        r.close()
        w.close(unlink=True)


def _root_buffer(arr):
    while isinstance(arr.base, np.ndarray):
        arr = arr.base
    return arr


class TestRestoreCopiesOnlyWhereItProtects:
    """ISSUE 24, ISSUE 28: how a piece reaches the restored tree is read
    off the piece and its destination, by no flag.  A piece still in the
    shm arena is ``read()`` — through a reused staging buffer on its way
    to an accelerator, into an array the tree owns for a host leaf or a
    device that may alias host memory (the CPU backend).  A piece that
    is an array goes on as it is, copied only where it borrows its bytes
    and the restored tree would keep referring to them."""

    @pytest.mark.parametrize("leaf_kind", ["device", "host"])
    @pytest.mark.parametrize("piece_kind", ["arena", "borrowed", "owned"])
    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_copy_or_not(self, monkeypatch, arena_pieces, platform,
                         piece_kind, leaf_kind):
        from dlrover_tpu.checkpoint import tree_utils

        want = np.arange(16, 80, dtype=np.float32)
        backing = np.arange(96, dtype=np.float32)
        if piece_kind == "arena":
            piece = arena_pieces({"w": want})[0]["w"]
        elif piece_kind == "borrowed":
            piece = backing[16:80]
            assert piece.base is not None
        else:
            piece = want.copy()
        put_on = []

        def device_put(x, device):
            put_on.append(device)
            return x  # whatever reaches the device, as it was given

        monkeypatch.setattr(tree_utils.jax, "device_put", device_put)
        monkeypatch.setattr(
            tree_utils.jax, "make_array_from_single_device_arrays",
            lambda shape, sharding, arrays: arrays[0])
        device = _StandInDevice(platform)
        target = {"w": _StandInLeaf(want, device)
                  if leaf_kind == "device" else np.zeros(64, np.float32)}
        source = tree_utils.ShardSource()
        source.add({"['w']|0": piece},
                   {"['w']|0": {"path": "['w']", "index": [[0, 64]]}})
        tally = {}
        audit.enable()
        try:
            out = tree_utils.restore_to_target(target, source, tally)["w"]
            snap = audit.snapshot()
        finally:
            audit.disable()
        keeps = leaf_kind == "host" or platform == "cpu"
        staged = piece_kind == "arena" and not keeps
        copied = keeps and piece_kind != "owned"
        np.testing.assert_array_equal(out, want)
        assert out.dtype == want.dtype and out.shape == want.shape
        assert put_on == ([device] if leaf_kind == "device" else [])
        assert tally == {
            "staged_bytes": want.nbytes if staged else 0,
            "copied_bytes": want.nbytes if copied else 0,
        }
        if piece_kind == "arena":
            # every arena byte is counted once, on one side or the other
            assert tally["staged_bytes"] + tally["copied_bytes"] == (
                want.nbytes)
            # a staged piece is a view of a staging buffer; a kept one
            # owns its bytes
            assert (_root_buffer(out) is out) == (not staged)
        else:
            assert np.shares_memory(out, piece) == (not copied)
        if piece_kind == "borrowed":
            assert np.shares_memory(out, backing) == (not copied)
        assert snap["copied_by_site"] == (
            {"restore_owned_copy": want.nbytes} if copied else {})


class _LatePut:
    """What a stand-in ``device_put`` returns: like the real one it has
    NOT read its host buffer when it returns; ``block_until_ready`` is
    when the bytes leave the host."""

    def __init__(self, host, awaited):
        self.host = host
        self.value = None
        self._awaited = awaited

    def block_until_ready(self):
        if self.value is None:
            self.value = np.array(self.host)
            self._awaited.append(self)
        return self


class TestRestoreStagesThroughReusedBuffers:
    """ISSUE 28: arena -> accelerator goes through two reused staging
    buffers; a buffer is refilled only after the put that read it is
    ready, and host memory is a constant, not the state."""

    SIZES = [4096, 17, 70_000, 1, 33_333, 70_000, 5, 12_345, 64, 50_000, 3]

    def _state(self):
        rng = np.random.RandomState(7)
        flat = {f"t{i:02d}": rng.standard_normal(n).astype(np.float32)
                for i, n in enumerate(self.SIZES)}
        flat["count"] = np.asarray(np.int32(9))
        flat["none"] = np.zeros((0, 4), np.float32)
        return flat

    def _restore(self, monkeypatch, arena_pieces, platform):
        from dlrover_tpu.checkpoint import tree_utils

        flat = self._state()
        handles, writer = arena_pieces(flat)
        puts, awaited = [], []

        def device_put(x, device):
            puts.append(_LatePut(x, awaited))
            return puts[-1]

        monkeypatch.setattr(tree_utils.jax, "device_put", device_put)
        monkeypatch.setattr(
            tree_utils.jax, "make_array_from_single_device_arrays",
            lambda shape, sharding, arrays: arrays[0])
        device = _StandInDevice(platform)
        target = {k: _StandInLeaf(v, device) for k, v in flat.items()}
        source = tree_utils.ShardSource()
        source.add(
            {f"['{k}']|0": h for k, h in handles.items()},
            {f"['{k}']|0": {"path": f"['{k}']",
                            "index": [[0, d] for d in flat[k].shape]}
             for k in flat})
        tally = {}
        out = tree_utils.restore_to_target(target, source, tally)
        return flat, writer, out, puts, awaited, tally

    def test_buffer_not_refilled_before_its_put_was_awaited(
            self, monkeypatch, arena_pieces):
        flat, writer, out, puts, awaited, tally = self._restore(
            monkeypatch, arena_pieces, "tpu")
        nbytes = sum(v.nbytes for v in flat.values())
        assert tally == {"staged_bytes": nbytes, "copied_bytes": 0}
        assert len(puts) == len(flat)
        # restore_to_target has awaited every put before it returned
        # (its buffers die with it), each exactly once
        assert sorted(map(id, awaited)) == sorted(map(id, puts))
        # and what each put read, late, is its own piece: nothing was
        # refilled under it
        for key, want in flat.items():
            got = out[key].value
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(got, want)
        # two buffers, each as large as the largest piece: a constant
        # for a state of any number of pieces
        roots = {(_root_buffer(p.host).ctypes.data,
                  _root_buffer(p.host).nbytes)
                 for p in puts if p.host.nbytes}
        assert len(roots) == 2
        assert {n for _addr, n in roots} == {
            max(v.nbytes for v in flat.values())}
        # overwrite the arena and every staging buffer: the restored
        # tree does not change
        writer.write_state({k: np.zeros_like(v) for k, v in flat.items()},
                           extra={"step": 2})
        for p in puts:
            _root_buffer(p.host)[...] = 0xFF
        for key, want in flat.items():
            np.testing.assert_array_equal(out[key].value, want)

    def test_a_device_that_may_alias_gets_arrays_of_its_own(
            self, monkeypatch, arena_pieces):
        flat, writer, out, puts, awaited, tally = self._restore(
            monkeypatch, arena_pieces, "cpu")
        nbytes = sum(v.nbytes for v in flat.values())
        assert tally == {"staged_bytes": 0, "copied_bytes": nbytes}
        # the CPU backend may adopt the buffer it is given: each piece
        # is read into an array nobody else refers to, no staging
        assert all(p.host.flags.owndata for p in puts)
        assert len({p.host.ctypes.data for p in puts if p.host.nbytes}) == (
            len([v for v in flat.values() if v.nbytes]))
        writer.write_state({k: np.zeros_like(v) for k, v in flat.items()},
                           extra={"step": 2})
        for key, want in flat.items():
            np.testing.assert_array_equal(out[key].host, want)

    def test_put_failure_still_awaits_what_is_in_flight(
            self, monkeypatch, arena_pieces):
        from dlrover_tpu.checkpoint import tree_utils

        flat = {f"t{i}": np.full(100, i, np.float32) for i in range(4)}
        handles, _w = arena_pieces(flat)
        awaited, puts = [], []

        def device_put(x, device):
            if len(puts) == 3:
                raise RuntimeError("device lost")
            puts.append(_LatePut(x, awaited))
            return puts[-1]

        monkeypatch.setattr(tree_utils.jax, "device_put", device_put)
        monkeypatch.setattr(
            tree_utils.jax, "make_array_from_single_device_arrays",
            lambda shape, sharding, arrays: arrays[0])
        device = _StandInDevice("tpu")
        source = tree_utils.ShardSource()
        source.add({f"['{k}']|0": h for k, h in handles.items()},
                   {f"['{k}']|0": {"path": f"['{k}']", "index": [[0, 100]]}
                    for k in flat})
        with pytest.raises(RuntimeError, match="device lost"):
            tree_utils.restore_to_target(
                {k: _StandInLeaf(v, device) for k, v in flat.items()},
                source)
        # nothing is left reading a buffer that is about to be freed
        assert sorted(map(id, awaited)) == sorted(map(id, puts))
        assert len(puts) == 3

    def test_resharded_target_reads_arena_pieces_for_the_overlap(
            self, arena_pieces):
        """A target box that matches no staged piece (another sharding)
        is assembled by overlap: the arena pieces it needs are read."""
        from dlrover_tpu.checkpoint import tree_utils

        full = np.arange(64, dtype=np.float32).reshape(8, 8)
        handles, _w = arena_pieces({"lo": full[:4], "hi": full[4:]})
        source = tree_utils.ShardSource()
        source.add(
            {"['w']|0": handles["lo"], "['w']|1": handles["hi"]},
            {"['w']|0": {"path": "['w']", "index": [[0, 4], [0, 8]]},
             "['w']|1": {"path": "['w']", "index": [[4, 8], [0, 8]]}})
        tally = {}
        out = tree_utils.restore_to_target(
            {"w": np.zeros((8, 8), np.float32)}, source, tally)
        np.testing.assert_array_equal(out["w"], full)
        assert out["w"].flags.owndata
        # assembled into a fresh array the tree owns: nothing to count
        assert tally == {"staged_bytes": 0, "copied_bytes": 0}


class _OtherHolder:
    """A second holder of a rank's fencing lock — in production the
    agent's saver, another process.  ``SharedLock`` names its holder by
    pid, so a second one inside this process needs a name of its own."""

    def __init__(self, job):
        from dlrover_tpu.checkpoint.engine import ckpt_lock_name
        from dlrover_tpu.common.multi_process import SharedLock

        self.lock = SharedLock(ckpt_lock_name(job, 0))
        self.lock._holder = "the-agents-saver"

    def try_acquire(self, timeout=0.1):
        got = self.lock.acquire(timeout=timeout)
        if got:
            self.lock.release()
        return got


class TestWarmRestoreIsFenced:
    """ISSUE 24: under an agent, ``load(target=...)`` reads the arena as
    views and holds the rank's ``SharedLock`` from before the views are
    taken until the restored state is ready."""

    @pytest.fixture
    def agent_engine(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.common.shm import arena_name

        job = f"ckpt-fenced-{os.getpid()}"
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", job)
        monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
        monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)  # the servers
        eng = CheckpointEngine(str(tmp_path), job_name=job)
        try:
            assert eng.agent_mode
            yield eng, job
        finally:
            eng.close()
            saver.stop()
            try:
                os.unlink(f"/dev/shm/{arena_name(job, 0)}")
            except FileNotFoundError:
                pass

    @staticmethod
    def _state(value):
        import jax.numpy as jnp

        return {"a": jnp.full(512, value, jnp.float32),
                "b": jnp.full((8, 64), value, jnp.float32)}

    @pytest.mark.parametrize("fault", [None, KeyError, RuntimeError])
    def test_lock_held_while_pieces_are_put(self, agent_engine, monkeypatch,
                                            fault):
        import time as _time

        from dlrover_tpu.checkpoint import tree_utils

        eng, job = agent_engine
        other = _OtherHolder(job)
        eng.save_to_memory(5, self._state(1.0))
        assert other.try_acquire()  # nobody holds it between calls
        during = []
        real_put = tree_utils.jax.device_put

        def slow_put(x, device):
            _time.sleep(0.05)
            during.append(other.try_acquire(timeout=0.1))
            if fault is not None:
                raise fault("injected into restore_to_target")
            return real_put(x, device)

        monkeypatch.setattr(tree_utils.jax, "device_put", slow_put)
        target = self._state(0.0)
        if fault is RuntimeError:
            with pytest.raises(RuntimeError):
                eng.load(target=target)
        else:
            got = eng.load(target=target)
            # a KeyError is "shm incomplete": the ladder goes to storage,
            # which holds nothing
            assert (got is None) == (fault is KeyError)
            if got is not None:
                state, meta = got
                assert meta["step"] == 5
                np.testing.assert_array_equal(
                    np.asarray(state["b"]), np.full((8, 64), 1.0, np.float32))
        assert during == [False] * (2 if fault is None else 1)
        assert other.try_acquire()  # released, also on the way out of a raise
        assert eng._arena_mu.acquire(timeout=1.0)
        eng._arena_mu.release()

    def test_write_during_load_lands_after_it(self, agent_engine,
                                              monkeypatch):
        """A writer that honours the lock (the saver's
        ``seed_from_replicas``) cannot tear a restore that is reading
        views: its ``write_state`` waits for the load, and what was
        restored is the earlier step, whole."""
        import threading
        import time as _time

        from dlrover_tpu.checkpoint import tree_utils
        from dlrover_tpu.common.shm import arena_name

        eng, job = agent_engine
        other = _OtherHolder(job)
        eng.save_to_memory(5, self._state(1.0))
        staged, extra = eng._arena.read_state(copy=True)
        newer = {k: np.full_like(v, 9.0) for k, v in staged.items()}
        loading, written = threading.Event(), threading.Event()
        seen_written = []

        def writer():
            assert loading.wait(timeout=30)
            arena = SharedMemoryArena(arena_name(job, 0))
            assert other.lock.acquire(timeout=30)
            try:
                arena.write_state(newer, extra=dict(extra, step=6))
                written.set()
            finally:
                other.lock.release()
                arena.close()

        real_put = tree_utils.jax.device_put

        def slow_put(x, device):
            loading.set()
            _time.sleep(0.3)  # the writer is waiting on the lock by now
            seen_written.append(written.is_set())
            return real_put(x, device)

        monkeypatch.setattr(tree_utils.jax, "device_put", slow_put)
        th = threading.Thread(target=writer)
        th.start()
        try:
            state, meta = eng.load(target=self._state(0.0))
        finally:
            th.join(timeout=30)
        assert not th.is_alive() and written.is_set()
        assert seen_written == [False, False]
        assert meta["step"] == 5
        for k, shape in (("a", (512,)), ("b", (8, 64))):
            np.testing.assert_array_equal(
                np.asarray(state[k]), np.full(shape, 1.0, np.float32))
        # and the write did land: the arena holds the newer step now
        monkeypatch.setattr(tree_utils.jax, "device_put", real_put)
        state6, meta6 = eng.load(target=self._state(0.0))
        assert meta6["step"] == 6
        np.testing.assert_array_equal(
            np.asarray(state6["a"]), np.full(512, 9.0, np.float32))
        # the first restore still holds the earlier step's values
        np.testing.assert_array_equal(
            np.asarray(state["a"]), np.full(512, 1.0, np.float32))


class TestSpeedMonitorStall:
    def test_ckpt_stall_folds_into_goodput(self):
        import time as _time

        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        now = _time.time()
        sm.collect_global_step(1, now - 10.0)
        sm.collect_global_step(2, now)
        assert sm.goodput() > 0.9
        sm.record_ckpt_stall(5.0, persist_mbps=400.0)
        assert sm.ckpt_stall_total == 5.0
        assert sm.ckpt_stall_last_ms == 5000.0
        assert sm.goodput() < 0.6  # ~5s of 10s elapsed was stall

    def test_same_step_ranks_count_max_not_sum(self):
        """64 ranks stalling ~1s concurrently for the same save is ~1s of
        lost wall-clock, not 64s — goodput must charge the per-step max."""
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        for _rank in range(64):
            sm.record_ckpt_stall(1.0, step=10)
        assert sm.ckpt_stall_total == 1.0
        sm.record_ckpt_stall(1.5, step=10)  # a slower rank straggles in
        assert sm.ckpt_stall_total == 1.5
        sm.record_ckpt_stall(2.0, step=20)  # next save accumulates
        assert sm.ckpt_stall_total == 3.5

    def test_interleaved_step_reports_still_dedup(self):
        """A rank's step-N report straggling in after step-N+1 reports
        started must not re-charge either step (the windowed map, not a
        single-slot tracker)."""
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        for _rank in range(7):
            sm.record_ckpt_stall(0.5, step=100)
        sm.record_ckpt_stall(0.6, step=101)
        sm.record_ckpt_stall(0.5, step=100)  # straggler from step 100
        sm.record_ckpt_stall(0.6, step=101)
        assert sm.ckpt_stall_total == pytest.approx(1.1)

    def test_throughput_only_report_touches_no_stall(self):
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        sm.record_ckpt_stall(1.0, step=5, staged_mbps=5000.0)
        sm.record_ckpt_stall(0.0, step=5, persist_mbps=750.0)
        assert sm.ckpt_stall_total == 1.0
        assert sm.ckpt_stall_last_ms == 1000.0
        assert sm.ckpt_persist_mbps == 750.0
        assert sm.ckpt_staged_mbps == 5000.0

    def test_stall_inside_down_window_not_double_counted(self):
        import time as _time

        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        sm.collect_global_step(1, _time.time() - 10.0)
        sm.mark_down()
        sm.record_ckpt_stall(5.0)
        assert sm.ckpt_stall_total == 0.0  # charged to downtime already


class TestWorkerPerfTTLCache:
    """``AsyncCheckpointSaver.worker_perf``'s 1s TTL cache (ISSUE 4
    follow-up): one Prometheus scrape samples several gauges, and each
    must NOT cost its own SharedDict round trip against a possibly-sick
    stat server — one bounded trip per TTL window, fresh values after
    expiry."""

    def _saver(self):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        class FakeStat:
            def __init__(self):
                self.calls = 0
                self.data = {"stall_ms_0": 40.0, "staged_mbps_0": 5000.0}

            def to_dict(self, timeout=None):
                self.calls += 1
                return dict(self.data)

        class FakeClock:
            """Injectable TTL clock: tests AGE the cache by stepping
            this, never by sleeping (and never by back-dating the
            stamp with the wrong clock family — the old wall-stamp
            aging compared ``time.time()`` stamps against a
            ``time.monotonic()`` now and never expired)."""

            def __init__(self):
                self.now = 100.0

            def __call__(self):
                return self.now

        saver = AsyncCheckpointSaver.__new__(AsyncCheckpointSaver)
        saver._stat = FakeStat()
        saver._perf_cache = (0.0, {})
        saver._perf_clock = FakeClock()
        return saver

    def test_one_round_trip_per_ttl_window(self):
        saver = self._saver()
        # One scrape samples several gauges; all ride ONE snapshot.
        assert saver.worker_perf() == saver._stat.data
        assert saver.last_stall_ms() == 40.0
        assert saver.staged_mbps() == 5000.0
        assert saver._stat.calls == 1

    def test_fresh_values_after_expiry(self):
        saver = self._saver()
        saver.worker_perf()
        assert saver._stat.calls == 1
        saver._stat.data = {"stall_ms_0": 99.0, "staged_mbps_0": 100.0}
        # Inside the window: stale-by-design snapshot, no new trip.
        saver._perf_clock.now += 0.5
        assert saver.last_stall_ms() == 40.0
        assert saver._stat.calls == 1
        # Step the clock past the 1s TTL: the next sample re-fetches.
        saver._perf_clock.now += 1.0
        assert saver.last_stall_ms() == 99.0
        assert saver._stat.calls == 2

    def test_failed_snapshot_degrades_to_empty_not_raise(self):
        saver = self._saver()

        def boom(timeout=None):
            saver._stat.calls += 1
            raise TimeoutError("stat server hung")

        saver._stat.to_dict = boom
        assert saver.worker_perf() == {}
        assert saver.last_stall_ms() == 0.0  # rides the cached {}
        assert saver._stat.calls == 1
