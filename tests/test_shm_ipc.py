"""Shared-memory arena + unix-socket IPC primitive tests (cross-process)."""

import multiprocessing as mp
import os
import threading
import time

import ml_dtypes
import numpy as np
import pytest

from dlrover_tpu import chaos
from dlrover_tpu.common import shm
from dlrover_tpu.common.multi_process import SharedDict, SharedLock, SharedQueue
from dlrover_tpu.common.shm import ArenaTensor, SharedMemoryArena, arena_name


class TestArena:
    def test_write_read_roundtrip(self):
        name = arena_name("t-job", 0)
        arena = SharedMemoryArena(name)
        flat = {
            "model/w": np.arange(1024, dtype=np.float32).reshape(32, 32),
            "model/b": np.ones(7, dtype=np.float64),
            "opt/step": np.array(42, dtype=np.int64),
            "model/f16": np.arange(16, dtype=np.float16),
        }
        arena.write_state(flat, extra={"step": 42, "world": 2})
        out, extra = arena.read_state()
        assert extra["step"] == 42
        for k in flat:
            np.testing.assert_array_equal(out[k], flat[k])
        arena.close(unlink=True)

    def test_grow_and_reader_remap(self):
        name = arena_name("t-grow", 0)
        w = SharedMemoryArena(name)
        w.write_state({"a": np.zeros(8, np.float32)}, extra={"step": 1})
        r = SharedMemoryArena(name)
        assert r.metadata()["extra"]["step"] == 1
        # Writer grows the segment (new inode); reader must remap
        # transparently on the next metadata() call — no manual reopen.
        w.write_state({"a": np.zeros(1 << 22, np.float32)}, extra={"step": 2})
        meta = r.metadata()
        assert meta["extra"]["step"] == 2
        w.close(unlink=True)
        r.close()

    def test_dirty_flag_invalidates_torn_write(self):
        """A writer killed mid-write leaves dirty=1; readers must see no
        valid state instead of torn tensor bytes."""
        name = arena_name("t-dirty", 0)
        w = SharedMemoryArena(name)
        w.write_state({"a": np.ones(8, np.float32)}, extra={"step": 1})
        assert w.metadata() is not None
        # Simulate a mid-write kill: set the header's dirty u32 (offset 44).
        w._seg.buf[44] = 1
        r = SharedMemoryArena(name)
        assert r.metadata() is None
        # A completed write clears it again.
        w.write_state({"a": np.ones(8, np.float32)}, extra={"step": 2})
        assert r.metadata()["extra"]["step"] == 2
        w.close(unlink=True)
        r.close()

    def test_empty_arena_metadata_none(self):
        arena = SharedMemoryArena("dlrtpu_nonexistent_arena_xyz")
        assert arena.metadata() is None
        assert arena.read_state() is None

    def test_cross_process_read(self):
        name = arena_name("t-xproc", 0)
        writer = SharedMemoryArena(name)
        data = np.random.rand(256, 16).astype(np.float32)
        writer.write_state({"x": data}, extra={"step": 9})

        def child(q):
            a = SharedMemoryArena(name)
            out, extra = a.read_state()
            q.put((float(out["x"].sum()), extra["step"]))
            a.close()

        q = mp.Queue()
        p = mp.Process(target=child, args=(q,))
        p.start()
        total, step = q.get(timeout=30)
        p.join(timeout=10)
        assert step == 9
        np.testing.assert_allclose(total, float(data.sum()), rtol=1e-5)
        writer.close(unlink=True)


@pytest.fixture(params=["native", "py"])
def backend(request, monkeypatch):
    """Both segment backends, by what `_open_segment` would pick: the
    native library, or none."""
    if request.param == "py":
        monkeypatch.setattr(shm, "shm_lib", lambda: None)
    elif shm.shm_lib() is None:
        pytest.skip("no native shm library on this host")
    return {"native": shm._NativeSegment, "py": shm._PySegment}[request.param]


def _fds_on(name):
    """What this process's descriptors on the segment's file name: the
    file, or the file " (deleted)" for a segment since re-created."""
    out = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # the listing's own descriptor
        if target.startswith(f"/dev/shm/{name}"):
            out.append(target)
    return out


_READ_CASES = {
    "float32": np.arange(1003, dtype=np.float32).reshape(17, 59),
    "bfloat16": (np.arange(777) / 7).astype(ml_dtypes.bfloat16).reshape(3, 259),
    "float8": (np.arange(301) / 16).astype(ml_dtypes.float8_e4m3fn),
    "scalar": np.asarray(np.int64(-5)),
    "empty": np.zeros((0, 3), np.float32),
}


class TestArenaReadPrimitive:
    """ISSUE 28: tensor bytes leave the arena by positional ``read()``
    on the segment's file, never through the mapping; what ``read()``
    gives is what the mapping holds, byte for byte."""

    @pytest.mark.parametrize("case", sorted(_READ_CASES))
    def test_read_equals_the_mapping(self, backend, case):
        want = _READ_CASES[case]
        name = arena_name(f"t-pread-{case}-{os.getpid()}", 0)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        try:
            # an odd-sized neighbour in front: the tensor still starts on
            # a 128-byte boundary
            w.write_state({"pad": np.ones(7, np.uint8), "t": want},
                          extra={"step": 3})
            handles, extra = r.read_state(copy=False)
            assert isinstance(r._seg, backend) and extra["step"] == 3
            h = handles["t"]
            assert isinstance(h, ArenaTensor)
            assert (h.dtype, h.shape, h.nbytes) == (
                want.dtype, want.shape, want.nbytes)
            assert h.offset % 128 == 0
            mapped = r._seg.buf[h.offset : h.offset + h.nbytes].tobytes()
            assert mapped == want.tobytes()
            # a fresh array of its own
            got = h.read()
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == mapped and got.flags.owndata
            # a window that is aligned to nothing
            raw = np.zeros(h.nbytes + 16, np.uint8)
            assert h.read_into(raw[3 : 3 + h.nbytes]) == h.nbytes
            assert raw[3 : 3 + h.nbytes].tobytes() == mapped
            assert not raw[:3].any() and not raw[3 + h.nbytes :].any()
            # a larger reused buffer: typed and shaped head of it
            out = np.full(h.nbytes + 5, 0xEE, np.uint8)
            typed = h.read(out=out)
            assert typed.dtype == want.dtype and typed.shape == want.shape
            assert typed.tobytes() == mapped
            assert (h.nbytes == 0 or np.shares_memory(typed, out))
            assert (out[h.nbytes :] == 0xEE).all()
            # chunks through a buffer whose size divides nothing
            scratch = np.empty(7, np.uint8)
            assert b"".join(bytes(c) for c in h.chunks(scratch)) == mapped
            # a byte range of it (a sliced persist's share)
            if h.nbytes >= 4:
                part = h.byte_range(1, h.nbytes - 2)
                assert part.dtype == np.uint8
                assert part.read().tobytes() == mapped[1:-2]
            with pytest.raises(ValueError):
                h.byte_range(0, h.nbytes + 1)
            # copy=True is the same read into owned arrays
            copies, _ = r.read_state(copy=True)
            assert copies["t"].dtype == want.dtype
            assert copies["t"].tobytes() == mapped
            assert copies["t"].flags.owndata
            with pytest.raises(TypeError):
                np.asarray(h)  # a handle is never mistaken for an array
        finally:
            r.close()
            w.close(unlink=True)

    def test_tensor_bytes_enter_by_pwrite(self, backend, monkeypatch):
        """The write side's twin: every tensor byte goes into the
        segment's file by positional ``write()`` (a restarted worker's
        first save would otherwise fault its fresh mapping in page by
        page); header and meta go through the mapping."""
        name = arena_name(f"t-pwrite-{os.getpid()}", 0)
        flat = {
            "strided": np.arange(4000, dtype=np.float32)[::2],
            "bf16": (np.arange(33) / 3).astype(ml_dtypes.bfloat16),
            "scalar": np.asarray(np.int32(7)),
            "empty": np.zeros((0,), np.float64),
            "big": np.arange(1 << 18, dtype=np.int64),
        }
        written = []
        real = os.pwrite

        def spy(fd, data, offset):
            n = real(fd, data, offset)
            written.append((offset, n))
            return n

        monkeypatch.setattr(shm.os, "pwrite", spy)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        try:
            w.write_state(flat, extra={"step": 1})
            assert isinstance(w._seg, backend)
            assert sum(n for _o, n in written) == sum(
                int(v.nbytes) for v in flat.values())
            data_start = shm.HEADER_SIZE + shm.DEFAULT_META_CAPACITY
            assert all(o >= data_start and o % 128 == 0
                       for o, _n in written)
            out, _ = r.read_state()
            for k, v in flat.items():
                assert out[k].dtype == v.dtype and out[k].shape == v.shape
                assert out[k].tobytes() == np.ascontiguousarray(v).tobytes()
        finally:
            r.close()
            w.close(unlink=True)

    @pytest.mark.parametrize("copy", [True, False])
    @pytest.mark.parametrize("damage", ["dirty", "chaos", "meta_crc"])
    def test_torn_arena_reads_none(self, backend, damage, copy):
        name = arena_name(f"t-torn-{damage}-{int(copy)}-{os.getpid()}", 0)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        try:
            w.write_state({"a": np.ones(64, np.float32)}, extra={"step": 1})
            assert r.read_state(copy=copy) is not None
            if damage == "dirty":
                w._seg.buf[44] = 1  # the header's dirty u32
            elif damage == "meta_crc":
                w._seg.buf[shm.HEADER_SIZE + 2] ^= 0xFF
            else:
                chaos.configure("shm.torn_read:times=1")
            assert r.read_state(copy=copy) is None
        finally:
            chaos.reset()
            r.close()
            w.close(unlink=True)

    def test_descriptor_follows_a_recreated_segment(self, backend):
        name = arena_name(f"t-regrow-{os.getpid()}", 0)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        try:
            w.write_state({"a": np.full(8, 1.0, np.float32)},
                          extra={"step": 1})
            old, _ = r.read_state(copy=False)
            assert old["a"].read()[0] == 1.0
            ino_before = os.fstat(r._seg._fd).st_ino
            big = np.full(1 << 21, 2.0, np.float32)
            w.write_state({"a": big}, extra={"step": 2})  # a new inode
            new, extra = r.read_state(copy=False)
            assert extra["step"] == 2
            assert os.fstat(r._seg._fd).st_ino == os.stat(
                f"/dev/shm/{name}").st_ino != ino_before
            np.testing.assert_array_equal(new["a"].read(), big)
            # the handle of the segment that is gone refuses, it does not
            # read whatever file its descriptor's number names now
            with pytest.raises(ValueError, match="closed"):
                old["a"].read()
            # and nothing of it is left open
            assert set(_fds_on(name)) == {f"/dev/shm/{name}"}
        finally:
            r.close()
            w.close(unlink=True)

    def test_close_leaves_no_descriptor(self, backend):
        name = arena_name(f"t-fds-{os.getpid()}", 0)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        w.write_state({"a": np.ones(8, np.float32)}, extra={"step": 1})
        handles, _ = r.read_state(copy=False)
        handles["a"].read()
        assert _fds_on(name)
        r.reopen()  # closes the old descriptor with the old mapping
        r.close()
        r.close()  # twice is harmless
        w.close(unlink=True)
        assert _fds_on(name) == []
        with pytest.raises(ValueError, match="closed"):
            handles["a"].read()

    def test_reads_share_one_descriptor_across_threads(self, backend):
        """Positional reads keep no file offset: eight threads through
        one arena object read eight tensors whole."""
        name = arena_name(f"t-threads-{os.getpid()}", 0)
        w = SharedMemoryArena(name)
        r = SharedMemoryArena(name)
        try:
            flat = {f"t{i}": np.full(1 << 16, i, np.int32) for i in range(8)}
            w.write_state(flat, extra={"step": 1})
            handles, _ = r.read_state(copy=False)
            bad = []

            def work(key):
                scratch = np.empty(4096, np.uint8)
                for _ in range(20):
                    got = b"".join(
                        bytes(c) for c in handles[key].chunks(scratch))
                    if got != flat[key].tobytes():
                        bad.append(key)

            threads = [threading.Thread(target=work, args=(k,))
                       for k in flat]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert bad == []
        finally:
            r.close()
            w.close(unlink=True)


def _lock_worker(name, hold_s, acquired_evt):
    lock = SharedLock(name)
    lock.acquire()
    acquired_evt.set()
    time.sleep(hold_s)
    lock.release()


class TestIpcPrimitives:
    def test_shared_lock_mutual_exclusion(self):
        lock = SharedLock("t-lock", create=True)
        try:
            evt = mp.Event()
            p = mp.Process(target=_lock_worker, args=("t-lock", 0.8, evt))
            p.start()
            assert evt.wait(10)
            t0 = time.time()
            assert lock.acquire(timeout=10)
            assert time.time() - t0 > 0.4  # had to wait for the child
            lock.release()
            p.join(timeout=10)
        finally:
            lock.close()

    def test_shared_lock_nonblocking(self):
        lock = SharedLock("t-lock2", create=True)
        other = SharedLock("t-lock2")
        # Different holder-id: simulate another live client.  (A "pid-…"
        # id of a dead process would be stolen by design.)
        other._holder = "other-live-holder"
        try:
            assert lock.acquire()
            assert not other.acquire(blocking=False, timeout=0.1)
            lock.release()
            assert other.acquire(blocking=False, timeout=1.0)
            other.release()
        finally:
            lock.close()

    def test_shared_queue(self):
        q = SharedQueue("t-q", create=True)
        try:
            q.put({"event": "save", "step": 1})
            q.put({"event": "save", "step": 2})
            assert q.qsize() == 2
            assert q.get()["step"] == 1
            assert q.get()["step"] == 2
            with pytest.raises(TimeoutError):
                q.get_nowait()
        finally:
            q.close()

    def test_shared_queue_blocking_get(self):
        q = SharedQueue("t-qb", create=True)
        try:
            def put_later():
                time.sleep(0.3)
                SharedQueue("t-qb").put("item")

            threading.Thread(target=put_later, daemon=True).start()
            assert q.get(timeout=10) == "item"
        finally:
            q.close()

    def test_shared_dict(self):
        d = SharedDict("t-d", create=True)
        try:
            d.set("step", 10)
            d.update({"path": "/ckpt/10", "ok": True})
            assert d.get("step") == 10
            assert d.get("missing", "dflt") == "dflt"
            snap = d.to_dict()
            assert snap["path"] == "/ckpt/10" and snap["ok"] is True
            d.delete("step")
            assert d.get("step") is None
        finally:
            d.close()

    def test_shared_dict_timeout_bounds_hung_server(self):
        """A hung stat server whose kernel backlog still ACCEPTS connects
        must cost a short-timeout dict op ~timeout+2s (the dict reply
        margin), not timeout+30s — the flash-ckpt save path and metrics
        scrape pass timeout=2.0 and rely on the bound actually holding
        (ISSUE 4 review finding)."""
        import socket as _socket

        from dlrover_tpu.common.multi_process import socket_path

        path = socket_path("dict", "t-hung")
        srv = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
        try:
            srv.bind(path)
            srv.listen(4)  # accepts into the backlog, never replies
            d = SharedDict("t-hung")  # client only
            t0 = time.time()
            with pytest.raises((ConnectionError, TimeoutError, OSError)):
                d.get("k", timeout=0.5)
            assert time.time() - t0 < 5.0
        finally:
            srv.close()
            import os as _os

            try:
                _os.unlink(path)
            except OSError:
                pass
