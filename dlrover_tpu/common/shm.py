"""POSIX shared-memory tensor arena — the flash-checkpoint staging area.

TPU-native re-design of the reference's shm scheme
(``elastic_agent/torch/ckpt_saver.py:73 TensorMeta``, ``:148
_create_shared_memory``, ``:218 SharedMemoryHandler``): a worker process
stages a flattened state (dict of numpy arrays, produced from the addressable
shards of a sharded jax pytree) into one named shm segment; the agent process
maps the same segment and persists it to storage asynchronously.

Segment layout::

    [ header 64B | meta region (msgpack, fixed capacity) | tensor data ]

Write protocol (single writer, fenced by a SharedLock at the engine layer):
tensor bytes first, then meta, then the header's ``meta_len``/``commit_count``
— a reader that sees a consistent header+crc sees consistent data.

Two backends: the C++ native one (``native/shm_arena.cc`` via ctypes —
shm_open/mmap, no Python resource-tracker interference) and a
``multiprocessing.shared_memory`` fallback.

The mapping serves the header and the meta blob (a few KB), on both
sides.  Tensor bytes enter and leave the arena by positional
``write()``/``read()`` on the segment's file (:func:`_pwrite_full`,
:class:`ArenaTensor`), from and into memory the caller owns: each
process has just made its mapping, and where a fault on a fresh
shared-memory page is dear (gVisor: ~35 us a 4 KiB page, once a mapping,
no fault-around) touching the 1.4 M pages of a 5.76 GB state costs most
of a minute — for the first save, for the agent's persist, for the
restore and (once the restore no longer faulted the pages in as a side
effect) for the restarted worker's first save — while ``read()`` and
``write()`` of the same file pay none of it and are one memcpy anywhere
else.
"""

from __future__ import annotations

import ctypes
import dataclasses
import errno
import os
import struct
from typing import Dict, Optional, Tuple

import msgpack
import numpy as np

from dlrover_tpu import chaos
from dlrover_tpu.common.byte_audit import audit
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.native import shm_lib

MAGIC = 0x44_4C_52_54_50_55_01_00  # "DLRTPU\x01\x00"
HEADER_SIZE = 64
DEFAULT_META_CAPACITY = 8 << 20  # 8 MB of msgpack metadata
# header: magic u64 | data_capacity u64 | meta_capacity u64 | meta_len u64 |
#         commit_count u64 | meta_crc u32 | dirty u32 | pad
# ``dirty`` is set before tensor bytes are overwritten and cleared by the
# final header write: a writer killed mid-write leaves dirty=1, and readers
# treat the arena as holding no valid state (tensor bytes are torn; the CRC
# only covers the meta blob).
_HEADER_FMT = "<QQQQQII"


@dataclasses.dataclass
class TensorMeta:
    """Placement of one tensor inside the arena (reference
    ``ckpt_saver.py:73``)."""

    dtype: str
    shape: tuple
    offset: int
    nbytes: int


def _pwrite_full(fd: int, buf, offset: int, what: str) -> None:
    """Write ``buf`` (C-contiguous bytes) to ``fd`` at ``offset``; the
    positional twin of :func:`_pread_full`."""
    if fd < 0:
        raise ValueError(f"shm segment {what} is closed")
    mv = memoryview(buf).cast("B")
    done = 0
    while done < len(mv):
        done += os.pwrite(fd, mv[done:], offset + done)


def _pread_full(fd: int, buf, offset: int, what: str) -> None:
    """Fill ``buf`` (writable, C-contiguous bytes) from ``fd`` starting
    at ``offset``.  Positional, so threads share the descriptor freely;
    one call moves at most ~2 GiB on Linux, hence the loop."""
    if fd < 0:
        raise ValueError(f"shm segment {what} is closed")
    mv = memoryview(buf).cast("B")
    done = 0
    while done < len(mv):
        got = os.preadv(fd, [mv[done:]], offset + done)
        if got <= 0:
            raise OSError(
                errno.EIO,
                f"shm segment {what}: short read at {offset + done} "
                f"({len(mv) - done} bytes missing)",
            )
        done += got


class ArenaTensor:
    """One staged tensor as :meth:`SharedMemoryArena.read_state` hands
    it to a bulk consumer: what it is (``dtype``, ``shape``, ``nbytes``)
    and where its bytes lie in the segment — never the bytes, and never
    a view of the mapping.  The bytes are fetched by ``read()`` on the
    segment's file into memory the consumer owns: a fresh array
    (:meth:`read`), a reused buffer (:meth:`read`'s ``out``,
    :meth:`chunks`), any window (:meth:`read_into`).

    Valid under the hold :meth:`SharedMemoryArena.read_state` describes;
    after the arena was closed or re-opened a read raises."""

    __slots__ = ("_seg", "dtype", "shape", "offset", "nbytes")

    def __init__(self, seg, dtype, shape, offset: int, nbytes: int):
        self._seg = seg
        self.dtype = np.dtype(dtype)
        self.shape = tuple(int(d) for d in shape)
        self.offset = int(offset)
        self.nbytes = int(nbytes)

    def __repr__(self) -> str:
        return (f"ArenaTensor({self.dtype.name}{list(self.shape)} "
                f"@{self.offset}+{self.nbytes})")

    def __array__(self, *_args, **_kwargs):
        # np.asarray() of a handle would otherwise make a 0-d object
        # array and every consumer downstream would write garbage
        raise TypeError(
            "ArenaTensor holds no bytes: call read(), read_into() or "
            "chunks()"
        )

    def byte_range(self, lo: int, hi: int) -> "ArenaTensor":
        """Bytes ``[lo, hi)`` of this tensor's C-order buffer, as a
        flat uint8 handle (a slice of a sliced persist)."""
        lo, hi = int(lo), int(hi)
        if not 0 <= lo <= hi <= self.nbytes:
            raise ValueError(f"byte range [{lo}, {hi}) outside {self!r}")
        return ArenaTensor(
            self._seg, np.uint8, (hi - lo,), self.offset + lo, hi - lo
        )

    def read_into(self, buf, lo: int = 0) -> int:
        """Fill ``buf`` (uint8 array, bytearray or writable memoryview)
        with this tensor's bytes from ``lo`` on: ``len(buf)`` of them,
        or what is left.  Returns the count."""
        mv = memoryview(buf).cast("B")
        n = min(len(mv), self.nbytes - lo)
        if n > 0:
            _pread_full(
                self._seg._fd, mv[:n], self.offset + lo, self._seg.name
            )
        return max(n, 0)

    def read(self, out: Optional[np.ndarray] = None) -> np.ndarray:
        """The tensor, typed and shaped, in memory the caller owns: a
        fresh array, or the head of ``out`` (a flat uint8 buffer of at
        least ``nbytes``, whose earlier contents are gone)."""
        if out is None:
            arr = np.empty(self.shape, dtype=self.dtype)
            self.read_into(arr.reshape(-1).view(np.uint8))
            return arr
        if out.dtype != np.uint8 or out.ndim != 1 or out.size < self.nbytes:
            raise ValueError(
                f"staging buffer {out.dtype}{list(out.shape)} cannot "
                f"take {self!r}"
            )
        head = out[: self.nbytes]
        self.read_into(head)
        return head.view(self.dtype).reshape(self.shape)

    def chunks(self, buf: np.ndarray):
        """The tensor's bytes in order, ``len(buf)`` at a time, each
        yielded as a memoryview of ``buf`` refilled: a chunk is gone
        once the next one is asked for."""
        mv = memoryview(buf).cast("B")
        for lo in range(0, self.nbytes, len(mv)):
            yield mv[: self.read_into(mv, lo)]


def _required_size(flat: Dict[str, np.ndarray], meta_capacity: int) -> int:
    data = sum(int(a.nbytes) for a in flat.values())
    # Round each tensor start to 128B for aligned copies.
    data += 128 * max(1, len(flat))
    return HEADER_SIZE + meta_capacity + data


class _NativeSegment:
    """shm_open/mmap backend via native/shm_arena.cc."""

    def __init__(self, name: str, size: int, create: bool):
        self._lib = shm_lib()
        if self._lib is None:
            raise OSError("native shm library unavailable")
        cname = ("/" + name.lstrip("/")).encode()
        self.name = name
        if create:
            fd = self._lib.shm_arena_create(cname, size)
        else:
            fd = self._lib.shm_arena_open(cname)
        if fd < 0:
            raise OSError(-fd, f"shm open failed for {name}")
        real = self._lib.shm_arena_size(fd)
        if real < 0:
            self._lib.shm_arena_close(fd)
            raise OSError(-real, f"fstat failed for {name}")
        self.size = int(real) if not create else max(int(real), size)
        ptr = self._lib.shm_arena_map(fd, self.size)
        if not ptr:
            self._lib.shm_arena_close(fd)
            raise OSError(f"mmap failed for {name}")
        self._fd = fd
        self._ptr = ptr
        self.buf = np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_ubyte)), shape=(self.size,)
        )

    def crc32(self, offset: int, n: int) -> int:
        return int(self._lib.shm_crc32(self._ptr + offset, n, 0))

    def close(self, unlink: bool = False) -> None:
        # a handle that outlives the segment must fail, not read
        # whichever file the descriptor's number names next
        fd, self._fd = self._fd, -1
        try:
            self._lib.shm_arena_unmap(self._ptr, self.size)
            self._lib.shm_arena_close(fd)
            if unlink:
                self._lib.shm_arena_unlink(("/" + self.name.lstrip("/")).encode())
        # graftcheck: disable=CC104 -- teardown path: the peer may have
        # already unmapped/unlinked the segment; close must not raise
        except Exception:  # noqa: BLE001
            pass


class _PySegment:
    """multiprocessing.shared_memory fallback backend."""

    def __init__(self, name: str, size: int, create: bool):
        from multiprocessing import resource_tracker, shared_memory

        self.name = name
        if create:
            try:
                self._shm = shared_memory.SharedMemory(
                    name=name, create=True, size=size
                )
            except FileExistsError:
                # A stale segment from a crashed run may be smaller than we
                # need (this backend cannot ftruncate-grow): replace it.
                existing = shared_memory.SharedMemory(name=name)
                if existing.size >= size:
                    self._shm = existing
                else:
                    existing.close()
                    existing.unlink()
                    self._shm = shared_memory.SharedMemory(
                        name=name, create=True, size=size
                    )
        else:
            self._shm = shared_memory.SharedMemory(name=name)
        # Detach from the resource tracker: segment lifetime is managed by the
        # agent (creator), not whichever process exits first.
        try:
            resource_tracker.unregister(f"/{name}", "shared_memory")
        # graftcheck: disable=CC104 -- unregister is best-effort: the
        # tracker API differs across Python versions and a miss only
        # re-enables the default cleanup
        except Exception:  # noqa: BLE001
            pass
        self.size = self._shm.size
        self.buf = np.frombuffer(self._shm.buf, dtype=np.uint8)
        try:
            self._fd = os.open(
                f"/dev/shm/{name.lstrip('/')}", os.O_RDWR | os.O_CLOEXEC
            )
        except OSError:
            self._shm.close()
            raise

    def crc32(self, offset: int, n: int) -> int:
        import zlib

        # zlib hashes the mapped bytes through the buffer protocol —
        # no tobytes() copy of the whole region just to checksum it.
        return zlib.crc32(self.buf[offset : offset + n]) & 0xFFFFFFFF

    def close(self, unlink: bool = False) -> None:
        fd, self._fd = self._fd, -1
        try:
            if fd >= 0:
                os.close(fd)
            self.buf = None
            self._shm.close()
            if unlink:
                self._shm.unlink()
        # graftcheck: disable=CC104 -- teardown path: double-close and
        # unlink-after-peer-unlink are expected during agent restarts
        except Exception:  # noqa: BLE001
            pass


def _shm_stat(name: str):
    """(st_ino, st_size) of the backing /dev/shm file, or None.  Both
    backends materialize the segment there on Linux, so this is the shared
    source of truth for 'has the writer re-created the segment?'."""
    try:
        st = os.stat(f"/dev/shm/{name.lstrip('/')}")
        return (st.st_ino, st.st_size)
    except OSError:
        return None


def _check_shm_space(name: str, size: int) -> None:
    """tmpfs hands out sparse files: creating a segment larger than what
    /dev/shm has left succeeds, and the process dies of SIGBUS in the
    middle of the copy.  Refuse up front with a plain message."""
    try:
        st = os.statvfs("/dev/shm")
    except OSError:
        return  # no tmpfs mount to ask (non-Linux)
    free = st.f_bavail * st.f_frsize
    have = (_shm_stat(name) or (0, 0))[1]
    if size - have > free:
        raise OSError(
            errno.ENOSPC,
            f"/dev/shm has {free >> 20} MiB free but checkpoint arena "
            f"{name} needs {(size - have) >> 20} MiB more: enlarge "
            "/dev/shm or checkpoint less state per host",
        )


def _open_segment(name: str, size: int, create: bool):
    if create:
        _check_shm_space(name, size)
    if shm_lib() is not None:
        try:
            return _NativeSegment(name, size, create)
        except OSError as e:
            if not create:
                raise FileNotFoundError(
                    f"shm segment {name} not found: {e}"
                ) from e
            logger.warning("native shm open failed (%s); python fallback", e)
    # No native toolchain: both read and write sides use the Python backend
    # (they interoperate — same /dev/shm file).
    try:
        return _PySegment(name, size, create)
    except FileNotFoundError:
        raise
    except OSError as e:
        if not create:
            raise FileNotFoundError(f"shm segment {name} not found: {e}") from e
        raise


class SharedMemoryArena:
    """One named arena holding one staged checkpoint state.

    Writers (worker processes) call :meth:`write_state`; readers (agent saver
    daemon, or a restarted worker doing a warm restore) call
    :meth:`read_state` / :meth:`metadata`.
    """

    def __init__(
        self,
        name: str,
        create: bool = False,
        size: int = 0,
        meta_capacity: int = DEFAULT_META_CAPACITY,
    ):
        self.name = name
        self._meta_capacity = meta_capacity
        self._seg = None
        if create and size:
            self._seg = _open_segment(name, size, create=True)

    # -- writer side --------------------------------------------------------
    def will_allocate(self, flat: Dict[str, np.ndarray]) -> bool:
        """Whether :meth:`write_state` of ``flat`` has to create the
        segment (none yet, or too small): the write then also touches
        every page for the first time, which is what a job's first save
        pays and no later one (a restarted worker finds the pages)."""
        need = _required_size(flat, self._meta_capacity)
        if self._seg is not None:
            return self._seg.size < need
        have = _shm_stat(self.name)
        return have is None or have[1] < need

    def write_state(
        self, flat: Dict[str, np.ndarray], extra: Optional[dict] = None
    ) -> None:
        """Stage a flat ``path -> ndarray`` state (+ JSON-able ``extra`` such
        as step, treedef, sharding info) into the arena, growing it if needed.
        """
        need = _required_size(flat, self._meta_capacity)
        if self._seg is None or self._seg.size < need:
            if self._seg is not None:
                self._seg.close(unlink=True)
            self._seg = _open_segment(self.name, need, create=True)
            self._seg_stat = _shm_stat(self.name)
        seg = self._seg

        # Mark the write in progress BEFORE touching tensor bytes, so a
        # writer killed mid-copy cannot be mistaken for a committed state
        # (the fencing lock may be stolen from a dead holder).
        prev = self._read_header()
        prev_commit = prev[4] if prev else 0
        dirty_header = struct.pack(
            _HEADER_FMT, MAGIC, seg.size, self._meta_capacity,
            prev[3] if prev else 0, prev_commit, prev[5] if prev else 0, 1,
        )
        seg.buf[: len(dirty_header)] = np.frombuffer(
            dirty_header, dtype=np.uint8
        )

        offset = HEADER_SIZE + self._meta_capacity
        metas: Dict[str, dict] = {}
        for path, arr in flat.items():
            arr = np.asarray(arr)
            offset = (offset + 127) & ~127  # 128B alignment
            if arr.nbytes:
                _pwrite_full(
                    seg._fd,
                    np.ascontiguousarray(arr).reshape(-1).view(np.uint8),
                    offset, seg.name,
                )
            # dtype.name round-trips extended types (bfloat16/fp8 via
            # ml_dtypes) where dtype.str degrades to raw void ('<V2').
            try:
                dtype_key = (
                    arr.dtype.name
                    if np.dtype(arr.dtype.name) == arr.dtype
                    else arr.dtype.str
                )
            except TypeError:
                dtype_key = arr.dtype.str
            metas[path] = dataclasses.asdict(
                TensorMeta(
                    dtype=dtype_key, shape=tuple(arr.shape),
                    offset=offset, nbytes=int(arr.nbytes),
                )
            )
            offset += arr.nbytes

        meta_blob = msgpack.packb(
            {"tensors": metas, "extra": extra or {}}, use_bin_type=True
        )
        if len(meta_blob) > self._meta_capacity:
            raise ValueError(
                f"checkpoint metadata ({len(meta_blob)}B) exceeds meta region "
                f"({self._meta_capacity}B); raise meta_capacity"
            )
        seg.buf[HEADER_SIZE : HEADER_SIZE + len(meta_blob)] = np.frombuffer(
            meta_blob, dtype=np.uint8
        )
        crc = seg.crc32(HEADER_SIZE, len(meta_blob))
        header = struct.pack(
            _HEADER_FMT,
            MAGIC,
            seg.size,
            self._meta_capacity,
            len(meta_blob),
            prev_commit + 1,
            crc,
            0,  # clear dirty: state is consistent again
        )
        seg.buf[: len(header)] = np.frombuffer(header, dtype=np.uint8)
        # mmap stores do not reliably bump the tmpfs file's mtime, so a
        # live arena written only through memcpy looks idle forever.
        # Touch it explicitly: the launcher's startup GC keys "live" on
        # mtime freshness and must never wipe a sibling run's staged
        # checkpoint on a shared host.
        try:
            os.utime(f"/dev/shm/{self.name.lstrip('/')}")
        except OSError:  # pragma: no cover - segment raced away
            pass

    # -- reader side --------------------------------------------------------
    def _ensure_open(self) -> None:
        if self._seg is None:
            self._seg = _open_segment(self.name, 0, create=False)
            self._seg_stat = _shm_stat(self.name)

    def _read_header(self):
        if self._seg is None:
            return None
        raw = bytes(self._seg.buf[: struct.calcsize(_HEADER_FMT)])
        vals = struct.unpack(_HEADER_FMT, raw)
        if vals[0] != MAGIC:
            return None
        return vals

    def reopen(self) -> None:
        """Re-map the segment (it may have been re-created bigger); its
        descriptor, which the tensor reads go through, is re-opened with
        it."""
        if self._seg is not None:
            self._seg.close()
            self._seg = None
        self._ensure_open()

    def metadata(self) -> Optional[dict]:
        """Read {tensors: {path: TensorMeta-dict}, extra: {...}} or None if
        the arena holds no committed state."""
        try:
            self._ensure_open()
        except FileNotFoundError:
            return None
        # Growth re-creates the named segment (new inode): a long-attached
        # reader must notice and remap, or it would serve stale state forever.
        cur_stat = _shm_stat(self.name)
        if cur_stat is not None and cur_stat != getattr(self, "_seg_stat", None):
            try:
                self.reopen()
            except FileNotFoundError:
                return None
        hdr = self._read_header()
        if hdr is None:
            return None
        _, data_cap, meta_cap, meta_len, commit, crc, dirty = hdr
        if chaos.inject("shm.torn_read") is not None:
            # Behave exactly as if the writer died mid-write: readers see
            # no valid state and must take their storage-fallback path.
            logger.warning(
                "chaos: shm.torn_read — arena %s reports torn state",
                self.name,
            )
            return None
        if dirty:
            logger.warning(
                "shm arena %s: writer died mid-write (dirty); no valid state",
                self.name,
            )
            return None
        if commit == 0 or meta_len == 0:
            return None
        if self._seg.crc32(HEADER_SIZE, meta_len) != crc:
            logger.warning("shm arena %s: meta crc mismatch (torn write?)", self.name)
            return None
        blob = bytes(self._seg.buf[HEADER_SIZE : HEADER_SIZE + meta_len])
        meta = msgpack.unpackb(blob, raw=False, strict_map_key=False)
        meta["commit_count"] = commit
        return meta

    def read_state(
        self, copy: bool = True
    ) -> Optional[Tuple[Dict[str, "np.ndarray | ArenaTensor"], dict]]:
        """Read the staged state: header and meta through the mapping,
        tensor bytes by ``read()`` on the segment's file — no consumer
        walks the mapping page by page.

        ``copy=False`` returns an :class:`ArenaTensor` per tensor: dtype,
        shape and place, and the means to fill a buffer from it.  It is
        for a consumer that moves the bytes on while it holds the arena
        (the saver's streamed persist, chunk by chunk into one reused
        buffer; the warm restore, piece by piece into a staging buffer
        and from there to ``jax.device_put``).  Lifetime contract: a
        handle reads the live segment, so it is valid only while (a)
        this arena object stays open (no concurrent
        :meth:`reopen`/:meth:`close` — callers hold their arena mutex)
        and (b) every writer is fenced out (the per-rank SharedLock),
        since a concurrent :meth:`write_state` would rewrite the bytes
        under it.  The caller takes both BEFORE this call and keeps them
        until the last byte has been read (for a restore: until
        ``block_until_ready`` has returned).

        ``copy=True`` reads every tensor into an array of its own, for
        a consumer that outlives that hold (the replica push, whose
        payload is shipped after the lock is released; a
        ``ShardSource`` returned to the caller of ``load()``; the
        reshard mover's source).  It needs the same hold for as long as
        it runs — ``dirty`` is looked at once, before the first
        tensor."""
        meta = self.metadata()
        if meta is None:
            return None
        out: Dict[str, "np.ndarray | ArenaTensor"] = {}
        nbytes_total = 0
        for path, tm in meta["tensors"].items():
            handle = ArenaTensor(
                self._seg, tm["dtype"], tm["shape"], tm["offset"],
                tm["nbytes"],
            )
            out[path] = handle.read() if copy else handle
            nbytes_total += handle.nbytes
        if copy:
            audit.record_copy(nbytes_total, "arena_read_copy")
        return out, meta["extra"]

    def close(self, unlink: bool = False) -> None:
        if self._seg is not None:
            self._seg.close(unlink=unlink)
            self._seg = None


def arena_name(job_name: str, local_rank: int, purpose: str = "ckpt") -> str:
    """Canonical per-rank arena naming (reference ``_get_shm_name``),
    scoped by the launcher run id so a fresh launch never reads a stale
    arena left by a previous job of the same name."""
    from dlrover_tpu.common.env import run_scoped

    safe = run_scoped(job_name).replace("/", "_")
    return f"dlrtpu_{safe}_{purpose}_{local_rank}"
