"""Segment movers: execute a :class:`~dlrover_tpu.reshard.plan.ReshardPlan`.

Two substrates, chosen per segment by the plan's rank topology:

- **intra-host** segments are numpy-level copies out of the rank's
  staged shards — the shm arena's tensors, ``read()`` into arrays of
  their own (:meth:`LocalShardSource.from_arena`), or zero-copy views of
  the live state's host shards;
- **cross-host** segments ride a replica-ring-style RPC
  (:class:`ReshardPeer`): the destination pulls each segment from the
  source rank's published shard table, and every payload carries a CRC-32
  the receiver verifies before the bytes can reach the rebuilt state
  (the ``check_replica_payload`` pattern from ``checkpoint/replica.py``).

Any missing, torn, or mismatched segment raises
:class:`ReshardMoveError`; the coordinator treats that as "live reshard
failed" and falls back to the checkpoint-restart ladder.

Chaos sites (``DLROVER_TPU_FAULTS``): ``reshard.drop_segment`` makes the
serving side lose a segment, ``reshard.stall_peer`` delays its replies,
``reshard.crash_mid_move`` hard-kills the pulling process between segment
applies.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from dlrover_tpu import chaos
from dlrover_tpu.checkpoint.shard_file import crc32_bytes
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.log import logger
from dlrover_tpu.reshard.plan import Box, ReshardPlan, Segment

_KV_PREFIX = "reshard/addr/"


class ReshardMoveError(RuntimeError):
    """A segment could not be moved (peer unreachable, payload lost or
    CRC-torn, shape mismatch).  Non-retryable at this layer: the caller
    falls back to the restart ladder."""


def _local_slices(box: Box, src_box: Box) -> Tuple[slice, ...]:
    return tuple(
        slice(bs - ss, be - ss) for (bs, be), (ss, _se) in zip(box, src_box)
    )


class LocalShardSource:
    """One rank's staged shards: ``{key: array}`` plus each key's global
    box.  Arrays may be zero-copy views (live host shards);
    :meth:`segment_view` never copies — the caller does, into the
    destination buffer."""

    def __init__(
        self,
        tensors: Dict[str, np.ndarray],
        infos: Dict[str, dict],
    ):
        self.tensors = tensors
        self.boxes: Dict[str, Box] = {
            key: tuple(tuple(int(v) for v in p) for p in meta["index"])
            for key, meta in infos.items()
        }

    @classmethod
    def from_arena(cls, arena) -> "LocalShardSource":
        """Source over a staged shm arena.  The tensors are
        ``read_state(copy=True)`` arrays — ``read()`` off the arena's
        file like every other bulk consumer's bytes, into memory the
        source owns — so the caller must keep the arena open and the
        writer fenced (the per-rank SharedLock / arena mutex) only for
        the length of this call; the source, and anything published
        from it, stays readable afterwards.  The price is one host copy
        of the rank's staged state for the length of the move.  Raises
        when the arena holds no valid staged state (a torn/mid-write
        arena must fail the move, which lands the resize on the restart
        ladder, not on torn bytes)."""
        read = arena.read_state(copy=True)
        if read is None:
            raise ReshardMoveError(
                f"arena {arena.name} holds no staged state"
            )
        tensors, extra = read
        infos = extra.get("tensors_info") or {}
        if not infos:
            raise ReshardMoveError(
                f"arena {arena.name} staged state carries no tensors_info"
            )
        return cls(tensors, infos)

    def segment_view(self, seg: Segment) -> np.ndarray:
        arr = self.tensors.get(seg.src_key)
        box = self.boxes.get(seg.src_key)
        if arr is None or box is None:
            raise ReshardMoveError(
                f"source shard {seg.src_key!r} not staged on rank "
                f"{seg.src_rank}"
            )
        if box != seg.src_box:
            raise ReshardMoveError(
                f"source shard {seg.src_key!r} box drifted: plan says "
                f"{seg.src_box}, table holds {box}"
            )
        return np.asarray(arr)[_local_slices(seg.box, box)]


class _PeerServicer:
    """RPC handler for :class:`ReshardPeer`: answers segment pulls from
    the locally published shard table."""

    def __init__(self, peer: "ReshardPeer"):
        self._peer = peer

    def __call__(self, msg: m.Message) -> Optional[m.Message]:
        if not isinstance(msg, m.ReshardFetch):
            return m.BaseResponse(
                success=False,
                reason=f"unknown message {type(msg).__name__}",
            )
        # Chaos: a stalled peer (slow NIC, contended host) delays every
        # reply; a dropped segment vanishes in flight — the puller must
        # fail the move and fall back, never hang or accept torn bytes.
        chaos.inject("reshard.stall_peer", rank=msg.src_rank)
        if chaos.inject(
            "reshard.drop_segment", rank=msg.src_rank
        ) is not None:
            return m.ReshardSegment(
                found=False, reason="chaos: segment dropped"
            )
        with self._peer._mu:
            table = self._peer._table
        if table is None:
            return m.ReshardSegment(found=False, reason="nothing published")
        epoch, step, source = table
        if msg.epoch != epoch or (msg.step >= 0 and msg.step != step):
            return m.ReshardSegment(
                found=False,
                reason=(
                    f"epoch/step mismatch (published {epoch}/{step}, "
                    f"asked {msg.epoch}/{msg.step})"
                ),
            )
        arr = source.tensors.get(msg.key)
        box = source.boxes.get(msg.key)
        if arr is None or box is None:
            return m.ReshardSegment(
                found=False, reason=f"shard {msg.key!r} not published"
            )
        want = tuple(tuple(int(v) for v in p) for p in msg.box)
        for (bs, be), (ss, se) in zip(want, box):
            if bs < ss or be > se:
                return m.ReshardSegment(
                    found=False,
                    reason=f"box {want} outside published shard {box}",
                )
        view = np.ascontiguousarray(
            np.asarray(arr)[_local_slices(want, box)]
        )
        payload = view.tobytes()
        return m.ReshardSegment(
            found=True,
            payload=payload,
            crc32=crc32_bytes(payload),
            dtype=str(view.dtype.name),
            shape=list(view.shape),
        )


class ReshardPeer:
    """Agent-side segment server + puller for one rank.

    ``publish`` exposes this rank's staged shards for the duration of a
    resize epoch (the arrays are NOT copied: where they are views of
    live host shards, keep those alive and unwritten until
    :meth:`unpublish`; a :meth:`LocalShardSource.from_arena` source
    owns its bytes); peers discover each other through
    the master KV store under ``reshard/addr/{rank}``, exactly like the
    replica ring."""

    def __init__(self, master_client=None, rank: int = 0):
        from dlrover_tpu.common.rpc import RpcServer, local_ip

        self.client = master_client
        self.rank = rank
        self._mu = threading.Lock()
        self._table: Optional[Tuple[int, int, LocalShardSource]] = None
        self._server = RpcServer(0, _PeerServicer(self))
        self._server.start()
        self.addr = f"{local_ip()}:{self._server.port}"
        self._peers: Dict[int, object] = {}
        self._register()

    def _register(self) -> None:
        if self.client is None:
            return
        try:
            self.client.kv_store_set(
                f"{_KV_PREFIX}{self.rank}", self.addr.encode()
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("reshard addr registration failed: %s", e)

    def publish(
        self,
        epoch: int,
        step: int,
        tensors: Dict[str, np.ndarray],
        infos: Dict[str, dict],
    ) -> None:
        with self._mu:
            self._table = (epoch, step, LocalShardSource(tensors, infos))

    def unpublish(self) -> None:
        with self._mu:
            self._table = None

    def _peer_client(self, rank: int, addr: Optional[str] = None):
        from dlrover_tpu.common.rpc import RpcClient

        if addr is None:
            if self.client is None:
                return None
            try:
                raw = self.client.kv_store_get(f"{_KV_PREFIX}{rank}")
            except Exception:  # noqa: BLE001
                return None
            if not raw:
                return None
            addr = raw.decode()
        cli = self._peers.get(rank)
        if cli is None or cli.addr != addr:
            cli = RpcClient(addr, timeout=30.0)
            self._peers[rank] = cli
        return cli

    def fetch_segment(
        self,
        seg: Segment,
        epoch: int,
        step: int = -1,
        addr: Optional[str] = None,
    ) -> np.ndarray:
        """Pull one segment from its source rank; CRC + shape verified
        before the bytes are trusted."""
        cli = self._peer_client(seg.src_rank, addr)
        if cli is None:
            raise ReshardMoveError(
                f"no reshard peer address for rank {seg.src_rank}"
            )
        try:
            resp = cli.call(
                m.ReshardFetch(
                    epoch=epoch,
                    step=step,
                    src_rank=seg.src_rank,
                    key=seg.src_key,
                    box=[list(p) for p in seg.box],
                )
            )
        except Exception as e:  # noqa: BLE001
            raise ReshardMoveError(
                f"segment pull from rank {seg.src_rank} failed: {e}"
            ) from e
        return check_segment_payload(resp, seg)

    def stop(self) -> None:
        self._server.stop()
        for cli in self._peers.values():
            cli.close()


def check_segment_payload(resp: m.Message, seg: Segment) -> np.ndarray:
    """Verify a :class:`~dlrover_tpu.common.messages.ReshardSegment`
    reply against the plan's segment: found, CRC-32 intact, shape and
    byte count exactly the planned region.  Returns the decoded array;
    raises :class:`ReshardMoveError` on any mismatch — a torn transfer
    must never reach the rebuilt state."""
    if not isinstance(resp, m.ReshardSegment) or not resp.found:
        raise ReshardMoveError(
            f"segment {seg.src_key!r} {seg.box} lost in flight: "
            f"{getattr(resp, 'reason', 'bad reply type')}"
        )
    if crc32_bytes(resp.payload) != resp.crc32:
        raise ReshardMoveError(
            f"segment {seg.src_key!r} {seg.box} payload CRC mismatch "
            "(torn transfer)"
        )
    want_shape = tuple(e - s for s, e in seg.box)
    if tuple(resp.shape) != want_shape:
        raise ReshardMoveError(
            f"segment {seg.src_key!r} shape {tuple(resp.shape)} != "
            f"planned {want_shape}"
        )
    try:
        arr = np.frombuffer(
            resp.payload, dtype=np.dtype(resp.dtype)
        ).reshape(want_shape)
    except (TypeError, ValueError) as e:
        raise ReshardMoveError(
            f"segment {seg.src_key!r} payload undecodable: {e}"
        ) from e
    return arr


class SegmentMover:
    """Execute a validated plan for one destination rank.

    ``local_sources`` maps source ranks whose shards are reachable
    in-process (this rank's own state; on a shared host, sibling ranks'
    arenas) to their :class:`LocalShardSource`.  Segments from any other
    rank go through ``fetch`` (a :class:`ReshardPeer` bound method, or
    any ``(segment) -> np.ndarray``)."""

    def __init__(
        self,
        dst_rank: int,
        local_sources: Dict[int, LocalShardSource],
        fetch: Optional[Callable[[Segment], np.ndarray]] = None,
    ):
        self.dst_rank = dst_rank
        self.local_sources = local_sources
        self.fetch = fetch

    def execute(
        self, plan: ReshardPlan
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, dict], dict]:
        """-> (tensors, infos, stats) for this rank's target shards, in
        exactly the ``flatten_to_shards`` key/info format so the result
        feeds ``ShardSource``/``restore_to_target`` (or the shm arena)
        unchanged."""
        t0 = time.perf_counter()
        out: Dict[str, np.ndarray] = {}
        infos: Dict[str, dict] = {}
        stats = {"local_bytes": 0, "cross_bytes": 0, "segments": 0}
        my_shards = plan.dst.shards.get(self.dst_rank, {})
        for key, box in my_shards.items():
            path = key.rsplit("|", 1)[0]
            info = plan.dst.tensors[path]
            shape = tuple(e - s for s, e in box)
            dtype = np.dtype(info.dtype) if info.dtype else None
            buf: Optional[np.ndarray] = None
            if dtype is not None:
                buf = np.empty(shape, dtype=dtype)
            for n, seg in enumerate(
                s for s in plan.for_dst_rank(self.dst_rank)
                if s.dst_key == key
            ):
                # Chaos: a puller hard-killed between segment applies —
                # the survivors' coordinator must detect the lost rank
                # and the job must land on the restart ladder with
                # fsck-clean storage (no torn state escapes this loop).
                chaos.inject(
                    "reshard.crash_mid_move", rank=self.dst_rank, step=n
                )
                src = self.local_sources.get(seg.src_rank)
                if src is not None:
                    piece = src.segment_view(seg)
                    stats["local_bytes"] += int(piece.nbytes)
                elif self.fetch is not None:
                    piece = self.fetch(seg)
                    stats["cross_bytes"] += int(piece.nbytes)
                else:
                    raise ReshardMoveError(
                        f"rank {seg.src_rank} unreachable: no local "
                        "source and no fetch path"
                    )
                if buf is None:
                    buf = np.empty(shape, dtype=np.asarray(piece).dtype)
                dst_sl = _local_slices(seg.box, box)
                buf[dst_sl] = np.asarray(piece).reshape(
                    tuple(e - s for s, e in seg.box)
                )
                stats["segments"] += 1
            if buf is None:
                # Zero-volume shard (empty tensor) or 0-d covered above;
                # allocate the empty buffer with the declared dtype.
                buf = np.empty(
                    shape, dtype=dtype if dtype is not None else np.float32
                )
            out[key] = buf
            infos[key] = {
                "path": path,
                "global_shape": list(info.global_shape),
                "index": [list(p) for p in box],
            }
        stats["elapsed_s"] = time.perf_counter() - t0
        return out, infos, stats
