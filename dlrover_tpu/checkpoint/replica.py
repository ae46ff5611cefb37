"""In-memory cross-node checkpoint replicas.

Parity with reference ``trainer/torch/flash_checkpoint/replica.py``
(``CkptReplicaManger :28``, ``ShardCkptReplicaManager :73``,
``FullCkptReplicaManager :247``): each node backs up its staged shm
checkpoint onto a peer so a *replaced* node can warm-restore without
touching (possibly slow/stale) persistent storage — the
emergency-checkpoint pattern over DCN (SURVEY.md §5 "Checkpoint/resume").

Topology: ring backup.  Node ``r`` pushes its processes' shards to node
``(r+1) % world`` over the control-plane RPC; a relaunched node ``r``
fetches them back from ``(r+1) % world``.  Peer addresses rendezvous
through the master KV store under ``replica/addr/{node_rank}``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from dlrover_tpu import chaos
from dlrover_tpu.agent.metrics import integrity_counters
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.rpc import RpcClient, RpcServer, local_ip
from dlrover_tpu.checkpoint import shard_file

_KV_PREFIX = "replica/addr/"


def _layout_mismatch(
    extra: dict, expect_process_id: int, expect_step: int
) -> Optional[str]:
    """Step/world-layout metadata check on a replica payload's ``extra``.
    Returns a rejection reason or ``None``."""
    if int(extra.get("step", -1)) != int(expect_step):
        return (
            f"step mismatch (payload says {extra.get('step')}, "
            f"envelope says {expect_step})"
        )
    pid = extra.get("process_id")
    if pid is not None and int(pid) != int(expect_process_id):
        return (
            f"process mismatch (payload proc {pid}, "
            f"envelope proc {expect_process_id})"
        )
    if not extra.get("tensors_info"):
        return "tensors_info missing (payload could never seed a restore)"
    if int(extra.get("num_processes", 0) or 0) <= 0:
        return "num_processes missing"
    return None


def check_replica_payload(
    payload: bytes, process_id: int, step: int
) -> Optional[str]:
    """CRC + layout verification of a replica payload (both directions of
    the ring exchange).  Returns a rejection reason or ``None``."""
    try:
        extra = shard_file.verify_shard(payload)
    except shard_file.ShardCorruptionError as e:
        return f"corrupt payload: {e}"
    return _layout_mismatch(extra, process_id, step)


def _chaos_torn_push(payload: bytes, step: int, process_id: int) -> bytes:
    """``replica.torn_push`` chaos site: only a prefix of the payload
    survives the transfer — the receiver's verification must reject it."""
    if chaos.inject(
        "replica.torn_push", step=step, rank=process_id
    ) is None:
        return payload
    return payload[: max(1, len(payload) // 2)]


class ReplicaStore:
    """Per-node replica holder: process_id -> (step, packed shard bytes)."""

    def __init__(self, max_bytes: int = 64 << 30):
        self._lock = threading.Lock()
        self._data: Dict[int, Tuple[int, bytes]] = {}
        self._max_bytes = max_bytes

    def put(self, process_id: int, step: int, payload: bytes) -> bool:
        with self._lock:
            cur = self._data.get(process_id)
            if cur is not None and cur[0] >= step:
                return False
            projected = sum(
                len(b) for pid, (_, b) in self._data.items()
                if pid != process_id
            ) + len(payload)
            if projected > self._max_bytes:
                logger.warning(
                    "replica store full (%d bytes); dropping step %d",
                    projected, step,
                )
                return False
            self._data[process_id] = (step, payload)
            return True

    def get(
        self, process_id: int, min_step: int = -1
    ) -> Optional[Tuple[int, bytes]]:
        with self._lock:
            cur = self._data.get(process_id)
            if cur is None or cur[0] < min_step:
                return None
            return cur

    def stats(self) -> dict:
        with self._lock:
            return {
                pid: {"step": s, "bytes": len(b)}
                for pid, (s, b) in self._data.items()
            }


class ReplicaServicer:
    """RPC handler hosted by the agent (push/fetch)."""

    def __init__(self, store: ReplicaStore):
        self._store = store

    def __call__(self, msg: m.Message) -> Optional[m.Message]:
        if isinstance(msg, m.ReplicaPush):
            # Verify before accepting: a torn push stored here would
            # poison a replaced node's warm restore later, when the
            # original copy is long gone.
            reason = check_replica_payload(
                msg.payload, msg.process_id, msg.step
            )
            if reason is not None:
                integrity_counters.inc("ckpt_replica_rejected")
                logger.warning(
                    "replica push (proc %d step %d) rejected: %s",
                    msg.process_id, msg.step, reason,
                )
                return m.BaseResponse(success=False, reason=reason)
            ok = self._store.put(msg.process_id, msg.step, msg.payload)
            return m.BaseResponse(success=ok)
        if isinstance(msg, m.ReplicaFetch):
            got = self._store.get(msg.process_id, msg.min_step)
            if got is None:
                return m.ReplicaData(found=False)
            return m.ReplicaData(found=True, step=got[0], payload=got[1])
        return m.BaseResponse(
            success=False, reason=f"unknown message {type(msg).__name__}"
        )


class CkptReplicaManager:
    """Agent-side manager: serve replicas, push own shards, seed restores.

    ``master_client`` provides the KV rendezvous; ``node_rank``/``world``
    come from the current rendezvous round (call :meth:`update_world` after
    each round — ring neighbours change when membership does).
    """

    def __init__(
        self,
        master_client,
        node_rank: Optional[int] = None,
        world_size: int = 1,
        push_interval_s: float = 30.0,
    ):
        self.client = master_client
        # Registration waits for a real rank: registering a default rank
        # here would clobber another node's address in the KV store until
        # the next update_world round.
        self.node_rank = -1 if node_rank is None else node_rank
        self.world_size = world_size
        self.push_interval = push_interval_s
        self._last_push: Dict[int, float] = {}
        self.store = ReplicaStore()
        self._server = RpcServer(0, ReplicaServicer(self.store))
        self._server.start()
        self.addr = f"{local_ip()}:{self._server.port}"
        self._peers: Dict[int, RpcClient] = {}
        if node_rank is not None:
            self._register()

    # -- membership --------------------------------------------------------
    def _register(self) -> None:
        if self.node_rank < 0:
            return
        try:
            self.client.kv_store_set(
                f"{_KV_PREFIX}{self.node_rank}", self.addr.encode()
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("replica addr registration failed: %s", e)

    def update_world(self, node_rank: int, world_size: int) -> None:
        self.node_rank = node_rank
        self.world_size = world_size
        self._register()

    def _peer(self, rank: int) -> Optional[RpcClient]:
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

    @property
    def backup_rank(self) -> int:
        return (self.node_rank + 1) % self.world_size

    # -- push (after each staged save; reference backup :57) ---------------
    def backup_shard(
        self,
        process_id: int,
        step: int,
        tensors: Dict[str, np.ndarray],
        extra: dict,
        force: bool = False,
    ) -> bool:
        if self.world_size <= 1:
            return False
        now = time.monotonic()
        # no entry = never pushed: the monotonic clock starts near the
        # host's boot, so "0.0" would throttle the first push of a host
        # that has been up for less than the interval
        last = self._last_push.get(process_id)
        if not force and last is not None and (
                now - last < self.push_interval):
            return False
        peer = self._peer(self.backup_rank)
        if peer is None:
            return False
        payload = shard_file.pack_shard(tensors, extra)
        payload = _chaos_torn_push(payload, step, process_id)
        try:
            resp = peer.call(
                m.ReplicaPush(
                    owner_node=self.node_rank,
                    process_id=process_id,
                    step=step,
                    payload=payload,
                )
            )
            ok = bool(getattr(resp, "success", False))
        except Exception as e:  # noqa: BLE001
            logger.warning("replica push to rank %d failed: %s",
                           self.backup_rank, e)
            return False
        if not ok and getattr(resp, "reason", ""):
            logger.warning(
                "replica push (proc %d step %d) refused by node %d: %s",
                process_id, step, self.backup_rank, resp.reason,
            )
        if ok:
            self._last_push[process_id] = now
            logger.info(
                "replica: backed up proc %d step %d (%.1f MB) to node %d",
                process_id, step, len(payload) / (1 << 20), self.backup_rank,
            )
        return ok

    # -- restore seed (replaced node; reference gather on restart) ---------
    def fetch_replica(
        self, process_id: int, min_step: int = -1
    ) -> Optional[Tuple[int, Dict[str, np.ndarray], dict]]:
        if self.world_size <= 1:
            return None
        peer = self._peer(self.backup_rank)
        if peer is None:
            return None
        try:
            resp = peer.call(
                m.ReplicaFetch(process_id=process_id, min_step=min_step)
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("replica fetch failed: %s", e)
            return None
        if not isinstance(resp, m.ReplicaData) or not resp.found:
            return None
        # Verify on fetch too: the store's copy was verified on push, but
        # the fetch rides the same wire — a torn transfer here would seed
        # the local arena with garbage the warm restore then trusts.
        try:
            tensors, extra = shard_file.unpack_shard(resp.payload)
        except shard_file.ShardCorruptionError as e:
            integrity_counters.inc("ckpt_replica_rejected")
            logger.warning(
                "replica fetch for proc %d rejected (corrupt payload): %s",
                process_id, e,
            )
            return None
        reason = _layout_mismatch(extra, process_id, resp.step)
        if reason is not None:
            integrity_counters.inc("ckpt_replica_rejected")
            logger.warning(
                "replica fetch for proc %d rejected: %s", process_id, reason
            )
            return None
        logger.info(
            "replica: recovered proc %d step %d from node %d",
            process_id, resp.step, self.backup_rank,
        )
        return resp.step, tensors, extra

    def stop(self) -> None:
        self._server.stop()
        for cli in self._peers.values():
            cli.close()
