"""Agent-side async checkpoint saver daemon.

Parity with reference ``elastic_agent/torch/ckpt_saver.py``
(``AsyncCheckpointSaver :353``, ``_sync_shm_to_storage :536``,
``save_shm_to_storage :701``, ``commit_checkpoint :822``): runs inside the
*agent* process, so persistence survives worker crashes; consumes save
events from a SharedQueue, copies each local rank's shm arena to storage
under the fencing lock, votes with done files, and (on the leader node)
advances the tracker after the master's cross-node step barrier.

Breakpoint-save: when the agent is about to stop workers (failure or
membership change) it calls :meth:`save_shm_to_storage` to persist whatever
steps are staged but not yet persisted — the "checkpoint-at-breakpoint" that
makes kill-and-rejoin cheap.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional

from dlrover_tpu import chaos
from dlrover_tpu.agent.metrics import integrity_counters, perf_stats
from dlrover_tpu.checkpoint import shard_file, slicer
from dlrover_tpu.checkpoint.engine import (
    ckpt_lock_name,
    ckpt_queue_name,
    ckpt_stat_name,
)
from dlrover_tpu.common.global_context import get_context
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedQueue,
)
from dlrover_tpu.common.shm import SharedMemoryArena, arena_name
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.obs import journal, span


class AsyncCheckpointSaver:
    def __init__(
        self,
        job_name: str,
        nproc_per_node: int,
        *,
        master_client=None,
        storage=None,
    ):
        self.job_name = job_name
        self.nproc = nproc_per_node
        self.client = master_client
        self.storage = storage or PosixDiskStorage()
        self._ctx = get_context()
        # Server side of the worker-facing primitives.
        self._queue = SharedQueue(ckpt_queue_name(job_name), create=True)
        self._locks = [
            SharedLock(ckpt_lock_name(job_name, lr), create=True)
            for lr in range(nproc_per_node)
        ]
        self._stat = SharedDict(ckpt_stat_name(job_name), create=True)
        # In-process mutex per rank: the replica thread, the save-event
        # thread and breakpoint saves share one cached arena object, and
        # reopen() munmaps the mapping and closes the descriptor tensor
        # reads go through — concurrent reopen()/read_state() on the
        # same instance is a use-after-munmap.  Always taken
        # *inside* the cross-process fencing lock (never around it).
        # Pre-populated for every rank so lazy init can't race either.
        self._arenas: Dict[int, SharedMemoryArena] = {
            lr: SharedMemoryArena(arena_name(job_name, lr))
            for lr in range(nproc_per_node)
        }
        self._arena_mus: Dict[int, threading.Lock] = {
            lr: threading.Lock() for lr in range(nproc_per_node)
        }
        self._persisted: Dict[int, int] = {}  # local_rank -> step
        # Dirty-fence memory per local rank (incremental saves), keyed
        # by the (ckpt_dir, process_id, world) scope it was built for —
        # an elastic re-rendezvous that re-identifies the rank resets it
        # (the next save is then full, never wrong).
        self._dirty: Dict[int, slicer.DirtyTracker] = {}
        self._dirty_scope: Dict[int, tuple] = {}
        self._perf_cache: tuple = (0.0, {})  # (fetched_at, stat snapshot)
        # TTL-cache clock seam: tests age the cache by stepping a fake
        # clock instead of sleeping (or back-dating with the WRONG
        # clock family — the old wall-stamp aging never expired a
        # monotonic-compared cache).
        self._perf_clock: Callable[[], float] = time.monotonic
        self._last_event: Dict[int, dict] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._replica_thread: Optional[threading.Thread] = None
        self._pool = ThreadPoolExecutor(
            max_workers=max(2, self._ctx.ckpt_shard_io_workers),
            thread_name_prefix="ckpt-io",
        )
        # Cross-node in-memory replicas (reference replica.py; opt-in via
        # DLROVER_TPU_CKPT_REPLICA=1 — costs DCN bandwidth per save).
        self.replica = None
        if self._ctx.ckpt_replica and master_client is not None:
            try:
                from dlrover_tpu.checkpoint.replica import (
                    CkptReplicaManager,
                )

                self.replica = CkptReplicaManager(master_client)
            except Exception:  # noqa: BLE001
                logger.exception("replica manager unavailable")

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._event_loop, name="async-ckpt-saver", daemon=True
            )
            self._thread.start()
            logger.info(
                "async checkpoint saver up (job=%s nproc=%d)",
                self.job_name, self.nproc,
            )
        if self.replica is not None and self._replica_thread is None:
            # Memory-only saves never enqueue events; replicate by
            # watching the arenas' staged steps directly.
            self._replica_thread = threading.Thread(
                target=self._replica_loop, name="ckpt-replica", daemon=True
            )
            self._replica_thread.start()

    def _replica_loop(self) -> None:
        interval = max(5.0, self.replica.push_interval / 2)
        pushed: Dict[int, int] = {}
        while not self._stop.wait(interval):
            for lr in range(self.nproc):
                try:
                    arena = self._arena(lr)
                    with self._arena_mu(lr):
                        arena.reopen()
                        # Cheap metadata peek first: copying the full state
                        # every poll just to compare steps would hold the
                        # fencing lock for a multi-GB memcpy.
                        meta = arena.metadata()
                    if meta is None or int(
                        meta.get("extra", {}).get("step", -1)
                    ) <= pushed.get(lr, -1):
                        continue
                    lock = self._locks[lr] if lr < len(self._locks) else None
                    if lock is not None and not lock.acquire(timeout=5.0):
                        continue
                    try:
                        with self._arena_mu(lr):
                            read = arena.read_state(copy=True)
                    finally:
                        if lock is not None:
                            lock.release()
                    if read is None:
                        continue
                    tensors, extra = read
                    step = int(extra.get("step", -1))
                    if step <= pushed.get(lr, -1):
                        continue
                    pid = int(extra.get("process_id", lr))
                    if self.replica.backup_shard(pid, step, tensors, extra):
                        pushed[lr] = step
                except FileNotFoundError:
                    continue  # no staged state yet on this rank
                except Exception:  # noqa: BLE001
                    logger.exception("replica push for rank %d failed", lr)

    def update_world(self, node_rank: int, world_size: int) -> None:
        """Refresh replica ring neighbours after a rendezvous round."""
        if self.replica is not None:
            self.replica.update_world(node_rank, world_size)

    def seed_from_replicas(
        self, process_ids: Dict[int, int], num_processes: int
    ) -> int:
        """Seed empty/stale local arenas from peer replicas before workers
        start (reference FullCkptReplicaManager gather-on-restart).

        ``process_ids``: local_rank -> global process_id for the coming
        round.  Returns how many arenas were seeded."""
        if self.replica is None:
            return 0
        seeded = 0
        for lr, pid in process_ids.items():
            arena = self._arena(lr)
            cur_step = -1
            try:
                with self._arena_mu(lr):
                    arena.reopen()
                    meta = arena.metadata()
                if meta is not None:
                    cur_step = int(meta.get("extra", {}).get("step", -1))
            except Exception as e:  # noqa: BLE001
                # No local arena yet is normal on a fresh node; the
                # fetch below then pulls the full replica (min_step=0).
                logger.debug(
                    "replica restore: arena peek failed for rank %d: "
                    "%s", lr, e,
                )
            got = self.replica.fetch_replica(pid, min_step=cur_step + 1)
            if got is None:
                continue
            step, tensors, extra = got
            if extra.get("num_processes") != num_processes:
                continue  # world changed: resharding goes through storage
            lock = self._locks[lr] if lr < len(self._locks) else None
            if lock is not None and not lock.acquire(timeout=30.0):
                continue
            try:
                with self._arena_mu(lr):
                    arena.write_state(tensors, extra=extra)
                seeded += 1
                logger.info(
                    "replica: seeded local arena %d with step %d", lr, step
                )
            finally:
                if lock is not None:
                    lock.release()
        return seeded

    def stop(self) -> None:
        self._stop.set()
        if self.replica is not None:
            self.replica.stop()
        self._pool.shutdown(wait=False)
        self._queue.close()
        for lock in self._locks:
            lock.close()
        self._stat.close()
        for arena in self._arenas.values():
            arena.close()

    def _arena(self, local_rank: int) -> SharedMemoryArena:
        return self._arenas[local_rank]

    def _arena_mu(self, local_rank: int) -> threading.Lock:
        return self._arena_mus[local_rank]

    # -- event loop (reference _sync_shm_to_storage :536) -------------------
    def _event_loop(self) -> None:
        while not self._stop.is_set():
            try:
                event = self._queue.get(timeout=2.0)
            except TimeoutError:
                continue
            except Exception:  # noqa: BLE001
                if not self._stop.is_set():
                    logger.exception("ckpt saver queue error")
                    time.sleep(1.0)
                continue
            if not isinstance(event, dict) or event.get("event") != "save":
                continue
            self._last_event[event.get("local_rank", 0)] = event
            try:
                self._handle_save(event)
            except Exception:  # noqa: BLE001
                logger.exception("ckpt save event failed: %s", event)

    def _handle_save(self, event: dict) -> None:
        with span("ckpt.persist", "ckpt", step=int(event.get("step", 0)),
                  rank=int(event.get("local_rank", 0)),
                  reason="breakpoint" if event.get("breakpoint")
                  else "save") as sp:
            self._persist_event(event, sp)

    def _persist_event(self, event: dict, persist_span) -> None:
        lr = int(event.get("local_rank", 0))
        step = int(event.get("step", 0))
        pid = int(event.get("process_id", lr))
        nproc_global = int(event.get("num_processes", self.nproc))
        ckpt_dir = event["ckpt_dir"]
        keep_last = shard_file.resolve_keep_last(event.get("max_to_keep"))
        lock = self._locks[lr] if lr < len(self._locks) else None
        with span("ckpt.persist.lock_wait", "ckpt"):
            locked = lock is None or lock.acquire(timeout=60.0)
        if not locked:
            logger.warning("saver: lock for rank %d busy; skipping", lr)
            return
        # Fast path: stream the arena's bytes to storage — read() chunk
        # by chunk into one reused buffer, CRC'd and written from there —
        # holding the fencing lock + arena mutex for the whole persist
        # (the handles' lifetime contract — see
        # SharedMemoryArena.read_state).  A worker staging its next step
        # waits on the lock for the persist duration, exactly like the
        # reference saver; the bench measures that stall.  Copy mode —
        # one full state copy under the lock, persist from the copy with
        # the lock released (the old bounded stall) — is kept for every
        # consumer that outlives the lock: the replica-ring push, and
        # operators on slow storage who set ckpt_zero_copy=False.
        copy_mode = self.replica is not None or not self._ctx.ckpt_zero_copy
        tensors = extra = None
        stats = None
        try:
            arena = self._arena(lr)
            with self._arena_mu(lr):
                arena.reopen()
                read = arena.read_state(copy=copy_mode)
                if read is None:
                    logger.warning("saver: arena for rank %d empty", lr)
                    return
                tensors, extra = read
                staged_step = int(extra.get("step", -1))
                if staged_step != step:
                    logger.info(
                        "saver: arena holds step %d (event wanted %d) — "
                        "persisting the staged one", staged_step, step,
                    )
                    step = staged_step
                    persist_span.set(step=step)
                if (
                    event.get("breakpoint")
                    and self._persisted.get(lr, -1) >= step
                ):
                    # The event loop persisted this step while the
                    # breakpoint save waited for the fencing lock.
                    return
                # The arena's CRC covers the meta blob only; validate the
                # staged state's own layout metadata before it becomes a
                # durable shard — a torn/mismatched stage must never be
                # persisted (and later trusted) under this event's
                # identity.
                reason = shard_file.validate_staged_state(
                    tensors, extra,
                    expect_process_id=pid,
                    expect_num_processes=nproc_global,
                )
                if reason is not None:
                    integrity_counters.inc("ckpt_staged_rejected")
                    logger.error(
                        "saver: rank %d staged state rejected, NOT "
                        "persisted (%s)", lr, reason,
                    )
                    return
                if not copy_mode:
                    stats = self._persist(
                        ckpt_dir, step, pid, tensors, extra, lr=lr,
                        sliced=not event.get("breakpoint"),
                        world=nproc_global,
                    )
        finally:
            if lock is not None:
                lock.release()
        if copy_mode:
            # Stable copies: persist outside the locks, then push.
            stats = self._persist(
                ckpt_dir, step, pid, tensors, extra, lr=lr,
                sliced=not event.get("breakpoint"), world=nproc_global,
            )
            if self.replica is not None:
                self._pool.submit(
                    self.replica.backup_shard, pid, step, tensors, extra
                )
        self._report_persist_perf(step, stats["mbps"])
        self._persisted[lr] = step
        # One round trip for the whole rank row: the persisted-step ack
        # plus the per-rank gauges the agg scrape sums.
        self._stat.update(
            {
                f"persisted_{lr}": step,
                f"persist_mbps_{lr}": round(stats["mbps"], 1),
                f"tensors_skipped_{lr}": stats.get("skipped", 0),
            }
        )
        logger.info(
            "saver: persisted rank %d step %d in %.2fs (%.0f MB/s, "
            "%d tensors ref'd unchanged)",
            lr, step, stats["seconds"], stats["mbps"],
            stats.get("skipped", 0),
        )
        if pid == 0:
            # Commit waits for the OTHER ranks' shards — never block the
            # event loop on it (they may be persisted by this same loop).
            self._pool.submit(
                self._commit, ckpt_dir, step, nproc_global, keep_last,
                parent_span=persist_span.sid,
            )

    def _tracker(
        self, lr: int, ckpt_dir: str, pid: int, world: int
    ) -> slicer.DirtyTracker:
        scope = (ckpt_dir, pid, world)
        if self._dirty_scope.get(lr) != scope:
            self._dirty[lr] = slicer.DirtyTracker()
            self._dirty_scope[lr] = scope
        return self._dirty[lr]

    def _persist(
        self, ckpt_dir: str, step: int, pid: int, tensors, extra,
        *, lr: int = 0, sliced: bool = True, world: Optional[int] = None,
    ) -> dict:
        """One streamed shard write + throughput stats/gauges.

        The rank writes only its disjoint slice of replicated tensors
        (``sliced=False`` on breakpoint saves: a dying partial world must
        leave restorable FULL shards, not orphan slices) and refs
        tensors whose dirty fence has not tripped since their holder
        step."""
        with span("ckpt.persist.write", "ckpt", host=True, step=step) as sp:
            stats = self._write_shard(
                ckpt_dir, step, pid, tensors, extra, lr, sliced, world)
            sp.set(bytes=int(stats["total_bytes"]),
                   read_bytes=int(stats["read_bytes"]),
                   mbps=round(stats["mbps"], 1),
                   skipped=int(stats["skipped"]))
        return stats

    def _write_shard(self, ckpt_dir, step, pid, tensors, extra, lr,
                     sliced, world) -> dict:
        t0 = time.perf_counter()
        chaos.inject("ckpt.slow_storage", step=step, rank=pid)
        world = int(world or extra.get("num_processes") or self.nproc)
        plan = slicer.plan_persist(
            tensors, extra,
            process_id=pid, num_processes=world,
            sliced=sliced and self._ctx.ckpt_sliced_persist,
            tracker=(
                self._tracker(lr, ckpt_dir, pid, world)
                if self._ctx.ckpt_incremental else None
            ),
            holder_exists=lambda s: self.storage.exists(
                shard_file.shard_path(ckpt_dir, s, pid)
            ),
        )
        stats = shard_file.write_shard_from_views(
            self.storage, ckpt_dir, step, pid, plan.tensors, plan.extra,
            workers=self._ctx.ckpt_persist_workers,
            meta_extra=plan.meta_extra,
        )
        self._tracker(lr, ckpt_dir, pid, world).note_plan(
            plan, step, stats.get("crcs", {})
        )
        stats["seconds"] = max(1e-9, time.perf_counter() - t0)
        stats["mbps"] = stats["total_bytes"] / stats["seconds"] / (1 << 20)
        stats["skipped"] = plan.skipped
        perf_stats.set("ckpt_persist_mbps", stats["mbps"])
        return stats

    def _report_persist_perf(self, step: int, mbps: float) -> None:
        """Throughput-only CkptPerf to the master (stall_ms=0 touches no
        stall bookkeeping) including the node's AGGREGATE persist rate
        and skipped-tensor count for the goodput/diagnosis log.  Called
        AFTER the fencing lock/arena mutex are released — a slow master
        must never stretch the lock hold the trainer's next save waits
        on.  Best-effort, short budget."""
        if self.client is None:
            return
        try:
            self.client.report_ckpt_perf(
                step=step, stall_ms=0.0, persist_mbps=mbps,
                agg_persist_mbps=self.agg_persist_mbps(),
                tensors_skipped=self.tensors_skipped_total(),
            )
        except Exception as e:  # noqa: BLE001
            logger.debug("persist perf report failed: %s", e)

    def agg_persist_mbps(self) -> float:
        """Sum of every local rank's last persist throughput — the
        node-level aggregate bandwidth the sliced persist exists to
        scale; rides the same one-round-trip stat snapshot as the other
        gauges."""
        snap = self.worker_perf()
        return sum(
            float(v) for k, v in snap.items()
            if k.startswith("persist_mbps_") and v is not None
        )

    def tensors_skipped_total(self) -> int:
        """Sum of every local rank's last dirty-fence skip count (the
        ``ckpt_tensors_skipped`` gauge)."""
        snap = self.worker_perf()
        return int(sum(
            int(v) for k, v in snap.items()
            if k.startswith("tensors_skipped_") and v is not None
        ))

    def worker_perf(self) -> Dict[str, float]:
        """One snapshot of the workers' reported perf stats — a single
        short-budget round trip, because this runs inside a Prometheus
        scrape handler (per-rank gets would cost nproc x timeout against
        a sick stat server and black out the whole endpoint).  A 1s TTL
        cache collapses the multiple gauges sampled by one scrape into
        ONE round trip (and one bounded wait against a hung server)."""
        ts, snap = self._perf_cache
        if self._perf_clock() - ts < 1.0:
            return snap
        try:
            snap = self._stat.to_dict(timeout=2.0) or {}
        except Exception as e:  # noqa: BLE001
            logger.debug("perf stat snapshot failed: %s", e)
            snap = {}
        self._perf_cache = (self._perf_clock(), snap)
        return snap

    def last_stall_ms(self) -> float:
        """Worst save_to_memory blocking time across local ranks, as the
        engines report it into the shared stat dict — the agent-side
        gauge behind ``ckpt_stall_ms_last``."""
        snap = self.worker_perf()
        return max(
            (float(v) for k, v in snap.items()
             if k.startswith("stall_ms_") and v is not None),
            default=0.0,
        )

    def staged_mbps(self) -> float:
        """Slowest rank's worker->shm staging throughput (the staging
        bottleneck) — the gauge behind ``ckpt_staged_mbps``."""
        snap = self.worker_perf()
        return min(
            (float(v) for k, v in snap.items()
             if k.startswith("staged_mbps_") and v is not None),
            default=0.0,
        )

    def _commit(self, ckpt_dir: str, step: int, world: int,
                keep_last: int = 3, timeout: float = 600.0,
                parent_span: str = "") -> None:
        # on a pool thread: the persist that asked for it is the parent
        with span("ckpt.persist.commit", "ckpt", parent=parent_span,
                  step=step):
            self._commit_when_ready(
                ckpt_dir, step, world, keep_last, timeout)

    def _commit_when_ready(self, ckpt_dir: str, step: int, world: int,
                           keep_last: int, timeout: float) -> None:
        deadline = time.time() + timeout
        if not shard_file.wait_sync_barrier(
            self.client, step, min(60.0, timeout / 4), self._stop
        ) and not self._stop.is_set():
            logger.warning(
                "saver: step-%d sync barrier did not open; "
                "committing on done files alone", step,
            )
        while time.time() < deadline:
            if shard_file.all_shards_done(self.storage, ckpt_dir, step, world):
                # Votes in hand, writes finished: an unprovable slice
                # cover is terminal for this step (the previous
                # committed step stays the restore point).
                if self._ctx.ckpt_commit_coverage and not slicer.commit_gate(
                    self.storage, ckpt_dir, step
                ):
                    journal("ckpt.commit", step=step, ok=False,
                            verdict="coverage_blocked")
                    return
                shard_file.commit(
                    self.storage, ckpt_dir, step, keep_last=keep_last
                )
                journal("ckpt.commit", step=step, ok=True,
                        verdict="coverage_proven"
                        if self._ctx.ckpt_commit_coverage
                        else "ungated")
                return
            if self._stop.is_set():
                # Saver shutdown while shards are still missing: these
                # pool threads are non-daemon and would otherwise pin the
                # dying agent process for the rest of the timeout.  (A
                # ready commit is still taken — the check above runs
                # first.)
                logger.info("saver: commit of step %d aborted (stop)", step)
                return
            time.sleep(0.5)
        logger.warning("saver: commit of step %d timed out", step)

    # -- breakpoint save (reference save_shm_to_storage :701) ---------------
    def save_shm_to_storage(self, reason: str = "") -> None:
        """Persist every staged-but-unpersisted arena now (called by the
        agent right before stopping workers)."""
        for lr in range(self.nproc):
            try:
                arena = self._arena(lr)
                # Take the fencing lock so an in-flight worker write
                # finishes first — an unlocked peek mid-write reads the
                # dirty flag and would silently skip this rank's state.
                lock = self._locks[lr] if lr < len(self._locks) else None
                if lock is not None and not lock.acquire(timeout=60.0):
                    logger.warning(
                        "breakpoint save: rank %d lock busy; skipping", lr
                    )
                    continue
                try:
                    with self._arena_mu(lr):
                        arena.reopen()
                        meta = arena.metadata()
                finally:
                    if lock is not None:
                        lock.release()
            except Exception as e:  # noqa: BLE001
                # Skipping a rank's state here silently loses it on the
                # next hard kill — this must be loud.
                logger.warning(
                    "breakpoint save: arena peek failed for rank %d "
                    "(state NOT persisted): %s", lr, e,
                )
                continue
            if meta is None:
                continue
            extra = meta.get("extra", {})
            step = int(extra.get("step", -1))
            ckpt_dir = extra.get("ckpt_dir", "")
            if step < 0 or not ckpt_dir:
                continue
            if self._persisted.get(lr, -1) >= step:
                continue
            logger.info(
                "breakpoint save (%s): persisting rank %d step %d",
                reason, lr, step,
            )
            self._handle_save(
                {
                    "event": "save",
                    "step": step,
                    "local_rank": lr,
                    "process_id": extra.get("process_id", lr),
                    "num_processes": extra.get("num_processes", self.nproc),
                    "ckpt_dir": ckpt_dir,
                    # A breakpoint save may be the last write a dying
                    # world ever makes: write FULL shards — orphan slices
                    # from a partial world would be unrestorable, where a
                    # full replicated shard from any one rank is.
                    "breakpoint": True,
                }
            )
