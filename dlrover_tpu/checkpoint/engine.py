"""Worker-side checkpoint engine: pytree -> shm arena -> (async) storage.

Parity with reference ``trainer/torch/flash_checkpoint/engine.py:136``
(``save_to_memory :417``, ``save_to_storage :435``, ``load :454``) +
``full_ckpt_engine.py``, TPU-native: the state is a sharded JAX pytree; each
process stages only its **addressable shards** (no gather, no host blowup),
with ``copy_to_host_async`` overlapping D2H against the step.

Two runtime modes, auto-detected:

- **agent mode** (production): the per-node agent runs an
  ``AsyncCheckpointSaver`` hosting the event queue / fencing locks; persisting
  shm -> storage happens in the *agent process*, so a crashing worker loses
  nothing (breakpoint-save, reference ``save_shm_to_storage :701``).
- **standalone mode** (no agent): a daemon thread in the worker persists; the
  shm arena still survives worker death, so warm restart works either way.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax

from dlrover_tpu import chaos
from dlrover_tpu.agent.metrics import integrity_counters, perf_stats
from dlrover_tpu.checkpoint import shard_file, slicer, tree_utils
from dlrover_tpu.common import env as env_utils
from dlrover_tpu.diagnosis.data import DiagnosisDataType
from dlrover_tpu.common.global_context import get_context
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedQueue,
    socket_path,
)
from dlrover_tpu.common.shm import SharedMemoryArena, arena_name
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage
from dlrover_tpu.obs import journal, span


@contextlib.contextmanager
def _released_after(lock):
    """Release an already-held lock on the way out."""
    try:
        yield
    finally:
        lock.release()


def ckpt_queue_name(job_name: str) -> str:
    return env_utils.run_scoped(f"{job_name}-ckptq")


def ckpt_lock_name(job_name: str, local_rank: int) -> str:
    return env_utils.run_scoped(f"{job_name}-ckptlock-{local_rank}")


def ckpt_stat_name(job_name: str) -> str:
    return env_utils.run_scoped(f"{job_name}-ckptstat")


def _walk_args(fetched: list, t0: float) -> dict:
    """Where ``flatten_to_shards`` waited, from its ``(bytes, start, end)``
    a device shard (the ``args`` of ``ckpt.save.d2h.fetch``): ``leaves``,
    ``asarray_s`` (its seconds inside ``np.asarray``, all shards),
    ``largest`` (the three largest shards, ``[bytes, seconds]``) and
    ``first_leaf_s`` (from ``t0`` until the first one's host array
    exists)."""
    args = {
        "leaves": len(fetched),
        "asarray_s": round(sum(end - start for _, start, end in fetched), 6),
        "largest": [[n, round(end - start, 6)] for n, start, end
                    in sorted(fetched, reverse=True)[:3]],
    }
    if fetched:
        args["first_leaf_s"] = round(fetched[0][2] - t0, 6)
    return args


class CheckpointEngine:
    def __init__(
        self,
        ckpt_dir: str,
        *,
        job_name: str = "",
        storage: Optional[CheckpointStorage] = None,
        master_client=None,
        # None = default rotation (keep 3); 0 = keep ALL step dirs;
        # N > 0 = keep the newest N.
        max_to_keep: Optional[int] = None,
    ):
        self.ckpt_dir = ckpt_dir
        self.job_name = job_name or env_utils.get_job_name()
        self.storage = storage or PosixDiskStorage()
        self.max_to_keep = max_to_keep
        self.client = master_client
        self._ctx = get_context()
        self.process_id = env_utils.get_process_id()
        self.num_processes = env_utils.get_num_processes()
        self.local_rank = int(os.environ.get("DLROVER_TPU_LOCAL_RANK", 0))
        self._arena = SharedMemoryArena(
            arena_name(self.job_name, self.local_rank)
        )
        # In-process arena fence: the standalone persist thread streams
        # from the arena's mapped bytes while the trainer may be staging
        # the next step into it — same contract the agent saver gets from
        # its arena mutex.  Taken INSIDE the cross-process fencing lock.
        self._arena_mu = threading.Lock()
        self._last_saved_step = -1
        self._last_persist_step = -1
        # Train-stall accounting: how long save_to_memory/_storage blocked
        # the step loop (the paper's headline "second-scale stall").
        self.last_stall_ms = 0.0
        self._last_staged_bytes = 0
        self._first_touch = False
        self._load_span = None  # the ckpt.load span while load() runs
        self._stat_client: Optional[SharedDict] = None
        # step -> "a corrupt shard was seen while reading this step's
        # candidates" (populated per load; drives quarantine decisions).
        self._step_had_corruption: Dict[int, bool] = {}
        # {path: [box, ...]} of the current load()'s target — drives the
        # reshard-plan shard selection on the storage path; None when
        # loading without a target (ShardSource mode reads everything).
        self._restore_boxes = None
        # (step, pid) -> ShardManifest fetched during shard selection and
        # REUSED by the data read (one header+meta pass per shard per
        # load, not two); reset per load().
        self._man_cache: Dict[Tuple[int, int], Any] = {}
        # Dirty-fence memory: which step physically holds each tensor's
        # last-persisted slice bytes (incremental saves).  Lost on
        # restart — the next save is then full, never wrong.
        self._dirty = slicer.DirtyTracker()

        self.agent_mode = os.path.exists(
            socket_path("queue", ckpt_queue_name(self.job_name))
        )
        self._queue: Optional[SharedQueue] = None
        self._lock: Optional[SharedLock] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._futures: list = []
        if self.agent_mode:
            self._queue = SharedQueue(ckpt_queue_name(self.job_name))
            self._lock = SharedLock(
                ckpt_lock_name(self.job_name, self.local_rank)
            )
            logger.info("checkpoint engine in agent mode")
        else:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ckpt-persist"
            )

    # -- save ---------------------------------------------------------------
    def _stage(self, step: int, state: Any, meta: Optional[dict]) -> Tuple[
        Dict[str, np.ndarray], dict
    ]:
        # Overlap all D2H copies before the synchronous flatten walk.
        def _prefetch(x):
            if isinstance(x, jax.Array):
                try:
                    x.copy_to_host_async()
                # graftcheck: disable=CC104 -- prefetch is a pure
                # optimization; the flatten walk below copies
                # synchronously either way
                except Exception:  # noqa: BLE001
                    pass
            return None

        with span("ckpt.save.d2h", "ckpt", host=True) as sp:
            with span("ckpt.save.d2h.issue", "ckpt"):
                jax.tree_util.tree_map(_prefetch, state)
            with span("ckpt.save.d2h.fetch", "ckpt") as fetch:
                t0 = time.monotonic()
                fetched: list = []
                tensors, info = tree_utils.flatten_to_shards(state, fetched)
                fetch.set(**_walk_args(fetched, t0))
            self._last_staged_bytes = sum(
                int(np.asarray(a).nbytes) for a in tensors.values()
            )
            sp.set(bytes=self._last_staged_bytes, tensors=len(tensors))
        extra = {
            "step": step,
            "meta": meta or {},
            "tensors_info": info,
            "process_id": self.process_id,
            "num_processes": self.num_processes,
            "ckpt_dir": self.ckpt_dir,
            "time": time.time(),
            # Every rank's leaf paths (identical pytree): lets the commit
            # coverage proof notice a dead rank's EXCLUSIVE tensors are
            # absent, not just torn slices of shared ones.
            "tree_paths": sorted({m["path"] for m in info.values()}),
        }
        with span("ckpt.save.lock_wait", "ckpt"):
            self._fence_arena()
        try:
            self._first_touch = self._arena.will_allocate(tensors)
            with span("ckpt.save.arena_write", "ckpt", host=True,
                      bytes=self._last_staged_bytes,
                      first_touch=self._first_touch):
                self._arena.write_state(tensors, extra=extra)
        finally:
            self._unfence_arena()
        self._last_saved_step = step
        return tensors, extra

    def _fence_arena(self) -> None:
        """Take the rank's cross-process fencing lock (agent mode) and,
        inside it, the in-process arena mutex: whoever writes the arena,
        reads its tensors or remaps it holds both for as long as that
        lasts.  A streamed persist (agent saver on the fencing lock, or
        the standalone persist thread on the arena mutex) legitimately
        holds its lock for a WHOLE streamed storage write, which can
        exceed a minute on slow storage — waiting is correct; crashing
        the trainer (or hanging it silently) is not.  A holder that dies
        is covered by ``SharedLock``'s dead-holder steal."""
        if self._lock is not None:
            self._acquire_patiently(self._lock.acquire, "shm fencing lock")
        try:
            self._acquire_patiently(self._arena_mu.acquire, "arena mutex")
        except BaseException:
            if self._lock is not None:
                self._lock.release()
            raise

    def _unfence_arena(self) -> None:
        self._arena_mu.release()
        if self._lock is not None:
            self._lock.release()

    @staticmethod
    def _acquire_patiently(
        acquire, what: str, budget: float = 600.0
    ) -> None:
        """Bounded lock wait: warn each minute, raise only after the
        persist path's own 600s budget — one home for the deadline
        arithmetic both arena locks share."""
        deadline = time.time() + budget
        while not acquire(timeout=60.0):
            if time.time() >= deadline:
                raise TimeoutError(f"could not acquire {what}")
            logger.warning(
                "%s still held (persist in flight?); waiting", what
            )

    def save_to_memory(
        self, step: int, state: Any, meta: Optional[dict] = None
    ) -> None:
        """Stage into shm only — the synchronous train stall; the state
        survives worker crash/restart on this host."""
        with span("ckpt.save", "ckpt", step=step,
                  rank=self.process_id) as sp:
            t0 = time.perf_counter()
            self._stage(step, state, meta)
            self._note_stall(step, time.perf_counter() - t0, sp)

    def _note_stall(self, step: int, seconds: float, save_span) -> None:
        """Surface the measured train stall: the ``ckpt.save`` span's
        args, the local gauge, the agent's shared stat dict (scraped as
        ``ckpt_stall_ms_last``), and the master's goodput accounting —
        the stall is real lost train time even though no restart
        happened."""
        self.last_stall_ms = seconds * 1000.0
        staged_mbps = (
            self._last_staged_bytes / max(seconds, 1e-9) / (1 << 20)
        )
        perf_stats.set("ckpt_stall_ms_last", self.last_stall_ms)
        perf_stats.set("ckpt_staged_mbps", staged_mbps)
        save_span.set(stall_ms=round(self.last_stall_ms, 1),
                      mbps=round(staged_mbps, 1),
                      bytes=self._last_staged_bytes,
                      first_touch=self._first_touch)
        logger.info(
            "flash ckpt: staged step %d to shm in %.3fs (%.0f MB/s, "
            "train stalled %.1fms)",
            step, seconds, staged_mbps, self.last_stall_ms,
        )
        with span("ckpt.save.report", "ckpt"):
            self._report_stall(step, staged_mbps)

    def _report_stall(self, step: int, staged_mbps: float) -> None:
        if self.agent_mode:
            try:
                # One round trip for both stats, short timeout: this sits
                # inside the save path whose whole point is a tens-of-ms
                # stall — a dead stat server (agent restarting) must cost
                # ~2s once, not the 60s default retry budget per save.
                self._stat().update(
                    {
                        f"stall_ms_{self.local_rank}": round(
                            self.last_stall_ms, 3
                        ),
                        f"staged_mbps_{self.local_rank}": round(
                            staged_mbps, 1
                        ),
                    },
                    timeout=2.0,
                )
            except Exception as e:  # noqa: BLE001
                logger.debug("stall stat report failed: %s", e)
        if self.client is not None:
            try:
                self.client.report_ckpt_perf(
                    step=step,
                    stall_ms=self.last_stall_ms,
                    staged_mbps=staged_mbps,
                )
            except Exception as e:  # noqa: BLE001
                logger.debug("ckpt perf report failed: %s", e)

    def _stat(self) -> SharedDict:
        """Cached client connection to the agent saver's stat dict."""
        if self._stat_client is None:
            self._stat_client = SharedDict(ckpt_stat_name(self.job_name))
        return self._stat_client

    def save_to_storage(
        self, step: int, state: Any, meta: Optional[dict] = None
    ) -> None:
        """Stage into shm + request async persistence."""
        with span("ckpt.save", "ckpt", step=step, rank=self.process_id,
                  storage=True) as sp:
            t0 = time.perf_counter()
            self._stage(step, state, meta)
            self._note_stall(step, time.perf_counter() - t0, sp)
        if self.agent_mode:
            self._queue.put(
                {
                    "event": "save",
                    "step": step,
                    "local_rank": self.local_rank,
                    "process_id": self.process_id,
                    "num_processes": self.num_processes,
                    "ckpt_dir": self.ckpt_dir,
                    "max_to_keep": self.max_to_keep,
                }
            )
        else:
            fut = self._pool.submit(self._persist, step)
            self._futures.append((step, fut))

    def _persist(self, step: int) -> None:
        """Standalone async persist: stream the shm arena's staged bytes.

        NOT the host arrays from ``flatten_to_shards`` — on the CPU
        backend those can be zero-copy aliases of live (donated) jax
        buffers, and an async stream from them races the next train step
        into a torn shard whose CRC (computed in the same pass over the
        same torn bytes) would still validate.  The arena holds a stable
        staged copy, which the writer ``read()``s chunk by chunk;
        ``_arena_mu`` fences it against concurrent re-staging for the
        duration of the stream (the ``ckpt_zero_copy=False`` knob trades
        that hold for one copy, exactly like the agent saver)."""
        with span("ckpt.persist", "ckpt", step=step, reason="save",
                  rank=self.process_id):
            self._persist_staged(step)

    def _persist_staged(self, step: int) -> None:
        try:
            zero_copy = self._ctx.ckpt_zero_copy
            with span("ckpt.persist.lock_wait", "ckpt"):
                self._arena_mu.acquire()
            with _released_after(self._arena_mu):
                read = self._arena.read_state(copy=not zero_copy)
                if read is None:
                    logger.error(
                        "NOT persisting step %d: arena holds no state",
                        step,
                    )
                    return
                tensors, extra = read
                staged_step = int(extra.get("step", -1))
                if staged_step != step:
                    logger.info(
                        "persist: arena holds step %d (wanted %d) — "
                        "persisting the staged one", staged_step, step,
                    )
                    step = staged_step
                reason = shard_file.validate_staged_state(
                    tensors, extra,
                    expect_process_id=self.process_id,
                    expect_num_processes=self.num_processes,
                )
                if reason is not None:
                    integrity_counters.inc("ckpt_staged_rejected")
                    logger.error(
                        "NOT persisting step %d: staged state invalid "
                        "(%s)", step, reason,
                    )
                    return
                if zero_copy:
                    self._stream_shard(step, tensors, extra)
            if not zero_copy:
                self._stream_shard(step, tensors, extra)
            self._last_persist_step = step
            if self.process_id == 0:
                with span("ckpt.persist.commit", "ckpt", step=step) as sp:
                    sp.set(ok=self._commit_when_ready(step))
        except Exception:  # noqa: BLE001
            logger.exception("checkpoint persist of step %d failed", step)

    def _stream_shard(self, step: int, tensors, extra) -> None:
        """Sliced + incremental streamed persist: this rank writes only
        its disjoint slice of replicated tensors (aggregate fleet write
        bandwidth scales with world size) and skips tensors whose dirty
        fence has not tripped since their holder step (a meta ref
        instead of a rewrite)."""
        with span("ckpt.persist.write", "ckpt", host=True, step=step) as sp:
            self._write_shard(step, tensors, extra, sp)

    def _write_shard(self, step: int, tensors, extra, write_span) -> None:
        chaos.inject("ckpt.slow_storage", step=step, rank=self.process_id)
        t0 = time.perf_counter()
        plan = slicer.plan_persist(
            tensors, extra,
            process_id=self.process_id,
            num_processes=self.num_processes,
            sliced=self._ctx.ckpt_sliced_persist,
            tracker=self._dirty if self._ctx.ckpt_incremental else None,
            holder_exists=lambda s: self.storage.exists(
                shard_file.shard_path(self.ckpt_dir, s, self.process_id)
            ),
        )
        stats = shard_file.write_shard_from_views(
            self.storage, self.ckpt_dir, step, self.process_id,
            plan.tensors, plan.extra,
            workers=self._ctx.ckpt_persist_workers,
            meta_extra=plan.meta_extra,
        )
        self._dirty.note_plan(plan, step, stats.get("crcs", {}))
        mbps = (
            stats["total_bytes"]
            / max(time.perf_counter() - t0, 1e-9) / (1 << 20)
        )
        write_span.set(rank=self.process_id, mbps=round(mbps, 1),
                       bytes=int(stats["total_bytes"]),
                       read_bytes=int(stats["read_bytes"]),
                       skipped=int(plan.skipped))
        perf_stats.set("ckpt_persist_mbps", mbps)
        # Standalone = one rank per process: its own persist rate IS its
        # contribution to the fleet aggregate the bench/master sum up.
        perf_stats.set("ckpt_agg_persist_mbps", mbps)
        perf_stats.set("ckpt_tensors_skipped", float(plan.skipped))
        if plan.skipped:
            logger.info(
                "flash ckpt: step %d incremental — %d/%d tensors "
                "unchanged (refs), %d of %d staged bytes written",
                step, plan.skipped, len(plan.tensors),
                plan.written_bytes, plan.logical_bytes,
            )
        if self.client is not None:
            try:
                self.client.report_ckpt_perf(
                    step=step, stall_ms=0.0, persist_mbps=mbps,
                    agg_persist_mbps=mbps,
                    tensors_skipped=plan.skipped,
                )
            except Exception as e:  # noqa: BLE001
                logger.debug("persist perf report failed: %s", e)

    def _commit_when_ready(self, step: int, timeout: float = 600.0) -> bool:
        """Leader: wait for every process's done file (optionally gated by
        the master's cross-node step barrier), prove the slice set covers
        every tensor, then advance the tracker."""
        deadline = time.time() + timeout
        shard_file.wait_sync_barrier(
            self.client, step, min(60.0, timeout / 4)
        )
        while time.time() < deadline:
            if shard_file.all_shards_done(
                self.storage, self.ckpt_dir, step, self.num_processes
            ):
                # Done votes in hand, every write is finished: a failed
                # coverage proof is terminal for this step (the previous
                # committed step stays the restore point).
                if self._ctx.ckpt_commit_coverage and not slicer.commit_gate(
                    self.storage, self.ckpt_dir, step
                ):
                    journal("ckpt.commit", step=step, ok=False,
                            verdict="coverage_blocked")
                    return False
                shard_file.commit(
                    self.storage, self.ckpt_dir, step,
                    keep_last=shard_file.resolve_keep_last(
                        self.max_to_keep
                    ),
                )
                journal("ckpt.commit", step=step, ok=True,
                        verdict="coverage_proven"
                        if self._ctx.ckpt_commit_coverage
                        else "ungated")
                return True
            time.sleep(0.5)
        logger.warning("commit of step %d timed out", step)
        journal("ckpt.commit", step=step, ok=False, verdict="timeout")
        return False

    def wait(self, timeout: float = 600.0) -> bool:
        """Block until the last storage save is fully committed."""
        for step, fut in self._futures:
            try:
                fut.result(timeout=timeout)
            except Exception:  # noqa: BLE001
                logger.exception("pending persist failed")
        self._futures = []
        if self._last_saved_step < 0:
            return True
        deadline = time.time() + timeout
        while time.time() < deadline:
            committed = shard_file.latest_step(self.storage, self.ckpt_dir)
            if committed is not None and committed >= self._last_persist_step:
                return True
            if self.agent_mode:
                stat = self._stat()
                try:
                    done = stat.get(f"persisted_{self.local_rank}", -1)
                    if done is not None and int(done) >= self._last_saved_step:
                        return True
                # graftcheck: disable=CC104 -- poll loop by design: the
                # stat read races the agent writer and simply retries
                # 0.5s later until the wait deadline
                except Exception:  # noqa: BLE001
                    pass
            time.sleep(0.5)
        return False

    # -- load ---------------------------------------------------------------
    def load(
        self, target: Any = None, *, target_mesh=None
    ) -> Optional[Tuple[Any, dict]]:
        """Restore the newest available state: shm (warm) else storage.

        With ``target`` given, returns (pytree-like-target, meta); without,
        returns (ShardSource, meta) for caller-side assembly.

        ``target_mesh`` (restore-to-any-mesh, ROADMAP item 2 entry
        point): re-home ``target`` onto that mesh before assembly — each
        leaf keeps its PartitionSpec (replicated for non-NamedSharding
        leaves) but lands on the NEW world's devices, so a checkpoint
        saved by any M-process world restores onto whatever mesh the new
        world has.  The storage path then reads only the source shards
        the reshard plan proves it needs (see :meth:`_select_pids`)."""
        with span("ckpt.load", "ckpt", rank=self.process_id) as sp:
            self._load_span = sp
            result = self._load(target, target_mesh)
            if result is None:
                sp.set(source="none")  # nothing restorable: a fresh start
            else:
                sp.set(step=int(result[1].get("step", -1)))
            return result

    def _load(self, target: Any, target_mesh):
        if target is not None and target_mesh is not None:
            target = self._retarget(target, target_mesh)
        self._restore_boxes = (
            self._target_boxes(target) if target is not None else None
        )
        self._man_cache = {}
        result = self._restore_from_shm(target)
        if result is not None:
            self._load_span.set(source="shm")
            return result
        self._load_span.set(source="storage")
        # Storage: committed step first, then newer uncommitted steps whose
        # available shards still cover the target (e.g. a breakpoint save
        # from a partial world with replicated state).  Corruption is
        # treated like absence — a damaged step is skipped (and
        # quarantined), never allowed to abort the whole restore.
        result = None
        chosen = -1
        self._step_had_corruption = {}
        for source, extra, selective in self._storage_candidates():
            cand_step = int(extra.get("step", -1))
            try:
                result = self._assemble_candidate(
                    source, extra, target, selective, cand_step
                )
                chosen = max(cand_step, 0)
                break
            except KeyError as e:
                logger.warning(
                    "storage step %s not restorable (%s); trying older",
                    extra.get("step"), e,
                )
            except Exception as e:  # noqa: BLE001 - unverified v1 payloads
                # can fail assembly in arbitrary ways; the ladder must
                # fall through to an older candidate, not crash.
                logger.warning(
                    "storage step %s failed to assemble (%s: %s); "
                    "trying older",
                    extra.get("step"), type(e).__name__, e,
                )
            if self._step_had_corruption.get(cand_step):
                self._quarantine(cand_step)
        with span("ckpt.load.agree", "ckpt"):
            return self._agree_storage_step(result, chosen, target)

    def _restore_from_shm(self, target: Any):
        """Warm restore from this rank's arena, or ``None`` for "go to
        storage" (the same answer on every rank).

        The arena hands out one ``ArenaTensor`` a piece (dtype, shape,
        place) and ``restore_to_target`` moves each one by ``read()`` on
        the arena's file: into a reused staging buffer and from there to
        ``jax.device_put`` for an accelerator, straight into an array
        the tree owns for a host leaf or the CPU backend.  No host copy
        of the state is made, and no page of the fresh mapping is
        touched for a tensor byte.  What makes the reads safe is the
        hold: the rank's fencing lock keeps every other writer out (the
        agent saver's ``seed_from_replicas`` may ``write_state`` this
        arena after a re-rendezvous) and the arena mutex keeps the
        segment open (``reopen()`` is inside the hold, and the
        standalone persist thread reads the same segment), from before
        the header is read until ``block_until_ready`` of the restored
        state has returned.  After that no piece of the state refers to
        the arena or to a staging buffer.

        Without a target the ``ShardSource`` escapes to the caller with
        unbounded lifetime, so it holds arrays of its own, ``read()``
        under the same hold.  The saver never waits on a collective, so
        holding a per-rank lock across the ranks' agreement cannot
        cycle."""
        self._fence_arena()
        try:
            got = self._load_from_shm(copy=target is None)
            with span("ckpt.load.agree", "ckpt"):
                # collective: same branch all ranks
                got = self._agree_shm_step(got)
            if got is None:
                return None
            source, extra = got
            try:
                result = self._finish_load(source, extra, target)
            except KeyError:
                result = None
                logger.warning(
                    "shm restore incomplete; falling back to storage"
                )
        finally:
            self._unfence_arena()
        # Collective: if any rank's shm assembly failed, all ranks
        # fall back together (collective-count symmetry).
        with span("ckpt.load.agree", "ckpt"):
            ok = self._all_ranks_ok(result is not None)
        return result if ok else None

    def _assemble_candidate(
        self, source, extra, target, selective: bool, step: int
    ):
        """Assemble one storage candidate; when PLAN-SELECTED reads left
        the target uncoverable (selection is bandwidth, never
        correctness), retry the same step reading every shard in full
        before letting the ladder fall to an older step."""
        try:
            return self._finish_load(source, extra, target)
        except KeyError:
            if not selective:
                raise
            logger.warning(
                "storage step %d uncoverable from plan-selected reads; "
                "retrying with a full read", step,
            )
            full = self._read_step(step, selective=False)
            if full is None:
                raise
            return self._finish_load(full[0], full[1], target)

    def _all_ranks_ok(self, ok: bool) -> bool:
        """Collective AND over processes (True everywhere or False
        everywhere); trivially ``ok`` single-process."""
        if self.num_processes <= 1:
            return ok
        try:
            import jax as _jax
            from jax.experimental import multihost_utils

            if _jax.process_count() != self.num_processes:
                return ok
            flags = np.asarray(
                multihost_utils.process_allgather(np.int64(1 if ok else 0))
            ).reshape(-1)
            return bool(flags.all())
        except Exception:  # noqa: BLE001
            return ok

    def _agree_storage_step(self, result, chosen: int, target):
        """Cross-rank agreement on the restored storage step: per-rank read
        failures must not let ranks silently resume from different steps.
        All processes call this (collective); single-process is a no-op."""
        if self.num_processes <= 1:
            return result
        try:
            import jax as _jax
            from jax.experimental import multihost_utils

            if _jax.process_count() != self.num_processes:
                return result
            steps = np.asarray(
                multihost_utils.process_allgather(np.int64(chosen))
            ).reshape(-1)
        except Exception:  # noqa: BLE001 - not in a distributed context
            return result
        if (steps == chosen).all():
            return result  # unanimous (including unanimous "nothing")
        if (steps < 0).any():
            agreed = -1  # someone has nothing restorable: all start fresh
        else:
            agreed = int(steps.min())
        logger.warning(
            "storage restore steps disagree across ranks (%s); agreeing "
            "on %s", steps.tolist(), agreed if agreed >= 0 else "fresh start",
        )
        retry = None
        if agreed >= 0:
            if chosen == agreed:
                retry = result
            else:
                for source, extra, selective in self._storage_candidates():
                    if int(extra.get("step", -1)) != agreed:
                        continue
                    try:
                        retry = self._assemble_candidate(
                            source, extra, target, selective, agreed
                        )
                    except Exception as e:  # noqa: BLE001 - uncoverable or
                        # damaged agreed step: fall to the collective below
                        logger.warning(
                            "agreed step %d failed to assemble: %s",
                            agreed, e,
                        )
                        retry = None
                    break
        # Second collective: every rank must have the agreed step or all
        # abandon the restore together.
        ok = np.asarray(
            multihost_utils.process_allgather(
                np.int64(1 if (retry is not None or agreed < 0) else 0)
            )
        ).reshape(-1)
        if not ok.all():
            logger.warning(
                "agreed storage step %d unrestorable on some rank; "
                "starting fresh", agreed,
            )
            return None
        return retry if agreed >= 0 else None

    def _finish_load(self, source, extra, target):
        meta = extra.get("meta", {})
        meta.setdefault("step", extra.get("step", 0))
        if target is None:
            return source, meta
        with span("ckpt.load.device_put", "ckpt", host=True) as sp:
            tally: Dict[str, int] = {}
            state = tree_utils.restore_to_target(target, source, tally)
            # device_put returns before the bytes are on the device, and
            # until they are it may still read the piece it was given
            jax.block_until_ready(state)
            sp.set(**tally)
        return state, meta

    def _agree_shm_step(self, got):
        """Cross-rank shard-step consistency check (reference ckpt_saver's
        ``check_complete_step_before_save`` / shard-step checks): a warm
        restore is only valid when every process staged the SAME step —
        staging lag at a crash can leave ranks a few steps apart, and mixing
        them silently corrupts replicated state.  On disagreement fall back
        to storage, whose commit protocol is all-ranks-atomic.

        Every process must call this (it is a collective)."""
        if self.num_processes <= 1:
            return got
        try:
            from jax.experimental import multihost_utils

            if jax.process_count() != self.num_processes:
                return got
            own = -1 if got is None else int(got[1].get("step", -1))
            steps = np.asarray(
                multihost_utils.process_allgather(np.int64(own))
            ).reshape(-1)
        except Exception:  # noqa: BLE001 - not in a distributed context
            return got
        if (steps >= 0).all() and (steps == steps[0]).all():
            return got
        if got is not None:
            logger.warning(
                "shm restore steps disagree across ranks (%s); "
                "falling back to committed storage checkpoint",
                steps.tolist(),
            )
        return None

    def _load_from_shm(self, copy: bool):
        """Read the staged state's header and meta; the caller holds
        :meth:`_fence_arena` (``reopen()`` closes the segment, and with
        ``copy=False`` the tensors are ``ArenaTensor`` handles whose
        bytes are read later, inside that hold: under
        ``ckpt.load.device_put``)."""
        with span("ckpt.load.shm_read", "ckpt", copy=copy) as sp:
            try:
                self._arena.reopen()
                read = self._arena.read_state(copy=copy)
            except (FileNotFoundError, OSError):
                return None  # no arena yet: first run on this host
            except Exception:  # noqa: BLE001
                logger.exception("shm restore failed; trying storage")
                return None
            if read is None:
                return None
            tensors, extra = read
            nbytes = sum(int(a.nbytes) for a in tensors.values())
            sp.set(bytes=nbytes)
            self._load_span.set(bytes=nbytes)
        info = extra.get("tensors_info", {})
        if not info:
            return None
        # A warm restore is only valid for the same world size — a changed
        # world's local shards won't match this process's old layout;
        # storage has every process's shards for true resharding.
        if extra.get("num_processes") != self.num_processes or extra.get(
            "process_id"
        ) != self.process_id:
            logger.info(
                "shm state belongs to another world layout "
                "(proc %s/%s vs %s/%s); falling back to storage",
                extra.get("process_id"), extra.get("num_processes"),
                self.process_id, self.num_processes,
            )
            return None
        source = tree_utils.ShardSource()
        source.add(tensors, info)
        logger.info(
            "flash ckpt: warm restore from shm (step %s)", extra.get("step")
        )
        return source, extra

    @staticmethod
    def _retarget(target: Any, target_mesh) -> Any:
        """Re-home a target tree onto ``target_mesh``: sharding-bearing
        leaves become ShapeDtypeStruct placeholders with the SAME
        PartitionSpec on the new mesh (NamedSharding leaves keep their
        factorization; any other sharding replicates); host leaves pass
        through untouched."""
        from jax.sharding import NamedSharding, PartitionSpec

        def per_leaf(leaf):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                return leaf
            spec = (
                sharding.spec
                if isinstance(sharding, NamedSharding)
                else PartitionSpec()
            )
            return jax.ShapeDtypeStruct(
                tuple(leaf.shape),
                leaf.dtype,
                sharding=NamedSharding(target_mesh, spec),
            )

        return jax.tree_util.tree_map(per_leaf, target)

    @staticmethod
    def _target_boxes(target: Any) -> Optional[Dict[str, list]]:
        """{path: [addressable boxes]} of a target tree — the question
        the reshard planner answers shard selection for.  ``None`` when
        the tree cannot be described (selection then reads everything)."""
        try:
            from jax.tree_util import keystr, tree_flatten_with_path

            from dlrover_tpu.checkpoint.tree_utils import (
                _leaf_placements,
                _norm_index,
            )

            out: Dict[str, list] = {}
            for path, leaf in tree_flatten_with_path(target)[0]:
                name = keystr(path)
                placed = _leaf_placements(leaf)
                if placed is not None:
                    _s, gshape, placements = placed
                    boxes = {
                        _norm_index(idx, gshape) for _d, idx in placements
                    }
                else:
                    shape = tuple(
                        getattr(leaf, "shape", np.shape(leaf))
                    )
                    boxes = {tuple((0, d) for d in shape)}
                out[name] = sorted(boxes)
            return out
        except Exception as e:  # noqa: BLE001 - selection is an
            # optimization; an undescribable target just reads all shards
            logger.debug("target-box derivation failed: %s", e)
            return None

    def _manifest(self, step: int, pid: int):
        """Cached header+meta fetch: shard selection and the data read
        share ONE verified meta pass per shard per load (PR 6 accepted
        the double read; this PR retires it).  Raises
        :class:`ShardCorruptionError`; ``None`` when absent."""
        man = self._man_cache.get((step, pid))
        if man is None:
            man = shard_file.read_shard_manifest(
                self.storage, self.ckpt_dir, step, pid
            )
            if man is not None:
                self._man_cache[(step, pid)] = man
        return man

    @staticmethod
    def _box_overlap(a, b) -> bool:
        if len(a) != len(b):
            return False
        return all(
            max(s1, s2) < min(e1, e2) for (s1, e1), (s2, e2) in zip(a, b)
        )

    def _needed_keys(self, man):
        """The minimal piece set this rank must read from one shard: keys
        whose box overlaps any target box.  ``None`` = read everything
        (no target, or an undescribable manifest)."""
        boxes = self._restore_boxes
        if boxes is None:
            return None
        try:
            info = man.extra.get("tensors_info") or {}
            need = set()
            for key, m in info.items():
                tb = boxes.get(m["path"])
                if not tb:
                    continue
                box = tuple(tuple(int(v) for v in p) for p in m["index"])
                if any(self._box_overlap(box, b) for b in tb):
                    need.add(key)
            return need
        except Exception as e:  # noqa: BLE001 - filtering is bandwidth;
            # an odd manifest just reads in full
            logger.debug("needed-key derivation failed: %s", e)
            return None

    def _select_pids(self, step: int, pids: list) -> list:
        """Plan-driven shard selection: of a step's shards, which source
        ranks' pieces does THIS process's target actually overlap?  A
        dp=16 world restoring replicated params should read one rank's
        shard, not sixteen — unless the step was SLICE-persisted, where
        the disjoint slices of every needed box are all needed (and only
        ranks holding overlapping pieces are).  Any failure (unreadable
        meta, uncoverable target, planner error) falls back to reading
        everything — selection is bandwidth, never correctness.  The
        manifests fetched here are cached and reused by the data read."""
        boxes = self._restore_boxes
        if boxes is None or len(pids) <= 1:
            return pids
        try:
            manifests = {}
            for pid in pids:
                man = self._manifest(step, pid)
                if man is None:
                    continue
                if not (man.extra.get("tensors_info") or {}):
                    return pids
                manifests[pid] = man
            if not manifests:
                return pids
            if any(m.extra.get("sliced") for m in manifests.values()):
                chosen = []
                for p in pids:
                    if p not in manifests:
                        continue
                    need = self._needed_keys(manifests[p])
                    if need is None:
                        # Derivation failed for this shard: "read
                        # everything" — excluding it would make every
                        # load pay the uncoverable-assembly full-read
                        # retry instead.
                        return pids
                    if need:
                        chosen.append(p)
            else:
                from dlrover_tpu.reshard.plan import ranks_needed

                need = ranks_needed(
                    {
                        pid: m.extra["tensors_info"]
                        for pid, m in manifests.items()
                    },
                    boxes,
                    dst_rank=self.process_id,
                )
                chosen = [p for p in pids if p in set(need)]
            if not chosen:
                return pids
            if len(chosen) < len(pids):
                logger.info(
                    "flash ckpt: reshard plan needs %d/%d shards of "
                    "step %d", len(chosen), len(pids), step,
                )
            return chosen
        except Exception as e:  # noqa: BLE001 - see docstring: selection
            # must never turn a restorable step into a failed one
            logger.debug(
                "shard selection for step %d fell back to full read: %s",
                step, e,
            )
            return pids

    def _read_step(self, step: int, selective: bool = True):
        """Read one step's shards into a ShardSource: plan-selected ranks
        only, needed pieces only, shards read CONCURRENTLY (each rank's
        restore pulls its minimal slice set from multiple slice files at
        once).  Returns ``(source, extra, was_selective)`` or ``None``
        when nothing was readable.

        A shard that fails verification is skipped like an absent one
        (the step may still cover the target from other ranks' shards).
        """
        with span("ckpt.load.storage_read", "ckpt", step=step,
                  selective=selective):
            return self._read_step_shards(step, selective)

    def _read_step_shards(self, step: int, selective: bool):
        source = tree_utils.ShardSource()
        extra_out = None
        corrupt = False
        read_failed = False
        pids = shard_file.list_shard_ids(self.storage, self.ckpt_dir, step)
        chosen = self._select_pids(step, pids) if selective else list(pids)
        was_selective = selective and (
            len(chosen) < len(pids) or self._restore_boxes is not None
        )

        def _read_one(pid: int, restrict: bool):
            try:
                man = self._manifest(step, pid)
                if man is None:
                    return pid, "absent", None
                keys = self._needed_keys(man) if restrict else None
                got = shard_file.read_shard_pieces(
                    self.storage, self.ckpt_dir, step, pid,
                    manifest=man, keys=keys,
                )
                if got is None:
                    # Absent counts as a failed SELECTED read too: a
                    # shard GC'd between list and read must trigger the
                    # unselected-replica fallback below, not starve it.
                    return pid, "absent", None
                return pid, "ok", got
            except shard_file.ShardCorruptionError as e:
                return pid, "corrupt", e
            except Exception as e:  # noqa: BLE001 - I/O hiccup: treat
                # the shard as absent (no quarantine — nothing proves
                # the bytes themselves are damaged).
                return pid, "error", e

        def _merge(results) -> None:
            nonlocal extra_out, corrupt, read_failed
            for pid, status, payload in results:
                if status == "ok":
                    tensors, slices, extra = payload
                    source.add(
                        tensors, extra.get("tensors_info", {}), slices
                    )
                    if pid == self.process_id or extra_out is None:
                        extra_out = extra
                elif status == "corrupt":
                    corrupt = True
                    read_failed = True
                    self._note_corruption(step, pid, payload)
                elif status == "error":
                    read_failed = True
                    logger.warning(
                        "shard (step %d, proc %d) unreadable (%s: %s); "
                        "skipping", step, pid,
                        type(payload).__name__, payload,
                    )
                else:
                    read_failed = True

        _merge(self._read_many(chosen, selective, _read_one))
        if read_failed and len(chosen) < len(pids):
            # A plan-selected shard was damaged/absent; the skipped
            # ranks may still cover the target (replicated layouts).
            # Selection saves bandwidth — it must never cost a
            # restorable step.
            rest = [p for p in pids if p not in set(chosen)]
            _merge(self._read_many(rest, False, _read_one))
        self._step_had_corruption[step] = corrupt
        if extra_out is None:
            if corrupt:
                self._quarantine(step)
            return None
        return source, extra_out, was_selective

    def _read_many(self, pids: list, restrict: bool, read_one):
        """Concurrent shard reads (bounded by ``ckpt_shard_io_workers``),
        results in ``pids`` order so extra_out stays deterministic."""
        if not pids:
            return []
        workers = min(
            len(pids), max(1, int(self._ctx.ckpt_shard_io_workers))
        )
        if workers <= 1 or len(pids) <= 1:
            return [read_one(pid, restrict) for pid in pids]
        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="ckpt-read"
        ) as pool:
            return list(pool.map(lambda p: read_one(p, restrict), pids))

    def _storage_candidates(self):
        """Yield (source, extra, selective) per restorable storage step:
        the committed (tracker) step first, then remaining step dirs
        newest-first.  The caller validates coverage by attempting
        assembly — an uncommitted step is usable when its present shards
        cover the target (fully replicated layouts need any one rank's
        shard; slice-persisted layouts need every overlapping slice).

        A step whose every shard is unreadable *and* showed corruption
        is quarantined on the spot."""
        committed = shard_file.latest_step(self.storage, self.ckpt_dir)
        steps = shard_file.list_steps(self.storage, self.ckpt_dir)
        candidates = []
        # Only a LIVE committed step is a candidate: on backends without
        # rename the quarantine is a marker file (list_steps filters it),
        # and the tracker must not smuggle the damaged step back in on
        # every restart.
        if committed is not None and committed in steps:
            candidates.append(committed)
        candidates.extend(
            s for s in sorted(steps, reverse=True) if s != committed
        )
        for step in candidates:
            got = self._read_step(step)
            if got is None:
                continue
            source, extra_out, was_selective = got
            logger.info(
                "flash ckpt: restore from storage step %d%s",
                step, "" if step == committed else " (uncommitted)",
            )
            yield source, extra_out, was_selective

    # -- integrity bookkeeping ----------------------------------------------
    def _note_corruption(
        self, step: int, pid: int, err: Exception
    ) -> None:
        integrity_counters.inc("ckpt_corruption_detected")
        logger.warning(
            "corrupt checkpoint shard (step %d, proc %d): %s",
            step, pid, err,
        )
        self._report_integrity(
            {
                "event": "corruption_detected",
                "step": step,
                "process_id": pid,
                "reason": str(err),
            }
        )

    def _quarantine(self, step: int) -> None:
        where = shard_file.quarantine_step(
            self.storage, self.ckpt_dir, step
        )
        if where is None:
            return
        integrity_counters.inc("ckpt_step_quarantined")
        self._report_integrity(
            {"event": "step_quarantined", "step": step, "path": where}
        )

    def _report_integrity(self, event: dict) -> None:
        """Best-effort diagnosis report: the master log is where silent
        bit-rot becomes an operator signal; the restore proceeds either
        way."""
        if self.client is None:
            return
        try:
            self.client.report_diagnosis_data(
                DiagnosisDataType.CKPT_INTEGRITY, json.dumps(event)
            )
        except Exception as e:  # noqa: BLE001
            logger.debug("ckpt integrity report failed: %s", e)

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        self._arena.close()
