"""Worker-process bootstrap: from agent env contract to a live JAX world.

The TPU-native replacement for torch's ``init_process_group`` + torchelastic
env plumbing (reference ``training.py _set_master_addr_port :570`` and the
worker-side ``torch.distributed`` init): the agent hands each worker its
``process_id``/``num_processes``/coordinator via env; ``init()`` brings up
``jax.distributed``, connects the master client, and returns an
:class:`ElasticContext` for step reporting, dynamic sharding and checkpoint
access.
"""

from __future__ import annotations

import atexit
import os
from typing import Optional

from dlrover_tpu import chaos
from dlrover_tpu.agent.master_client import MasterClient, build_master_client
from dlrover_tpu.common import env as env_utils
from dlrover_tpu.common.jax_env import (
    compilation_cache_dir,
    enable_compilation_cache,
    initialize_distributed_from_env,
    process_age_s,
)
from dlrover_tpu.common.log import logger, set_role
from dlrover_tpu.obs import ENV_PARENT, journal, span


class ElasticContext:
    """What a worker knows about its place in the elastic job."""

    def __init__(self):
        self.node_id = env_utils.get_node_id()
        self.node_rank = env_utils.get_node_rank()
        self.node_num = env_utils.get_node_num()
        self.process_id = env_utils.get_process_id()
        self.num_processes = env_utils.get_num_processes()
        self.local_rank = int(os.environ.get("DLROVER_TPU_LOCAL_RANK", 0))
        self.restart_count = int(
            os.environ.get("DLROVER_TPU_RESTART_COUNT", 0)
        )
        self.rdzv_round = int(os.environ.get("DLROVER_TPU_RDZV_ROUND", 0))
        #: Fleet role of this process (ISSUE 10): entrypoints shared by
        #: several roles (e.g. llama_serve_fleet) branch on it.
        self.node_role = os.environ.get("DLROVER_TPU_NODE_ROLE", "worker")
        self.job_name = env_utils.get_job_name()
        self.master_addr = env_utils.get_master_addr()
        self.client: Optional[MasterClient] = None
        self.distributed = False
        self._last_metrics_report = 0.0
        self._last_reshard_poll = 0.0
        self._last_reshard_epoch = -1

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    def report_step(self, step: int) -> None:
        """Feed the master's speed monitor / goodput accounting (leader
        only; reference ``report_global_step``) and, throttled, this node's
        step-metrics diagnosis stream (per-node stall detection,
        reference xpu-timer collector)."""
        # Chaos: ``worker.kill:rank=R,step=N`` hard-kills this worker at a
        # deterministic step; the agent's monitor loop must detect it,
        # breakpoint-save, and re-form the world.
        chaos.inject("worker.kill", rank=self.process_id, step=step)
        if self.client is None:
            return
        # per step: the ring alone, never the journal file
        with span("trainer.report_step", "trainer", ring_only=True,
                  step=step):
            self._report_step(step)

    def _report_step(self, step: int) -> None:
        if self.is_leader:
            try:
                self.client.report_global_step(step)
            except Exception as e:  # noqa: BLE001
                logger.warning("report_step failed: %s", e)
        if self.local_rank == 0:
            import time as _time

            nowm = _time.monotonic()
            if nowm - self._last_metrics_report > 30.0:
                self._last_metrics_report = nowm
                try:
                    import json as _json

                    self.client.report_diagnosis_data(
                        "step_metrics",
                        _json.dumps({"step": step, "ts": _time.time()}),
                    )
                except Exception as e:  # noqa: BLE001
                    # Missing a heartbeat is survivable; a silent
                    # string of them looks like a hang to the master.
                    logger.debug("step-metrics report failed: %s", e)


    # -- live resharding (ISSUE 6) ------------------------------------------
    def poll_reshard(self):
        """Between-steps check for a pending resize epoch (the master's
        live-reshard broadcast).  Throttled to
        ``Context.reshard_poll_interval`` so it can ride the step loop;
        returns a ``ReshardEpochInfo`` exactly once per NEW preparing
        epoch, else ``None``.  The caller (the training loop) quiesces at
        the step boundary, runs ``ElasticTrainer.reshard_live``, and
        reports the verdict via :meth:`report_reshard`."""
        if self.client is None:
            return None
        import time as _time

        from dlrover_tpu.common.global_context import get_context

        now = _time.monotonic()
        if now - self._last_reshard_poll < get_context().reshard_poll_interval:
            return None
        self._last_reshard_poll = now
        try:
            info = self.client.get_reshard_epoch()
        except Exception as e:  # noqa: BLE001
            logger.debug("reshard-epoch poll failed: %s", e)
            return None
        if info.status != "preparing" or info.epoch <= self._last_reshard_epoch:
            return None
        self._last_reshard_epoch = info.epoch
        logger.info(
            "reshard: observed resize epoch %d -> %d processes (spec=%s)",
            info.epoch, info.target_num_processes, info.target_spec,
        )
        return info

    def report_reshard(self, epoch: int, outcome=None, error: str = "") -> None:
        """Report a live-reshard verdict back to the master (best-effort:
        a lost report only means the epoch times out into the restart
        ladder — safe, just slower)."""
        if self.client is None:
            return
        try:
            if outcome is not None and getattr(outcome, "ok", False):
                self.client.report_reshard(
                    epoch, True,
                    downtime_ms=outcome.downtime_s * 1000.0,
                    moved_mb=outcome.moved_mb,
                )
            else:
                self.client.report_reshard(
                    epoch, False, reason=error or "reshard failed"
                )
        except Exception as e:  # noqa: BLE001
            logger.warning("reshard report failed: %s", e)


_ctx: Optional[ElasticContext] = None


def init(connect_master: bool = True) -> ElasticContext:
    """Bootstrap this worker process.  Idempotent."""
    global _ctx
    if _ctx is not None:
        return _ctx
    ctx = ElasticContext()
    set_role(f"worker-{ctx.process_id}")
    # the agent's span that started this process (agent.start_workers):
    # the restart is one tree across both processes' journals
    parent = os.environ.get(ENV_PARENT, "")
    # what the new interpreter and its imports took before this line
    journal("bootstrap.process_start", durable=True,
            since_process_start_s=round(process_age_s(), 3),
            rank=ctx.process_id, restart_count=ctx.restart_count,
            **({"psid": parent} if parent else {}))
    with span("bootstrap.init", "bootstrap", parent=parent,
              rank=ctx.process_id, restart_count=ctx.restart_count):
        _bring_up(ctx, connect_master)
    _ctx = ctx
    return ctx


def _bring_up(ctx: ElasticContext, connect_master: bool) -> None:
    if enable_compilation_cache():
        logger.info(
            "persistent XLA compilation cache at %s", compilation_cache_dir()
        )
    ctx.distributed = initialize_distributed_from_env()
    if ctx.distributed:
        import jax

        logger.info(
            "jax.distributed up: process %d/%d, %d local / %d global devices",
            ctx.process_id, ctx.num_processes,
            jax.local_device_count(), jax.device_count(),
        )
        atexit.register(_shutdown)
    if connect_master and ctx.master_addr:
        ctx.client = build_master_client(ctx.master_addr, ctx.node_id)


def get_elastic_context() -> Optional[ElasticContext]:
    return _ctx


def _shutdown() -> None:
    try:
        import threading

        import jax
        from jax.experimental import multihost_utils

        # Ranks can be many steps apart in wall-clock at exit (async
        # dispatch); sync first so the coordination service's shutdown
        # barrier (short timeout) sees everyone arrive together.  The sync
        # is bounded: a worker exiting alone (crash path) must not block
        # the agent's failure detection waiting for peers that will never
        # arrive.
        done = threading.Event()

        def _sync():
            try:
                multihost_utils.sync_global_devices("dlrover_tpu_exit")
            # graftcheck: disable=CC104 -- exit barrier is best-effort
            # by design: a crashed peer must not turn our clean exit
            # into a hang (the timeout path below documents this)
            except Exception:  # noqa: BLE001
                pass
            done.set()

        threading.Thread(target=_sync, daemon=True).start()
        if done.wait(timeout=60.0):
            jax.distributed.shutdown()
        # else: skip the shutdown barrier entirely; process teardown
        # closes the coordination channel and peers learn via heartbeat.
    # graftcheck: disable=CC104 -- teardown must never mask the
    # worker's real exit status with a shutdown-path error
    except Exception:  # noqa: BLE001
        pass
