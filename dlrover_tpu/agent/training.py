"""Elastic training agent: the per-node supervisor process.

Parity with reference ``elastic_agent/torch/training.py``
(``ElasticLaunchConfig :143``, ``MasterRendezvousHandler :217``,
``ElasticTrainingAgent :405``, ``launch_agent :1098``) re-designed for the
JAX runtime: instead of torchelastic's c10d store bootstrap, a completed
master rendezvous elects a **JAX coordinator** (rank-0 node, fresh port per
round) and assigns contiguous ``process_id`` s; workers then run
``jax.distributed.initialize``.  A membership change or worker failure tears
the round down and re-forms the world (JAX requires runtime re-init +
recompile — the flash-checkpoint shm restore hides the state reload,
SURVEY.md §7 "hard parts").

Agent responsibilities each round (reference ``_invoke_run :863``):
  rendezvous -> spawn workers -> monitor (exit codes, heartbeats,
  membership) -> on failure: breakpoint-save + diagnose -> restart/relaunch.
"""

from __future__ import annotations

import dataclasses
import os
import shlex
import signal
import subprocess
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import (
    DiagnosisActionType,
    NodeEnv,
    NodeStatus,
    RendezvousName,
)
from dlrover_tpu.common.env import worker_env
from dlrover_tpu.common.global_context import get_context
from dlrover_tpu.common.jax_env import (
    device_runtime_opened,
    host_chip_count,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.rpc import find_free_port, local_ip
from dlrover_tpu.obs import (
    ENV_DIR,
    ENV_PARENT,
    ENV_PROCESS,
    current_span_id,
    get_recorder,
    span,
)


@dataclasses.dataclass
class ElasticLaunchConfig:
    """Launch knobs (reference ``ElasticLaunchConfig :143`` +
    ``auto_configure_params :186``)."""

    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    node_id: int = 0
    node_rank: int = 0
    max_restarts: int = 3
    monitor_interval: float = 2.0
    rdzv_timeout: float = 600.0
    network_check: bool = False
    comm_perf_test: bool = False
    log_dir: str = ""
    job_name: str = "local-job"
    slice_id: str = ""
    #: Fleet role of this node (ISSUE 10): the master's job manager
    #: files it under the matching node group (worker / gateway /
    #: embedding) so one ElasticJob can launch heterogeneous roles.
    node_role: str = "worker"

    def auto_configure(self) -> None:
        """Fill derived params from env."""
        if self.slice_id == "":
            self.slice_id = os.environ.get("TPU_WORKER_HOSTNAMES", "")


def check_one_process_per_chip(nproc_per_node: int) -> None:
    """A chip belongs to one process at a time, and one JAX process drives
    every chip of its host: N workers on a chip host are N processes asking
    the runtime for the same chips (one wins, the rest die in libtpu's lock
    or hang in topology exchange).  Refuse up front, by the rule's name."""
    chips = host_chip_count()
    if chips and nproc_per_node > 1:
        raise ValueError(
            f"--nproc_per_node={nproc_per_node} on a host with {chips} TPU "
            "chip(s): one process per chip host — a node runs ONE worker "
            "that drives all local chips.  Use --nproc_per_node=1 here; >1 "
            "is for the virtual CPU mesh (JAX_PLATFORMS=cpu)."
        )


class WorkerProcess:
    def __init__(self, local_rank: int, proc: subprocess.Popen, log_file=None):
        self.local_rank = local_rank
        self.proc = proc
        self.log_file = log_file

    def poll(self) -> Optional[int]:
        return self.proc.poll()


class RunResult:
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    MEMBERSHIP_CHANGED = "membership_changed"
    STOP_JOB = "stop_job"
    RESTART_REQUESTED = "restart_requested"
    RELAUNCH_REQUESTED = "relaunch_requested"


class MonitorWatch:
    """What one call of ``ElasticTrainingAgent._monitor`` did with its
    time: the ``args`` of its ``agent.monitor`` span.  Each turn of the
    loop is three parts — its sleep, its polls of the workers, its
    question to the master — and a monotonic stamp closes each; the sums
    live here and nothing is recorded per turn (the span is journalled
    once, as ``_monitor`` returns; a record a second for the life of a job
    is not low-rate).

    ``unseen_s`` runs from the end of the newest poll pass in which no
    worker had a non-zero exit code (the entry to ``_monitor`` if none)
    to the end of the pass that saw one: the most the agent itself can
    have sat on a worker the kernel had already made waitable.  Near one
    interval, with ``busy_max_s`` (the longest ``poll + rpc`` of one turn:
    how long the loop was away from its sleep) near 0, the loop is sound
    and whatever else lies between a worker's death and the agent's
    notice of it is a process that polled as alive."""

    PARTS = 3  # sleep, poll, rpc

    def __init__(self):
        self._at = self._clean_at = time.monotonic()
        self._turn: List[float] = []  # the open turn's parts so far
        self._last: deque = deque(maxlen=4)  # the last turns' parts
        self.turns = 0
        self.sums = [0.0] * self.PARTS
        self.turn_max_s = self.busy_max_s = 0.0
        self.rpc_errors = 0
        self.unseen_s: Optional[float] = None

    def _stamp(self) -> float:
        now = time.monotonic()
        self._turn.append(now - self._at)
        self._at = now
        return now

    def slept(self) -> None:
        self._stamp()

    def polled(self, failed: bool) -> None:
        now = self._stamp()
        if failed:
            self.unseen_s = now - self._clean_at
        else:
            self._clean_at = now

    def asked(self, error: bool) -> None:
        self._stamp()
        self.rpc_errors += error
        self._close_turn()

    def _close_turn(self) -> None:
        """A turn that returned before its later parts has them as 0."""
        if not self._turn:
            return
        parts = self._turn + [0.0] * (self.PARTS - len(self._turn))
        self._turn = []
        self.turns += 1
        self.sums = [a + b for a, b in zip(self.sums, parts)]
        self.turn_max_s = max(self.turn_max_s, sum(parts))
        self.busy_max_s = max(self.busy_max_s, parts[1] + parts[2])
        self._last.append(parts)

    def args(self) -> dict:
        self._close_turn()
        sleep_s, poll_s, rpc_s = (round(x, 6) for x in self.sums)
        out = {
            "turns": self.turns, "sleep_s": sleep_s, "poll_s": poll_s,
            "rpc_s": rpc_s, "turn_max_s": round(self.turn_max_s, 6),
            "busy_max_s": round(self.busy_max_s, 6),
            "rpc_errors": self.rpc_errors,
            "last_turns": [[round(x, 6) for x in t] for t in self._last],
        }
        if self.unseen_s is not None:
            out["unseen_s"] = round(self.unseen_s, 6)
        return out


class ElasticTrainingAgent:
    """One agent per node; supervises ``nproc_per_node`` worker processes
    running the user script (reference ``ElasticTrainingAgent :405``)."""

    def __init__(
        self,
        config: ElasticLaunchConfig,
        entrypoint: List[str],
        master_addr: str,
        client: Optional[MasterClient] = None,
    ):
        self.config = config
        self.entrypoint = entrypoint
        self.master_addr = master_addr
        self.client = client or MasterClient(master_addr, config.node_id)
        self._ctx = get_context()
        self._workers: List[WorkerProcess] = []
        self._stop_evt = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._pending_action: Optional[str] = None
        self._restart_count = 0
        self._host = local_ip()
        self._rdzv_round = -1
        # Hooks the checkpoint saver plugs into (task: flash checkpoint).
        self.on_workers_stopping = None  # callable(reason) before kill
        self.saver = None  # AsyncCheckpointSaver, attached by launcher
        self._last_failures: List[tuple] = []
        # Sticky: chaos crash sites observed once (by their exit codes)
        # stay scrubbed from every later worker generation
        # (see _start_workers).
        self._spent_crash_sites: set = set()
        from dlrover_tpu.diagnosis.agent import DiagnosisAgent

        self.diagnosis = DiagnosisAgent(
            self.client,
            log_dir=config.log_dir,
            max_in_place_restarts=config.max_restarts,
        )
        from dlrover_tpu.agent.config_tuner import ParalConfigTuner
        from dlrover_tpu.agent.monitor import ResourceMonitor

        self.resource_monitor = ResourceMonitor(self.client)
        self.config_tuner = ParalConfigTuner(self.client)

    def _report_status(self, status: str, exit_reason: str = "") -> None:
        """Status reports are at-least-once best-effort: a report that
        exhausts its RPC retries (master restarting, network flap) must
        never take down the agent that is supposed to survive it."""
        try:
            self.client.report_node_status(
                status, node_type=self.config.node_role or "worker",
                exit_reason=exit_reason,
            )
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "status report %r failed (continuing): %s", status, e
            )

    def _report_failure_safe(
        self, error_data: str, restart_count: int = 0
    ) -> None:
        """Best-effort failure report (same contract as _report_status):
        the agent is about to recover from the failure locally, and a
        flaky master must not turn that recovery into a crash."""
        try:
            self.client.report_failure(
                error_data, restart_count=restart_count
            )
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "failure report failed (continuing): %s", e
            )

    # -- heartbeats --------------------------------------------------------
    def _start_heartbeat(self) -> None:
        if self._hb_thread is not None:
            return

        def loop():
            while not self._stop_evt.wait(self._ctx.node_heartbeat_interval):
                try:
                    actions = self.client.report_heartbeat()
                    for a in actions:
                        if a.action_type != DiagnosisActionType.NONE:
                            logger.info("heartbeat action: %s (%s)",
                                        a.action_type, a.reason)
                            self._pending_action = a.action_type
                except Exception as e:  # noqa: BLE001
                    logger.warning("heartbeat failed: %s", e)

        self._hb_thread = threading.Thread(
            target=loop, name="agent-heartbeat", daemon=True
        )
        self._hb_thread.start()

    # -- rendezvous (reference MasterRendezvousHandler.next_rendezvous) ----
    def _rendezvous(self) -> dict:
        with span("agent.rendezvous", "agent",
                  restart_count=self._restart_count) as sp:
            world_info = self._join_world()
            sp.set(round=world_info["round"],
                   num_processes=world_info["num_processes"])
            return world_info

    def _join_world(self) -> dict:
        """Join + poll until this node is in a completed world.

        Returns {round, world, my_rank, coordinator, num_processes}.

        Hardened against a master restart mid-rendezvous (chaos
        ``master.restart`` / ``rdzv.lost_node``): RPC failures during the
        poll are retried until the rendezvous deadline, and while no world
        has formed the join (+ registration, which the join's world
        metadata depends on) is re-sent every ``rdzv_rejoin_interval``
        seconds with the SAME attempt id — a no-op on a healthy master,
        a state re-seed on one that lost its membership.
        """
        cfg = self.config
        coord_port = find_free_port()
        attempt_id = uuid.uuid4().hex
        deadline = time.monotonic() + cfg.rdzv_timeout
        rejoin_interval = max(1.0, self._ctx.rdzv_rejoin_interval)
        joined = False
        last_join = 0.0
        join_failures = 0

        if cfg.node_role not in ("worker", "chief"):
            # Service roles (gateway / embedding store, ISSUE 10)
            # register for supervision + heartbeats but must NOT join
            # the training rendezvous — they have no place in the XLA
            # mesh, and a join would count them into the world size.
            # Their "world" is themselves.
            while True:
                try:
                    self.client.register_node(
                        node_type=cfg.node_role,
                        node_rank=cfg.node_rank,
                        host=self._host,
                        agent_port=coord_port,
                        slice_id=cfg.slice_id,
                        local_world_size=cfg.nproc_per_node,
                    )
                    break
                except Exception as e:  # noqa: BLE001
                    if time.time() >= deadline:
                        # Same contract as the worker path's rendezvous
                        # timeout: an agent that never registered must
                        # NOT launch an unsupervised orphan (the fleet
                        # reconciler would spawn a duplicate beside it).
                        raise TimeoutError(
                            f"{cfg.node_role}-role registration did "
                            f"not succeed within {cfg.rdzv_timeout}s"
                        ) from e
                    logger.warning(
                        "%s-role registration failed (will retry): %s",
                        cfg.node_role, e,
                    )
                    time.sleep(1.0)
            return {
                "round": 0,
                "world": {0: {
                    "node_id": cfg.node_id,
                    "local_world_size": cfg.nproc_per_node,
                    "process_id_base": 0,
                }},
                "my_rank": 0,
                "coordinator": "",
                "num_processes": cfg.nproc_per_node,
            }

        def _join() -> None:
            self.client.register_node(
                node_type=cfg.node_role,
                node_rank=cfg.node_rank,
                host=self._host,
                agent_port=coord_port,
                slice_id=cfg.slice_id,
                local_world_size=cfg.nproc_per_node,
            )
            self.client.join_rendezvous(
                cfg.node_rank, cfg.nproc_per_node,
                rdzv_name=RendezvousName.TRAINING, slice_id=cfg.slice_id,
                attempt_id=attempt_id,
            )

        while time.monotonic() < deadline:
            if not joined or time.monotonic() - last_join >= rejoin_interval:
                try:
                    _join()
                    if joined:
                        logger.info(
                            "rendezvous: re-sent join (no world after "
                            "%.0fs; master may have restarted)",
                            time.monotonic() - last_join,
                        )
                    joined = True
                    last_join = time.monotonic()
                    join_failures = 0
                except Exception as e:  # noqa: BLE001
                    join_failures += 1
                    logger.warning(
                        "rendezvous join failed (will retry): %s", e
                    )
                    if join_failures % 3 == 0:
                        # A channel that rode out a master restart can
                        # stay wedged in TRANSIENT_FAILURE; start fresh.
                        self.client.reconnect()
                    time.sleep(min(1.0, max(0.0, deadline - time.monotonic())))
                    continue
            try:
                round_, _, world, coordinator = self.client.get_comm_world(
                    RendezvousName.TRAINING
                )
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "rendezvous poll failed (will retry): %s", e
                )
                time.sleep(min(1.0, max(0.0, deadline - time.monotonic())))
                continue
            if world:
                my_rank = None
                for rank, meta in world.items():
                    if meta["node_id"] == cfg.node_id:
                        my_rank = int(rank)
                        break
                if my_rank is None:
                    # Completed without us (node-unit cut) - keep waiting for
                    # the next round.
                    time.sleep(1.0)
                    continue
                num_processes = sum(
                    w["local_world_size"] for w in world.values()
                )
                self._rdzv_round = round_
                logger.info(
                    "rendezvous round %d: world=%d nodes, my_rank=%d, "
                    "coordinator=%s", round_, len(world), my_rank, coordinator,
                )
                return {
                    "round": round_,
                    "world": world,
                    "my_rank": my_rank,
                    "coordinator": coordinator,
                    "num_processes": num_processes,
                }
            time.sleep(0.5)
        raise TimeoutError(
            f"rendezvous did not complete within {cfg.rdzv_timeout}s"
        )

    # -- worker lifecycle ---------------------------------------------------
    def _start_workers(self, world_info: dict) -> None:
        with span("agent.start_workers", "agent",
                  round=world_info["round"],
                  restart_count=self._restart_count) as sp:
            self._spawn_workers(world_info)
            sp.set(pids=[w.proc.pid for w in self._workers])

    def _spawn_workers(self, world_info: dict) -> None:
        cfg = self.config
        world = world_info["world"]
        my = world[world_info["my_rank"]]
        base = my["process_id_base"]
        self._workers = []
        if self.saver is not None:
            # Refresh replica ring + seed arenas from peers (a replaced
            # node recovers the last staged step without storage).
            try:
                self.saver.update_world(world_info["my_rank"], len(world))
                self.saver.seed_from_replicas(
                    {lr: base + lr for lr in range(cfg.nproc_per_node)},
                    world_info["num_processes"],
                )
            except Exception:  # noqa: BLE001
                logger.exception("replica seeding failed")
        # Workers run `python script.py`, whose sys.path[0] is the script's
        # dir; make the launcher's cwd and this framework importable
        # (torchrun's PYTHONPATH contract).
        import dlrover_tpu

        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            dlrover_tpu.__file__)))
        extra_path = [os.getcwd(), pkg_root]
        # A one-shot chaos crash fault that already fired in a worker
        # (worker.kill, or ckpt.crash_* in standalone-engine mode) must
        # not re-arm in the replacements — fault-firing state is per
        # process, so an inherited plan would crash-loop the job.
        # Non-crash faults intentionally survive the restart.  The spent
        # set is sticky (a later unrelated failure must not resurrect a
        # fault) and keyed on the plan's own exit codes, so exit=
        # overrides are recognized.
        from dlrover_tpu import chaos

        plan = chaos.active_plan()
        if plan is not None:
            crash_sites = {
                s.exit_code: s.site
                for s in plan.specs
                if s.kind == "crash" and s.site != "master.restart"
            }
            for _, code in self._last_failures:
                site = crash_sites.get(code)
                if site:
                    self._spent_crash_sites.add(site)
        for lr in range(cfg.nproc_per_node):
            env = dict(os.environ)
            if self._spent_crash_sites:
                chaos.scrub_env(env, self._spent_crash_sites)
            old_pp = env.get("PYTHONPATH", "")
            env["PYTHONPATH"] = os.pathsep.join(
                [p for p in extra_path if p]
                + ([old_pp] if old_pp else [])
            )
            env.update(
                worker_env(
                    job_name=cfg.job_name,
                    master_addr=self.master_addr,
                    node_id=cfg.node_id,
                    node_rank=world_info["my_rank"],
                    node_num=len(world),
                    process_id=base + lr,
                    num_processes=world_info["num_processes"],
                    coordinator=world_info["coordinator"],
                    restart_count=self._restart_count,
                )
            )
            env["DLROVER_TPU_LOCAL_RANK"] = str(lr)
            env["DLROVER_TPU_LOCAL_WORLD_SIZE"] = str(cfg.nproc_per_node)
            env["DLROVER_TPU_RDZV_ROUND"] = str(world_info["round"])
            env["DLROVER_TPU_NODE_ROLE"] = cfg.node_role or "worker"
            # the flight recorder: this process's dump directory (the
            # launcher's default, or the operator's), one journal file
            # per worker incarnation
            if get_recorder().out_dir:
                env[ENV_DIR] = get_recorder().out_dir
            env[ENV_PROCESS] = (
                f"worker-r{base + lr}-i{self._restart_count}")
            # ... and the span that starts it (agent.start_workers), the
            # parent of the worker's bootstrap: one tree per restart
            env[ENV_PARENT] = current_span_id()
            log_file = None
            stdout = stderr = None
            if cfg.log_dir:
                os.makedirs(cfg.log_dir, exist_ok=True)
                path = os.path.join(
                    cfg.log_dir,
                    f"worker_r{world_info['my_rank']}_l{lr}"
                    f"_round{world_info['round']}.log",
                )
                log_file = open(path, "ab")
                stdout = stderr = log_file
            proc = subprocess.Popen(
                self.entrypoint,
                env=env,
                stdout=stdout,
                stderr=stderr,
                start_new_session=True,  # own process group for clean kill
            )
            self._workers.append(WorkerProcess(lr, proc, log_file))
        logger.info(
            "started %d worker(s): pids=%s",
            len(self._workers), [w.proc.pid for w in self._workers],
        )

    def _stop_workers(self, reason: str = "", grace: float = 10.0) -> None:
        if not self._workers:
            return
        with span("agent.stop_workers", "agent", reason=str(reason)):
            self._halt_workers(reason, grace)

    def _halt_workers(self, reason: str, grace: float) -> None:
        if self.on_workers_stopping is not None:
            try:
                self.on_workers_stopping(reason)
            except Exception:  # noqa: BLE001
                logger.exception("on_workers_stopping hook failed")
        for w in self._workers:
            if w.poll() is None:
                try:
                    os.killpg(os.getpgid(w.proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + grace
        for w in self._workers:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                w.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(w.proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                w.proc.wait()
        for w in self._workers:
            if w.log_file:
                w.log_file.close()
        logger.info("stopped workers (%s)", reason or "requested")
        self._workers = []

    # -- monitor loop (reference training.py:886) ---------------------------
    def _monitor(self) -> str:
        """One ``agent.monitor`` span a call, closed as it returns (and
        journalled then: it is there when the agent is killed a moment
        later); ``agent.restart`` starts where it ends."""
        watch = MonitorWatch()
        result = ""
        sp = span("agent.monitor", "agent",
                  interval=self.config.monitor_interval).start()
        try:
            result = self._watch_workers(watch)
            return result
        finally:
            sp.end(result=str(result), **watch.args())

    def _watch_workers(self, watch: MonitorWatch) -> str:
        cfg = self.config
        while True:
            time.sleep(cfg.monitor_interval)
            watch.slept()
            # 1. master-pushed actions (via heartbeat thread)
            action = self._pending_action
            self._pending_action = None
            if action == DiagnosisActionType.STOP_JOB:
                return RunResult.STOP_JOB
            if action == DiagnosisActionType.RELAUNCH_WORKER:
                return RunResult.RELAUNCH_REQUESTED
            if action == DiagnosisActionType.RESTART_WORKER:
                return RunResult.RESTART_REQUESTED
            # 2. worker process health
            codes = [w.poll() for w in self._workers]
            failed = any(c is not None and c != 0 for c in codes)
            watch.polled(failed)
            if all(c == 0 for c in codes):
                return RunResult.SUCCEEDED
            if failed:
                bad = [
                    (w.local_rank, c)
                    for w, c in zip(self._workers, codes)
                    if c not in (None, 0)
                ]
                logger.warning("worker failure(s): %s", bad)
                self._last_failures = bad
                return RunResult.FAILED
            # 3. membership change -> re-rendezvous (reference
            #    _membership_changed :1028)
            rpc_error = False
            try:
                if self.client.num_nodes_waiting(RendezvousName.TRAINING) > 0:
                    return RunResult.MEMBERSHIP_CHANGED
            except Exception as e:  # noqa: BLE001
                rpc_error = True
                logger.warning("num_nodes_waiting failed: %s", e)
            finally:
                watch.asked(rpc_error)

    # -- main entry (reference _invoke_run :863) ----------------------------
    def run(self) -> int:
        cfg = self.config
        self._start_heartbeat()
        self.resource_monitor.start()
        if self._ctx.auto_tune:
            self.config_tuner.start()
        metrics_port = int(os.environ.get("DLROVER_TPU_METRICS_PORT", "0"))
        if metrics_port:
            from dlrover_tpu.agent.metrics import (
                INTEGRITY_COUNTER_NAMES,
                MetricsRegistry,
                MetricsServer,
                integrity_counters,
                perf_stats,
            )
            from dlrover_tpu.agent.monitor import current_usage

            reg = MetricsRegistry()
            reg.gauge("restart_count", lambda: float(self._restart_count))
            reg.gauge("rdzv_round", lambda: float(self._rdzv_round))
            # Checkpoint-integrity signals (replica rejections and staged
            # -state rejections happen in this process; corruption found
            # by worker-side restores reaches the master via the
            # ckpt_integrity diagnosis reports instead).
            for cname in INTEGRITY_COUNTER_NAMES:
                reg.gauge(
                    cname,
                    lambda n=cname: float(integrity_counters.get(n)),
                )
            # Flash-ckpt fast-path signals (ISSUE 4): persist throughput
            # is set by the in-process saver; the train-stall and staging
            # gauges read the workers' reports out of the saver's shared
            # stat dict (one short-budget snapshot per gauge sample).
            reg.gauge(
                "ckpt_persist_mbps",
                lambda: perf_stats.get("ckpt_persist_mbps"),
            )
            reg.gauge(
                "ckpt_stall_ms_last",
                lambda: (
                    self.saver.last_stall_ms()
                    if self.saver is not None
                    else perf_stats.get("ckpt_stall_ms_last")
                ),
            )
            reg.gauge(
                "ckpt_staged_mbps",
                lambda: (
                    self.saver.staged_mbps()
                    if self.saver is not None
                    else perf_stats.get("ckpt_staged_mbps")
                ),
            )
            # Scale-out checkpoint gauges (ISSUE 7), riding the saver's
            # one-round-trip stat snapshot: aggregate = the node's summed
            # per-rank slice-write bandwidth; skipped = dirty-fence refs
            # in the ranks' last incremental saves.
            reg.gauge(
                "ckpt_agg_persist_mbps",
                lambda: (
                    self.saver.agg_persist_mbps()
                    if self.saver is not None
                    else perf_stats.get("ckpt_agg_persist_mbps")
                ),
            )
            reg.gauge(
                "ckpt_tensors_skipped",
                lambda: (
                    float(self.saver.tensors_skipped_total())
                    if self.saver is not None
                    else perf_stats.get("ckpt_tensors_skipped")
                ),
            )
            reg.gauge(
                "node_cpu_percent",
                lambda: current_usage()["cpu_percent"],
            )
            reg.gauge(
                "node_memory_mb", lambda: current_usage()["memory_mb"]
            )
            try:
                self.metrics_server = MetricsServer(reg, metrics_port)
                self.metrics_server.start()
            except OSError:
                logger.warning(
                    "metrics port %d unavailable; endpoint disabled",
                    metrics_port,
                )
        # Flash-checkpoint saver daemon: lives in the agent so persistence
        # survives worker crashes (reference start_async_saving_ckpt :869).
        if self.saver is None:
            try:
                from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

                self.saver = AsyncCheckpointSaver(
                    cfg.job_name, cfg.nproc_per_node,
                    master_client=self.client,
                )
                self.saver.start()
                self.on_workers_stopping = self.saver.save_shm_to_storage
            except Exception:  # noqa: BLE001
                logger.exception("could not start async checkpoint saver")
        # failure seen by _monitor -> workers started again, across one
        # turn of the loop below
        restart = None
        try:
            while True:
                world_info = self._rendezvous()
                self._report_status(NodeStatus.RUNNING)
                self._start_workers(world_info)
                if restart is not None:
                    restart.end(round=world_info["round"])
                    restart = None
                result = self._monitor()
                if result not in (RunResult.SUCCEEDED, RunResult.STOP_JOB,
                                  RunResult.RELAUNCH_REQUESTED):
                    restart = span(
                        "agent.restart", "agent", reason=str(result),
                        restart_count=self._restart_count + (
                            result == RunResult.FAILED),
                        exit_codes=[c for _, c in self._last_failures]
                        if result == RunResult.FAILED else [],
                    ).start()
                if result == RunResult.SUCCEEDED:
                    self._stop_workers("success", grace=5.0)
                    self._report_status(NodeStatus.SUCCEEDED)
                    logger.info("node %d training succeeded", cfg.node_id)
                    return 0
                if result == RunResult.STOP_JOB:
                    self._stop_workers("stop-job")
                    self._report_status(
                        NodeStatus.FAILED, exit_reason="stopped_by_master"
                    )
                    return 1
                if result == RunResult.RELAUNCH_REQUESTED:
                    # Master diagnosed this node as sick: exit so the
                    # platform replaces it (in-place restart won't help).
                    self._stop_workers("master requested node relaunch")
                    self._report_status(
                        NodeStatus.FAILED, exit_reason="relaunch_requested"
                    )
                    return 1
                if result == RunResult.FAILED:
                    self._restart_count += 1
                    self._report_failure_safe(
                        f"worker failure (restart {self._restart_count}/"
                        f"{cfg.max_restarts}): {self._last_failures}",
                        restart_count=self._restart_count,
                    )
                    # RESTART (in place) vs RELAUNCH (replace this node) —
                    # reference diagnose_training_failure training.py:934.
                    action = self.diagnosis.diagnose_training_failure(
                        self._last_failures, self._restart_count
                    )
                    if (
                        action == DiagnosisActionType.RELAUNCH_WORKER
                        or self._restart_count > cfg.max_restarts
                    ):
                        self._stop_workers("relaunch requested")
                        self._report_status(
                            NodeStatus.FAILED,
                            exit_reason="relaunch_requested"
                            if self._restart_count <= cfg.max_restarts
                            else "max_restarts",
                        )
                        return 1
                    self._stop_workers("worker failure; re-rendezvous")
                elif result in (
                    RunResult.MEMBERSHIP_CHANGED,
                    RunResult.RESTART_REQUESTED,
                ):
                    logger.info("restarting workers: %s", result)
                    self._stop_workers(result)
                # loop -> new rendezvous round
        finally:
            if restart is not None:
                restart.end(gave_up=True)
            self._stop_evt.set()
            self._stop_workers("agent exiting")
            if self.saver is not None:
                self.saver.stop()
            # The chip is the workers': say whether this process ever
            # took it (it must not — chip_smoke.py reads this line).
            logger.info(
                "agent exit: device runtime opened by the agent: %s",
                device_runtime_opened(),
            )


def launch_agent(
    config: ElasticLaunchConfig,
    entrypoint: List[str],
    master_addr: str,
) -> int:
    """Build and run the agent (reference ``launch_agent :1098``)."""
    # again after master-pushed overrides of the launch config
    check_one_process_per_chip(config.nproc_per_node)
    agent = ElasticTrainingAgent(config, entrypoint, master_addr)
    return agent.run()
