"""``tpurun`` — the job launcher CLI (``python -m dlrover_tpu.run``).

Parity with reference ``dlrover-run`` (``trainer/torch/elastic_run.py``:
``parse_args :125``, ``_launch_dlrover_local_master :245``,
``_check_dlrover_master_available :277``, ``run :413``): a torchrun-style
front-end that (on node 0 of standalone jobs) spawns a local master
subprocess, waits for it, merges master-pushed run config, then hands off to
the elastic agent.

Examples::

    # single host, 2 worker processes, local master auto-spawned
    tpurun --standalone --nproc_per_node=2 train.py --lr 3e-4

    # multi-host: every host points at the job master
    tpurun --master_addr=10.0.0.2:5001 --nnodes=2:4 --node_rank=$RANK train.py
"""

from __future__ import annotations

import argparse
import atexit
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import List, Optional, Tuple

from dlrover_tpu import chaos, obs
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import (
    ElasticLaunchConfig,
    check_one_process_per_chip,
    launch_agent,
)
from dlrover_tpu.common.log import logger, set_role
from dlrover_tpu.common.rpc import addr_connectable


def parse_nnodes(spec: str) -> Tuple[int, int]:
    if ":" in spec:
        lo, hi = spec.split(":", 1)
        return int(lo), int(hi)
    n = int(spec)
    return n, n


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        "tpurun", description="elastic TPU training launcher"
    )
    p.add_argument("--standalone", action="store_true",
                   help="single-host mode: auto-spawn a local master")
    p.add_argument("--standby", action="store_true",
                   help="master HA (ISSUE 13): give the standalone local "
                        "master a durable state journal plus a WARM "
                        "STANDBY that adopts the state on a crash "
                        "(instead of the cold blank-state relaunch)")
    p.add_argument("--master_state_dir", default="",
                   help="control-plane journal dir for --standby "
                        "(default: a run-scoped dir under the system "
                        "temp dir)")
    p.add_argument("--cell", type=int, default=0,
                   help="multi-cell control plane (ISSUE 15): spawn a "
                        "shared cell registry plus N cell masters "
                        "(consistent-hash node ownership); this node "
                        "talks to its node id's OWNING cell.  Composes "
                        "with --standby: every cell master then gets "
                        "its own journal + warm standby")
    p.add_argument("--nnodes", default="1",
                   help="'N' or 'MIN:MAX' elastic node range")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("DLROVER_TPU_NODE_RANK", 0)))
    p.add_argument("--node_id", type=int, default=-1,
                   help="stable node id (defaults to node_rank)")
    p.add_argument("--master_addr", default=os.environ.get(
        "DLROVER_TPU_MASTER_ADDR", ""))
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--monitor_interval", type=float, default=2.0)
    p.add_argument("--rdzv_timeout", type=float, default=600.0)
    p.add_argument("--network_check", action="store_true",
                   help="run the pre-flight matmul+psum node check")
    p.add_argument("--comm_perf_test", action="store_true")
    p.add_argument("--node_unit", type=int, default=1)
    p.add_argument("--log_dir", default="")
    p.add_argument("--job_name", default=os.environ.get(
        "DLROVER_TPU_JOB_NAME", "local-job"))
    p.add_argument("--node_role", default=os.environ.get(
        "DLROVER_TPU_NODE_ROLE", "worker"),
        help="fleet role of this node (ISSUE 10): 'worker' joins the "
             "training rendezvous; service roles ('gateway', "
             "'embedding') register for supervision only and run "
             "their entrypoint outside the XLA mesh")
    p.add_argument("--no_python", action="store_true",
                   help="entrypoint is a program, not a python script")
    p.add_argument("--job_file", default="",
                   help="declarative ElasticJob YAML (script, args, "
                        "replicas, ckpt config); explicit CLI flags win")
    p.add_argument("entrypoint", nargs="?", default="",
                   help="training script (optional with --job_file)")
    p.add_argument("args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.job_file:
        _apply_job_file(p, args)
    elif not args.entrypoint:
        p.error("entrypoint is required (or pass --job_file)")
    return args


def _apply_job_file(parser: argparse.ArgumentParser,
                    args: argparse.Namespace) -> None:
    """Fill launcher settings from an ElasticJob YAML (reference
    ``elastic_job.yaml`` consumed by the operator; here the launcher
    reads it directly).  A flag the user set explicitly (i.e. differs
    from the parser default) is never overridden."""
    from dlrover_tpu.scheduler.jobfile import load_elastic_job, nnodes_arg

    jf = load_elastic_job(args.job_file)

    def default_only(name: str, value) -> None:
        if getattr(args, name) == parser.get_default(name):
            setattr(args, name, value)

    if not args.entrypoint and jf.script:
        args.entrypoint = jf.script
    if not args.entrypoint:
        parser.error(
            f"--job_file {args.job_file}: no spec.template.script and no "
            "entrypoint argument"
        )
    default_only("job_name", jf.name)
    default_only("nnodes", nnodes_arg(jf))
    default_only("nproc_per_node", jf.nproc_per_node)
    default_only("node_unit", jf.node_unit)
    default_only("max_restarts", jf.max_restarts)
    if jf.network_check:
        args.network_check = True
    ckpt_extra = []
    if jf.ckpt_dir:
        ckpt_extra.append(f"--ckpt_dir={jf.ckpt_dir}")
    if jf.ckpt_interval:
        ckpt_extra.append(f"--ckpt_interval={jf.ckpt_interval}")
    if not args.args:
        extra = list(jf.script_args) + ckpt_extra
        args.args = ["--", *extra] if extra else []
    else:
        # User-provided script args replace the YAML's, but the
        # checkpoint config is durability state, not a script arg —
        # keep it unless the user explicitly overrides the same flag
        # (exact flag-name match; a substring test would false-positive
        # on e.g. --ckpt_dirs).
        user_flags = {
            a.split("=", 1)[0] for a in args.args if a.startswith("--")
        }
        args.args = list(args.args) + [
            e for e in ckpt_extra
            if e.split("=", 1)[0] not in user_flags
        ]


def _master_cmd(args, port: int, port_file: str = "",
                state_dir: str = "") -> List[str]:
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    cmd = [
        sys.executable, "-m", "dlrover_tpu.master.main",
        "--port", str(port),
        "--job_name", args.job_name,
        "--platform", "local",
        "--min_nodes", str(min_nodes),
        "--max_nodes", str(max_nodes),
        "--node_unit", str(args.node_unit),
    ]
    if port_file:
        cmd += ["--port_file", port_file]
    if state_dir:
        cmd += ["--state_dir", state_dir]
    # Multi-cell launches stash the per-cell identity on a COPY of the
    # args namespace (the count flag itself is ``--cell``), so every
    # relaunch path — cold supervisor, HA promote — reproduces it.
    if getattr(args, "cell_id", ""):
        cmd += ["--cell_id", args.cell_id,
                "--cell_registry", getattr(args, "cell_registry", "")]
    return cmd


def _launch_local_master(args, state_dir: str = "") \
        -> Tuple[subprocess.Popen, str, int]:
    """Spawn ``python -m dlrover_tpu.master.main`` and wait for its port
    (reference ``_launch_dlrover_local_master :245``)."""
    port_file = tempfile.mktemp(prefix="dlrtpu_master_port_")
    proc = subprocess.Popen(_master_cmd(args, 0, port_file, state_dir))
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                content = f.read().strip()
            if content:
                os.unlink(port_file)
                return proc, f"127.0.0.1:{content}", int(content)
        if proc.poll() is not None:
            raise RuntimeError(
                f"local master exited early with code {proc.returncode}"
            )
        time.sleep(0.2)
    raise TimeoutError("local master did not report its port in 60s")


#: Chaos crash sites aimed at the PRIMARY master; a standby inheriting
#: the env verbatim would arm them too and die alongside it.
_MASTER_CRASH_SITES = ("master.kill", "master.restart",
                       "master.journal_torn")


def _launch_standby_master(args, state_dir: str, primary_addr: str) \
        -> Tuple[subprocess.Popen, str]:
    """Spawn a warm standby (``master.main --standby``) and wait for the
    port it BOUND (it serves only after takeover)."""
    port_file = tempfile.mktemp(prefix="dlrtpu_standby_port_")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.master.main",
        "--standby", "--state_dir", state_dir,
        "--primary_addr", primary_addr,
        "--port", "0", "--port_file", port_file,
        "--job_name", args.job_name,
    ]
    if getattr(args, "cell_id", ""):
        cmd += ["--cell_id", args.cell_id,
                "--cell_registry", getattr(args, "cell_registry", "")]
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    cmd += ["--min_nodes", str(min_nodes), "--max_nodes", str(max_nodes),
            "--node_unit", str(args.node_unit)]
    env = chaos.scrub_env(dict(os.environ), _MASTER_CRASH_SITES)
    proc = subprocess.Popen(cmd, env=env)
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                content = f.read().strip()
            if content:
                os.unlink(port_file)
                return proc, f"127.0.0.1:{content}"
        if proc.poll() is not None:
            raise RuntimeError(
                f"standby master exited early with code {proc.returncode}"
            )
        time.sleep(0.2)
    raise TimeoutError("standby master did not report its port in 60s")


def _supervise_local_master(
    args,
    holder: List[subprocess.Popen],
    port: int,
    stop_evt: threading.Event,
    max_restarts: int = 3,
) -> threading.Thread:
    """Keep the standalone job's local master alive: if it exits nonzero
    while the job is still running, relaunch it on the SAME port (agents
    ride the gap via RPC retry + rendezvous re-join).  A clean exit (rc=0,
    job finished) ends supervision.  This is what turns a chaos
    ``master.restart`` — or a real master crash — into a blip instead of
    a dead job."""

    def loop() -> None:
        restarts = 0
        while not stop_evt.wait(1.0):
            proc = holder[0]
            rc = proc.poll()
            if rc is None:
                continue
            if rc == 0 or rc < 0:
                # rc 0: job finished.  rc < 0: killed by a signal — the
                # master never signals itself, so this is the launcher's
                # own teardown (atexit terminate on an abnormal exit
                # path); respawning would orphan a master on the port.
                return
            if restarts >= max_restarts:
                logger.error(
                    "local master exited rc=%d and restart budget (%d) is "
                    "spent; agents will time out", rc, max_restarts,
                )
                return
            restarts += 1
            logger.warning(
                "local master exited rc=%d; relaunching on port %d "
                "(restart %d/%d)", rc, port, restarts, max_restarts,
            )
            env = dict(os.environ)
            plan = chaos.active_plan()
            restart_codes = {
                s.exit_code for s in plan.specs
                if s.site == "master.restart"
            } if plan is not None else set()
            if rc in restart_codes:
                # The one-shot crash fault fired (matched by the plan's
                # own exit code, so exit= overrides are recognized); a
                # replacement inheriting the plan verbatim would re-arm
                # it and die identically.
                chaos.scrub_env(env, ("master.restart",))
            holder[0] = subprocess.Popen(_master_cmd(args, port), env=env)

    thread = threading.Thread(
        target=loop, name="master-supervisor", daemon=True
    )
    thread.start()
    return thread


def _supervise_ha_masters(
    args,
    state_dir: str,
    primary_holder: List[subprocess.Popen],
    standby_holder: List[subprocess.Popen],
    stop_evt: threading.Event,
    max_restarts: int = 3,
) -> threading.Thread:
    """The --standby supervision mode (ISSUE 13), next to the cold
    ``_supervise_local_master`` path: on a primary crash the standby
    ADOPTS the journaled state (hot), so the supervisor's job is not to
    relaunch the dead primary but to (a) wait for the takeover, (b)
    promote the standby process into the primary slot, and (c) spawn a
    FRESH standby behind the new leader so the next crash is also hot.
    Agents follow the leader via the state-dir ``addr`` file chain, so
    repeated failovers need no env changes.  A standby that dies while
    the primary is healthy is simply respawned."""
    from dlrover_tpu.master.state import read_addr

    def loop() -> None:
        restarts = 0
        while not stop_evt.wait(1.0):
            primary, standby = primary_holder[0], standby_holder[0]
            prc = primary.poll()
            if prc is None:
                src = standby.poll()
                if src is not None and src != 0 and not stop_evt.is_set():
                    if restarts >= max_restarts:
                        logger.error(
                            "standby exited rc=%d and restart budget (%d) "
                            "is spent; next master crash will be cold",
                            src, max_restarts,
                        )
                        return
                    restarts += 1
                    logger.warning(
                        "standby exited rc=%d; respawning (restart %d/%d)",
                        src, restarts, max_restarts,
                    )
                    try:
                        standby_holder[0], _ = _launch_standby_master(
                            args, state_dir, read_addr(state_dir)
                        )
                    except (RuntimeError, TimeoutError) as e:
                        logger.error(
                            "could not respawn a standby: %s; next "
                            "master crash will be cold", e,
                        )
                        return
                continue
            if prc == 0 or (prc < 0 and stop_evt.is_set()):
                # Job finished, or launcher teardown signalled the
                # master.  Unlike the cold supervisor, a signal death
                # alone is NOT teardown here: an external SIGKILL/OOM
                # kill of the primary is exactly the failure HA covers,
                # so only rc<0 paired with our own stop event returns.
                return
            # Primary crashed: the standby should take over.  Wait for
            # the new leader to publish its address (bounded).
            old_addr = read_addr(state_dir)
            deadline = time.time() + 60
            new_addr = ""
            while time.time() < deadline and not stop_evt.is_set():
                cur = read_addr(state_dir)
                if cur and cur != old_addr:
                    new_addr = cur
                    break
                if standby_holder[0].poll() is not None:
                    break  # standby died too — cold path below
                time.sleep(0.2)
            if not new_addr:
                logger.error(
                    "primary exited rc=%d and no takeover observed; "
                    "agents will time out", prc,
                )
                return
            logger.warning(
                "primary exited rc=%d; standby took over at %s",
                prc, new_addr,
            )
            # Promote, then back the new leader with a fresh standby.
            primary_holder[0] = standby_holder[0]
            if restarts >= max_restarts:
                logger.error(
                    "standby restart budget (%d) spent; the next master "
                    "crash will be cold", max_restarts,
                )
                return
            restarts += 1
            try:
                standby_holder[0], _ = _launch_standby_master(
                    args, state_dir, new_addr
                )
            except (RuntimeError, TimeoutError) as e:
                logger.error("could not respawn a standby: %s", e)
                return

    thread = threading.Thread(
        target=loop, name="master-ha-supervisor", daemon=True
    )
    thread.start()
    return thread


def _launch_cell_registry(args) -> Tuple[subprocess.Popen, str]:
    """Spawn the shared cell-registry KV and wait for its port."""
    port_file = tempfile.mktemp(prefix="dlrtpu_cellreg_port_")
    proc = subprocess.Popen([
        sys.executable, "-m", "dlrover_tpu.cells.main",
        "--registry", "--port", "0", "--port_file", port_file,
    ])
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                content = f.read().strip()
            if content:
                os.unlink(port_file)
                return proc, f"127.0.0.1:{content}"
        if proc.poll() is not None:
            raise RuntimeError(
                f"cell registry exited early rc={proc.returncode}"
            )
        time.sleep(0.2)
    raise TimeoutError("cell registry did not report its port in 60s")


def _launch_cells(args, master_stop: threading.Event) -> str:
    """``--cell N`` (ISSUE 15): registry + N cell masters, each under
    the SAME supervision ladder a single master gets (cold relaunch,
    or journal + warm standby with ``--standby``).  Returns the addr of
    THIS node's owning cell master."""
    import argparse as _argparse

    from dlrover_tpu.cells.cell import cell_for_node

    reg_proc, reg_addr = _launch_cell_registry(args)
    atexit.register(
        lambda: reg_proc.poll() is None and reg_proc.terminate()
    )
    # Exported for sidecar tooling: `python -m dlrover_tpu.cells.main
    # --federation` (and operator debugging) defaults its registry
    # address from this.
    os.environ["DLROVER_TPU_CELL_REGISTRY"] = reg_addr
    cell_ids = [f"cell{i}" for i in range(args.cell)]
    base_state = args.master_state_dir or os.path.join(
        tempfile.gettempdir(),
        f"dlrtpu_cells_{args.job_name}_"
        f"{os.environ['DLROVER_TPU_RUN_ID']}",
    )
    addrs: dict = {}
    for cid in cell_ids:
        cell_args = _argparse.Namespace(**vars(args))
        cell_args.cell_id = cid
        cell_args.cell_registry = reg_addr
        state_dir = ""
        if args.standby:
            state_dir = os.path.join(base_state, cid)
            os.makedirs(state_dir, exist_ok=True)
        holder: List[subprocess.Popen] = []
        proc, addr, port = _launch_local_master(cell_args, state_dir)
        holder.append(proc)
        addrs[cid] = (addr, state_dir)
        atexit.register(
            lambda h=holder: h[0].poll() is None and h[0].terminate()
        )
        if args.standby:
            sb_holder: List[subprocess.Popen] = []
            sb_proc, _sb_addr = _launch_standby_master(
                cell_args, state_dir, addr
            )
            sb_holder.append(sb_proc)
            atexit.register(
                lambda h=sb_holder: h[0].poll() is None
                and h[0].terminate()
            )
            _supervise_ha_masters(
                cell_args, state_dir, holder, sb_holder, master_stop,
                args.max_restarts,
            )
        else:
            _supervise_local_master(
                cell_args, holder, port, master_stop, args.max_restarts
            )
    node_id = args.node_id if args.node_id >= 0 else args.node_rank
    own = cell_for_node(node_id, cell_ids)
    own_addr, own_state = addrs[own]
    if own_state:
        # The agent's failover chain follows the OWNING cell's journal.
        os.environ["DLROVER_TPU_MASTER_STATE_DIR"] = own_state
    logger.info(
        "multi-cell control plane up: registry %s, cells %s; node %d "
        "-> %s at %s", reg_addr,
        {c: a for c, (a, _s) in addrs.items()}, node_id, own, own_addr,
    )
    return own_addr


def _gc_shm_arenas(
    job_name: str, run_id: str = "", min_age_s: float = 3600.0
) -> None:
    """Unlink /dev/shm arenas of ``job_name``: one run id exactly (exit
    cleanup), or — with no run id — only arenas idle for ``min_age_s``
    (startup GC).  The age guard matters: several nodes of one job can
    share a host, and a relaunching node must never wipe a live sibling's
    staged checkpoint (live arenas are rewritten every few steps, so their
    mtime is always fresh)."""
    import glob
    import time as _time

    safe = job_name.replace("/", "_")
    scope = f"{safe}-{run_id}" if run_id else f"{safe}-*"
    now = _time.time()
    for path in glob.glob(f"/dev/shm/dlrtpu_{scope}_*"):
        try:
            # graftcheck: disable=OB301 -- compared against the file's
            # wall-clock mtime; wall time is the point here
            if not run_id and now - os.stat(path).st_mtime < min_age_s:
                continue
            os.unlink(path)
        except OSError:
            pass


def run(args: argparse.Namespace) -> int:
    set_role(f"agent-{args.node_rank}")
    try:  # before any process is started
        check_one_process_per_chip(args.nproc_per_node)
    except ValueError as e:
        raise SystemExit(f"dlrover_tpu.run: {e}")
    os.environ["DLROVER_TPU_NODE_ROLE"] = args.node_role
    # One id per launcher invocation: namespaces host-local IPC (shm
    # arenas/queues/locks) so stale state from a previous launch of the
    # same job name can't leak into this one.
    os.environ.setdefault("DLROVER_TPU_RUN_ID", uuid.uuid4().hex[:8])
    # A training job has a flight-recorder journal without asking: the
    # agent (this process) and, through it, every worker incarnation
    # write their spans there as they end, so a job that died by SIGKILL
    # can be read back (python -m dlrover_tpu.obs.postmortem <dir>).  The
    # operator's DLROVER_TPU_OBS_DIR wins; ours goes when the job ends
    # well.
    own_obs_dir = "" if os.environ.get(obs.ENV_DIR) else obs.job_dir(
        args.job_name, os.environ["DLROVER_TPU_RUN_ID"])
    obs.gc_job_dirs()
    obs.configure(out_dir=own_obs_dir or os.environ[obs.ENV_DIR],
                  process=f"agent-n{args.node_rank}")
    logger.info("flight recorder journals: %s",
                obs.get_recorder().out_dir)
    # Run-scoped arenas would otherwise accumulate in RAM-backed /dev/shm,
    # one multi-GB set per launch: GC leftovers of earlier launches of this
    # job now, and unlink our own at exit.  Durable state lives in storage
    # (breakpoint saves persist before workers are torn down).
    _gc_shm_arenas(args.job_name)
    atexit.register(_gc_shm_arenas, args.job_name,
                    os.environ["DLROVER_TPU_RUN_ID"])
    if chaos.active_plan() is not None:
        logger.warning(
            "launcher: chaos fault plan is ACTIVE: %s",
            chaos.active_plan().describe(),
        )
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    master_holder: List[subprocess.Popen] = []
    standby_holder: List[subprocess.Popen] = []
    master_stop = threading.Event()
    master_addr = args.master_addr
    ha_state_dir = ""
    if args.standalone and not master_addr and args.cell > 0:
        master_addr = _launch_cells(args, master_stop)
        ha_state_dir = os.environ.get("DLROVER_TPU_MASTER_STATE_DIR", "")
    elif args.standalone and not master_addr:
        if args.standby:
            ha_state_dir = args.master_state_dir or os.path.join(
                tempfile.gettempdir(),
                f"dlrtpu_ha_{args.job_name}_"
                f"{os.environ['DLROVER_TPU_RUN_ID']}",
            )
            os.makedirs(ha_state_dir, exist_ok=True)
        proc, master_addr, master_port = _launch_local_master(
            args, ha_state_dir
        )
        master_holder.append(proc)
        if args.standby:
            sb_proc, standby_addr = _launch_standby_master(
                args, ha_state_dir, master_addr
            )
            standby_holder.append(sb_proc)
            # Agents (and their workers, which inherit the env) learn
            # both the failover chain (state-dir addr file) and the
            # static standby address.
            os.environ["DLROVER_TPU_MASTER_STATE_DIR"] = ha_state_dir
            os.environ["DLROVER_TPU_MASTER_STANDBY_ADDR"] = standby_addr
            _supervise_ha_masters(
                args, ha_state_dir, master_holder, standby_holder,
                master_stop, args.max_restarts,
            )
            atexit.register(
                lambda: standby_holder[0].poll() is None
                and standby_holder[0].terminate()
            )
        else:
            _supervise_local_master(
                args, master_holder, master_port, master_stop
            )
        atexit.register(
            lambda: master_holder[0].poll() is None
            and master_holder[0].terminate()
        )
    if not master_addr:
        raise SystemExit(
            "either --standalone or --master_addr is required"
        )
    if not addr_connectable(master_addr, timeout=30):
        raise SystemExit(f"master at {master_addr} is not reachable")

    node_id = args.node_id if args.node_id >= 0 else args.node_rank
    config = ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        nproc_per_node=args.nproc_per_node,
        node_id=node_id,
        node_rank=args.node_rank,
        max_restarts=args.max_restarts,
        monitor_interval=args.monitor_interval,
        rdzv_timeout=args.rdzv_timeout,
        network_check=args.network_check,
        comm_perf_test=args.comm_perf_test,
        log_dir=args.log_dir,
        job_name=args.job_name,
        node_role=args.node_role,
    )
    config.auto_configure()

    # Merge master-pushed run config (reference _elastic_config_from_master).
    # The state-dir hook makes the launcher's own client follow a
    # failover (the final job-exit report must reach the NEW leader).
    client = MasterClient(master_addr, node_id, state_dir=ha_state_dir)
    def _coerce(cur, val):
        # bool("false") is True: string-valued run configs (the usual
        # wire form) need explicit truthiness parsing for bool fields.
        if isinstance(cur, bool) and isinstance(val, str):
            return val.strip().lower() in ("1", "true", "yes", "on")
        return type(cur)(val)

    try:
        pushed = client.get_elastic_run_config()
        for key, val in pushed.items():
            if hasattr(config, key):
                setattr(config, key, _coerce(getattr(config, key), val))
    except Exception as e:  # noqa: BLE001
        logger.warning("could not fetch master run config: %s", e)

    # Gate on the CONFIG (CLI merged with master-pushed run config just
    # above) so a master enabling/disabling the checks actually takes
    # effect — node_health_check reads config.comm_perf_test too.
    if config.network_check or config.comm_perf_test:
        from dlrover_tpu.agent.node_check import node_health_check

        ok = node_health_check(config, master_addr, client)
        if not ok:
            logger.error("node health check failed; exiting for relaunch")
            return 3

    entry = (
        [args.entrypoint] if args.no_python
        else [sys.executable, "-u", args.entrypoint]
    )
    script_args = args.args
    if script_args and script_args[0] == "--":
        script_args = script_args[1:]
    try:
        rc = launch_agent(config, entry + script_args, master_addr)
    finally:
        # Stop master supervision on EVERY exit path: if the agent raised,
        # the atexit terminate must not race a supervisor respawn.
        master_stop.set()
    if master_holder:
        try:
            client.report_job_exit(rc == 0, "launcher done")
        except Exception as e:  # noqa: BLE001
            # Best-effort courtesy RPC, but a dead master here usually
            # explains a confusing exit — leave a trace.
            logger.debug("job-exit report to master failed: %s", e)
        try:
            master_holder[0].wait(timeout=30)
        except subprocess.TimeoutExpired:
            logger.warning("local master did not exit in 30s; terminating")
            master_holder[0].terminate()
    client.close()
    if own_obs_dir and rc == 0:
        obs.configure()  # ring only from here: no exit spill re-creates it
        shutil.rmtree(own_obs_dir, ignore_errors=True)
        try:
            os.rmdir(os.path.dirname(own_obs_dir))  # if it is empty now
        except OSError:
            pass
    return rc


def main() -> None:
    sys.exit(run(parse_args()))


if __name__ == "__main__":
    main()
