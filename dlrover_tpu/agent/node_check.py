"""Pre-flight node health check: paired matmul+psum benchmark.

Parity with reference ``NodeCheckElasticAgent`` (``training.py:1241``,
payloads ``trainer/torch/node_check/nvidia_gpu.py:39``) on TPU terms: nodes
rendezvous in the *network-check* service, are paired into 2-node sub-worlds
(round 0: adjacent; round 1: fastest-with-slowest), and each pair runs a
small ``jit`` matmul + ``psum`` benchmark over its own JAX world.  Elapsed
times feed the master's fault/straggler detection
(``NetworkCheckRendezvousManager``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.rpc import find_free_port, local_ip

# The check payload runs in a subprocess: it needs the chip, and the agent
# must never open the device runtime itself (a chip belongs to one process
# at a time).  ``subprocess.run`` returns only after the child is gone, so
# the chip is free again before the workers start.
_PAYLOAD = r"""
import os, sys, time
import jax
coord = os.environ.get("DLROVER_TPU_CHECK_COORD", "")
nproc = int(os.environ.get("DLROVER_TPU_CHECK_NPROC", "1"))
pid = int(os.environ.get("DLROVER_TPU_CHECK_PID", "0"))
if coord and nproc > 1:
    jax.distributed.initialize(coord, num_processes=nproc, process_id=pid)
import jax.numpy as jnp
# Payload must be big enough to discriminate a sick chip from dispatch
# noise (reference uses a large matmul + a 16M-element allreduce): on an
# accelerator, 8 x 4096^3 matmuls ~ 1.1 TFLOP and the allreduce moves
# 64 MB; on CPU (tests) the small sizes keep the check sub-second.
on_cpu = jax.default_backend() == "cpu"
n = int(os.environ.get(
    "DLROVER_TPU_CHECK_MATMUL_N", "512" if on_cpu else "4096"))
x = jnp.ones((n, n), jnp.bfloat16)
f = jax.jit(lambda a: a @ a)
f(x).block_until_ready()  # compile outside the timed region
t0 = time.perf_counter()
for _ in range(8):
    x = f(x)
x.block_until_ready()
# Fault injection for tests: a "slow node" pays a fixed tax inside the
# timed region so straggler detection has something to catch.
time.sleep(float(os.environ.get("DLROVER_TPU_CHECK_DELAY_S", "0")))
matmul_t = time.perf_counter() - t0
if coord and nproc > 1:
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    import numpy as np
    mesh = Mesh(np.array(jax.devices()), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    g = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))
    m = int(os.environ.get(
        "DLROVER_TPU_CHECK_ALLREDUCE_M",
        "1048576" if on_cpu else "16777216"))
    per = m // max(1, jax.device_count())
    arr = jax.make_array_from_process_local_data(
        sharding, np.ones((per * jax.local_device_count(),), np.float32))
    g(arr).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(4):
        g(arr).block_until_ready()
    comm_t = time.perf_counter() - t0
    if os.environ.get("DLROVER_TPU_COMM_PERF", "") == "1":
        # Bandwidth sweep (reference --comm-perftest): allreduce bus
        # bandwidth at growing payloads, algbw = 2*(n-1)/n * bytes / t.
        nd = jax.device_count()
        for m_sweep in (1 << 20, 1 << 22, 1 << 24):
            per_s = m_sweep // nd
            a = jax.make_array_from_process_local_data(
                sharding,
                np.ones((per_s * jax.local_device_count(),), np.float32))
            g(a).block_until_ready()
            t1 = time.perf_counter()
            reps = 4
            for _ in range(reps):
                g(a).block_until_ready()
            el = (time.perf_counter() - t1) / reps
            busbw = 2.0 * (nd - 1) / nd * (m_sweep * 4) / el / 1e9
            print(f"COMM_PERF bytes={m_sweep * 4} time_s={el:.6f} "
                  f"busbw_gbps={busbw:.3f}", flush=True)
else:
    comm_t = 0.0
print(f"NODE_CHECK_RESULT {matmul_t + comm_t:.6f}", flush=True)
"""


def _run_check_payload(
    coord: str, nproc: int, pid: int, timeout: float = 300.0,
    comm_perf: bool = False,
) -> Optional[float]:
    env = dict(os.environ)
    env["DLROVER_TPU_CHECK_COORD"] = coord
    env["DLROVER_TPU_CHECK_NPROC"] = str(nproc)
    env["DLROVER_TPU_CHECK_PID"] = str(pid)
    if comm_perf:
        env["DLROVER_TPU_COMM_PERF"] = "1"
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PAYLOAD],
            env=env,
            capture_output=True,
            timeout=timeout,
            text=True,
        )
    except subprocess.TimeoutExpired:
        logger.error("node check payload timed out")
        return None
    result = None
    for line in out.stdout.splitlines():
        if line.startswith("COMM_PERF"):
            logger.info("comm perf: %s", line[len("COMM_PERF "):])
        if line.startswith("NODE_CHECK_RESULT"):
            result = float(line.split()[1])
    if result is not None:
        return result
    logger.error(
        "node check payload failed rc=%d stderr=%s",
        out.returncode, out.stderr[-2000:],
    )
    return None


def node_health_check(
    config, master_addr: str, client: MasterClient, rounds: int = 2
) -> bool:
    """Run ``rounds`` of the paired benchmark; returns False if the master
    declares this node faulty (reference ``node_health_check :1460``).
    With ``config.comm_perf_test`` the final round also sweeps allreduce
    payload sizes and logs bus bandwidth (reference ``--comm-perftest``)."""
    host = local_ip()
    comm_perf = bool(getattr(config, "comm_perf_test", False))
    for r in range(rounds):
        port = find_free_port()
        client.register_node(
            node_rank=config.node_rank,
            host=host,
            agent_port=port,
            local_world_size=1,
            slice_id=config.slice_id,
        )
        client.join_rendezvous(
            config.node_rank, 1, rdzv_name=RendezvousName.NETWORK_CHECK
        )
        world, coord, my_pid, nproc = {}, "", 0, 1
        deadline = time.time() + 120
        while time.time() < deadline:
            _, _, world, coord = client.get_comm_world(
                RendezvousName.NETWORK_CHECK
            )
            if world:
                break
            time.sleep(0.5)
        if world:
            nproc = len(world)
            for rank, meta in world.items():
                if meta["node_id"] == config.node_id:
                    my_pid = int(rank)
        elapsed = _run_check_payload(
            coord if nproc > 1 else "", nproc, my_pid,
            comm_perf=comm_perf and r == rounds - 1,
        )
        succeeded = elapsed is not None
        client.report_network_check(
            succeeded, elapsed if elapsed else 0.0, round_=r
        )
        logger.info(
            "node check round %d: ok=%s elapsed=%s", r, succeeded, elapsed
        )
        if r + 1 < rounds:
            # Advance the master's pairing round.
            from dlrover_tpu.common import messages as m

            # Round advance is master-driven in the dist master; standalone
            # agents simply re-join and report with the next round index.
            time.sleep(1.0)
    # Peers may still be reporting their final round; the verdict is only
    # final once the master has every participant's result (the `complete`
    # flag) — a stability heuristic would false-settle exactly when a peer
    # is the straggler being waited on.
    deadline = time.time() + 30.0
    while time.time() < deadline:
        _, _, complete = client.get_stragglers_full()
        if complete:
            break
        time.sleep(0.75)
    faults, _ = client.get_fault_nodes()
    if config.node_id in faults:
        return False
    stragglers, times = client.get_stragglers()
    if config.node_id in stragglers:
        logger.warning(
            "node %d flagged as straggler (times=%s)", config.node_id, times
        )
    return True
