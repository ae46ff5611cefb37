"""Flagship chaos e2e scenarios: a real process tree under a seeded
fault plan (``DLROVER_TPU_FAULTS``).

Three scenarios from the chaosd brief, all deterministic via the plan
seed:

1. RPC flap during training — client-side UNAVAILABLE injected on every
   control-plane call; training must still finish.
2. Master restart mid-rendezvous — the master hard-exits (chaos
   ``master.restart``) while node 0 is still waiting for node 1; a
   replacement master on the same port knows nothing, and node 0's
   periodic rendezvous re-join must re-seed it.  (Workers here are
   control-plane-only stubs: multi-process XLA collectives are not
   available on the CPU backend, and the scenario is about the control
   plane anyway.)
3. Crash mid-checkpoint-commit — the agent process hard-exits between
   writing step shards and advancing the tracker; a relaunch (same run
   id) must warm-restore from the surviving shm arena and keep training.

Marked ``slow``: the tier-1 lane runs only the sub-second chaos units in
``test_chaos.py``; these process-tree scenarios ride the e2e lane.
"""

import os
import re
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.chaos, pytest.mark.e2e, pytest.mark.slow]


def _read(path):
    if not os.path.exists(path):
        return ""
    with open(path) as f:
        return f.read()


def _env(extra=None):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO,
        }
    )
    env.pop("DLROVER_TPU_FAULTS", None)
    if extra:
        env.update(extra)
    return env


def _launch_standalone(tmp_path, job_name, script_args, env_extra=None,
                       log_name="run.log"):
    log = open(tmp_path / log_name, "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.run",
            "--standalone", "--nproc_per_node=1",
            f"--job_name={job_name}",
            "--monitor_interval=1",
            os.path.join(REPO, "examples", "nanogpt_train.py"),
            "--", *script_args,
        ],
        cwd=REPO, env=_env(env_extra), stdout=log,
        stderr=subprocess.STDOUT, start_new_session=True,
    )
    return proc, tmp_path / log_name


def _terminate(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


class TestRpcFlap:
    def test_training_survives_rpc_flaps(self, tmp_path):
        """Scenario 1: every control-plane RPC drops with p=0.25 (seeded).
        Jittered retry + idempotency tokens + best-effort status reports
        must carry the job to TRAIN_DONE."""
        proc, log = _launch_standalone(
            tmp_path, "chaos-rpcflap", ["--steps=8"],
            env_extra={
                "DLROVER_TPU_FAULTS": "rpc.unavailable:p=0.25,seed=7",
            },
        )
        try:
            rc = proc.wait(timeout=420)
        finally:
            _terminate([proc])
        content = _read(log)
        assert rc == 0, content[-3000:]
        assert "TRAIN_DONE step=8" in content, content[-3000:]
        # The plan actually bit: injected UNAVAILABLEs show up as retries.
        assert "chaos: fault plan active" in content, content[:2000]
        assert "chaos: rpc.unavailable fired" in content, content[-3000:]
        assert re.search(r"RPC \w+ to .* failed .*UNAVAILABLE", content), (
            content[-3000:]
        )


CTRL_WORKER = """\
import sys
import time

print("CTRL_WORKER_START", flush=True)
time.sleep(3.0)
print("CTRL_WORKER_DONE", flush=True)
sys.exit(0)
"""


class TestMasterRestartMidRendezvous:
    def test_rejoin_reseeds_replacement_master(self, tmp_path):
        """Scenario 2: the master dies (chaos master.restart, exit 42)
        while node 0 waits for node 1; a stateless replacement master on
        the same port must learn node 0 again via the agent's periodic
        re-join, then complete the round once node 1 arrives."""
        from dlrover_tpu.common.rpc import find_free_port

        job = "chaos-mrestart"
        port = find_free_port()
        worker_py = tmp_path / "ctrl_worker.py"
        worker_py.write_text(CTRL_WORKER)

        def start_master(faults):
            env = _env({"DLROVER_TPU_FAULTS": faults} if faults else None)
            log = open(tmp_path / "master.log", "a")
            return subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.master.main",
                    f"--port={port}", f"--job_name={job}",
                    "--min_nodes=2", "--max_nodes=2",
                ],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            )

        def start_node(rank):
            env = _env(
                {
                    # Fast re-join so the scenario stays snappy (>
                    # master's 3s lastcall window, well under default 10).
                    "DLROVER_TPU_RDZV_REJOIN_INTERVAL": "4",
                }
            )
            log = open(tmp_path / f"node{rank}.log", "w")
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.run",
                    "--nnodes=2", "--nproc_per_node=1",
                    f"--node_rank={rank}",
                    f"--master_addr=127.0.0.1:{port}",
                    f"--job_name={job}", "--monitor_interval=1",
                    str(worker_py),
                ],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            )
            return proc, tmp_path / f"node{rank}.log"

        # Master that hard-exits ~6s in — while node0 (min_nodes=2, no
        # peer yet) is still mid-rendezvous.
        m1 = start_master("master.restart:at=6s")
        n0, log0 = start_node(0)
        procs = [m1, n0]
        try:
            rc = m1.wait(timeout=60)
            assert rc == 42, f"master exited {rc}, wanted chaos 42:\n" + (
                _read(tmp_path / "master.log")[-2000:]
            )
            assert n0.poll() is None, (
                "node0 died with the master:\n" + _read(log0)[-3000:]
            )
            # Replacement master, same port, no faults, zero state.
            m2 = start_master(None)
            procs.append(m2)
            # Hold node 1 back past node 0's re-join interval so the log
            # provably shows node 0 re-seeding the blank master itself.
            time.sleep(6.0)
            n1, log1 = start_node(1)
            procs.append(n1)
            rc0 = n0.wait(timeout=300)
            rc1 = n1.wait(timeout=300)
            c0, c1 = _read(log0), _read(log1)
            assert rc0 == 0, c0[-3000:]
            assert rc1 == 0, c1[-3000:]
            assert "CTRL_WORKER_DONE" in c0, c0[-3000:]
            assert "CTRL_WORKER_DONE" in c1, c1[-3000:]
            # Node 0 really did ride through the restart via re-join.
            assert "re-sent join" in c0, c0[-3000:]
        finally:
            _terminate(procs)


class TestCrashMidCommit:
    def test_agent_crash_between_shards_and_tracker(self, tmp_path):
        """Scenario 3: the agent hard-exits mid-commit (after shard+done
        files, before the tracker advance — ``every=2`` crashes the 2nd
        commit so the 1st step is durably committed first).  The tracker
        must still name the previous step, and a relaunch with the same
        run id must warm-restore from the surviving shm arena."""
        job = "chaos-commit"
        ckpt = str(tmp_path / "ckpt")
        run_id = "chaoscommit1"
        proc, log = _launch_standalone(
            tmp_path, job,
            ["--steps=100000", f"--ckpt_dir={ckpt}", "--ckpt_interval=3",
             "--ckpt_storage_interval=3", "--batch_per_proc=2"],
            env_extra={
                "DLROVER_TPU_FAULTS":
                    "ckpt.crash_before_commit:every=2,times=1",
                "DLROVER_TPU_RUN_ID": run_id,
            },
            log_name="run1.log",
        )
        worker_pids = []
        try:
            rc = proc.wait(timeout=420)
            content = _read(log)
            # The commit crash takes down the whole agent process.
            assert rc == 66, f"rc={rc}\n" + content[-3000:]
            m = re.search(
                r"started 1 worker\(s\): pids=\[(\d+)\]", content
            )
            assert m, content[-3000:]
            worker_pids = [int(m.group(1))]
        finally:
            # The agent died hard: reap its orphans (the worker runs in
            # its own session; the master shares the launcher's group).
            for pid in worker_pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        # Commit atomicity: the crash hit a commit before its tracker
        # write, so the tracker either names the prior durable commit (a
        # valid step) or — if the two in-flight commits raced — does not
        # exist at all.  It is never torn.
        tracker = os.path.join(ckpt, "latest_checkpointed_step.txt")
        committed = 3
        if os.path.exists(tracker):
            committed = int(open(tracker).read().strip())
            assert committed >= 3

        # Relaunch with the SAME run id: the shm arena survived the agent
        # crash, so the restore must take the warm path.
        proc2, log2 = _launch_standalone(
            tmp_path, job,
            ["--steps=100000", f"--ckpt_dir={ckpt}", "--ckpt_interval=3",
             "--batch_per_proc=2"],
            env_extra={"DLROVER_TPU_RUN_ID": run_id},
            log_name="run2.log",
        )
        try:
            restored = False
            deadline = time.time() + 420
            while time.time() < deadline:
                c2 = _read(log2)
                if re.search(r"restored step=\d+", c2) and re.search(
                    r"step \d+ loss", c2
                ):
                    restored = True
                    break
                if proc2.poll() is not None:
                    break
                time.sleep(1.0)
            c2 = _read(log2)
            assert restored, "no restore after relaunch:\n" + c2[-3000:]
            assert "warm restore from shm" in c2, c2[-3000:]
            step = int(re.search(r"restored step=(\d+)", c2).group(1))
            assert step >= committed
        finally:
            _terminate([proc2])


class TestCorruptCommittedShard:
    def test_restore_falls_back_and_fsck_flags(self, tmp_path):
        """Scenario 4 (ISSUE 3 flagship): chaos corrupts the committed
        step's shard bytes as the agent persists them — the done file and
        tracker advance normally, exactly silent bit-rot.  A cold
        relaunch (new run id, no warm shm) must detect the damage,
        quarantine the step dir as ``step_N.corrupt``, and restore the
        previous committed step; ``checkpoint.fsck`` must exit nonzero
        naming the corrupt shard."""
        job = "chaos-corrupt"
        ckpt = str(tmp_path / "ckpt")
        proc, log = _launch_standalone(
            tmp_path, job,
            ["--steps=8", f"--ckpt_dir={ckpt}", "--ckpt_interval=3",
             "--ckpt_storage_interval=3", "--batch_per_proc=2"],
            env_extra={
                "DLROVER_TPU_FAULTS": "storage.corrupt_shard:step=8",
                "DLROVER_TPU_RUN_ID": "corrupt1",
            },
            log_name="run1.log",
        )
        try:
            rc = proc.wait(timeout=420)
        finally:
            _terminate([proc])
        content = _read(log)
        assert rc == 0, content[-3000:]
        assert "chaos: storage.corrupt_shard fired" in content, (
            content[-3000:]
        )
        # The commit protocol proceeded: the tracker names the damaged
        # final step (the trainer's end-of-run durable save) — integrity
        # is restore-side verification's job.
        tracker = os.path.join(ckpt, "latest_checkpointed_step.txt")
        assert int(_read(tracker).strip()) == 8

        # fsck flags the damage, naming the corrupt shard.
        fsck = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.checkpoint.fsck", ckpt],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=120,
        )
        assert fsck.returncode == 1, fsck.stdout + fsck.stderr
        assert "shard_00000.ckpt" in fsck.stdout, fsck.stdout

        # Cold relaunch (different run id -> fresh shm arena): the ladder
        # must skip the corrupt committed step 8 and restore step 6.
        proc2, log2 = _launch_standalone(
            tmp_path, job,
            ["--steps=8", f"--ckpt_dir={ckpt}", "--ckpt_interval=3",
             "--batch_per_proc=2"],
            env_extra={"DLROVER_TPU_RUN_ID": "corrupt2"},
            log_name="run2.log",
        )
        try:
            rc2 = proc2.wait(timeout=420)
        finally:
            _terminate([proc2])
        c2 = _read(log2)
        assert rc2 == 0, c2[-3000:]
        assert "restored step=6" in c2, c2[-3000:]
        assert "corrupt checkpoint shard (step 8" in c2, c2[-3000:]
        assert os.path.isdir(
            os.path.join(ckpt, "step_0000000008.corrupt")
        ), sorted(os.listdir(ckpt))
        # The quarantined dir still holds the evidence for fsck.
        fsck2 = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.checkpoint.fsck", ckpt],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=120,
        )
        assert fsck2.returncode == 1
        assert "quarantined" in fsck2.stdout.lower()


@pytest.mark.serving
class TestServingFleetKillAndDrain:
    """ISSUE 5 flagship: a 2-replica fleet under a real process tree.

    Replica r0 is chaos-killed mid-stream (``serving.replica_kill``
    fires after its 2nd completion, with work in flight); the gateway
    re-dispatches its in-flight requests, the relaunched r0 replays its
    journal, and EVERY admitted request completes exactly once — no
    loss (all results arrive), no duplicate (the gateway's completed
    counter equals the request count; journal-replay dupes are counted
    and dropped).  Then a scale-down drain retires one replica with
    requests in flight and nothing observes the shrink."""

    def _spawn(self, tmp_path, name, argv, env_extra=None):
        log = open(tmp_path / f"{name}.log", "w")
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "examples", "llama_serve_fleet.py"),
             *argv],
            cwd=REPO, env=_env(env_extra), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        return proc, tmp_path / f"{name}.log"

    def test_exactly_once_across_kill_and_drain(self, tmp_path):
        from dlrover_tpu.common.messages import (
            ServeDrainRequest,
            ServeFleetStats,
            ServeFleetStatsRequest,
        )
        from dlrover_tpu.common.rpc import RpcClient, find_free_port
        from dlrover_tpu.serving import ServeClient

        port = find_free_port()
        journal_dir = str(tmp_path / "journals")
        procs = []
        gw_proc, gw_log = self._spawn(
            tmp_path, "gateway",
            ["--role", "gateway", "--port", str(port),
             "--lease_timeout", "3"],
        )
        procs.append(gw_proc)

        def spawn_replica(rid, faults=None):
            extra = {"DLROVER_TPU_FAULTS": faults} if faults else None
            proc, log = self._spawn(
                tmp_path, f"replica-{rid}",
                ["--role", "replica", "--gateway",
                 f"127.0.0.1:{port}", "--replica_id", rid,
                 "--slots", "2", "--max_len", "64",
                 "--journal_dir", journal_dir,
                 "--poll_interval", "0.02",
                 "--round_floor_ms", "40"],
                env_extra=extra,
            )
            procs.append(proc)
            return proc, log

        try:
            # r0 dies the moment its 3rd completion would start
            # (served==2), leaving admitted work in flight.
            r0, r0_log = spawn_replica(
                "r0", faults="serving.replica_kill:step=2",
            )
            r1, _ = spawn_replica("r1")
            rpc = RpcClient(f"127.0.0.1:{port}", timeout=10.0)

            def fleet_stats():
                reply = rpc.call(ServeFleetStatsRequest(),
                                 idempotent=True)
                assert isinstance(reply, ServeFleetStats), reply
                return reply.stats

            deadline = time.time() + 120
            while time.time() < deadline:
                try:
                    if fleet_stats()["replicas_alive"] >= 2:
                        break
                except Exception:
                    pass
                time.sleep(0.5)
            else:
                raise AssertionError(
                    "fleet never formed: " + _read(gw_log)[-2000:]
                )

            client = ServeClient(rpc, poll_interval=0.05)
            n_req = 12
            prompts = [[(7 * i + j) % 50 + 1 for j in range(5)]
                       for i in range(n_req)]
            # STAGGERED budgets: equal budgets finish a replica's two
            # slots in the same emit pass, and the kill (which fires at
            # the tick AFTER the 2nd completion) would then land with
            # nothing in flight.  Desynchronized completions guarantee
            # r0 dies holding admitted work — the re-dispatch path
            # under test.
            budgets = [8 + (i % 7) for i in range(n_req)]
            for i, prompt in enumerate(prompts):
                ack = client.submit(f"req-{i}", prompt, budgets[i])
                assert ack.status in ("accepted", "done"), ack

            # The chaos kill lands mid-stream: r0 exits 78.
            rc0 = r0.wait(timeout=120)
            assert rc0 == 78, _read(r0_log)[-2000:]

            # The supervisor's role: relaunch r0 (spent crash site
            # scrubbed), same journal -> replay + re-register.
            r0b, r0b_log = spawn_replica("r0")

            results = {}
            for i in range(n_req):
                reply = client.result(f"req-{i}", timeout=120)
                assert reply.state == "done", (
                    f"req-{i}: {reply.state} {reply.reason}; gateway: "
                    + _read(gw_log)[-2000:]
                )
                results[i] = list(reply.tokens)
                # Full budget, no EOS cut, whoever served it.
                assert len(results[i]) == budgets[i]

            # r0's relaunch replays its journal when it registers —
            # wait for that report to land (its pre-kill completions
            # were already answered, so the replay MUST dedupe).
            deadline = time.time() + 60
            while time.time() < deadline:
                c = fleet_stats()["counters"]
                if c["duplicate_completions"] >= 1:
                    break
                time.sleep(0.5)
            stats = fleet_stats()
            c = stats["counters"]
            # No loss, no duplicate: every admitted request completed
            # EXACTLY once at the gateway.
            assert c["completed"] == n_req, c
            assert c["failed"] == 0 and c["timeout"] == 0, c
            # The kill actually cost in-flight work that was
            # re-dispatched (lease expiry or r0's re-register).
            assert c["redispatched"] >= 1, c
            # r0's journal replay re-reported its pre-kill completions;
            # dedupe dropped them.
            assert c["duplicate_completions"] >= 1, c

            # Exactly-once is also client-visible: resubmitting every
            # request answers from the dedupe cache with the SAME
            # tokens (no second decode, byte-identical).
            for i in range(n_req):
                ack = client.submit(f"req-{i}", prompts[i], budgets[i])
                assert ack.status == "done", ack
                assert list(ack.tokens) == results[i]
            assert fleet_stats()["counters"]["completed"] == n_req

            # --- scale-down drain with requests in flight ---
            for i in range(6):
                client.submit(f"late-{i}", prompts[i], 12)
            assert rpc.call(
                ServeDrainRequest(replica_id="r1")
            ).success
            for i in range(6):
                reply = client.result(f"late-{i}", timeout=120)
                assert reply.state == "done", (reply.state,
                                               reply.reason)
                assert len(reply.tokens) == 12
            # The drained replica exits cleanly after finishing its
            # in-flight work; the fleet shrinks to r0 only.
            assert r1.wait(timeout=60) == 0, _read(gw_log)[-1000:]
            deadline = time.time() + 30
            while time.time() < deadline:
                if fleet_stats()["replicas_alive"] == 1:
                    break
                time.sleep(0.5)
            stats = fleet_stats()
            assert stats["replicas_alive"] == 1, stats
            c = stats["counters"]
            assert c["completed"] == n_req + 6, c
            assert c["failed"] == 0 and c["timeout"] == 0, c
            content = _read(tmp_path / "replica-r0.log")
            assert "REPLICA_READY id=r0" in content
        finally:
            _terminate(procs)


@pytest.mark.reshard
class TestReshardDropSegmentFallsToLadder:
    """ISSUE 6 acceptance e2e: a plan segment lost mid-move
    (``reshard.drop_segment``) fails the live reshard LOUDLY; the job
    degrades to the checkpoint-restart ladder (flash-ckpt restore onto
    the new mesh), resumes past the resize point, and storage is
    fsck-clean afterwards — no hang, no torn state."""

    DRIVER = r"""
import os
import sys
import tempfile

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint import fsck as fsck_mod
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.tree_utils import flatten_to_shards
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh
from dlrover_tpu.reshard.coordinator import (
    ReshardError,
    reshard_shards,
    target_placeholders,
)
from dlrover_tpu.reshard.mover import (
    LocalShardSource,
    ReshardPeer,
    SegmentMover,
)

devs = jax.devices()
mesh2 = build_mesh(MeshSpec(fsdp=2), devs[:2])
mesh4 = build_mesh(MeshSpec(fsdp=4), devs[:4])
host = np.arange(256, dtype=np.float32).reshape(32, 8)
state = {"w": jax.device_put(host, NamedSharding(mesh2, P("fsdp")))}
step_fn = jax.jit(lambda s: {k: v + 1.0 for k, v in s.items()})
state = step_fn(state)
jax.block_until_ready(state)  # "step 1" done on the old mesh

ckpt_dir = os.path.join(tempfile.mkdtemp(prefix="rs_e2e_"), "ckpt")
eng = CheckpointEngine(ckpt_dir, job_name="rs-e2e")
eng.save_to_storage(1, state)
assert eng.wait(120), "checkpoint never committed"

# Live reshard attempt with a REAL cross-peer pull: this process holds
# rank 0's half locally; "rank 1"'s half is served over the reshard RPC
# (same wire path a multi-host move takes) — and the chaos plan drops
# exactly one segment on that wire.
tensors, infos = flatten_to_shards(state)
keys = sorted(tensors)
assert len(keys) == 2, keys
(k0, k1) = keys
src_infos = {0: {k0: infos[k0]}, 1: {k1: infos[k1]}}
server = ReshardPeer(rank=1)
server.publish(1, 1, {k1: tensors[k1]}, {k1: infos[k1]})
puller = ReshardPeer(rank=0)
target = target_placeholders(state, mesh4)
try:
    new_state, _stats = reshard_shards(
        {k0: tensors[k0]}, {k0: infos[k0]}, target,
        rank=0, src_infos_by_rank=src_infos,
        fetch=lambda seg: puller.fetch_segment(
            seg, epoch=1, step=1, addr=server.addr
        ),
        epoch=1,
    )
    print("LIVE_RESHARD_OK (chaos did not fire?)")
    sys.exit(3)
except ReshardError as e:
    print(f"LIVE_FAILED: {e}")
finally:
    server.stop()
    puller.stop()

# The ladder: restore the committed checkpoint onto the NEW mesh and
# resume stepping — the correctness backstop the live path fell back to.
got = eng.load(target, target_mesh=mesh4)
assert got is not None, "ladder restore found nothing"
restored, meta = got
np.testing.assert_array_equal(np.asarray(restored["w"]), host + 1.0)
restored = step_fn(restored)
jax.block_until_ready(restored)
np.testing.assert_array_equal(np.asarray(restored["w"]), host + 2.0)
print(f"LADDER_RESTORED step={int(meta.get('step', -1))} resumed_on="
      f"{restored['w'].sharding.mesh.shape['fsdp']}dev")
eng.close()

rc = fsck_mod.main([ckpt_dir])
print(f"fsck_rc={rc}")
print("DONE")
sys.exit(0 if rc == 0 else 4)
"""

    def test_drop_segment_degrades_to_restart_ladder(
        self, cpu_mesh_subprocess
    ):
        proc = cpu_mesh_subprocess(
            self.DRIVER,
            devices=4,
            env_extra={
                "DLROVER_TPU_FAULTS": "reshard.drop_segment:times=1,seed=9",
            },
            timeout=300,
        )
        out = proc.stdout
        assert proc.returncode == 0, (out[-3000:], proc.stderr[-3000:])
        assert "LIVE_FAILED" in out and "dropped" in out, out[-2000:]
        assert "LADDER_RESTORED step=1 resumed_on=4dev" in out
        assert "fsck_rc=0" in out
        assert "DONE" in out


@pytest.mark.serving
class TestDisaggKillMidHandoff:
    """ISSUE 8 acceptance e2e: a prefill replica is chaos-killed in
    the kill-mid-handoff window — AFTER taking a prefill-grant and
    producing the KV segment, BEFORE the kv-ready reaches the gateway
    (``serving.replica_kill:method=prefill_export``).  The gateway's
    lease machinery re-dispatches the prefill to the surviving prefill
    replica, the decode pool imports the re-shipped segment, and every
    request completes EXACTLY once: the journal/dedupe contracts keyed
    by req_id make the replay clean (resubmits answer byte-identically
    from the cache; the completed counter equals the request count)."""

    def _spawn(self, tmp_path, name, argv, env_extra=None):
        log = open(tmp_path / f"{name}.log", "w")
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "examples", "llama_serve_fleet.py"),
             *argv],
            cwd=REPO, env=_env(env_extra), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        return proc, tmp_path / f"{name}.log"

    def test_prefill_kill_replays_and_completes_exactly_once(
            self, tmp_path):
        from dlrover_tpu.common.messages import (
            ServeFleetStats,
            ServeFleetStatsRequest,
        )
        from dlrover_tpu.common.rpc import RpcClient, find_free_port
        from dlrover_tpu.serving import ServeClient

        port = find_free_port()
        journal_dir = str(tmp_path / "journals")
        procs = []
        gw_proc, gw_log = self._spawn(
            tmp_path, "gateway",
            ["--role", "gateway", "--port", str(port),
             "--lease_timeout", "3"],
        )
        procs.append(gw_proc)

        def spawn_replica(rid, role, faults=None):
            extra = {"DLROVER_TPU_FAULTS": faults} if faults else None
            proc, log = self._spawn(
                tmp_path, f"replica-{rid}",
                ["--role", "replica", "--gateway",
                 f"127.0.0.1:{port}", "--replica_id", rid,
                 "--replica_role", role,
                 "--slots", "2", "--max_len", "64",
                 "--journal_dir", journal_dir,
                 "--poll_interval", "0.02",
                 "--round_floor_ms", "20"],
                env_extra=extra,
            )
            procs.append(proc)
            return proc, log

        try:
            # p0 dies exporting its FIRST KV segment (the window
            # between prefill-grant and decode-grant); p1 survives.
            p0, p0_log = spawn_replica(
                "p0", "prefill",
                faults="serving.replica_kill:method=prefill_export",
            )
            p1, _ = spawn_replica("p1", "prefill")
            d0, _ = spawn_replica("d0", "decode")
            rpc = RpcClient(f"127.0.0.1:{port}", timeout=10.0)

            def fleet_stats():
                reply = rpc.call(ServeFleetStatsRequest(),
                                 idempotent=True)
                assert isinstance(reply, ServeFleetStats), reply
                return reply.stats

            deadline = time.time() + 180
            while time.time() < deadline:
                try:
                    if fleet_stats()["replicas_alive"] >= 3:
                        break
                except Exception:
                    pass
                time.sleep(0.5)
            else:
                raise AssertionError(
                    "fleet never formed: " + _read(gw_log)[-2000:]
                )

            client = ServeClient(rpc, poll_interval=0.05)
            n_req = 8
            prompts = [[(5 * i + j) % 50 + 1 for j in range(5)]
                       for i in range(n_req)]
            budgets = [6 + (i % 5) for i in range(n_req)]
            for i, prompt in enumerate(prompts):
                ack = client.submit(f"req-{i}", prompt, budgets[i])
                assert ack.status in ("accepted", "done"), ack

            # The chaos kill lands in the handoff window: p0 exits 78.
            rc0 = p0.wait(timeout=120)
            assert rc0 == 78, _read(p0_log)[-2000:]

            results = {}
            for i in range(n_req):
                reply = client.result(f"req-{i}", timeout=150)
                assert reply.state == "done", (
                    f"req-{i}: {reply.state} {reply.reason}; gateway: "
                    + _read(gw_log)[-2000:]
                )
                results[i] = list(reply.tokens)
                assert len(results[i]) == budgets[i]

            stats = fleet_stats()
            c = stats["counters"]
            # Exactly once at the gateway, despite the mid-handoff
            # kill: no loss, no double-complete, and the killed
            # prefill's work really was re-dispatched.
            assert c["completed"] == n_req, c
            assert c["failed"] == 0 and c["timeout"] == 0, c
            assert c["redispatched"] >= 1, c
            assert c["kv_handoffs"] >= n_req, c
            assert c["duplicate_completions"] == 0, c

            # Client-visible exactly-once: resubmits answer from the
            # dedupe cache, byte-identical, with no second decode.
            for i in range(n_req):
                ack = client.submit(f"req-{i}", prompts[i], budgets[i])
                assert ack.status == "done", ack
                assert list(ack.tokens) == results[i]
            assert fleet_stats()["counters"]["completed"] == n_req

            # The decode journal replays across a decode-replica
            # restart: kill d0, relaunch on the same journal; its
            # replay reports dedupe instead of double-completing.
            d0.send_signal(signal.SIGKILL)
            d0.wait(timeout=30)
            d0b, _ = spawn_replica("d0", "decode")
            deadline = time.time() + 90
            while time.time() < deadline:
                if fleet_stats()["counters"][
                        "duplicate_completions"] >= 1:
                    break
                time.sleep(0.5)
            c = fleet_stats()["counters"]
            assert c["duplicate_completions"] >= 1, c
            assert c["completed"] == n_req, c
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()


@pytest.mark.serving
class TestGatewayKillFailover:
    """ISSUE 9 flagship: a SHARDED gateway tier under a real process
    tree — registry server in-test, two tier gateways and two
    journaled replicas as subprocesses, a consistent-hash TierClient
    driver.

    ``serving.gateway_kill:method=g1,step_ge=2`` hard-kills gateway g1
    (exit 81) at its first registry heartbeat after two requests
    COMPLETED at it — deterministically mid-stream, seeded, no
    wall-clock guess.  The failover law under test: g1's lease ages
    out of the shared registry, the ring re-forms so the surviving
    gateway adopts g1's hash range, the client resubmits every id it
    never saw a result for, the replicas' fan-out link re-registers
    and re-routes reports — and every admitted request completes
    EXACTLY once: results for g1's orphaned ids arrive via the
    adopting gateway (journal replay answering for already-decoded
    work), and a second resubmit round returns byte-identical tokens
    from the dedupe cache."""

    def _spawn(self, tmp_path, name, argv, env_extra=None):
        log = open(tmp_path / f"{name}.log", "w")
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "examples", "llama_serve_fleet.py"),
             *argv],
            cwd=REPO, env=_env(env_extra), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        return proc, tmp_path / f"{name}.log"

    def test_surviving_gateway_adopts_range_exactly_once(
            self, tmp_path):
        from dlrover_tpu import obs
        from dlrover_tpu.chaos.plan import EXIT_GATEWAY_KILL
        from dlrover_tpu.serving import (
            RegistryServer,
            RpcKv,
            ServeRegistry,
            TierClient,
        )

        registry_server = RegistryServer()
        journal_dir = str(tmp_path / "journals")
        # Flight-recorder dumps (ISSUE 12): every role spills here —
        # g1 via the chaos pre-exit hook, g0/replicas at shutdown, the
        # in-test driver explicitly — and the trace-verified
        # assertions after teardown merge them.
        obs_dir = str(tmp_path / "obs")
        obs.configure(out_dir=obs_dir, process="driver")
        procs = []
        try:
            def spawn_gateway(gid, faults=None):
                extra = {"DLROVER_TPU_OBS_DIR": obs_dir}
                if faults:
                    extra["DLROVER_TPU_FAULTS"] = faults
                proc, log = self._spawn(
                    tmp_path, f"gateway-{gid}",
                    ["--role", "gateway", "--registry",
                     registry_server.addr, "--gateway_id", gid,
                     "--lease_timeout", "2"],
                    env_extra=extra,
                )
                procs.append(proc)
                return proc, log

            g0, _g0_log = spawn_gateway("g0")
            g1, _g1_log = spawn_gateway(
                "g1", "serving.gateway_kill:method=g1,step_ge=2,seed=7"
            )

            def spawn_replica(rid):
                proc, log = self._spawn(
                    tmp_path, f"replica-{rid}",
                    ["--role", "replica", "--registry",
                     registry_server.addr, "--lease_timeout", "2",
                     "--replica_id", rid,
                     "--slots", "2", "--max_len", "96",
                     "--journal_dir", journal_dir,
                     "--poll_interval", "0.02",
                     "--round_floor_ms", "30"],
                    env_extra={"DLROVER_TPU_OBS_DIR": obs_dir},
                )
                procs.append(proc)
                return proc, log

            spawn_replica("r0")
            spawn_replica("r1")

            registry = ServeRegistry(
                RpcKv(registry_server.addr), job="fleet", lease_s=2.0,
            )
            cli = TierClient(registry, poll_interval=0.05,
                             refresh_s=0.2)
            deadline = time.time() + 120
            while time.time() < deadline:
                snaps = cli.stats()
                if len(snaps) == 2 and all(
                    s.get("replicas_alive", 0) >= 2 for s in snaps
                ):
                    break
                time.sleep(0.5)
            else:
                pytest.fail("tier never became 2 gateways x 2 "
                            "replicas")

            # Wave 1 primes the kill trigger (g1 needs >= 2
            # completions); wave 2's longer budgets keep work in
            # flight across the death.  Prompts are the seeded
            # deterministic stream, so every decode of one id yields
            # identical tokens wherever it runs.
            import numpy as np

            rng = np.random.RandomState(3)
            prompts = {
                f"req-{i}": rng.randint(
                    1, 64, size=(int(rng.randint(4, 10)),)
                ).astype(int).tolist()
                for i in range(12)
            }
            budgets = {}
            for i, (rid, prompt) in enumerate(prompts.items()):
                budgets[rid] = 6 if i < 4 else 24
                ack = cli.submit(rid, prompt, budgets[rid],
                                 submit_timeout=30)
                assert ack.status in ("accepted", "done"), (rid, ack)
                time.sleep(0.05)

            # The chaos site must fire: g1 exits with the tier's
            # dedicated code while the fleet still holds work.
            try:
                g1.wait(timeout=90)
            except subprocess.TimeoutExpired:
                pytest.fail("gateway g1 never chaos-killed")
            assert g1.returncode == EXIT_GATEWAY_KILL

            # Every admitted request reaches DONE through the
            # survivor; ids orphaned at g1 arrive via failover
            # resubmit + journal replay/dedupe.
            tokens = {}
            for rid in prompts:
                reply = cli.result(rid, timeout=120)
                assert reply.state == "done", (rid, reply)
                assert len(reply.tokens) == budgets[rid], rid
                tokens[rid] = list(reply.tokens)
            assert cli.resubmitted >= 1  # failover actually exercised

            # Exactly-once, proven from the outside: a full resubmit
            # round answers every id from the dedupe cache,
            # byte-identical — nothing re-decodes, nothing is lost.
            snaps = cli.stats()
            assert len(snaps) == 1  # only the survivor remains
            completed_before = snaps[0]["counters"]["completed"]
            for rid, prompt in prompts.items():
                ack = cli.submit(rid, prompt, budgets[rid],
                                 submit_timeout=30)
                assert ack.status == "done", (rid, ack)
                assert list(ack.tokens) == tokens[rid], rid
            after = cli.stats()[0]["counters"]
            assert after["completed"] == completed_before
            assert after["dedupe_hits"] >= len(prompts)
            assert g0.poll() is None  # the survivor is still up
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            registry_server.stop()

        # ---- Trace-verified epilogue (ISSUE 12) -----------------------
        # Every process has now spilled its flight recorder: g1 via the
        # chaos pre-exit hook, g0 via its clean-shutdown atexit, the
        # replicas via the SIGTERM hook — and the in-test driver here.
        from dlrover_tpu.obs import collect
        from dlrover_tpu.obs.postmortem import analyze
        from dlrover_tpu.utils.trace_analysis import TraceAnalysis

        obs.get_recorder().dump(reason="exit")
        dumps = collect.load_dir(obs_dir)
        by_proc = {d["meta"]["process"]: d["meta"] for d in dumps}
        # The kill is VISIBLE: a dump whose header names the injected
        # chaos site, from the dead gateway itself.
        assert by_proc["gw-g1"]["reason"] == "chaos", by_proc
        assert by_proc["gw-g1"]["chaos_site"] == \
            "serving.gateway_kill"
        assert "gw-g0" in by_proc and "driver" in by_proc
        assert any(p.startswith("rep-") for p in by_proc)
        # One merged, Perfetto-loadable fleet trace; the repo's own
        # chrome-trace tooling consumes it.
        merged_path = str(tmp_path / "fleet_trace.json")
        collect.write_chrome_trace(obs_dir, merged_path)
        ta = TraceAnalysis.from_file(merged_path)
        assert ta.events, "merged chrome trace holds no spans"
        # Every admitted request: a complete span tree ending in
        # exactly one EFFECTIVE terminal (a journal replay at the
        # adopting gateway may supersede the dead gateway's terminal —
        # the duplicates must AGREE, which is exactly-once evidence),
        # with the gateway's phase spans summing to the measured
        # TTFT/latency within 5%.
        rep = collect.validate_traces(dumps, tolerance=0.05)
        for rid in prompts:
            tr = rep["traces"].get(obs.trace_id_for(rid))
            assert tr is not None, f"{rid}: no trace in the merge"
            assert tr["ok"], (rid, tr)
            assert tr["state"] == "done", (rid, tr)
        # The failover is visible as resubmit spans in the ORIGINAL
        # traces (the driver's dump), never as duplicate traces.
        driver = next(d for d in dumps
                      if d["meta"]["process"] == "driver")
        resub_tids = {e.get("tid") for e in driver["events"]
                      if e.get("name") == "client.resubmit"}
        assert resub_tids, "no resubmit spans recorded"
        assert resub_tids <= {
            obs.trace_id_for(rid) for rid in prompts
        }
        # The postmortem reconstructs the incident from the dumps.
        pm = analyze(obs_dir)
        assert pm["crashed"] == ["gw-g1"]
        assert pm["chaos_sites"] == ["serving.gateway_kill"]
        assert any(r["terminal_process"] in ("gw-g0", "gw-g1")
                   for r in pm["rerouted"]) or pm["rerouted"] == []


@pytest.mark.serving
@pytest.mark.fleet
class TestFleetGatewayRelaunchMixed:
    """ISSUE 10 acceptance e2e: ONE fleet — training workers (a real
    job manager over the in-memory platform, the control-plane-only
    worker pattern scenario 2 uses) AND a serving role (two subprocess
    tier gateways + two journaled subprocess replicas) — under one
    FleetManager.

    ``serving.gateway_kill:method=g1,step_ge=2`` hard-kills gateway g1
    (exit 81) after two completions with work still in flight.  Where
    the ISSUE-9 e2e proved the tier merely SURVIVES (survivors adopt
    the range), the law here is SUPERVISED REPLACEMENT: the fleet
    reconciler observes the lease lapse, relaunches the gateway under
    the SAME id (so the replacement re-adopts exactly the dead hash
    ranges), desired count is restored — and every in-flight request
    still completes exactly once, with the training role untouched by
    the churn."""

    def _spawn(self, tmp_path, name, argv, env_extra=None):
        log = open(tmp_path / f"{name}.log", "w")
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "examples", "llama_serve_fleet.py"),
             *argv],
            cwd=REPO, env=_env(env_extra), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        return proc, tmp_path / f"{name}.log"

    def test_supervisor_replaces_killed_gateway_exactly_once(
            self, tmp_path):
        import threading

        from dlrover_tpu.chaos.plan import EXIT_GATEWAY_KILL
        from dlrover_tpu.common.constants import NodeType
        from dlrover_tpu.fleet import (
            FleetManager,
            GatewayRole,
            RoleSpec,
            TrainingRole,
        )
        from dlrover_tpu.master.dist_job_manager import (
            DistributedJobManager,
        )
        from dlrover_tpu.master.job_auto_scaler import (
            AllreduceTrainingAutoScaler,
        )
        from dlrover_tpu.master.scaler import PlatformScaler
        from dlrover_tpu.master.speed_monitor import SpeedMonitor
        from dlrover_tpu.scheduler.job import JobArgs, NodeGroupArgs
        from dlrover_tpu.scheduler.platform import InMemoryPlatform
        from dlrover_tpu.serving import (
            HashRing,
            RegistryServer,
            RpcKv,
            ServeRegistry,
            TierClient,
        )

        registry_server = RegistryServer()
        journal_dir = str(tmp_path / "journals")
        procs = []
        gw_launches = {}  # gid -> [proc, ...] in launch order
        mu = threading.Lock()

        def spawn_gateway(gid):
            with mu:
                first = gid not in gw_launches
                n = len(gw_launches.setdefault(gid, [])) + 1
            faults = (
                "serving.gateway_kill:method=g1,step_ge=2,seed=7"
                if gid == "g1" and first else None
            )
            extra = {"DLROVER_TPU_FAULTS": faults} if faults else None
            proc, _log = self._spawn(
                tmp_path, f"gateway-{gid}-{n}",
                ["--role", "gateway", "--registry",
                 registry_server.addr, "--gateway_id", gid,
                 "--lease_timeout", "2"],
                env_extra=extra,
            )
            with mu:
                gw_launches[gid].append(proc)
                procs.append(proc)
            return proc

        # -- the ONE fleet: training role + supervised gateway role.
        job_args = JobArgs(job_name="fleet")
        job_args.node_groups[NodeType.WORKER] = NodeGroupArgs(
            count=2, min_count=1, max_count=4
        )
        platform = InMemoryPlatform()
        jm = DistributedJobManager(
            job_args, platform, PlatformScaler("fleet", platform)
        )
        jm.start()
        scaler = AllreduceTrainingAutoScaler(
            job_args, jm, SpeedMonitor(), None
        )
        fleet = FleetManager(interval=0.5)
        fleet.add_role(TrainingRole(
            RoleSpec("training", desired=2, min_count=1, max_count=4),
            scaler, jm,
        ))
        fleet.add_role(GatewayRole(
            RoleSpec("gateway", desired=2, min_count=1, max_count=3),
            ServeRegistry(RpcKv(registry_server.addr), job="fleet",
                          lease_s=2.0),
            spawn_gateway, id_prefix="g",
        ))

        def spawn_replica(rid):
            proc, log = self._spawn(
                tmp_path, f"replica-{rid}",
                ["--role", "replica", "--registry",
                 registry_server.addr, "--lease_timeout", "2",
                 "--replica_id", rid,
                 "--slots", "2", "--max_len", "96",
                 "--journal_dir", journal_dir,
                 "--poll_interval", "0.02",
                 "--round_floor_ms", "30"],
            )
            procs.append(proc)
            return proc, log

        try:
            fleet.start()  # spawns g0 + g1 on the first pass
            spawn_replica("r0")
            spawn_replica("r1")

            registry = ServeRegistry(
                RpcKv(registry_server.addr), job="fleet", lease_s=2.0,
            )
            cli = TierClient(registry, poll_interval=0.05,
                             refresh_s=0.2)
            deadline = time.time() + 120
            while time.time() < deadline:
                snaps = cli.stats()
                if len(snaps) == 2 and all(
                    s.get("replicas_alive", 0) >= 2 for s in snaps
                ):
                    break
                time.sleep(0.5)
            else:
                pytest.fail("fleet never became 2 gateways x 2 "
                            "replicas")
            assert len(jm.alive_workers()) == 2  # training side is up

            import numpy as np

            rng = np.random.RandomState(3)
            prompts = {
                f"req-{i}": rng.randint(
                    1, 64, size=(int(rng.randint(4, 10)),)
                ).astype(int).tolist()
                for i in range(12)
            }
            budgets = {}
            for i, (rid, prompt) in enumerate(prompts.items()):
                budgets[rid] = 6 if i < 4 else 24
                ack = cli.submit(rid, prompt, budgets[rid],
                                 submit_timeout=30)
                assert ack.status in ("accepted", "done"), (rid, ack)
                time.sleep(0.05)

            # The chaos site fires: g1's FIRST incarnation exits 81.
            g1_first = None
            deadline = time.time() + 90
            while time.time() < deadline:
                with mu:
                    launches = gw_launches.get("g1", [])
                    g1_first = launches[0] if launches else None
                if g1_first is not None and \
                        g1_first.poll() is not None:
                    break
                time.sleep(0.5)
            assert g1_first is not None and \
                g1_first.returncode == EXIT_GATEWAY_KILL, (
                    "gateway g1 never chaos-killed"
                )

            # SUPERVISED REPLACEMENT: the reconciler relaunches g1
            # under its own id; the registry shows the full desired
            # set again (not merely the survivor adopting the range).
            deadline = time.time() + 60
            while time.time() < deadline:
                with mu:
                    relaunched = len(gw_launches.get("g1", [])) >= 2
                if set(registry.gateways()) == {"g0", "g1"} \
                        and relaunched:
                    break
                time.sleep(0.5)
            assert set(registry.gateways()) == {"g0", "g1"}, (
                "gateway count never returned to desired"
            )
            with mu:
                assert len(gw_launches["g1"]) >= 2  # real relaunch

            # Every in-flight request completes EXACTLY once across
            # the death + replacement.
            tokens = {}
            for rid in prompts:
                reply = cli.result(rid, timeout=120)
                assert reply.state == "done", (rid, reply)
                assert len(reply.tokens) == budgets[rid], rid
                tokens[rid] = list(reply.tokens)

            # Exactly-once proven from outside: a full resubmit round
            # answers byte-identical from journals/dedupe caches.
            for rid, prompt in prompts.items():
                ack = cli.submit(rid, prompt, budgets[rid],
                                 submit_timeout=30)
                assert ack.status == "done", (rid, ack)
                assert list(ack.tokens) == tokens[rid], rid

            # The replacement really OWNS the re-adopted ranges: a
            # fresh request consistent-hashed to g1 completes there.
            ring = HashRing(["g0", "g1"])
            extra_rid = next(
                f"extra-{i}" for i in range(1000)
                if ring.owner(f"extra-{i}") == "g1"
            )
            ack = cli.submit(extra_rid, [1, 2, 3, 4], 6,
                             submit_timeout=30)
            assert ack.status in ("accepted", "done")
            reply = cli.result(extra_rid, timeout=60)
            assert reply.state == "done"

            # The training role rode through the serving churn.
            assert len(jm.alive_workers()) == 2
            status = fleet.status()
            assert status["roles"]["gateway"]["desired"] == 2
        finally:
            fleet.stop()
            jm.stop()
            with mu:
                all_procs = list(procs)
            for proc in all_procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in all_procs:
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            registry_server.stop()


class TestMasterKillWarmFailover:
    """Flagship ISSUE 13 scenario: training + serving fleet in flight,
    the PRIMARY master is chaos-SIGKILLed (``master.kill``, exit 83 —
    the unclean death, distinct from the supervised ``master.restart``
    cold path) mid-rendezvous and mid-task.  The warm standby replays
    the control-state journal and takes over; the proof obligations:

    - no data-shard task is lost or double-completed across the
      blackout (held doing tasks complete exactly once, the rest of the
      queue drains with every task id granted exactly once);
    - the half-formed rendezvous (node 0 waiting, node 1 absent)
      completes on the NEW master when node 1 finally joins;
    - the in-flight reshard epoch resolves (DONE after both workers
      report ok post-takeover);
    - the master-backed serving registry never observes a blank master
      (the gateway entry is visible at the first post-takeover read),
      and every serving request submitted across the window finishes
      exactly-once;
    - ``statecheck`` exits 0 on the surviving journal.
    """

    @pytest.mark.ha
    def test_training_and_serving_ride_warm_takeover(self, tmp_path):
        import threading

        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.rpc import addr_connectable
        from dlrover_tpu.master.state import read_addr
        from dlrover_tpu.serving import (
            GatewayConfig,
            GatewayCore,
            LoopbackTransport,
            ReplicaRunner,
        )
        from dlrover_tpu.serving.tier import MasterKv, ServeRegistry

        job = "hakill"
        state_dir = tmp_path / "state"
        state_dir.mkdir()

        def start_master_proc(extra_args, faults, log_name, extra_env=None):
            env = _env({"DLROVER_TPU_FAULTS": faults} if faults else None)
            if extra_env:
                env.update(extra_env)
            env.pop("DLROVER_TPU_MASTER_STATE_DIR", None)
            port_file = tmp_path / f"{log_name}.port"
            log = open(tmp_path / f"{log_name}.log", "w")
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.master.main",
                    "--port=0", f"--port_file={port_file}",
                    f"--job_name={job}", "--min_nodes=2", "--max_nodes=2",
                    f"--state_dir={state_dir}", *extra_args,
                ],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            )
            deadline = time.time() + 60
            while time.time() < deadline:
                if port_file.exists() and port_file.read_text().strip():
                    return proc, f"127.0.0.1:{port_file.read_text().strip()}"
                assert proc.poll() is None, (
                    f"{log_name} died rc={proc.returncode}:\n"
                    + _read(tmp_path / f"{log_name}.log")[-3000:]
                )
                time.sleep(0.2)
            raise TimeoutError(f"{log_name} never reported a port")

        # Primary: chaos-killed ~7s after its import (setup below takes
        # ~2-3s, so the kill lands with tasks doing, a reshard epoch
        # PREPARING, node 0 alone in the waiting set, and serving
        # traffic mid-stream).
        primary, paddr = start_master_proc(
            [], "master.kill:at=7s", "primary"
        )
        standby, saddr = start_master_proc(
            ["--standby", f"--primary_addr={paddr}"], None, "standby",
            extra_env={
                "DLROVER_TPU_HA_LEASE_S": "1.5",
                "DLROVER_TPU_HA_TAIL_POLL_S": "0.1",
            },
        )
        procs = [primary, standby]

        class FakeServer:
            """Deterministic arithmetic decode over the real
            ReplicaRunner protocol (token i = (sum(prompt)+i) % 97)."""

            def __init__(self, slots=4):
                self.slots = slots
                self._pending = []
                self._active = {}
                self.last_stats = {}

            def submit(self, rid, prompt, mnt, prefix_len=0, prefix_fp=""):
                self._pending.append((rid, [int(t) for t in prompt],
                                      int(mnt)))

            def cancel(self, rid):
                before = len(self._pending)
                self._pending = [p for p in self._pending if p[0] != rid]
                return len(self._pending) < before

            def abort(self, rid):
                return self.cancel(rid) or \
                    self._active.pop(rid, None) is not None

            def pending_count(self):
                return len(self._pending)

            def pending_rids(self):
                return [r for r, _, _ in self._pending]

            def active_rids(self):
                return list(self._active)

            def free_slots(self):
                return max(
                    0, self.slots - len(self._active) - len(self._pending)
                )

            def serve_incremental(self, tick=None, on_finish=None,
                                  on_token=None, idle_wait=0.0005):
                while True:
                    if tick is not None and tick() is False:
                        return {}
                    while self._pending and len(self._active) < self.slots:
                        rid, p, mnt = self._pending.pop(0)
                        self._active[rid] = (p, mnt)
                    for rid in list(self._active):
                        p, mnt = self._active.pop(rid)
                        new = [(sum(p) + i) % 97 for i in range(mnt)]
                        if on_finish is not None:
                            # Contract: the full sequence (prompt echoed
                            # + new tokens); the runner strips the echo.
                            on_finish(rid, list(p) + new)
                    time.sleep(idle_wait)

        hb_stop = threading.Event()
        clients = []
        try:
            c0 = MasterClient(paddr, 0, state_dir=str(state_dir))
            c1 = MasterClient(paddr, 1, state_dir=str(state_dir))
            clients += [c0, c1]
            for nid, c in ((0, c0), (1, c1)):
                c.register_node(node_rank=nid, host="127.0.0.1",
                                agent_port=9100 + nid, local_world_size=1)
            # Mid-rendezvous: ONLY node 0 joins pre-kill.
            c0.join_rendezvous(node_rank=0, local_world_size=1)
            # Data sharding: 12 shards; 2 completed, 2 HELD doing
            # across the kill.
            c0.report_dataset_shard_params(
                dataset_name="ds", dataset_size=120, shard_size=10
            )
            granted_ids = []
            pre = [c0.get_task("ds") for _ in range(4)]
            granted_ids += [t.task_id for t in pre]
            assert all(t.task_id >= 0 for t in pre)
            c0.report_task_result("ds", pre[0].task_id, True)
            c0.report_task_result("ds", pre[1].task_id, True)
            held = pre[2:]
            # In-flight reshard epoch.
            epoch_info = c0.announce_reshard(
                2, {"dp": 2}, expected_reports=2, deadline_s=120.0
            )
            epoch = epoch_info.epoch
            assert epoch >= 1 and epoch_info.status == "preparing"
            # Serving: master-backed registry + a real loopback fleet.
            reg_client = MasterClient(paddr, 9, state_dir=str(state_dir))
            clients.append(reg_client)
            registry = ServeRegistry(MasterKv(reg_client), job=job,
                                     lease_s=60.0)
            registry.announce_gateway("g0", "127.0.0.1:7777")

            def heartbeat():
                while not hb_stop.wait(0.5):
                    try:
                        registry.announce_gateway("g0", "127.0.0.1:7777")
                    except Exception:  # noqa: BLE001 - blackout window
                        pass

            threading.Thread(target=heartbeat, daemon=True).start()

            core = GatewayCore(GatewayConfig())
            transport = LoopbackTransport(self._core_handle(core))
            runner = ReplicaRunner(
                FakeServer(), transport, "rep0", poll_interval=0.005,
            )
            threading.Thread(target=runner.run, daemon=True).start()
            serve_ids = []
            serve_stop = threading.Event()

            def submit_loop():
                i = 0
                while not serve_stop.wait(0.15):
                    rid = f"s{i}"
                    core.submit(rid, [i + 1, i + 2], 4)
                    serve_ids.append(rid)
                    i += 1

            threading.Thread(target=submit_loop, daemon=True).start()

            # --- the kill -------------------------------------------------
            rc = primary.wait(timeout=90)
            assert rc == 83, (
                f"primary exited {rc}, wanted chaos master.kill 83:\n"
                + _read(tmp_path / "primary.log")[-3000:]
            )
            t_kill = time.monotonic()
            deadline = time.time() + 60
            while time.time() < deadline:
                if read_addr(str(state_dir)) == saddr and \
                        addr_connectable(saddr, timeout=0.5):
                    break
                assert standby.poll() is None, (
                    "standby died:\n"
                    + _read(tmp_path / "standby.log")[-3000:]
                )
                time.sleep(0.2)
            assert read_addr(str(state_dir)) == saddr, (
                "no takeover observed:\n"
                + _read(tmp_path / "standby.log")[-3000:]
            )
            blackout_s = time.monotonic() - t_kill
            # The registry never observes a blank master: the FIRST
            # post-takeover read shows the journaled gateway entry.
            fresh = MasterClient(saddr, 8)
            clients.append(fresh)
            gws = ServeRegistry(MasterKv(fresh), job=job,
                                lease_s=60.0).gateways()
            assert "g0" in gws, f"blank registry after takeover: {gws}"

            # Held doing tasks complete EXACTLY once on the new master.
            for t in held:
                c0.report_task_result("ds", t.task_id, True)
            # Node 1 finally joins: the half-formed round completes on
            # the standby (its waiting set replayed node 0).
            c1.join_rendezvous(node_rank=1, local_world_size=1)
            world = {}
            deadline = time.time() + 60
            while time.time() < deadline and len(world) != 2:
                _, _, world, coord = c0.get_comm_world()
                time.sleep(0.2)
            assert len(world) == 2, "rendezvous never completed"
            node_ids = sorted(w["node_id"] for w in world.values())
            assert node_ids == [0, 1]

            # Drain the queue: every task id granted exactly once
            # fleet-wide, none lost, none double-completed.
            while True:
                t = c1.get_task("ds")
                if t.task_id < 0:
                    break
                granted_ids.append(t.task_id)
                c1.report_task_result("ds", t.task_id, True)
            assert sorted(granted_ids) == list(range(12)), granted_ids
            assert len(set(granted_ids)) == 12  # no double grants

            # The in-flight reshard epoch resolves DONE.
            assert c0.report_reshard(epoch, ok=True)
            assert c1.report_reshard(epoch, ok=True)
            assert c0.get_reshard_epoch().status == "done"

            # Serving: stop admitting, everything submitted across the
            # window finishes exactly-once with correct bytes.
            serve_stop.set()
            time.sleep(0.3)
            deadline = time.time() + 60
            while time.time() < deadline and \
                    core.counters["completed"] < len(serve_ids):
                time.sleep(0.1)
            assert core.counters["completed"] == len(serve_ids)
            assert core.counters["duplicate_completions"] == 0
            for i, rid in enumerate(serve_ids):
                st = core.status(rid)
                assert st.state == "done"
                assert st.tokens == [
                    (2 * i + 3 + k) % 97 for k in range(4)
                ]
            hb_stop.set()
            core.drain("rep0")
            print(f"WARM_FAILOVER_OK blackout_s={blackout_s:.2f} "
                  f"serving={len(serve_ids)} tasks=12")
        finally:
            hb_stop.set()
            for c in clients:
                try:
                    c.close()
                except Exception:  # noqa: BLE001 - teardown
                    pass
            _terminate(procs)
        # The surviving journal passes fsck (after the standby exited).
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.master.statecheck",
             str(state_dir)],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    @staticmethod
    def _core_handle(core):
        """Gateway.handle dispatch over a bare core (loopback)."""
        from dlrover_tpu.common import messages as m

        def handle(msg):
            if isinstance(msg, m.ServeReplicaRegister):
                core.register(msg.replica_id, msg.slots, msg.role)
            elif isinstance(msg, m.ServeReplicaDeregister):
                core.deregister(msg.replica_id)
            elif isinstance(msg, m.ServeReplicaPoll):
                return core.poll(msg.replica_id, msg.free_slots,
                                 msg.active, msg.stats, msg.warm_prefixes)
            elif isinstance(msg, m.ServeTokens):
                core.stream(msg.replica_id, msg.req_id, msg.tokens)
            elif isinstance(msg, m.ServeDone):
                core.complete(msg.replica_id, msg.req_id, msg.tokens,
                              msg.ok, msg.reason, msg.replayed)
            return None

        return handle


@pytest.mark.cells
@pytest.mark.ha
class TestCellMasterKillFailover:
    """Flagship ISSUE 15 scenario: TWO cells, each a full master with
    its own PR-13 journal + warm standby, training-shaped (data-shard
    queues) and serving-shaped (master-KV serve registry) control-plane
    load on BOTH.  Cell0's master is chaos-SIGKILLed
    (``cell.master_kill``, exit 85) mid-stream.  Proof obligations:

    - cell0's warm standby adopts the journaled state: the partly
      consumed shard queue continues exactly-once (no task id lost or
      double-granted fleet-wide), and the serving-registry entries
      announced pre-kill are visible post-takeover;
    - cell1 NEVER blacks out: its probe stream of short-budget RPCs
      shows no gap above one probe budget while cell0 fails over (the
      per-cell blackout metric beside the fleet-wide one);
    - the shared cell registry re-learns cell0 from the promoted
      standby, so the ring covers both cells again;
    - ``statecheck`` exits 0 on cell0's surviving journal.
    """

    def test_one_cell_dies_the_other_never_blacks_out(self, tmp_path):
        import json as _json
        import threading

        from dlrover_tpu import chaos as _chaos
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.cells.registry import CellRegistry
        from dlrover_tpu.common import messages as wire
        from dlrover_tpu.common.rpc import RpcClient
        from dlrover_tpu.master.state import read_addr
        from dlrover_tpu.serving.tier import RpcKv, ServeRegistry, MasterKv

        job = "cellkill"

        def start(cmd_args, log_name, extra_env=None):
            env = _env(extra_env)
            env.pop("DLROVER_TPU_MASTER_STATE_DIR", None)
            port_file = tmp_path / f"{log_name}.port"
            log = open(tmp_path / f"{log_name}.log", "w")
            proc = subprocess.Popen(
                [sys.executable, *cmd_args,
                 f"--port_file={port_file}"],
                cwd=REPO, env=env, stdout=log,
                stderr=subprocess.STDOUT,
            )
            deadline = time.time() + 60
            while time.time() < deadline:
                if port_file.exists() and port_file.read_text().strip():
                    return proc, (
                        f"127.0.0.1:{port_file.read_text().strip()}"
                    )
                assert proc.poll() is None, (
                    f"{log_name} died rc={proc.returncode}:\n"
                    + _read(tmp_path / f"{log_name}.log")[-3000:]
                )
                time.sleep(0.1)
            raise TimeoutError(f"{log_name} never reported a port")

        procs = []
        try:
            reg_proc, reg_addr = start(
                ["-m", "dlrover_tpu.cells.main", "--registry",
                 "--port", "0"],
                "registry",
            )
            procs.append(reg_proc)

            cells = {}
            for cid in ("cell0", "cell1"):
                state_dir = tmp_path / f"state_{cid}"
                state_dir.mkdir()
                base = ["-m", "dlrover_tpu.master.main", "--port=0",
                        f"--job_name={job}", "--min_nodes=1",
                        "--max_nodes=4", f"--cell_id={cid}",
                        f"--cell_registry={reg_addr}",
                        f"--state_dir={state_dir}"]
                hb_env = {"DLROVER_TPU_CELL_LEASE_S": "2.0"}
                prim_env = dict(hb_env)
                if cid == "cell0":
                    # The kill site fires in the cell heartbeat after
                    # ~4s — mid-task-queue, mid-serving-announce.
                    prim_env["DLROVER_TPU_FAULTS"] = (
                        "cell.master_kill:method=cell0,at=4s"
                    )
                primary, paddr = start(base, f"{cid}_primary",
                                       extra_env=prim_env)
                standby, saddr = start(
                    base + ["--standby", f"--primary_addr={paddr}"],
                    f"{cid}_standby",
                    extra_env={
                        **hb_env,
                        "DLROVER_TPU_HA_LEASE_S": "1.0",
                        "DLROVER_TPU_HA_TAIL_POLL_S": "0.05",
                    },
                )
                procs += [primary, standby]
                cells[cid] = {
                    "primary": primary, "standby": standby,
                    "addr": paddr, "state": str(state_dir),
                }

            # Training-shaped load: a data-shard queue per cell,
            # partly consumed pre-kill.
            tasks_per_cell = 12
            granted = {"cell0": [], "cell1": []}
            clients = {}
            for cid, ent in cells.items():
                cli = MasterClient(ent["addr"], 0,
                                   state_dir=ent["state"])
                clients[cid] = cli
                cli.report_dataset_shard_params(
                    dataset_name=f"ds-{cid}",
                    dataset_size=tasks_per_cell * 10, shard_size=10,
                )
                for _ in range(4):
                    t = cli.get_task(f"ds-{cid}")
                    granted[cid].append(t.task_id)
                cli.report_task_result(f"ds-{cid}",
                                       granted[cid][0], True)
            # Serving-shaped load: serve-registry announcements riding
            # each cell's master KV.
            for cid in cells:
                sreg = ServeRegistry(MasterKv(clients[cid]), job=job)
                sreg.announce_gateway(f"gw-{cid}", f"10.0.0.1:{cid}")
                sreg.announce_replica(f"rep-{cid}", slots=4)

            # Cell1's never-blacks-out probe: short-budget RPCs on a
            # tight loop; the max success gap IS the per-cell blackout.
            stop_probe = threading.Event()
            gaps = {"max": 0.0, "count": 0}

            def probe_cell1():
                addr = cells["cell1"]["addr"]
                last = time.monotonic()
                while not stop_probe.is_set():
                    cli = RpcClient(addr, timeout=0.5)
                    try:
                        cli.call(
                            wire.KVStoreGet(key="probe"),
                            timeout=0.5, retries=1, deadline=0.5,
                            idempotent=True,
                        )
                        now = time.monotonic()
                        gaps["max"] = max(gaps["max"], now - last)
                        gaps["count"] += 1
                        last = now
                    except Exception:  # noqa: BLE001 - counted as gap
                        pass
                    finally:
                        cli.close()
                    time.sleep(0.05)

            prober = threading.Thread(target=probe_cell1, daemon=True)
            prober.start()

            # Wait for the chaos kill (exit 85).
            rc = cells["cell0"]["primary"].wait(timeout=60)
            assert rc == _chaos.EXIT_CELL_MASTER_KILL, (
                _read(tmp_path / "cell0_primary.log")[-3000:]
            )
            t_kill = time.monotonic()
            # The standby takes over: the addr file flips.
            old = cells["cell0"]["addr"]
            deadline = time.time() + 30
            new_addr = ""
            while time.time() < deadline:
                cur = read_addr(cells["cell0"]["state"])
                if cur and cur != old:
                    new_addr = cur
                    break
                time.sleep(0.1)
            assert new_addr, "cell0 standby never took over"

            # Drain cell0's queue through the failover-aware client:
            # every remaining task id granted exactly once.
            cli0 = clients["cell0"]
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    t = cli0.get_task("ds-cell0")
                except Exception:  # noqa: BLE001 - blackout window
                    time.sleep(0.2)
                    continue
                if t.task_id < 0:
                    break
                granted["cell0"].append(t.task_id)
            ids0 = granted["cell0"]
            assert sorted(ids0) == list(range(tasks_per_cell)), ids0
            assert len(set(ids0)) == len(ids0), "task double-granted"

            # The pre-kill serving registry survived into the new
            # leader (journaled KV writes replayed).
            sreg0 = ServeRegistry(MasterKv(cli0), job=job)
            assert f"gw-cell0" in sreg0.gateways()
            assert f"rep-cell0" in sreg0.replicas()

            # Cell1 never blacked out, and drains its own queue too.
            stop_probe.set()
            prober.join(timeout=5)
            assert gaps["count"] > 10
            assert gaps["max"] < 1.0, (
                f"cell1 observed a {gaps['max']:.2f}s gap"
            )
            cli1 = clients["cell1"]
            while True:
                t = cli1.get_task("ds-cell1")
                if t.task_id < 0:
                    break
                granted["cell1"].append(t.task_id)
            assert sorted(granted["cell1"]) == \
                list(range(tasks_per_cell))

            # The shared registry re-learned cell0 from the promoted
            # standby: the ring covers both cells again.
            creg = CellRegistry(RpcKv(reg_addr), job=job, lease_s=2.0)
            deadline = time.time() + 20
            live = {}
            while time.time() < deadline:
                live = creg.cells()
                if set(live) == {"cell0", "cell1"} and \
                        live["cell0"]["addr"] == new_addr:
                    break
                time.sleep(0.2)
            assert set(live) == {"cell0", "cell1"}, live
            assert live["cell0"]["addr"] == new_addr

            for cli in clients.values():
                cli.close()

            # The surviving journal is statecheck-clean.
            check = subprocess.run(
                [sys.executable, "-m",
                 "dlrover_tpu.master.statecheck",
                 cells["cell0"]["state"], "--json"],
                capture_output=True, text=True, timeout=120,
                cwd=REPO, env=_env(),
            )
            assert check.returncode == 0, check.stdout + check.stderr
            report = _json.loads(check.stdout)
            assert report["damage"] == []
        finally:
            _terminate(procs)
