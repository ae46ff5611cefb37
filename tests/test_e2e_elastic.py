"""End-to-end elastic training tests: tpurun -> master -> agent -> workers.

The flagship system test (SURVEY.md §4 "system tests"): a real process tree
on one host, 2 worker processes forming a 4-device JAX world over CPU, with
a mid-run worker SIGKILL exercising failure detection, breakpoint save,
re-rendezvous and flash-checkpoint warm restore.
"""

import os
import re
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launch(tmp_path, job_name, extra_args, env_extra=None, steps=15):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "PYTHONPATH": REPO,
            # what the job leaves in the temp dir (the journals of a
            # job that was killed) goes with the test's own directory
            "TMPDIR": str(tmp_path),
        }
    )
    if env_extra:
        env.update(env_extra)
    log = open(tmp_path / "run.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.run",
            "--standalone", "--nproc_per_node=2",
            f"--job_name={job_name}",
            "--monitor_interval=1",
            os.path.join(REPO, "examples", "nanogpt_train.py"),
            "--", f"--steps={steps}", *extra_args,
        ],
        cwd=REPO,
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
    )
    return proc, tmp_path / "run.log"


def _read(path):
    with open(path) as f:
        return f.read()


@pytest.mark.e2e
class TestEndToEnd:
    def test_happy_path(self, tmp_path):
        proc, log = _launch(tmp_path, "e2e-happy", [], steps=8)
        rc = proc.wait(timeout=420)
        content = _read(log)
        assert rc == 0, content[-3000:]
        assert content.count("TRAIN_DONE step=8") == 2, content[-3000:]
        assert "jax.distributed up: process 0/2" in content
        # the job had a flight-recorder journal without asking for one,
        # and a job that ends well leaves none behind
        m = re.search(r"flight recorder journals: (\S+)", content)
        assert m and m.group(1).startswith(
            str(tmp_path / "dlrover_tpu_obs" / "e2e-happy-")), content[:2000]
        assert not os.path.exists(m.group(1))

    def test_kill_worker_restore(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        obs_dir = str(tmp_path / "obs")
        proc, log = _launch(
            tmp_path, "e2e-kill",
            [f"--ckpt_dir={ckpt_dir}", "--ckpt_interval=3"],
            steps=2000,  # long enough that the kill lands mid-run
            env_extra={"DLROVER_TPU_OBS_DIR": obs_dir},
        )
        # Wait for a checkpoint to be staged (step >= 10 reported).
        deadline = time.time() + 300
        killed = False
        while time.time() < deadline:
            content = _read(log) if os.path.exists(log) else ""
            m = re.search(r"started 2 worker\(s\): pids=\[(\d+), (\d+)\]",
                          content)
            if m and re.search(r"step (1[0-9]|[2-9][0-9]) loss", content):
                os.kill(int(m.group(2)), signal.SIGKILL)
                killed = True
                break
            if proc.poll() is not None:
                pytest.fail("launcher exited early:\n" + content[-3000:])
            time.sleep(1.0)
        assert killed, "never reached a running training step"
        # Shorten the wait: once the job restores past the kill point we
        # don't need all 2000 steps — stop it after confirming restore.
        restored = False
        deadline = time.time() + 420
        while time.time() < deadline:
            content = _read(log)
            if re.search(r"restored step=\d+", content):
                restored = True
                break
            if proc.poll() is not None:
                break
            time.sleep(2.0)
        content = _read(log)
        # The kill must have been absorbed via the agent's breakpoint save
        # (staged-but-unpersisted state flushed before restarting workers).
        assert "breakpoint save" in content, content[-3000:]
        assert restored, "no restore observed:\n" + content[-3000:]
        step = int(re.search(r"restored step=(\d+)", content).group(1))
        assert step >= 3
        # And specifically the warm path: same host, staged shm state —
        # restore must come from shm, not a storage round trip.
        assert "warm restore from shm" in content, content[-3000:]
        try:
            _check_restart_journals(obs_dir)
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def _check_restart_journals(obs_dir):
    """What the flight recorder holds of the restart while the job still
    runs (nothing has been spilled: every line was written as its span
    ended): the agent's ``agent.restart`` with its three children and the
    breakpoint persist inside ``agent.stop_workers``, the killed worker's
    saves, the new incarnation's warm load."""
    from dlrover_tpu.obs.collect import load_dir

    deadline = time.time() + 60
    while time.time() < deadline:
        dumps = {d["meta"]["process"]: d["events"]
                 for d in load_dir(obs_dir)}
        done = [e for e in dumps.get("agent-n0", [])
                if e.get("name") == "agent.restart"]
        loads = [e for name, evs in dumps.items() if name.endswith("-i1")
                 for e in evs if e.get("name") == "ckpt.load"]
        if done and len(loads) == 2:
            break
        time.sleep(1.0)
    assert {"agent-n0", "worker-r0-i0", "worker-r1-i0", "worker-r0-i1",
            "worker-r1-i1"} <= set(dumps), sorted(dumps)
    agent = [e for e in dumps["agent-n0"] if e.get("k") == "span"]
    (restart,) = [e for e in agent if e["name"] == "agent.restart"]
    assert restart["args"]["reason"] == "failed"
    assert restart["args"]["restart_count"] == 1
    assert restart["args"]["exit_codes"] == [-9]
    kids = {e["name"]: e for e in agent if e.get("psid") == restart["sid"]}
    assert set(kids) == {"agent.stop_workers", "agent.rendezvous",
                         "agent.start_workers"}
    for kid in kids.values():
        assert kid["ts"] >= restart["ts"] - 1
        assert kid["ts"] + kid["dur"] <= restart["ts"] + restart["dur"] + 1
    persists = [e for e in agent if e["name"] == "ckpt.persist"
                and e.get("psid") == kids["agent.stop_workers"]["sid"]]
    assert persists and all(
        p["args"]["reason"] == "breakpoint" for p in persists)
    # the worker that was SIGKILLed left its saves behind all the same
    killed = [e for e in dumps["worker-r1-i0"]
              if e.get("name") == "ckpt.save"]
    assert killed and killed[0]["args"]["first_touch"] is True
    # Both ranks take the same branch.  (The log's "warm restore from
    # shm" line is written before the ranks agree; the span says where
    # the state really came from — storage, when the kill left the two
    # arenas a step apart.)
    assert len({ld["args"]["source"] for ld in loads}) == 1, loads
    assert loads[0]["args"]["source"] in ("shm", "storage")
    assert all(ld["args"]["step"] >= 3 for ld in loads)
    assert any(e.get("kind") == "bootstrap.process_start"
               for e in dumps["worker-r0-i1"])


def _free_port() -> int:
    from dlrover_tpu.common.rpc import find_free_port

    return find_free_port()


def _start_master(tmp_path, job_name, port, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    log = open(tmp_path / "master.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.master.main",
            f"--port={port}", f"--job_name={job_name}",
            "--min_nodes=2", "--max_nodes=2", *extra,
        ],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    return proc, tmp_path / "master.log"


def _start_node(tmp_path, job_name, master_port, node_rank, script_args,
                env_extra=None):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO,
            "TMPDIR": str(tmp_path),  # a killed node's journals go with it
        }
    )
    if env_extra:
        env.update(env_extra)
    log = open(tmp_path / f"node{node_rank}.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.run",
            "--nnodes=2", "--nproc_per_node=1",
            f"--node_rank={node_rank}",
            f"--master_addr=127.0.0.1:{master_port}",
            f"--job_name={job_name}",
            "--monitor_interval=1",
            *script_args,
        ],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    return proc, tmp_path / f"node{node_rank}.log"


@pytest.mark.e2e
class TestMultiNode:
    def test_agent_kill_node_relaunch(self, tmp_path):
        """Kill a whole NODE (its agent process), not just a worker: the
        master must evict the dead incarnation, the surviving node must
        re-rendezvous with the replacement, and training must resume from
        the flash checkpoint (VERDICT round-1 e2e matrix item)."""
        job = "e2e-agentkill"
        port = _free_port()
        ckpt = str(tmp_path / "ckpt")
        mproc, mlog = _start_master(tmp_path, job, port)
        script = [
            os.path.join(REPO, "examples", "nanogpt_train.py"),
            "--", "--steps=2000", f"--ckpt_dir={ckpt}",
            "--ckpt_interval=3", "--batch_per_proc=2",
        ]
        n0, log0 = _start_node(tmp_path, job, port, 0, script)
        n1, log1 = _start_node(tmp_path, job, port, 1, script)
        procs = [mproc, n0, n1]
        try:
            # Wait until both nodes are training (a double-digit step).
            deadline = time.time() + 420
            while time.time() < deadline:
                c1 = _read(log1) if os.path.exists(log1) else ""
                if re.search(r"step (1[0-9]|[2-9][0-9]) loss", c1):
                    break
                for p, plog, nm in (
                    (mproc, mlog, "master"),
                    (n0, log0, "node0"),
                    (n1, log1, "node1"),
                ):
                    if p.poll() is not None:
                        pytest.fail(
                            f"{nm} exited early:\n" + _read(plog)[-3000:]
                        )
                time.sleep(1.0)
            else:
                pytest.fail("never reached training:\n" + _read(log1)[-3000:])

            n1.kill()  # SIGKILL the agent: the whole node dies
            n1.wait(timeout=30)

            # Platform-relaunch stand-in: a replacement agent process for
            # the same node_rank (what the reconciler/GKE would do).
            time.sleep(3.0)
            n1b, log1b = _start_node(
                tmp_path, job, port, 1, script,
            )
            procs.append(n1b)

            resumed = False
            deadline = time.time() + 420
            while time.time() < deadline:
                c1b = _read(log1b) if os.path.exists(log1b) else ""
                if re.search(r"restored step=(\d+)", c1b) and re.search(
                    r"step \d+ loss", c1b
                ):
                    resumed = True
                    break
                if n1b.poll() is not None:
                    pytest.fail(
                        "replacement node exited:\n" + c1b[-3000:]
                    )
                time.sleep(2.0)
            c1b = _read(log1b)
            assert resumed, (
                "replacement never resumed:\nnode1b:\n" + c1b[-2500:]
                + "\nnode0:\n" + _read(log0)[-1500:]
            )
            step = int(re.search(r"restored step=(\d+)", c1b).group(1))
            assert step >= 3
            # The surviving node went through a fresh rendezvous round.
            assert re.search(r"restored step=\d+", _read(log0))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def test_network_check_flags_slow_node(self, tmp_path):
        """Pre-flight node check with an injected slow node: the paired
        benchmark must finish on both nodes and the master's straggler
        detection must flag the slow one (VERDICT round-1 item; reference
        NetworkCheckRendezvousManager straggler isolation)."""
        job = "e2e-netcheck"
        port = _free_port()
        mproc, mlog = _start_master(
            tmp_path, job, port, extra=("--network_check",)
        )
        script = [
            "--network_check",
            os.path.join(REPO, "examples", "nanogpt_train.py"),
            "--", "--steps=4", "--batch_per_proc=2",
        ]
        n0, log0 = _start_node(tmp_path, job, port, 0, script)
        n1, log1 = _start_node(
            tmp_path, job, port, 1, script,
            env_extra={"DLROVER_TPU_CHECK_DELAY_S": "3"},
        )
        procs = [mproc, n0, n1]
        try:
            rc0 = n0.wait(timeout=600)
            rc1 = n1.wait(timeout=600)
            c0, c1 = _read(log0), _read(log1)
            assert rc0 == 0, c0[-3000:]
            assert rc1 == 0, c1[-3000:]
            # Both checks ran to completion...
            assert "node check round 1" in c0
            assert "node check round 1" in c1
            # ...and the delayed node (only) was flagged as the straggler.
            assert "flagged as straggler" in c1, c1[-3000:]
            assert "flagged as straggler" not in c0, c0[-3000:]
            # The check is advisory for stragglers: training still ran.
            assert "TRAIN_DONE" in c0 and "TRAIN_DONE" in c1
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()


@pytest.mark.e2e
class TestScaleUp:
    # slow-lane (ISSUE 8 satellite): 25s, and multi-process XLA
    # collectives cannot run on this CI container anyway — the tier-1
    # budget is better spent on tests that can pass here.
    @pytest.mark.slow
    def test_node_join_grows_world(self, tmp_path):
        """Elastic scale-UP: training starts with one node (min_nodes=1),
        a second node joins mid-run, the master's waiting-list triggers a
        membership change, and training resumes as a 2-process world from
        the flash checkpoint (the allreduce auto-scaler's grow path,
        end-to-end)."""
        job = "e2e-scaleup"
        port = _free_port()
        ckpt = str(tmp_path / "ckpt")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        mlog_f = open(tmp_path / "master.log", "w")
        mproc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                f"--port={port}", f"--job_name={job}",
                "--min_nodes=1", "--max_nodes=2",
            ],
            cwd=REPO, env=env, stdout=mlog_f, stderr=subprocess.STDOUT,
        )
        mlog = tmp_path / "master.log"

        def start_node(rank):
            nenv = dict(os.environ)
            nenv.update(
                {
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                    "PYTHONPATH": REPO,
                }
            )
            log = open(tmp_path / f"node{rank}.log", "w")
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.run",
                    "--nnodes=1:2", "--nproc_per_node=1",
                    f"--node_rank={rank}",
                    f"--master_addr=127.0.0.1:{port}",
                    f"--job_name={job}", "--monitor_interval=1",
                    os.path.join(REPO, "examples", "nanogpt_train.py"),
                    # Big enough that the solo phase can't finish before
                    # the join (tiny nanogpt is ~ms/step on CPU).
                    "--", "--steps=100000", f"--ckpt_dir={ckpt}",
                    "--ckpt_interval=3", "--batch_per_proc=8",
                    "--seq_len=64",
                ],
                cwd=REPO, env=nenv, stdout=log, stderr=subprocess.STDOUT,
            )
            return proc, tmp_path / f"node{rank}.log"

        n0, log0 = start_node(0)
        procs = [mproc, n0]
        try:
            # Phase 1: single-node world training.
            deadline = time.time() + 420
            while time.time() < deadline:
                c0 = _read(log0) if os.path.exists(log0) else ""
                # A world of 1 skips jax.distributed init; the agent's
                # rendezvous log carries the world size instead.
                if (
                    "world=1 nodes" in c0
                    and re.search(r"step (1[0-9]|[2-9][0-9]) loss", c0)
                ):
                    break
                if n0.poll() is not None or mproc.poll() is not None:
                    pytest.fail("early exit:\n" + c0[-3000:]
                                + _read(mlog)[-1500:])
                time.sleep(1.0)
            else:
                pytest.fail("node0 never trained solo:\n"
                            + _read(log0)[-3000:])

            # Phase 2: node 1 joins mid-run.
            n1, log1 = start_node(1)
            procs.append(n1)
            grown = False
            deadline = time.time() + 420
            while time.time() < deadline:
                c0 = _read(log0)
                c1 = _read(log1) if os.path.exists(log1) else ""
                if (
                    "jax.distributed up: process 0/2" in c0
                    and "jax.distributed up: process 1/2" in c1
                    and re.search(r"restored step=\d+", c0)
                    and re.search(r"step \d+ loss", c1)
                ):
                    grown = True
                    break
                for p, nm in ((mproc, "master"), (n0, "node0"),
                              (n1, "node1")):
                    if p.poll() is not None:
                        pytest.fail(f"{nm} died during scale-up:\n"
                                    + c0[-2000:] + c1[-2000:])
                time.sleep(1.0)
            assert grown, (
                "world never grew to 2:\nnode0:\n" + _read(log0)[-2500:]
                + "\nnode1:\n" + (_read(log1) if os.path.exists(log1)
                                  else "")[-2500:]
            )
            # The restore carried training state across the resize.
            step = int(re.search(r"restored step=(\d+)",
                                 _read(log0)).group(1))
            assert step >= 3
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()


@pytest.mark.e2e
class TestScaleDown:
    # slow-lane (ISSUE 8 satellite): 21s, multi-process XLA collectives
    # (see TestScaleUp).
    @pytest.mark.slow
    def test_node_loss_shrinks_world(self, tmp_path):
        """Elastic scale-DOWN: two nodes train; one dies and is NOT
        replaced; with min_nodes=1 the survivor must re-rendezvous as a
        1-node world and keep training from the checkpoint."""
        job = "e2e-scaledown"
        port = _free_port()
        ckpt = str(tmp_path / "ckpt")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        # Fast failure detection so the test (and recovery) is snappy:
        # master declares a silent node dead after 20s of missed
        # heartbeats and broadcasts RESTART_WORKER to the survivors.
        env["DLROVER_TPU_NODE_HEARTBEAT_TIMEOUT"] = "20"
        mproc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                f"--port={port}", f"--job_name={job}",
                "--min_nodes=1", "--max_nodes=2",
            ],
            cwd=REPO, env=env,
            stdout=open(tmp_path / "master.log", "w"),
            stderr=subprocess.STDOUT,
        )

        def start_node(rank):
            nenv = dict(os.environ)
            nenv.update(
                {
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                    "PYTHONPATH": REPO,
                }
            )
            log = open(tmp_path / f"node{rank}.log", "w")
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "dlrover_tpu.run",
                    "--nnodes=1:2", "--nproc_per_node=1",
                    f"--node_rank={rank}",
                    f"--master_addr=127.0.0.1:{port}",
                    f"--job_name={job}", "--monitor_interval=1",
                    os.path.join(REPO, "examples", "nanogpt_train.py"),
                    "--", "--steps=100000", f"--ckpt_dir={ckpt}",
                    "--ckpt_interval=3", "--batch_per_proc=8",
                    "--seq_len=64",
                ],
                cwd=REPO, env=nenv, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,  # killpg must take the whole node
            )
            return proc, tmp_path / f"node{rank}.log"

        n0, log0 = start_node(0)
        n1, log1 = start_node(1)
        procs = [mproc, n0, n1]
        try:
            deadline = time.time() + 420
            while time.time() < deadline:
                c0 = _read(log0) if os.path.exists(log0) else ""
                if (
                    "jax.distributed up: process 0/2" in c0
                    and re.search(r"step (1[0-9]|[2-9][0-9]) loss", c0)
                ):
                    break
                for p, nm in ((mproc, "master"), (n0, "node0"),
                              (n1, "node1")):
                    if p.poll() is not None:
                        pytest.fail(f"{nm} exited early:\n" + c0[-3000:])
                time.sleep(1.0)
            else:
                pytest.fail("2-node world never trained:\n"
                            + _read(log0)[-3000:])

            # Node 1 is gone for good (spot preemption): kill its WHOLE
            # process group — agent and workers — so nothing lingers.
            os.killpg(os.getpgid(n1.pid), signal.SIGKILL)
            n1.wait(timeout=30)

            shrunk = False
            deadline = time.time() + 420
            while time.time() < deadline:
                c0 = _read(log0)
                # After the failure round the survivor re-forms a world
                # of 1 and keeps stepping (restore from shm/storage).
                tail = c0.split("jax.distributed up: process 0/2")[-1]
                if (
                    "world=1 nodes" in tail
                    and re.search(r"restored step=\d+", tail)
                    and re.search(r"step \d+ loss", tail)
                ):
                    shrunk = True
                    break
                if n0.poll() is not None or mproc.poll() is not None:
                    pytest.fail("survivor/master died:\n" + c0[-3000:])
                time.sleep(1.0)
            assert shrunk, (
                "world never shrank to 1:\n" + _read(log0)[-3000:]
            )
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()


@pytest.mark.e2e
class TestJobFileLaunch:
    # slow-lane (ISSUE 8 satellite): 20s full job-file launch (see
    # TestScaleUp).
    @pytest.mark.slow
    def test_yaml_job_file_launches_nanogpt(self, tmp_path):
        """The declarative ElasticJob YAML drives tpurun end-to-end
        (VERDICT r2 next #10): script, args, nproc and ckpt config all
        come from the file."""
        yaml_text = f"""\
apiVersion: elastic.dlrover-tpu/v1alpha1
kind: ElasticJob
metadata:
  name: e2e-yaml
spec:
  replicaSpecs:
    worker:
      replicas: 1
  template:
    script: examples/nanogpt_train.py
    args: ["--steps=8"]
    nprocPerNode: 2
  checkpoint:
    dir: {tmp_path / 'ckpt'}
    interval: 3
"""
        job_file = tmp_path / "job.yaml"
        job_file.write_text(yaml_text)
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "PYTHONPATH": REPO,
        })
        log = open(tmp_path / "run.log", "w")
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.run",
                "--standalone", "--monitor_interval=1",
                f"--job_file={job_file}",
            ],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
        )
        rc = proc.wait(timeout=420)
        content = _read(tmp_path / "run.log")
        assert rc == 0, content[-3000:]
        assert content.count("TRAIN_DONE step=8") == 2, content[-3000:]
        # ckpt config came from the YAML
        assert (tmp_path / "ckpt").exists(), content[-1500:]


@pytest.mark.e2e
class TestElasticServing:
    def test_kill_worker_mid_serving_replays_only_inflight(
        self, tmp_path
    ):
        """Serving under elasticity (beyond the reference, whose RL
        stack shells out to an unsupervised vllm): SIGKILL the serving
        worker mid-run; the agent relaunches it, the journal keeps every
        finished request, and the restarted worker replays only the
        in-flight remainder — final results byte-identical to solo
        greedy decode."""
        journal_dir = tmp_path / "journal"
        proc, log = _launch_serving(tmp_path, journal_dir)
        try:
            # Kill the worker once >=2 requests finished but the job is
            # still running (requests=12, throttled).
            deadline = time.time() + 420
            killed = False
            while time.time() < deadline:
                content = _read(log) if os.path.exists(log) else ""
                m = re.search(
                    r"started 1 worker\(s\): pids=\[(\d+)\]", content
                )
                if m and content.count("SERVED rid=") >= 2:
                    os.kill(int(m.group(1)), signal.SIGKILL)
                    killed = True
                    break
                if proc.poll() is not None:
                    pytest.fail(
                        "launcher exited early:\n" + content[-3000:]
                    )
                time.sleep(0.3)
            assert killed, (
                "never reached 2 served requests:\n"
                + _read(log)[-3000:]
            )
            deadline = time.time() + 420
            done = False
            while time.time() < deadline:
                content = _read(log)
                if "SERVE_ELASTIC_DONE" in content:
                    done = True
                    break
                if proc.poll() is not None:
                    break
                time.sleep(1.0)
            content = _read(log)
            assert done, "serving never completed:\n" + content[-3000:]
            # The restarted incarnation must have REPLAYED the journal:
            # from_journal > 0 (finished work survived the kill) and
            # served_now < 12 (not everything was redone).
            m = re.search(
                r"SERVE_ELASTIC_DONE requests=12 served_now=(\d+) "
                r"from_journal=(\d+)", content,
            )
            assert m, content[-2000:]
            served_now, from_journal = int(m.group(1)), int(m.group(2))
            assert from_journal >= 2, content[-2000:]
            assert served_now == 12 - from_journal
            rc = proc.wait(timeout=120)
            assert rc == 0, content[-2000:]
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
        # Journal-complete and byte-exact vs solo greedy decode.
        import json as _json

        import numpy as np

        recs = {}
        with open(journal_dir / "results.jsonl") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = _json.loads(line)
                except ValueError:
                    continue  # torn tail from the SIGKILL
                recs.setdefault(int(rec["rid"]), rec["tokens"])
        assert sorted(recs) == list(range(12)), sorted(recs)
        from dlrover_tpu.models import llama, llama_infer
        import jax
        import jax.numpy as jnp

        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(1)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(n),)).astype(
                np.int32
            )
            for n in rng.randint(4, 12, size=(12,))
        ]
        for rid in (0, 5, 11):  # spot-check across the set
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(prompts[rid])[None],
                max_new_tokens=48,
            ))[0]
            np.testing.assert_array_equal(
                np.asarray(recs[rid], np.int32), solo
            )


def _launch_serving(tmp_path, journal_dir):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO,
        }
    )
    log = open(tmp_path / "serve.log", "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.run",
            "--standalone", "--nproc_per_node=1",
            "--job_name=e2e-serve",
            "--monitor_interval=1",
            os.path.join(REPO, "examples", "llama_serve_elastic.py"),
            "--", "--requests=12", "--max_new_tokens=48",
            f"--journal_dir={journal_dir}", "--throttle_s=1.0",
        ],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    return proc, tmp_path / "serve.log"
