"""The rules that let the program run on a chip, checked on the CPU.

A chip belongs to one process at a time, JAX on a chip host fails or hangs
where it is free on the CPU, and GSPMD cannot partition a Mosaic kernel — none
of which a CPU test run can feel.  So the rules are pinned here as behaviour:
the agent asks JAX nothing, one worker per chip host, ``chip_smoke.py``'s parent
never imports JAX and cannot print ``"ok": true`` without a TPU, a stale native
binary is never trusted, a too-small ``/dev/shm`` is refused up front, the
model's kernels run once per shard of the mesh in scope — and the smoke's own
serve and four-device phases run as functions, tiny, on the virtual CPU mesh.
"""

import errno
import glob
import importlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import REPO_ROOT

sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402

fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
rn = importlib.import_module("dlrover_tpu.ops.rmsnorm")
ce = importlib.import_module("dlrover_tpu.ops.cross_entropy")


# -- one process for each chip ---------------------------------------------


class TestOneProcessPerChip:
    def test_agent_side_never_asks_jax_for_devices(self, tmp_path,
                                                   monkeypatch):
        """The agent's own threads — resource monitor, metrics gauges,
        the checkpoint saver persisting what a worker staged — with
        ``jax.devices``/``jax.local_devices`` patched to raise.  The worker
        is a real second process, as in production."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.agent.monitor import ResourceMonitor, current_usage
        from dlrover_tpu.checkpoint import shard_file
        from dlrover_tpu.common.storage import PosixDiskStorage

        def boom(*a, **k):
            raise AssertionError("the agent asked JAX for devices")

        monkeypatch.setattr(jax, "devices", boom)
        monkeypatch.setattr(jax, "local_devices", boom)
        job = f"agent-nojax-{os.getpid()}"

        class Client:
            reports = 0

            def report_used_resource(self, **kw):
                Client.reports += 1

        assert set(current_usage()) == {"cpu_percent", "memory_mb"}
        mon = ResourceMonitor(Client(), interval_s=0.05)
        saver = AsyncCheckpointSaver(job, nproc_per_node=1)
        saver.start()
        mon.start()
        try:
            worker = subprocess.run(
                [sys.executable, "-c", (
                    "import numpy as np\n"
                    "from dlrover_tpu.checkpoint.engine import "
                    "CheckpointEngine\n"
                    f"eng = CheckpointEngine({str(tmp_path)!r}, "
                    f"job_name={job!r})\n"
                    "assert eng.agent_mode\n"
                    "eng.save_to_storage(4, {'w': np.full((64, 64), 1.5, "
                    "np.float32)})\n"
                    "assert eng.wait(60)\n"
                )],
                env=dict(os.environ, PYTHONPATH=REPO_ROOT,
                         DLROVER_TPU_JOB_NAME=job),
                capture_output=True, text=True, timeout=120,
            )
            assert worker.returncode == 0, worker.stderr[-2000:]
            saver.save_shm_to_storage("test")  # the breakpoint path too
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and shard_file.latest_step(
                PosixDiskStorage(), str(tmp_path)
            ) != 4:
                time.sleep(0.1)
            assert shard_file.latest_step(
                PosixDiskStorage(), str(tmp_path)) == 4
            assert Client.reports > 0
        finally:
            mon.stop()
            saver.stop()
            for seg in glob.glob(f"/dev/shm/dlrtpu_{job}_*"):
                os.unlink(seg)

    def test_agent_process_reports_it_never_opened_the_device(self,
                                                              tmp_path):
        """The whole launcher tree, one trivial worker: the agent imports
        JAX (the saver does) but must exit without a backend."""
        entry = tmp_path / "entry.py"
        entry.write_text("print('WORKER_RAN', flush=True)\n")
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
        env.pop("DLROVER_TPU_FAULTS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
             "--nproc_per_node=1", "--monitor_interval=0.5",
             f"--job_name=nojax-{os.getpid()}", str(entry)],
            env=env, cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=120,
        )
        out = proc.stdout + proc.stderr
        assert proc.returncode == 0, out[-2000:]
        assert "WORKER_RAN" in out
        assert "device runtime opened by the agent: False" in out

    @pytest.mark.parametrize("chips,nproc,refused", [
        (1, 1, False), (4, 1, False), (0, 2, False), (1, 2, True),
        (4, 4, True),
    ])
    def test_nproc_rule(self, monkeypatch, chips, nproc, refused):
        from dlrover_tpu.agent import training

        monkeypatch.setattr(training, "host_chip_count", lambda: chips)
        if not refused:
            training.check_one_process_per_chip(nproc)
            return
        with pytest.raises(ValueError, match="one process per chip host"):
            training.check_one_process_per_chip(nproc)

    def test_launcher_refuses_before_starting_anything(self, monkeypatch):
        from dlrover_tpu import run as launcher
        from dlrover_tpu.agent import training

        monkeypatch.setattr(training, "host_chip_count", lambda: 1)
        started = []
        monkeypatch.setattr(launcher, "_launch_local_master",
                            lambda *a, **k: started.append(a))
        args = launcher.parse_args(
            ["--standalone", "--nproc_per_node=2", "x.py"])
        with pytest.raises(SystemExit) as e:
            launcher.run(args)
        assert "--nproc_per_node=1" in str(e.value)
        assert "virtual CPU mesh" in str(e.value)
        assert not started

    def test_host_chip_count_reads_device_files_not_jax(self, monkeypatch):
        from dlrover_tpu.common import jax_env

        files = {"/dev/accel[0-9]*": [],
                 "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1"]}
        monkeypatch.setattr(jax_env.glob, "glob", lambda pat: files[pat])
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert jax_env.host_chip_count() == 2
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert jax_env.host_chip_count() == 0  # the virtual CPU mesh
        monkeypatch.delenv("JAX_PLATFORMS")
        files["/dev/accel[0-9]*"] = ["/dev/accel0"]
        assert jax_env.host_chip_count() == 1


# -- chip_smoke.py's own contract ------------------------------------------


class TestChipSmokeContract:
    def test_no_tpu_means_not_ok_and_parent_stays_off_jax(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
            env=env, cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=120,
        )
        lines = proc.stdout.strip().splitlines()
        assert proc.returncode != 0
        last = json.loads(lines[-1])
        assert last["ok"] is False
        assert last["device"]["platform"] == "cpu"
        assert "(parent imported jax: False)" in lines[-2]
        assert not any('"ok": true' in ln for ln in lines
                       if not ln.startswith("["))

    def test_serve_phase_tiny(self):
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        res = chip_smoke.serve_phase(
            cfg, slots=2, max_len=384, new_tokens=4,
            prompt_lens=(8, 300),  # 300 > the largest bucket: chunked
        )
        assert res["ok"] is True
        assert res["device"]["platform"] == "cpu"

    def test_restore_phase_tiny(self, tmp_path, monkeypatch):
        """The no-alias check at a toy size: on the CPU backend, which may
        alias a numpy buffer, every piece is copied first and the phase
        says so; the claim it exists for is made on the chip."""
        monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
        res = chip_smoke.restore_phase(leaves=2, leaf_mib=1)
        assert res["ok"] is True
        assert res["device"]["platform"] == "cpu"

    @pytest.mark.parametrize("remat_block", [False, True])
    def test_mesh4_phase_tiny_with_kernels_per_shard(self, monkeypatch,
                                                     remat_block):
        """fsdp2 x tp2 against one device, the model's kernels steered to
        Pallas in interpret mode (here, in the test: on the CPU the
        dispatchers would pick the references and never meet the mesh);
        with block remat the policy that keeps the flash kernel's two
        outputs reaches them inside the kernels' ``shard_map``."""
        monkeypatch.setattr(
            llama, "flash_attention",
            lambda q, k, v, backend=None, **kw: fa.flash_attention(
                q, k, v, backend="pallas", interpret=True, **kw))
        monkeypatch.setattr(
            llama, "rmsnorm",
            lambda x, w, **kw: rn.rmsnorm(
                x, w, backend="pallas", interpret=True, **kw))
        monkeypatch.setattr(
            llama, "softmax_cross_entropy",
            lambda lg, y, **kw: ce.softmax_cross_entropy(
                lg, y, backend="pallas", interpret=True, **kw))
        cfg = llama.LlamaConfig.tiny(  # GQA: 4 q, 2 kv
            dtype=jnp.float32, remat_block=remat_block)
        res = chip_smoke.mesh4_phase(
            cfg, batch=4, seq=32, steps=3, rel_tol=1e-4)
        assert res["ok"] is True
        assert res["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": len(jax.devices())}


# -- kernels run once per shard of the mesh in scope -------------------------


class TestPerShardKernels:
    def _mesh(self):
        return build_mesh(MeshSpec(fsdp=2, tp=2), jax.devices()[:4])

    def test_flash_gqa_and_rmsnorm_match_reference_under_a_mesh(self):
        mesh = self._mesh()
        rng = np.random.RandomState(0)
        B, H, KV, S, D = 4, 4, 2, 128, 64
        q, k, v = (jnp.asarray(rng.randn(B, h, S, D), jnp.float32)
                   for h in (H, KV, KV))

        def loss(q, k, v, backend):
            return jnp.sum(fa.flash_attention(
                q, k, v, backend=backend, interpret=True) ** 2)

        want = jax.value_and_grad(
            lambda *a: loss(*a, "reference"), argnums=(0, 1, 2))(q, k, v)
        sh = NamedSharding(mesh, P(("dp", "fsdp"), "tp", None, None))
        with jax.set_mesh(mesh):
            got = jax.jit(jax.value_and_grad(
                lambda *a: loss(*a, "pallas"), argnums=(0, 1, 2)))(
                *(jax.device_put(t, sh) for t in (q, k, v)))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            assert g.sharding.spec == sh.spec  # stayed sharded
            np.testing.assert_allclose(g, w, atol=1e-4)

        x = jnp.asarray(rng.randn(4, 16, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64), jnp.float32)
        with jax.set_mesh(mesh):
            y = jax.jit(lambda x, w: rn.rmsnorm(
                x, w, backend="pallas", interpret=True))(
                jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp")))),
                w)
        np.testing.assert_allclose(
            y, rn.rmsnorm(x, w, backend="reference"), atol=1e-5)

    def test_kv_heads_must_divide_tp(self):
        q = jnp.zeros((4, 4, 64, 64))
        kv = jnp.zeros((4, 1, 64, 64))  # one kv head, tp=2
        with jax.set_mesh(self._mesh()):
            with pytest.raises(ValueError, match="n_kv_head % tp == 0"):
                jax.jit(lambda q, k, v: fa.flash_attention(
                    q, k, v, backend="pallas", interpret=True))(q, kv, kv)

    def test_batch_must_divide_the_batch_shards(self):
        with jax.set_mesh(self._mesh()):
            with pytest.raises(ValueError, match="not divisible"):
                jax.jit(lambda x, w: rn.rmsnorm(
                    x, w, backend="pallas", interpret=True))(
                    jnp.zeros((3, 8, 64)), jnp.ones((64,)))

    def test_quant_kernel_refuses_a_mesh_by_name(self):
        from dlrover_tpu.ops.quant import quantize_blockwise

        x = jnp.ones((1024,))
        with jax.set_mesh(self._mesh()):
            with pytest.raises(NotImplementedError, match="backend='jnp'"):
                jax.jit(lambda x: quantize_blockwise(
                    x, backend="pallas", interpret=True))(x)
            codes, _ = jax.jit(
                lambda x: quantize_blockwise(x, backend="jnp"))(x)
        assert codes.shape == (8, 128)

    def test_no_mesh_in_scope_calls_the_kernel_bare(self):
        from dlrover_tpu.ops import per_shard

        assert per_shard.free_axes() == ((), {})
        fn = object()
        assert per_shard.per_shard(fn, (), None, None) is fn


# -- no fallback that hides a stale binary or a full /dev/shm ----------------


class TestNativeAndShm:
    def test_make_is_asked_even_when_the_so_exists(self, monkeypatch):
        from dlrover_tpu.common import native

        so = os.path.join(native._NATIVE_DIR, "libpacker.so")
        assert native.packer_lib() is not None and os.path.exists(so)
        calls = []
        monkeypatch.setattr(
            native.subprocess, "run",
            lambda cmd, **kw: calls.append(cmd) or
            subprocess.CompletedProcess(cmd, 0, b"", b""))
        assert native._build("libpacker.so") == so
        assert calls == [["make", "-C", native._NATIVE_DIR, "libpacker.so"]]

    def test_full_dev_shm_is_refused_with_a_plain_message(self, monkeypatch):
        from dlrover_tpu.common import shm

        real = os.statvfs("/dev/shm")

        class Tiny:
            f_bavail, f_frsize = 1, real.f_frsize

        monkeypatch.setattr(shm.os, "statvfs", lambda p: Tiny)
        with pytest.raises(OSError) as e:
            shm.SharedMemoryArena(f"t_full_{os.getpid()}").write_state(
                {"w": np.zeros((1 << 20,), np.float32)})
        assert e.value.errno == errno.ENOSPC
        assert "/dev/shm has 0 MiB free" in str(e.value)
        assert not os.path.exists(f"/dev/shm/t_full_{os.getpid()}")
