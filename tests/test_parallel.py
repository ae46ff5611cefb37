"""Parallel-layer tests on the 8-device virtual CPU mesh: mesh specs,
sharding rules, accelerate strategy build/search, Ulysses SP, ring
attention, MoE-EP, pipeline parallel, local SGD."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.parallel.accelerate import (
    Strategy,
    accelerate,
    infer_param_specs,
)
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh, candidate_specs


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
from dlrover_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_spec,
)


class TestMeshSpec:
    def test_normalize_and_build(self, cpu_mesh_devices):
        spec = MeshSpec(dp=-1, tp=2).normalized(8)
        assert spec.dp == 4 and spec.tp == 2
        mesh = build_mesh(spec, cpu_mesh_devices[:8])
        assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2

    def test_bad_spec_rejected(self):
        with pytest.raises(ValueError):
            MeshSpec(dp=3, tp=2).normalized(8)

    def test_candidates_cover_ddp_fsdp_tp(self):
        specs = candidate_specs(8)
        descs = {s.describe() for s in specs}
        assert "dp8" in descs  # pure DDP
        assert "fsdp8" in descs  # pure FSDP/ZeRO-3
        assert any("tp" in d for d in descs)  # TP mixes

    def test_logical_rules(self):
        assert logical_to_spec(("batch", None)) == P(("dp", "fsdp"))
        assert logical_to_spec(("embed", "mlp")) == P("fsdp", "tp")
        # Axis reuse is suppressed.
        assert logical_to_spec(("heads", "mlp")) == P("tp")


class TestAccelerate:
    def _problem(self):
        def init_fn(rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (16, 32)),
                "w2": jax.random.normal(k2, (32, 8)),
            }

        def loss_fn(params, batch):
            h = jnp.tanh(batch["x"] @ params["w1"])
            pred = h @ params["w2"]
            return jnp.mean((pred - batch["y"]) ** 2)

        batch = {
            "x": np.random.randn(16, 16).astype(np.float32),
            "y": np.random.randn(16, 8).astype(np.float32),
        }
        return init_fn, loss_fn, batch

    def test_explicit_strategy_runs(self, cpu_mesh_devices):
        init_fn, loss_fn, batch = self._problem()
        job = accelerate(
            loss_fn=loss_fn,
            init_fn=init_fn,
            optimizer=optax.sgd(0.1),
            sample_batch=batch,
            strategy=Strategy(mesh=MeshSpec(dp=4, fsdp=2)),
            devices=cpu_mesh_devices[:8],
        )
        state = job.create_state(jax.random.PRNGKey(0))
        b = jax.device_put(batch, job.batch_sharding)
        losses = []
        for _ in range(3):
            state, metrics = job.train_step(state, b)
            losses.append(float(metrics["loss"]))
        assert losses[2] < losses[0]  # it learns
        assert int(state["step"]) == 3

    def test_auto_search_selects_strategy(self, cpu_mesh_devices):
        init_fn, loss_fn, batch = self._problem()
        job = accelerate(
            loss_fn=loss_fn,
            init_fn=init_fn,
            optimizer=optax.sgd(0.1),
            sample_batch=batch,
            strategy=[
                Strategy(mesh=MeshSpec(dp=8)),
                Strategy(mesh=MeshSpec(fsdp=8)),
            ],
            devices=cpu_mesh_devices[:8],
        )
        assert job.strategy.mesh.describe() in ("dp8", "fsdp8")
        assert job.cost is not None

    def test_grad_accum_and_remat(self, cpu_mesh_devices):
        init_fn, loss_fn, batch = self._problem()
        job = accelerate(
            loss_fn=loss_fn,
            init_fn=init_fn,
            optimizer=optax.sgd(0.1),
            sample_batch=batch,
            strategy=Strategy(
                mesh=MeshSpec(dp=8), grad_accum=2, remat="full"
            ),
            devices=cpu_mesh_devices[:8],
        )
        state = job.create_state(jax.random.PRNGKey(0))
        b = jax.device_put(batch, job.batch_sharding)
        state, metrics = job.train_step(state, b)
        assert np.isfinite(float(metrics["loss"]))

    def test_remat_block_matches_unremat(self):
        """Per-block remat (LlamaConfig.remat_block) must be a pure
        memory/compute trade: loss and grads identical to the plain
        forward."""
        import dataclasses

        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(n_layer=3)
        cfg_r = dataclasses.replace(cfg, remat_block=True)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size
        )
        batch = {"tokens": tokens}
        l0, g0 = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, cfg)
        )(params)
        l1, g1 = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, cfg_r)
        )(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            )

    def test_infer_param_specs_zero3(self):
        params = {"big": np.zeros((64, 8)), "tiny": np.zeros((3,)),
                  "scalar": np.zeros(())}
        specs = infer_param_specs(params, MeshSpec(fsdp=8))
        assert specs["big"] == P("fsdp")
        assert specs["tiny"] == P()  # 3 not divisible by 8
        assert specs["scalar"] == P()

    def test_offload_remat_matches_none_and_places_on_host(
        self, cpu_mesh_devices
    ):
        """Strategy(remat='offload'): block residuals parked in host DRAM
        (reference selective_offloading_checkpoint.py:252)."""
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(n_layer=2)
        rng = np.random.RandomState(0)
        sample = {"tokens": rng.randint(0, 250, size=(8, 17)).astype(
            np.int32)}

        def job_for(remat):
            return accelerate(
                loss_fn=lambda p, b: llama.loss_fn(
                    p, b, cfg, moe_aux_weight=0.0
                ),
                init_fn=lambda r: llama.init_params(r, cfg),
                optimizer=optax.adamw(1e-3),
                sample_batch=sample,
                strategy=Strategy(mesh=MeshSpec(dp=2), remat=remat),
                devices=cpu_mesh_devices[:2],
            )

        j_off = job_for("offload")
        j_none = job_for("none")
        batch = {"tokens": jnp.asarray(sample["tokens"])}
        s_off = j_off.create_state(jax.random.PRNGKey(0))
        s_none = j_none.create_state(jax.random.PRNGKey(0))
        for _ in range(2):
            s_off, m_off = j_off.train_step(s_off, batch)
            s_none, m_none = j_none.train_step(s_none, batch)
        # Rematerialization reorders bf16 reductions: tiny drift is
        # expected, equality is not.
        np.testing.assert_allclose(
            float(m_off["loss"]), float(m_none["loss"]), rtol=1e-3
        )
        # The host-placement effect itself is only observable on TPU
        # runtimes (the single-memory CPU backend elides pinned_host
        # transfers entirely — verified: even an explicit in-jit
        # device_put to pinned_host lowers with no memory annotation).
        # What IS checkable everywhere: the policy names the tagged
        # residual and requests offload, not save.
        from dlrover_tpu.parallel.accelerate import REMAT_POLICIES
        from jax._src.ad_checkpoint import name_p
        from jax._src.interpreters.partial_eval import Offloadable

        pol = REMAT_POLICIES["offload"]
        # Policy contract: the tagged residual offloads device->host;
        # everything else rematerializes.
        decision = pol(name_p, name="block_out")
        assert isinstance(decision, Offloadable)
        assert (decision.src, decision.dst) == ("device", "pinned_host")
        assert not isinstance(pol(name_p, name="other"), Offloadable)
        assert not isinstance(pol(None), Offloadable)


class TestUlyssesSP:
    def test_matches_single_device_attention(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.sequence import (
            _attn_core,
            ulysses_attention,
        )

        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("tp",))
        B, S, H, D = 2, 16, 4, 8
        rng = jax.random.PRNGKey(1)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, D),
                              jnp.float32)
            for i in range(3)
        )
        ref = _attn_core(q, k, v, causal=True)
        sharding = NamedSharding(mesh, P(None, "tp", None, None))
        qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
        out = ulysses_attention(qs, ks, vs, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestRingAttention:
    def test_matches_reference(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.ring_attention import ring_attention
        from dlrover_tpu.parallel.sequence import _attn_core

        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("tp",))
        B, S, H, D = 2, 32, 2, 8
        rng = jax.random.PRNGKey(2)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, D),
                              jnp.float32)
            for i in range(3)
        )
        ref = _attn_core(q, k, v, causal=True)
        sharding = NamedSharding(mesh, P(None, "tp", None, None))
        qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_non_causal(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.ring_attention import ring_attention
        from dlrover_tpu.parallel.sequence import _attn_core

        mesh = Mesh(np.array(cpu_mesh_devices[:2]), ("tp",))
        B, S, H, D = 1, 8, 2, 4
        rng = jax.random.PRNGKey(3)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, D),
                              jnp.float32)
            for i in range(3)
        )
        ref = _attn_core(q, k, v, causal=False)
        sharding = NamedSharding(mesh, P(None, "tp", None, None))
        out = ring_attention(
            *(jax.device_put(t, sharding) for t in (q, k, v)),
            mesh, causal=False,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


class TestPipeline:
    def test_matches_sequential(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.pipeline import (
            pipeline_apply,
            stack_stage_params,
        )

        n_stages = 4
        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("pp",))
        rng = jax.random.PRNGKey(0)
        stages = []
        for i in range(n_stages):
            k = jax.random.fold_in(rng, i)
            stages.append(
                {"w": jax.random.normal(k, (8, 8)) * 0.5}
            )

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.PRNGKey(9), (8, 8))
        ref = x
        for p in stages:
            ref = stage_fn(p, ref)

        stacked = stack_stage_params(stages)
        out = jax.jit(
            lambda sp, xx: pipeline_apply(
                stage_fn, sp, xx, mesh, n_microbatches=4
            )
        )(stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_1f1b_schedule_valid(self):
        from dlrover_tpu.parallel.pipeline import build_1f1b_schedule

        for S, M in [(1, 2), (2, 2), (2, 4), (4, 4), (4, 6), (3, 5)]:
            sched = build_1f1b_schedule(S, M)
            fwd, bwd = sched.fwd, sched.bwd
            t_f, t_b = {}, {}
            for t in range(fwd.shape[0]):
                for s in range(S):
                    if fwd[t, s] >= 0:
                        t_f[(int(fwd[t, s]), s)] = t
                    if bwd[t, s] >= 0:
                        t_b[(int(bwd[t, s]), s)] = t
            # Every micro forward+backward on every stage, deps respected.
            for m in range(M):
                for s in range(S):
                    assert (m, s) in t_f and (m, s) in t_b, (S, M, m, s)
                    if s > 0:
                        assert t_f[(m, s)] > t_f[(m, s - 1)]
                    if s < S - 1:
                        assert t_b[(m, s)] > t_b[(m, s + 1)]
                    else:
                        assert t_b[(m, s)] > t_f[(m, s)]
            # 1F1B memory bound: in-flight fwd-not-yet-bwd per stage <= S.
            for s in range(S):
                events = sorted(
                    [(t_f[(m, s)], 1) for m in range(M)]
                    + [(t_b[(m, s)], -1) for m in range(M)]
                )
                live = peak = 0
                for _, d in events:
                    live += d
                    peak = max(peak, live)
                assert peak <= S, (S, M, s, peak)

    @pytest.mark.parametrize("S,M", [(2, 4), (4, 4), (4, 6)])
    def test_1f1b_matches_autodiff(self, cpu_mesh_devices, S, M):
        from dlrover_tpu.parallel.pipeline import (
            pipeline_value_and_grad,
            stack_stage_params,
        )

        d = 8
        mesh = Mesh(
            np.array(cpu_mesh_devices[:8]).reshape(S, 8 // S), ("pp", "dp")
        )
        rng = jax.random.PRNGKey(0)
        stages = [
            {"w": jax.random.normal(jax.random.fold_in(rng, i), (d, d)) * 0.5}
            for i in range(S)
        ]
        pre = {"we": jax.random.normal(jax.random.fold_in(rng, 50), (4, d))}
        post = {"wo": jax.random.normal(jax.random.fold_in(rng, 51), (d, 3))}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        def pre_fn(p, tok):
            return p["we"][tok]  # [B] int -> [B, d]

        def post_fn(p, x, tgt):
            logits = x @ p["wo"]
            return jnp.mean((logits - tgt) ** 2)

        B = 2 * M
        tok = jax.random.randint(jax.random.PRNGKey(7), (B,), 0, 4)
        tgt = jax.random.normal(jax.random.PRNGKey(8), (B, 3))

        def ref_loss(stacked, pre, post):
            micros_t = tok.reshape(M, -1)
            micros_y = tgt.reshape(M, -1, 3)
            total = 0.0
            for m in range(M):
                x = pre_fn(pre, micros_t[m])
                for s in range(S):
                    x = stage_fn(
                        jax.tree_util.tree_map(lambda p: p[s], stacked), x
                    )
                total = total + post_fn(post, x, micros_y[m]) / M
            return total

        stacked = stack_stage_params(stages)
        ref_l, ref_g = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
            stacked, pre, post
        )
        loss, grads = jax.jit(
            lambda sp, pr, po: pipeline_value_and_grad(
                stage_fn, pre_fn, post_fn, sp, pr, po, tok, tgt, mesh,
                n_microbatches=M,
            )
        )(stacked, pre, post)
        np.testing.assert_allclose(float(loss), float(ref_l), atol=1e-5)
        for got, want in zip(grads, ref_g):
            for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4
                )

    def test_llama_pp_matches_unpipelined(self, cpu_mesh_devices):
        from dlrover_tpu.models import llama, llama_pp

        cfg = llama.LlamaConfig.tiny(n_layer=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size
        )
        batch = {"tokens": tokens}
        mesh = Mesh(
            np.array(cpu_mesh_devices[:8]).reshape(2, 2, 2),
            ("pp", "fsdp", "tp"),
        )

        ref = float(
            llama.loss_fn(params, batch, cfg, attn_impl="reference")
        )
        gpipe = jax.jit(
            lambda p, b: llama_pp.pipeline_loss_fn(
                p, b, cfg, mesh, n_microbatches=2
            )
        )(params, batch)
        np.testing.assert_allclose(float(gpipe), ref, atol=2e-3)

        loss_1f1b, grads = jax.jit(
            lambda p, b: llama_pp.pipeline_train_grads(
                p, b, cfg, mesh, n_microbatches=2
            )
        )(params, batch)
        np.testing.assert_allclose(float(loss_1f1b), ref, atol=2e-3)
        # Grad structure matches params; values match autodiff.
        ref_grads = jax.grad(
            lambda p: llama.loss_fn(
                p, batch, cfg, attn_impl="reference", moe_aux_weight=0.0
            )
        )(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(ref_grads),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3
            )


class TestLocalSGD:
    def test_diloco_sync_with_divergent_replicas(self, cpu_mesh_devices):
        """Replica-divergent state is held as a stacked P('dp') array, so
        the replication checker stays ON (no check_vma escape)."""
        from dlrover_tpu.parallel.local_sgd import LocalSGDSync

        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("dp",))
        sync = LocalSGDSync(outer_lr=1.0, outer_momentum=0.0, dp_axis="dp")
        params = {"w": jnp.ones((4, 4))}
        anchor, mom = sync.init(params)
        local = sync.scatter(mesh, params)
        assert local["w"].shape == (4, 4, 4)

        # Each replica drifts by a DIFFERENT amount: replica r subtracts
        # (r+1)*0.1, so mean drift = 0.25 and new params = 1 - 0.25.
        drifts = jnp.arange(1, 5, dtype=jnp.float32) * 0.1

        def inner(p, d):
            return {"w": p["w"] - d}

        local = sync.inner_apply(mesh, inner, local, drifts)
        new_p, new_anchor, new_m = sync.apply(mesh, local, anchor, mom)
        np.testing.assert_allclose(
            np.asarray(new_p["w"]), np.full((4, 4), 0.75), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(new_anchor["w"]), np.asarray(new_p["w"])
        )
        # Momentum accumulated the mean delta.
        np.testing.assert_allclose(
            np.asarray(new_m["w"]), np.full((4, 4), 0.25), atol=1e-6
        )

    def test_diloco_masked_replica_excluded(self, cpu_mesh_devices):
        """replica_weights=0 drops an anomalous replica's drift from the
        outer update (anomaly-detection integration point)."""
        from dlrover_tpu.parallel.local_sgd import LocalSGDSync

        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("dp",))
        sync = LocalSGDSync(outer_lr=1.0, outer_momentum=0.0, dp_axis="dp")
        params = {"w": jnp.ones((4, 4))}
        anchor, mom = sync.init(params)
        local = sync.scatter(mesh, params)
        # Replica 3 "diverged": huge drift.  Mask it out.
        drifts = jnp.array([0.1, 0.2, 0.3, 100.0], jnp.float32)
        local = sync.inner_apply(
            mesh, lambda p, d: {"w": p["w"] - d}, local, drifts
        )
        norms = sync.delta_norms(mesh, local, anchor)
        assert norms.shape == (4,)
        assert float(norms[3]) > 50 * float(norms[2])
        weights = jnp.array([1.0, 1.0, 1.0, 0.0], jnp.float32)
        new_p, _, _ = sync.apply(
            mesh, local, anchor, mom, replica_weights=weights
        )
        # Mean drift over the surviving replicas = 0.2.
        np.testing.assert_allclose(
            np.asarray(new_p["w"]), np.full((4, 4), 0.8), atol=1e-6
        )

    def test_ewma_detector_flags_outlier(self):
        from dlrover_tpu.parallel.local_sgd import OnlineEWMADetector

        det = OnlineEWMADetector(alpha=0.1, warmup_steps=20,
                                 base_threshold=3.0)
        rng = np.random.RandomState(0)
        for _ in range(200):
            det.update(1.0 + 0.01 * rng.randn())
        assert not det.is_anomaly(1.02)
        assert det.is_anomaly(5.0)
        # State round-trips (elastic restart keeps the baseline).
        clone = OnlineEWMADetector()
        clone.load_state_dict(det.state_dict())
        assert clone.is_anomaly(5.0) and not clone.is_anomaly(1.02)

    def test_diloco_inner_steps_stay_local(self, cpu_mesh_devices):
        """inner_apply must not introduce cross-replica collectives: the
        jaxpr of the lowered step contains no psum/pmean over dp."""
        from dlrover_tpu.parallel.local_sgd import LocalSGDSync

        mesh = Mesh(np.array(cpu_mesh_devices[:2]), ("dp",))
        sync = LocalSGDSync(dp_axis="dp")
        params = {"w": jnp.ones((2, 2))}
        local = sync.scatter(mesh, params)
        batches = jnp.ones((2, 4, 2))

        def inner(p, b):
            g = jax.grad(lambda w: jnp.sum((b @ w) ** 2))(p["w"])
            return {"w": p["w"] - 0.01 * g}

        lowered = jax.jit(
            lambda lp, bb: sync.inner_apply(mesh, inner, lp, bb)
        ).lower(local, batches)
        text = lowered.as_text()
        assert "all-reduce" not in text and "all-gather" not in text, (
            "inner step leaked a cross-replica collective"
        )

    def test_diloco_sync_multiprocess(self, tmp_path):
        """Two real OS processes under jax.distributed, one CPU device
        each, forming a global dp=2 mesh: both must agree on the synced
        parameters (reference outer_optim_model_averager 2-rank DDP test).
        """
        import subprocess
        import sys

        port = _free_port()
        script = r"""
import os, sys
import numpy as np
pid = int(sys.argv[1]); coord = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.distributed.initialize(coord, num_processes=2, process_id=pid)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dlrover_tpu.parallel.local_sgd import LocalSGDSync

mesh = Mesh(np.array(jax.devices()), ("dp",))
sync = LocalSGDSync(outer_lr=1.0, outer_momentum=0.0)
params = {"w": jnp.ones((2, 2))}
anchor, mom = sync.init(params)
local = sync.scatter(mesh, params)
# Divergent inner drift: process r subtracts (r+1)*0.2 from its slice.
drifts = jnp.arange(1, 3, dtype=jnp.float32) * 0.2
local = sync.inner_apply(
    mesh, lambda p, d: {"w": p["w"] - d}, local, drifts
)
new_p, _, _ = sync.apply(mesh, local, anchor, mom)
got = np.asarray(jax.device_get(new_p["w"]))
np.testing.assert_allclose(got, np.full((2, 2), 0.7), atol=1e-6)
print(f"RESULT {pid} {got[0,0]:.6f}")
"""
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "PYTHONPATH": repo}
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", script, str(i), f"127.0.0.1:{port}"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=repo, env=env,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out}"
            assert f"RESULT {i} 0.700000" in out, out


class TestSPMultiprocess:
    """2 real OS processes under jax.distributed, one CPU device each:
    the Ulysses and ring attention paths must lower and agree with the
    single-device reference with the shard_map VMA checker fully on
    (VERDICT r2 next #7 — these paths carried check_vma=False)."""

    @pytest.mark.parametrize("path", ["ulysses", "ring"])
    def test_two_process_attention(self, path):
        import os
        import subprocess
        import sys

        port = _free_port()
        script = r"""
import os, sys
import numpy as np
pid = int(sys.argv[1]); coord = sys.argv[2]; path = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.distributed.initialize(coord, num_processes=2, process_id=pid)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()), ("tp",))
B, S, H, D = 2, 8, 4, 8
rng = np.random.RandomState(0)
qg = rng.randn(B, S, H, D).astype(np.float32) * 0.5
kg = rng.randn(B, S, H, D).astype(np.float32) * 0.5
vg = rng.randn(B, S, H, D).astype(np.float32) * 0.5
sh = NamedSharding(mesh, P(None, "tp", None, None))
def mk(a):
    return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])
q, k, v = mk(qg), mk(kg), mk(vg)

if path == "ulysses":
    from dlrover_tpu.parallel.sequence import ulysses_attention as attn
else:
    from dlrover_tpu.parallel.ring_attention import ring_attention as attn
out = jax.jit(
    lambda q, k, v: attn(q, k, v, mesh, seq_axis="tp", causal=True)
)(q, k, v)

# Single-device reference, computed identically in both processes.
scale = 1.0 / np.sqrt(D)
att = np.einsum("bshd,bthd->bhst", qg, kg) * scale
mask = np.tril(np.ones((S, S), bool))
att = np.where(mask, att, -1e30)
att = att - att.max(-1, keepdims=True)
p = np.exp(att); p /= p.sum(-1, keepdims=True)
ref = np.einsum("bhst,bthd->bshd", p, vg)

local = np.asarray(out.addressable_shards[0].data)
lo = pid * (S // 2)
np.testing.assert_allclose(local, ref[:, lo:lo + S // 2], atol=2e-3)
print(f"RESULT {pid} OK")
"""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "PYTHONPATH": repo}
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", script, str(i),
                 f"127.0.0.1:{port}", path],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=repo, env=env,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out}"
            assert f"RESULT {i} OK" in out, out


class TestHybridMesh:
    def test_dcn_axes_span_slices(self, cpu_mesh_devices):
        """dp rides across slices; fsdp stays inside one slice."""
        from dlrover_tpu.parallel.mesh import MeshSpec, build_hybrid_mesh

        devs = cpu_mesh_devices[:8]
        # Fake 2 slices of 4 chips each.
        fake_slice = {id(d): i // 4 for i, d in enumerate(devs)}
        mesh = build_hybrid_mesh(
            MeshSpec(dp=2, fsdp=4),
            devs,
            dcn_axes=("pp", "dp"),
            slice_of=lambda d: fake_slice[id(d)],
        )
        arr = mesh.devices  # [pp=1, dp=2, fsdp=4, ep=1, tp=1]
        assert arr.shape == (1, 2, 4, 1, 1)
        # Each dp row holds exactly one slice's devices.
        for dp_i in range(2):
            row = arr[0, dp_i].reshape(-1)
            assert {fake_slice[id(d)] for d in row} == {dp_i}

    def test_slice_count_mismatch_rejected(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.mesh import MeshSpec, build_hybrid_mesh

        devs = cpu_mesh_devices[:8]
        fake_slice = {id(d): i // 4 for i, d in enumerate(devs)}
        import pytest

        with pytest.raises(ValueError, match="slices"):
            build_hybrid_mesh(
                MeshSpec(dp=4, fsdp=2), devs,
                slice_of=lambda d: fake_slice[id(d)],
            )

    def test_non_prefix_dcn_axes_rejected(self, cpu_mesh_devices):
        from dlrover_tpu.parallel.mesh import MeshSpec, build_hybrid_mesh

        import pytest

        with pytest.raises(ValueError, match="prefix"):
            build_hybrid_mesh(
                MeshSpec(dp=2, fsdp=4), cpu_mesh_devices[:8],
                dcn_axes=("fsdp",),
            )

    def test_diloco_over_hybrid_mesh(self, cpu_mesh_devices):
        """The multislice DiLoCo composition: dp (DCN, per-slice replicas)
        x fsdp (ICI, sharded params inside each slice)."""
        from dlrover_tpu.parallel.local_sgd import LocalSGDSync
        from dlrover_tpu.parallel.mesh import MeshSpec, build_hybrid_mesh

        devs = cpu_mesh_devices[:4]
        fake_slice = {id(d): i // 2 for i, d in enumerate(devs)}
        mesh = build_hybrid_mesh(
            MeshSpec(dp=2, fsdp=2), devs,
            slice_of=lambda d: fake_slice[id(d)],
        )
        sync = LocalSGDSync(outer_lr=1.0, outer_momentum=0.0)
        params = {"w": jnp.ones((4, 4))}
        anchor, mom = sync.init(params)
        local = sync.scatter(mesh, params)
        drifts = jnp.array([0.1, 0.3], jnp.float32)
        local = sync.inner_apply(
            mesh, lambda p, d: {"w": p["w"] - d}, local, drifts
        )
        new_p, _, _ = sync.apply(mesh, local, anchor, mom)
        np.testing.assert_allclose(
            np.asarray(new_p["w"]), np.full((4, 4), 0.8), atol=1e-6
        )


class TestInterleavedPipeline:
    @pytest.mark.parametrize("S,V,M", [(2, 2, 4), (4, 2, 8), (2, 3, 6)])
    def test_schedule_valid_and_slots_disjoint(self, S, V, M):
        from dlrover_tpu.parallel.pipeline import (
            build_interleaved_1f1b_schedule,
        )

        sched = build_interleaved_1f1b_schedule(S, V, M)
        SV = S * V
        n_slot = min(M, SV)
        done_f, done_b = {}, {}
        for t in range(sched.fwd.shape[0]):
            for s in range(S):
                for tab, done in ((sched.fwd, done_f), (sched.bwd, done_b)):
                    e = tab[t, s]
                    if e >= 0:
                        m, v = divmod(int(e), V)
                        j = v * S + s
                        assert (m, j) not in done
                        done[(m, j)] = t
        assert len(done_f) == len(done_b) == M * SV
        for m in range(M):
            for j in range(SV):
                if j > 0:
                    assert done_f[(m, j - 1)] < done_f[(m, j)]
                if j < SV - 1:
                    assert done_b[(m, j + 1)] < done_b[(m, j)]
            assert done_f[(m, SV - 1)] < done_b[(m, SV - 1)]
        # Ring-slot safety: two micros sharing slot m % n_slot must never
        # be co-resident in any of the executor's rings at one virtual
        # stage (x_saved: fwd..bwd; in_ring: fwd@j-1..fwd@j;
        # g_ring: bwd@j+1..bwd@j; seed: fwd@last..bwd@last).
        def overlap(a, b):
            return not (a[1] <= b[0] or b[1] <= a[0])

        for j in range(SV):
            for kind in ("x", "in", "g"):
                spans = {}
                for m in range(M):
                    if kind == "x":
                        span = (done_f[(m, j)], done_b[(m, j)])
                    elif kind == "in":
                        if j == 0:
                            continue
                        span = (done_f[(m, j - 1)], done_f[(m, j)])
                    else:
                        if j == SV - 1:
                            continue
                        span = (done_b[(m, j + 1)], done_b[(m, j)])
                    spans.setdefault(m % n_slot, []).append(span)
                for slot, ss in spans.items():
                    ss.sort()
                    for a, b in zip(ss, ss[1:]):
                        assert not overlap(a, b), (S, V, M, j, kind, slot)

    @pytest.mark.parametrize(
        "S,V,M", [(2, 2, 4), (4, 2, 4), (2, 3, 6), (2, 4, 4), (4, 3, 6)]
    )
    def test_interleaved_matches_autodiff(self, cpu_mesh_devices, S, V, M):
        from dlrover_tpu.parallel.pipeline import (
            deinterleave_stage_grads,
            interleave_stage_params,
            pipeline_value_and_grad_interleaved,
        )

        d = 8
        SV = S * V
        mesh = Mesh(
            np.array(cpu_mesh_devices[:S]).reshape(S, 1), ("pp", "dp")
        )
        rng = jax.random.PRNGKey(0)
        virt = [
            {"w": jax.random.normal(jax.random.fold_in(rng, i), (d, d)) * 0.4}
            for i in range(SV)
        ]
        pre = {"we": jax.random.normal(jax.random.fold_in(rng, 50), (4, d))}
        post = {"wo": jax.random.normal(jax.random.fold_in(rng, 51), (d, 3))}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        def pre_fn(p, tok):
            return p["we"][tok]

        def post_fn(p, x, tgt):
            return jnp.mean((x @ p["wo"] - tgt) ** 2)

        B = 2 * M
        tok = jax.random.randint(jax.random.PRNGKey(7), (B,), 0, 4)
        tgt = jax.random.normal(jax.random.PRNGKey(8), (B, 3))

        def ref_loss(virt_list, pre, post):
            micros_t = tok.reshape(M, -1)
            micros_y = tgt.reshape(M, -1, 3)
            total = 0.0
            for m in range(M):
                x = pre_fn(pre, micros_t[m])
                for j in range(SV):
                    x = stage_fn(virt_list[j], x)
                total = total + post_fn(post, x, micros_y[m]) / M
            return total

        ref_l, ref_g = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
            virt, pre, post
        )
        stacked = interleave_stage_params(virt, S)
        loss, (d_blocks, d_pre, d_post) = jax.jit(
            lambda sp, pr, po: pipeline_value_and_grad_interleaved(
                stage_fn, pre_fn, post_fn, sp, pr, po, tok, tgt, mesh,
                n_microbatches=M, n_chunks=V,
            )
        )(stacked, pre, post)
        np.testing.assert_allclose(float(loss), float(ref_l), atol=1e-5)
        got_virt = deinterleave_stage_grads(d_blocks, S, V)
        for j in range(SV):
            np.testing.assert_allclose(
                np.asarray(got_virt[j]["w"]), np.asarray(ref_g[0][j]["w"]),
                atol=1e-4,
            )
        for got, want in ((d_pre, ref_g[1]), (d_post, ref_g[2])):
            for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4
                )


class TestScheduledWorkOnly:
    S, V, M = 2, 2, 4  # M = 2S

    def _toy(self, cpu_mesh_devices):
        """One tanh layer a virtual stage, an embedding in front and a
        cross-entropy head behind: (mesh, virt, pre, post, tok, tgt,
        xent)."""
        d, vocab, micro_bs = 8, 16, 4
        mesh = Mesh(np.array(cpu_mesh_devices[:self.S]), ("pp",))
        rng = jax.random.PRNGKey(0)
        virt = [
            {"w": jax.random.normal(jax.random.fold_in(rng, i), (d, d))
             * 0.4}
            for i in range(self.S * self.V)
        ]
        pre = {"we": jax.random.normal(jax.random.fold_in(rng, 50),
                                       (vocab, d))}
        post = {"wo": jax.random.normal(jax.random.fold_in(rng, 51),
                                        (d, vocab))}
        B = self.M * micro_bs
        tok = jax.random.randint(jax.random.PRNGKey(7), (B,), 0, vocab)
        tgt = jax.random.randint(jax.random.PRNGKey(8), (B,), 0, vocab)

        def xent(p, x, tgt):
            logits = x @ p["wo"]
            lse = jax.nn.logsumexp(logits, -1)
            return jnp.mean(
                lse - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0]
            )

        return mesh, virt, pre, post, tok, tgt, xent

    def test_1f1b_unit_bodies_fire_only_when_scheduled(
        self, cpu_mesh_devices
    ):
        """The lax.cond gating must make the lm-head loss (post_fn), the
        embedding (pre_fn), and the stage body execute EXACTLY as many
        times as the 1F1B schedule assigns — not once per (tick, stage)
        as a masked/ungated executor would (VERDICT r2 weak #2; reference
        atorch pipeline_parallel/scheduler.py:15 runs only scheduled
        cells)."""
        from dlrover_tpu.parallel.pipeline import (
            build_interleaved_1f1b_schedule,
            interleave_stage_params,
            pipeline_value_and_grad_interleaved,
        )

        S, V, M = self.S, self.V, self.M
        SV = S * V
        mesh, virt, pre, post, tok, tgt, xent = self._toy(cpu_mesh_devices)

        counts = {"pre": 0, "post": 0, "stage": 0}

        def bump(name):
            jax.debug.callback(lambda: counts.__setitem__(
                name, counts[name] + 1))

        def stage_fn(p, x):
            bump("stage")
            return jnp.tanh(x @ p["w"])

        def pre_fn(p, tok):
            bump("pre")
            return p["we"][tok]

        def post_fn(p, x, tgt):
            bump("post")
            return xent(p, x, tgt)

        stacked = interleave_stage_params(virt, S)
        f = jax.jit(
            lambda sp, pr, po: pipeline_value_and_grad_interleaved(
                stage_fn, pre_fn, post_fn, sp, pr, po, tok, tgt, mesh,
                n_microbatches=M, n_chunks=V,
            )
        )
        jax.block_until_ready(f(stacked, pre, post))  # compile + run
        jax.effects_barrier()
        counts.update(pre=0, post=0, stage=0)
        jax.block_until_ready(f(stacked, pre, post))
        jax.effects_barrier()

        n_ticks = build_interleaved_1f1b_schedule(S, V, M).fwd.shape[0]
        # post: M in-scan loss units (one per microbatch, last virtual
        # stage only) + the deferred post-scan d_post recompute (its
        # grad-of-scan fires an in-body callback once, not per iter).
        assert M <= counts["post"] <= 2 * M, counts
        # pre: M scheduled entry-stage units + the deferred d_pre vjp.
        assert M <= counts["pre"] <= 2 * M, counts
        # stage: M*SV scheduled fwd units + M*SV vjp-linearize forwards.
        assert counts["stage"] == 2 * M * SV, counts
        # An ungated executor fires each body once per (tick, physical
        # stage) — n_ticks*S times: make sure we are far below that.
        assert counts["post"] < n_ticks * S, (counts, n_ticks)
        assert counts["pre"] < n_ticks * S, (counts, n_ticks)

    def test_interleaved_1f1b_beats_gpipe(self, cpu_mesh_devices):
        """At M = 2S the cond-gated interleaved 1F1B executor runs fewer
        layer bodies than the GPipe fill-drain scan: GPipe's ungated
        scan runs every stage at every one of its S + M - 1 ticks (and
        its transposed scan as many again), while gated 1F1B runs only
        the M * S * V scheduled forward units and their linearizations
        (VERDICT r2 next #2).  Counted with the instrument of the test
        above, one count a layer body on either side; the callback takes
        no operand, so a checkpointed backward cannot be counted with it
        and GPipe is counted on its forward scan alone.  No clock."""
        from dlrover_tpu.parallel.pipeline import (
            interleave_stage_params,
            pipeline_apply,
            pipeline_value_and_grad_interleaved,
            stack_stage_params,
        )

        S, V, M = self.S, self.V, self.M
        mesh, virt, pre, post, tok, tgt, post_fn = self._toy(
            cpu_mesh_devices)
        counts = {"layer": 0}

        def layer(w, x):
            jax.debug.callback(lambda: counts.__setitem__(
                "layer", counts["layer"] + 1))
            return jnp.tanh(x @ w)

        def stage_fn(p, x):
            return layer(p["w"], x)

        def pre_fn(p, tok):
            return p["we"][tok]

        stacked = interleave_stage_params(virt, S)
        f_1f1b = jax.jit(
            lambda sp, pr, po: pipeline_value_and_grad_interleaved(
                stage_fn, pre_fn, post_fn, sp, pr, po, tok, tgt, mesh,
                n_microbatches=M, n_chunks=V,
            )
        )

        # GPipe comparator: the same S*V layers folded V-per-physical-
        # stage, checkpointed, trained by autodiff through the scan.
        # GPipe stage s holds the V *consecutive* layers s*V..s*V+V-1 (the
        # non-interleaved placement); the composed model is the same
        # virt[0..S*V-1] chain as the interleaved executor runs.
        gp_stacked = stack_stage_params([
            {f"w_{c}": virt[s * V + c]["w"] for c in range(V)}
            for s in range(S)
        ])

        def gp_body(p, x):
            for c in range(V):
                x = layer(p[f"w_{c}"], x)
            return x

        gp_stage_fn = jax.checkpoint(gp_body)

        def gpipe_loss(sp, pr, po):
            y = pipeline_apply(
                gp_stage_fn, sp, pre_fn(pr, tok), mesh, n_microbatches=M
            )
            return post_fn(po, y, tgt)

        f_gpipe = jax.jit(jax.value_and_grad(gpipe_loss, argnums=(0, 1, 2)))
        f_gpipe_fwd = jax.jit(gpipe_loss)

        def layer_bodies(f, *a):
            jax.block_until_ready(f(*a))  # compile + run
            jax.effects_barrier()
            counts["layer"] = 0
            jax.block_until_ready(f(*a))
            jax.effects_barrier()
            return counts["layer"]

        # Same training computation (sanity): losses agree.
        np.testing.assert_allclose(
            float(f_1f1b(stacked, pre, post)[0]),
            float(f_gpipe(gp_stacked, pre, post)[0]), rtol=1e-4)
        n_1f1b = layer_bodies(f_1f1b, stacked, pre, post)
        n_gpipe_fwd = layer_bodies(f_gpipe_fwd, gp_stacked, pre, post)
        # scheduled forward units + their vjp-linearize forwards
        assert n_1f1b == 2 * M * S * V, (n_1f1b, n_gpipe_fwd)
        # every stage's V layers at every tick, scheduled or not
        assert n_gpipe_fwd == V * S * (S + M - 1), (n_1f1b, n_gpipe_fwd)
        assert n_1f1b // 2 < n_gpipe_fwd


class TestInterleavedLlama:
    def test_llama_interleaved_pp_matches_unpipelined(
        self, cpu_mesh_devices
    ):
        """pp=2 x chunks=2 (4 virtual stages of 1 layer) on Llama: loss
        and grads match the unpipelined model, composed with fsdp/tp."""
        from dlrover_tpu.models import llama, llama_pp

        cfg = llama.LlamaConfig.tiny(n_layer=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size
        )
        batch = {"tokens": tokens}
        mesh = Mesh(
            np.array(cpu_mesh_devices[:8]).reshape(2, 2, 2),
            ("pp", "fsdp", "tp"),
        )
        ref = float(
            llama.loss_fn(params, batch, cfg, attn_impl="reference",
                          moe_aux_weight=0.0)
        )
        loss, grads = jax.jit(
            lambda p, b: llama_pp.pipeline_train_grads(
                p, b, cfg, mesh, n_microbatches=2, n_chunks=2
            )
        )(params, batch)
        np.testing.assert_allclose(float(loss), ref, atol=2e-3)
        ref_grads = jax.grad(
            lambda p: llama.loss_fn(
                p, batch, cfg, attn_impl="reference", moe_aux_weight=0.0
            )
        )(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(ref_grads),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3
            )


class TestPackedSequences:
    def test_packed_equals_separate(self):
        """Two sequences packed into one row (segment_ids + per-segment
        rope reset + cross-boundary loss mask) must produce the same loss
        as the two sequences in separate rows."""
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(n_layer=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        a = rng.randint(0, cfg.vocab_size, size=(1, 17)).astype(np.int32)
        b = rng.randint(0, cfg.vocab_size, size=(1, 17)).astype(np.int32)

        # Separate rows: mean of the two per-sequence token losses.
        sep = 0.5 * (
            float(llama.loss_fn(params, {"tokens": jnp.asarray(a)}, cfg,
                                moe_aux_weight=0.0))
            + float(llama.loss_fn(params, {"tokens": jnp.asarray(b)}, cfg,
                                  moe_aux_weight=0.0))
        )

        packed = np.concatenate([a, b], axis=1)  # [1, 34]
        seg = np.concatenate(
            [np.zeros_like(a), np.ones_like(b)], axis=1
        )
        loss = float(
            llama.loss_fn(
                params,
                {"tokens": jnp.asarray(packed),
                 "segment_ids": jnp.asarray(seg)},
                cfg, moe_aux_weight=0.0,
            )
        )
        np.testing.assert_allclose(loss, sep, rtol=1e-5)

    def test_segment_positions(self):
        from dlrover_tpu.models.llama import segment_positions

        seg = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2]])
        pos = segment_positions(seg)
        np.testing.assert_array_equal(
            np.asarray(pos[0]), [0, 1, 2, 0, 1, 0, 1, 2]
        )

    def test_moe_pads_take_no_capacity(self):
        """Pad positions (segment -1) must not claim expert-capacity
        slots or pollute the aux loss: real tokens routed AFTER pads in
        the flattened order get the same expert outputs as they would
        with no pads present (ADVICE r2: pads could displace real
        tokens via the position-ordered capacity cumsum)."""
        from dlrover_tpu.models import llama
        from dlrover_tpu.models.llama import _moe_swiglu

        cfg = llama.LlamaConfig.tiny(n_layer=1, num_experts=2, top_k=1)
        C = cfg.d_model
        rng = jax.random.PRNGKey(0)
        moe = {
            "router": jax.random.normal(rng, (C, 2), jnp.float32) * 0.5,
            "wg": jax.random.normal(
                jax.random.fold_in(rng, 1), (2, C, cfg.d_ff)) * 0.1,
            "wi": jax.random.normal(
                jax.random.fold_in(rng, 2), (2, C, cfg.d_ff)) * 0.1,
            "wo": jax.random.normal(
                jax.random.fold_in(rng, 3), (2, cfg.d_ff, C)) * 0.1,
        }
        real = jax.random.normal(jax.random.fold_in(rng, 4), (1, 4, C))
        # Tight capacity: exactly enough slots for the real tokens.
        out_ref, aux_ref = _moe_swiglu(real, moe, cfg, capacity=4)

        # Same real tokens preceded by 4 pads (arbitrary embeddings).
        pad = jax.random.normal(jax.random.fold_in(rng, 5), (1, 4, C))
        x = jnp.concatenate([pad, real], axis=1)  # [1, 8, C]
        valid = jnp.asarray([[False] * 4 + [True] * 4])
        out, aux = _moe_swiglu(x, moe, cfg, capacity=4, valid=valid)

        # Real tokens keep their no-pad outputs (pads claimed no slots)
        # and pads contribute zero delta.
        np.testing.assert_allclose(
            np.asarray(out[:, 4:]), np.asarray(out_ref), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :4]), 0.0, atol=1e-6
        )
        # Aux statistics computed over real tokens only.
        np.testing.assert_allclose(
            float(aux["moe_aux"]), float(aux_ref["moe_aux"]), atol=1e-5)
        np.testing.assert_allclose(
            float(aux["moe_z"]), float(aux_ref["moe_z"]), rtol=1e-5)
        # ... and counted over real tokens only: 4 tokens x top-1
        assert int(aux["tokens_per_expert"].sum()) == 4


class TestPaddedPackingLoss:
    def test_pad_positions_excluded_from_loss(self):
        """A padded packed row's loss must equal the unpadded sequence's
        loss: pad->pad pairs (segment -1) contribute nothing."""
        from dlrover_tpu.data.packing import pack_sequences
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(n_layer=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        doc = np.random.RandomState(0).randint(1, 250, size=(9,))
        tokens, segs = pack_sequences([doc], seq_len=16)
        assert (segs == -1).sum() > 0  # padding present
        packed_loss = float(
            llama.loss_fn(
                params,
                {"tokens": jnp.asarray(tokens),
                 "segment_ids": jnp.asarray(segs)},
                cfg, moe_aux_weight=0.0,
            )
        )
        plain_loss = float(
            llama.loss_fn(
                params, {"tokens": jnp.asarray(doc[None])}, cfg,
                moe_aux_weight=0.0,
            )
        )
        np.testing.assert_allclose(packed_loss, plain_loss, rtol=1e-5)


class TestMoEExactness:
    def test_dispatch_matches_per_token_math(self):
        """Capacity-dispatch MoE must equal the explicit per-token
        sum_k gate_k * expert_k(x) when nothing is dropped (regression:
        an off-by-(E-1) in the capacity position dropped every expert's
        FIRST token from the dispatch)."""
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(
            n_layer=2, num_experts=2, moe_every=2, dtype=jnp.float32
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        moe = params["layers"][1]["moe"]
        x = jax.random.normal(
            jax.random.PRNGKey(7), (2, 8, cfg.d_model), jnp.float32
        )
        toks = x.reshape(-1, cfg.d_model)
        probs = jax.nn.softmax(toks @ moe["router"], -1)
        gv, gi = jax.lax.top_k(probs, cfg.top_k)
        gv = gv / jnp.maximum(gv.sum(-1, keepdims=True), 1e-9)
        ref = jnp.zeros_like(toks)
        for n in range(toks.shape[0]):
            acc = 0
            for k in range(cfg.top_k):
                e = int(gi[n, k])
                h = jax.nn.silu(toks[n] @ moe["wg"][e]) * (
                    toks[n] @ moe["wi"][e]
                )
                acc = acc + gv[n, k] * (h @ moe["wo"][e])
            ref = ref.at[n].set(acc)
        out, _aux = llama._moe_swiglu(x, moe, cfg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.reshape(x.shape)), atol=1e-6
        )


class TestLongContextLlama:
    """Model-level long-context paths: llama trains with the sequence
    sharded over the mesh via ring attention / Ulysses SP, matching the
    single-device reference loss (SURVEY §5 long-context; reference
    distributed_attention.py:21 + sequence_parallel_optimization.py:9)."""

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_llama_loss_matches_reference(self, cpu_mesh_devices, impl):
        from dlrover_tpu.models import llama

        # fp32 + n_kv_head == n_head: ring/ulysses repeat KV heads so
        # GQA parity is exercised elsewhere; here the check is the
        # sequence-sharded attention itself.
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=4, dtype=jnp.float32,
            max_seq_len=128,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size
        )
        batch = {"tokens": tokens}
        ref = float(
            llama.loss_fn(params, batch, cfg, attn_impl="reference",
                          moe_aux_weight=0.0)
        )
        mesh = Mesh(
            np.array(cpu_mesh_devices[:4]).reshape(2, 2), ("dp", "tp")
        )
        with mesh:
            got = float(
                jax.jit(
                    lambda p, b: llama.loss_fn(
                        p, b, cfg, attn_impl=impl, mesh=mesh,
                        moe_aux_weight=0.0,
                    )
                )(params, batch)
            )
        np.testing.assert_allclose(got, ref, rtol=2e-5)

    def test_llama_trains_with_ring_attention(self, cpu_mesh_devices):
        """A few steps of real training through the ring path: loss
        falls (the long-context configuration is trainable end-to-end,
        not just a forward parity point)."""
        import optax

        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=4, max_seq_len=128
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = Mesh(
            np.array(cpu_mesh_devices[:2]).reshape(1, 2), ("dp", "tp")
        )
        tx = optax.adamw(5e-3)
        opt = tx.init(params)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 65), 0, 64
        )
        batch = {"tokens": tokens}

        @jax.jit
        def step(p, o, b):
            loss, g = jax.value_and_grad(
                lambda pp: llama.loss_fn(
                    pp, b, cfg, attn_impl="ring", mesh=mesh,
                    moe_aux_weight=0.0,
                )
            )(p)
            up, o = tx.update(g, o, p)
            return optax.apply_updates(p, up), o, loss

        with mesh:
            losses = []
            for _ in range(8):
                params, opt, loss = step(params, opt, batch)
                losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.3, losses


class TestPipelineCompiledHlo:
    def test_permute_count_per_tick_is_constant(self, cpu_mesh_devices):
        """Compiled evidence for the list-scheduler claim that fewer
        ticks mean fewer ICI hops: the executor is a scan whose BODY
        carries a fixed number of collective-permutes, so total hops =
        n_ticks x that constant.  Assert the per-body permute count is
        small and INDEPENDENT of the microbatch count (more microbatches
        must only add ticks, never per-tick collectives)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh

        from dlrover_tpu.parallel.pipeline import (
            interleave_stage_params,
            pipeline_value_and_grad_interleaved,
        )

        S, V = 2, 2
        d, vocab = 8, 16
        mesh = Mesh(np.array(cpu_mesh_devices[:S]), ("pp",))
        rng = jax.random.PRNGKey(0)
        virt = [
            {"w": jax.random.normal(jax.random.fold_in(rng, i), (d, d))}
            for i in range(S * V)
        ]
        pre = {"we": jax.random.normal(jax.random.fold_in(rng, 50),
                                       (vocab, d))}
        post = {"wo": jax.random.normal(jax.random.fold_in(rng, 51),
                                        (d, vocab))}
        stacked = interleave_stage_params(virt, S)

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        def pre_fn(p, tok):
            return p["we"][tok]

        def post_fn(p, x, tgt):
            logits = x @ p["wo"]
            lse = jax.nn.logsumexp(logits, -1)
            return jnp.mean(
                lse - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0]
            )

        def permute_count(M):
            micro_bs = 4
            B = M * micro_bs
            tok = jax.ShapeDtypeStruct((B,), jnp.int32)
            tgt = jax.ShapeDtypeStruct((B,), jnp.int32)
            txt = (
                jax.jit(
                    lambda sp, pr, po, a, b:
                    pipeline_value_and_grad_interleaved(
                        stage_fn, pre_fn, post_fn, sp, pr, po, a, b,
                        mesh, n_microbatches=M, n_chunks=V,
                    )
                )
                .lower(stacked, pre, post, tok, tgt)
                .compile()
                .as_text()
            )
            return txt.count("collective-permute(") + txt.count(
                "collective-permute-start("
            )

        c4, c8 = permute_count(4), permute_count(8)
        assert c4 == c8, (c4, c8)
        # A handful of permutes per tick (fwd hop, bwd hop, wrap
        # plumbing) — an executor that unrolled hops per microbatch
        # into the body would blow far past this.
        assert 0 < c4 <= 8, c4
