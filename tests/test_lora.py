"""LoRA fine-tuning: pytree factors + pure merge over the unchanged
llama machinery (reference: atorch llama2 fine-tuning's LoRA mode;
product surface + composition parity with
``atorch/examples/llama2/fsdp_llama2.py:116-127`` and
``atorch/atorch/tests/common_tests/fsdp_lora_load_test.py``)."""

import numpy as np

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.models import llama, lora


def _setup():
    cfg = llama.LlamaConfig.tiny(n_layer=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestLora:
    def test_merge_is_identity_at_init(self):
        cfg, params = _setup()
        l = lora.init_lora(jax.random.PRNGKey(1), params, rank=4)
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (2, 17), 0, cfg.vocab_size
        )
        base = llama.loss_fn(params, {"tokens": tokens}, cfg,
                             moe_aux_weight=0.0)
        merged = llama.loss_fn(lora.merge(params, l), {"tokens": tokens},
                               cfg, moe_aux_weight=0.0)
        np.testing.assert_allclose(float(base), float(merged), rtol=1e-6)

    def test_lora_trains_factors_only(self):
        cfg, params = _setup()
        l = lora.init_lora(jax.random.PRNGKey(1), params, rank=8,
                           targets=lora.ATTN_TARGETS + lora.MLP_TARGETS)
        assert lora.num_lora_params(l) < 0.2 * llama.num_params(params)
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (4, 17), 0, 64
        )
        batch = {"tokens": tokens}
        tx = optax.masked(optax.adamw(1e-2), lora.trainable_mask(l))
        opt = tx.init(l)

        @jax.jit
        def step(l, opt):
            loss, g = jax.value_and_grad(
                lambda ll: llama.loss_fn(
                    lora.merge(params, ll), batch, cfg,
                    moe_aux_weight=0.0,
                )
            )(l)
            up, opt = tx.update(g, opt, l)
            return optax.apply_updates(l, up), opt, loss

        losses = []
        for _ in range(10):
            l, opt, loss = step(l, opt)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.3, losses
        # Base params untouched by construction; decode works on the
        # merged tree through the standard machinery.
        from dlrover_tpu.models import llama_infer

        out = llama_infer.generate(
            lora.merge(params, l), cfg, tokens[:, :5], max_new_tokens=3,
            temperature=0.0,
        )
        assert out.shape == (4, 8)

    def test_targets_subset(self):
        cfg, params = _setup()
        l = lora.init_lora(jax.random.PRNGKey(1), params, rank=2,
                           targets=("wq",))
        assert set(l["layers"][0].keys()) == {"wq"}


def _lora_problem(n_layer=2, seq=16, batch=8, **cfg_over):
    cfg = llama.LlamaConfig.tiny(n_layer=n_layer, max_seq_len=seq,
                                 **cfg_over)
    base = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)
    ).astype("int32")
    return cfg, base, toks


class TestLoraCompose:
    """LoRA x {fsdp, pp, checkpoint-resume} through the PRODUCT
    path (accelerate's ``frozen`` state) — the claims lora.py used to
    make without tests (round-3 review Weak #5)."""

    def test_lora_fsdp_sharded_base_trained_factors(
        self, cpu_mesh_devices
    ):
        from dlrover_tpu.parallel.accelerate import Strategy, accelerate
        from dlrover_tpu.parallel.mesh import MeshSpec

        cfg, base, toks = _lora_problem()

        def loss_fn(factors, batch, frozen):
            return llama.loss_fn(lora.merge(frozen, factors), batch, cfg)

        job = accelerate(
            loss_fn=loss_fn,
            init_fn=lambda r: lora.init_lora(r, base, rank=4),
            optimizer=optax.masked(optax.adamw(1e-2),
                                   lora.trainable_mask),
            sample_batch={"tokens": toks},
            strategy=Strategy(mesh=MeshSpec(dp=2, fsdp=4)),
            devices=cpu_mesh_devices[:8],
            frozen=base,
        )
        state = job.create_state(jax.random.PRNGKey(2))
        # Base is sharded on fsdp (ZeRO-3 placement), factors exist.
        wq_spec = state["frozen"]["layers"][0]["wq"].sharding.spec
        assert "fsdp" in str(wq_spec)
        batch = {"tokens": jnp.asarray(toks)}
        losses = []
        for _ in range(8):
            state, m = job.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.05, losses
        # The frozen base never moves; the factors do.
        for a, b in zip(
            jax.tree_util.tree_leaves(state["frozen"]),
            jax.tree_util.tree_leaves(base),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(
            jnp.abs(state["params"]["layers"][0]["wq"]["b"]).max()
        ) > 0

    def test_lora_pp_grads_match_dense_merge(self, cpu_mesh_devices):
        """Pipelined loss over the merged tree: grads wrt the FACTORS
        through pp=2 match the unpipelined merge path."""
        from jax.sharding import Mesh

        from dlrover_tpu.models import llama_pp

        cfg, base, toks = _lora_problem(n_layer=4, batch=4)
        l0 = lora.init_lora(jax.random.PRNGKey(1), base, rank=4)
        # B starts at 0 (merge == identity); perturb so grads are
        # non-trivial through both factor matrices.
        l0 = jax.tree_util.tree_map(
            lambda x: x + 0.01 if getattr(x, "ndim", 0) == 2 else x, l0
        )
        batch = {"tokens": jnp.asarray(toks[:, :34])}
        mesh = Mesh(
            np.array(cpu_mesh_devices[:8]).reshape(2, 2, 2),
            ("pp", "fsdp", "tp"),
        )

        def pp_loss(factors):
            return llama_pp.pipeline_loss_fn(
                lora.merge(base, factors), batch, cfg, mesh,
                n_microbatches=2,
            )

        def dense_loss(factors):
            return llama.loss_fn(
                lora.merge(base, factors), batch, cfg,
                attn_impl="reference", moe_aux_weight=0.0,
            )

        lp, gp = jax.jit(jax.value_and_grad(pp_loss))(l0)
        ld, gd = jax.jit(jax.value_and_grad(dense_loss))(l0)
        np.testing.assert_allclose(float(lp), float(ld), atol=2e-3)
        # ~2% relative slack: the pipelined scan and the dense path
        # reduce microbatch contributions in different orders.
        for a, b in zip(
            jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gd)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1.5e-2
            )

    def test_abstract_frozen_streams_in_after_compile(
        self, cpu_mesh_devices
    ):
        """The 7B flow: accelerate() gets SHAPES for the frozen base,
        candidates score on sharded zeros (no base transfer), and the
        real weights arrive via create_state(frozen_values=...) already
        sharded."""
        from dlrover_tpu.parallel.accelerate import Strategy, accelerate
        from dlrover_tpu.parallel.mesh import MeshSpec

        cfg, base, toks = _lora_problem()
        abstract = jax.eval_shape(lambda: base)

        def loss_fn(factors, batch, frozen):
            return llama.loss_fn(lora.merge(frozen, factors), batch, cfg)

        job = accelerate(
            loss_fn=loss_fn,
            init_fn=lambda r: lora.init_lora(r, abstract, rank=4),
            optimizer=optax.masked(optax.adamw(1e-2),
                                   lora.trainable_mask),
            sample_batch={"tokens": toks},
            # Two candidates + profiling exercises the zeros-scoring
            # path (no concrete base exists to score with).
            strategy=[
                Strategy(mesh=MeshSpec(dp=4)),
                Strategy(mesh=MeshSpec(dp=2, fsdp=2)),
            ],
            profile_steps=1,
            devices=cpu_mesh_devices[:4],
            frozen=abstract,
        )
        # Without frozen_values: zeros (scoring default).
        z = job.create_state(jax.random.PRNGKey(0), frozen_values="zeros")
        assert float(jnp.abs(z["frozen"]["embed"]).max()) == 0.0
        # Stream the real weights leaf-by-leaf onto the frozen sharding.
        sharded = jax.tree_util.tree_map(
            jax.device_put, base, job.state_sharding["frozen"]
        )
        state = job.create_state(
            jax.random.PRNGKey(0), frozen_values=sharded
        )
        batch = {"tokens": jnp.asarray(toks)}
        l0 = None
        for i in range(6):
            state, m = job.train_step(state, batch)
            if i == 0:
                l0 = float(m["loss"])
        assert float(m["loss"]) < l0

    def test_lora_ckpt_resume_equivalence(self, tmp_path,
                                          cpu_mesh_devices):
        """Save the factor tree (NOT the base) mid-run, restore into a
        fresh job, continue: trajectories match the uninterrupted run."""
        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer
        from dlrover_tpu.parallel.accelerate import Strategy, accelerate
        from dlrover_tpu.parallel.mesh import MeshSpec

        cfg, base, toks = _lora_problem()

        def loss_fn(factors, batch, frozen):
            return llama.loss_fn(lora.merge(frozen, factors), batch, cfg)

        def mk_job():
            return accelerate(
                loss_fn=loss_fn,
                init_fn=lambda r: lora.init_lora(r, base, rank=4),
                optimizer=optax.masked(optax.adamw(1e-2),
                                       lora.trainable_mask),
                sample_batch={"tokens": toks},
                strategy=Strategy(mesh=MeshSpec(dp=2, fsdp=2)),
                devices=cpu_mesh_devices[:4],
                frozen=base,
            )

        batch = {"tokens": jnp.asarray(toks)}
        job = mk_job()
        state = job.create_state(jax.random.PRNGKey(2))
        # Uninterrupted 6-step trajectory.
        ref_state = state
        for _ in range(6):
            ref_state, ref_m = job.train_step(ref_state, batch)

        # 3 steps, factor-only save, fresh job + restore, 3 more.
        state = job.create_state(jax.random.PRNGKey(2))
        for _ in range(3):
            state, _ = job.train_step(state, batch)
        ck = FlashCheckpointer(str(tmp_path / "ck"), job_name="lora-eq")
        saved = {k: v for k, v in state.items() if k != "frozen"}
        ck.save(saved, meta={"step": 3}, storage=True)
        ck.wait()
        # The factor checkpoint must not contain the base model.
        import os

        total = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(tmp_path / "ck") for f in fs
        )
        base_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(base)
        )
        assert total < base_bytes / 2, (total, base_bytes)

        job2 = mk_job()
        state2 = job2.create_state(jax.random.PRNGKey(7))  # different rng
        target = {k: v for k, v in state2.items() if k != "frozen"}
        got, meta = ck.load(target=target)
        assert int(meta["step"]) == 3
        state2 = dict(got, frozen=state2["frozen"])
        for _ in range(3):
            state2, m2 = job2.train_step(state2, batch)
        np.testing.assert_allclose(
            float(m2["loss"]), float(ref_m["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(state2["params"]),
            jax.tree_util.tree_leaves(ref_state["params"]),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6
            )
