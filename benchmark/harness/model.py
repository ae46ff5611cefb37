"""Glue between the benchmark's data files and the system under test: the
configuration's adapter and reference found by name, seeded tokens, the
accelerated job as a user builds it, and the comparison with the plain
reference (forward AND gradients)."""

from __future__ import annotations

import numpy as np

from benchmark.harness import common

#: Relative L2 tolerance on the final-norm hidden states, system (bf16
#: compute, Pallas kernels) against the float32 reference, per square root
#: of the depth: bf16 keeps 8 bits, every matmul and the kernels' bf16
#: probabilities round to ~0.4 %, and the roundings of successive residual
#: blocks add like a random walk.  Measured on the v5e (PR 22): 1.86 % at 2
#: layers (1.3 % per sqrt(layer)), 3.52 % at 8.  fp8 or int8 matmuls move
#: them by ~5 % a layer.  A reference with the window dropped is 62 % away
#: at published width (87 % past the window; fault_probe.py, PR 22).
HIDDEN_REL_TOL_PER_SQRT_LAYER = 2e-2
#: The mean loss of 8,192+ tokens averages the per-token rounding away:
#: measured difference ~1e-4 relative; 2e-3 allows bf16 logits.
LOSS_REL_TOL = 2e-3
#: Relative L2 tolerance on each compared gradient leaf (the adapter's
#: ``grad_leaves``: q, k, v projections and the embedding), system backward
#: (flash_bwd_dq/dkv, remat recompute, bf16) against ``jax.grad`` of the
#: float32 reference, per square root of the depth.  Measured on the v5e at
#: 2 layers (PR 22): wq and wk 4.2 % (ds = p * (dp - delta) cancels in
#: bf16), wv 2.5 %, embed 2.3 %, the same to three digits for every seed;
#: a reference with the window dropped is 67-68 % away in every leaf, with
#: the window halved 95-97 % (benchmark/harness/fault_probe.py at published
#: width).  8 % x sqrt(layers) is 2.7x the rounding at 2 layers and leaves
#: a planted fault 3x outside at 8.
GRAD_REL_TOL_PER_SQRT_LAYER = 8e-2


def hidden_rel_tol(n_layers: int) -> float:
    return HIDDEN_REL_TOL_PER_SQRT_LAYER * n_layers ** 0.5


def grad_rel_tol(n_layers: int) -> float:
    return GRAD_REL_TOL_PER_SQRT_LAYER * n_layers ** 0.5


def sample_tokens(seed: int, indices, seq_len: int, vocab: int) -> np.ndarray:
    """[len(indices), seq_len + 1] int32: the tokens of each sample index,
    a pure function of (seed, index) — the same in every incarnation."""
    rows = [
        np.random.default_rng([seed, int(i)]).integers(
            0, vocab, size=seq_len + 1, dtype=np.int32)
        for i in indices
    ]
    return np.stack(rows, axis=0)


def build_job(cell: dict, devices=None):
    """``accelerate()`` as a user of the framework calls it, at the sizes
    the cell's files give.  Returns ``(job, model_config)``."""
    import jax
    import optax

    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    traffic, cfg = cell["traffic_data"], cell["config_data"]
    adapter = common.adapter_of(cfg)
    mc = adapter.model_config(cfg, remat_block=cell["remat_block"],
                              seq_len=traffic["seq_len"])
    sample = np.zeros(
        (cell["batch_sequences"], traffic["seq_len"] + 1), np.int32)
    job = accelerate(
        loss_fn=adapter.loss_fn(mc),
        init_fn=adapter.init_fn(mc),
        optimizer=optax.adamw(traffic["learning_rate"]),
        sample_batch={"tokens": sample},
        strategy=Strategy(mesh=MeshSpec(**cell["mesh"])),
        param_specs="planner",
        # on the chip the device count equals `chips` (checked before);
        # a rehearsal has four virtual devices for every cell
        devices=devices or jax.devices()[:cell["chips"]],
    )
    return job, mc


def _rel_l2(a, b):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum(jnp.square(a - b))) / jnp.sqrt(
        jnp.sum(jnp.square(b)))


def comparison_sequences(cell: dict) -> int:
    """One seeded sequence per data-parallel shard of the cell's mesh."""
    return max(1, cell["mesh"].get("fsdp", 1) * cell["mesh"].get("dp", 1))


def comparison_programs(cell: dict, mc, ref_cfg=None):
    """Two functions to jit, kept apart so that the system's backward and
    the reference's never hold the chip's memory together:

    ``system(params, tokens) -> (loss, hidden, grads)`` and
    ``against_reference(params, tokens, loss, hidden, grads) -> distances``.
    ``ref_cfg`` replaces the configuration the REFERENCE computes (the
    fault probe: a dropped window must be found)."""
    import jax
    import jax.numpy as jnp

    cfg = cell["config_data"]
    adapter = common.adapter_of(cfg)
    reference = common.load_module("reference", cfg["reference"])
    ref_cfg = ref_cfg or cfg

    def system(p, t):
        def loss_of(leaves):
            hidden, loss = adapter.hidden_and_loss(
                adapter.with_leaves(p, leaves), t, mc)
            return loss, hidden

        (loss, hidden), grads = jax.value_and_grad(loss_of, has_aux=True)(
            adapter.grad_leaves(p))
        return loss, hidden, grads

    def against_reference(p, t, loss, hidden, grads):
        def loss_of(leaves):
            hidden_r, loss_r = reference.hidden_and_loss(
                adapter.with_leaves(p, leaves), t, ref_cfg)
            return loss_r, hidden_r

        (loss_r, hidden_r), grads_r = jax.value_and_grad(
            loss_of, has_aux=True)(adapter.grad_leaves(p))
        # the part of the sequence past the window, where a dropped window
        # would show undiluted
        w = cfg.get("sliding_window") or 0
        cut = w if 0 < w < hidden.shape[1] else 0
        return {
            "reference_loss": loss_r,
            "loss_rel_diff": jnp.abs(loss - loss_r) / jnp.abs(loss_r),
            "hidden_rel_l2": _rel_l2(hidden, hidden_r),
            "hidden_rel_l2_past_window": _rel_l2(
                hidden[:, cut:], hidden_r[:, cut:]),
            "grad_rel_l2": {k: _rel_l2(g.astype(jnp.float32), grads_r[k])
                            for k, g in grads.items()},
        }

    return system, against_reference


def check_against_reference(job, mc, cell: dict, params, seed: int,
                            ref_cfg=None) -> dict:
    """System forward and backward (kernels, bf16, remat, the job's mesh and
    layout) against the plain reference and its ``jax.grad`` on seeded
    sequences, one per data-parallel shard.  The optimizer pass (optax) is
    not compared.  Returns the distances and ``ok``."""
    import jax

    traffic, cfg = cell["traffic_data"], cell["config_data"]
    n_seq = comparison_sequences(cell)
    toks = sample_tokens(seed + 7919, range(10**6, 10**6 + n_seq),
                         traffic["seq_len"], cfg["vocab_size"])
    system, against_reference = comparison_programs(cell, mc, ref_cfg)
    batch = jax.make_array_from_process_local_data(
        job.batch_sharding["tokens"], toks)
    with jax.set_mesh(job.mesh):
        loss, hidden, grads = jax.jit(system)(params, batch)
        out = jax.jit(against_reference)(params, batch, loss, hidden, grads)
    del hidden, grads
    out = jax.tree_util.tree_map(float, jax.device_get(out))
    layers = cfg["num_hidden_layers"]
    worst = max(out["grad_rel_l2"], key=out["grad_rel_l2"].get)
    flat = [out["reference_loss"], out["loss_rel_diff"], out["hidden_rel_l2"],
            out["hidden_rel_l2_past_window"], *out["grad_rel_l2"].values()]
    ok = bool(
        np.isfinite(flat).all()
        and out["loss_rel_diff"] <= LOSS_REL_TOL
        and max(out["hidden_rel_l2"], out["hidden_rel_l2_past_window"])
        <= hidden_rel_tol(layers)
        and out["grad_rel_l2"][worst] <= grad_rel_tol(layers))
    grads_by_kind = {}
    for name, v in out.pop("grad_rel_l2").items():
        kind = name.rsplit(".", 1)[-1]
        grads_by_kind[kind] = max(grads_by_kind.get(kind, 0.0), v)
    return dict(out, ok=ok, system_loss=float(loss),
                hidden_rel_tol=hidden_rel_tol(layers),
                grad_rel_l2_worst_by_leaf_kind=grads_by_kind,
                grad_rel_l2_worst_leaf=worst,
                grad_rel_tol=grad_rel_tol(layers), sequences=int(n_seq))
