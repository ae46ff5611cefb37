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


def _with_extra(out) -> tuple:
    """``(hidden, loss, extra)`` of a ``hidden_and_loss``; ``extra`` is None
    where the block makes no discrete choice (the adapter contract,
    ``benchmark/run.py``)."""
    hidden, loss, *extra = out
    return hidden, loss, (extra[0] if extra else None)


def _choice_distances(choices: dict, own: dict, probs: dict) -> dict:
    """The system's discrete ``choices`` (by name: ints ``[..., k]``) against
    the reference's ``own`` choices and the float32 ``probs`` (``[..., n]``)
    it made them from.  Per name: the share of tokens where the system took
    another set than the reference would have, and the largest amount by
    which the reference's probability of something the system took lies
    under that of the reference's weakest pick.  Rounding flips a choice
    only near a tie, so that amount stays small; a wrong choice is far
    from a tie."""
    import jax
    import jax.numpy as jnp

    share, gap = {}, {}
    for name, chosen in choices.items():
        p = probs[name]
        member = lambda idx: jnp.sum(  # noqa: E731
            jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.int32), axis=-2)
        share[name] = jnp.mean(
            jnp.any(member(chosen) != member(own[name]), axis=-1))
        weakest = jnp.min(jnp.take_along_axis(p, own[name], axis=-1), -1)
        gap[name] = jnp.max(jnp.maximum(
            weakest[..., None] - jnp.take_along_axis(p, chosen, axis=-1), 0))
    return {"choice_diff_share": share, "choice_prob_gap": gap}


def comparison_programs(cell: dict, mc, ref_cfg=None):
    """Three functions to jit, kept apart so that the system's backward and
    the reference's never hold the chip's memory together:

    ``system(params, tokens) -> (loss, hidden, grads, extra)``,
    ``against_reference(params, tokens, loss, hidden, grads, extra) ->
    distances`` and, for a configuration whose adapter returns ``extra``
    alone, ``independent(params, tokens, loss, hidden, extra) -> distances``
    (forward only).  ``ref_cfg`` replaces the configuration the REFERENCE
    computes (the fault probe: a dropped window must be found)."""
    import jax
    import jax.numpy as jnp

    cfg = cell["config_data"]
    adapter = common.adapter_of(cfg)
    reference = common.load_module("reference", cfg["reference"])
    ref_cfg = ref_cfg or cfg

    def system(p, t):
        def loss_of(leaves):
            hidden, loss, extra = _with_extra(adapter.hidden_and_loss(
                adapter.with_leaves(p, leaves), t, mc))
            return loss, (hidden, extra)

        (loss, (hidden, extra)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(adapter.grad_leaves(p))
        return loss, hidden, grads, extra

    def against_reference(p, t, loss, hidden, grads, extra):
        # a routed reference computes the experts the SYSTEM chose
        given = {} if extra is None else {"given": extra["choices"]}

        def loss_of(leaves):
            hidden_r, loss_r, extra_r = _with_extra(
                reference.hidden_and_loss(
                    adapter.with_leaves(p, leaves), t, ref_cfg, **given))
            return loss_r, (hidden_r, extra_r)

        (loss_r, (hidden_r, extra_r)), grads_r = jax.value_and_grad(
            loss_of, has_aux=True)(adapter.grad_leaves(p))
        # the part of the sequence past the window, where a dropped window
        # would show undiluted
        w = cfg.get("sliding_window") or 0
        cut = w if 0 < w < hidden.shape[1] else 0
        out = {
            "reference_loss": loss_r,
            "loss_rel_diff": jnp.abs(loss - loss_r) / jnp.abs(loss_r),
            "hidden_rel_l2": _rel_l2(hidden, hidden_r),
            "hidden_rel_l2_past_window": _rel_l2(
                hidden[:, cut:], hidden_r[:, cut:]),
            "grad_rel_l2": {k: _rel_l2(g.astype(jnp.float32), grads_r[k])
                            for k, g in grads.items()},
        }
        if extra is not None:
            out.update(_choice_distances(
                extra["choices"], extra_r["choices"], extra_r["probs"]))
            out["scalar_rel_diff"] = {
                k: jnp.abs(v - extra_r["scalars"][k])
                / jnp.abs(extra_r["scalars"][k])
                for k, v in extra["scalars"].items()}
        return out

    def independent(p, t, loss, hidden, extra):
        hidden_r, loss_r, extra_r = reference.hidden_and_loss(p, t, ref_cfg)
        return {
            "loss_rel_diff_independent":
                jnp.abs(loss - loss_r) / jnp.abs(loss_r),
            "hidden_rel_l2_independent": _rel_l2(hidden, hidden_r),
            "choice_diff_share_independent": _choice_distances(
                extra["choices"], extra_r["choices"],
                extra_r["probs"])["choice_diff_share"],
        }

    return system, against_reference, independent


def _worst(by_name: dict) -> tuple:
    """(name, value) of the largest; ("", 0.0) of none (a routed block
    whose loss has no further scalar)."""
    name = max(by_name, key=by_name.get, default="")
    return name, by_name.get(name, 0.0)


def check_against_reference(job, mc, cell: dict, params, seed: int,
                            ref_cfg=None) -> dict:
    """System forward and backward (kernels, bf16, remat, the job's mesh and
    layout) against the plain reference and its ``jax.grad`` on seeded
    sequences, one per data-parallel shard.  The optimizer pass (optax) is
    not compared.  Returns the distances and ``ok``.

    Where the adapter returns ``extra`` (a routed block) the reference
    computes the experts the system chose, so that hidden states, loss and
    gradients are held to the SAME tolerances as any dense block; the
    choices themselves are held to the adapter's two limits against the
    reference's own probabilities, the further scalars of the loss to its
    third; and the distances to the reference routing for itself are
    reported and judge nothing: one token in twenty sits on a near tie that
    bf16 rounding decides, and is 15 % away (PERF.md section 4)."""
    import jax

    traffic, cfg = cell["traffic_data"], cell["config_data"]
    n_seq = comparison_sequences(cell)
    toks = sample_tokens(seed + 7919, range(10**6, 10**6 + n_seq),
                         traffic["seq_len"], cfg["vocab_size"])
    system, against_reference, independent = comparison_programs(
        cell, mc, ref_cfg)
    batch = jax.make_array_from_process_local_data(
        job.batch_sharding["tokens"], toks)
    apart = {}
    with jax.set_mesh(job.mesh):
        loss, hidden, grads, extra = jax.jit(system)(params, batch)
        out = jax.jit(against_reference)(
            params, batch, loss, hidden, grads, extra)
        del grads
        if extra is not None:
            apart = jax.jit(independent)(params, batch, loss, hidden, extra)
    del hidden
    out, apart = jax.tree_util.tree_map(float, jax.device_get((out, apart)))
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
    routed = {}
    if extra is not None:
        adapter = common.adapter_of(cfg)
        share_at, share = _worst(out.pop("choice_diff_share"))
        gap_at, gap = _worst(out.pop("choice_prob_gap"))
        scalar_at, scalar = _worst(out.pop("scalar_rel_diff"))
        routed = {
            "choice_diff_share": share, "choice_diff_share_at": share_at,
            "choice_diff_share_tol":
                adapter.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * layers ** 0.5,
            "choice_prob_gap": gap, "choice_prob_gap_at": gap_at,
            "choice_prob_gap_tol":
                adapter.CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER * layers ** 0.5,
            "scalar_rel_diff": scalar, "scalar_rel_diff_at": scalar_at,
            "scalar_rel_tol": adapter.SCALAR_REL_TOL,
            "loss_rel_diff_independent": apart["loss_rel_diff_independent"],
            "hidden_rel_l2_independent": apart["hidden_rel_l2_independent"],
            "choice_diff_share_independent": _worst(
                apart["choice_diff_share_independent"])[1],
        }
        ok = bool(
            ok and np.isfinite([share, gap, scalar]).all()
            and share <= routed["choice_diff_share_tol"]
            and gap <= routed["choice_prob_gap_tol"]
            and scalar <= routed["scalar_rel_tol"])
    return dict(out, ok=ok, system_loss=float(loss),
                hidden_rel_tol=hidden_rel_tol(layers),
                grad_rel_l2_worst_by_leaf_kind=grads_by_kind,
                grad_rel_l2_worst_leaf=worst,
                grad_rel_tol=grad_rel_tol(layers), sequences=int(n_seq),
                **routed)
