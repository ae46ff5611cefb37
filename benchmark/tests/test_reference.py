"""The plain reference against the system at toy widths on the CPU, forward
and gradients, and the proof that the tolerances separate rounding from a
fault: a dropped window, a halved window and a wrong GQA grouping each
fail them."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import llama_dense
from benchmark.harness import model
from benchmark.harness.common import BENCH_DIR
from benchmark.reference import mistral_ref


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(BENCH_DIR, "configs", "rehearsal-tiny.json")) as f:
        cfg = json.load(f)
    from dlrover_tpu.models import llama

    seq = cfg["rehearsal_seq_len"]
    lcfg = llama_dense.model_config(cfg, remat_block=False, seq_len=seq)
    params = llama.init_params(jax.random.PRNGKey(0), lcfg)
    # larger than init's 0.02 so that attention matters to the output
    params = jax.tree_util.tree_map(
        lambda x: x * 4.0 if x.ndim == 2 else x, params)
    toks = model.sample_tokens(3, range(2), seq, cfg["vocab_size"])
    return cfg, lcfg, params, jnp.asarray(toks)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _system_hidden(lcfg, params, toks, dtype):
    import dataclasses

    from dlrover_tpu.models import llama

    lc = dataclasses.replace(lcfg, dtype=dtype)
    h, loss = llama_dense.hidden_and_loss(params, toks, lc)
    # the adapter's path is the step's own loss
    assert float(loss) == pytest.approx(
        float(llama.loss_fn(params, {"tokens": toks}, lc)), rel=1e-6)
    return h, float(loss)


def _grad_distances(lcfg, params, toks, dtype, ref_cfg, ref_params=None):
    """Worst relative L2 per kind of leaf, system gradients against
    ``jax.grad`` of the reference (the arithmetic of
    ``model.comparison_programs``)."""
    import dataclasses

    lc = dataclasses.replace(lcfg, dtype=dtype)

    def grads(fn, p, cfg):
        return jax.grad(lambda lv: fn(
            llama_dense.with_leaves(p, lv), toks, cfg)[1])(
                llama_dense.grad_leaves(p))

    gs = grads(llama_dense.hidden_and_loss, params, lc)
    gr = grads(mistral_ref.hidden_and_loss, ref_params or params, ref_cfg)
    worst = {}
    for k in gs:
        kind = k.rsplit(".", 1)[-1]
        worst[kind] = max(worst.get(kind, 0.0),
                          _rel(gs[k].astype(jnp.float32), gr[k]))
    return worst


def test_system_in_float32_equals_the_reference(tiny):
    cfg, lcfg, params, toks = tiny
    h_ref, loss_ref = mistral_ref.hidden_and_loss(params, toks, cfg)
    h_sys, loss_sys = _system_hidden(lcfg, params, toks, jnp.float32)
    assert _rel(h_sys, h_ref) < 1e-4
    assert abs(loss_sys - float(loss_ref)) / float(loss_ref) < 1e-5


def test_system_in_bfloat16_is_inside_the_tolerance(tiny):
    cfg, lcfg, params, toks = tiny
    h_ref, loss_ref = mistral_ref.hidden_and_loss(params, toks, cfg)
    h_sys, loss_sys = _system_hidden(lcfg, params, toks, jnp.bfloat16)
    assert _rel(h_sys, h_ref) < model.hidden_rel_tol(2)
    assert (abs(loss_sys - float(loss_ref)) / float(loss_ref)
            < model.LOSS_REL_TOL)


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-4), (jnp.bfloat16, model.grad_rel_tol(2))])
def test_system_gradients_are_the_references(tiny, dtype, tol):
    cfg, lcfg, params, toks = tiny
    worst = _grad_distances(lcfg, params, toks, dtype, cfg)
    assert sorted(worst) == ["embed", "wk", "wq", "wv"]
    assert max(worst.values()) < tol, worst


def test_query_blocks_do_not_change_the_reference(tiny):
    cfg, _, params, toks = tiny
    a, _ = mistral_ref.hidden_and_loss(params, toks, cfg, q_block=16)
    b, _ = mistral_ref.hidden_and_loss(params, toks, cfg, q_block=64)
    assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("fault", ["no_window", "half_window", "wrong_gqa"])
def test_a_fault_is_outside_the_tolerance(tiny, fault):
    cfg, lcfg, params, toks = tiny
    h_ref, _ = mistral_ref.hidden_and_loss(params, toks, cfg)
    if fault == "no_window":
        bad = dict(cfg, sliding_window=0)
    elif fault == "half_window":
        bad = dict(cfg, sliding_window=cfg["sliding_window"] // 2)
    else:  # head h reads KV head h % KV instead of h // rep
        bad = dict(cfg)
        perm = np.arange(cfg["num_attention_heads"]).reshape(
            cfg["num_key_value_heads"], -1).T.reshape(-1)
        hd = cfg["head_dim"]
        cols = (perm[:, None] * hd + np.arange(hd)[None]).reshape(-1)
        params = dict(params, layers=[
            dict(ly, wq=ly["wq"][:, cols], wo=ly["wo"][cols, :])
            for ly in params["layers"]])
        h_ref2, _ = mistral_ref.hidden_and_loss(params, toks, bad)
        # permuting q heads and wo rows together regroups which KV head
        # each query head reads and changes nothing else
        assert _rel(h_ref2, h_ref) > 3 * model.hidden_rel_tol(2)
        return
    h_bad, _ = mistral_ref.hidden_and_loss(params, toks, bad)
    w = cfg["sliding_window"]
    assert _rel(h_bad[:, w:], h_ref[:, w:]) > 3 * model.hidden_rel_tol(2)


@pytest.mark.parametrize("scale", [0.25, 1.0])
@pytest.mark.parametrize("fault", ["no_window", "half_window", "wrong_gqa"])
def test_a_fault_is_outside_the_gradient_tolerance(tiny, fault, scale):
    """At the fixture's weights (init x 4) and at init itself (x 1, the
    state the chip checks): the q, k, v gradients of a faulty attention
    are more than two tolerances away
    (at published width: six, PERF.md section 6)."""
    cfg, lcfg, params, toks = tiny
    params = jax.tree_util.tree_map(
        lambda x: x * scale if x.ndim == 2 else x, params)
    ref_cfg, ref_params = cfg, None
    if fault == "no_window":
        ref_cfg = dict(cfg, sliding_window=0)
    elif fault == "half_window":
        ref_cfg = dict(cfg, sliding_window=cfg["sliding_window"] // 2)
    else:  # the reference's heads regrouped: h reads KV head h % KV
        perm = np.arange(cfg["num_attention_heads"]).reshape(
            cfg["num_key_value_heads"], -1).T.reshape(-1)
        hd = cfg["head_dim"]
        cols = (perm[:, None] * hd + np.arange(hd)[None]).reshape(-1)
        ref_params = dict(params, layers=[
            dict(ly, wq=ly["wq"][:, cols], wo=ly["wo"][cols, :])
            for ly in params["layers"]])
    worst = _grad_distances(lcfg, params, toks, jnp.bfloat16, ref_cfg,
                            ref_params)
    # (the regrouped reference returns dq in its permuted column order, so
    # only the k and v gradients are compared in every case)
    assert min(worst[k] for k in ("wk", "wv")) > 2 * model.grad_rel_tol(2), (
        worst)


def test_check_against_reference_finds_a_planted_fault():
    """The whole comparison as a steady cell runs it (the job's mesh, two
    jitted programs), at toy width on four virtual devices: the true
    reference passes, a reference without the window does not."""
    from benchmark.harness import common

    cell = common.rehearsal_cell(
        common.load_cell("mistral7b-l8.train-fsdp2tp2"))
    job, mc = model.build_job(cell, devices=jax.devices()[:4])
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    good = model.check_against_reference(job, mc, cell, params, 0)
    assert good["ok"] and good["sequences"] == 2, good
    assert sorted(good["grad_rel_l2_worst_by_leaf_kind"]) == [
        "embed", "wk", "wq", "wv"]
    bad = model.check_against_reference(
        job, mc, cell, params, 0,
        ref_cfg=dict(cell["config_data"], sliding_window=0))
    assert not bad["ok"]
    assert bad["grad_rel_l2_worst_by_leaf_kind"]["wk"] > 2 * bad[
        "grad_rel_tol"], bad
