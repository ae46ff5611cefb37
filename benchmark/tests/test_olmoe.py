"""The OLMoE configuration's adapter and reference under
``check_against_reference`` at toy width (``configs/olmoe-rehearsal.json``,
CPU): the system — the program's sorted, dropless routed block, q/k RMSNorm,
unnormalised top-k, all-k balance count, z-loss — reads ``ok``; both planted
routed faults, a dropped q/k norm, a first-choice balance count and a z
weight off by a tenth do not; and the four readers of the routed block's
per-layer metrics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import olmoe
from benchmark.harness import common, fault_probe, model, moe_read
from benchmark.reference import olmoe_ref

TOY = common.load_json("configs", "olmoe-rehearsal.json")
CELL = {
    "name": "olmoe-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": False,
    "traffic_data": {"seq_len": 256, "learning_rate": 3e-4},
}


@pytest.fixture(scope="module")
def toy():
    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    return job, mc, params


def _check(toy, mc=None, ref_cfg=None, params=None):
    job, toy_mc, toy_params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params or toy_params, 0, ref_cfg=ref_cfg)


def _decisive_router(params):
    """At initialisation every expert is as likely as the next, and the
    balance term reads 1 however it counts.  A router 200 times larger
    prefers some experts, as a trained one does."""
    layer = params["layers"][0]
    moe = dict(layer["moe"], router=200.0 * layer["moe"]["router"])
    return dict(params, layers=[dict(layer, moe=moe)])


def test_the_adapter_says_what_the_configuration_says():
    mc = olmoe.model_config(TOY, remat_block=False, seq_len=64)
    assert (mc.num_experts, mc.top_k, mc.moe_every) == (8, 2, 1)
    assert mc.capacity_factor is None  # no token is dropped
    assert (mc.norm_topk_prob, mc.balance_all_k, mc.qk_norm) == (
        False, True, True)
    assert (olmoe.AUX_WEIGHT, olmoe.Z_WEIGHT) == (
        olmoe_ref.ROUTER_AUX_LOSS_COEF, olmoe_ref.ROUTER_Z_LOSS_COEF)
    with pytest.raises(ValueError, match="sliding_window"):
        olmoe.model_config(dict(TOY, sliding_window=32), remat_block=False,
                           seq_len=64)
    with pytest.raises(ValueError, match="clip_qkv"):
        olmoe.model_config(dict(TOY, clip_qkv=8.0), remat_block=False,
                           seq_len=64)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="qk_norm"):
        olmoe.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss, extra = olmoe.hidden_and_loss(params, toks, mc)
    own, counters = olmoe.loss_fn(mc)(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert float(own) == pytest.approx(float(llama.loss_fn(
        params, {"tokens": toks}, mc, moe_aux_weight=olmoe.AUX_WEIGHT,
        moe_z_weight=olmoe.Z_WEIGHT)), rel=1e-6)
    chosen = extra["choices"][olmoe_ref.experts_name(0)]
    assert chosen.shape == (2, 64, 2) and chosen.dtype == jnp.int32
    assert sorted(extra["scalars"]) == ["moe_aux", "moe_z"]
    assert counters["moe_tokens_per_expert"].shape == (1, 8)


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = olmoe.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = olmoe_ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    for key in ("moe_aux", "moe_z"):
        assert float(extra["scalars"][key]) == pytest.approx(
            float(extra_r["scalars"][key]), rel=1e-5)
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "embed", "k_norm", "q_norm", "router", "wg", "wi", "wk", "wo", "wq",
        "wv"]
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(1)
    assert out["choice_diff_share_tol"] == pytest.approx(
        olmoe.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER)
    assert out["scalar_rel_diff_at"] in ("moe_aux", "moe_z")


@pytest.mark.parametrize("fault", [
    "norm_topk_prob flipped",
    "num_experts_per_tok minus one",
    "q/k norm dropped",
    "balance counted on the first choice",
    "z weight off by a tenth",
])
def test_a_planted_fault_reads_not_ok(toy, fault):
    _, mc, _ = toy
    planted = fault_probe.planted_faults(TOY)
    assert sorted(planted) == ["none", "norm_topk_prob flipped",
                               "num_experts_per_tok minus one"]
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    elif fault.startswith("q/k"):
        out = _check(toy, mc=dataclasses.replace(mc, qk_norm=False))
        assert max(out["grad_rel_l2_worst_by_leaf_kind"][k]
                   for k in ("wq", "wk")) > out["grad_rel_tol"], out
    elif fault.startswith("balance"):
        params = _decisive_router(toy[2])
        assert _check(toy, params=params)["ok"]
        out = _check(toy, mc=dataclasses.replace(mc, balance_all_k=False),
                     params=params)
        assert out["scalar_rel_diff_at"] == "moe_aux"
        assert out["scalar_rel_diff"] > out["scalar_rel_tol"], out
    else:
        out = _check(toy, ref_cfg=dict(
            TOY, router_z_loss_coef=1.1 * olmoe_ref.ROUTER_Z_LOSS_COEF))
    assert not out["ok"], out


def test_flop_and_byte_counts():
    cfg = common.load_json("configs", "olmoe-l1.json")
    per_token = olmoe.model_flops_per_token(cfg, 4096)
    # head 60.5 %, the 8 active experts 29.6 %, projections 9.9 %
    assert per_token["matmul"] == pytest.approx(1.0218e9, rel=1e-3)
    assert 6.0 * 2048 * 50304 / per_token["matmul"] == pytest.approx(
        0.605, abs=1e-3)
    assert per_token["attention"] == pytest.approx(0.0503e9, rel=1e-2)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = olmoe.grouped_matmul_least_seconds(cfg, 8, 4096, peaks)
    rows = 8 * 4096 * 8
    assert least["flops"] == 18.0 * rows * 2048 * 1024
    assert least["bytes"] == (18.0 * rows * 3072
                              + 24.0 * 64 * 2048 * 1024)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(0.05023, rel=1e-3)


# -- the routed block's per-layer readers -----------------------------------


def _fake_scope_shares(monkeypatch, by):
    monkeypatch.setattr(moe_read.obs_read, "records", lambda spans: [])
    monkeypatch.setattr(
        moe_read.obs_read, "scope_shares",
        lambda recs, trace: {"by": by, "unphased_pct": 0.0} if by else None)


def test_the_readers_on_a_traced_routed_step(monkeypatch):
    by = {("forward", "moe_router"): 1.0, ("backward", "moe_router"): 2.0,
          ("forward", "moe_permute"): 3.0, ("backward", "moe_permute"): 4.0,
          ("forward", "moe_experts"): 1.0, ("recompute", "moe_experts"): 1.0,
          ("forward", "moe_combine"): 2.0, ("backward", "lm_head_loss"): 50.0}
    _fake_scope_shares(monkeypatch, by)
    # 2 s busy; the grouped-matmul kernels' 0.5 s carry no scope of their own
    trace = {"busy_s": 2.0, "kernel_s": {"pallas_other": 0.5,
                                         "flash_fwd": 0.1}}
    secs = moe_read.scope_seconds({"x": 1}, trace)
    assert secs["moe_experts"] == pytest.approx(0.02 * 2 + 0.5)
    cell = common.load_cell("olmoe-l1.train-4k")
    counters = {"cell": cell, "chips": 1, "traced_steps": 5,
                "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                "step_metrics": {"moe_tokens_per_expert": [
                    [10, 30, 20, 20], [25, 25, 25, 5]]}}
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    whole = 0.14 * 2 + 0.5
    assert read("step.moe_share_pct") == pytest.approx(100 * whole / 2.0)
    assert read("moe.permute_share_pct") == pytest.approx(
        100 * (0.12 * 2) / whole)
    least = olmoe.grouped_matmul_least_seconds(
        cell["config_data"], cell["batch_sequences"], 4096,
        counters["peaks"])["seconds"]
    assert read("moe.grouped_matmul_roofline") == pytest.approx(
        100 * least * 5 / 0.54)
    assert read("moe.load_max_over_mean") == pytest.approx(30 * 4 / 80)


def test_the_readers_find_nothing_in_a_dense_step(monkeypatch):
    _fake_scope_shares(monkeypatch, {("forward", "mlp"): 40.0})
    trace = {"busy_s": 2.0, "kernel_s": {"flash_fwd": 0.1}}
    counters = {"cell": common.load_cell("mistral7b-l2.train-steady"),
                "chips": 1, "traced_steps": 5, "peaks": {},
                "step_metrics": {"grad_norm": 1.0}}
    for name in ("step.moe_share_pct", "moe.permute_share_pct",
                 "moe.grouped_matmul_roofline", "moe.load_max_over_mean"):
        reader = common.load_module("layer_metrics", name)
        assert reader.read({"x": 1}, trace, counters) is None, name
