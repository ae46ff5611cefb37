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


def _program(monkeypatch, scopes):
    monkeypatch.setattr(moe_read.obs_read, "records", lambda spans: [
        {"kind": "accelerate.program", "scopes": scopes, "_proc": ""}])


ROUTED_SCOPES = {
    "f.1": ["forward", "moe_router"], "f.2": ["backward", "moe_router"],
    "f.3": ["forward", "moe_permute"], "f.4": ["backward", "moe_permute"],
    "f.5": ["forward", "moe_experts"], "f.6": ["forward", "moe_combine"],
    "f.7": ["backward", "lm_head_loss"],
    "gmm.1": ["forward", "moe_experts"], "gmm.2": ["recompute", "moe_experts"],
    "tgmm.1": ["backward", "moe_experts"],
    "gather_sum.1": ["forward", "moe_combine"],
    "gather_sum.2": ["backward", "moe_permute"],
    "rmsnorm_fwd.1": ["forward", "moe_router"]}


def _routed_trace(gmm_calls):
    """2 s busy.  ``gmm_calls``: the ``gmm`` kernel's seconds by calling
    instruction, 0.3 s in all."""
    kernel_call_s = {
        "gmm": gmm_calls, "tgmm": {"tgmm.1": 0.2},
        "gather_sum": {"gather_sum.1": 0.06, "gather_sum.2": 0.10},
        "rmsnorm_fwd": {"rmsnorm_fwd.1": 0.01},
        "flash_fwd": {"flash_fwd.1": 0.1}}
    kernel_s = {k: sum(v.values()) for k, v in kernel_call_s.items()}
    return {"busy_s": 2.0, "kernel_s": kernel_s,
            "kernel_call_s": kernel_call_s,
            "op_self_s": dict(kernel_s, **{
                "f.1 f32[8]": 0.02, "f.2": 0.04, "f.3": 0.06, "f.4": 0.08,
                "f.5 bf16[8]": 0.04, "f.6": 0.04, "f.7": 1.0})}


def _counters(cell):
    return {"cell": cell, "chips": 1, "traced_steps": 5,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "step_metrics": {"moe_tokens_per_expert": [
                [10, 30, 20, 20], [25, 25, 25, 5]]}}


def test_the_readers_on_a_traced_routed_step(monkeypatch, capsys):
    _program(monkeypatch, ROUTED_SCOPES)
    trace = _routed_trace({"gmm.1": 0.2, "gmm.2": 0.1})
    secs = moe_read.scope_seconds({"x": 1}, trace)
    # the grouped matmuls' calls are the experts'; gather_sum's forward call
    # is the combine's and its backward call the permute's, not the experts'
    assert secs["moe_experts"] == pytest.approx(0.04 + 0.3 + 0.2)
    assert secs["moe_combine"] == pytest.approx(0.04 + 0.06)
    assert secs["moe_permute"] == pytest.approx(0.06 + 0.08 + 0.10)
    assert secs["moe_router"] == pytest.approx(0.02 + 0.04 + 0.01)
    assert secs["unplaced"] == 0.0
    whole = 0.54 + 0.10 + 0.24 + 0.07
    assert secs["whole"] == pytest.approx(whole)
    cell = common.load_cell("olmoe-l1.train-4k")
    counters = _counters(cell)
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.moe_share_pct") == pytest.approx(100 * whole / 2.0)
    assert "MOE_KERNELS gmm=0.3000s tgmm=0.2000s gather_sum=0.1600s " \
        "unplaced=0.0000s" in capsys.readouterr().out
    assert read("moe.permute_share_pct") == pytest.approx(
        100 * (whole - 0.54) / whole)
    least = olmoe.grouped_matmul_least_seconds(
        cell["config_data"], cell["batch_sequences"], 4096,
        counters["peaks"])["seconds"]
    assert read("moe.grouped_matmul_roofline") == pytest.approx(
        100 * least * 5 / 0.54)
    assert read("moe.load_max_over_mean") == pytest.approx(30 * 4 / 80)


def test_a_kernel_call_nobody_can_place_is_reported(monkeypatch, capsys):
    """A grouped matmul called by an instruction the program's table does
    not name: in the block's total, in no scope, said in the note line —
    and the roofline's share reads lower for it, never higher."""
    _program(monkeypatch, ROUTED_SCOPES)
    trace = _routed_trace({"gmm.1": 0.2, "custom-call.7": 0.1})
    secs = moe_read.scope_seconds({"x": 1}, trace)
    assert secs["moe_experts"] == pytest.approx(0.04 + 0.2 + 0.2)
    assert secs["unplaced"] == pytest.approx(0.1)
    assert secs["whole"] == pytest.approx(0.95)
    cell = common.load_cell("olmoe-l1.train-4k")
    counters = _counters(cell)
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.moe_share_pct") == pytest.approx(100 * 0.95 / 2.0)
    assert "unplaced=0.1000s (in the block's total, in no scope)" in (
        capsys.readouterr().out)
    assert read("moe.permute_share_pct") == pytest.approx(
        100 * (0.95 - 0.44 - 0.1) / 0.95)
    least = olmoe.grouped_matmul_least_seconds(
        cell["config_data"], cell["batch_sequences"], 4096,
        counters["peaks"])["seconds"]
    assert read("moe.grouped_matmul_roofline") == pytest.approx(
        100 * least * 5 / 0.54)


def test_the_readers_find_nothing_in_a_dense_step(monkeypatch):
    _program(monkeypatch, {"f.1": ["forward", "mlp"]})
    trace = {"busy_s": 2.0, "kernel_s": {"flash_fwd": 0.1},
             "op_self_s": {"f.1": 0.8, "flash_fwd": 0.1}}
    counters = {"cell": common.load_cell("mistral7b-l2.train-steady"),
                "chips": 1, "traced_steps": 5, "peaks": {},
                "step_metrics": {"grad_norm": 1.0}}
    for name in ("step.moe_share_pct", "moe.permute_share_pct",
                 "moe.grouped_matmul_roofline", "moe.load_max_over_mean"):
        reader = common.load_module("layer_metrics", name)
        assert reader.read({"x": 1}, trace, counters) is None, name
