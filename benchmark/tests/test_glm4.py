"""The GLM-4.7-Flash configuration's adapter and reference under
``check_against_reference`` at toy width (``configs/glm4-rehearsal.json``,
CPU): the system — latent attention, the sigmoid router with its selection
bias, a share of the experts, the shared expert, the leading dense layer and
the prediction block of ``dlrover_tpu/models/llama.py`` — reads ``ok``; the
planted faults that apply, one expert fewer held, a wrong scale, a wrong
rotary base, a balance term counted on the first choice and either loss
weight off by a tenth do not; the counts of the adapter; and the four new
per-layer readers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import glm4_moe_lite as glm
from benchmark.harness import common, fault_probe, mla_read, model
from benchmark.reference import glm4_moe_lite_ref as ref

TOY = common.load_json("configs", "glm4-rehearsal.json")
CELL = {
    "name": "glm4-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": 128, "learning_rate": 3e-4},
}


@pytest.fixture(scope="module")
def toy():
    from dlrover_tpu import obs

    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    yield job, mc, _decisive(params)
    # the build's spans stay in the process's ring: a later file's test
    # of "nothing recorded" (test_obs_read.py) must find it empty
    obs.reset()


def _decisive(params):
    """At initialisation every sigmoid score is 0.5 and the top four are a
    coin's; a router 40 times larger prefers some experts, as a trained
    one does, and a bias off zero makes the choice differ from the
    weights' order."""
    def routed(layer):
        if "moe" not in layer:
            return layer
        moe = layer["moe"]
        bias = 0.05 * jnp.cos(jnp.arange(moe["router_bias"].shape[0]))
        return dict(layer, moe=dict(
            moe, router=40.0 * moe["router"],
            router_bias=bias.astype(jnp.float32)))

    mtp = dict(params["mtp"], block=routed(params["mtp"]["block"]))
    return dict(params, layers=[routed(l) for l in params["layers"]],
                mtp=mtp)


def _check(toy, mc=None, ref_cfg=None, params=None):
    job, toy_mc, toy_params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params or toy_params, 0, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    full = common.load_json("configs", "glm4_7_flash-l5.json")
    mc = glm.model_config(full, remat_block=True, seq_len=8192)
    # the router is the source's 64 wide with 4 picks; this chip holds 8
    assert (mc.num_experts, mc.top_k, mc.experts_held,
            mc.experts_held_first) == (64, 4, 8, 0)
    assert (mc.n_head, mc.head_dim, mc.d_model, mc.d_ff, mc.expert_width,
            mc.q_lora_rank, mc.kv_lora_rank) == (
                20, 256, 2048, 10240, 1536, 768, 512)
    assert (mc.first_k_dense, mc.n_shared_experts, mc.mtp_layers,
            mc.block_applications, mc.vocab_size) == (1, 1, 1, 6, 19360)
    assert (mc.router_score, mc.routed_scaling, mc.norm_topk_prob,
            mc.router_bias_rate, mc.balance_per_sequence,
            mc.capacity_factor) == ("sigmoid", 1.8, True, 1e-3, True, None)
    assert [mc.is_moe_layer(i) for i in range(5)] == [
        False, True, True, True, True]
    assert (glm.SEQ_AUX_WEIGHT, glm.MTP_WEIGHT) == (
        ref.SEQ_AUX_WEIGHT, ref.MTP_WEIGHT)
    assert full["parameters"] == 706_518_848
    for key, bad in (("n_group", 8), ("topk_method", "greedy"),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            glm.model_config(dict(full, **{key: bad}), remat_block=False,
                             seq_len=64)
    with pytest.raises(ValueError, match="sliding_window"):
        glm.model_config(dict(full, sliding_window=32), remat_block=False,
                         seq_len=64)
    toy_mc = glm.model_config(TOY, remat_block=False, seq_len=64)
    assert (toy_mc.num_experts, toy_mc.experts_held) == (16, 4)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        glm.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss, extra = glm.hidden_and_loss(params, toks, mc)
    fn = glm.loss_fn(mc)
    own, counters = fn(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert fn.rule_leaves == llama.rule_leaves(mc) and len(
        fn.rule_leaves) == 3
    assert sorted(counters[llama.RULE_UPDATES]) == sorted(fn.rule_leaves)
    assert hidden.shape == (4, 64, 64)  # main, then the prediction block's
    assert sorted(extra["choices"]) == [
        "layers.1.experts", "layers.2.experts", "mtp.experts"]
    chosen = extra["choices"]["mtp.experts"]
    assert chosen.shape == (2, 64, 4) and int(chosen.max()) > 3  # of 16
    assert sorted(extra["scalars"]) == ["moe_seq_aux", "mtp"]
    assert counters["moe_tokens_per_expert"].shape == (3, 16)
    assert counters["moe_held_pairs"].shape == (3,)
    assert sorted(set(counters) - {llama.RULE_UPDATES}) == [
        "main_ce", "moe_held_pairs", "moe_router_bias_abs_max",
        "moe_seq_aux", "moe_tokens_per_expert", "moe_z", "mtp_ce"]


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = glm.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    for key in ("moe_seq_aux", "mtp"):
        assert float(extra["scalars"][key]) == pytest.approx(
            float(extra_r["scalars"][key]), rel=1e-5)
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))
    # under ``given`` the reference computes the system's experts
    _, loss_g, _ = ref.hidden_and_loss(
        params, toks, TOY, given=extra["choices"])
    assert float(loss_g) == pytest.approx(float(loss_r), rel=1e-6)


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "embed", "router", "w_down", "w_eh", "w_gate", "w_up", "wg", "wi",
        "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(3)
    assert out["choice_diff_share_tol"] == pytest.approx(
        glm.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 3 ** 0.5)
    assert out["scalar_rel_diff_at"] in ("moe_seq_aux", "mtp")


@pytest.mark.parametrize("fault", [
    "norm_topk_prob flipped",
    "num_experts_per_tok minus one",
    "one expert fewer held",
    "scaling factor 1.0",
    "rotary base 100",
    "balance counted on the first choice",
    "prediction weight off by a tenth",
    "balance weight off by a tenth",
    "router stream in fp8",
])
def test_a_planted_fault_reads_not_ok(toy, fault):
    _, mc, _ = toy
    planted = fault_probe.planted_faults(TOY)
    assert sorted(planted) == ["none", "norm_topk_prob flipped",
                               "num_experts_per_tok minus one"]
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    elif fault.startswith("one expert"):
        out = _check(toy, ref_cfg=dict(TOY, n_routed_experts=3))
    elif fault.startswith("scaling"):
        out = _check(toy, ref_cfg=dict(TOY, routed_scaling_factor=1.0))
    elif fault.startswith("rotary"):
        out = _check(toy, ref_cfg=dict(TOY, rope_theta=100))
    elif fault.startswith("balance counted"):
        out = _check(toy, mc=dataclasses.replace(
            mc, balance_per_sequence=False, balance_all_k=False))
        assert out["scalar_rel_diff_at"] == "moe_seq_aux"
        assert out["scalar_rel_diff"] > out["scalar_rel_tol"], out
    elif fault.startswith("router stream"):
        out = _check(toy, ref_cfg=dict(TOY, planted=ref.FP8_ROUTER_STREAM))
        assert (out["choice_diff_share"] > out["choice_diff_share_tol"]
                or out["choice_prob_gap"] > out["choice_prob_gap_tol"]), out
    elif fault.startswith("prediction weight"):
        out = _check(toy, ref_cfg=dict(TOY, mtp_weight=1.1 * ref.MTP_WEIGHT))
        assert out["scalar_rel_diff_at"] == "mtp"
    else:
        out = _check(toy, ref_cfg=dict(
            TOY, seq_aux_weight=1.1 * ref.SEQ_AUX_WEIGHT))
        assert out["scalar_rel_diff_at"] == "moe_seq_aux"
    assert not out["ok"], out


def test_flop_and_byte_counts():
    cfg = common.load_json("configs", "glm4_7_flash-l5.json")
    per_token = glm.model_flops_per_token(cfg, 8192)
    # the issue's split of 1.209 GFLOP of forward matmul a token: six MLA
    # applications 63 %, two head calls 13 %, five routed blocks 12 %,
    # the dense layer 10 %, w_eh 1.4 %
    forward = per_token["matmul"] / 3.0
    assert forward == pytest.approx(0.7049e9, rel=1e-3)
    mla_proj = 2.0 * 21_757_952
    mla_attn = per_token["attention"] / 3.0 / 6
    assert mla_proj == pytest.approx(43.5e6, rel=1e-3)
    assert mla_attn == pytest.approx(83.9e6, rel=2e-3)
    whole = forward + per_token["attention"] / 3.0
    assert whole == pytest.approx(1.209e9, rel=2e-3)
    assert 6 * (mla_proj + mla_attn) / whole == pytest.approx(0.63, abs=5e-3)
    assert 2 * 2.0 * 2048 * 19360 / whole == pytest.approx(0.13, abs=5e-3)
    # a token meets 0.5 held experts a routed block
    assert glm._counts(cfg)["held_picks"] == 0.5
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = glm.grouped_matmul_least_seconds(cfg, 2, 8192, peaks)
    rows = 2 * 8192 * 0.5
    assert least["flops"] == 18.0 * rows * 2048 * 1536
    assert least["bytes"] == 18.0 * rows * 3584 + 24.0 * 8 * 2048 * 1536
    flash = glm.flash_least_seconds(cfg, 2, 8192, peaks)
    pairs = 8192 * 8193 // 2
    assert flash["flops"] == pytest.approx(
        7 * 2.0 * 20 * 256 * pairs * 2 * 6 / 5)
    assert flash["bound"] == "flops"


# -- the new per-layer readers ----------------------------------------------


def _program(monkeypatch, scopes, subscopes, applications=6):
    rec = {"kind": "accelerate.program", "scopes": scopes,
           "block_applications": applications}
    if subscopes is not None:
        rec["subscopes"] = subscopes
    monkeypatch.setattr(mla_read.obs_read, "records", lambda spans: [rec])


def test_the_readers_on_a_traced_step(monkeypatch):
    scopes = {
        "f.1": ["forward", "attention"], "f.2": ["backward", "attention"],
        "f.3": ["recompute", "attention"], "f.4": ["forward", "attention"],
        "f.5": ["forward", "mtp"], "f.6": ["backward", "mtp"],
        "f.7": ["forward", "moe_experts"], "f.8": ["forward", "mtp"]}
    subscopes = {"f.1": "mla_q", "f.2": "mla_kv", "f.3": "mla_out",
                 "f.5": "mla_q", "f.6": "moe_router", "f.8": "attention"}
    _program(monkeypatch, scopes, subscopes)
    trace = {"busy_s": 10.0,
             "op_self_s": {"f.1 bf16[8]": 0.5, "f.2 bf16[8]": 0.3,
                           "f.3": 0.2, "f.4": 0.4, "f.5": 0.1, "f.6": 0.6,
                           "f.7": 1.0, "f.8": 0.2, "flash_fwd": 1.2,
                           "unknown.9": 0.7},
             "kernel_s": {"flash_fwd": 1.2, "flash_bwd_dq": 0.9,
                          "flash_bwd_dkv": 0.9, "pallas_other": 0.5}}
    secs = mla_read.seconds({"x": 1}, trace)
    assert secs["attention_ops"] == pytest.approx(0.5 + 0.3 + 0.2 + 0.4
                                                  + 0.1 + 0.2)
    assert secs["mla_q"] == pytest.approx(0.6)
    assert secs["mtp_ops"] == pytest.approx(0.9)
    assert secs["flash"] == pytest.approx(3.0)
    counters = {"step_metrics": {
        "moe_held_pairs": [8000, 9000],
        "moe_tokens_per_expert": [[1024] * 64, [1024] * 64]}}
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.attention_share_pct") == pytest.approx(
        100 * (1.7 + 3.0) / 10)
    assert read("mla.latent_share_pct") == pytest.approx(
        100 * (0.6 + 0.3) / 4.7)
    assert read("step.mtp_share_pct") == pytest.approx(
        100 * (0.9 + 3.0 / 6) / 10)
    assert read("moe.held_pair_share_pct") == pytest.approx(
        100 * 9000 / 65536)


def test_the_prediction_blocks_routed_branch_is_a_routed_block(monkeypatch):
    """Under ``mtp`` the four ``moe_*`` scopes are nested: an instruction
    counts by its innermost scope, the block's kernels by their name and
    ``gather_sum`` by its call's phase — and ``step.mtp_share_pct`` takes no
    kernel for the instruction that bears its name (``gmm``)."""
    from benchmark.harness import moe_read

    scopes = {
        "f.1": ["forward", "moe_experts"], "f.2": ["backward", "mtp"],
        "f.3": ["forward", "mtp"], "f.4": ["forward", "mtp"],
        "gmm": ["forward", "mtp"], "gmm.1": ["forward", "moe_experts"],
        "gather_sum.1": ["forward", "mtp"],
        "gather_sum.2": ["backward", "mtp"],
        "gather_sum.3": ["recompute", "moe_combine"],
        "rmsnorm_fwd.1": ["forward", "mtp"]}
    subscopes = {"f.2": "moe_router", "f.3": "moe_experts", "f.4": "mla_q",
                 "gather_sum.1": "gather_sum", "gather_sum.2": "gather_sum",
                 "gather_sum.3": "gather_sum", "rmsnorm_fwd.1": "rmsnorm_fwd"}
    _program(monkeypatch, scopes, subscopes)
    calls = {"gmm": {"gmm": 0.2, "gmm.1": 0.8},
             "gather_sum": {"gather_sum.1": 0.1, "gather_sum.2": 0.3,
                            "gather_sum.3": 0.05},
             "rmsnorm_fwd": {"rmsnorm_fwd.1": 0.4}}
    kernel_s = {k: sum(v.values()) for k, v in calls.items()}
    trace = {"busy_s": 10.0, "kernel_s": kernel_s, "kernel_call_s": calls,
             "op_self_s": dict(kernel_s, **{
                 "f.1 bf16[8]": 1.0, "f.2": 0.6, "f.3": 0.5, "f.4": 0.7})}
    secs = moe_read.scope_seconds({"x": 1}, trace)
    assert secs["moe_experts"] == pytest.approx(1.0 + 0.5 + 0.2 + 0.8)
    assert secs["moe_router"] == pytest.approx(0.6)  # no norm under mtp
    assert secs["moe_combine"] == pytest.approx(0.1 + 0.05)
    assert secs["moe_permute"] == pytest.approx(0.3)
    assert (secs["unplaced"], secs["whole"]) == (0.0, pytest.approx(3.55))
    mtp = common.load_module("layer_metrics", "step.mtp_share_pct").read(
        {"x": 1}, trace, {})
    assert mtp == pytest.approx(100 * (0.6 + 0.5 + 0.7) / 10)


@pytest.mark.parametrize("name", [
    "step.attention_share_pct", "mla.latent_share_pct",
    "step.mtp_share_pct", "moe.held_pair_share_pct"])
def test_a_program_without_the_scopes_or_counters_reads_nothing(
        monkeypatch, name):
    """The parent journals no ``subscopes`` and returns no
    ``moe_held_pairs``: the readers return None and do not raise."""
    _program(monkeypatch, {"f.1": ["forward", "attention"]}, None)
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5},
             "kernel_s": {"flash_fwd": 0.2}}
    reader = common.load_module("layer_metrics", name)
    assert reader.read({"x": 1}, trace, {"step_metrics": {
        "moe_tokens_per_expert": [[4, 4]]}}) is None
    assert reader.read({}, {}, {}) is None
