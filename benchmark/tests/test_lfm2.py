"""The LFM2-8B-A1B configuration's adapter and reference under
``check_against_reference`` at toy width (``configs/lfm2-rehearsal.json``,
CPU): the system — the gated short-convolution mixer, the per-head q/k norm,
the sigmoid router with its selection bias and its 1e-6, a share of the
experts and no shared expert, a routed MLP behind a convolution layer, the
tied head of ``dlrover_tpu/models/llama.py`` — reads ``ok``; the planted
faults and the lower-precision stand-in do not; the counts of the adapter;
the two new per-layer readers; and the cell's rehearsal end to end."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import lfm2_moe as lfm
from benchmark.harness import common, conv_read, lfm2_probe, model
from benchmark.reference import lfm2_moe_ref as ref

CELL_NAME = "lfm2_8b_a1b-l5.train-decayed"
FULL = common.load_json("configs", "lfm2_8b_a1b-l5.json")
TOY = common.load_json("configs", "lfm2-rehearsal.json")
CELL = {
    "name": "lfm2-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": 128, "learning_rate": 1e-5},
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
    """At initialisation every sigmoid score is 0.5, the softmax of
    attention is flat, the mixer's three factors are of order 1e-2 and no
    gain shows: a router 10 times larger with a bias off zero prefers some
    experts (40 times, GLM's, saturates these sigmoids: what is left of the
    last router's gradient then rounds by 19 % in bf16, float32 exact),
    queries and keys 30 times larger prefer some keys, an ``in_proj`` 10
    times larger makes the gates matter, and head gains off one tell the
    two forms of the norm apart — as a trained model's do."""
    def layer_of(layer):
        if "conv" in layer:
            conv = layer["conv"]
            layer = dict(layer, conv=dict(
                conv, in_proj=10.0 * conv["in_proj"]))
        else:
            dims = jnp.arange(layer["q_norm"].shape[0], dtype=jnp.float32)
            layer = dict(
                layer, wq=30.0 * layer["wq"], wk=30.0 * layer["wk"],
                q_norm=1.0 + 0.5 * jnp.cos(dims),
                k_norm=1.0 + 0.5 * jnp.sin(dims))
        if "moe" in layer:
            moe = layer["moe"]
            bias = 0.05 * jnp.cos(jnp.arange(moe["router_bias"].shape[0]))
            layer = dict(layer, moe=dict(
                moe, router=10.0 * moe["router"],
                router_bias=bias.astype(jnp.float32)))
        return layer

    return dict(params, layers=[layer_of(l) for l in params["layers"]])


def _check(toy, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 0, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    mc = lfm.model_config(FULL, remat_block=True, seq_len=8192)
    # the router is the source's 32 wide with 4 picks; this chip holds 8
    assert (mc.num_experts, mc.top_k, mc.experts_held,
            mc.experts_held_first, mc.n_shared_experts) == (32, 4, 8, 0, 0)
    assert (mc.n_head, mc.n_kv_head, mc.head_dim, mc.d_model, mc.d_ff,
            mc.expert_width, mc.vocab_size) == (
                32, 8, 64, 2048, 7168, 1792, 16384)
    assert mc.layer_types == ("conv", "attention", "conv", "conv", "conv")
    assert (mc.conv_layers, mc.attention_layers, mc.block_applications,
            mc.conv_taps, mc.first_k_dense) == (4, 1, 1, 3, 1)
    assert (mc.qk_norm, mc.qk_norm_per_head, mc.rope, mc.rope_theta,
            mc.tie_word_embeddings) == (True, True, True, 1e6, True)
    assert (mc.router_score, mc.routed_scaling, mc.norm_topk_prob,
            mc.router_norm_eps, mc.router_bias_rate, mc.capacity_factor) == (
                "sigmoid", 1.0, True, 1e-6, 1e-3, None)
    assert [mc.is_moe_layer(i) for i in range(5)] == [
        False, True, True, True, True]
    assert lfm.ROUTER_NORM_EPS == ref.ROUTER_NORM_EPS
    assert FULL["parameters"] == 507_820_288
    for key, bad in (("conv_bias", True), ("use_expert_bias", False),
                     ("model_type", "lfm2")):
        with pytest.raises(ValueError, match=key):
            lfm.model_config(dict(FULL, **{key: bad}), remat_block=False,
                             seq_len=64)
    with pytest.raises(ValueError, match="sliding_window"):
        lfm.model_config(dict(FULL, sliding_window=32), remat_block=False,
                         seq_len=64)
    with pytest.raises(ValueError, match="layer_types"):
        lfm.model_config(dict(FULL, layer_types=["conv", "mamba"] * 2
                              + ["conv"]), remat_block=False, seq_len=64)
    toy_mc = lfm.model_config(TOY, remat_block=False, seq_len=64)
    assert (toy_mc.num_experts, toy_mc.experts_held) == (16, 4)
    assert toy_mc.layer_types == ("conv", "attention", "conv")


def test_the_file_is_the_source_but_for_what_it_lists():
    published, reduced = FULL["published"], FULL["reduced"]
    assert sorted(reduced) == ["layer_types", "num_dense_layers",
                               "num_experts", "num_hidden_layers",
                               "vocab_size"]
    for key, value in published.items():
        if key in reduced:
            assert (reduced[key]["from"], reduced[key]["to"]) == (
                value, FULL[key]), key
        else:
            assert FULL[key] == value, key
    # one leading dense layer and one whole period, as published layers 1-5
    assert FULL["layer_types"] == published["layer_types"][1:6]
    (entry,) = [c for c in common.load_spec()["configs"]
                if c["name"] == "lfm2_8b_a1b-l5"]
    assert sorted(entry["reduced"]) == sorted(reduced)
    assert entry["source"] == FULL["source"]


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["published", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    known = (set(lfm.MAPPED) | set(lfm.FIXED) | set(lfm.INERT)
             | set(common.CONFIG_META_KEYS))
    assert set(cfg) <= known
    # and every key of the source is accounted for
    assert set(FULL["published"]) <= (
        set(lfm.MAPPED) | set(lfm.FIXED) | set(lfm.INERT))


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        layer_types: tuple = ()

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="conv_taps"):
        lfm.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss, extra = lfm.hidden_and_loss(params, toks, mc)
    fn = lfm.loss_fn(mc)
    own, counters = fn(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert fn.rule_leaves == llama.rule_leaves(mc) and len(
        fn.rule_leaves) == 2
    assert fn.program_facts == {"conv_layers": 2, "attention_layers": 1}
    assert sorted(counters[llama.RULE_UPDATES]) == sorted(fn.rule_leaves)
    assert hidden.shape == (2, 64, 64)
    assert sorted(extra["choices"]) == ["layers.1.experts",
                                        "layers.2.experts"]
    chosen = extra["choices"]["layers.2.experts"]
    assert chosen.shape == (2, 64, 4) and int(chosen.max()) > 3  # of 16
    assert extra["scalars"] == {}
    assert counters["moe_tokens_per_expert"].shape == (2, 16)
    assert counters["moe_held_pairs"].shape == (2,)
    assert sorted(lfm.grad_leaves(params)) == [
        "embed", "layers.0.conv.conv_w", "layers.0.conv.in_proj",
        "layers.0.conv.out_proj", "layers.1.k_norm", "layers.1.moe.router",
        "layers.1.moe.wg", "layers.1.moe.wi", "layers.1.moe.wo",
        "layers.1.q_norm", "layers.1.wk", "layers.1.wq", "layers.1.wv",
        "layers.2.conv.conv_w", "layers.2.conv.in_proj",
        "layers.2.conv.out_proj"]
    again = lfm.with_leaves(params, lfm.grad_leaves(params))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a is b, again, params))


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = lfm.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert extra_r["scalars"] == {}
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))
        assert extra_r["probs"][name].shape == (2, 64, 16)
    # under ``given`` the reference computes the system's experts
    _, loss_g, _ = ref.hidden_and_loss(
        params, toks, TOY, given=extra["choices"])
    assert float(loss_g) == pytest.approx(float(loss_r), rel=1e-6)


def test_the_reference_computes_the_experts_it_is_given(toy):
    """Another set than its own changes the result, and the choices it
    reports stay those it would have made itself."""
    _, _, params = toy
    toks = jnp.asarray(model.sample_tokens(3, range(1), 64, 4096))
    _, loss_own, own = ref.hidden_and_loss(params, toks, TOY)
    other = {name: (chosen + 1) % 16
             for name, chosen in own["choices"].items()}
    _, loss_other, extra = ref.hidden_and_loss(params, toks, TOY, given=other)
    assert abs(float(loss_other) - float(loss_own)) > 1e-6
    # (the first routed block's: the later ones read another stream now)
    first = "layers.1.experts"
    assert np.array_equal(extra["choices"][first], own["choices"][first])
    assert not np.array_equal(extra["choices"][first], other[first])


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "conv_w", "embed", "in_proj", "k_norm", "out_proj", "q_norm",
        "router", "wg", "wi", "wk", "wo", "wq", "wv"]
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(3)
    assert out["choice_diff_share_tol"] == pytest.approx(
        lfm.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 3 ** 0.5)
    # the loss has no further scalar: nothing is read, nothing judged
    assert (out["scalar_rel_diff"], out["scalar_rel_diff_at"]) == (0.0, "")


@pytest.mark.parametrize("fault", [
    "qk_norm_whole_width", "c_x_exchanged", "conv_ahead",
    "fp8_routed_stream", "fp8_stream", "norm_topk_prob flipped",
    "num_experts_per_tok minus one",
    "one expert fewer held", "the norm's constant 0.5", "rotary base 100",
])
def test_a_planted_fault_reads_not_ok(toy, fault):
    planted = lfm2_probe.planted_configs(TOY, ref)
    assert sorted(planted) == sorted(ref.FAULTS + (
        "norm_topk_prob flipped", "num_experts_per_tok minus one"))
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    elif fault in ref.STAND_INS:
        out = _check(toy, ref_cfg=dict(TOY, planted=fault))
    elif fault.startswith("one expert"):
        out = _check(toy, ref_cfg=dict(TOY, num_experts=3))
    elif fault.startswith("the norm's constant"):
        _, mc, _ = toy
        out = _check(toy, mc=dataclasses.replace(mc, router_norm_eps=0.5))
    else:
        out = _check(toy, ref_cfg=dict(TOY, rope_theta=100))
    assert not out["ok"], out


def test_fp8_experts_moves_the_expert_leaves(toy):
    """The experts' matmuls alone in fp8: at toy width the experts add so
    little to the stream that nothing but their own gradient leaves and
    the routers' moves — threefold, and still inside the standing limits.
    At published width the choices of the later blocks find it
    (``harness/lfm2_probe.py``; PERF.md section 4)."""
    true = _check(toy)["grad_rel_l2_worst_by_leaf_kind"]
    low = _check(toy, ref_cfg=dict(TOY, planted="fp8_experts"))[
        "grad_rel_l2_worst_by_leaf_kind"]
    for kind in ("wg", "wi", "wo", "router"):
        assert low[kind] > 2.5 * true[kind], (kind, low[kind], true[kind])
    for kind in ("in_proj", "wq", "embed"):
        assert low[kind] == pytest.approx(true[kind], rel=0.05)


def test_flop_and_byte_counts():
    per_token = lfm.model_flops_per_token(FULL, 8192)
    # the issue's count: 1.30 GFLOP a token — conv mixers 31 %, held experts
    # 20 %, the dense MLP 20 %, the head 15.5 %, the attention layer 12.5 %
    total = per_token["total"]
    assert total == pytest.approx(1.298e9, rel=1e-3)
    assert 6.0 * 4 * 4 * 2048 * 2048 / total == pytest.approx(0.31, abs=5e-3)
    assert 6.0 * 4 * 3 * 2048 * 1792 / total == pytest.approx(0.20, abs=5e-3)
    assert 6.0 * 3 * 2048 * 7168 / total == pytest.approx(0.20, abs=5e-3)
    assert 6.0 * 2048 * 16384 / total == pytest.approx(0.155, abs=5e-3)
    attention = 6.0 * (2 * 2048 * 2048 + 2 * 2048 * 512) + per_token[
        "attention"]
    assert attention / total == pytest.approx(0.125, abs=5e-3)
    assert per_token["conv"] == 3.0 * 4 * 2 * 3 * 2048
    # a token meets ONE held expert a routed block: 4 x 8 / 32
    counts = lfm._counts(FULL)
    assert (counts["held_picks"], counts["routed_blocks"],
            counts["conv_layers"], counts["attention_layers"]) == (
                1.0, 4, 4, 1)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = lfm.grouped_matmul_least_seconds(FULL, 4, 8192, peaks)
    rows = 4 * 8192 * 1.0  # 4,096 an expert
    assert least["flops"] == pytest.approx(
        18.0 * rows * 2048 * 1792 * 4 / 5)
    assert least["bytes"] == pytest.approx(
        (18.0 * rows * 3840 + 24.0 * 8 * 2048 * 1792) * 4 / 5)
    assert least["bound"] == "flops"
    flash = lfm.flash_least_seconds(FULL, 4, 8192, peaks)
    pairs = 8192 * 8193 // 2
    assert flash["flops"] == pytest.approx(
        7 * 2.0 * 32 * 64 * pairs * 4 / 5)
    assert flash["bound"] == "flops"


# -- the new per-layer readers ----------------------------------------------


def _program(monkeypatch, scopes, subscopes, **facts):
    rec = dict({"kind": "accelerate.program", "scopes": scopes}, **facts)
    if subscopes is not None:
        rec["subscopes"] = subscopes
    monkeypatch.setattr(conv_read.obs_read, "records", lambda spans: [rec])


def test_the_readers_on_a_traced_step(monkeypatch):
    scopes = {
        "f.1": ["forward", "conv"], "f.2": ["backward", "conv"],
        "f.3": ["recompute", "conv"], "f.4": ["forward", "conv"],
        "f.5": ["backward", "conv"], "f.6": ["forward", "attention"],
        "f.7": ["forward", "moe_experts"], "w.1": ["recompute", "conv"]}
    subscopes = {"f.1": "conv_in", "f.2": "conv_gate", "f.3": "conv_gate",
                 "f.4": "conv_out", "f.6": "flash_fwd", "w.1": "conv_in"}
    _program(monkeypatch, scopes, subscopes, conv_layers=4)
    trace = {"busy_s": 10.0,
             "op_self_s": {"f.1 bf16[8]": 1.0, "f.2 f32[8]": 0.6,
                           "f.3": 0.4, "f.4": 0.5, "f.5": 0.2, "f.6": 0.9,
                           "f.7": 2.0, "w.1": 0.25, "rmsnorm_fwd": 0.3,
                           "unknown.9": 0.7},
             "kernel_s": {"rmsnorm_fwd": 0.3}}
    secs = conv_read.seconds({"x": 1}, trace)
    assert secs["conv"] == pytest.approx(2.95)  # f.5: the residual add's
    assert (secs["conv_in"], secs["conv_gate"], secs["conv_out"],
            secs["conv_layers"]) == (1.25, 1.0, 0.5, 4)
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, {})
    assert read("step.conv_share_pct") == pytest.approx(29.5)
    assert read("conv.gate_share_pct") == pytest.approx(100 * 1.0 / 2.95)


@pytest.mark.parametrize("name", [
    "step.conv_share_pct", "conv.gate_share_pct"])
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals no ``conv`` scope, a dense step no ``subscopes``
    at all: the readers return None and do not raise."""
    reader = common.load_module("layer_metrics", name)
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5},
             "kernel_s": {"flash_fwd": 0.2}}
    _program(monkeypatch, {"f.1": ["forward", "attention"]}, None)
    assert reader.read({"x": 1}, trace, {}) is None
    _program(monkeypatch, {"f.1": ["forward", "ssm"]}, {"f.1": "ssm_conv"})
    assert reader.read({"x": 1}, trace, {}) is None
    assert reader.read({}, {}, {}) is None


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2_8b_a1b-l5", "train-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        4, True, {"fsdp": 1, "tp": 1})
    decayed, steady = (common.load_json("traffic", f"{name}.json")
                       for name in ("train-decayed", "train-steady"))
    assert decayed["learning_rate"] == 1e-5
    assert {k: v for k, v in decayed.items()
            if k not in ("learning_rate", "what")} == {
                k: v for k, v in steady.items()
                if k not in ("learning_rate", "what")}
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert {"step.conv_share_pct", "conv.gate_share_pct",
            "step.moe_share_pct", "moe.permute_share_pct",
            "moe.grouped_matmul_roofline", "moe.held_pair_share_pct",
            "moe.load_max_over_mean", "flash_roofline", "step.mfu_pct",
            "step.recompute_share_pct"} <= named
    assert len(spec["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--rehearse", "--workload", CELL_NAME, "--seconds", "2",
         "--trace", "1"],
        env=env, cwd=common.REPO, capture_output=True, text=True,
        timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    last = res.stdout.strip().splitlines()[-1]
    found = json.loads(last[last.index("{"):])
    assert found["correct"] and found["failed"] == 0
    # the counters' readers find theirs on the CPU; the two new ones read a
    # device trace, which a rehearsal has none of
    assert {"moe.held_pair_share_pct", "moe.load_max_over_mean"} <= set(
        found["metrics_found"])
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for name in ("'conv_layers': 2", "'attention_layers': 1", "'conv_in'",
                 "'conv_gate'", "'conv_out'", "'moe_permute'"):
        assert name in program
