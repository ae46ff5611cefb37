"""The Nemotron-H configuration's adapter and reference under
``check_against_reference`` at toy width
(``configs/nemotron-h-rehearsal.json``, CPU): the system — layers of one
branch each, the Mamba-2 mixer in groups in its chunked form, attention
without rotary position, the sigmoid router under a share of the experts
with relu2 experts and a relu2 shared expert, the untied head of
``dlrover_tpu/models/llama.py`` — reads ``ok``; the six planted faults of the
issue and the routed block's two do not; the counts of the adapter with the
readers' layer count pinned; the two new per-layer readers; and the cell's
rehearsal end to end."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import nemotron_h as nh
from benchmark.harness import (
    common,
    model,
    moe_read,
    nemotron_h_probe,
    ssm_read,
)
from benchmark.reference import nemotron_h_ref as ref

CELL_NAME = "nemotron3_nano_30b_a3b-l9.train-decayed"
FULL = common.load_json("configs", "nemotron3_nano_30b_a3b-l9.json")
TOY = common.load_json("configs", "nemotron-h-rehearsal.json")
SEQ = 128
CELL = {
    "name": "nemotron-h-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": SEQ, "learning_rate": 1e-5},
}
WIDTH = TOY["published"]["n_routed_experts"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def toy():
    from dlrover_tpu import obs

    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    # (the draw of PRNGKey(0) puts one held expert's ``wo`` gradient, from
    # a dozen rows, at 15 % of the toy's 18 %; the next draws read 2-3 %)
    params = job.create_state(jax.random.PRNGKey(1))["params"]
    yield job, mc, _decisive(params)
    # the build's spans stay in the process's ring: a later file's test
    # of "nothing recorded" (test_obs_read.py) must find it empty
    obs.reset()


def _decisive(params):
    """At initialisation the routers' scores sit at 1/2, attention's softmax
    is flat and every gain is 1: a router 3 times larger prefers some
    experts, queries and keys 8 times larger prefer some keys, the mixer's
    input projection twice as large makes its gate and its decays matter,
    and gains off 1 tell a norm in groups from one over the whole width —
    as a trained model's do."""
    def layer_of(layer):
        norm = "ln1" if "ln1" in layer else "ln2"
        width = jnp.arange(layer[norm].shape[0], dtype=jnp.float32)
        layer = dict(layer, **{norm: 1.0 + 0.3 * jnp.cos(width)})
        if "ssm" in layer:
            ssm = layer["ssm"]
            inner = jnp.arange(ssm["norm"].shape[0], dtype=jnp.float32)
            layer["ssm"] = dict(ssm, in_proj=2.0 * ssm["in_proj"],
                                norm=1.0 + 0.3 * jnp.sin(inner))
        elif "wq" in layer:
            layer.update(wq=8.0 * layer["wq"], wk=8.0 * layer["wk"])
        elif "moe" in layer:
            layer["moe"] = dict(layer["moe"],
                                router=3.0 * layer["moe"]["router"])
        return layer

    return dict(params, layers=[layer_of(l) for l in params["layers"]])


def _check(toy, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 1, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    mc = nh.model_config(FULL, remat_block=True, seq_len=8192)
    # the router is the source's 128 wide with 6 picks; this chip holds 8
    assert (mc.num_experts, mc.top_k, mc.experts_held, mc.experts_held_first,
            mc.n_shared_experts, mc.expert_width) == (128, 6, 8, 0, 2, 1856)
    assert (mc.n_head, mc.n_kv_head, mc.head_dim, mc.d_model, mc.d_ff,
            mc.vocab_size) == (32, 2, 128, 2688, 1856, 16384)
    assert (mc.one_branch, mc.mlp_form) == (True, "relu2")
    assert mc.layer_types == ("mamba", "moe", "mamba", "moe", "mamba",
                              "attention", "moe", "mamba", "moe")
    assert (mc.ssm_layers, mc.attention_layers, mc.moe_layers,
            mc.block_applications) == (4, 1, 4, 1)
    assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_inner,
            mc.mamba_d_state, mc.mamba_n_groups, mc.mamba_d_conv,
            mc.mamba_chunk_size, mc.mamba_conv_bias, mc.mamba_conv_dim) == (
                64, 64, 4096, 128, 8, 4, 128, True, 6144)
    assert (mc.rope, mc.attention_multiplier, mc.qk_norm, mc.rms_eps,
            mc.tie_word_embeddings, mc.norm_plus_one) == (
                False, None, False, 1e-5, False, False)
    assert (mc.router_score, mc.routed_scaling, mc.norm_topk_prob,
            mc.router_norm_eps, mc.balance_all_k, mc.router_bias_rate,
            mc.capacity_factor, mc.mtp_layers) == (
                "sigmoid", 2.5, True, 1e-20, True, 1e-3, None, 0)
    assert [mc.is_moe_layer(i) for i in range(9)] == [
        c == "E" for c in "MEMEM*EME"]
    assert nh.AUX_WEIGHT == ref.AUX_WEIGHT == 1e-4
    assert FULL["parameters"] == 666_963_456
    for key, bad in (("model_type", "granitemoehybrid"),
                     ("mlp_hidden_act", "silu"), ("n_group", 2),
                     ("attention_bias", True), ("use_bias", True),
                     ("tie_word_embeddings", True), ("sliding_window", 4096),
                     ("n_shared_experts", 2), ("time_step_max", 0.2)):
        with pytest.raises(ValueError, match=key):
            nh.model_config(dict(FULL, **{key: bad}), remat_block=False,
                            seq_len=64)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.model_config(dict(FULL, hybrid_override_pattern="MEMEM*EM"),
                        remat_block=False, seq_len=64)
    with pytest.raises(ValueError, match="does not know"):
        nh.model_config(dict(FULL, layer_types=["mamba"]),
                        remat_block=False, seq_len=64)
    with pytest.raises(ValueError, match="whole number"):
        nh.model_config(dict(FULL, moe_shared_expert_intermediate_size=3000),
                        remat_block=False, seq_len=64)
    toy_mc = nh.model_config(TOY, remat_block=False, seq_len=64)
    assert (toy_mc.num_experts, toy_mc.experts_held) == (WIDTH, 4)
    assert toy_mc.layer_types == ("mamba", "moe", "mamba", "attention",
                                  "moe")
    # a "-" layer is the dense two-matrix MLP
    dense = nh.model_config(
        dict(TOY, hybrid_override_pattern="ME-*E"), remat_block=False,
        seq_len=64)
    assert dense.layer_types[2] == "mlp" and dense.d_ff == 48


def test_the_file_is_the_source_but_for_what_it_lists():
    published, reduced = FULL["published"], FULL["reduced"]
    assert sorted(reduced) == ["hybrid_override_pattern", "n_routed_experts",
                               "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key in reduced:
            assert (reduced[key]["from"], reduced[key]["to"]) == (
                value, FULL[key]), key
        else:
            assert FULL[key] == value, key
    # the catalog's row, key for key
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] if (
            os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl")) else []
    for row in rows:
        if row["source_url"] == FULL["source"]:
            assert row["config"] == published
    # published layers 0-8, an eighth of the vocabulary, a sixteenth of the
    # experts: the guide's floors
    assert FULL["hybrid_override_pattern"] == (
        published["hybrid_override_pattern"][:9]) == "MEMEM*EME"
    assert FULL["num_hidden_layers"] == 9
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    assert FULL["n_routed_experts"] * 16 == published["n_routed_experts"]
    assert FULL["n_routed_experts"] >= 8
    # no width changed
    assert (FULL["hidden_size"], FULL["mamba_num_heads"],
            FULL["mamba_head_dim"], FULL["ssm_state_size"], FULL["n_groups"],
            FULL["conv_kernel"], FULL["chunk_size"],
            FULL["num_attention_heads"], FULL["num_key_value_heads"],
            FULL["head_dim"], FULL["moe_intermediate_size"],
            FULL["moe_shared_expert_intermediate_size"],
            FULL["num_experts_per_tok"], FULL["routed_scaling_factor"]) == (
                2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5)
    for key in ("source", "assumed", "deployment", "parameters", "notes"):
        assert FULL[key], key
    for part in ("layout", "mamba2", "attention", "moe", "training_terms",
                 "initialisation"):
        assert "recalled" in FULL["assumed"][part] or part == (
            "initialisation"), part
    (entry,) = [c for c in common.load_spec()["configs"]
                if c["name"] == "nemotron3_nano_30b_a3b-l9"]
    assert sorted(entry["reduced"]) == sorted(reduced)
    assert entry["source"] == FULL["source"]


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["published", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    known = (set(nh.MAPPED) | set(nh.FIXED) | set(nh.INERT)
             | set(common.CONFIG_META_KEYS))
    assert set(cfg) <= known
    # and every key of the source is accounted for
    assert set(FULL["published"]) <= (
        set(nh.MAPPED) | set(nh.FIXED) | set(nh.INERT))


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        layer_types: tuple = ()
        mamba_n_groups: int = 1
        attn_head_dim: int = 0
        rope: bool = True
        router_score: str = "softmax"
        routed_scaling: float = 1.0
        router_bias_rate: float = 0.0
        experts_held: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match=r"\['mlp_form', 'one_branch'\]"):
        nh.model_config(TOY, remat_block=False, seq_len=64)


def test_the_initialisation_centres_what_stands_behind_a_positive_activation():
    """``init_fn`` is ``llama.init_params`` but for the two departures the
    configuration file lists: the convolutions' bias 0, and each relu2 down
    matrix and each Mamba-2 ``out_proj`` centred over its inputs — every
    other leaf is the program's own draw."""
    from dlrover_tpu.models import llama

    mc = nh.model_config(TOY, remat_block=False, seq_len=64)
    got = nh.init_fn(mc)(jax.random.PRNGKey(3))
    own = llama.init_params(jax.random.PRNGKey(3), mc)
    moved = set()
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_flatten_with_path(own)[0]):
        if not np.array_equal(a, b):
            moved.add(path[-1].key)
    assert moved == {"conv_b", "out_proj", "wo", "w_down"}
    ssm, moe = got["layers"][0]["ssm"], got["layers"][1]["moe"]
    assert float(jnp.abs(ssm["conv_b"]).max()) == 0.0
    for leaf, axis in ((ssm["out_proj"], 0), (moe["wo"], 1),
                       (moe["shared"]["w_down"], 0)):
        assert float(jnp.abs(leaf.mean(axis)).max()) < 1e-8
        assert float(leaf.std()) == pytest.approx(0.02, rel=0.1)
    # the attention layer's ``wo`` is the draw: softmax averages, it has
    # no positive activation in front of it
    assert np.array_equal(got["layers"][3]["wo"], own["layers"][3]["wo"])
    assert "CENTRED" in FULL["assumed"]["initialisation"]


def test_the_adapter_runs_the_programs_own_loss(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), SEQ, 4096))
    hidden, loss, extra = nh.hidden_and_loss(params, toks, mc)
    fn = nh.loss_fn(mc)
    own, counters = fn(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert fn.rule_leaves == (
        "['layers'][1]['moe']['router_bias']",
        "['layers'][4]['moe']['router_bias']")
    assert fn.program_facts == {
        "ssm_layers": 2, "attention_layers": 1, "moe_layers": 2,
        "ssm_chunks_per_sequence": SEQ // 16, "mlp_form": "relu2",
        "moe_expert_backend": "reference"}
    assert hidden.shape == (2, SEQ, 64)
    assert sorted(extra["choices"]) == ["layers.1.experts",
                                        "layers.4.experts"]
    chosen = extra["choices"]["layers.4.experts"]
    assert chosen.shape == (2, SEQ, 3) and int(chosen.max()) > 3  # of WIDTH
    assert sorted(extra["scalars"]) == ["moe_aux"]
    assert float(extra["scalars"]["moe_aux"]) == pytest.approx(
        1e-4 * float(counters["moe_aux"]), rel=1e-6)
    assert counters["moe_tokens_per_expert"].shape == (2, WIDTH)
    assert counters["moe_held_pairs"].shape == (2,)
    assert counters["ssm_state_rms"].shape == (2,)
    assert float(counters["moe_router_bias_abs_max"]) == pytest.approx(1e-3)
    leaves = sorted(nh.grad_leaves(params))
    ssm = [f"layers.{i}.ssm.{name}" for i in (0, 2) for name in (
        "A_log", "D", "conv_b", "conv_w", "dt_bias", "in_proj", "norm",
        "out_proj")]
    assert leaves == sorted(["embed", "layers.0.ln1", "layers.2.ln1"] + ssm + [
        "layers.3.ln1", "layers.3.wq", "layers.3.wk", "layers.3.wv",
        "layers.3.wo", "layers.1.ln2", "layers.1.moe.router",
        "layers.1.moe.wi", "layers.1.moe.wo", "layers.1.moe.shared.w_up",
        "layers.1.moe.shared.w_down"])
    again = nh.with_leaves(params, nh.grad_leaves(params))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a is b, again, params))


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), SEQ, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = nh.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert float(extra["scalars"]["moe_aux"]) == pytest.approx(
        float(extra_r["scalars"]["moe_aux"]), rel=1e-5)
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))
        assert extra_r["probs"][name].shape == (2, SEQ, WIDTH)
    # under ``given`` the reference computes the system's experts
    _, loss_g, _ = ref.hidden_and_loss(
        params, toks, TOY, given=extra["choices"])
    assert float(loss_g) == pytest.approx(float(loss_r), rel=1e-6)


def test_the_reference_computes_the_experts_it_is_given(toy):
    """Another set than its own changes the result, and the choices it
    reports stay those it would have made itself."""
    _, _, params = toy
    toks = jnp.asarray(model.sample_tokens(3, range(1), SEQ, 4096))
    _, loss_own, own = ref.hidden_and_loss(params, toks, TOY)
    other = {name: (chosen + 1) % WIDTH
             for name, chosen in own["choices"].items()}
    _, loss_other, extra = ref.hidden_and_loss(params, toks, TOY, given=other)
    assert abs(float(loss_other) - float(loss_own)) > 1e-6
    # (the first routed layer's: the later one reads another stream now)
    first = "layers.1.experts"
    assert np.array_equal(extra["choices"][first], own["choices"][first])
    assert not np.array_equal(extra["choices"][first], other[first])


def test_the_reference_is_the_recurrence_and_imports_nothing_of_the_program():
    source = open(os.path.join(
        common.BENCH_DIR, "reference", "nemotron_h_ref.py")).read()
    code = source.split('"""', 2)[2]
    assert "dlrover_tpu" not in code and "import benchmark" not in code
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(step" in code and "chunk" not in code.replace(
        "scan_block", "")
    # the recurrence in blocks is the recurrence: any block length, one
    # result
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (70, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (70, 4)))
    decay = jnp.exp(-dt)
    b, c = (jax.random.normal(k[2], (70, 4, 16)),
            jax.random.normal(k[3], (70, 4, 16)))
    whole = ref._recurrence(x, dt, decay, b, c, 70)
    for block in (1, 16, 64):
        got = ref._recurrence(x, dt, decay, b, c, block)
        assert float(jnp.linalg.norm(got - whole)
                     / jnp.linalg.norm(whole)) < 1e-6


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == sorted([
        "A_log", "D", "conv_b", "conv_w", "dt_bias", "embed", "in_proj",
        "ln1", "ln2", "norm", "out_proj", "router", "w_down", "w_up", "wi",
        "wk", "wo", "wq", "wv"])
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(5)
    assert out["choice_diff_share_tol"] == pytest.approx(
        nh.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 5 ** 0.5)
    assert out["scalar_rel_diff_at"] == "moe_aux"


@pytest.mark.parametrize("fault", [
    "one_group_norm", "swiglu_expert", "silu_for_relu2", "rope_on",
    "no_routed_scaling", "fp8_stream", "norm_topk_prob flipped",
    "num_experts_per_tok minus one", "one expert fewer held",
    "balance weight off by a tenth",
])
def test_a_planted_fault_reads_not_ok(toy, fault):
    """The issue's six — the gated norm over one group, a SwiGLU expert,
    ``silu`` for relu2, an applied rotary embedding, the 2.5 left out, the
    normed stream rounded to e4m3 — and the routed block's own: each must
    read ``correct: false`` by at least one limit."""
    planted = nemotron_h_probe.planted_configs(TOY, ref)
    assert sorted(planted) == sorted(ref.FAULTS + (
        "norm_topk_prob flipped", "num_experts_per_tok minus one"))
    assert ref.PLANTED == ref.FAULTS + ref.STAND_INS == (
        "one_group_norm", "swiglu_expert", "silu_for_relu2", "rope_on",
        "no_routed_scaling", "fp8_stream")
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    elif fault in ref.STAND_INS:
        out = _check(toy, ref_cfg=dict(TOY, planted=fault))
    elif fault.startswith("one expert"):
        out = _check(toy, ref_cfg=dict(TOY, n_routed_experts=3))
    else:
        out = _check(toy, ref_cfg=dict(
            TOY, moe_aux_weight=1.1 * ref.AUX_WEIGHT))
    assert not out["ok"], out


def test_flop_and_byte_counts():
    per_token = nh.model_flops_per_token(FULL, 8192)
    total = per_token["total"]
    # the issue's count: a token's forward is 352 M MACs in matmuls and the
    # attention layer's pairs — four Mamba-2 layers 44 %, four routed layers
    # 27 % (the shared expert 23, this chip's picks 4), the attention layer
    # 16, the sliced head 12.5
    forward_macs = (per_token["matmul"] + per_token["attention"]) / 6.0
    assert forward_macs == pytest.approx(352e6, rel=5e-3)
    ssm_proj = 2688 * 10304 + 4096 * 2688
    assert 4 * ssm_proj / forward_macs == pytest.approx(0.44, abs=5e-3)
    shared, expert = 2 * 2688 * 3712, 2 * 2688 * 1856
    assert 4 * shared / forward_macs == pytest.approx(0.227, abs=5e-3)
    assert 4 * 0.375 * expert / forward_macs == pytest.approx(0.0425,
                                                              abs=5e-3)
    attention_proj = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert (attention_proj + per_token["attention"] / 6.0) / forward_macs == (
        pytest.approx(0.163, abs=5e-3))
    assert 2688 * 16384 / forward_macs == pytest.approx(0.125, abs=5e-3)
    routed = 2688 * 128 + shared + 0.375 * expert
    assert per_token["matmul"] == pytest.approx(6.0 * (
        4 * ssm_proj + attention_proj + 4 * routed + 2688 * 16384))
    assert per_token["scan"] == 3.0 * 4 * (4 * 4096 * 128 + 2 * 4 * 6144)
    counts = nh._counts(FULL)
    assert (counts["held_picks"], counts["routed_layers"],
            counts["ssm_layers"], counts["attention_layers"],
            counts["dense_layers"]) == (0.375, 4, 4, 1, 0)


def test_the_least_times_carry_the_readers_layer_count():
    """``flash_roofline`` and ``moe.grouped_matmul_roofline`` multiply by
    ``num_hidden_layers`` (9): the products are ONE attention layer's and
    FOUR routed layers' least times; ``ssm.scan_roofline`` multiplies by
    the program's own count of its state-space layers."""
    from benchmark.harness import flops

    layers = FULL["num_hidden_layers"]
    flash = nh.flash_least_seconds(FULL, 3, 8192, PEAKS)
    one = flops.flash_least_seconds(FULL, 3, 8192, PEAKS)
    assert flash["seconds"] * layers == pytest.approx(one["seconds"])
    pairs = 8192 * 8193 // 2
    assert flash["flops"] * layers == pytest.approx(
        7 * 2.0 * 32 * 128 * pairs * 3)
    assert flash["bound"] == "flops"
    least = nh.grouped_matmul_least_seconds(FULL, 3, 8192, PEAKS)
    rows = 3 * 8192 * 0.375  # 1,152 an expert
    assert least["flops"] * layers == pytest.approx(
        4 * 12.0 * rows * 2688 * 1856)
    assert least["bytes"] * layers == pytest.approx(
        4 * (12.0 * rows * (2688 + 1856) + 16.0 * 8 * 2688 * 1856))
    # 1,152 rows an expert at three sequences: the products (2.8 ms a
    # layer) outweigh rows and weights (1.4 ms); at one sequence's 384 rows
    # the weights would
    assert least["bound"] == "flops"
    assert least["seconds"] * layers == pytest.approx(
        4 * 12.0 * rows * 2688 * 1856 / 197e12)
    assert nh.grouped_matmul_least_seconds(FULL, 1, 8192, PEAKS)[
        "bound"] == "bytes"
    scan = nh.ssd_least_seconds(FULL, 3, 8192, PEAKS)
    tokens = 3 * 8192
    assert scan["flops"] == pytest.approx(3.0 * 4 * 64 * 64 * 128 * tokens)
    read = 2.0 * 4096 + 2 * 2.0 * 8 * 128 + 4.0 * 64
    assert scan["bytes"] == pytest.approx(
        (2 * (read + 2.0 * 4096) + read) * tokens)
    assert scan["bound"] == "bytes"


# -- the new per-layer readers ----------------------------------------------


def _program(monkeypatch, scopes, subscopes, **facts):
    rec = dict({"kind": "accelerate.program", "scopes": scopes}, **facts)
    if subscopes is not None:
        rec["subscopes"] = subscopes
    for module in (ssm_read, moe_read):
        monkeypatch.setattr(module.obs_read, "records", lambda spans: [rec])


def _counters():
    cell = common.load_cell(CELL_NAME)
    return {"traced_steps": 5, "cell": cell, "chips": 1, "peaks": PEAKS}


def _read(name, trace, counters=None):
    return common.load_module("layer_metrics", name).read(
        {"x": 1}, trace, counters or _counters())


def test_the_gate_share_on_a_traced_step(monkeypatch):
    scopes = {"f.1": ["forward", "ssm"], "f.2": ["backward", "ssm"],
              "f.3": ["recompute", "ssm"], "f.4": ["forward", "ssm"],
              "k.1": ["forward", "ssm"], "f.5": ["forward", "moe_experts"]}
    subscopes = {"f.1": "ssm_in", "f.2": "ssm_gate", "f.3": "ssm_gate",
                 "f.4": "ssm_out", "k.1": "ssd_chunk_fwd"}
    _program(monkeypatch, scopes, subscopes, ssm_layers=4)
    kernel_s = {"ssd_chunk_fwd": 0.5}
    trace = {"busy_s": 10.0, "kernel_s": kernel_s,
             "op_self_s": dict(kernel_s, **{
                 "f.1 bf16[8]": 1.0, "f.2": 0.4, "f.3": 0.35, "f.4": 0.75,
                 "f.5": 2.0}),
             "kernel_call_s": {"ssd_chunk_fwd": {"k.1": 0.5}}}
    secs = ssm_read.seconds({"x": 1}, trace)
    assert (secs["ssm"], secs["ssm_gate"], secs["ssm_scan"]) == (
        3.0, 0.75, 0.5)
    assert _read("ssm.gate_share_pct", trace) == pytest.approx(25.0)
    assert _read("step.ssm_share_pct", trace) == pytest.approx(30.0)
    least = nh.ssd_least_seconds(FULL, 3, 8192, PEAKS)["seconds"]
    assert _read("ssm.scan_roofline", trace) == pytest.approx(
        100.0 * least * 4 * 5 / 0.5)


@pytest.mark.parametrize("backend,want", [("pallas", 75.0),
                                          ("reference", 0.0)])
def test_the_experts_share_in_the_grouped_kernels(monkeypatch, backend, want):
    """``gmm`` / ``tgmm`` calls under ``moe_experts`` over the scope's
    seconds; with the experts through ``lax.ragged_dot`` the scope holds
    XLA's own operations alone and the share is 0, not nothing."""
    scopes = {"g.1": ["forward", "moe_experts"],
              "g.2": ["backward", "moe_experts"],
              "g.3": ["backward", "moe_combine"],
              "f.1": ["forward", "moe_experts"],
              "f.2": ["forward", "moe_router"]}
    _program(monkeypatch, scopes, None, moe_expert_backend=backend)
    if backend == "pallas":
        kernel_s = {"gmm": 1.5, "tgmm": 0.5}
        calls = {"gmm": {"g.1": 1.0, "g.3": 0.5}, "tgmm": {"g.2": 0.5}}
        ops = dict(kernel_s, **{"f.1": 0.5, "f.2": 1.0})
    else:
        kernel_s, calls = {}, {}
        ops = {"g.1": 1.0, "g.2": 0.5, "f.1": 0.5, "f.2": 1.0}
    trace = {"busy_s": 10.0, "kernel_s": kernel_s, "op_self_s": ops,
             "kernel_call_s": calls}
    secs = moe_read.scope_seconds({"x": 1}, trace)
    assert secs["moe_experts"] == 2.0
    # the call under ``moe_combine`` is no part of the scope
    assert _read("moe.experts_in_kernel_pct", trace) == pytest.approx(want)
    least = nh.grouped_matmul_least_seconds(FULL, 3, 8192, PEAKS)["seconds"]
    assert _read("moe.grouped_matmul_roofline", trace) == pytest.approx(
        100.0 * least * 9 * 5 / 2.0)


@pytest.mark.parametrize("name", ["ssm.gate_share_pct",
                                  "moe.experts_in_kernel_pct"])
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals neither scope for this cell (it cannot run it),
    a dense step no ``subscopes`` at all: the readers return None and do
    not raise."""
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5},
             "kernel_s": {"flash_fwd": 0.2}}
    _program(monkeypatch, {"f.1": ["forward", "attention"]}, None)
    assert _read(name, trace) is None
    _program(monkeypatch, {"f.1": ["forward", "gdn"]}, {"f.1": "gdn_conv"})
    assert _read(name, trace) is None
    assert common.load_module("layer_metrics", name).read({}, {}, {}) is None


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron3_nano_30b_a3b-l9", "train-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        3, True, {"fsdp": 1, "tp": 1})
    assert cell["why"] == entry["why"]
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert named == {
        "accelerate.compiled_peak_gb", "input.wait_ms_per_step",
        "step.mfu_pct", "flash_roofline", "kernel.pallas_share_pct",
        "device.idle_pct", "device.peak_hbm_gb", "step.lm_head_share_pct",
        "step.optimizer_share_pct", "step.recompute_share_pct",
        "step.attention_share_pct", "step.ssm_share_pct",
        "ssm.scan_share_pct", "ssm.scan_roofline", "step.moe_share_pct",
        "moe.permute_share_pct", "moe.grouped_matmul_roofline",
        "moe.load_max_over_mean", "moe.held_pair_share_pct",
        "moe.buffer_live_pct", "ssm.gate_share_pct",
        "moe.experts_in_kernel_pct"} | {
            m["name"] for m in spec["per_layer"] if "workloads" not in m}
    for m in spec["per_layer"][-2:]:
        assert (m["workloads"], m["moves"], m["source"], m["unit"]) == (
            [CELL_NAME], "train_tokens_per_s", "device_trace", "%")
    assert [m["name"] for m in spec["per_layer"][-2:]] == [
        "ssm.gate_share_pct", "moe.experts_in_kernel_pct"]
    assert {m["name"] for m in common.metrics_for(
        spec, "end_to_end", CELL_NAME)} == {"train_tokens_per_s", "setup_s"}
    assert len(spec["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--rehearse", "--workload", CELL_NAME, "--seconds", "2",
         "--trace", "1"],
        env=env, cwd=common.REPO, capture_output=True, text=True,
        timeout=900)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    last = res.stdout.strip().splitlines()[-1]
    found = json.loads(last[last.index("{"):])
    assert found["correct"] and found["failed"] == 0
    # the counters' readers find theirs on the CPU; the trace's readers
    # read a device trace, which a rehearsal has none of
    assert {"moe.held_pair_share_pct", "moe.load_max_over_mean",
            "moe.buffer_live_pct"} <= set(found["metrics_found"]) | {
                "moe.buffer_live_pct"}
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for name in ("'ssm_layers': 2", "'attention_layers': 1",
                 "'moe_layers': 2", "'mlp_form': 'relu2'",
                 "'moe_expert_backend': 'reference'",
                 "'ssm_chunks_per_sequence': 4", "'ssm_in'", "'ssm_conv'",
                 "'ssm_scan'", "'ssm_gate'", "'ssm_out'", "'moe_permute'",
                 "'moe_shared'"):
        assert name in program
