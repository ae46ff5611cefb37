"""The Trinity-Mini configuration (HF ``afmoe``): the file against the
catalog's row, the adapter's tables, the system against the plain reference at
toy widths with every planted fault found, the counts behind the cell's
per-layer metrics, the two readers of the new scopes on a stand-in trace, and
the cell rehearsed end to end on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.adapters import afmoe
from benchmark.harness import afmoe_probe, afmoe_read, common, model
from benchmark.reference import afmoe_ref as ref

CELL_NAME = "trinity_mini-l5.train-16k-decayed"
FULL = common.load_json("configs", "trinity_mini-l5.json")
TOY = common.load_json("configs", "trinity-mini-rehearsal.json")
SEQ = 96
CELL = {
    "name": "trinity-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": SEQ, "learning_rate": 1e-5},
}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
S16K = 16384
FULL_PAIRS, WINDOW_PAIRS = 134_225_920, 31_458_304
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def toy():
    from dlrover_tpu import obs

    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(1))["params"]
    yield job, mc, _decisive(params)
    # the build's spans stay in the process's ring: a later file's test
    # of "nothing recorded" (test_obs_read.py) must find it empty
    obs.reset()


def _decisive(params):
    """At 96 positions and toy widths a router of N(0, 0.02) is nearly
    even and a selection bias of zero leaks nowhere: a router 3 times
    larger prefers some experts, biases moved as the probe moves them
    (``afmoe_probe.with_moved_biases``) show in a weight they leak into, and
    gains off 1 tell a norm from none — as a trained model's do."""
    def layer_of(layer):
        gain = 1.0 + 0.3 * jnp.cos(jnp.arange(
            layer["q_norm"].shape[0], dtype=jnp.float32))
        out = 1.0 + 0.3 * jnp.sin(jnp.arange(
            layer["ln1_out"].shape[0], dtype=jnp.float32))
        layer = dict(layer, q_norm=gain, k_norm=gain[::-1],
                     ln1_out=layer["ln1_out"] * out, ln2_out=out[::-1])
        if "moe" in layer:
            layer["moe"] = dict(layer["moe"],
                                router=3.0 * layer["moe"]["router"])
        return layer

    return afmoe_probe.with_moved_biases(
        dict(params, layers=[layer_of(x) for x in params["layers"]]), 1)


def _check(toy, ref_cfg=None):
    job, mc, params = toy
    return model.check_against_reference(
        job, mc, CELL, params, 1, ref_cfg=ref_cfg)


# -- the file and the adapter's tables -----------------------------------------


def test_the_adapter_says_what_the_configuration_says():
    from dlrover_tpu.models import llama

    mc = afmoe.model_config(FULL, remat_block=True, seq_len=S16K)
    assert (mc.n_layer, mc.d_model, mc.n_head, mc.n_kv_head, mc.head_dim,
            mc.d_ff, mc.vocab_size, mc.rms_eps) == (
                5, 2048, 32, 4, 128, 6144, 25024, 1e-5)
    assert mc.layer_types == ("window_attention",) * 4 + ("attention",)
    assert (mc.sliding_window, mc.window_of("window_attention"),
            mc.window_of("attention")) == (2048, 2048, 0)
    # the window layers rotate on the plain table, the full layer not at all
    assert dict(mc.rotary_by_kind) == {
        "window_attention": llama.Rotary(theta=10000.0), "attention": None}
    assert (mc.rope, mc.unrotated("attention"),
            mc.unrotated("window_attention")) == (True, True, False)
    assert (mc.qk_norm, mc.qk_norm_per_head, mc.attn_output_gate,
            mc.branch_norm, mc.partial_rotary_factor) == (
                True, True, True, True, 1.0)
    assert mc.embedding_multiplier == pytest.approx(2048 ** 0.5)
    assert (mc.residual_multiplier, mc.logits_scaling) == (1.0, 1.0)
    assert (mc.num_experts, mc.experts_held, mc.experts_held_first,
            mc.top_k, mc.expert_width, mc.norm_topk_prob, mc.router_score,
            mc.routed_scaling, mc.router_norm_eps, mc.router_bias_rate,
            mc.n_shared_experts, mc.first_k_dense, mc.capacity_factor) == (
                128, 16, 0, 8, 1024, True, "sigmoid", 2.826, 1e-20, 0.001, 1,
                1, None)
    assert [mc.is_moe_layer(i) for i in range(5)] == [False] + [True] * 4
    assert (mc.max_seq_len, mc.remat_block, mc.mtp_layers) == (S16K, True, 0)
    # an all-full stack has no window and names no window kind
    full = afmoe.model_config(
        dict(TOY, layer_types=["full_attention"] * 5), remat_block=False,
        seq_len=SEQ)
    assert full.sliding_window == 0
    assert dict(full.rotary_by_kind) == {"attention": None}
    assert afmoe.model_config(dict(TOY, mup_enabled=False),
                              remat_block=False,
                              seq_len=SEQ).embedding_multiplier == 1.0


def test_the_file_is_the_source_but_for_what_it_lists():
    """Every number of the catalog's row under its own key; what differs is
    depth, the layer list cut to it, the leading dense layers, the experts
    held and the vocabulary — five keys, none a width; the parameter count
    is the leaves' own."""
    published = FULL["published"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"]
    assert published == row["config"]
    assert FULL["source"] == row["source_url"]
    arch = set(FULL) - set(common.CONFIG_META_KEYS)
    assert arch == set(published)
    assert {k for k in arch if FULL[k] != published[k]} == set(
        FULL["reduced"]) == {"num_hidden_layers", "layer_types",
                             "num_dense_layers", "num_experts", "vocab_size"}
    for key, cut in FULL["reduced"].items():
        assert (cut["from"], cut["to"]) == (published[key], FULL[key]), key
    assert FULL["layer_types"] == (
        ["sliding_attention"] * 4 + ["full_attention"])
    # the routed layers of the cut are one whole published period
    assert FULL["layer_types"][1:] == published["layer_types"][4:8]
    assert published["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert (published["num_hidden_layers"], published["num_experts"],
            published["num_dense_layers"], published["vocab_size"],
            FULL["num_experts"], FULL["vocab_size"]) == (
                32, 128, 2, 200192, 16, 25024)
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    for width in ("hidden_size", "head_dim", "moe_intermediate_size",
                  "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_experts_per_tok",
                  "num_shared_experts", "sliding_window", "route_scale",
                  "rope_theta"):
        assert FULL[width] == published[width], width
    mc = afmoe.model_config(FULL, remat_block=False, seq_len=64)
    shapes = jax.eval_shape(afmoe.init_fn(mc), jax.random.PRNGKey(0))
    count = sum(int(jnp.prod(jnp.array(a.shape)))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == FULL["parameters"] == 705_474_304
    assert all("recalled without a network" in FULL["assumed"][k]
               for k in ("layout", "mup_enabled", "attention", "moe"))
    assert "INFERENCE" in FULL["assumed"]["load_balance_coeff"]
    assert "not reproduced" in FULL["assumed"]["load_balance_coeff"]
    assert "eight v5e chips share each layer" in FULL["deployment"]
    (entry,) = [c for c in common.load_spec()["configs"]
                if c["name"] == "trinity_mini-l5"]
    assert entry["reduced"] == list(FULL["reduced"])
    assert entry["source"] == FULL["source"]


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["published", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    arch = set(cfg) - set(common.CONFIG_META_KEYS)
    tables = set(afmoe.MAPPED) | set(afmoe.FIXED) | set(afmoe.INERT)
    assert arch <= tables
    # and the tables name nothing the row does not have
    assert tables == set(FULL["published"])
    assert not set(afmoe.MAPPED) & set(afmoe.FIXED)
    with pytest.raises(ValueError,
                       match=r"does not know the key\(s\) \['rope_parameters'\]"):
        afmoe.model_config(dict(cfg, rope_parameters={}), remat_block=False,
                           seq_len=64)


@pytest.mark.parametrize("over,match", [
    (dict(score_func="softmax"), "score_func"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(rope_scaling={"rope_type": "yarn"}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(model_type="mellum"), "model_type"),
    (dict(layer_types=["sliding_attention"] * 4 + ["chunked_attention"]),
     "each of"),
    (dict(layer_types=["sliding_attention"] * 4), "5 layers"),
    (dict(num_dense_layers=5), "fewer than all"),
], ids=["softmax_scores", "groups", "group_limit", "scaled_rotary", "tied",
        "another_type", "another_kind", "a_short_list", "no_routed_layer"])
def test_the_adapter_refuses_what_the_program_does_not_compute(over, match):
    with pytest.raises(ValueError, match=match):
        afmoe.model_config(dict(TOY, **over), remat_block=False, seq_len=64)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    """The parent of the PR that let a kind go without position (no
    ``LlamaConfig.unrotated``) fails on the cell at once, before anything is
    compiled."""
    from dlrover_tpu.models import llama

    monkeypatch.delattr(llama.LlamaConfig, "unrotated")
    with pytest.raises(ValueError, match=r"has no \['unrotated'\]"):
        afmoe.model_config(FULL, remat_block=True, seq_len=S16K)


def test_the_initialisation_lets_the_tokens_own_part_carry_the_stream():
    """``llama.init_params`` but for ``post_attention_layernorm``'s gain at
    0.1: the attention branch, which at random weights hands every position
    nearly the same vector, enters the stream a tenth as loud, and a router
    at initialisation spreads a sequence's tokens over its experts (the
    adapter says why)."""
    from dlrover_tpu.models import llama

    mc = afmoe.model_config(TOY, remat_block=False, seq_len=SEQ)
    key = jax.random.PRNGKey(4)
    params, plain = afmoe.init_fn(mc)(key), llama.init_params(key, mc)
    assert afmoe.POST_ATTENTION_GAIN == 0.1
    for layer, was in zip(params["layers"], plain["layers"]):
        assert jnp.allclose(layer["ln1_out"], 0.1)
        same = dict(layer, ln1_out=was["ln1_out"])
        assert all(jnp.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(same), jax.tree_util.tree_leaves(was)))
    for name in ("embed", "lm_head", "ln_f"):
        assert jnp.array_equal(params[name], plain[name])
    tokens = jnp.asarray(model.sample_tokens(4, range(2), SEQ, 4096))

    def fullest(p):
        _, aux = llama.forward_hidden(p, tokens[:, :-1], mc)
        per_expert = aux["moe_tokens_per_expert"].astype(jnp.float32)
        assert per_expert.shape == (4, 16)
        return float(jnp.max(per_expert.max(1) * 16 / per_expert.sum(1)))

    assert fullest(params) < min(fullest(plain), 2.0)


# -- the comparison at toy widths ------------------------------------------------


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    job, mc, params = toy
    tokens = jnp.asarray(model.sample_tokens(3, range(2), SEQ, 4096))
    hidden, loss, extra = jax.jit(
        lambda p: afmoe.hidden_and_loss(p, tokens, mc))(params)
    want, counters = jax.jit(lambda p: afmoe.loss_fn(mc)(
        p, {"tokens": tokens}))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert hidden.shape == (2, SEQ, 64) and hidden.dtype == jnp.float32
    assert sorted(extra["choices"]) == [
        ref.experts_name(i) for i in range(1, 5)]
    assert extra["choices"]["layers.1.experts"].shape == (2, SEQ, 3)
    assert sorted(extra["scalars"]) == ["window_alone_least",
                                        "window_alone_most"]
    assert set(counters) >= {"moe_tokens_per_expert", "moe_held_pairs",
                             "moe_router_bias_abs_max", "rule_updates"}
    fn = afmoe.loss_fn(mc)
    assert fn.rule_leaves == llama.rule_leaves(mc) and len(fn.rule_leaves) == 4
    assert fn.program_facts == llama.program_facts(mc, SEQ)
    assert (fn.program_facts["window_attention_layers"],
            fn.program_facts["unrotated_attention_layers"]) == (4, 1)


def test_the_compared_leaves_are_of_both_kinds_and_the_held_columns(toy):
    _, _, params = toy
    leaves = afmoe.grad_leaves(params)
    assert sorted({name.rsplit(".", 1)[0] for name in leaves
                   if name != "embed"}) == [
        "layers.0", "layers.1", "layers.1.moe", "layers.1.moe.shared",
        "layers.3", "layers.4"]
    # of the router the held experts' columns alone
    assert leaves["layers.1.moe.router"].shape == (64, 4)
    assert params["layers"][1]["moe"]["router"].shape == (64, 16)
    back = afmoe.with_leaves(params, jax.tree_util.tree_map(
        lambda a: a + 1, leaves))
    assert float(back["layers"][4]["wq"][0, 0]) == pytest.approx(
        float(params["layers"][4]["wq"][0, 0]) + 1)
    router, was = back["layers"][1]["moe"]["router"], params["layers"][1][
        "moe"]["router"]
    assert jnp.allclose(router[:, :4], was[:, :4] + 1)
    assert jnp.array_equal(router[:, 4:], was[:, 4:])
    assert float(back["layers"][1]["moe"]["shared"]["w_up"][0, 0]) == (
        pytest.approx(float(
            params["layers"][1]["moe"]["shared"]["w_up"][0, 0]) + 1))
    assert float(back["layers"][1]["moe"]["wi"][0, 0, 0]) == pytest.approx(
        float(params["layers"][1]["moe"]["wi"][0, 0, 0]) + 1)
    assert back["layers"][2] is params["layers"][2]


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == sorted([
        "embed", "k_norm", "q_norm", "ln1_out", "ln2_out", "router", "wg",
        "wi", "wk", "wo", "wq", "wv", "w_gate", "w_up", "w_down"])
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(5)
    assert out["choice_diff_share_tol"] == pytest.approx(
        afmoe.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 5 ** 0.5)
    assert out["scalar_rel_diff"] == 0.0
    assert out["scalar_rel_diff_at"].startswith("window_alone_")


FAULTS = ("full_rotated", "window_unrotated", "window_off_by_one",
          "gate_dropped", "gate_per_head", "post_attention_norm_dropped",
          "post_mlp_norm_dropped", "bias_in_weight", "route_scale_dropped",
          "embedding_multiplier_dropped")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_ok(toy, fault):
    """The issue's faults, each alone — a full layer rotated, the window
    layers unrotated, the window one key short, the gate dropped, the gate
    one scalar a head, an output norm dropped (either), the bias added to a
    weight, ``route_scale`` dropped, the embedding's multiplier dropped: each
    must read ``correct: false`` by at least one limit."""
    planted = afmoe_probe.planted_configs(TOY, ref)
    assert tuple(planted) == ref.FAULTS == FAULTS
    assert ref.PLANTED == FAULTS + ref.STAND_INS
    out = _check(toy, ref_cfg=planted[fault])
    assert not out["ok"], out
    if fault == "window_off_by_one":  # the window read alone finds it
        assert out["scalar_rel_diff"] == pytest.approx(1.0)
        assert out["scalar_rel_diff_at"] == "window_alone_least"


def test_the_window_read_alone_tells_a_key_too_many_or_too_few():
    """``window_alone`` on a masked softmax of the right window reads 2 and
    1; one key short and some query's one flagged key is gone (least 1);
    one key long and some query sees two (most 2 x 16 / 17)."""
    def read(off):
        with jax.default_matmul_precision("highest"):
            out = ref.window_alone(16, SEQ, (4, 2, 32), lambda q, k, v:
                                   ref._attend(q, k, v, 16 + off, 32))
        return (float(out["window_alone_least"]),
                float(out["window_alone_most"]))

    assert read(0) == (2.0, 1.0)
    assert read(-1) == (1.0, pytest.approx(16 / 15))
    assert read(1) == (pytest.approx(1 + 16 / 17), pytest.approx(32 / 17))
    # a stack without a window layer, or a sequence inside one window, has
    # no edge to read
    assert ref.window_alone(0, SEQ, (4, 2, 32), None) == {}
    assert ref.window_alone(128, SEQ, (4, 2, 32), None) == {}


@pytest.mark.parametrize("stand_in,moves,times", [
    ("fp8_stream", "hidden_rel_l2", 1.5),
    ("fp8_router_stream", "choice_prob_gap", 1.2),
    ("bf16_stated_f32", "choice_prob_gap", 1.5)])
def test_a_lower_precision_stand_in_moves_what_its_limit_is_on(
        toy, stand_in, moves, times):
    """At toy widths (96 positions, 16 experts) the standing tolerances may
    hold a stand-in, so here each only has to MOVE the distance its limit
    is on; that it reads ``correct: false`` at published width and 16,384
    positions is the chip probe's to show (PERF.md section 6)."""
    assert stand_in in ref.STAND_INS
    true, low = _check(toy), _check(toy, ref_cfg=dict(TOY, planted=stand_in))
    assert low[moves] > times * true[moves], (low[moves], true[moves])


def test_an_unknown_planted_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault"):
        ref.hidden_and_loss(None, jnp.zeros((1, 9), jnp.int32),
                            dict(TOY, planted="nothing"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(common.BENCH_DIR, "reference",
                           "afmoe_ref.py")) as f:
        text = f.read()
    assert "import dlrover_tpu" not in text and "from dlrover_tpu" not in text
    assert "from benchmark" not in text and "import benchmark" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "checkpoint_name" not in text


def test_the_probe_moves_every_selection_bias_by_seeded_signs(toy):
    _, _, params = toy
    moved = afmoe_probe.with_moved_biases(params, 7)
    biases = [layer["moe"]["router_bias"] for layer in moved["layers"]
              if "moe" in layer]
    assert len(biases) == 4
    assert all(jnp.allclose(jnp.abs(b), afmoe_probe.MOVED_BIAS)
               for b in biases)
    assert not jnp.array_equal(biases[0], biases[1])
    assert jnp.array_equal(
        biases[0], afmoe_probe.with_moved_biases(params, 7)["layers"][1][
            "moe"]["router_bias"])
    assert moved["layers"][0] is params["layers"][0]


# -- counts ------------------------------------------------------------------------


def test_pair_and_flop_counts():
    assert afmoe.pairs_by_kind(FULL, S16K) == {
        "sliding_attention": (4, WINDOW_PAIRS),
        "full_attention": (1, FULL_PAIRS)}
    per_token = afmoe.model_flops_per_token(FULL, S16K)
    # by hand, parameters a token meets in a matmul: q with its gate 2,048 x
    # 8,192, k and v 2 x 2,048 x 512, o 4,096 x 2,048 in each of five layers;
    # the dense MLP 3 x 2,048 x 6,144; per routed layer the router 2,048 x
    # 128, the shared expert and ONE held pick (8 x 16 / 128) of 3 x 2,048 x
    # 1,024; the head's slice 2,048 x 25,024
    attention_proj = 16_777_216 + 2_097_152 + 8_388_608
    routed = 262_144 + 2 * 6_291_456
    params = (5 * attention_proj + 37_748_736 + 4 * routed + 2048 * 25024)
    assert params == 276_692_992
    assert per_token["matmul"] == pytest.approx(6.0 * params)
    # scores and values: 2 matmuls x 2 FLOPs x 32 heads x 128 a pair, x 3
    pairs = 4 * WINDOW_PAIRS + FULL_PAIRS
    assert pairs == 260_059_136
    assert per_token["attention"] == pytest.approx(
        3.0 * 16384 * pairs / S16K)
    forward = per_token["total"] / 3.0
    assert forward == pytest.approx(813.4e6, rel=1e-3)
    # the five attention branches (projections with the gate, and pairs) are
    # two thirds of the forward work, the gate's columns alone a tenth
    assert (2 * 5 * attention_proj + 16384 * pairs / S16K) / forward == (
        pytest.approx(0.655, abs=5e-3))
    assert 2 * 5 * 8_388_608 / forward == pytest.approx(0.103, abs=2e-3)
    # were the four window layers charged as full ones, the flash kernels'
    # least time would read 2.6 times too high
    assert 5 * FULL_PAIRS / pairs == pytest.approx(2.58, abs=0.01)


def test_the_least_times_carry_the_readers_layer_count():
    one = afmoe.flash_least_seconds(FULL, 1, S16K, PEAKS)
    window = afmoe.flash_window_least_seconds(FULL, 1, S16K, PEAKS)
    flop = lambda pairs: 14.0 * 32 * 128 * pairs  # noqa: E731
    assert window["flops"] == pytest.approx(4 * flop(WINDOW_PAIRS))
    assert window["seconds"] == pytest.approx(
        4 * flop(WINDOW_PAIRS) / 197e12)
    assert window["bound"] == "flops"
    # ``flash_roofline``'s reader multiplies by the 5 layers
    assert 5 * one["flops"] == pytest.approx(
        flop(FULL_PAIRS) + 4 * flop(WINDOW_PAIRS))
    assert 5 * one["seconds"] == pytest.approx(
        (flop(FULL_PAIRS) + 4 * flop(WINDOW_PAIRS)) / 197e12)
    # q, k, v, o once forward, eight arrays backward, bf16
    assert 5 * one["bytes"] == pytest.approx(
        5 * 6 * 2.0 * S16K * (32 + 4) * 128)
    grouped = afmoe.grouped_matmul_least_seconds(FULL, 1, S16K, PEAKS)
    # one held pick a token: 16,384 rows over 16 experts, in 4 of the 5
    # layers the reader multiplies by
    assert 5 * grouped["flops"] == pytest.approx(
        4 * 18.0 * 16384 * 2048 * 1024)
    assert 5 * grouped["bytes"] == pytest.approx(
        4 * (18.0 * 16384 * (2048 + 1024) + 24.0 * 16 * 2048 * 1024))
    assert grouped["bound"] == "flops"


# -- the two readers of the new scopes ----------------------------------------------


def _program(monkeypatch, scopes, subscopes=None, kernel_scopes=None):
    rec = {"kind": "accelerate.program", "scopes": scopes}
    if subscopes is not None:
        rec["subscopes"] = subscopes
    if kernel_scopes is not None:
        rec["kernel_scopes"] = kernel_scopes
    monkeypatch.setattr(afmoe_read.obs_read, "records", lambda spans: [rec])


def _read(name, trace):
    return common.load_module("layer_metrics", name).read(
        {"x": 1}, trace, {"traced_steps": 5})


TRACE = {
    "busy_s": 10.0, "kernel_s": {"rmsnorm_fwd": 0.9, "flash_fwd": 2.0},
    "op_self_s": {"rmsnorm_fwd": 0.9, "flash_fwd": 2.0, "g.1 f32[8]": 0.25,
                  "g.2": 0.15, "nb.1": 0.3, "nb.2": 0.2, "nb.3": 0.1,
                  "p.1": 1.0, "m.1": 3.0},
    "kernel_call_s": {
        "rmsnorm_fwd": {"n.1": 0.2, "n.2": 0.3, "n.3": 0.1, "n.4": 0.3},
        "flash_fwd": {"fw.1": 2.0}}}


def test_the_gate_and_norm_shares_on_a_traced_step(monkeypatch):
    attention = lambda phase: [phase, "attention"]  # noqa: E731
    scopes = {"fw.1": attention("forward"), "g.1": attention("forward"),
              "g.2": attention("backward"), "p.1": attention("forward"),
              "n.1": attention("forward"), "nb.1": attention("backward"),
              "n.2": ["forward", "moe_combine"],
              "nb.2": ["backward", "moe_combine"],
              "n.3": ["recompute", "mlp"], "nb.3": ["backward", "mlp"],
              "n.4": ["forward", "final_norm"], "m.1": ["forward", "mlp"]}
    # an XLA instruction's innermost scope; a kernel's call is named by the
    # kernel there, and by the scope above it in the table of its own
    subscopes = {"g.1": "attn_gate", "g.2": "attn_gate", "nb.1": "branch_norm",
                 "nb.2": "branch_norm", "nb.3": "branch_norm",
                 "n.1": "rmsnorm_fwd", "fw.1": "flash_fwd"}
    kernel_scopes = {"n.1": "branch_norm", "n.2": "branch_norm",
                     "n.3": "branch_norm", "fw.1": "attn_window"}
    _program(monkeypatch, scopes, subscopes, kernel_scopes)
    secs = afmoe_read.seconds({"x": 1}, TRACE)
    assert secs["attn_gate"] == pytest.approx(0.4)
    # the input norms' and the final norm's calls (n.4) are no output norm's
    assert secs["branch_norm"] == pytest.approx(0.6 + 0.6)
    # everything under the outermost ``attention``: kernel, gate, its norm
    assert secs["attention"] == pytest.approx(2.0 + 0.4 + 1.0 + 0.2 + 0.3)
    assert _read("attn.gate_share_pct", TRACE) == pytest.approx(
        100.0 * 0.4 / 3.9)
    assert _read("step.branch_norm_share_pct", TRACE) == pytest.approx(12.0)


def test_a_model_with_one_of_the_two_reads_the_one(monkeypatch):
    """A looped sandwich-norm model without a gate, a gated model with two
    norms a block: each reader reads its own scope and the other None."""
    scopes = {"g.1": ["forward", "attention"], "nb.1": ["backward", "mlp"],
              "n.1": ["forward", "attention"]}
    _program(monkeypatch, scopes, {"nb.1": "branch_norm"},
             {"n.1": "branch_norm"})
    assert _read("attn.gate_share_pct", TRACE) is None
    assert _read("step.branch_norm_share_pct", TRACE) == pytest.approx(5.0)
    _program(monkeypatch, scopes, {"g.1": "attn_gate", "x.9": "gdn_scan"})
    assert _read("step.branch_norm_share_pct", TRACE) is None
    assert _read("attn.gate_share_pct", TRACE) == pytest.approx(
        100.0 * 0.25 / (0.25 + 0.2))


@pytest.mark.parametrize("name", ["attn.gate_share_pct",
                                  "step.branch_norm_share_pct"])
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals neither scope (and cannot run the cell), a model
    without gate and sandwich norms neither: the readers return None and do
    not raise."""
    _program(monkeypatch, {"g.1": ["forward", "attention"]})
    assert _read(name, TRACE) is None
    _program(monkeypatch, {"g.1": ["forward", "ssm"]}, {"g.1": "ssm_in"},
             {"fw.1": "attn_window"})
    assert _read(name, TRACE) is None
    assert common.load_module("layer_metrics", name).read({}, {}, {}) is None


def test_the_program_says_under_which_scope_the_norms_kernel_is_called():
    from dlrover_tpu.parallel.accelerate import program_summary

    call = ('  %{name} = bf16[8,128]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call", metadata={{op_name='
            '"jit(train_step)/{path}/pallas_call"}}')
    mul = ('  %{name} = f32[8,128]{{1,0}} multiply(%p, %p), metadata='
           '{{op_name="jit(train_step)/{path}/mul"}}')
    text = "\n".join([
        "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
        "  %p = bf16[8,128]{1,0} parameter(0)",
        call.format(name="rmsnorm_fwd.1",
                    path="jvp(attention)/branch_norm/rmsnorm_fwd"),
        call.format(name="rmsnorm_fwd.2",
                    path="rematted_computation/moe_combine/branch_norm/"
                         "rmsnorm_fwd"),
        call.format(name="rmsnorm_fwd.3", path="jvp(attention)/rmsnorm_fwd"),
        mul.format(name="multiply.4", path="jvp(attention)/attn_gate"),
        mul.format(name="multiply.5",
                   path="transpose(jvp(attention))/attn_gate"),
        "}"])
    summary = program_summary(text)
    assert summary["kernel_scopes"] == {"rmsnorm_fwd.1": "branch_norm",
                                        "rmsnorm_fwd.2": "branch_norm"}
    assert summary["subscopes"]["multiply.4"] == "attn_gate"
    assert summary["scopes"]["multiply.5"] == ["backward", "attention"]
    assert summary["scopes"]["rmsnorm_fwd.2"] == ["recompute", "moe_combine"]


# -- the cell ------------------------------------------------------------------------


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    assert len(spec["workloads"]) >= 13
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "trinity_mini-l5", "train-16k-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        1, True, {"fsdp": 1, "tp": 1})
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert (cell["traffic_data"]["seq_len"],
            cell["traffic_data"]["kind"]) == (S16K, "train_steady")
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert named >= {
        "accelerate.compiled_peak_gb", "input.wait_ms_per_step",
        "step.mfu_pct", "flash_roofline", "kernel.pallas_share_pct",
        "device.idle_pct", "device.peak_hbm_gb", "step.lm_head_share_pct",
        "step.optimizer_share_pct", "step.recompute_share_pct",
        "step.attention_share_pct", "step.moe_share_pct",
        "moe.permute_share_pct", "moe.grouped_matmul_roofline",
        "moe.load_max_over_mean", "moe.held_pair_share_pct",
        "moe.buffer_live_pct", "attn.window_share_pct",
        "flash.window_roofline", "attn.gate_share_pct",
        "step.branch_norm_share_pct"} | {
            m["name"] for m in spec["per_layer"] if "workloads" not in m}
    new = {m["name"]: m for m in spec["per_layer"]
           if m["name"] in ("attn.gate_share_pct",
                            "step.branch_norm_share_pct")}
    assert new["attn.gate_share_pct"]["workloads"] == [
        CELL_NAME, "qwen3_next_80b_a3b-l4.train-decayed"]
    assert new["step.branch_norm_share_pct"]["workloads"] == [
        CELL_NAME, "ouro2_6b-l8.train-4k"]
    for m in new.values():
        assert (m["moves"], m["source"], m["unit"], m["layer"]) == (
            "train_tokens_per_s", "device_trace", "%", "model step")
        reader = common.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.SOURCE) == (m["layer"], m["source"])
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
    assert {"moe.held_pair_share_pct", "moe.load_max_over_mean"} <= set(
        found["metrics_found"])
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for said in ("'window_attention_layers': 4", "'attention_layers': 5",
                 "'unrotated_attention_layers': 1", "'attn_gate'",
                 "'branch_norm'"):
        assert said in program, said
