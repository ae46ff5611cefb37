"""The Mellum 2 configuration (window and full attention layers in one
stack, each kind on its own rotary table, a share of softmax-routed
experts): the adapter's key tables against the catalog's row as the file
keeps it, its own counts of pairs, FLOPs and least times, the comparison with
``reference/mellum_ref.py`` at toy widths with every planted fault, the two
readers of the attention kinds' scopes on a recorded toy trace, and the cell
rehearsed end to end on the CPU."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.adapters import mellum
from benchmark.harness import common, mellum_probe, model, window_read
from benchmark.reference import mellum_ref as ref

CELL_NAME = "mellum2_12b_a2_5b-l8.train-16k-decayed"
FULL = common.load_json("configs", "mellum2_12b_a2_5b-l8.json")
TOY = common.load_json("configs", "mellum-rehearsal.json")
SEQ = 96
CELL = {
    "name": "mellum-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": SEQ, "learning_rate": 1e-5},
}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
S16K = 16384
FULL_PAIRS, WINDOW_PAIRS = 134_225_920, 16_253_440


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
    """At initialisation attention's softmax is flat and the routers' 64
    probabilities nearly even: queries and keys 8 times larger prefer some
    keys (so that a window, a table or a norm matters), a router 3 times
    larger prefers some experts, and head gains off 1 tell a norm from
    none — as a trained model's do."""
    def layer_of(layer):
        gain = 1.0 + 0.3 * jnp.cos(jnp.arange(
            layer["q_norm"].shape[0], dtype=jnp.float32))
        return dict(layer, wq=8.0 * layer["wq"], wk=8.0 * layer["wk"],
                    q_norm=gain, k_norm=gain[::-1],
                    moe=dict(layer["moe"], router=3.0 * layer["moe"]["router"]))

    return dict(params, layers=[layer_of(l) for l in params["layers"]])


def _check(toy, ref_cfg=None):
    job, mc, params = toy
    return model.check_against_reference(
        job, mc, CELL, params, 1, ref_cfg=ref_cfg)


# -- the file and the adapter's tables -----------------------------------------


def test_the_adapter_says_what_the_configuration_says():
    from dlrover_tpu.models import llama

    mc = mellum.model_config(FULL, remat_block=True, seq_len=S16K)
    assert (mc.n_layer, mc.d_model, mc.n_head, mc.n_kv_head, mc.head_dim,
            mc.vocab_size, mc.rms_eps) == (8, 2304, 32, 4, 128, 12288, 1e-6)
    assert mc.layer_types == (
        ("window_attention",) * 3 + ("attention",)) * 2
    assert (mc.sliding_window, mc.window_of("window_attention"),
            mc.window_of("attention")) == (1024, 1024, 0)
    assert dict(mc.rotary_by_kind) == {
        "window_attention": llama.Rotary(theta=500000.0),
        "attention": llama.Rotary(
            theta=500000.0, factor=16.0,
            original_max_position_embeddings=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)}
    assert (mc.qk_norm, mc.qk_norm_per_head, mc.attn_output_gate,
            mc.partial_rotary_factor) == (True, True, False, 1.0)
    assert (mc.num_experts, mc.experts_held, mc.experts_held_first,
            mc.top_k, mc.expert_width, mc.norm_topk_prob, mc.balance_all_k,
            mc.router_score, mc.n_shared_experts, mc.capacity_factor) == (
                64, 8, 0, 8, 896, True, True, "softmax", 0, None)
    assert all(mc.is_moe_layer(i) for i in range(8))
    assert (mc.max_seq_len, mc.remat_block) == (S16K, True)
    # an all-full stack has no window: HF's ``sliding_window`` is the
    # sliding layers' own
    full = dict(TOY, layer_types=["full_attention"] * 4, rope_parameters={
        "full_attention": TOY["rope_parameters"]["full_attention"]})
    assert mellum.model_config(
        full, remat_block=False, seq_len=SEQ).sliding_window == 0


def test_the_file_is_the_source_but_for_what_it_lists():
    """Every number of the catalog's row under its own key; what differs
    is depth, the two per-layer lists cut to it, the experts held and the
    vocabulary; the parameter count is the leaves' own."""
    published = FULL["published"]
    arch = set(FULL) - set(common.CONFIG_META_KEYS)
    assert arch == set(published)
    assert {k for k in arch if FULL[k] != published[k]} == set(
        FULL["reduced"]) == {"num_hidden_layers", "layer_types",
                             "mlp_layer_types", "num_experts", "vocab_size"}
    assert FULL["layer_types"] == published["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert FULL["mlp_layer_types"] == ["sparse"] * 8
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"], FULL["num_experts"],
            FULL["vocab_size"]) == (28, 64, 98304, 8, 12288)
    for width in ("hidden_size", "head_dim", "moe_intermediate_size",
                  "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_experts_per_tok",
                  "sliding_window", "rope_parameters"):
        assert FULL[width] == published[width], width
    mc = mellum.model_config(FULL, remat_block=False, seq_len=64)
    shapes = jax.eval_shape(mellum.init_fn(mc), jax.random.PRNGKey(0))
    count = sum(int(jnp.prod(jnp.array(a.shape)))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == FULL["parameters"] == 624_075_008
    for said in ("recalled without a network",):
        assert all(said in FULL["assumed"][k] for k in (
            "layout", "qk_norm", "attention", "rotary", "moe"))
    assert "not reproduced" in FULL["assumed"]["mtp"]


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["published", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    arch = set(cfg) - set(common.CONFIG_META_KEYS)
    tables = (set(mellum.MAPPED) | set(mellum.FIXED) | set(mellum.INERT)
              | set(mellum.ALL_SPARSE))
    assert arch <= tables
    # and the tables name nothing the row does not have
    assert tables == set(FULL["published"])
    assert not set(mellum.MAPPED) & set(mellum.FIXED)
    with pytest.raises(ValueError, match="does not know the key"):
        mellum.model_config(dict(cfg, rope_scaling=None), remat_block=False,
                            seq_len=64)


@pytest.mark.parametrize("over,match", [
    (dict(use_sliding_window=False), "use_sliding_window"),
    (dict(max_window_layers=4), "max_window_layers"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mlp_layer_types=["sparse"] * 3 + ["dense"]), "each routed"),
    (dict(layer_types=["sliding_attention"] * 3 + ["chunked_attention"]),
     "each of"),
    (dict(rope_parameters={"sliding_attention": {
        "rope_type": "default", "rope_theta": 1e4}}), "rope_parameters"),
], ids=["use_sliding_window", "max_window_layers", "bias", "a_dense_layer",
        "another_kind", "a_kind_without_its_table"])
def test_the_adapter_refuses_what_the_program_does_not_compute(over, match):
    with pytest.raises(ValueError, match=match):
        mellum.model_config(dict(TOY, **over), remat_block=False, seq_len=64)


def test_a_rope_type_the_program_has_not_is_refused():
    rope = dict(TOY["rope_parameters"])
    rope["full_attention"] = dict(rope["full_attention"], rope_type="llama3")
    with pytest.raises(ValueError, match="rope_type default or yarn"):
        mellum.model_config(dict(TOY, rope_parameters=rope),
                            remat_block=False, seq_len=64)
    with pytest.raises(ValueError, match="mellum_ref: rope_type"):
        ref.rotary_table(rope["full_attention"], 32, 8)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    """The parent of the PR that brought ``rotary_by_kind`` fails on the
    cell at once, before anything is compiled."""
    from dlrover_tpu.models import llama

    fields = [f for f in dataclasses.fields(llama.LlamaConfig)
              if f.name != "rotary_by_kind"]
    monkeypatch.setattr(dataclasses, "fields", lambda _: fields)
    with pytest.raises(ValueError, match=r"has no \['rotary_by_kind'\]"):
        mellum.model_config(FULL, remat_block=True, seq_len=S16K)


def test_the_initialisation_lets_the_embedding_carry_the_stream():
    """Embedding rows N(0, 1), the projections that write into the stream
    N(0, 0.02 / sqrt(2 x layers)), the rest as ``llama.init_params`` draws
    it: so that a router at initialisation tells tokens apart (the adapter
    says why)."""
    from dlrover_tpu.models import llama

    mc = mellum.model_config(TOY, remat_block=False, seq_len=SEQ)
    key = jax.random.PRNGKey(4)
    params, plain = mellum.init_fn(mc)(key), llama.init_params(key, mc)
    assert float(jnp.std(params["embed"])) == pytest.approx(1.0, rel=0.02)
    assert jnp.allclose(params["embed"], 50.0 * plain["embed"])
    for layer, was in zip(params["layers"], plain["layers"]):
        assert jnp.allclose(layer["wo"], was["wo"] / 8 ** 0.5)
        assert jnp.allclose(layer["moe"]["wo"], was["moe"]["wo"] / 8 ** 0.5)
        for same in ("wq", "wk", "wv", "ln1", "ln2", "q_norm", "k_norm"):
            assert layer[same] is was[same] or jnp.array_equal(
                layer[same], was[same])
        for same in ("router", "wg", "wi"):
            assert jnp.array_equal(layer["moe"][same], was["moe"][same])
    assert jnp.array_equal(params["lm_head"], plain["lm_head"])
    # a router then spreads a sequence's tokens over its experts: the
    # fullest of 16 takes under twice the mean in every layer
    tokens = jnp.asarray(model.sample_tokens(4, range(2), SEQ, 4096))
    _, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    per_expert = aux["moe_tokens_per_expert"].astype(jnp.float32)
    assert float(jnp.max(per_expert.max(1) * 16 / per_expert.sum(1))) < 2.0


# -- the comparison at toy widths ------------------------------------------------


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    job, mc, params = toy
    tokens = jnp.asarray(model.sample_tokens(3, range(2), SEQ, 4096))
    hidden, loss, extra = jax.jit(
        lambda p: mellum.hidden_and_loss(p, tokens, mc))(params)
    want, counters = jax.jit(lambda p: mellum.loss_fn(mc)(
        p, {"tokens": tokens}))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert hidden.shape == (2, SEQ, 64) and hidden.dtype == jnp.float32
    assert sorted(extra["choices"]) == [ref.experts_name(i) for i in range(4)]
    assert extra["choices"]["layers.0.experts"].shape == (2, SEQ, 3)
    assert set(counters) >= {"moe_tokens_per_expert", "moe_held_pairs",
                             "moe_aux"}
    assert mellum.loss_fn(mc).program_facts == llama.program_facts(mc, SEQ)
    assert mellum.loss_fn(mc).program_facts["window_attention_layers"] == 3


def test_the_compared_leaves_are_of_both_kinds(toy):
    _, _, params = toy
    leaves = mellum.grad_leaves(params)
    assert sorted({name.rsplit(".", 1)[0] for name in leaves
                   if name != "embed"}) == [
        "layers.0", "layers.0.moe", "layers.2", "layers.3"]
    back = mellum.with_leaves(params, jax.tree_util.tree_map(
        lambda a: a + 1, leaves))
    assert float(back["layers"][3]["wq"][0, 0]) == pytest.approx(
        float(params["layers"][3]["wq"][0, 0]) + 1)
    assert float(back["layers"][0]["moe"]["wi"][0, 0, 0]) == pytest.approx(
        float(params["layers"][0]["moe"]["wi"][0, 0, 0]) + 1)
    assert back["layers"][1] is params["layers"][1]
    # at the cell's depth: the first and the last layer of each kind
    eight = [dict(params["layers"][0]) for _ in range(8)]
    assert sorted({int(name.split(".")[1]) for name in mellum.grad_leaves(
        dict(params, layers=eight)) if name != "embed"}) == [0, 3, 6, 7]


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == sorted([
        "embed", "k_norm", "q_norm", "router", "wg", "wi", "wk", "wo", "wq",
        "wv"])
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(4)
    assert out["choice_diff_share_tol"] == pytest.approx(
        mellum.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 2)
    assert out["scalar_rel_diff_at"] == "moe_aux"


FAULTS = ("window_on_full", "window_left_full", "plain_table_on_full",
          "attention_factor_dropped", "qk_norm_dropped", "expert_dropped")


@pytest.mark.parametrize("fault", FAULTS + (
    "window dropped", "window halved", "norm_topk_prob flipped",
    "num_experts_per_tok minus one", "balance weight off by a tenth"))
def test_a_planted_fault_reads_not_ok(toy, fault):
    """The issue's faults, each alone — the window put on a full layer, a
    window layer left full, the plain table on a full layer, the attention
    factor left out, the q/k norms left out, one held expert's pairs
    dropped — and the routed block's and the window's own
    (``fault_probe.py``): each must read ``correct: false`` by at least one
    limit."""
    planted = mellum_probe.planted_configs(TOY, ref)
    assert sorted(planted) == sorted(FAULTS + (
        "window dropped", "window halved", "norm_topk_prob flipped",
        "num_experts_per_tok minus one"))
    assert ref.PLANTED == ref.FAULTS + ref.STAND_INS == FAULTS + (
        "bf16_stated_f32",)
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    else:
        out = _check(toy, ref_cfg=dict(
            TOY, router_aux_loss_coef=1.1 * ref.ROUTER_AUX_LOSS_COEF))
    assert not out["ok"], out


def test_the_lower_precision_stand_in_moves_what_the_file_states_in_float32(
        toy):
    """bfloat16 in the router, the norms' statistics and the rotary
    tables: at toy widths (96 positions, 16 experts) the standing
    tolerances hold it, so here it only has to MOVE the distances the
    limits are on; that it reads ``correct: false`` at published width and
    16,384 positions is the chip probe's to show (PERF.md section 4)."""
    true, low = _check(toy), _check(
        toy, ref_cfg=dict(TOY, planted="bf16_stated_f32"))
    worst = lambda out: max(  # noqa: E731
        out["grad_rel_l2_worst_by_leaf_kind"][k] for k in ("wq", "wk"))
    assert worst(low) > 1.5 * worst(true)
    assert low["choice_prob_gap"] > true["choice_prob_gap"]


def test_an_unknown_planted_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault"):
        ref.hidden_and_loss(None, jnp.zeros((1, 9), jnp.int32),
                            dict(TOY, planted="nothing"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(common.BENCH_DIR, "reference",
                           "mellum_ref.py")) as f:
        text = f.read()
    assert "import dlrover_tpu" not in text and "from dlrover_tpu" not in text
    assert "from benchmark" not in text and "import benchmark" not in text
    assert 'default_matmul_precision("highest")' in text


# -- counts ------------------------------------------------------------------------


def test_pair_and_flop_counts():
    assert mellum.pairs_by_kind(FULL, S16K) == {
        "sliding_attention": (6, WINDOW_PAIRS),
        "full_attention": (2, FULL_PAIRS)}
    per_token = mellum.model_flops_per_token(FULL, S16K)
    # the issue's forward count a token: projections 8 x 42.5 M, held
    # experts 8 x 12.4 M, head 56.6 M (the routers 8 x 0.3 M beside them),
    # scores 2 x 134.2 M + 6 x 16.25 M = 366 M: 864 M, attention (its
    # projections and scores) 82 % of it
    matmul = 8 * (2 * 21_233_664 + 2 * 147_456 + 2 * 6_193_152) + (
        2 * 2304 * 12288)
    assert per_token["matmul"] == pytest.approx(3.0 * matmul)
    scores = 16384 * (2 * FULL_PAIRS + 6 * WINDOW_PAIRS) / S16K
    assert per_token["attention"] == pytest.approx(3.0 * scores)
    forward = per_token["total"] / 3.0
    assert forward == pytest.approx(864e6, rel=5e-3)
    assert (8 * 2 * 21_233_664 + scores) / forward == pytest.approx(
        0.82, abs=5e-3)
    # were the six window layers charged as full ones, the whole would read
    # 1.8 times and the flash kernels' 2.9 times too high
    as_full = forward - scores + 8 * 16384 * FULL_PAIRS / S16K
    assert as_full / forward == pytest.approx(1.82, abs=0.01)
    assert 8 * FULL_PAIRS / (2 * FULL_PAIRS + 6 * WINDOW_PAIRS) == (
        pytest.approx(2.93, abs=0.01))


def test_the_least_times_carry_the_readers_layer_count():
    one = mellum.flash_least_seconds(FULL, 1, S16K, PEAKS)
    window = mellum.flash_window_least_seconds(FULL, 1, S16K, PEAKS)
    flop = lambda pairs: 14.0 * 32 * 128 * pairs  # noqa: E731
    assert window["flops"] == pytest.approx(6 * flop(WINDOW_PAIRS))
    assert window["seconds"] == pytest.approx(
        6 * flop(WINDOW_PAIRS) / 197e12)
    assert window["bound"] == "flops"
    # ``flash_roofline``'s reader multiplies by the 8 layers
    assert 8 * one["flops"] == pytest.approx(
        2 * flop(FULL_PAIRS) + 6 * flop(WINDOW_PAIRS))
    assert 8 * one["seconds"] == pytest.approx(
        (2 * flop(FULL_PAIRS) + 6 * flop(WINDOW_PAIRS)) / 197e12)
    # q, k, v, o once forward, eight arrays backward, bf16
    assert 8 * one["bytes"] == pytest.approx(
        8 * 6 * 2.0 * S16K * (32 + 4) * 128)
    # a window layer's bytes bind where the sequence is its window
    short = mellum.flash_window_least_seconds(
        dict(FULL, sliding_window=16), 1, 1024, PEAKS)
    assert short["bound"] == "bytes"
    grouped = mellum.grouped_matmul_least_seconds(FULL, 1, S16K, PEAKS)
    # one held pick a token: 16,384 rows over 8 experts
    assert grouped["flops"] == pytest.approx(18.0 * 16384 * 2304 * 896)
    assert grouped["bytes"] == pytest.approx(
        18.0 * 16384 * (2304 + 896) + 24.0 * 8 * 2304 * 896)


# -- the two readers of the attention kinds' scopes ---------------------------------


def _program(monkeypatch, scopes, subscopes=None, kernel_scopes=None):
    rec = {"kind": "accelerate.program", "scopes": scopes}
    if subscopes is not None:
        rec["subscopes"] = subscopes
    if kernel_scopes is not None:
        rec["kernel_scopes"] = kernel_scopes
    monkeypatch.setattr(window_read.obs_read, "records", lambda spans: [rec])


def _counters():
    return {"traced_steps": 5, "cell": common.load_cell(CELL_NAME),
            "chips": 1, "peaks": PEAKS}


def _read(name, trace):
    return common.load_module("layer_metrics", name).read(
        {"x": 1}, trace, _counters())


def test_the_window_share_and_roofline_on_a_traced_step(monkeypatch):
    attention = lambda phase: [phase, "attention"]  # noqa: E731
    scopes = {"fw.1": attention("forward"), "fw.2": attention("forward"),
              "bq.1": attention("backward"), "bq.2": attention("backward"),
              "bk.1": attention("backward"), "bk.2": attention("backward"),
              "t.1": attention("forward"), "t.2": attention("backward"),
              "p.1": attention("forward"), "g.1": ["forward", "moe_experts"]}
    # a kernel's call is named by the kernel in ``subscopes``: the table of
    # its own says under which kind it ran
    subscopes = {"fw.1": "flash_fwd", "fw.2": "flash_fwd",
                 "t.1": "attn_window", "t.2": "attn_full"}
    kernel_scopes = {"fw.1": "attn_window", "fw.2": "attn_full",
                     "bq.1": "attn_window", "bq.2": "attn_full",
                     "bk.1": "attn_window", "bk.2": "attn_full",
                     "g.1": "moe_experts"}
    _program(monkeypatch, scopes, subscopes, kernel_scopes)
    kernel_s = {"flash_fwd": 0.5, "flash_bwd_dq": 0.6, "flash_bwd_dkv": 0.9,
                "gmm": 0.3}
    trace = {"busy_s": 10.0, "kernel_s": kernel_s,
             "op_self_s": dict(kernel_s, **{
                 "t.1 bf16[8]": 0.25, "t.2": 0.25, "p.1": 3.0}),
             "kernel_call_s": {
                 "flash_fwd": {"fw.1": 0.1, "fw.2": 0.4},
                 "flash_bwd_dq": {"bq.1": 0.2, "bq.2": 0.4},
                 "flash_bwd_dkv": {"bk.1": 0.2, "bk.2": 0.7},
                 "gmm": {"g.1": 0.3}}}
    secs = window_read.seconds({"x": 1}, trace)
    assert secs["flash_window"] == pytest.approx(0.5)
    assert secs["flash_full"] == pytest.approx(1.5)
    assert secs["attn_window"] == pytest.approx(0.75)
    assert secs["attn_full"] == pytest.approx(1.75)
    assert _read("attn.window_share_pct", trace) == pytest.approx(30.0)
    least = mellum.flash_window_least_seconds(
        FULL, 1, S16K, PEAKS)["seconds"]
    assert _read("flash.window_roofline", trace) == pytest.approx(
        100.0 * least * 5 / 0.5)
    # both kinds together are ``flash_roofline``'s
    both = mellum.flash_least_seconds(FULL, 1, S16K, PEAKS)["seconds"]
    assert _read("flash_roofline", trace) == pytest.approx(
        100.0 * both * 8 * 5 / 2.0)


@pytest.mark.parametrize("name", ["attn.window_share_pct",
                                  "flash.window_roofline"])
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals no ``kernel_scopes`` (and cannot run the cell),
    a model with one kind of attention layer none that names a kind: the
    readers return None and do not raise."""
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5, "flash_fwd": 0.2},
             "kernel_s": {"flash_fwd": 0.2},
             "kernel_call_s": {"flash_fwd": {"k.1": 0.2}}}
    _program(monkeypatch, {"f.1": ["forward", "attention"]})
    assert _read(name, trace) is None
    _program(monkeypatch, {"f.1": ["forward", "ssm"],
                           "k.1": ["forward", "mtp"]},
             {"f.1": "ssm_in"}, {"k.1": "attention"})
    assert _read(name, trace) is None
    assert common.load_module("layer_metrics", name).read({}, {}, {}) is None


def test_the_program_says_under_which_scope_a_kernel_is_called():
    from dlrover_tpu.parallel.accelerate import program_summary

    call = ('  %{name} = bf16[8,128]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call", metadata={{op_name='
            '"jit(train_step)/{path}/pallas_call"}}')
    text = "\n".join([
        "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
        "  %p = bf16[8,128]{1,0} parameter(0)",
        call.format(name="flash_fwd.1",
                    path="jvp(attention)/attn_window/jvp(flash_fwd)"),
        call.format(name="flash_bwd_dq.2", path="transpose(jvp(attention))/"
                    "attn_full/flash_bwd_dq"),
        call.format(name="flash_fwd.3", path="jvp(attention)/flash_fwd"),
        call.format(name="rmsnorm_fwd.4", path="jvp(final_norm)/rmsnorm_fwd"),
        "}"])
    summary = program_summary(text)
    assert summary["kernel_scopes"] == {"flash_fwd.1": "attn_window",
                                        "flash_bwd_dq.2": "attn_full"}
    assert summary["kernels"] == {"flash_fwd": 2, "flash_bwd_dq": 1,
                                  "rmsnorm_fwd": 1}
    assert summary["scopes"]["flash_fwd.1"] == ["forward", "attention"]
    # a program whose kernels sit under one scope each journals no such table
    flat = "\n".join(ln for ln in text.splitlines() if "attn_" not in ln)
    assert "kernel_scopes" not in program_summary(flat)


# -- the cell ------------------------------------------------------------------------


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mellum2_12b_a2_5b-l8", "train-16k-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        1, True, {"fsdp": 1, "tp": 1})
    assert cell["why"] == entry["why"]
    traffic, decayed = cell["traffic_data"], common.load_json(
        "traffic", "train-decayed.json")
    assert {k: v for k, v in traffic.items() if k not in (
        "seq_len", "what")} == {k: v for k, v in decayed.items() if k not in (
            "seq_len", "what")}
    assert (traffic["seq_len"], traffic["kind"]) == (S16K, "train_steady")
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert named == {
        "accelerate.compiled_peak_gb", "input.wait_ms_per_step",
        "step.mfu_pct", "flash_roofline", "kernel.pallas_share_pct",
        "device.idle_pct", "device.peak_hbm_gb", "step.lm_head_share_pct",
        "step.optimizer_share_pct", "step.recompute_share_pct",
        "step.attention_share_pct", "step.moe_share_pct",
        "moe.permute_share_pct", "moe.grouped_matmul_roofline",
        "moe.load_max_over_mean", "moe.held_pair_share_pct",
        "moe.buffer_live_pct", "attn.window_share_pct",
        "flash.window_roofline"} | {
            m["name"] for m in spec["per_layer"] if "workloads" not in m}
    for m in spec["per_layer"][-2:]:
        assert (m["workloads"], m["moves"], m["source"], m["unit"]) == (
            [CELL_NAME], "train_tokens_per_s", "device_trace", "%")
    assert [m["name"] for m in spec["per_layer"][-2:]] == [
        "attn.window_share_pct", "flash.window_roofline"]
    assert {m["name"] for m in common.metrics_for(
        spec, "end_to_end", CELL_NAME)} == {"train_tokens_per_s", "setup_s"}
    assert len(spec["workloads"]) >= 11
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
    assert {"moe.held_pair_share_pct", "moe.load_max_over_mean"} <= set(
        found["metrics_found"])
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for name in ("'window_attention_layers': 3", "'attention_layers': 4",
                 "'attn_window_pairs_per_sequence': 1416",
                 "'attn_full_pairs_per_sequence': 4656", "'attn_window'",
                 "'attn_full'", "'rotary'", "'moe_permute'"):
        assert name in program, name
