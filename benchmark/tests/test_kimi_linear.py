"""The Kimi Linear configuration's adapter and reference under
``check_against_reference`` at toy width
(``configs/kimi-linear-rehearsal.json``, CPU): the system — the KDA mixer in
its chunked per-channel form, latent attention with one query matrix, no
position and values narrower than keys, the sigmoid router under a share of
the experts, the shared expert, the dense first layer, the untied head of
``dlrover_tpu/models/llama.py`` — reads ``ok``; the planted faults and the
lower-precision stand-in of the stream do not; the counts of the adapter; the
three new per-layer readers, which give one number whether the trace names
the new kernels or calls them ``pallas_other``; and the cell's rehearsal end
to end."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import kimi_linear as kl
from benchmark.harness import common, kda_read, kimi_linear_probe, model
from benchmark.reference import kimi_linear_ref as ref

CELL_NAME = "kimi_linear_48b_a3b-l5.train-16k-decayed"
FULL = common.load_json("configs", "kimi_linear_48b_a3b-l5.json")
TOY = common.load_json("configs", "kimi-linear-rehearsal.json")
SEQ = 160
CELL = {
    "name": "kimi-linear-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": SEQ, "learning_rate": 1e-5},
}
WIDTH = TOY["published"]["num_experts"]


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
    """At initialisation the low-rank gates put out nearly nothing, the
    output gate sits at 1/2 everywhere and the routers' scores at 1/2: the
    two gates' second halves 10 times larger make the decay and the output
    gate depend on the token, a bias off zero tells its being dropped,
    queries 20 times larger prefer some keys (and feel the shared key part),
    a router 3 times larger prefers some experts — as a trained model's
    do; and the mixers' output projections 8 times larger put a mixer's part
    of the stream where it is at published width (a 64-wide row of N(0, 0.02)
    against a 4,096-wide one), beside the embedding the adapter draws at
    N(0, 1)."""
    def layer_of(layer):
        if "kda" in layer:
            kda = layer["kda"]
            layer = dict(layer, kda=dict(
                kda, f_b=10.0 * kda["f_b"], g_b=10.0 * kda["g_b"],
                w_beta=5.0 * kda["w_beta"], out_proj=8.0 * kda["out_proj"],
                g_bias=0.5 * jnp.cos(jnp.arange(
                    kda["g_bias"].shape[0], dtype=jnp.float32))))
        else:
            layer = dict(layer, wq=20.0 * layer["wq"], wo=8.0 * layer["wo"])
        if "moe" in layer:
            layer = dict(layer, moe=dict(
                layer["moe"], router=3.0 * layer["moe"]["router"]))
        return layer

    return dict(params, layers=[layer_of(x) for x in params["layers"]])


def _check(toy, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 0, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    mc = kl.model_config(FULL, remat_block=True, seq_len=16384)
    # the router is the source's 256 wide with 8 picks; this chip holds 8
    assert (mc.num_experts, mc.top_k, mc.experts_held, mc.experts_held_first,
            mc.n_shared_experts, mc.first_k_dense) == (256, 8, 8, 0, 1, 1)
    assert (mc.n_head, mc.n_kv_head, mc.head_dim, mc.value_head_dim,
            mc.d_model, mc.d_ff, mc.expert_width, mc.vocab_size) == (
                32, 32, 192, 128, 2304, 9216, 1024, 20480)
    assert mc.layer_types == ("kda", "kda", "kda", "attention", "kda")
    assert (mc.kda_layers, mc.attention_layers, mc.kda_heads, mc.kda_d_head,
            mc.kda_d_conv) == (4, 1, 32, 128, 4)
    assert (mc.q_lora_rank, mc.kv_lora_rank, mc.qk_nope_head_dim,
            mc.qk_rope_head_dim, mc.v_head_dim, mc.rope, mc.rms_eps) == (
                0, 512, 128, 64, 128, False, 1e-5)
    assert (mc.router_score, mc.routed_scaling, mc.norm_topk_prob,
            mc.router_bias_rate, mc.balance_per_sequence, mc.capacity_factor,
            mc.mtp_layers, mc.tie_word_embeddings) == (
                "sigmoid", 2.446, True, 1e-3, True, None, 0, False)
    assert [mc.is_moe_layer(i) for i in range(5)] == [False] + [True] * 4
    assert kl.SEQ_AUX_WEIGHT == ref.SEQ_AUX_WEIGHT == 1e-4
    assert FULL["parameters"] == 602_450_816
    for key, bad in (("q_lora_rank", 768), ("mla_use_nope", False),
                     ("model_type", "deepseek_v3"), ("hidden_act", "gelu"),
                     ("moe_router_activation_func", "softmax"),
                     ("num_expert_group", 8), ("moe_layer_freq", 2),
                     ("num_nextn_predict_layers", 1),
                     ("tie_word_embeddings", True),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            kl.model_config(dict(FULL, **{key: bad}), remat_block=False,
                            seq_len=64)
    with pytest.raises(ValueError, match="sliding_window"):
        kl.model_config(dict(FULL, sliding_window=32), remat_block=False,
                        seq_len=64)
    lists = dict(FULL["linear_attn_config"], full_attn_layers=[3, 4])
    with pytest.raises(ValueError, match="do not divide layers 1..5"):
        kl.model_config(dict(FULL, linear_attn_config=lists),
                        remat_block=False, seq_len=64)
    toy_mc = kl.model_config(TOY, remat_block=False, seq_len=64)
    assert (toy_mc.num_experts, toy_mc.experts_held) == (WIDTH, 4)
    assert toy_mc.layer_types == ("kda", "kda", "attention", "kda")
    # the published lists, whole: 20 KDA layers to 7 latent ones
    whole = kl.layer_types(FULL["published"])
    assert (len(whole), whole.count("kda"), whole.count("attention")) == (
        27, 20, 7)
    assert whole[:5] == mc.layer_types and whole[-1] == "attention"
    assert list(kl.layer_types(FULL)) == [
        "attention" if kind == "mla" else kind
        for kind in ref.layer_kinds(FULL)]


def test_the_file_is_the_source_but_for_what_it_lists():
    published = FULL["published"]
    assert FULL["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    arch = set(FULL) - set(common.CONFIG_META_KEYS)
    assert arch == set(published)
    assert {k for k in arch if FULL[k] != published[k]} == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"} == set(FULL["reduced"])
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (27, 256, 163840)
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    # of the nested group only the two lists are cut, with the depth
    lin, lin_p = FULL["linear_attn_config"], published["linear_attn_config"]
    assert {k for k in lin if lin[k] != lin_p[k]} == {
        "kda_layers", "full_attn_layers"}
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    assert lin["kda_layers"] == [i for i in lin_p["kda_layers"] if i <= 5]
    for key in ("kda_gate_rank", "kda_gate_bias", "layer_lists", "mla",
                "router_bias_update_rate", "seq_aux_weight"):
        assert key in FULL["assumed"], key
    assert "thirty-two v5e chips" in FULL["deployment"]


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["full", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    known = (set(kl.MAPPED) | set(kl.FIXED) | set(kl.INERT)
             | set(common.CONFIG_META_KEYS))
    assert set(cfg) <= known
    # and every key of the source is accounted for
    assert set(FULL["published"]) <= (
        set(kl.MAPPED) | set(kl.FIXED) | set(kl.INERT))


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        layer_types: tuple = ()
        kv_lora_rank: int = 0
        experts_held: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="kda_heads"):
        kl.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), SEQ, 4096))
    hidden, loss, extra = kl.hidden_and_loss(params, toks, mc)
    fn = kl.loss_fn(mc)
    own, counters = fn(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert len(fn.rule_leaves) == 3  # the three routed blocks' biases
    assert fn.program_facts == {
        "kda_layers": 3, "attention_layers": 1, "kda_chunks_per_sequence": 2}
    assert hidden.shape == (2, SEQ, 64)
    assert sorted(extra["choices"]) == [
        f"layers.{i}.experts" for i in (1, 2, 3)]
    chosen = extra["choices"]["layers.2.experts"]
    assert chosen.shape == (2, SEQ, 4) and int(chosen.max()) > 3  # of WIDTH
    # (a toy sequence gets none of the rule's own numbers: below)
    assert sorted(extra["scalars"]) == ["moe_seq_aux"]
    assert counters["moe_tokens_per_expert"].shape == (3, WIDTH)
    assert counters["moe_held_pairs"].shape == (3,)
    assert counters["kda_state_rms"].shape == (3,)
    assert float(counters["kda_decay_min"]) >= 0.0
    # every compared leaf whole but the router: the held experts' columns
    leaves = kl.grad_leaves(params)
    assert leaves["layers.1.moe.router"].shape == (64, TOY["num_experts"])
    assert leaves["layers.1.moe.wg"].shape[0] == TOY["num_experts"]
    again = kl.with_leaves(params, leaves)
    router = again["layers"][1]["moe"]["router"]
    assert router is not params["layers"][1]["moe"]["router"]
    np.testing.assert_array_equal(router, params["layers"][1]["moe"]["router"])
    again["layers"][1]["moe"]["router"] = params["layers"][1]["moe"]["router"]
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a is b, again, params))


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), SEQ, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = kl.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 2e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert float(extra["scalars"]["moe_seq_aux"]) == pytest.approx(
        float(extra_r["scalars"]["moe_seq_aux"]), rel=1e-5)
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))
        assert extra_r["probs"][name].shape == (2, SEQ, WIDTH)
    # under ``given`` the reference computes the system's experts
    _, loss_g, _ = ref.hidden_and_loss(
        params, toks, TOY, given=extra["choices"])
    assert float(loss_g) == pytest.approx(float(loss_r), rel=1e-6)


def test_the_reference_is_the_recurrence_and_imports_nothing_of_the_program():
    source = open(os.path.join(
        common.BENCH_DIR, "reference", "kimi_linear_ref.py")).read()
    assert "dlrover_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    # a decay constant over the channels is the scalar rule's recurrence
    from benchmark.reference import qwen3_next_ref as scalar

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 70, 2, 8))
    k = q[:, ::-1] / jnp.linalg.norm(q[:, ::-1], axis=-1, keepdims=True)
    v = jax.random.normal(jax.random.PRNGKey(1), (1, 70, 2, 8))
    g = -jax.random.uniform(jax.random.PRNGKey(2), (1, 70, 2))
    beta = jax.nn.sigmoid(v[..., 0])
    with jax.default_matmul_precision("highest"):
        per_channel = ref._delta_rule(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, None,
            scan_block=16)
        per_head = scalar._delta_rule(q, k, v, g, beta, None, scan_block=16)
    np.testing.assert_allclose(per_channel, per_head, rtol=1e-5, atol=1e-6)


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == sorted([
        "A_log", "conv_k", "conv_q", "conv_v", "dt_bias", "embed", "f_a",
        "f_b", "g_a", "g_b", "g_bias", "kv_a_norm", "norm", "out_proj",
        "router", "w_beta", "w_down", "w_gate", "w_up", "wg", "wi", "wk",
        "wkv_a", "wkv_b", "wo", "wq", "wv"])
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(4)
    assert out["choice_diff_share_tol"] == pytest.approx(
        kl.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER * 2)
    assert out["scalar_rel_diff_at"] == "moe_seq_aux"


@pytest.mark.parametrize("fault", [
    "decay_per_head", "k_pe_rotated", "v_padded", "gate_silu",
    "gate_bias_dropped", "beta_left_out",
    "moe_renormalize flipped", "num_experts_per_token minus one",
    "one expert fewer held", "balance weight off by a tenth",
])
def test_a_planted_fault_reads_not_ok(toy, fault):
    planted = kimi_linear_probe.planted_configs(TOY, ref)
    assert sorted(planted) == sorted(ref.FAULTS + (
        "moe_renormalize flipped", "num_experts_per_token minus one"))
    if fault in planted:
        out = _check(toy, ref_cfg=planted[fault])
    elif fault in ref.STAND_INS:
        out = _check(toy, ref_cfg=dict(TOY, planted=fault))
    elif fault.startswith("one expert"):
        out = _check(toy, ref_cfg=dict(TOY, num_experts=3))
    else:
        out = _check(toy, ref_cfg=dict(
            TOY, seq_aux_weight=1.1 * ref.SEQ_AUX_WEIGHT))
    assert not out["ok"], out


@pytest.mark.parametrize("stand_in", ref.STAND_INS)
def test_a_lower_precision_stand_in_moves_the_reference(toy, stand_in):
    """The stand-ins — of the stated bf16 (fp8 on the stream entering the
    mixers, or the routers) and of what the rule keeps in float32 (the
    decay's running sum, the state) — move the reference at toy width too:
    the hidden states, or the choices.  Whether a limit FINDS them is the
    probe's to say at published width on the chip
    (``harness/kimi_linear_probe.py``; PERF.md section 6): the adapter's two
    choice limits lie between what the system reads there and what the fp8
    stand-ins read, its scalar limit between what the rule alone reads and
    what its own two stand-ins read (the next test)."""
    assert ref.STAND_INS == (
        "fp8_stream", "fp8_router_stream", "bf16_gamma", "bf16_state")
    assert ref.PLANTED == ref.FAULTS + ref.STAND_INS
    true = _check(toy)
    low = _check(toy, ref_cfg=dict(TOY, planted=stand_in))
    assert np.isfinite(low["hidden_rel_l2"])
    if stand_in == "fp8_router_stream":
        assert low["choice_diff_share"] > 2 * true["choice_diff_share"]
        assert low["choice_prob_gap"] > 2 * true["choice_prob_gap"]
    else:
        assert low["hidden_rel_l2"] > true["hidden_rel_l2"]


def test_the_rule_alone_tells_float32_from_bfloat16_at_the_limit():
    """``rule_alone``'s numbers, the program's op (as the adapter calls it:
    ``hidden_and_loss`` puts them among its scalars) against the reference's
    recurrence on the same operands, at the published head size over 1,024
    positions: under the adapter's scalar limit against the true recurrence;
    over it against each of the two stand-ins of what the rule keeps in
    float32 (the decay's running sum, the state); and a toy sequence gets
    no numbers."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.gated_delta import gated_delta_chunked

    heads, seq, seed = 8, ref.RULE_ALONE_MIN_POSITIONS, 44
    cfg = dict(
        FULL, hidden_size=256, intermediate_size=256, vocab_size=4096,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=4,
        linear_attn_config=dict(
            FULL["linear_attn_config"], num_heads=heads, kda_layers=[1],
            full_attn_layers=[]))
    mc = kl.model_config(cfg, remat_block=False, seq_len=seq)
    params = kl.init_fn(mc)(jax.random.PRNGKey(seed))
    toks = jnp.asarray(model.sample_tokens(seed, range(1), seq, 4096))[:, :-1]

    def numbers(rule):
        out = jax.jit(lambda p, t: ref.rule_alone(
            p, t, mc.kda_d_head, mc.rms_eps, rule))(params, toks)
        assert sorted(out) == sorted(
            f"kda_rule_out_rms.{h}" for h in range(heads))
        return np.array([float(out[f"kda_rule_out_rms.{h}"])
                         for h in range(heads)])

    system = numbers(lambda q, k, v, g, beta: gated_delta_chunked(
        q.astype(mc.dtype), k.astype(mc.dtype), v.astype(mc.dtype), g, beta,
        llama.KDA_CHUNK)[0])
    apart = {planted: float(np.max(np.abs(system / numbers(
        lambda *operands: ref._delta_rule(*operands, planted)) - 1)))
        for planted in (None, "bf16_gamma", "bf16_state")}
    assert apart[None] < kl.SCALAR_REL_TOL / 1.5, apart
    assert apart["bf16_gamma"] > 1.25 * kl.SCALAR_REL_TOL, apart
    assert apart["bf16_state"] > 1.25 * kl.SCALAR_REL_TOL, apart
    assert ref.rule_alone(params, toks[:, :seq - 1], mc.kda_d_head,
                          mc.rms_eps, None) == {}


def test_flop_and_byte_counts():
    per_token = kl.model_flops_per_token(FULL, 16384)
    total = per_token["total"]
    assert total == pytest.approx(2.619e9, rel=2e-3)
    kda_proj = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert kl._counts(FULL)["kda_proj"] == kda_proj == 39_460_864
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert kl._counts(FULL)["mla"] == mla == 29_114_368
    # the four KDA layers' projections 36 %, the one latent layer's causal
    # pairs 19 % beside its projections' 7 %, the head 11 %, the rule 4 %
    assert 6.0 * 4 * kda_proj / total == pytest.approx(0.362, abs=5e-3)
    assert per_token["attention"] / total == pytest.approx(0.192, abs=5e-3)
    assert per_token["attention"] == pytest.approx(
        3.0 * 2 * 32 * (192 + 128) * (16384 * 16385 // 2) / 16384)
    assert 6.0 * mla / total == pytest.approx(0.067, abs=5e-3)
    assert 6.0 * 2304 * 20480 / total == pytest.approx(0.108, abs=5e-3)
    rule = 32 * (10.0 * 128 * 128 + 6.0 * 128 * 128)
    assert per_token["kda"] == 3.0 * 4 * (rule + 2 * 4 * 3 * 4096)
    assert per_token["kda"] / total == pytest.approx(0.039, abs=5e-3)
    counts = kl._counts(FULL)
    assert (counts["held_picks"], counts["routed_blocks"],
            counts["kda_layers"], counts["attention_layers"],
            counts["dense_layers"]) == (0.25, 4, 4, 1, 1)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = kl.grouped_matmul_least_seconds(FULL, 1, 16384, peaks)
    rows = 16384 * 0.25  # 512 an expert
    assert least["flops"] == pytest.approx(18.0 * rows * 2304 * 1024 * 4 / 5)
    assert least["bytes"] == pytest.approx(
        (18.0 * rows * 3328 + 24.0 * 8 * 2304 * 1024) * 4 / 5)
    flash = kl.flash_least_seconds(FULL, 1, 16384, peaks)
    pairs = 16384 * 16385 // 2
    # 192-wide scores, 128-wide values, one layer in five
    assert flash["flops"] == pytest.approx(
        2.0 * (4 * 192 + 3 * 128) * 32 * pairs / 5)
    assert flash["bytes"] == pytest.approx(
        2.0 * 16384 * 32 * 6 * (192 + 128) / 5)
    assert flash["bound"] == "flops"
    scan = kl.kda_least_seconds(FULL, 1, 16384, peaks)
    assert scan["flops"] == pytest.approx(3.0 * rule * 16384)
    assert scan["bytes"] == pytest.approx(32 * (34.0 * 128 + 12) * 16384)
    # the float32 decay and its cotangent make the rule's floor its bytes
    assert scan["bound"] == "bytes"
    assert scan["seconds"] == pytest.approx(scan["bytes"] / 819e9)
    assert kl.CHUNK == 128


# -- the new per-layer readers ----------------------------------------------

READERS = ("step.kda_share_pct", "kda.scan_share_pct", "kda.scan_roofline")


def _program(monkeypatch, scopes, subscopes, kernel_scopes=None, **facts):
    rec = dict({"kind": "accelerate.program", "scopes": scopes}, **facts)
    if subscopes is not None:
        rec["subscopes"] = subscopes
    if kernel_scopes is not None:
        rec["kernel_scopes"] = kernel_scopes
    monkeypatch.setattr(kda_read.obs_read, "records", lambda spans: [rec])


def _counters():
    cell = common.load_cell(CELL_NAME)
    return {"traced_steps": 5, "cell": cell, "chips": 1,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


#: one traced step, synthetic: XLA instructions ``f.*`` and the calls of six
#: Mosaic kernels ``k.*`` (the rule's pair, a convolution's pair, the gated
#: norm, the block's input norm) and one of another scope's
SCOPES = {
    "f.1": ["forward", "kda"], "f.2": ["backward", "kda"],
    "f.3": ["recompute", "kda"], "f.4": ["forward", "kda"],
    "f.5": ["backward", "kda"], "f.6": ["forward", "attention"],
    "f.7": ["forward", "moe_experts"],
    "k.1": ["forward", "kda"], "k.2": ["backward", "kda"],
    "k.3": ["forward", "kda"], "k.4": ["backward", "kda"],
    "k.5": ["recompute", "kda"], "k.6": ["forward", "kda"],
    "k.7": ["forward", "attention"]}
SUBSCOPES = {"f.1": "kda_in", "f.2": "kda_scan", "f.3": "kda_scan",
             "f.4": "kda_out", "f.6": "mla_q", "k.1": "kda_chunk_fwd",
             "k.2": "kda_chunk_bwd", "k.3": "conv_silu_fwd",
             "k.4": "conv_silu_bwd", "k.5": "gated_norm_fwd",
             "k.6": "rmsnorm_fwd", "k.7": "flash_fwd"}
KERNEL_SCOPES = {"k.1": "kda_scan", "k.2": "kda_scan", "k.3": "kda_conv",
                 "k.4": "kda_conv", "k.5": "kda_gate"}
XLA_OPS = {"f.1 bf16[8]": 1.0, "f.2 f32[8]": 0.6, "f.3": 0.4, "f.4": 0.5,
           "f.5": 0.2, "f.6": 0.9, "f.7": 2.0, "unknown.9": 0.7}
KERNEL_CALLS = {"kda_chunk_fwd": {"k.1": 0.25}, "kda_chunk_bwd": {"k.2": 0.75},
                "conv_silu_fwd": {"k.3": 0.125},
                "conv_silu_bwd": {"k.4": 0.125},
                "gated_norm_fwd": {"k.5": 0.0625},
                "rmsnorm_fwd": {"k.6": 0.0625}, "flash_fwd": {"k.7": 0.5}}


def _trace(known: tuple) -> dict:
    """The synthetic trace as ``trace_reduce`` would reduce it if its
    ``PALLAS_KERNELS`` held the names in ``known``: every other Mosaic
    kernel's calls are filed under ``pallas_other``."""
    calls: dict = {}
    for name, by_call in KERNEL_CALLS.items():
        calls.setdefault(name if name in known else "pallas_other",
                         {}).update(by_call)
    kernel_s = {label: sum(by.values()) for label, by in calls.items()}
    return {"busy_s": 10.0, "op_self_s": dict(XLA_OPS, **kernel_s),
            "kernel_s": kernel_s, "kernel_call_s": calls}


#: today's ``trace_reduce.PALLAS_KERNELS`` knows two of the seven; a later
#: benchmark PR may name them all (ROADMAP R0k)
LABELLINGS = {"as_pallas_other": ("rmsnorm_fwd", "flash_fwd"),
              "by_name": tuple(KERNEL_CALLS)}


@pytest.mark.parametrize("labelling", sorted(LABELLINGS))
def test_the_readers_on_a_traced_step(monkeypatch, labelling):
    """One number whether the trace names the new kernels or calls them
    ``pallas_other``: every Mosaic call is placed by its CALLING instruction
    (R0k is not repeated)."""
    _program(monkeypatch, SCOPES, SUBSCOPES, KERNEL_SCOPES, kda_layers=4)
    trace = _trace(LABELLINGS[labelling])
    assert ("pallas_other" in trace["kernel_s"]) == (
        labelling == "as_pallas_other")
    secs = kda_read.seconds({"x": 1}, trace)
    # f.5 (the residual add) and k.6 (the input norm) count under kda alone
    assert secs["kda"] == pytest.approx(2.7 + 1.375)
    assert (secs["kda_in"], secs["kda_conv"], secs["kda_scan"],
            secs["kda_gate"], secs["kda_out"], secs["kda_layers"]) == (
                1.0, 0.25, 2.0, 0.0625, 0.5, 4)
    counters = _counters()
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.kda_share_pct") == pytest.approx(40.75)
    assert read("kda.scan_share_pct") == pytest.approx(100 * 2.0 / 4.075)
    least = kl.kda_least_seconds(
        FULL, counters["cell"]["batch_sequences"], 16384,
        counters["peaks"])["seconds"]
    assert read("kda.scan_roofline") == pytest.approx(
        100.0 * least * 4 * 5 / 2.0)
    assert common.load_module("layer_metrics", "kda.scan_roofline").read(
        {"x": 1}, trace, dict(counters, traced_steps=0)) is None


def test_both_labellings_give_one_number(monkeypatch):
    _program(monkeypatch, SCOPES, SUBSCOPES, KERNEL_SCOPES, kda_layers=4)
    a, b = (kda_read.seconds({"x": 1}, _trace(known))
            for known in LABELLINGS.values())
    assert a == b


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals no ``kda`` scope, a dense step no ``subscopes``
    at all: the readers return None and do not raise."""
    reader = common.load_module("layer_metrics", name)
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5},
             "kernel_s": {"flash_fwd": 0.2}}
    _program(monkeypatch, {"f.1": ["forward", "attention"]}, None)
    assert reader.read({"x": 1}, trace, _counters()) is None
    _program(monkeypatch, {"f.1": ["forward", "gdn"]}, {"f.1": "gdn_scan"})
    assert reader.read({"x": 1}, trace, _counters()) is None
    assert reader.read({}, {}, {}) is None


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi_linear_48b_a3b-l5", "train-16k-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        1, True, {"fsdp": 1, "tp": 1})
    assert cell["why"] == entry["why"]
    assert cell["traffic_data"]["seq_len"] == 16384
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert set(READERS) | {
        "step.moe_share_pct", "moe.permute_share_pct",
        "moe.grouped_matmul_roofline", "moe.held_pair_share_pct",
        "moe.buffer_live_pct", "moe.load_max_over_mean", "flash_roofline",
        "step.attention_share_pct", "mla.latent_share_pct", "step.mfu_pct",
        "step.recompute_share_pct", "step.lm_head_share_pct",
        "step.optimizer_share_pct", "kernel.pallas_share_pct",
        "device.idle_pct", "device.peak_hbm_gb", "input.wait_ms_per_step",
        "accelerate.compiled_peak_gb"} <= named
    assert not {"step.gdn_share_pct", "step.mtp_share_pct"} & named
    assert {m["name"] for m in common.metrics_for(
        spec, "end_to_end", CELL_NAME)} == {"train_tokens_per_s", "setup_s"}
    assert len(spec["workloads"]) >= 12
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
    # the counters' readers find theirs on the CPU; the three new ones read
    # a device trace, which a rehearsal has none of
    assert {"moe.held_pair_share_pct", "moe.load_max_over_mean"} <= set(
        found["metrics_found"])
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for name in ("'kda_layers': 3", "'attention_layers': 1",
                 "'kda_chunks_per_sequence': 2", "'kda_in'", "'kda_conv'",
                 "'kda_scan'", "'kda_gate'", "'kda_out'", "'mla_q'",
                 "'mla_kv'", "'mla_out'", "'moe_permute'", "'moe_shared'"):
        assert name in program
