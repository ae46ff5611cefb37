"""The Phi-4-mini-flash configuration (HF ``phi4flash``): the file against the
catalog's row, the adapter's tables, the parameter count leaf by leaf, the
system against the plain reference at toy widths with every planted fault
found, the counts behind the cell's per-layer metrics, the five readers of
the new scopes on a stand-in trace, and the cell rehearsed end to end on the
CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.adapters import phi4flash
from benchmark.harness import common, model, phi4flash_probe, s6_read
from benchmark.reference import phi4flash_ref as ref

CELL_NAME = "phi4_mini_flash-l6.train-16k-decayed"
FULL = common.load_json("configs", "phi4_mini_flash-l6.json")
TOY = common.load_json("configs", "phi4flash-rehearsal.json")
SEQ = 96
CELL = {
    "name": "phi4flash-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": SEQ, "learning_rate": 1e-5},
}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
S16K = 16384
FULL_PAIRS, WINDOW_PAIRS = 134_225_920, 8_257_792
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _with_values(cfg, **values):
    """``cfg`` with entries of ``assumed["values"]`` replaced."""
    assumed = dict(cfg.get("assumed", {}))
    assumed["values"] = dict(assumed["values"], **values)
    return dict(cfg, assumed=assumed)


NEW_METRICS = ("step.s6_share_pct", "s6.scan_share_pct", "s6.scan_roofline",
               "step.gmu_share_pct", "attn.diff_share_pct")


@pytest.fixture(scope="module")
def toy():
    from dlrover_tpu import obs

    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(1))["params"]
    yield job, mc, phi4flash_probe.with_moved_biases(params, 1)
    # the build's spans stay in the process's ring: a later file's test
    # of "nothing recorded" (test_obs_read.py) must find it empty
    obs.reset()


def _check(toy, ref_cfg=None):
    job, mc, params = toy
    return model.check_against_reference(
        job, mc, CELL, params, 1, ref_cfg=ref_cfg)


# -- the file and the adapter's tables -----------------------------------------


def test_the_adapter_says_what_the_configuration_says():
    mc = phi4flash.model_config(FULL, remat_block=True, seq_len=S16K)
    assert (mc.n_layer, mc.d_model, mc.n_head, mc.n_kv_head, mc.head_dim,
            mc.d_ff, mc.vocab_size, mc.rms_eps) == (
                6, 2560, 40, 20, 64, 10240, 25008, 1e-5)
    assert mc.layer_types == ("mamba1", "window_attention", "mamba1",
                              "attention", "gmu", "cross_attention")
    assert (mc.memory_layer, mc.shared_kv_layer) == (2, 3)
    assert (mc.s6_d_inner, mc.s6_d_state, mc.s6_d_conv, mc.s6_dt_rank) == (
        5120, 16, 4, 160)
    assert (mc.sliding_window, mc.window_of("window_attention"),
            mc.window_of("attention"), mc.window_of("cross_attention")) == (
                512, 512, 0, 0)
    assert (mc.rope, mc.norm_form, mc.attn_bias, mc.tie_word_embeddings) == (
        False, "layernorm", True, True)
    # lambda_init by the PUBLISHED index
    assert [round(x, 4) for x in mc.diff_attention] == [
        0.2, 0.3555, 0.7951, 0.7963, 0.7973, 0.798]
    assert (mc.max_seq_len, mc.remat_block) == (S16K, True)
    assert (mc.embedding_multiplier, mc.residual_multiplier,
            mc.logits_scaling, mc.attention_multiplier) == (1, 1, 1, None)


def test_the_file_is_the_source_but_for_what_it_lists():
    """Every number of the catalog's row under its own key; what differs is
    depth and the vocabulary — two keys, no width; the parameter count is
    the leaves' own, leaf group by leaf group."""
    published = FULL["published"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Phi-4-mini-flash-reasoning"]
    assert published == row["config"]
    assert FULL["source"] == row["source_url"]
    assert {k for k in published if FULL[k] != published[k]} == set(
        FULL["reduced"]) == {"num_hidden_layers", "vocab_size"}
    for key, cut in FULL["reduced"].items():
        assert (cut["from"], cut["to"]) == (published[key], FULL[key]), key
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    # what the source does not carry sits under ``assumed``: the file's
    # architecture keys are its source's and no others
    assert set(FULL) - set(common.CONFIG_META_KEYS) == set(published)
    assert FULL["assumed"]["values"] == {
        "published_layers": [0, 1, 16, 17, 18, 19], "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 160}
    assert phi4flash.layer_kinds(FULL) == [
        "mamba1", "window", "mamba1_memory", "full_kv", "gmu", "cross"]
    # the whole model's kinds by the same rule: 9 : 8 : 1 : 7 : 7
    kinds = [phi4flash.published_kind(l) for l in range(32)]
    assert [kinds.count(k) for k in (
        "mamba1", "mamba1_memory", "window", "full_kv", "gmu", "cross")] == [
            8, 1, 8, 1, 7, 7]
    for width in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "sliding_window", "layer_norm_eps",
                  "mb_per_layer"):
        assert FULL[width] == published[width], width
    assert all("recalled without a network" in FULL["assumed"][k] for k in (
        "layout", "kinds", "mamba", "gmu", "differential_attention",
        "window_edge", "cross_attention"))
    assert "eight v5e chips share each pipeline stage" in FULL["deployment"]
    assert "WHAT THE CUT DISTORTS" in FULL["notes"]
    (entry,) = [c for c in common.load_spec()["configs"]
                if c["name"] == "phi4_mini_flash-l6"]
    assert entry["reduced"] == list(FULL["reduced"])
    assert entry["source"] == FULL["source"]


def test_the_parameters_leaf_group_by_leaf_group():
    counts = phi4flash.parameter_counts(FULL)
    assert (counts["mlp"], counts["norms"], counts["mamba1"],
            counts["attention"], counts["gmu"], counts["cross"]) == (
                78_643_200, 10_240, 41_241_600, 19_668_864, 26_214_400,
                13_112_704)
    assert counts["layers"] == 633_068_672
    assert (counts["embed"], counts["final_norm"]) == (64_020_480, 5_120)
    assert counts["total"] == FULL["parameters"] == 697_094_272
    mc = phi4flash.model_config(FULL, remat_block=False, seq_len=64)
    shapes = jax.eval_shape(phi4flash.init_fn(mc), jax.random.PRNGKey(0))
    per_layer = [sum(int(jnp.prod(jnp.array(a.shape)))
                     for a in jax.tree_util.tree_leaves(layer))
                 for layer in shapes["layers"]]
    assert per_layer == [119_895_040, 98_322_304, 119_895_040, 98_322_304,
                         104_867_840, 91_766_144]
    assert sum(int(jnp.prod(jnp.array(a.shape)))
               for a in jax.tree_util.tree_leaves(shapes)) == 697_094_272
    # the whole model, by the published rule of kinds: 3.85 B
    whole = phi4flash.parameter_counts(_with_values(
        dict(FULL, vocab_size=200064), published_layers=list(range(32))))
    assert whole["total"] == 3_852_562_944


@pytest.mark.parametrize("cfg", [FULL, TOY], ids=["published", "toy"])
def test_the_adapter_knows_every_key_of_the_file(cfg):
    arch = set(cfg) - set(common.CONFIG_META_KEYS)
    tables = (set(phi4flash.MAPPED) | set(phi4flash.FIXED)
              | set(phi4flash.INERT))
    assert arch <= tables
    # the tables name the row's keys and no others; what the row does not
    # carry is read from ``assumed["values"]``
    assert tables == set(FULL["published"])
    assert set(cfg["assumed"]["values"]) == set(phi4flash.ASSUMED_VALUES)
    with pytest.raises(ValueError,
                       match=r"does not know the key\(s\) \['rope_theta'\]"):
        phi4flash.model_config(dict(cfg, rope_theta=1e4), remat_block=False,
                               seq_len=64)


@pytest.mark.parametrize("over,match", [
    (dict(model_type="phi3"), "model_type"),
    (dict(mb_per_layer=3), "mb_per_layer"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(mlp_bias=True), "mlp_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(published_layers=[0, 1, 17, 16, 18, 19, 20, 21]),
     "in their published order"),
    (dict(published_layers=[0, 1, 2, 17, 18, 19, 20, 21]),
     "the memory layer and the shared-K/V layer kept"),
    (dict(published_layers=[0, 1, 16, 17]), "8 layers"),
    (dict(mamba_dt_rank=4, dt_rank=4), "from assumed.values"),
], ids=["another_type", "another_stride", "untied", "mlp_bias", "gelu",
        "out_of_order", "readers_without_the_memory_layer", "a_short_list",
        "an_unknown_value"])
def test_the_adapter_refuses_what_the_program_does_not_compute(over, match):
    values = {k: over.pop(k) for k in list(over)
              if k not in TOY or k in TOY["assumed"]["values"]}
    with pytest.raises(ValueError, match=match):
        phi4flash.model_config(_with_values(dict(TOY, **over), **values),
                               remat_block=False, seq_len=64)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    """The parent of the PR that brought the settings: its ``LlamaConfig``
    lacks them, and the adapter says which before anything is compiled."""
    import dataclasses

    from dlrover_tpu.models import llama

    real = dataclasses.fields

    def without(cls):
        return [f for f in real(cls) if f.name not in (
            "s6_d_inner", "memory_layer", "diff_attention")]

    monkeypatch.setattr(dataclasses, "fields", without)
    with pytest.raises(ValueError, match=(
            r"LlamaConfig has no \['diff_attention', 'memory_layer', "
            r"'s6_d_inner'\]")):
        phi4flash.model_config(TOY, remat_block=False, seq_len=64)
    assert set(phi4flash.NEEDS) <= {f.name for f in real(llama.LlamaConfig)}


# -- the system against the reference at toy widths --------------------------------


def test_the_system_is_the_reference(toy):
    out = _check(toy)
    assert out["ok"], out
    assert out["scalar_rel_diff"] <= phi4flash.SCALAR_REL_TOL
    assert out["choice_diff_share"] == 0.0 == out["choice_prob_gap"]


@pytest.mark.parametrize("fault", ref.FAULTS + ref.STAND_INS)
def test_every_planted_fault_is_found(toy, fault):
    out = _check(toy, dict(TOY, planted=fault))
    assert not out["ok"], (fault, out)


def test_the_scan_alone_finds_the_stand_in_the_model_does_not(toy):
    """At seeded weights the state's part of ``y = s C + D x`` is a
    hundredth of it: the bfloat16 state and decay move the reference's own
    hidden states by less than the system's rounding, and the scan read
    ALONE, without the skip and on the same operands, by ten times and more
    what the system is away."""
    true = _check(toy)
    low = _check(toy, dict(TOY, planted=ref.STAND_INS[0]))
    assert low["scalar_rel_diff_at"].startswith("s6_scan_out_rms.")
    assert low["scalar_rel_diff"] > phi4flash.SCALAR_REL_TOL
    assert low["scalar_rel_diff"] > 10 * true["scalar_rel_diff"]
    assert low["hidden_rel_l2"] == pytest.approx(true["hidden_rel_l2"],
                                                 rel=1e-3)


def test_an_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault 'nope'"):
        ref.hidden_and_loss({}, jnp.zeros((1, 9), jnp.int32),
                            dict(TOY, planted="nope"))


# -- the counts behind the per-layer metrics ---------------------------------------


def test_the_pairs_and_the_flops_against_a_hand_count():
    pairs = phi4flash.pairs_by_kind(FULL, S16K)
    assert pairs == {"window": (1, WINDOW_PAIRS), "full_kv": (1, FULL_PAIRS),
                     "cross": (1, FULL_PAIRS)}
    need = phi4flash.model_flops_per_token(FULL, S16K)
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * 5120 + 2560 * 2560
    params = (2 * mamba + 2 * attention + 2 * 2560 * 2560 + 2 * 2560 * 5120
              + 6 * 3 * 2560 * 10240 + 2560 * 25008)
    assert need["matmul"] == 6.0 * params
    # 2 x 64 + 2 x 128 a query head and attended pair, forward
    assert need["attention"] == pytest.approx(
        3.0 * 40 * 384 * (2 * FULL_PAIRS + WINDOW_PAIRS) / S16K)
    assert need["scan"] == 3.0 * 2 * (9 * 5120 * 16 + 2 * 4 * 5120)
    assert need["total"] == need["matmul"] + need["attention"] + need["scan"]
    # the two full-causal layers are 252 of 260 MFLOP of pairs, forward
    assert 40 * 384 * 2 * FULL_PAIRS / S16K == pytest.approx(251.7e6, rel=1e-3)


def test_the_least_times_against_a_hand_count():
    flop = lambda p: 20.0 * 64 * 40 * p  # noqa: E731
    nbytes = 2.0 * S16K * (3 * (2560 + 1280 + 2560) + 3 * 5120)
    window = phi4flash.flash_window_least_seconds(FULL, 1, S16K, PEAKS)
    assert window["flops"] == flop(WINDOW_PAIRS)
    assert window["bytes"] == nbytes
    # 512 keys a query at 64-wide heads: 2.15 ms of FLOPs over 1.38 of bytes
    assert window["bound"] == "flops"
    assert window["seconds"] == pytest.approx(flop(WINDOW_PAIRS) / 197e12)
    one = phi4flash.flash_least_seconds(FULL, 1, S16K, PEAKS)
    # ``flash_roofline``'s reader multiplies by the 6 layers
    assert 6 * one["flops"] == pytest.approx(
        2 * flop(FULL_PAIRS) + flop(WINDOW_PAIRS))
    assert 6 * one["seconds"] == pytest.approx(
        (2 * flop(FULL_PAIRS) + flop(WINDOW_PAIRS)) / 197e12)
    scan = phi4flash.s6_least_seconds(FULL, 1, S16K, PEAKS)
    assert scan["flops"] == 3.0 * 9 * 5120 * 16 * S16K
    # x bf16, dt f32, B and C bf16 in, y f32 out; again with dy f32 in and
    # dx bf16, ddt f32, dB and dC out
    assert scan["bytes"] == S16K * (
        (6 * 5120 + 64) + 4 * 5120 + (6 * 5120 + 64) + 4 * 5120
        + 6 * 5120 + 64)
    assert scan["bound"] == "bytes"
    assert scan["seconds"] == pytest.approx(scan["bytes"] / 819e9)


# -- the five readers of the new scopes --------------------------------------------


def _program(monkeypatch, scopes, subscopes=None, kernel_scopes=None, **facts):
    rec = dict({"kind": "accelerate.program", "scopes": scopes}, **facts)
    if subscopes is not None:
        rec["subscopes"] = subscopes
    if kernel_scopes is not None:
        rec["kernel_scopes"] = kernel_scopes
    monkeypatch.setattr(s6_read.obs_read, "records", lambda spans: [rec])


def _read(name, trace, cell=None):
    return common.load_module("layer_metrics", name).read(
        {"x": 1}, trace, {"traced_steps": 5, "peaks": PEAKS, "chips": 1,
                          "cell": cell})


TRACE = {
    "busy_s": 10.0,
    "kernel_s": {"pallas_other": 2.2, "flash_fwd": 1.0},
    "op_self_s": {"pallas_other": 2.2, "flash_fwd": 1.0, "in.1": 0.5,
                  "dt.1": 0.2, "lay.1": 0.1, "gate.1": 0.3, "out.1": 0.4,
                  "gm.1": 0.6, "d.1 f32[8]": 0.25, "d.2": 0.15, "q.1": 0.6,
                  "m.1": 3.0},
    "kernel_call_s": {
        "pallas_other": {"s6f.1": 0.5, "s6b.1": 1.2, "cv.1": 0.3,
                         "other.1": 0.2},
        "flash_fwd": {"fw.1": 1.0}}}


def test_the_five_shares_on_a_traced_step(monkeypatch):
    s6 = lambda phase: [phase, "s6"]  # noqa: E731
    scopes = {"s6f.1": s6("forward"), "s6b.1": s6("backward"),
              "cv.1": s6("recompute"), "in.1": s6("forward"),
              "dt.1": s6("forward"), "lay.1": s6("backward"),
              "gate.1": s6("forward"), "out.1": s6("backward"),
              "gm.1": ["forward", "gmu"], "fw.1": ["forward", "attention"],
              "d.1": ["forward", "attention"],
              "d.2": ["backward", "attention"],
              "q.1": ["forward", "attention"], "m.1": ["forward", "mlp"],
              "other.1": ["forward", "moe_experts"]}
    subscopes = {"in.1": "s6_in", "dt.1": "s6_dt", "lay.1": "s6_scan",
                 "gate.1": "s6_gate", "out.1": "s6_out", "d.1": "attn_diff",
                 "d.2": "attn_diff", "s6f.1": "s6_scan_fwd",
                 "fw.1": "flash_fwd"}
    kernel_scopes = {"s6f.1": "s6_scan", "s6b.1": "s6_scan",
                     "cv.1": "s6_conv", "fw.1": "attn_full"}
    _program(monkeypatch, scopes, subscopes, kernel_scopes, s6_layers=2)
    secs = s6_read.seconds({"x": 1}, TRACE)
    # the kernels' calls count by the scope above them, whatever the trace
    # calls them
    assert secs["s6_scan"] == pytest.approx(0.5 + 1.2 + 0.1)
    assert secs["s6_conv"] == pytest.approx(0.3)
    assert secs["s6"] == pytest.approx(3.5)
    assert secs["gmu"] == pytest.approx(0.6)
    assert (secs["attn_diff"], secs["attention"]) == (
        pytest.approx(0.4), pytest.approx(2.0))
    assert _read("step.s6_share_pct", TRACE) == pytest.approx(35.0)
    assert _read("s6.scan_share_pct", TRACE) == pytest.approx(
        100.0 * (0.3 + 0.2 + 1.8 + 0.3) / 3.5)
    assert _read("step.gmu_share_pct", TRACE) == pytest.approx(6.0)
    assert _read("attn.diff_share_pct", TRACE) == pytest.approx(20.0)
    cell = {"config_data": FULL, "batch_sequences": 1,
            "traffic_data": {"seq_len": S16K}}
    least = phi4flash.s6_least_seconds(FULL, 1, S16K, PEAKS)["seconds"]
    assert _read("s6.scan_roofline", TRACE, cell) == pytest.approx(
        100.0 * least * 2 * 5 / 1.8)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals none of the scopes (and cannot run the cell), a
    Mamba-2 hybrid none either: the readers return None and do not raise."""
    _program(monkeypatch, {"q.1": ["forward", "attention"]})
    assert _read(name, TRACE) is None
    _program(monkeypatch, {"in.1": ["forward", "ssm"],
                           "q.1": ["forward", "attention"]},
             {"in.1": "ssm_in"}, {"s6f.1": "ssm_scan"}, ssm_layers=9)
    assert _read(name, TRACE) is None
    assert common.load_module("layer_metrics", name).read({}, {}, {}) is None


def test_the_program_says_under_which_scope_the_scans_kernels_are_called():
    from dlrover_tpu.parallel.accelerate import program_summary

    call = ('  %{name} = f32[8,128]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call", metadata={{op_name='
            '"jit(train_step)/{path}/pallas_call"}}')
    mul = ('  %{name} = f32[8,128]{{1,0}} multiply(%p, %p), metadata='
           '{{op_name="jit(train_step)/{path}/mul"}}')
    text = "\n".join([
        "ENTRY %main (p: f32[8,128]) -> f32[8,128] {",
        "  %p = f32[8,128]{1,0} parameter(0)",
        call.format(name="s6_scan_fwd.1",
                    path="jvp(s6)/s6_scan/s6_scan_fwd"),
        call.format(name="s6_scan_bwd.2",
                    path="transpose(jvp(s6))/s6_scan/s6_scan_bwd"),
        call.format(name="flash_fwd.3",
                    path="jvp(attention)/attn_cross/flash_fwd"),
        mul.format(name="multiply.4", path="jvp(attention)/attn_diff"),
        mul.format(name="multiply.5", path="jvp(gmu)"),
        "}"])
    summary = program_summary(text)
    assert summary["kernel_scopes"] == {
        "s6_scan_fwd.1": "s6_scan", "s6_scan_bwd.2": "s6_scan",
        "flash_fwd.3": "attn_cross"}
    assert summary["kernels"] == {"s6_scan_fwd": 1, "s6_scan_bwd": 1,
                                  "flash_fwd": 1}
    assert summary["subscopes"]["multiply.4"] == "attn_diff"
    assert summary["scopes"]["multiply.5"] == ["forward", "gmu"]
    assert summary["scopes"]["s6_scan_bwd.2"] == ["backward", "s6"]


# -- the cell ------------------------------------------------------------------------


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    assert len(spec["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "phi4_mini_flash-l6", "train-16k-decayed", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        1, True, {"fsdp": 1, "tp": 1})
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    assert (cell["traffic_data"]["seq_len"],
            cell["traffic_data"]["kind"]) == (S16K, "train_steady")
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert named == {
        "accelerate.compiled_peak_gb", "input.wait_ms_per_step",
        "step.mfu_pct", "flash_roofline", "kernel.pallas_share_pct",
        "device.idle_pct", "device.peak_hbm_gb", "step.lm_head_share_pct",
        "step.optimizer_share_pct", "step.recompute_share_pct",
        "step.attention_share_pct", "attn.window_share_pct",
        "flash.window_roofline", *NEW_METRICS} | {
            m["name"] for m in spec["per_layer"] if "workloads" not in m}
    for m in spec["per_layer"]:
        if m["name"] not in NEW_METRICS:
            continue
        assert m["workloads"] == [CELL_NAME]
        assert (m["moves"], m["source"], m["unit"]) == (
            "train_tokens_per_s", "device_trace", "%")
        reader = common.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.SOURCE) == (m["layer"], m["source"])
    assert [m["layer"] for m in spec["per_layer"]
            if m["name"] == "s6.scan_roofline"] == ["kernels"]
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
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for said in ("'s6_layers': 2", "'gmu_layers': 2",
                 "'cross_attention_layers': 2", "'attention_layers': 4",
                 "'window_attention_layers': 1", "'attn_diff'", "'s6_scan'",
                 "memory_bytes_per_sequence", "shared_kv_bytes_per_sequence"):
        assert said in program, said
    (metrics,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("STEP_METRICS ")]
    assert "s6_state_rms" in metrics and "s6_decay_min" in metrics
