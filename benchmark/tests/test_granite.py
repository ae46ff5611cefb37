"""The Granite-4.0-H configuration's adapter and reference under
``check_against_reference`` at toy width (``configs/granite-rehearsal.json``,
CPU): the system — the chunked state-space-duality scan, the causal
convolution, the gated norm, attention without rotary position at a stated
scale, the four multipliers and the tied head of
``dlrover_tpu/models/llama.py`` — reads ``ok`` against the SEQUENTIAL
recurrence of ``reference/granite_hybrid_ref.py``; every planted fault and
the fp8 stand-in do not; the counts of the adapter; the three new per-layer
readers; and the new cell's rehearsal end to end."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.adapters import granite_hybrid as granite
from benchmark.harness import common, granite_probe, model, ssm_read
from benchmark.reference import granite_hybrid_ref as ref

TOY = common.load_json("configs", "granite-rehearsal.json")
FULL = common.load_json("configs", "granite4_h_micro-l10.json")
CELL_NAME = "granite4_h_micro-l10.train-steady"
CELL = {
    "name": "granite-toy.test", "config_data": TOY, "chips": 1,
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
    """N(0, 0.02) query and key projections give scores near 0 and a flat
    softmax, under which neither a position nor a scale shows; 30 times
    larger they prefer some keys, as a trained layer's do."""
    return dict(params, layers=[
        layer if "ssm" in layer else dict(
            layer, wq=30.0 * layer["wq"], wk=30.0 * layer["wk"])
        for layer in params["layers"]])


def _check(toy, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 0, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    mc = granite.model_config(FULL, remat_block=True, seq_len=8192)
    assert mc.layer_types == ("mamba",) * 9 + ("attention",)
    assert (mc.ssm_layers, mc.attention_layers, mc.block_applications) == (
        9, 1, 1)
    assert (mc.n_head, mc.n_kv_head, mc.head_dim, mc.d_model, mc.d_ff,
            mc.vocab_size) == (32, 8, 64, 2048, 8192, 12544)
    assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_state,
            mc.mamba_n_groups, mc.mamba_d_conv, mc.mamba_expand,
            mc.mamba_chunk_size, mc.mamba_conv_bias, mc.mamba_proj_bias) == (
                64, 64, 128, 1, 4, 2, 256, True, False)
    assert (mc.mamba_d_inner, mc.mamba_conv_dim) == (4096, 4352)
    assert (mc.rope, mc.attention_multiplier, mc.embedding_multiplier,
            mc.residual_multiplier, mc.logits_scaling,
            mc.tie_word_embeddings) == (False, 1 / 64, 12.0, 0.22, 8.0, True)
    # the cut: layers 6-15 of the published list, one whole period
    assert FULL["layer_types"] == FULL["published"]["layer_types"][6:16]
    assert FULL["published"]["layer_types"].count("attention") == 4
    assert FULL["vocab_size"] * 8 == FULL["published"]["vocab_size"]
    assert FULL["parameters"] == 772_160_448
    for key, bad in (("position_embedding_type", "rope"),
                     ("tie_word_embeddings", False),
                     ("num_local_experts", 8), ("mamba_proj_bias", True),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            granite.model_config(dict(FULL, **{key: bad}),
                                 remat_block=False, seq_len=64)
    with pytest.raises(ValueError, match="sliding_window"):
        granite.model_config(dict(FULL, sliding_window=32),
                             remat_block=False, seq_len=64)
    toy_mc = granite.model_config(TOY, remat_block=False, seq_len=64)
    assert (toy_mc.ssm_layers, toy_mc.attention_layers) == (3, 1)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="layer_types"):
        granite.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss = granite.hidden_and_loss(params, toks, mc)
    fn = granite.loss_fn(mc)
    own, counters = fn(params, {"tokens": toks})
    assert float(loss) == pytest.approx(float(own), rel=1e-6)
    assert hidden.shape == (2, 64, 64) and hidden.dtype == jnp.float32
    assert sorted(counters) == ["ssm_decay_min", "ssm_state_rms"]
    assert counters["ssm_state_rms"].shape == (3,)
    assert fn.program_facts == llama.program_facts(mc, 128) == {
        "ssm_layers": 3, "attention_layers": 1,
        "ssm_chunks_per_sequence": 8}
    # the leaves whose gradients are compared: the first and the last
    # state-space layer's mixer, the attention layer's q, k, v, the table
    leaves = granite.grad_leaves(params)
    assert sorted(leaves) == sorted(
        ["embed"] + [f"layers.{i}.{n}" for i in (0, 2)
                     for n in granite._SSM_LEAVES]
        + [f"layers.3.{n}" for n in ("wq", "wk", "wv")])
    doubled = granite.with_leaves(
        params, {k: 2 * v for k, v in leaves.items()})
    assert float(doubled["layers"][2]["ssm"]["A_log"][1]) == pytest.approx(
        2 * float(params["layers"][2]["ssm"]["A_log"][1]))
    assert doubled["layers"][1] is params["layers"][1]
    assert "lm_head" not in doubled


def test_system_in_float32_equals_the_sequential_reference(toy):
    """The chunked dual form against the recurrence one position at a time,
    both float32: hidden states, loss and every compared gradient leaf
    differ by rounding alone."""
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)

    def of(fn, cfg):
        def loss_of(leaves):
            hidden, loss = fn(granite.with_leaves(params, leaves), toks, cfg)
            return loss, hidden
        return jax.value_and_grad(loss_of, has_aux=True)(
            granite.grad_leaves(params))

    (loss, hidden), grads = of(granite.hidden_and_loss, f32)
    (loss_r, hidden_r), grads_r = of(ref.hidden_and_loss, TOY)
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert rel(hidden, hidden_r) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    for name, g in grads.items():
        # two orders of summation through four blocks, in float32
        assert rel(g, grads_r[name]) < 1e-3, name


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "A_log", "D", "conv_w", "dt_bias", "embed", "in_proj", "norm",
        "out_proj", "wk", "wq", "wv"]
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(4)
    assert out["grad_rel_tol"] == model.grad_rel_tol(4)


@pytest.mark.parametrize(
    "fault", sorted(granite_probe.planted_configs(TOY, ref)) + [
        "fp8_stream", "attention_multiplier 1/sqrt(d)", "logits_scaling 1",
        "embedding_multiplier 1"])
def test_a_planted_fault_reads_not_ok(toy, fault):
    planted = granite_probe.planted_configs(TOY, ref)
    assert sorted(planted) == [
        "conv_shifted", "gate_after_norm", "no_D", "residual_multiplier 1",
        "rope_on"]
    if fault in planted:
        ref_cfg = planted[fault]
    elif fault == "fp8_stream":
        ref_cfg = dict(TOY, planted=fault)
    else:
        key, value = fault.split(" ")
        ref_cfg = dict(TOY, **{key: 0.25 if key.startswith("att") else 1.0})
    out = _check(toy, ref_cfg=ref_cfg)
    assert not out["ok"], out


def test_the_bf16_scan_stand_in_moves_what_the_scan_computes(toy):
    """The scan's own precision fault: cumulative sums and decays in
    bfloat16.  At toy width and 16-position chunks the sums are short and
    the standing limits need not catch it (the chip's reading at published
    width and 256-position chunks is in PERF.md); it must move the distance
    of the scan's own leaves by more than float32 rounding does."""
    true = _check(toy)
    low = _check(toy, ref_cfg=dict(TOY, planted="bf16_scan"))
    by_kind, low_kind = (true["grad_rel_l2_worst_by_leaf_kind"],
                         low["grad_rel_l2_worst_by_leaf_kind"])
    assert low_kind["dt_bias"] > 2 * by_kind["dt_bias"], (by_kind, low_kind)
    assert low_kind["A_log"] > by_kind["A_log"], (by_kind, low_kind)
    with pytest.raises(ValueError, match="unknown planted"):
        ref.hidden_and_loss(None, None, dict(TOY, planted="nothing"))


def test_flop_and_byte_counts():
    per_token = granite.model_flops_per_token(FULL, 8192)
    forward = per_token["total"] / 3.0
    mlps = 2.0 * 10 * 3 * 2048 * 8192
    proj = 2.0 * 9 * (2048 * 8512 + 4096 * 2048)
    flash = 2.0 * 2 * 32 * 64 * (8193 / 2)
    head = 2.0 * 2048 * 12544
    scan = 9 * (4.0 * 64 * 64 * 128 + 2 * 4 * 4352)
    attn_proj = 2.0 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert forward == pytest.approx(
        mlps + proj + flash + head + scan + attn_proj, rel=1e-9)
    # the issue's split of the forward work a token (1.6 GFLOP): the ten
    # MLPs 62 %, the Mamba projections 29 %, the one flash layer 2.1 %,
    # the head 3.2 %; the REQUIRED scan work is 1.2 % (the chunked form's
    # own matmuls, which the issue's 2.4 % counts, are not required)
    assert forward == pytest.approx(1.597e9, rel=1e-3)
    assert mlps / forward == pytest.approx(0.63, abs=0.01)
    assert proj / forward == pytest.approx(0.29, abs=0.01)
    assert flash / forward == pytest.approx(0.021, abs=1e-3)
    assert head / forward == pytest.approx(0.032, abs=1e-3)
    assert scan / forward == pytest.approx(0.012, abs=1e-3)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # one layer in ten runs the flash kernels: the reader multiplies by 10
    one = granite.flops.flash_least_seconds(FULL, 2, 8192, peaks)
    scaled = granite.flash_least_seconds(FULL, 2, 8192, peaks)
    assert scaled["seconds"] * 10 == pytest.approx(one["seconds"])
    assert one["flops"] == pytest.approx(
        7 * 2.0 * 32 * 64 * (8192 * 8193 // 2) * 2)
    least = granite.ssd_least_seconds(FULL, 2, 8192, peaks)
    tokens = 2 * 8192
    assert least["flops"] == 3.0 * 4 * 64 * 64 * 128 * tokens
    read = 2 * 4096 + 2 * 2 * 128 + 4 * 64
    assert least["bytes"] == (3 * read + 2 * 2 * 4096) * tokens
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(least["bytes"] / 819e9)
    assert granite.ssd_least_seconds(FULL, 2, 8192, peaks, shards=2)[
        "seconds"] == pytest.approx(least["seconds"] / 2)


# -- the new per-layer readers ----------------------------------------------


def _program(monkeypatch, scopes, subscopes, **facts):
    rec = dict({"kind": "accelerate.program", "scopes": scopes}, **facts)
    if subscopes is not None:
        rec["subscopes"] = subscopes
    monkeypatch.setattr(ssm_read.obs_read, "records", lambda spans: [rec])


def test_the_readers_on_a_traced_step(monkeypatch):
    scopes = {
        "f.1": ["forward", "ssm"], "f.2": ["backward", "ssm"],
        "f.3": ["recompute", "ssm"], "f.4": ["forward", "ssm"],
        "f.5": ["backward", "ssm"], "f.6": ["forward", "ssm"],
        "f.7": ["forward", "attention"], "f.8": ["forward", "mlp"],
        "w.1": ["recompute", "ssm"]}
    subscopes = {"f.1": "ssm_in", "f.2": "ssm_scan", "f.3": "ssm_scan",
                 "f.4": "ssm_conv", "f.5": "ssm_gate", "f.6": "ssm_out",
                 "f.7": "mla_q", "w.1": "ssm_scan"}
    _program(monkeypatch, scopes, subscopes, ssm_layers=9)
    trace = {"busy_s": 10.0,
             "op_self_s": {"f.1 bf16[8]": 1.0, "f.2 f32[8]": 0.6,
                           "f.3": 0.4, "f.4": 0.3, "f.5": 0.2, "f.6": 0.5,
                           "f.7": 0.9, "f.8": 2.0, "w.1": 0.25,
                           "rmsnorm_fwd": 0.3, "unknown.9": 0.7},
             "kernel_s": {"rmsnorm_fwd": 0.3}}
    secs = ssm_read.seconds({"x": 1}, trace)
    assert secs["ssm"] == pytest.approx(3.25)
    assert secs["ssm_scan"] == pytest.approx(1.25)
    assert (secs["ssm_in"], secs["ssm_out"], secs["ssm_layers"]) == (
        1.0, 0.5, 9)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"traced_steps": 5, "peaks": peaks, "chips": 1,
                "cell": common.load_cell(CELL_NAME)}
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.ssm_share_pct") == pytest.approx(32.5)
    assert read("ssm.scan_share_pct") == pytest.approx(
        100 * (0.3 + 1.25 + 0.2) / 3.25)
    least = granite.ssd_least_seconds(FULL, 2, 8192, peaks)["seconds"]
    assert read("ssm.scan_roofline") == pytest.approx(
        100 * least * 9 * 5 / 1.25)


def test_the_scan_kernels_seconds_reach_the_scan(monkeypatch):
    """The same step with what a chunk puts out as Mosaic kernels: their
    calls under ``ssm`` count there and under ``ssm_scan``, the recomputed
    forward call as recomputation; a norm's call stays out."""
    scopes = {
        "f.1": ["forward", "ssm"], "f.2": ["backward", "ssm"],
        "f.8": ["forward", "mlp"],
        "ssd_chunk_fwd.1": ["forward", "ssm"],
        "ssd_chunk_fwd.2": ["recompute", "ssm"],
        "ssd_chunk_bwd.1": ["backward", "ssm"],
        "rmsnorm_fwd.1": ["forward", "ssm"]}
    subscopes = {"f.1": "ssm_in", "f.2": "ssm_scan",
                 "ssd_chunk_fwd.1": "ssd_chunk_fwd",
                 "ssd_chunk_fwd.2": "ssd_chunk_fwd",
                 "ssd_chunk_bwd.1": "ssd_chunk_bwd",
                 "rmsnorm_fwd.1": "rmsnorm_fwd"}
    _program(monkeypatch, scopes, subscopes, ssm_layers=9)
    calls = {"ssd_chunk_fwd": {"ssd_chunk_fwd.1": 0.2,
                               "ssd_chunk_fwd.2": 0.1},
             "ssd_chunk_bwd": {"ssd_chunk_bwd.1": 0.45},
             "rmsnorm_fwd": {"rmsnorm_fwd.1": 0.3}}
    kernel_s = {k: sum(v.values()) for k, v in calls.items()}
    trace = {"busy_s": 10.0, "kernel_s": kernel_s, "kernel_call_s": calls,
             "op_self_s": dict(kernel_s, **{
                 "f.1 bf16[8]": 1.0, "f.2 f32[8]": 0.25, "f.8": 2.0})}
    secs = ssm_read.seconds({"x": 1}, trace)
    assert secs["ssm"] == pytest.approx(1.0 + 0.25 + 0.75)
    assert secs["ssm_scan"] == pytest.approx(0.25 + 0.75)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"traced_steps": 5, "peaks": peaks, "chips": 1,
                "cell": common.load_cell(CELL_NAME)}
    read = lambda name: common.load_module(  # noqa: E731
        "layer_metrics", name).read({"x": 1}, trace, counters)
    assert read("step.ssm_share_pct") == pytest.approx(20.0)
    assert read("ssm.scan_share_pct") == pytest.approx(50.0)
    least = granite.ssd_least_seconds(FULL, 2, 8192, peaks)["seconds"]
    assert read("ssm.scan_roofline") == pytest.approx(
        100 * least * 9 * 5 / 1.0)
    assert read("step.recompute_share_pct") == pytest.approx(1.0)


@pytest.mark.parametrize("name", [
    "step.ssm_share_pct", "ssm.scan_share_pct", "ssm.scan_roofline"])
def test_a_program_without_the_scopes_reads_nothing(monkeypatch, name):
    """The parent journals no ``ssm`` scope, a dense step no ``subscopes``
    at all: the readers return None and do not raise."""
    reader = common.load_module("layer_metrics", name)
    trace = {"busy_s": 1.0, "op_self_s": {"f.1": 0.5},
             "kernel_s": {"flash_fwd": 0.2}}
    counters = {"traced_steps": 5, "chips": 1,
                "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                "cell": common.load_cell("mistral7b-l2.train-steady")}
    _program(monkeypatch, {"f.1": ["forward", "attention"]}, None)
    assert reader.read({"x": 1}, trace, counters) is None
    _program(monkeypatch, {"f.1": ["forward", "attention"]},
             {"f.1": "mla_q"})
    assert reader.read({"x": 1}, trace, counters) is None
    assert reader.read({}, {}, {}) is None


def test_the_new_cell_rehearses_end_to_end():
    """``run.py --rehearse`` of the cell as named: the toy sibling through
    the steady runner on the CPU, the comparison included."""
    spec = common.load_spec()
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "granite4_h_micro-l10", "train-steady", 1)
    cell = common.load_cell(CELL_NAME)
    assert (cell["batch_sequences"], cell["remat_block"], cell["mesh"]) == (
        2, True, {"fsdp": 1, "tp": 1})
    named = {m["name"] for m in
             common.metrics_for(spec, "per_layer", CELL_NAME)}
    assert {"step.ssm_share_pct", "ssm.scan_share_pct", "ssm.scan_roofline",
            "flash_roofline", "step.mfu_pct",
            "step.recompute_share_pct"} <= named
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
    (program,) = [ln for ln in res.stdout.splitlines()
                  if ln.startswith("PROGRAM ")]
    for name in ("'ssm_layers': 3", "'attention_layers': 1",
                 "'ssm_chunks_per_sequence': 4", "'ssm_scan'", "'ssm_gate'"):
        assert name in program
