"""The Ouro configuration's adapter and reference under
``check_against_reference`` at toy width (``configs/ouro-rehearsal.json``,
CPU): the system — the program's looped stack, sandwich norm, per-pass
final norm, exit gate and expectation loss through the one reduced head
call — reads ``ok``; every pass's stream, the exit distribution, the loss
and the gradient of every leaf (the gate's among them) equal the
reference's in float32; each planted fault of
``benchmark/harness/ouro_probe.py`` does not read ``ok``; the adapter's
FLOP counts by hand (``test_flops.py``'s cases for this adapter live here:
a PR may add files to the benchmark, not edit them); and the two per-layer
readers this configuration brought."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import ouro
from benchmark.harness import common, flops, model, ouro_probe
from benchmark.reference import ouro_ref

TOY = common.load_json("configs", "ouro-rehearsal.json")
FULL = common.load_json("configs", "ouro2_6b-l8.json")
CELL = {
    "name": "ouro-toy.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": True,
    "traffic_data": {"seq_len": 128, "learning_rate": 3e-4},
}
PASSES = TOY["total_ut_steps"]


@pytest.fixture(scope="module")
def toy():
    job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    return job, mc, params


def _check(toy, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 0, ref_cfg=ref_cfg)


def test_the_adapter_says_what_the_configuration_says():
    mc = ouro.model_config(FULL, remat_block=True, seq_len=4096)
    assert (mc.n_layer, mc.loop_passes, mc.block_applications) == (8, 4, 32)
    assert (mc.branch_norm, mc.exit_gate_beta) == (True, 0.1)
    assert ouro.EXIT_ENTROPY_BETA == ouro_ref.EXIT_ENTROPY_BETA
    assert (mc.d_model, mc.n_head, mc.n_kv_head, mc.head_dim, mc.d_ff,
            mc.vocab_size) == (2048, 16, 16, 128, 5632, 49152)
    assert (mc.rope_theta, mc.rms_eps, mc.sliding_window) == (1e6, 1e-6, 0)
    assert FULL["parameters"] == 8 * (4 * 2048**2 + 3 * 2048 * 5632
                                      + 4 * 2048) + 2 * 49152 * 2048 + (
        2048 + 2048 + 1)
    for bad, match in ((dict(sliding_window=4096), "sliding_window"),
                       (dict(use_sliding_window=True), "use_sliding_window"),
                       (dict(total_ut_steps=1), "total_ut_steps=1"),
                       (dict(layer_types=["sliding_attention"] * 2),
                        "layer_types"),
                       (dict(num_experts=8), "num_experts")):
        with pytest.raises(ValueError, match=match):
            ouro.model_config(dict(TOY, **bad), remat_block=False, seq_len=64)


def test_a_program_without_the_settings_is_refused_by_name(monkeypatch):
    from dlrover_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Old)
    with pytest.raises(ValueError, match="loop_passes"):
        ouro.model_config(TOY, remat_block=False, seq_len=64)


def test_the_adapter_runs_the_programs_own_loss(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss = ouro.hidden_and_loss(params, toks, mc)
    own, counters = ouro.loss_fn(mc)(params, {"tokens": toks})
    assert float(loss) == float(own)
    assert hidden.shape == (PASSES * 2, 64, 64)
    assert sorted(counters) == [
        "loop_ce", "loop_exit_entropy", "loop_exit_prob"]
    assert counters["loop_ce"].shape == (PASSES,)


def test_system_in_float32_equals_the_reference_in_every_pass_and_leaf(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)

    from dlrover_tpu.models import llama

    def both(fn, cfg):
        def loss_of(p):
            hidden, loss, *rest = fn(p, toks, cfg)
            return loss, (hidden, rest)
        return jax.value_and_grad(loss_of, has_aux=True)(params)

    (loss, (hidden, _)), grads = both(ouro.hidden_and_loss, f32)
    (loss_r, (hidden_r, (counters_r,))), grads_r = both(
        ouro_ref.hidden_and_loss, TOY)
    # the program's own counters, as accelerate()'s step hands them out
    _, counters = llama.loss_fn(params, {"tokens": toks}, f32, metrics=True)
    z, z_r = (h.reshape(PASSES, -1) for h in (hidden, hidden_r))
    assert float(jnp.max(jnp.linalg.norm(z - z_r, axis=1)
                         / jnp.linalg.norm(z_r, axis=1))) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert sorted(counters) == sorted(counters_r)
    for key, value in counters_r.items():
        np.testing.assert_allclose(counters[key], value, rtol=1e-4,
                                   err_msg=key)
        # the exit probabilities are a distribution in both
    for c in (counters, counters_r):
        assert float(jnp.sum(c["loop_exit_prob"])) == pytest.approx(
            1.0, abs=1e-5)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    flat_r, tree_r = jax.tree_util.tree_flatten_with_path(grads_r)
    assert tree == tree_r
    for (path, g), (_, g_r) in zip(flat, flat_r):
        scale = float(jnp.linalg.norm(g_r))
        assert scale > 0, path  # every leaf is reached, the gate's too
        assert float(jnp.linalg.norm(g - g_r)) / scale < 2e-3, path


def test_the_true_reference_reads_ok(toy):
    out = _check(toy)
    assert out["ok"], out
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "embed", "exit_gate", "w_down", "wk", "wq", "wv"]
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(2)
    # no discrete choice: the dense contract, two programs
    assert "scalar_rel_diff" not in out and "choice_diff_share" not in out


def test_per_pass_distances_of_the_probe(toy):
    job, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(3, range(1), 128, 4096))
    z = jax.jit(ouro_probe.per_pass_distances(CELL, mc))(params, toks)
    assert z["z_rel_l2"].shape == (PASSES,)
    assert 0.0 < float(jnp.max(z["z_rel_l2"])) < model.hidden_rel_tol(2)


@pytest.mark.parametrize("fault", ouro_ref.PLANTED)
def test_a_planted_fault_reads_not_ok(toy, fault):
    out = _check(toy, ref_cfg=dict(TOY, planted=fault))
    assert not out["ok"], out
    if fault == "no_remainder":  # the streams are right, the loss is not
        assert out["hidden_rel_l2"] < out["hidden_rel_tol"]
        assert out["loss_rel_diff"] > model.LOSS_REL_TOL


def test_an_unknown_planted_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault"):
        ouro_ref.hidden_and_loss({}, jnp.zeros((1, 3), jnp.int32),
                                 dict(TOY, planted="typo"))


def test_exit_distribution_of_the_reference_by_hand():
    lam = jnp.asarray([[0.5], [0.25], [0.9]])
    np.testing.assert_allclose(
        ouro_ref.exit_distribution(lam)[:, 0], [0.5, 0.125, 0.375])
    np.testing.assert_allclose(
        ouro_ref.exit_distribution(lam, remainder=False)[:, 0],
        [0.5, 0.125, 0.3375])


def test_flop_counts_by_hand():
    per_token = ouro.model_flops_per_token(FULL, 4096)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert flops.matmul_params(FULL)["layer"] == layer == 51_380_224
    # 32 block applications, 4 heads, 4 gates
    assert per_token["matmul"] == 6.0 * (
        32 * layer + 4 * 2048 * 49152 + 4 * 2048)
    # per application 12 * heads * head_dim * pairs / seq
    assert per_token["attention"] == 32 * 12 * 16 * 128 * (
        4096 * 4097 // 2) / 4096
    assert per_token["total"] == pytest.approx(13.89e9, rel=1e-3)
    heads = 6.0 * 4 * 2048 * 49152
    assert heads / per_token["total"] == pytest.approx(0.174, abs=1e-3)
    # one pass fewer costs a quarter less
    three = ouro.model_flops_per_token(dict(FULL, total_ut_steps=3), 4096)
    assert three["total"] == pytest.approx(0.75 * per_token["total"])


def test_flash_least_seconds_covers_all_block_applications():
    """``flash_roofline``'s reader multiplies by ``num_hidden_layers``:
    the adapter's count is one layer's FOUR applications."""
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    one = flops.flash_least_seconds(FULL, 2, 4096, peaks)
    got = ouro.flash_least_seconds(FULL, 2, 4096, peaks)
    assert got["seconds"] == pytest.approx(4 * one["seconds"])
    assert got["flops"] == 4 * 7 * 2 * 16 * 128 * (4096 * 4097 // 2) * 2
    assert got["bound"] == one["bound"] == "flops"
    assert got["seconds"] * FULL["num_hidden_layers"] == pytest.approx(
        32 * one["seconds"])


# -- the two per-layer readers this configuration brought --------------------


def _fake_scope_shares(monkeypatch, by, program=None):
    from benchmark.harness import obs_read

    monkeypatch.setattr(obs_read, "records", lambda spans: [
        dict(program or {}, kind="accelerate.program", _proc="")])
    monkeypatch.setattr(
        obs_read, "scope_shares",
        lambda recs, trace: {"by": by, "unphased_pct": 0.0,
                             "unplaced_kernel_s": {}} if by else None)


def _read(name, kernel_s=None):
    return common.load_module("layer_metrics", name).read(
        {"x": 1}, {"busy_s": 2.0, "kernel_s": kernel_s or {}}, {})


LOOPED_BY = {
    # flash_fwd's 64 calls are placed one by one: 4.0 forward, 4.0 recomputed
    ("forward", "attention"): 10.0 + 4.0,
    ("recompute", "attention"): 9.0 + 4.0,
    ("recompute", "mlp"): 12.0, ("backward", "mlp"): 30.0,
    ("forward", "exit_gate"): 0.25, ("backward", "exit_gate"): 0.5,
    ("forward", "lm_head_loss"): 17.0}


def test_the_readers_on_a_traced_looped_step(monkeypatch):
    _fake_scope_shares(monkeypatch, LOOPED_BY, {
        "kernels": {"flash_fwd": 64}, "block_applications": 32})
    # the phase alone: the kernel's seconds are not split a second time
    assert _read("step.recompute_share_pct", {"flash_fwd": 0.16}) == (
        pytest.approx(21.0 + 4.0))
    assert _read("loop.exit_gate_share_pct") == pytest.approx(0.75)


def test_the_readers_on_a_step_without_loop_or_remat(monkeypatch):
    _fake_scope_shares(monkeypatch, {("forward", "mlp"): 40.0}, {
        "kernels": {"flash_fwd": 2}, "block_applications": 2})
    assert _read("step.recompute_share_pct", {"flash_fwd": 0.1}) == 0.0
    assert _read("loop.exit_gate_share_pct") is None
    # a program that journals no scope table (the parent of the PR that
    # added it): nothing to read, nothing raised
    _fake_scope_shares(monkeypatch, None)
    assert _read("step.recompute_share_pct") is None
    assert _read("loop.exit_gate_share_pct") is None
