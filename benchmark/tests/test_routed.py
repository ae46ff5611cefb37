"""A routed block under ``check_against_reference``: the reference computes
the experts the system chose, so that the standing tolerances judge the
arithmetic; the choices are judged on how many differ and how near a tie
each flip was.  Toy width through the program's own routed path
(``routed_adapter.py``, ``routed_ref.py``: with the tests, named by no
configuration file), every planted fault, and one OLMoE layer at published
widths pinning the phenomenon the contract rests on."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import common, fault_probe, model
from benchmark.tests import routed_adapter, routed_ref

NAME = "routed-toy"
#: toy widths; as OLMoE's block in kind: every layer routed, 8 experts top 2
TOY = {
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 4096,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "adapter": NAME, "reference": NAME,
}
CELL = {
    "name": f"{NAME}.test", "config_data": TOY, "chips": 1,
    "mesh": {"fsdp": 1, "tp": 1}, "batch_sequences": 2, "remat_block": False,
    "traffic_data": {"seq_len": 256, "learning_rate": 3e-4},
}


def _name_modules(monkeypatch, adapter):
    """``NAME`` resolves to the tests' adapter and reference, every other
    name to the benchmark's files as always."""
    load = common.load_module
    mine = {("adapters", NAME): adapter, ("reference", NAME): routed_ref}
    monkeypatch.setattr(
        common, "load_module",
        lambda folder, name: mine.get((folder, name)) or load(folder, name))


@pytest.fixture(scope="module")
def toy():
    with pytest.MonkeyPatch.context() as mp:
        _name_modules(mp, routed_adapter)
        job, mc = model.build_job(CELL, devices=jax.devices()[:1])
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    return job, mc, params


def _check(monkeypatch, toy, adapter=routed_adapter, mc=None, ref_cfg=None):
    job, toy_mc, params = toy
    _name_modules(monkeypatch, adapter)
    return model.check_against_reference(
        job, mc or toy_mc, CELL, params, 0, ref_cfg=ref_cfg)


def _variant(**changed):
    return types.SimpleNamespace(**dict(vars(routed_adapter), **changed))


def test_the_adapter_runs_the_programs_own_loss(toy):
    from dlrover_tpu.models import llama

    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(1, range(2), 64, 4096))
    hidden, loss, extra = routed_adapter.hidden_and_loss(params, toks, mc)
    assert float(loss) == pytest.approx(
        float(llama.loss_fn(params, {"tokens": toks}, mc)), rel=1e-6)
    chosen = extra["choices"][routed_ref.experts_name(1)]
    assert chosen.shape == (2, 64, 2) and chosen.dtype == jnp.int32
    assert float(extra["scalars"]["moe_aux"]) > 0


def test_system_in_float32_equals_the_reference_and_takes_its_experts(toy):
    _, mc, params = toy
    toks = jnp.asarray(model.sample_tokens(2, range(2), 64, 4096))
    f32 = dataclasses.replace(mc, dtype=jnp.float32)
    hidden, loss, extra = routed_adapter.hidden_and_loss(params, toks, f32)
    hidden_r, loss_r, extra_r = routed_ref.hidden_and_loss(params, toks, TOY)
    assert float(jnp.linalg.norm(hidden - hidden_r)
                 / jnp.linalg.norm(hidden_r)) < 1e-4
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert float(extra["scalars"]["moe_aux"]) == pytest.approx(
        float(extra_r["scalars"]["moe_aux"]), rel=1e-5)
    for name, chosen in extra["choices"].items():
        assert np.array_equal(np.sort(chosen, -1),
                              np.sort(extra_r["choices"][name], -1))


def test_the_true_reference_reads_ok(monkeypatch, toy):
    out = _check(monkeypatch, toy)
    assert out["ok"], out
    # the standing tolerances, not the adapter's, judge the arithmetic
    assert out["hidden_rel_tol"] == model.hidden_rel_tol(2)
    assert out["grad_rel_tol"] == model.grad_rel_tol(2)
    assert sorted(out["grad_rel_l2_worst_by_leaf_kind"]) == [
        "embed", "router", "wg", "wi", "wk", "wo", "wq", "wv"]
    assert out["choice_diff_share_tol"] == pytest.approx(0.10 * 2 ** 0.5)
    assert out["choice_prob_gap_tol"] == pytest.approx(2e-3 * 2 ** 0.5)
    assert 0 <= out["choice_prob_gap"] <= out["choice_prob_gap_tol"]
    assert out["scalar_rel_diff_at"] == "moe_aux"
    # reported, never judged
    for key in ("hidden_rel_l2_independent", "loss_rel_diff_independent",
                "choice_diff_share_independent"):
        assert np.isfinite(out[key])


def test_a_loss_without_further_scalars_is_judged_on_the_rest(
        monkeypatch, toy):
    def no_scalars(params, tokens, mc):
        hidden, loss, extra = routed_adapter.hidden_and_loss(
            params, tokens, mc)
        return hidden, loss, dict(extra, scalars={})

    out = _check(monkeypatch, toy,
                 adapter=_variant(hidden_and_loss=no_scalars))
    assert out["ok"] and out["scalar_rel_diff"] == 0.0, out


def _wrong_expert(params, tokens, mc):
    """A system that takes, in every token, its second expert at random,
    computes what it took and says so."""
    _, _, own = routed_ref.hidden_and_loss(params, tokens, TOY)
    taken = {}
    for i, (name, chosen) in enumerate(sorted(own["choices"].items())):
        other = jax.random.randint(
            jax.random.PRNGKey(i), chosen.shape[:-1], 1, TOY["num_experts"])
        taken[name] = chosen.at[..., 1].set(
            (chosen[..., 0] + other) % TOY["num_experts"])
    hidden, loss, extra = routed_ref.hidden_and_loss(
        params, tokens, TOY, given=taken)
    return hidden, loss, {"choices": taken, "scalars": extra["scalars"]}


def _aux_off_by_a_tenth(params, tokens, mc):
    hidden, loss, extra = routed_adapter.hidden_and_loss(params, tokens, mc)
    aux = extra["scalars"]["moe_aux"]
    return (hidden, loss + 0.1 * routed_adapter.AUX_WEIGHT * aux,
            dict(extra, scalars={"moe_aux": 1.1 * aux}))


@pytest.mark.parametrize("fault", [
    "renormalised against a reference that is not",
    "a capacity that drops tokens",
    "one chosen expert replaced at random",
    "an auxiliary scalar off by a tenth",
])
def test_a_planted_fault_reads_not_ok(monkeypatch, toy, fault):
    _, mc, _ = toy
    far = False
    if fault.startswith("renormalised"):
        out = _check(monkeypatch, toy,
                     ref_cfg=dict(TOY, norm_topk_prob=False))
        far = True
    elif fault.startswith("a capacity"):
        out = _check(monkeypatch, toy,
                     mc=dataclasses.replace(mc, capacity_factor=0.5))
        far = True
    elif fault.startswith("one chosen"):
        out = _check(monkeypatch, toy,
                     adapter=_variant(hidden_and_loss=_wrong_expert))
        # the arithmetic under its own choices is right: only the choice
        # itself gives it away, far from any tie
        assert out["hidden_rel_l2"] < out["hidden_rel_tol"], out
        assert out["choice_prob_gap"] > 5 * out["choice_prob_gap_tol"], out
    else:
        out = _check(monkeypatch, toy,
                     adapter=_variant(hidden_and_loss=_aux_off_by_a_tenth))
        assert out["loss_rel_diff"] < model.LOSS_REL_TOL, out
        assert out["scalar_rel_diff"] > 10 * out["scalar_rel_tol"], out
    if far:
        # at initialisation the experts add little to the stream (hidden
        # 1.7 tolerances away) and their own gradients are the sharp
        # instrument: 0.8 % under the true reference, 70 % and more here
        assert out["hidden_rel_l2"] > out["hidden_rel_tol"], out
        assert min(out["grad_rel_l2_worst_by_leaf_kind"][k]
                   for k in ("wg", "wi", "wo")) > 5 * out["grad_rel_tol"], out
    assert not out["ok"], out


def test_the_fault_probe_plants_routed_faults_by_configuration(
        monkeypatch, toy):
    faults = fault_probe.planted_faults(TOY)
    assert sorted(faults) == ["none", "norm_topk_prob flipped",
                              "num_experts_per_tok minus one"]
    for name, ref_cfg in faults.items():
        out = _check(monkeypatch, toy, ref_cfg=ref_cfg)
        assert out["ok"] == (name == "none"), (name, out)
    dense = common.load_json("configs", "rehearsal-tiny.json")
    assert sorted(fault_probe.planted_faults(dense)) == [
        "none", "window dropped", "window halved"]


# -- one OLMoE layer at published widths -----------------------------------

#: allenai/OLMoE-1B-7B-0125-Instruct config.json (the guide's catalog): the
#: widths of one layer; the vocabulary cut to 4,096 rows, which no router sees
OLMOE_LAYER = {
    "hidden_size": 2048, "intermediate_size": 1024,
    "num_attention_heads": 16, "num_key_value_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "norm_topk_prob": False, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "num_hidden_layers": 1, "vocab_size": 4096,
}


def _olmoe_layer_params(seed: int) -> dict:
    """N(0, 0.02) as ``llama.init_params`` draws, with OLMoE's q/k norms."""
    d, f = OLMOE_LAYER["hidden_size"], OLMOE_LAYER["intermediate_size"]
    e, v = OLMOE_LAYER["num_experts"], OLMOE_LAYER["vocab_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    draw = lambda *shape: 0.02 * jax.random.normal(  # noqa: E731
        next(keys), shape, jnp.float32)
    ones = jnp.ones(d, jnp.float32)
    layer = {"ln1": ones, "ln2": ones, "q_norm": ones, "k_norm": ones,
             "wq": draw(d, d), "wk": draw(d, d), "wv": draw(d, d),
             "wo": draw(d, d),
             "moe": {"router": draw(d, e), "wg": draw(e, d, f),
                     "wi": draw(e, d, f), "wo": draw(e, f, d)}}
    return {"embed": draw(v, d), "lm_head": draw(d, v), "ln_f": ones,
            "layers": [layer]}


def test_one_olmoe_layer_at_published_widths():
    """bf16 operands and residual stream against float32 ``highest``, 1,024
    tokens of one sequence (the measurement of ISSUE 25, redone): rounding
    flips the expert set of a few tokens in a hundred, each of them far off,
    so the rule every dense block is held to fails a correct system when the
    reference routes for itself and passes it under the system's choices;
    and every flip is near a tie."""
    params = _olmoe_layer_params(0)
    toks = jnp.asarray(model.sample_tokens(0, [0], 1024, 4096))
    ref = lambda **kw: jax.jit(  # noqa: E731
        lambda p, t, g: routed_ref.hidden_and_loss(
            p, t, OLMOE_LAYER, given=g, **kw))
    hidden, loss, extra = ref(dtype=jnp.bfloat16)(params, toks, None)
    apart, _, _ = ref()(params, toks, None)
    under, loss_u, extra_u = ref()(params, toks, extra["choices"])
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    tol = model.hidden_rel_tol(1)
    assert rel(hidden, apart) > tol          # today's rule: 3.3 % of 2.0 %
    assert rel(hidden, under) < 0.6 * tol    # 0.8 %: ordinary bf16 rounding
    assert abs(float(loss) - float(loss_u)) / float(loss_u) < (
        model.LOSS_REL_TOL)
    dist = jax.device_get(model._choice_distances(
        extra["choices"], extra_u["choices"], extra_u["probs"]))
    (share,), (gap,) = (dist["choice_diff_share"].values(),
                        dist["choice_prob_gap"].values())
    assert 0.01 < share < 0.10, share        # 4.8 %
    assert 0 < gap < routed_adapter.CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER, gap
    assert share < routed_adapter.CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER
    aux, aux_u = (float(e["scalars"]["moe_aux"]) for e in (extra, extra_u))
    assert abs(aux - aux_u) / aux_u < routed_adapter.SCALAR_REL_TOL
