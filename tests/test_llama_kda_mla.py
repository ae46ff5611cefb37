"""Kimi Delta Attention layers (the delta rule with a decay per key channel,
``layer_types`` naming "kda") beside latent-attention layers whose queries
come from one matrix, which carry no rotary position and whose values are
narrower than their keys, under a share of sigmoid-routed experts: the
program (``models/llama.py`` through the benchmark's adapter) against the
plain reference ``benchmark/reference/kimi_linear_ref.py`` on seeded weights
at the rehearsal size, and each piece alone."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from conftest import REFUSING_PATH_NAMES, refusing_calls  # noqa: E402

from benchmark.adapters import kimi_linear as ADAPTER  # noqa: E402
from benchmark.reference import kimi_linear_ref as REF  # noqa: E402
from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention,
    reference_attention,
)

S = 160  # a chunk of the rule and a quarter


def _hf(**over) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-rehearsal.json")) as f:
        return dict(json.load(f), **over)


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 4096, (batch, S + 1)), jnp.int32)


def _mc(cfg, **over):
    return dataclasses.replace(
        ADAPTER.model_config(cfg, remat_block=True, seq_len=S),
        dtype=jnp.float32, **over)


def _lively(params):
    """The gates off their initial flat spots, so that the decay, beta, the
    output gate and the router matter as a trained model's do."""
    def layer_of(layer):
        if "kda" in layer:
            kda = layer["kda"]
            layer = dict(layer, kda=dict(
                kda, f_b=20.0 * kda["f_b"], g_b=20.0 * kda["g_b"],
                w_beta=10.0 * kda["w_beta"],
                g_bias=0.5 * jnp.cos(jnp.arange(
                    kda["g_bias"].shape[0], dtype=jnp.float32))))
        else:
            layer = dict(layer, wq=20.0 * layer["wq"])
        if "moe" in layer:
            layer = dict(layer, moe=dict(
                layer["moe"], router=3.0 * layer["moe"]["router"]))
        return layer

    return dict(params, layers=[layer_of(x) for x in params["layers"]])


@pytest.fixture(scope="module")
def compared():
    """System (float32 compute, so that the comparison is tight) and
    reference, forward and every leaf's gradient, on one seeded tree."""
    cfg = _hf()
    mc = _mc(cfg)
    params = _lively(llama.init_params(jax.random.PRNGKey(7), mc))
    tokens = _tokens()

    def system(p):
        hidden, loss, extra = ADAPTER.hidden_and_loss(p, tokens, mc)
        return loss, (hidden, extra)

    (loss, (hidden, extra)), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)

    def reference(p):
        hidden_r, loss_r, extra_r = REF.hidden_and_loss(
            p, tokens, cfg, given=extra["choices"])
        return loss_r, (hidden_r, extra_r)

    (loss_r, (hidden_r, extra_r)), grads_r = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return dict(cfg=cfg, mc=mc, params=params, loss=loss, hidden=hidden,
                extra=extra, grads=grads, loss_r=loss_r, hidden_r=hidden_r,
                extra_r=extra_r, grads_r=grads_r)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def test_the_rehearsal_has_every_kind_of_layer(compared):
    mc = compared["mc"]
    assert mc.layer_types == ("kda", "kda", "attention", "kda")
    assert (mc.kda_layers, mc.attention_layers, mc.moe_layers) == (3, 1, 3)
    assert (mc.q_lora_rank, mc.rope, mc.head_dim, mc.value_head_dim) == (
        0, False, 24, 16)
    kinds = [sorted(k for k in layer if k in ("kda", "wkv_a", "mlp", "moe"))
             for layer in compared["params"]["layers"]]
    assert kinds == [["kda", "mlp"], ["kda", "moe"], ["moe", "wkv_a"],
                     ["kda", "moe"]]
    latent = compared["params"]["layers"][2]
    assert latent["wq"].shape == (64, 4 * 24) and "wq_a" not in latent
    assert latent["wo"].shape == (4 * 16, 64)
    assert latent["wkv_b"].shape == (16, 4 * (16 + 16))


def test_hidden_states_agree_with_the_reference(compared):
    assert _rel(compared["hidden"], compared["hidden_r"]) < 2e-4


def test_loss_and_balance_term_agree_with_the_reference(compared):
    assert abs(float(compared["loss"] - compared["loss_r"])) < 2e-5 * float(
        compared["loss_r"])
    aux = float(compared["extra"]["scalars"]["moe_seq_aux"])
    aux_r = float(compared["extra_r"]["scalars"]["moe_seq_aux"])
    assert aux > 0 and abs(aux - aux_r) < 1e-5 * aux_r


def test_the_experts_taken_are_the_references_own(compared):
    assert sorted(compared["extra"]["choices"]) == [
        "layers.1.experts", "layers.2.experts", "layers.3.experts"]
    for name, chosen in compared["extra"]["choices"].items():
        own = compared["extra_r"]["choices"][name]
        assert np.array_equal(np.sort(np.asarray(chosen), -1),
                              np.sort(np.asarray(own), -1)), name


def test_every_leafs_gradient_agrees_with_the_reference(compared):
    flat, _ = jax.tree_util.tree_flatten_with_path(compared["grads"])
    flat_r = jax.tree_util.tree_leaves(compared["grads_r"])
    assert len(flat) == len(flat_r) > 80
    for (path, g), g_r in zip(flat, flat_r):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            continue  # moved by its rule, never by a gradient
        assert float(jnp.linalg.norm(g_r.ravel())) > 0, name
        assert _rel(g, g_r) < 2e-3, name


def test_the_adapters_gradient_leaves_cover_every_kind(compared):
    names = set(ADAPTER.grad_leaves(compared["params"]))
    for leaf in ("f_a", "f_b", "A_log", "dt_bias", "w_beta", "g_a", "g_b",
                 "conv_q", "conv_k", "conv_v", "out_proj"):
        assert f"layers.0.kda.{leaf}" in names, leaf
        assert f"layers.3.kda.{leaf}" in names, leaf
    for leaf in ("wq", "wkv_a", "wkv_b", "wo"):
        assert f"layers.2.{leaf}" in names, leaf
    assert "layers.1.moe.router" in names and "embed" in names
    back = ADAPTER.with_leaves(
        compared["params"], ADAPTER.grad_leaves(compared["params"]))
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(
        compared["params"])


# -- the planted faults, at toy width in float32 ------------------------------


@pytest.mark.parametrize("planted", REF.FAULTS)
def test_a_planted_fault_moves_the_reference(compared, planted):
    """Each fault the probe plants computes another model: far outside what
    float32 agrees to, in the hidden states."""
    hidden, _, _ = jax.jit(lambda p: REF.hidden_and_loss(
        p, _tokens(), dict(compared["cfg"], planted=planted),
        given=compared["extra"]["choices"]))(compared["params"])
    assert _rel(hidden, compared["hidden_r"]) > 5e-3, planted


def test_an_unknown_plant_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault 'nothing'"):
        REF.hidden_and_loss({}, _tokens(), _hf(planted="nothing"))


# -- latent attention's new forms, each alone ---------------------------------


def _latent(**over):
    base = dict(n_layer=1, n_head=4, n_kv_head=4, max_seq_len=S,
                dtype=jnp.float32, kv_lora_rank=16, q_lora_rank=0,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                rope=False)
    cfg = llama.LlamaConfig.tiny(**dict(base, **over))
    return cfg, llama.init_params(jax.random.PRNGKey(1), cfg)


def _hidden(cfg, params, tokens):
    return llama.forward_hidden(params, tokens, cfg)[0]


def test_without_rotation_an_earlier_swap_moves_nothing_later():
    """A position-free layer sees a SET of earlier tokens: swapping tokens 3
    and 9 leaves every position from 10 on as it was, and with ``rope`` True
    it does not."""
    tokens = _tokens(3, 1)[:, :S] % 256
    swapped = tokens.at[0, 3].set(tokens[0, 9]).at[0, 9].set(tokens[0, 3])
    cfg, params = _latent()
    params = dict(params, layers=[dict(
        params["layers"][0], wq=30.0 * params["layers"][0]["wq"])])
    a, b = _hidden(cfg, params, tokens), _hidden(cfg, params, swapped)
    assert _rel(a[:, 10:], b[:, 10:]) < 1e-5
    assert _rel(a[:, :10], b[:, :10]) > 1e-3
    turned = dataclasses.replace(cfg, rope=True)
    a, b = _hidden(turned, params, tokens), _hidden(turned, params, swapped)
    assert _rel(a[:, 10:], b[:, 10:]) > 1e-4


def test_a_query_latent_and_one_query_matrix_are_two_sets_of_leaves():
    _, direct = _latent()
    _, through = _latent(q_lora_rank=12)
    assert {"wq"} == set(direct["layers"][0]) - set(through["layers"][0])
    assert {"wq_a", "q_a_norm", "wq_b"} == (
        set(through["layers"][0]) - set(direct["layers"][0]))
    cfg, _ = _latent()
    axes = llama.param_logical_axes(cfg)["layers"][0]
    assert axes["wq"] == ("embed", "heads") and "wq_a" not in axes


@pytest.mark.parametrize("widths", [(24, 16), (16, 24), (192, 128)],
                         ids=["narrower_v", "wider_v", "published"])
def test_flash_at_unequal_widths_equals_its_reference(widths):
    """``v`` and ``o`` at a width of their own through the three kernels
    (interpret mode), GQA included: output and gradients."""
    d, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(d), 4)
    q = jax.random.normal(keys[0], (1, 4, 200, d))
    k = jax.random.normal(keys[1], (1, 2, 200, d))
    v = jax.random.normal(keys[2], (1, 2, 200, dv))
    cot = jax.random.normal(keys[3], (1, 4, 200, dv))

    def run(fn, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, **kw)
            return jnp.sum(o * cot), o
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)

    (_, o), grads = run(flash_attention, backend="pallas", interpret=True,
                        block_q=128, block_k=128, bwd_block_q=128,
                        bwd_block_k=128)
    (_, o_ref), want = run(lambda q, k, v: reference_attention(q, k, v))
    assert o.shape == (1, 4, 200, dv) and _rel(o, o_ref) < 1e-5
    for g, w in zip(grads, want):
        assert g.shape == w.shape and _rel(g, w) < 1e-5


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_narrower_values_refuse_ring_and_ulysses_by_name(impl):
    from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg, params = _latent()
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with pytest.raises(NotImplementedError,
                       match=f"v_head_dim=16 under 24-wide q and k.*'{impl}'"):
        llama.forward_hidden(params, _tokens(1, 1)[:, :S] % 256, cfg,
                             attn_impl=impl, mesh=mesh)


# -- the share ----------------------------------------------------------------


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One routed KDA layer, a 16-wide router top-4: the two chips of a
    2-way expert-parallel layer hold experts 0-7 and 8-15 and compute the
    pairs routed to them; their routed parts, with the mixer and the shared
    expert (computed alike on every chip) counted once, add up to the uncut
    reference's layer."""
    whole = _hf(num_hidden_layers=1, num_experts=16, first_k_dense_replace=0)
    whole["linear_attn_config"] = dict(
        whole["linear_attn_config"], kda_layers=[1], full_attn_layers=[])
    uncut = dataclasses.replace(
        ADAPTER.model_config(whole, remat_block=False, seq_len=S),
        dtype=jnp.float32)
    assert uncut.experts_held == 0 and uncut.num_experts == 16
    params = _lively(llama.init_params(jax.random.PRNGKey(5), uncut))
    tokens = _tokens(9)
    want, _, _ = REF.hidden_and_loss(params, tokens, whole)

    layer = params["layers"][0]
    x = params["embed"][tokens[:, :-1]]
    positions = jnp.broadcast_to(jnp.arange(S), x.shape[:2])

    def share(first, experts=True):
        cfg = dataclasses.replace(uncut, experts_held=8,
                                  experts_held_first=first)
        moe = dict(layer["moe"], **{
            k: layer["moe"][k][first:first + 8] for k in ("wg", "wi", "wo")})
        if not experts:  # what every chip computes alike: the experts put
            moe["wo"] = jnp.zeros_like(moe["wo"])  # out nothing
        return llama.block_apply(dict(layer, moe=moe), x, cfg, positions)[0]

    alike = share(0, experts=False)  # the mixer and the shared expert, once
    y = alike + sum(share(first) - alike for first in (0, 8))
    got = REF._rms(y, params["ln_f"], whole["rms_norm_eps"])
    assert _rel(got, want) < 2e-5
    # a share alone is not the layer, and neither is the part computed alike
    assert _rel(REF._rms(share(0), params["ln_f"], 1e-5), want) > 1e-2
    # and the reference, told which slice is held, computes each share as
    # the program does (the final norm is not additive, so share by share)

    def held(first):
        moe = dict(layer["moe"], **{
            k: layer["moe"][k][first:first + 8] for k in ("wg", "wi", "wo")})
        return dict(params, layers=[dict(layer, moe=moe)])

    for first in (0, 8):
        ref_share, _, _ = REF.hidden_and_loss(held(first), tokens, dict(
            whole, num_experts=8, published={"num_experts": 16},
            held_first=first))
        sys_share = REF._rms(share(first), params["ln_f"],
                             whole["rms_norm_eps"])
        assert _rel(sys_share, ref_share) < 2e-5, first


# -- what the configuration refuses, and what the other paths refuse ----------

_KDA = dict(n_layer=2, layer_types=("kda", "attention"), kda_heads=2,
            kda_d_head=16)


@pytest.mark.parametrize("over,match", [
    (dict(_KDA, kda_heads=0), "'kda' layers with kda_heads=0"),
    (dict(_KDA, kda_d_conv=0), "kda_d_conv=0"),
    (dict(_KDA, mtp_layers=1, num_experts=4), "mtp_layers=1"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
          n_kv_head=4), "v_head_dim > 0"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=7,
          v_head_dim=8, n_kv_head=4), "an even qk_rope_head_dim"),
    (dict(kv_lora_rank=16, q_lora_rank=-1, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, n_kv_head=4),
     "q_lora_rank >= 0"),
    (dict(n_layer=2, layer_types=("window_attention", "attention"),
          sliding_window=16, n_kv_head=4, kv_lora_rank=16,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8),
     "'window_attention' layers with sliding_window=16 or kv_lora_rank=16"),
], ids=["no_heads", "no_taps", "prediction_block", "no_value_width",
        "odd_shared_part", "negative_query_rank", "window_beside_latent"])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        llama.LlamaConfig.tiny(**over)


@pytest.mark.parametrize("kw", [
    dict(segment_ids=np.zeros((2, S), np.int32)),
    dict(attn_fn=lambda *a: None)], ids=["segment_ids", "attn_fn"])
def test_a_kda_layer_refuses_documents_and_a_cache_by_name(kw):
    cfg = llama.LlamaConfig.tiny(**_KDA, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, S, 64), jnp.float32)
    with pytest.raises(NotImplementedError, match="a 'kda' layer with"):
        llama.block_apply(params["layers"][0], x, cfg,
                          jnp.zeros((2, S), jnp.int32), **kw)


_LATENT = dict(n_kv_head=4, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8)
#: each new form alone, on a config every other row of the table lets by
NEW_FORMS = {
    "kda": (dict(_KDA), "layer_types with a 'kda' entry (1 of 2 layers)",
            "not attention over every earlier position"),
    "direct_query": (
        dict(_LATENT, q_lora_rank=0, v_head_dim=24),
        "kv_lora_rank=16 with q_lora_rank=0",
        "latent attention, its queries from one matrix"),
    "narrower_values": (
        dict(_LATENT, q_lora_rank=12, v_head_dim=16),
        "v_head_dim=16 under 24-wide keys",
        "latent attention, values of another width than the keys"),
    "no_position": (
        dict(_LATENT, q_lora_rank=12, v_head_dim=24, rope=False),
        "24-wide keys and rope=False",
        "latent attention, no rotary position on either part"),
}


@pytest.mark.parametrize("form", sorted(NEW_FORMS))
@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_the_refusal_names_the_form_and_the_path(where, path, form):
    over, said, what = NEW_FORMS[form]
    cfg = llama.LlamaConfig.tiny(**over)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    assert said in str(e.value) and what in str(e.value)
    assert path in str(e.value) and "training path only" in str(e.value)


def test_plain_latent_attention_is_refused_as_it_was():
    cfg = llama.LlamaConfig.tiny(**_LATENT, q_lora_rank=12, v_head_dim=24)
    with pytest.raises(ValueError) as e:
        llama.refuse_training_path_only(cfg, "here")
    assert str(e.value).endswith(
        "rope=True: latent attention, training path only "
        "(llama.forward_hidden / loss_fn)")
    assert len(llama.TRAINING_PATH_ONLY) == 26
    assert "kda" in llama.MIXER_KINDS and llama.MIXER_KINDS["kda"] == "kda"


# -- what the program says of itself ------------------------------------------


def test_a_thirty_second_of_the_experts_is_sized_as_every_share():
    """8 of 256 held: the sized buffer is the even share and a quarter
    (5,120 rows for 4,096), by the one formula every other share takes."""
    bounds = llama._moe_buffer_bounds
    assert bounds(16384, 8, 256, 8) == (5120, 131072)
    assert bounds(16384, 8, 128, 8) == (10240, 131072)
    assert bounds(2 * 8192, 10, 512, 32) == (12800, 163840)


def test_program_facts_count_the_kda_layers_and_their_chunks(compared):
    facts = llama.program_facts(compared["mc"], 16384)
    assert facts == {"kda_layers": 3, "attention_layers": 1,
                     "kda_chunks_per_sequence": 128}
    assert llama.KDA_CHUNK == 128


def test_flops_per_token_counts_the_new_parts():
    """One KDA layer against one attention layer of the same model: the
    four projections, two low-rank gates and beta, the rule at its chunk
    and three convolutions; a latent layer's scores over 24 dims and values
    over 16, one query matrix."""
    cfg, _ = _latent()
    base = dict(n_layer=2, n_head=4, n_kv_head=4, max_seq_len=S,
                kv_lora_rank=16, q_lora_rank=0, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, rope=False, kda_heads=4,
                kda_d_head=16)
    both = llama.LlamaConfig.tiny(**base, layer_types=("kda", "attention"))
    d, f = 64, 128
    mla = d * 4 * 24 + d * 24 + 16 * 4 * 32 + 4 * 16 * d
    kda = d * (3 * 64 + 2 * 16 + 4) + 2 * 16 * 64 + 64 * d
    rule = 4 * (10 * 128 * 16 + 6 * 16 * 16) + 2 * 4 * 3 * 64
    head = 2 * 256 * d
    assert llama.flops_per_token(cfg) == pytest.approx(
        6.0 * (mla + 3 * d * f + head) + 6.0 * S * 4 * (24 + 16))
    assert llama.flops_per_token(both) == pytest.approx(
        6.0 * (mla + kda + 2 * 3 * d * f + head)
        + 6.0 * S * 4 * (24 + 16) + 3.0 * rule)
