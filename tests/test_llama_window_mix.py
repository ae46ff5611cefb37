"""Window and full attention layers in ONE stack (``layer_types`` naming
"window_attention" beside "attention"), each kind on its own rotary table
(``rotary_by_kind``: the plain one, or YaRN's blend with its attention
factor), under a share of softmax-routed experts: the program
(``models/llama.py`` through the benchmark's adapter) against the plain
reference ``benchmark/reference/mellum_ref.py`` on seeded weights at the
rehearsal size, and each piece alone."""

import hashlib
import importlib.util
import json
import math
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

from benchmark.adapters import mellum as ADAPTER  # noqa: E402
from benchmark.reference import mellum_ref as REF  # noqa: E402
from dlrover_tpu.models import llama  # noqa: E402

S = 96
SLIDING, FULL = "sliding_attention", "full_attention"
STACKS = {
    "three_to_one": [SLIDING] * 3 + [FULL],
    "all_window": [SLIDING] * 4,
    "all_full": [FULL] * 4,
}
#: the published five numbers of the full layers' table, and the base
PUBLISHED = dict(theta=500000.0, factor=16.0,
                 original_max_position_embeddings=8192, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=1.2772588722239782)


def _hf(stack="three_to_one", **over) -> dict:
    """The rehearsal configuration with another stack of layer types."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum-rehearsal.json")) as f:
        cfg = json.load(f)
    kinds = STACKS[stack]
    cfg["layer_types"] = kinds
    cfg["rope_parameters"] = {k: v for k, v in cfg["rope_parameters"].items()
                              if k in kinds}
    return dict(cfg, **over)


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 4096, (batch, S + 1)), jnp.int32)


@pytest.fixture(scope="module", params=sorted(STACKS))
def compared(request):
    """System (float32 compute, so that the comparison is tight) and
    reference, forward and every leaf's gradient, on one seeded tree."""
    import dataclasses

    cfg = _hf(request.param)
    mc = dataclasses.replace(
        ADAPTER.model_config(cfg, remat_block=True, seq_len=S),
        dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(7), mc)
    tokens = _tokens()

    def system(p):
        hidden, loss, extra = ADAPTER.hidden_and_loss(p, tokens, mc)
        return loss, (hidden, extra)

    (loss, (hidden, extra)), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)

    def reference(p):
        hidden_r, loss_r, extra_r = REF.hidden_and_loss(
            p, tokens, cfg, given=extra["choices"], q_block=32)
        return loss_r, (hidden_r, extra_r)

    (loss_r, (hidden_r, extra_r)), grads_r = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return dict(cfg=cfg, mc=mc, loss=loss, hidden=hidden, extra=extra,
                grads=grads, loss_r=loss_r, hidden_r=hidden_r,
                extra_r=extra_r, grads_r=grads_r)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def test_hidden_states_agree_with_the_reference(compared):
    assert _rel(compared["hidden"], compared["hidden_r"]) < 2e-4


def test_loss_and_balance_term_agree_with_the_reference(compared):
    assert abs(float(compared["loss"] - compared["loss_r"])) < 2e-5 * float(
        compared["loss_r"])
    aux = float(compared["extra"]["scalars"]["moe_aux"])
    aux_r = float(compared["extra_r"]["scalars"]["moe_aux"])
    assert aux > 0 and abs(aux - aux_r) < 1e-5 * aux_r


def test_the_experts_taken_are_the_references_own(compared):
    for name, chosen in compared["extra"]["choices"].items():
        own = compared["extra_r"]["choices"][name]
        assert np.array_equal(np.sort(np.asarray(chosen), -1),
                              np.sort(np.asarray(own), -1)), name


def test_every_leafs_gradient_agrees_with_the_reference(compared):
    flat, _ = jax.tree_util.tree_flatten_with_path(compared["grads"])
    flat_r = jax.tree_util.tree_leaves(compared["grads_r"])
    assert len(flat) == len(flat_r) > 40
    for (path, g), g_r in zip(flat, flat_r):
        assert float(jnp.linalg.norm(g_r.ravel())) > 0, path
        assert _rel(g, g_r) < 2e-3, jax.tree_util.keystr(path)


# -- what a layer of each kind can see ----------------------------------------


def _one_layer(kind):
    cfg = llama.LlamaConfig.tiny(
        n_layer=1, max_seq_len=S, dtype=jnp.float32, layer_types=(kind,),
        sliding_window=16 if kind == "window_attention" else 0)
    return cfg, llama.init_params(jax.random.PRNGKey(1), cfg)


@pytest.mark.parametrize("kind,moves_past_the_window", [
    ("window_attention", False), ("attention", True)])
def test_a_key_a_window_back_moves_a_full_layer_alone(
        kind, moves_past_the_window):
    """Token 0 changed: a window layer's output moves at positions 0..15
    and nowhere at or past 16 (``0 <= t - s < 16``); a full layer's moves
    everywhere."""
    cfg, params = _one_layer(kind)
    tokens = _tokens(3, batch=1)[:, :S]
    other = tokens.at[0, 0].set((tokens[0, 0] + 1) % 256)
    run = jax.jit(lambda t: llama.forward_hidden(params, t % 256, cfg)[0])
    moved = np.abs(np.asarray(run(tokens) - run(other))).max(-1)[0]
    assert moved[:16].min() > 0
    assert (moved[16:].max() > 0) == moves_past_the_window
    if moves_past_the_window:
        assert moved[16:].min() > 0


def test_the_window_is_the_window_kinds_alone_in_a_mixed_model():
    cfg = llama.LlamaConfig.tiny(
        n_layer=4, sliding_window=16,
        layer_types=("window_attention",) * 3 + ("attention",))
    assert (cfg.window_of("window_attention"), cfg.window_of("attention"),
            cfg.window_layers, cfg.attention_layers,
            cfg.block_applications) == (16, 0, 3, 4, 4)
    # one global window, as ever, where no layer is of the window kind
    plain = llama.LlamaConfig.tiny(sliding_window=16)
    assert (plain.window_of("attention"), plain.window_layers) == (16, 0)
    # both kinds hold the same leaves, under the same axes
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["layers"][0]) == set(params["layers"][3])
    axes = llama.param_logical_axes(cfg)
    assert axes["layers"][0] == axes["layers"][3]


# -- the rotary tables ----------------------------------------------------------


def _closed_form(dim=128):
    """YaRN's frequencies from the published five numbers in float64."""
    p = PUBLISHED
    d = lambda n: (dim * math.log(  # noqa: E731
        p["original_max_position_embeddings"] / (2 * math.pi * n))
        / (2 * math.log(p["theta"])))
    low, high = math.floor(d(p["beta_fast"])), math.ceil(d(p["beta_slow"]))
    j = np.arange(dim // 2, dtype=np.float64)
    f = p["theta"] ** (-j / (dim // 2))
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return low, high, f / p["factor"] * ramp + f * (1 - ramp), f


def test_yarn_low_and_high_at_the_published_numbers():
    low, high, _, _ = _closed_form()
    assert (low, high) == (18, 35)
    assert llama.Rotary(**PUBLISHED).correction_range(128) == (18, 35)
    assert REF.yarn_range(dict(
        PUBLISHED, rope_theta=PUBLISHED["theta"]), 128) == (18, 35)


def test_yarn_frequencies_against_the_closed_form():
    _, _, inv, plain = _closed_form()
    got = np.asarray(llama.Rotary(**PUBLISHED).inv_freq(128), np.float64)
    np.testing.assert_allclose(got, inv, rtol=3e-6)
    # the fast dims keep their frequency, the slow ones run 16 times slower
    np.testing.assert_allclose(got[:18], plain[:18], rtol=3e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=3e-6)


def test_the_table_carries_the_attention_factor_on_cos_and_sin():
    positions = jnp.arange(16384)[None]
    rotary = llama.Rotary(**PUBLISHED)
    cos, sin = llama._rotary_table(positions, rotary, 128)
    assert cos.shape == sin.shape == (1, 16384, 1, 64)
    assert cos.dtype == sin.dtype == jnp.float32
    _, _, inv, _ = _closed_form()
    # float32 angles at position 16,383 carry ~1e-3 rad of rounding on the
    # fastest dims: compared where the angle is small enough to be exact
    angle = np.arange(16384)[:, None] * inv[None, 40:]
    np.testing.assert_allclose(
        np.asarray(cos[0, :, 0, 40:]),
        PUBLISHED["attention_factor"] * np.cos(angle), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(sin[0, :, 0, 40:]),
        PUBLISHED["attention_factor"] * np.sin(angle), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(cos ** 2 + sin ** 2), PUBLISHED["attention_factor"] ** 2,
        rtol=1e-5)
    # and the reference's own, written apart, is the same table
    cos_r, sin_r = REF.rotary_table(
        dict(PUBLISHED, rope_theta=500000.0, rope_type="yarn"), 128, 16384)
    np.testing.assert_allclose(np.asarray(cos[0, :, 0]), np.asarray(cos_r),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin[0, :, 0]), np.asarray(sin_r),
                               atol=2e-3)


def test_factor_one_gives_the_plain_table_bit_for_bit():
    positions = jnp.broadcast_to(jnp.arange(S), (2, S))
    plain = llama._rotary_table(positions, llama.Rotary(500000.0), 32)
    one = llama._rotary_table(positions, llama.Rotary(
        500000.0, factor=1.0, original_max_position_embeddings=8192), 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, S, 4, 32))
    for a, b in zip(plain, one):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(llama._rope(x, positions, 500000.0)),
                          np.asarray(llama._rotate(x, *one)))


def test_a_kinds_table_is_built_once_a_step_not_once_a_layer():
    """Eight layers of two kinds: two tables (a cosine and a sine each) in
    the traced step, whatever the depth."""
    cfg = llama.LlamaConfig.tiny(
        n_layer=8, sliding_window=16, max_seq_len=S,
        layer_types=(("window_attention",) * 3 + ("attention",)) * 2,
        rotary_by_kind={"attention": llama.Rotary(1e4, 4.0, 32),
                        "window_attention": llama.Rotary(1e4)})
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    text = str(jax.make_jaxpr(
        lambda p: llama.forward_hidden(p, _tokens()[:, :S] % 256, cfg)[0])(
            params))
    assert text.count(" cos ") == text.count(" sin ") == 2
    plain = llama.LlamaConfig.tiny(n_layer=8, max_seq_len=S)
    text = str(jax.make_jaxpr(
        lambda p: llama.forward_hidden(p, _tokens()[:, :S] % 256, plain)[0])(
            jax.eval_shape(
                lambda: llama.init_params(jax.random.PRNGKey(0), plain))))
    assert text.count(" cos ") == 16  # q and k of each layer, as ever


# -- the share ---------------------------------------------------------------------


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One layer, a 16-wide router top-3: the four chips of a 4-way
    expert-parallel layer each hold four experts and compute the pairs
    routed to them; their routed parts, with attention (computed alike on
    every chip) counted once, add up to the uncut reference's layer."""
    import dataclasses

    whole = _hf(num_hidden_layers=1, num_experts=16)
    whole["layer_types"], whole["mlp_layer_types"] = [SLIDING], ["sparse"]
    whole["rope_parameters"] = {
        SLIDING: whole["rope_parameters"][SLIDING]}
    uncut = dataclasses.replace(
        ADAPTER.model_config(whole, remat_block=False, seq_len=S),
        dtype=jnp.float32)
    assert uncut.experts_held == 0
    params = llama.init_params(jax.random.PRNGKey(5), uncut)
    tokens = _tokens(9)
    want, _, _ = REF.hidden_and_loss(params, tokens, whole, q_block=32)

    layer = params["layers"][0]
    x = params["embed"][tokens[:, :-1]]
    positions = jnp.broadcast_to(jnp.arange(S), x.shape[:2])

    def out_of(cfg, layer):
        return llama.block_apply(
            layer, x, cfg, positions, attn_kind="window_attention")[0]

    def share(first, zero=False):
        cfg = dataclasses.replace(uncut, experts_held=4,
                                  experts_held_first=first)
        moe = dict(layer["moe"], **{
            k: layer["moe"][k][first:first + 4] for k in ("wg", "wi", "wo")})
        if zero:  # the attention half alone: the experts put out nothing
            moe["wo"] = jnp.zeros_like(moe["wo"])
        return out_of(cfg, dict(layer, moe=moe))

    h = share(0, zero=True)
    y = h + sum(share(first) - h for first in (0, 4, 8, 12))
    got = REF._rms(y, params["ln_f"], whole["rms_norm_eps"])
    assert _rel(got, want) < 2e-5
    # and a share alone is not the layer
    assert _rel(REF._rms(share(0), params["ln_f"], 1e-6), want) > 1e-2


# -- one global window compiles to what it compiled to ---------------------------

#: sha256 of the StableHLO text (no source locations) that
#: ``jit(value_and_grad(loss_fn))`` lowers to on the CPU backend for the
#: config below — ``sliding_window`` on every layer, ``layer_types`` empty,
#: block remat, as the three Mistral cells run — computed AT THE PARENT of
#: the PR that brought the second attention kind (commit e9aaea2, jax
#: 0.9.0).  A later PR that changes the model's traced operations on purpose
#: computes it anew on ITS parent and says so.
ONE_WINDOW_HLO_SHA256 = "b3aa0d9ffa9ac1936e58c48979bac4e57e5a66f70a45f22c09968f24c777b459"


def test_one_global_window_lowers_to_the_text_it_lowered_to_at_the_parent():
    cfg = llama.LlamaConfig.tiny(sliding_window=16, max_seq_len=64,
                                 remat_block=True)
    tokens = jnp.arange(65)[None] % 256
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg))).lower(
            shapes).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_WINDOW_HLO_SHA256


def test_naming_every_layer_attention_changes_nothing_either():
    """``layer_types`` all "attention" with a window is the one global
    window too: no layer is of the window kind."""
    tokens = jnp.arange(65)[None] % 256

    def text(**over):
        cfg = llama.LlamaConfig.tiny(sliding_window=16, max_seq_len=64, **over)
        shapes = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        return jax.jit(
            lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg)).lower(
                shapes).as_text()

    assert text() == text(layer_types=("attention",) * 2)
    assert text() != text(layer_types=("window_attention", "attention"))


# -- counts --------------------------------------------------------------------------


def test_program_facts_count_both_kinds_and_their_pairs():
    cfg = llama.LlamaConfig.tiny(
        n_layer=8, sliding_window=1024, max_seq_len=16384,
        layer_types=(("window_attention",) * 3 + ("attention",)) * 2)
    facts = llama.program_facts(cfg, 16384)
    assert facts == {
        "window_attention_layers": 6, "attention_layers": 8,
        "attn_full_pairs_per_sequence": 134_225_920,
        "attn_window_pairs_per_sequence": 16_253_440}
    assert llama.program_facts(llama.LlamaConfig.tiny(sliding_window=16),
                               64) == {}
    assert (llama.attended_pairs(16384, 0), llama.attended_pairs(
        16384, 1024), llama.attended_pairs(8, 16)) == (
            134_225_920, 16_253_440, 36)


def test_flops_per_token_charges_a_window_layer_its_window():
    base = dict(n_layer=4, sliding_window=16, max_seq_len=64)
    full = llama.LlamaConfig.tiny(**base)
    mixed = llama.LlamaConfig.tiny(
        **base, layer_types=("window_attention",) * 3 + ("attention",))
    h_d = full.n_head * full.head_dim
    # three layers meet 16 keys a query where they met 64
    assert llama.flops_per_token(full) - llama.flops_per_token(mixed) == (
        6.0 * 2 * 3 * (64 - 16) * h_d)
    # one global window counts the whole sequence, as it always has
    assert llama.flops_per_token(full) == llama.flops_per_token(
        llama.LlamaConfig.tiny(n_layer=4, max_seq_len=64))


# -- refusals, by name ---------------------------------------------------------------

_MIX = dict(n_layer=2, sliding_window=16,
            layer_types=("window_attention", "attention"))


@pytest.mark.parametrize("over,match", [
    (dict(_MIX, sliding_window=0),
     "'window_attention' layers with sliding_window=0"),
    (dict(_MIX, n_kv_head=4, kv_lora_rank=16, q_lora_rank=16,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16),
     "'window_attention' layers with sliding_window=16 or kv_lora_rank=16"),
    (dict(rotary_by_kind={"window_attention": llama.Rotary(1e4)}),
     "rotary_by_kind names 'window_attention'"),
    (dict(_MIX, layer_types=("window_attention",) * 2,
          rotary_by_kind={"attention": llama.Rotary(1e4)}),
     "rotary_by_kind names 'attention'"),
    (dict(layer_types=("mamba", "attention"), mamba_n_heads=2,
          mamba_d_head=8, mamba_d_state=8,
          rotary_by_kind={"mamba": llama.Rotary(1e4)}),
     "rotary_by_kind names 'mamba'"),
    (dict(rope=False, rotary_by_kind={"attention": llama.Rotary(1e4)}),
     "rope=False"),
    (dict(rotary_by_kind={"attention": llama.Rotary(1e4, factor=4.0)}),
     "original_max_position_embeddings > 0"),
    (dict(rotary_by_kind={"attention": llama.Rotary(1e4, factor=0.5)}),
     "factor >= 1"),
    (dict(rotary_by_kind={"attention": 1e4}), "is no Rotary"),
], ids=["no_window", "latent", "kind_without_a_layer", "full_without_a_layer",
        "no_attention_kind", "nope", "yarn_without_its_length",
        "factor_under_one", "not_a_rotary"])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        llama.LlamaConfig.tiny(**over)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_a_window_layer_refuses_ring_and_ulysses_by_name(impl):
    cfg = llama.LlamaConfig.tiny(**_MIX)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError,
                       match=f"'window_attention' layer's window of 16.*"
                             f"not '{impl}'"):
        llama.forward_hidden(params, _tokens()[:, :S] % 256, cfg,
                             attn_impl=impl)


#: each new setting alone, on a config every other row of the table lets by
NEW_SETTINGS = {
    "layer_types": (
        dict(_MIX), "layer_types with a 'window_attention' entry (1 of 2 "
        "layers)", "not attention over every earlier position"),
    "rotary_by_kind": (
        dict(rotary_by_kind={"attention": llama.Rotary(1e4, 4.0, 32)}),
        "rotary_by_kind=(('attention', Rotary(",
        "a rotary table of a kind of layer's own"),
}


@pytest.mark.parametrize("setting", sorted(NEW_SETTINGS))
@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_the_refusal_names_the_setting_and_the_path(where, path, setting):
    over, said, what = NEW_SETTINGS[setting]
    cfg = llama.LlamaConfig.tiny(**over)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    assert said in str(e.value) and what in str(e.value)
    assert path in str(e.value) and "training path only" in str(e.value)


def test_the_table_of_refusals_gained_the_rotary_row():
    names = [row[0] for row in llama.TRAINING_PATH_ONLY]
    assert names[20] == "rotary_by_kind"
    # and, since, five rows for what crosses layers, differential
    # attention, the norm's form and the attention biases
    assert names[21:] == ["memory_layer", "shared_kv_layer",
                          "diff_attention", "norm_form", "attn_bias"]
    assert len(names) == len(set(names)) == 26
    for name, computed, _ in llama.TRAINING_PATH_ONLY:
        if name != "layer_types":
            assert getattr(llama.LlamaConfig(), name) == computed, name


def test_a_dict_of_tables_is_kept_as_sorted_pairs():
    """Hashable (the config is a static argument of jitted functions) and
    the same whichever way it was written."""
    a = llama.LlamaConfig.tiny(**_MIX, rotary_by_kind={
        "window_attention": llama.Rotary(1e4),
        "attention": llama.Rotary(1e4, 4.0, 32)})
    b = llama.LlamaConfig.tiny(**_MIX, rotary_by_kind=(
        ("attention", llama.Rotary(1e4, 4.0, 32)),
        ("window_attention", llama.Rotary(1e4))))
    assert a == b and hash(a) == hash(b)
    assert dict(a.rotary_by_kind)["attention"].factor == 4.0
    assert llama.LlamaConfig.tiny().rotary_by_kind == ()
