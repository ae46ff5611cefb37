"""Window layers that rotate beside full layers WITHOUT position
(``rotary_by_kind``'s ``None``), a sigmoid gate per element on the attention
output and per-head q/k norms under sandwich norms (``branch_norm``), a
leading dense layer and then a share of sigmoid-routed experts with a
selection bias and a shared expert, the embedding times ``sqrt(d_model)``:
the program (``models/llama.py`` through the benchmark's adapter) against the
plain reference ``benchmark/reference/afmoe_ref.py`` on seeded weights at the
rehearsal size, and each piece alone."""

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

from benchmark.adapters import afmoe as ADAPTER  # noqa: E402
from benchmark.reference import afmoe_ref as REF  # noqa: E402
from dlrover_tpu.models import llama  # noqa: E402

S = 96
SLIDING, FULL = "sliding_attention", "full_attention"


def _hf(**over) -> dict:
    """The rehearsal configuration (a dense window layer, three routed
    window layers, a routed full layer)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "trinity-mini-rehearsal.json")) as f:
        return dict(json.load(f), **over)


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 4096, (batch, S + 1)), jnp.int32)


def _moved(params, by=0.3):
    """Selection biases off zero (by alternating signs) and output gains off
    one: a bias that leaked into a weight, or a gain not applied, shows."""
    def layer_of(layer):
        gain = 1.0 + 0.3 * jnp.cos(jnp.arange(
            layer["ln1_out"].shape[0], dtype=jnp.float32))
        layer = dict(layer, ln1_out=gain, ln2_out=gain[::-1])
        if "moe" in layer:
            bias = layer["moe"]["router_bias"]
            layer["moe"] = dict(layer["moe"], router_bias=by * jnp.cos(
                jnp.pi * jnp.arange(bias.shape[0], dtype=jnp.float32)))
        return layer

    return dict(params, layers=[layer_of(x) for x in params["layers"]])


@pytest.fixture(scope="module", params=[True, False],
                ids=["remat", "no_remat"])
def compared(request):
    """System (float32 compute, so that the comparison is tight) and
    reference, forward and every leaf's gradient, on one seeded tree."""
    cfg = _hf()
    mc = dataclasses.replace(
        ADAPTER.model_config(cfg, remat_block=request.param, seq_len=S),
        dtype=jnp.float32)
    params = _moved(llama.init_params(jax.random.PRNGKey(7), mc))
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
    return dict(cfg=cfg, mc=mc, params=params, tokens=tokens, loss=loss,
                hidden=hidden, extra=extra, grads=grads, loss_r=loss_r,
                hidden_r=hidden_r, extra_r=extra_r, grads_r=grads_r)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def test_the_adapter_builds_the_combination(compared):
    mc = compared["mc"]
    assert mc.layer_types == ("window_attention",) * 4 + ("attention",)
    assert dict(mc.rotary_by_kind) == {
        "window_attention": llama.Rotary(theta=10000.0), "attention": None}
    assert (mc.unrotated("attention"), mc.unrotated("window_attention"),
            mc.unrotated_layers) == (True, False, 1)
    assert (mc.branch_norm, mc.attn_output_gate, mc.attn_head_dim,
            mc.qk_norm, mc.qk_norm_per_head) == (True, True, 32, True, True)
    assert mc.embedding_multiplier == 8.0  # sqrt(64)
    assert (mc.num_experts, mc.experts_held, mc.top_k, mc.first_k_dense,
            mc.n_shared_experts, mc.router_score, mc.routed_scaling,
            mc.router_bias_rate, mc.norm_topk_prob) == (
                16, 4, 3, 1, 1, "sigmoid", 2.826, 0.001, True)
    assert [mc.is_moe_layer(i) for i in range(5)] == [False] + [True] * 4


def test_hidden_states_agree_with_the_reference(compared):
    assert _rel(compared["hidden"], compared["hidden_r"]) < 2e-4


def test_the_loss_is_the_cross_entropy_alone_and_agrees(compared):
    assert abs(float(compared["loss"] - compared["loss_r"])) < 2e-5 * float(
        compared["loss_r"])
    want, _ = llama.loss_fn(compared["params"], {"tokens": compared["tokens"]},
                            compared["mc"], moe_aux_weight=0.0, metrics=True)
    assert float(compared["loss"]) == pytest.approx(float(want), rel=1e-6)


def test_the_window_read_alone_is_exact_on_both_sides(compared):
    for side in ("extra", "extra_r"):
        scalars = compared[side]["scalars"]
        assert float(scalars["window_alone_least"]) == 2.0
        assert float(scalars["window_alone_most"]) == 1.0


def test_the_experts_taken_are_the_references_own(compared):
    assert sorted(compared["extra"]["choices"]) == [
        REF.experts_name(i) for i in range(1, 5)]
    for name, chosen in compared["extra"]["choices"].items():
        own = compared["extra_r"]["choices"][name]
        assert np.array_equal(np.sort(np.asarray(chosen), -1),
                              np.sort(np.asarray(own), -1)), name


def test_every_leafs_gradient_agrees_with_the_reference(compared):
    flat, _ = jax.tree_util.tree_flatten_with_path(compared["grads"])
    flat_r = jax.tree_util.tree_leaves(compared["grads_r"])
    assert len(flat) == len(flat_r) > 60
    for (path, g), g_r in zip(flat, flat_r):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # chooses, takes no gradient
            assert not np.asarray(g).any() and not np.asarray(g_r).any()
            continue
        assert float(jnp.linalg.norm(g_r.ravel())) > 0, name
        assert _rel(g, g_r) < 2e-3, name


# -- rotation by kind ----------------------------------------------------------


def _one_layer(kind, **over):
    cfg = llama.LlamaConfig.tiny(
        n_layer=1, max_seq_len=S, dtype=jnp.float32, layer_types=(kind,),
        sliding_window=16 if kind == "window_attention" else 0, **over)
    return cfg, llama.init_params(jax.random.PRNGKey(1), cfg)


@pytest.mark.parametrize("kind,by_kind,moves", [
    ("attention", {"attention": None}, False),
    ("window_attention", {"window_attention": None}, False),
    ("attention", {}, True),
    ("window_attention", {}, True),
    ("window_attention", {"window_attention": llama.Rotary(1e4)}, True),
], ids=["full_unrotated", "window_unrotated", "full_rotated",
        "window_rotated", "window_on_its_table"])
def test_a_layer_without_position_is_blind_to_the_positions(
        kind, by_kind, moves):
    """A block's output under positions 0, 1, 2.. and 0, 3, 6..: the same to
    the bit where the kind carries no rotary position, another where it
    rotates (a shift alone would move neither: rotation is relative)."""
    cfg, params = _one_layer(kind, rotary_by_kind=by_kind)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, 64), jnp.float32)

    def out(stretch):
        positions = jnp.broadcast_to(jnp.arange(S), (2, S)) * stretch
        table = {k: llama._rotary_table(positions, r, cfg.rotary_dim)
                 for k, r in cfg.rotary_by_kind if r is not None}
        return llama.block_apply(
            params["layers"][0], x, cfg, positions, attn_kind=kind,
            rotary=table.get(kind))[0]

    a, b = np.asarray(out(1)), np.asarray(out(3))
    assert np.array_equal(a, b) != moves
    if moves:
        assert _rel(jnp.asarray(a), jnp.asarray(b)) > 1e-5


def _mixed(**over):
    return llama.LlamaConfig.tiny(**dict(dict(
        n_layer=4, sliding_window=16, max_seq_len=S, dtype=jnp.float32,
        layer_types=("window_attention",) * 3 + ("attention",)), **over))


def test_no_table_is_built_for_a_kind_without_position():
    """Three window layers on a table of their own and one full layer
    without: ONE cosine and one sine in the traced step; with the window
    kind unnamed its three layers build theirs, q and k each."""
    def trig(cfg):
        shapes = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        text = str(jax.make_jaxpr(lambda p: llama.forward_hidden(
            p, _tokens()[:, :S] % 256, cfg)[0])(shapes))
        return text.count(" cos "), text.count(" sin ")

    assert trig(_mixed(rotary_by_kind={
        "attention": None, "window_attention": llama.Rotary(1e4)})) == (1, 1)
    assert trig(_mixed(rotary_by_kind={"attention": None})) == (6, 6)
    assert trig(_mixed(rotary_by_kind={
        "attention": None, "window_attention": None})) == (0, 0)


def test_the_two_ways_to_say_it_compute_the_same():
    """The window kind on a plain table of its own at ``rope_theta`` is the
    window kind unnamed; every kind ``None`` is ``rope`` False."""
    tokens = _tokens(4)[:, :S] % 256
    params = llama.init_params(jax.random.PRNGKey(3), _mixed())

    def run(**over):
        return np.asarray(llama.forward_hidden(params, tokens,
                                               _mixed(**over))[0])

    named = run(rotary_by_kind={"attention": None,
                                "window_attention": llama.Rotary(1e4)})
    assert np.array_equal(named, run(rotary_by_kind={"attention": None}))
    assert not np.array_equal(named, run())
    assert np.array_equal(
        run(rotary_by_kind={"attention": None, "window_attention": None}),
        run(rope=False))


# -- the gate and the sandwich norms beside experts -------------------------------


def test_the_output_norm_sits_on_the_routed_blocks_partial_sum(compared):
    """``ln2_out`` of a routed layer norms what ``_moe_swiglu`` returns —
    the shared expert and the held experts' pairs — and nothing else."""
    mc, params = compared["mc"], compared["params"]
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (2, S))
    y, stats = llama.block_apply(layer, x, mc, positions,
                                 attn_kind="window_attention")
    a = llama._attention(
        llama.rmsnorm(x, layer["ln1"], eps=mc.rms_eps), layer, mc, positions,
        "auto", None, None, "window_attention")
    h = x + llama.rmsnorm(a, layer["ln1_out"], eps=mc.rms_eps)
    partial, _ = llama._moe_swiglu(
        llama.rmsnorm(h, layer["ln2"], eps=mc.rms_eps), layer["moe"], mc)
    want = h + llama.rmsnorm(partial, layer["ln2_out"], eps=mc.rms_eps)
    assert _rel(y, want) < 1e-6
    assert int(stats["held_pairs"]) < 2 * S * 3  # a share, not every pick


def test_the_two_shares_of_a_routed_block_add_up_to_the_uncut_block():
    """16 experts in two shares of 8, top-3 of a 16-wide sigmoid router with
    a moved bias: the routed parts taken BEFORE ``ln2_out``, with the shared
    expert (computed alike on both chips) counted once, add up to the uncut
    reference's routed block; one share alone does not."""
    whole = _hf(num_experts=16)
    uncut = dataclasses.replace(
        ADAPTER.model_config(whole, remat_block=False, seq_len=S),
        dtype=jnp.float32)
    assert uncut.experts_held == 0
    moe = _moved(llama.init_params(
        jax.random.PRNGKey(5), uncut))["layers"][2]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, S, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, own, _ = REF._routed(u, moe, whole, None, None)
        shared = REF._swiglu(u, *(moe["shared"][k] for k in (
            "w_gate", "w_up", "w_down")))

    def share(first):
        cfg = dataclasses.replace(uncut, experts_held=8,
                                  experts_held_first=first)
        held = dict(moe, **{k: moe[k][first:first + 8]
                            for k in ("wg", "wi", "wo")})
        out, stats = llama._moe_swiglu(u, held, cfg)
        assert np.array_equal(np.sort(np.asarray(stats["experts"]), -1),
                              np.sort(np.asarray(own), -1))
        return out

    parts = [share(0), share(8)]
    assert _rel(parts[0] + parts[1] - shared, want) < 2e-5
    assert _rel(parts[0], want) > 1e-2


def test_the_scopes_nest_inside_the_blocks_outermost(compared):
    """``attn_gate`` under ``attention``; ``branch_norm`` under
    ``attention``, ``mlp`` (the dense layer) and ``moe_combine`` — so no
    reader of an outermost scope sees either."""
    import importlib

    acc = importlib.import_module("dlrover_tpu.parallel.accelerate")
    mc, params = compared["mc"], compared["params"]
    compiled = jax.jit(jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": compared["tokens"]}, mc, moe_aux_weight=0.0,
        metrics=True)[0])).lower(params).compile()
    outer, inner = acc.scope_tables(compiled.as_text())
    above = {}
    for name, scope in inner.items():
        above.setdefault(scope, set()).add(outer[name][1])
    assert above["attn_gate"] == {"attention"}
    assert above["branch_norm"] == {"attention", "mlp", "moe_combine"}
    phases = {outer[name][0] for name, scope in inner.items()
              if scope == "branch_norm"}
    assert {"forward", "backward"} <= phases


def test_the_tree_and_its_axes_hold_the_same_leaves(compared):
    mc, params = compared["mc"], compared["params"]
    axes = llama.param_logical_axes(mc)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(axes, is_leaf=is_axes))
    routed = params["layers"][1]
    assert routed["wq"].shape == (64, 4 * 2 * 32)  # [q | gate] a head
    assert routed["moe"]["wg"].shape == (4, 64, 32)
    assert routed["moe"]["router"].shape == (64, 16)
    assert "mlp" in params["layers"][0] and "moe" not in params["layers"][0]
    fresh = llama.init_params(jax.random.PRNGKey(0), mc)
    assert not np.asarray(fresh["layers"][1]["moe"]["router_bias"]).any()
    assert np.array_equal(np.asarray(fresh["layers"][1]["ln2_out"]),
                          np.ones(64, np.float32))
    assert llama.rule_leaves(mc) == tuple(
        f"['layers'][{i}]['moe']['router_bias']" for i in range(1, 5))


# -- counts ------------------------------------------------------------------------


def test_program_facts_count_the_layers_without_position():
    cfg = _mixed(rotary_by_kind={"attention": None}, max_seq_len=16384,
                 sliding_window=2048)
    assert llama.program_facts(cfg, 16384) == {
        "window_attention_layers": 3, "attention_layers": 4,
        "attn_full_pairs_per_sequence": 134_225_920,
        "attn_window_pairs_per_sequence": 31_458_304,
        "unrotated_attention_layers": 1}
    # a model none of whose layers rotates says so by ``rope`` and journals
    # what it journalled; one whose layers all rotate journals no count
    assert "unrotated_attention_layers" not in llama.program_facts(
        _mixed(rope=False), 64)
    assert "unrotated_attention_layers" not in llama.program_facts(
        _mixed(), 64)


def test_flops_per_token_count_the_gates_columns_and_no_rotation():
    base = llama.LlamaConfig.tiny(attn_head_dim=32)
    gated = llama.LlamaConfig.tiny(attn_head_dim=32, attn_output_gate=True)
    # ``wq`` is twice as wide: 64 x (4 x 32) more parameters a layer
    assert llama.flops_per_token(gated) - llama.flops_per_token(base) == (
        6.0 * 2 * 64 * 4 * 32)
    assert llama.flops_per_token(_mixed()) == llama.flops_per_token(
        _mixed(rotary_by_kind={"attention": None}))


# -- what combines now, and the refusals that stay, by name -----------------------


@pytest.mark.parametrize("over", [
    dict(attn_output_gate=True), dict(attn_head_dim=32),
    dict(attn_output_gate=True, attn_head_dim=32, qk_norm=True,
         qk_norm_per_head=True, num_experts=4, moe_every=1, experts_held=2,
         remat_block=True),
    dict(loop_passes=2, exit_gate_beta=0.1),
], ids=["gate", "head_size", "everything_beside_experts", "looped"])
def test_sandwich_norms_combine_with(over):
    cfg = llama.LlamaConfig.tiny(branch_norm=True, **over)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    loss = llama.loss_fn(params, {"tokens": _tokens()[:, :65] % 256}, cfg)
    assert np.isfinite(float(loss))


_LATENT = dict(n_kv_head=4, kv_lora_rank=16, q_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16)


@pytest.mark.parametrize("over,match", [
    (dict(_LATENT, attn_output_gate=True),
     "attn_output_gate=True with kv_lora_rank=16"),
    (dict(attn_output_gate=True, mtp_layers=1), "mtp_layers=1"),
    (dict(attn_output_gate=True, loop_passes=2, exit_gate_beta=0.1),
     "loop_passes=2"),
    (dict(_LATENT, attn_head_dim=32), "attn_head_dim=32 with kv_lora_rank=16"),
    (dict(attn_head_dim=32, mtp_layers=1), "mtp_layers=1"),
    (dict(attn_head_dim=32, loop_passes=2, exit_gate_beta=0.1),
     "a stack that runs once"),
    (dict(partial_rotary_factor=0.5, branch_norm=True),
     "partial_rotary_factor=0.5 with .* or branch_norm=True"),
    (dict(norm_plus_one=True, branch_norm=True),
     "norm_plus_one=True with .*output norms' gains are stored plain"),
    (dict(rope=False, rotary_by_kind={"attention": None}), "rope=False"),
    (dict(_LATENT, rotary_by_kind={"attention": None}), "kv_lora_rank=16"),
    (dict(rotary_by_kind={"window_attention": None}),
     "rotary_by_kind names 'window_attention'"),
    (dict(rotary_by_kind={"attention": False}), "nor None"),
    (dict(one_branch=True, layer_types=("attention", "mlp"),
          branch_norm=True), "branch_norm=True"),
], ids=["gate_latent", "gate_mtp", "gate_looped", "head_size_latent",
        "head_size_mtp", "head_size_looped", "part_rotation_sandwich",
        "plus_one_sandwich", "none_under_nope", "none_latent",
        "none_of_a_kind_without_a_layer", "false_is_not_none",
        "one_branch_sandwich"])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        llama.LlamaConfig.tiny(**over)


@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_every_other_path_refuses_a_kind_without_position(where, path):
    """``llama_infer``, ``llama_pp`` and ``hf_convert`` compute rotary
    position on every layer: the per-kind setting is refused through
    ``TRAINING_PATH_ONLY``'s row, with no edit of their own."""
    cfg = llama.LlamaConfig.tiny(rotary_by_kind={"attention": None})
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    said = str(e.value)
    assert "rotary_by_kind=(('attention', None),)" in said
    assert "a kind without position" in said
    assert path in said and "training path only" in said
