"""Gated short-convolution layers beside attention on the normal path (the
LFM2-8B-A1B block): the convolution mixer against a loop over positions,
forward and every gradient; the q/k norm of each head's own dims; a sigmoid
router normalised over ``sum + router_norm_eps``; the mixer kind and the MLP
kind of a layer chosen apart, so that a convolution layer may be routed; a
chip's SHARE of the experts adding up to the whole layer — each against a
plain formula written out here, in float32 on seeded weights.

With the defaults nothing of it may show: ``tests/test_llama_mla_moe.py``
holds a dense, a routed and a looped config to the loss and gradients an
earlier commit gave, bit for bit, and runs here unchanged.  Every path that
cannot compute a convolution layer refuses it by name.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import REFUSING_PATH_NAMES, refusing_calls

from dlrover_tpu.models import llama, llama_infer
from dlrover_tpu.parallel.mesh import MeshSpec

acc = importlib.import_module("dlrover_tpu.parallel.accelerate")

F32 = jnp.float32
B, S, D = 2, 16, 32


def _lfm(**over):
    """A dense convolution layer, a routed attention layer, a routed
    convolution layer: 8 experts top-2 behind a sigmoid router with a
    selection bias, per-head q/k norm, a tied head."""
    base = dict(
        vocab_size=512, n_layer=3, n_head=4, n_kv_head=2, d_model=D,
        d_ff=64, max_seq_len=64, dtype=F32,
        layer_types=("conv", "attention", "conv"), qk_norm=True,
        qk_norm_per_head=True, num_experts=8, top_k=2, moe_every=1,
        first_k_dense=1, d_ff_expert=16, router_score="sigmoid",
        router_norm_eps=1e-6, router_bias_rate=1e-3,
        tie_word_embeddings=True)
    base.update(over)
    return llama.LlamaConfig(**base)


def _tokens(seed=0, vocab=512, s=S, b=B):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (b, s + 1)).astype(np.int32))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _decisive(params, seed=7):
    """Gains off 1, biases off 0 and a router 40 times larger: at
    initialisation every sigmoid score is 0.5 and no gain shows."""
    key = jax.random.PRNGKey(seed)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, hash(name) % (2 ** 31))
        if name.endswith("['router']"):
            return 40.0 * a
        if name.endswith("['router_bias']"):
            return 0.05 * jnp.cos(jnp.arange(a.shape[0], dtype=F32))
        if a.ndim == 1:
            return a + 0.3 * jax.random.normal(k, a.shape)
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


# -- the plain formulas -------------------------------------------------------


def _rms(x, w, eps=1e-5):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv_by_position(u, conv):
    """The mixer one position at a time: ``[B | C | X] = u in_proj``, ``c_t
    = sum_k w_k (B X)_{t-(K-1)+k}`` with nothing before the sequence, ``(C
    c) out_proj``."""
    d = u.shape[-1]
    taps = conv["conv_w"].shape[0]
    bcx = jnp.einsum("bsd,de->bse", u, conv["in_proj"], precision="highest")
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = gate_b * x
    rows = []
    for t in range(u.shape[1]):
        c = jnp.zeros_like(z[:, 0])
        for k in range(taps):
            src = t - (taps - 1) + k
            if src >= 0:
                c = c + conv["conv_w"][k] * z[:, src]
        rows.append(gate_c[:, t] * c)
    return jnp.einsum("bsd,de->bse", jnp.stack(rows, 1), conv["out_proj"],
                      precision="highest")


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_by_head(u, layer, cfg, per_head=True):
    """GQA with the q/k norm head by head (or over the whole width, the
    gain tiled), then RoPE, causal softmax at 1/sqrt(head_dim)."""
    b, s, _ = u.shape
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q, k, v = u @ layer["wq"], u @ layer["wk"], u @ layer["wv"]
    if per_head:
        q = jnp.stack([_rms(q[..., i * hd:(i + 1) * hd], layer["q_norm"])
                       for i in range(h)], 2)
        k = jnp.stack([_rms(k[..., i * hd:(i + 1) * hd], layer["k_norm"])
                       for i in range(kv)], 2)
    else:
        q = _rms(q, jnp.tile(layer["q_norm"], h)).reshape(b, s, h, hd)
        k = _rms(k, jnp.tile(layer["k_norm"], kv)).reshape(b, s, kv, hd)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, h // kv, 2)
    v = jnp.repeat(v.reshape(b, s, kv, hd), h // kv, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                                 -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, h * hd) @ layer[
        "wo"]


def _routed_whole(y, moe, cfg, experts=None):
    """The routed block over ``experts`` (default: all of them), every
    expert over every token with the weight 0 where it was not chosen."""
    s = jax.nn.sigmoid(y @ moe["router"])
    _, chosen = jax.lax.top_k(s + moe["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + cfg.router_norm_eps)
    combine = jnp.sum(w[..., None] * jax.nn.one_hot(
        chosen, cfg.num_experts, dtype=F32), -2)
    out = jnp.zeros_like(y)
    for e in (range(cfg.num_experts) if experts is None else experts):
        hidden = jax.nn.silu(y @ moe["wg"][e]) * (y @ moe["wi"][e])
        out = out + combine[..., e, None] * (hidden @ moe["wo"][e])
    return out


def _plain_loss(params, toks, cfg):
    """The whole model by the equations, float32."""
    inp, tgt = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for layer, kind in zip(params["layers"], cfg.layer_types):
            u = _rms(x, layer["ln1"])
            x = x + (_conv_by_position(u, layer["conv"]) if kind == "conv"
                     else _attention_by_head(u, layer, cfg))
            y = _rms(x, layer["ln2"])
            if "moe" in layer:
                x = x + _routed_whole(y, layer["moe"], cfg)
            else:
                mlp = layer["mlp"]
                x = x + (jax.nn.silu(y @ mlp["w_gate"])
                         * (y @ mlp["w_up"])) @ mlp["w_down"]
        logp = jax.nn.log_softmax(
            _rms(x, params["ln_f"]) @ params["embed"].T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))


# -- the mixer ----------------------------------------------------------------


def _conv_leaves(seed=0, taps=3):
    cfg = _lfm(conv_taps=taps)
    conv = llama._init_conv(jax.random.PRNGKey(seed), cfg)
    # N(0, 0.02) projections give outputs of 1e-4: 25 times larger the
    # three factors are of order one and every term of a gradient shows
    conv = dict(conv, in_proj=25.0 * conv["in_proj"],
                out_proj=25.0 * conv["out_proj"])
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, D))
    return cfg, conv, u


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_mixer_equals_the_loop_over_positions(taps):
    cfg, conv, u = _conv_leaves(taps=taps)
    assert conv["conv_w"].shape == (taps, D)
    got = llama._conv_mixer(u, conv, cfg)
    want = _conv_by_position(u, conv)
    assert got.shape == (B, S, D) and _rel(got, want) < 1e-5


@pytest.mark.parametrize("leaf", ["u", "in_proj", "conv_w", "out_proj"])
def test_the_mixer_has_the_loops_gradients(leaf):
    cfg, conv, u = _conv_leaves()
    probe = jax.random.normal(jax.random.PRNGKey(9), (B, S, D))

    def through(fn):
        def scalar(u, conv):
            return jnp.sum(fn(u, conv) * probe)
        du, dconv = jax.grad(scalar, argnums=(0, 1))(u, conv)
        return dict(dconv, u=du)

    got = through(lambda u, conv: llama._conv_mixer(u, conv, cfg))
    want = through(_conv_by_position)
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 1e-5


def test_the_mixer_is_causal():
    """Position t's output does not move when position t + 1 does, and the
    first position reads nothing before the sequence."""
    cfg, conv, u = _conv_leaves()
    t = 10
    moved = u.at[:, t + 1].add(3.0)
    a, b = (llama._conv_mixer(x, conv, cfg) for x in (u, moved))
    np.testing.assert_array_equal(np.asarray(a[:, :t + 1]),
                                  np.asarray(b[:, :t + 1]))
    assert float(jnp.max(jnp.abs(a[:, t + 1] - b[:, t + 1]))) > 1e-3
    # two taps back and no further
    assert float(jnp.max(jnp.abs(a[:, t + 3] - b[:, t + 3]))) > 1e-3
    np.testing.assert_array_equal(np.asarray(a[:, t + 4:]),
                                  np.asarray(b[:, t + 4:]))


def test_the_gates_are_not_interchangeable():
    """``B * X`` commutes, ``C`` does not: with the last two thirds of
    ``in_proj`` exchanged the mixer computes something else."""
    cfg, conv, u = _conv_leaves()
    w = conv["in_proj"]
    b_x = dict(conv, in_proj=jnp.concatenate(
        [w[:, 2 * D:], w[:, D:2 * D], w[:, :D]], 1))
    c_x = dict(conv, in_proj=jnp.concatenate(
        [w[:, :D], w[:, 2 * D:], w[:, D:2 * D]], 1))
    out = llama._conv_mixer(u, conv, cfg)
    assert _rel(llama._conv_mixer(u, b_x, cfg), out) < 1e-6
    assert _rel(llama._conv_mixer(u, c_x, cfg), out) > 0.5


def test_bf16_streams_gate_in_float32_and_round_once():
    cfg, conv, u = _conv_leaves()
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    got = llama._conv_mixer(u.astype(jnp.bfloat16), conv, low)
    assert got.dtype == jnp.bfloat16
    assert _rel(got.astype(F32), _conv_by_position(u, conv)) < 2e-2


# -- the q/k norm of each head ------------------------------------------------


def _attention_layer(cfg, seed=0):
    layer = llama._init_layer(jax.random.PRNGKey(seed), cfg, False)
    gains = jax.random.PRNGKey(seed + 5)
    return dict(
        layer, wq=30.0 * layer["wq"], wk=30.0 * layer["wk"],
        q_norm=1.0 + 0.3 * jax.random.normal(gains, layer["q_norm"].shape),
        k_norm=1.0 + 0.3 * jnp.cos(jnp.arange(layer["k_norm"].shape[0],
                                               dtype=F32)))


def test_the_head_gains_are_one_head_wide():
    cfg = _lfm()
    layer = llama._init_layer(jax.random.PRNGKey(0), cfg, False)
    assert layer["q_norm"].shape == layer["k_norm"].shape == (cfg.head_dim,)
    whole = dataclasses.replace(cfg, qk_norm_per_head=False)
    layer = llama._init_layer(jax.random.PRNGKey(0), whole, False)
    assert layer["q_norm"].shape == (cfg.n_head * cfg.head_dim,)
    assert layer["k_norm"].shape == (cfg.n_kv_head * cfg.head_dim,)


@pytest.mark.parametrize("per_head", [True, False],
                         ids=["per head", "whole width"])
def test_attention_norms_q_and_k_as_the_setting_says(per_head):
    cfg = _lfm()
    layer = _attention_layer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = llama._attention(u, layer, cfg, positions, "auto", None)
    want = _attention_by_head(u, layer, cfg, per_head=per_head)
    if per_head:
        assert _rel(got, want) < 1e-5
    else:  # the other form of the norm is another function
        assert _rel(got, want) > 0.05


def test_the_head_norm_is_each_heads_own():
    """Scaling ONE head's slice of q leaves every head's normed q as it
    was (the whole-width form would shrink the others)."""
    cfg = _lfm()
    layer = _attention_layer(cfg)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, S, 4 * 8))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2 * 8))
    normed, _ = llama.qk_normed(q, k, layer, cfg)
    louder, _ = llama.qk_normed(q.at[..., :8].multiply(50.0), k, layer, cfg)
    np.testing.assert_allclose(louder, normed, atol=1e-4)
    whole = dataclasses.replace(cfg, qk_norm_per_head=False)
    tiled = dict(layer, q_norm=jnp.tile(layer["q_norm"], 4),
                 k_norm=jnp.tile(layer["k_norm"], 2))
    a, _ = llama.qk_normed(q, k, tiled, whole)
    b, _ = llama.qk_normed(q.at[..., :8].multiply(50.0), k, tiled, whole)
    assert _rel(b[..., 8:], a[..., 8:]) > 0.5


def test_the_kv_cache_decoder_applies_the_head_norms():
    cfg = llama.LlamaConfig.tiny(
        vocab_size=250, n_layer=2, dtype=F32, qk_norm=True,
        qk_norm_per_head=True)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 250, (2, 9)))
    logits, _ = llama.forward(params, toks, cfg)
    cache = llama_infer.init_cache(cfg, 2, 16)
    cached, cache = llama_infer.forward_step(params, toks[:, :6], cfg, cache)
    np.testing.assert_allclose(cached, logits[:, :6], atol=2e-4)
    for t in range(6, 9):  # one token at a time through the cache
        step, cache = llama_infer.forward_step(
            params, toks[:, t:t + 1], cfg, cache)
        np.testing.assert_allclose(step[:, 0], logits[:, t], atol=2e-4)


# -- the router's constant and the share --------------------------------------


def _routed_layer(cfg, seed=0):
    layer = llama._init_layer(jax.random.PRNGKey(seed), cfg, True,
                              mixer="conv")
    return _decisive(layer)["moe"]


@pytest.mark.parametrize("eps", [1e-20, 1e-6, 0.5])
def test_the_chosen_scores_are_divided_by_their_sum_plus_the_constant(eps):
    cfg = _lfm(router_norm_eps=eps)
    moe = _routed_layer(cfg)
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, stats = llama._moe_swiglu(y, moe, cfg)
    assert _rel(got, _routed_whole(y, moe, cfg)) < 1e-5
    assert stats["tokens_per_expert"].sum() == B * S * cfg.top_k
    if eps == 0.5:  # a constant that large shows
        other = _routed_whole(y, moe, dataclasses.replace(
            cfg, router_norm_eps=1e-6))
        assert _rel(got, other) > 0.1


def test_the_default_constant_is_the_one_the_tree_had():
    assert llama.LlamaConfig().router_norm_eps == 1e-20


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_share_computes_its_own_experts_part(first):
    cfg = _lfm(experts_held=2, experts_held_first=first)
    moe = _routed_layer(_lfm())
    held = dict(moe, **{k: moe[k][first:first + 2]
                        for k in ("wg", "wi", "wo")})
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, stats = llama._moe_swiglu(y, held, cfg)
    want = _routed_whole(y, moe, cfg, experts=range(first, first + 2))
    assert _rel(got, want) < 1e-5
    per_expert = np.asarray(stats["tokens_per_expert"])
    assert int(stats["held_pairs"]) == per_expert[first:first + 2].sum()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the parts that the four chips compute
    add up to the whole layer of the uncut formula (no shared expert to
    count once)."""
    whole = _lfm()
    moe = _routed_layer(whole)
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    total = jnp.zeros_like(y)
    for first in (0, 2, 4, 6):
        cfg = _lfm(experts_held=2, experts_held_first=first)
        held = dict(moe, **{k: moe[k][first:first + 2]
                            for k in ("wg", "wi", "wo")})
        total = total + llama._moe_swiglu(y, held, cfg)[0]
    assert _rel(total, _routed_whole(y, moe, whole)) < 1e-5
    assert _rel(total, llama._moe_swiglu(y, moe, whole)[0]) < 1e-5


# -- the model ----------------------------------------------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("fused", [False, True], ids=["logits", "fused"])
def test_the_loss_and_gradients_match_the_equations(remat, fused):
    cfg = _lfm(remat_block=remat)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = _tokens()
    (loss, counters), grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": toks}, cfg, moe_aux_weight=0.0,
                                fused_lm_head=fused, metrics=True),
        has_aux=True)(params)
    want, want_grads = jax.value_and_grad(_plain_loss)(params, toks, cfg)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    compared = 0
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert float(jnp.max(jnp.abs(g))) == 0.0  # a rule moves it
            continue
        assert _rel(g, wanted[path]) < 2e-4, name
        compared += 1
    assert compared == 31  # every leaf but the two selection biases
    # a convolution layer's routed MLP reports like any other
    assert counters["moe_tokens_per_expert"].shape == (2, 8)
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(1).tolist() == [
        B * S * 2] * 2
    assert sorted(counters[llama.RULE_UPDATES]) == sorted(
        llama.rule_leaves(cfg))


def test_the_mixer_and_the_mlp_of_a_layer_are_chosen_apart():
    cfg = _lfm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    held = [sorted(k for k in layer if k in (
        "conv", "ssm", "wq", "moe", "mlp")) for layer in params["layers"]]
    assert held == [["conv", "mlp"], ["moe", "wq"], ["conv", "moe"]]
    assert [cfg.mixer_kind(i) for i in range(3)] == [
        "conv", "attention", "conv"]
    assert (cfg.conv_layers, cfg.ssm_layers, cfg.attention_layers,
            cfg.block_applications) == (2, 0, 1, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    for layer in params["layers"]:
        out, stats = llama.block_apply(layer, x, cfg, positions)
        assert out.shape == x.shape
        assert ("moe_aux" in stats) == ("moe" in layer)
    # the axes tree names every leaf of the parameters
    axes = llama.param_logical_axes(cfg)
    jax.tree_util.tree_map(
        lambda a, p: None, axes, params,
        is_leaf=lambda a: isinstance(a, tuple))
    assert axes["layers"][2]["conv"] == {
        "in_proj": ("embed", "mlp"), "conv_w": (None, "mlp"),
        "out_proj": ("mlp", "embed")}
    assert "wo" not in axes["layers"][0] and "moe" in axes["layers"][2]


def test_defaults_are_todays_and_name_no_convolution_layer():
    cfg = llama.LlamaConfig()
    assert (cfg.conv_taps, cfg.qk_norm_per_head, cfg.router_norm_eps,
            cfg.conv_layers) == (3, False, 1e-20, 0)
    assert tuple(llama.MIXER_KINDS)[:3] == ("attention", "mamba", "conv")
    assert llama.program_facts(cfg, 4096) == {}
    assert llama.program_facts(_lfm(), 4096) == {
        "conv_layers": 2, "attention_layers": 1}


def test_published_keys_count_the_parameters_of_the_cut():
    """The benchmark's cut of LFM2-8B-A1B (published layers 1-5, 8 of 32
    experts held, a quarter of the vocabulary) from shapes alone."""
    cfg = llama.LlamaConfig(
        vocab_size=16384, n_layer=5, n_head=32, n_kv_head=8, d_model=2048,
        d_ff=7168, max_seq_len=8192, rope_theta=1e6,
        layer_types=("conv", "attention", "conv", "conv", "conv"),
        qk_norm=True, qk_norm_per_head=True, num_experts=32, top_k=4,
        moe_every=1, first_k_dense=1, d_ff_expert=1792,
        router_score="sigmoid", router_norm_eps=1e-6, router_bias_rate=1e-3,
        experts_held=8, tie_word_embeddings=True)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers"][0]["conv"]) == 16_783_360
    assert count(shapes["layers"][0]) == 60_827_648
    assert count(shapes["layers"][1]) == 98_635_936
    assert [count(layer) for layer in shapes["layers"][2:]] == [
        104_933_408] * 3
    assert count(shapes) == 507_820_288
    # 6 x the matmul parameters of every layer as ONE dense MLP wide (the
    # estimator's convention), the taps, the causal square, head and lookup
    conv = 4 * 2048 * 2048 + 3 * 2048 * 7168
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 7168
    assert llama.flops_per_token(cfg) == (
        6.0 * (4 * conv + attn + 2 * 16384 * 2048)
        + 6.0 * 2 * 8192 * 2048 + 3.0 * 4 * 2 * 3 * 2048)


def test_initialisation_is_the_mixers_own():
    cfg = _lfm(d_model=256, n_head=4)
    conv = llama.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["conv"]
    taps = np.asarray(conv["conv_w"])
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.5
    assert abs(float(np.std(conv["in_proj"])) - 0.02) < 2e-3
    assert abs(float(np.std(conv["out_proj"])) - 0.02) < 2e-3


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("conv", "attention", "window")), "layer_types"),
    (dict(layer_types=("conv", "attention")), "layer_types"),
    (dict(conv_taps=0), "conv_taps=0"),
    (dict(num_experts=0, loop_passes=2, exit_gate_beta=0.1),
     "loop_passes=2"),
    (dict(mtp_layers=1), "mtp_layers=1"),
    (dict(qk_norm=False), "qk_norm_per_head"),
    (dict(layer_types=("mamba", "attention", "conv"), mamba_n_heads=8,
          mamba_d_head=8, mamba_d_state=0), "mamba_d_state"),
])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        _lfm(**over)


def test_experts_may_follow_all_three_kinds():
    """A 'mamba' mixer beside this config's experts was refused by name
    ("beside a 'mamba' one not yet") until the one-branch hybrid needed the
    pair: it builds, and the routed layers are ``is_moe_layer``'s whatever
    the mixer."""
    cfg = _lfm(layer_types=("mamba", "attention", "conv"), mamba_n_heads=8,
               mamba_d_head=8, mamba_d_state=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert [("moe" in layer) for layer in params["layers"]] == [
        cfg.is_moe_layer(i) for i in range(3)]
    assert "ssm" in params["layers"][0] and "conv" in params["layers"][2]


def test_a_dense_stack_may_mix_all_three_kinds():
    cfg = _lfm(layer_types=("mamba", "attention", "conv"), num_experts=0,
               router_bias_rate=None, mamba_n_heads=8, mamba_d_head=8,
               mamba_d_state=16, mamba_chunk_size=8)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    loss, counters = llama.loss_fn(
        params, {"tokens": _tokens()}, cfg, metrics=True)
    assert np.isfinite(float(loss))
    assert counters["ssm_state_rms"].shape == (1,)
    assert llama.program_facts(cfg, 64) == {
        "ssm_layers": 1, "ssm_chunks_per_sequence": 8, "conv_layers": 1,
        "attention_layers": 1}


# -- the step: scopes, counters, a mesh ---------------------------------------


def _job(cfg, mesh=MeshSpec(dp=1), devices=1, batch=B):
    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=0.0,
                             metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    loss.program_facts = llama.program_facts(cfg, S)
    return acc.accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-2),
        sample_batch={"tokens": np.zeros((batch, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=mesh), param_specs="planner",
        devices=jax.devices()[:devices])


def test_the_step_journals_the_scopes_and_hands_out_the_counters():
    cfg = _lfm(remat_block=True, experts_held=4)
    job = _job(cfg)
    assert {"conv", "attention", "mlp", "moe_router", "moe_permute",
            "moe_experts", "moe_combine", "lm_head_loss"} <= {
        v[1] for v in job.program["scopes"].values()}
    by_inner = {}
    for name, inner in job.program["subscopes"].items():
        by_inner.setdefault(inner, set()).add(job.program["scopes"][name][0])
    # (the recomputation's copy is the AOT test's to find: the CPU
    # compiler merges it with the forward's)
    for inner in ("conv_in", "conv_gate", "conv_out"):
        assert {"forward", "backward"} <= by_inner[inner], inner
    assert (job.program["conv_layers"],
            job.program["attention_layers"]) == (2, 1)
    state = job.create_state(jax.random.PRNGKey(0))
    assert "lm_head" not in state["params"]
    losses = []
    for _ in range(3):  # the same batch: its loss must fall
        state, metrics = job.train_step(state, {"tokens": _tokens()})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert np.asarray(metrics["moe_tokens_per_expert"]).shape == (2, 8)
    assert np.asarray(metrics["moe_held_pairs"]).shape == (2,)
    # the rule moved the selection biases, no gradient did
    bias = state["params"]["layers"][2]["moe"]["router_bias"]
    assert float(jnp.max(jnp.abs(bias))) == pytest.approx(3e-3, rel=1e-4)


def test_fsdp2_tp2_gives_the_one_device_loss_and_gradients():
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    # wide enough for the planner to shard the mixer's projections
    cfg = _lfm(d_model=128, d_ff=128)
    job = _job(cfg, mesh=MeshSpec(fsdp=2, tp=2), devices=4)
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    plan = job.state_sharding["params"]["layers"][2]["conv"]
    both = jax.sharding.PartitionSpec("fsdp", "tp")
    assert (plan["in_proj"].spec, plan["out_proj"].spec) == (both, both)
    toks = np.asarray(_tokens())
    batch = jax.make_array_from_process_local_data(
        job.batch_sharding["tokens"], toks)

    def loss(p, t):
        return llama.loss_fn(p, {"tokens": t}, cfg, moe_aux_weight=0.0)

    with jax.set_mesh(job.mesh):
        got, got_grads = jax.jit(jax.value_and_grad(loss))(params, batch)
    alone = jax.tree_util.tree_map(np.asarray, params)
    want, want_grads = jax.jit(jax.value_and_grad(loss))(alone, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        if float(jnp.linalg.norm(wanted[path])) == 0.0:
            continue  # the selection biases
        assert _rel(np.asarray(g), wanted[path]) < 1e-4, (
            jax.tree_util.keystr(path))


# -- what cannot compute it says so -------------------------------------------


@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_the_refusal_names_the_conv_layers_and_the_path(where, path):
    # a dense stack, so that the layer kind is the first thing refused
    cfg = _lfm(num_experts=0, router_bias_rate=None,
               tie_word_embeddings=False)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    assert "'conv' entry (2 of 3 layers)" in str(e.value)
    assert path in str(e.value) and "training path only" in str(e.value)


@pytest.mark.parametrize("kw", [
    dict(segment_ids=np.zeros((B, S), np.int32)),
    dict(attn_fn=lambda *a: None)], ids=["segment_ids", "attn_fn"])
def test_a_conv_layer_refuses_what_its_taps_do_not_know(kw):
    cfg = _lfm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with pytest.raises(NotImplementedError, match="'conv' layer"):
        llama.block_apply(params["layers"][0], x, cfg, positions, **kw)
