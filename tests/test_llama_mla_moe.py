"""The GLM-4.7-Flash / DeepSeek-V3 block on the normal path: latent
attention whose head size is not ``d_model / n_head``, a sigmoid router with
a selection bias that a rule moves, a shared expert, a leading dense layer,
a layer that holds a SHARE of the experts its router knows, and a
multi-token-prediction block — each against a plain formula written out
here, in float32 on seeded weights.

With the defaults nothing of it may show: a dense, a routed and a looped
config give, bit for bit, the loss and gradients the parent commit gave
(values recorded from it).  Every path that cannot compute the new settings
refuses them by name.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import REFUSING_PATH_NAMES, refusing_calls

from dlrover_tpu.models import llama
from dlrover_tpu.ops.grouped_matmul import grouped_matmul_ragged
from dlrover_tpu.parallel.mesh import MeshSpec

acc = importlib.import_module("dlrover_tpu.parallel.accelerate")
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

F32 = jnp.float32
B, S = 2, 16
E, K, HELD = 16, 4, 4


def _glm(**over):
    base = dict(
        vocab_size=512, n_layer=3, n_head=4, n_kv_head=4, d_model=64,
        d_ff=160, max_seq_len=64, dtype=F32, num_experts=E, top_k=K,
        moe_every=1, first_k_dense=1, d_ff_expert=32, n_shared_experts=1,
        router_score="sigmoid", routed_scaling=1.8, router_bias_rate=1e-3,
        balance_per_sequence=True, experts_held=HELD, mtp_layers=1,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32)
    base.update(over)
    return llama.LlamaConfig(**base)


def _tokens(seed=0, vocab=512, s=S):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (B, s + 1)).astype(np.int32))


def _close(got, want, tol=2e-5):
    got_l, tree = jax.tree_util.tree_flatten(got)
    want_l, tree2 = jax.tree_util.tree_flatten(want)
    assert tree == tree2
    for a, b in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol * float(np.abs(b).max() + 1e-30))


# -- the plain formulas -------------------------------------------------------


def _rms(x, w, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, half = x.shape[1], x.shape[3] // 2
    ang = jnp.arange(s, dtype=F32)[:, None] / (
        theta ** (jnp.arange(half, dtype=F32) / half))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla_plain(y, layer, cfg):
    """The issue's equations, head by head, the mask written out."""
    b, s, _ = y.shape
    h, nope, rope = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_q = _rms(y @ layer["wq_a"], layer["q_a_norm"])
    q = (c_q @ layer["wq_b"]).reshape(b, s, h, nope + rope)
    down = y @ layer["wkv_a"]
    c_kv = _rms(down[..., :cfg.kv_lora_rank], layer["kv_a_norm"])
    k_rope = _rope(down[..., None, cfg.kv_lora_rank:], cfg.rope_theta)
    kv = (c_kv @ layer["wkv_b"]).reshape(b, s, h, nope + cfg.v_head_dim)
    outs = []
    mask = jnp.tril(jnp.ones((s, s), bool))
    for i in range(h):
        q_i = jnp.concatenate(
            [q[:, :, i, :nope],
             _rope(q[:, :, i:i + 1, nope:], cfg.rope_theta)[:, :, 0]], -1)
        k_i = jnp.concatenate([kv[:, :, i, :nope], k_rope[:, :, 0]], -1)
        scores = jnp.einsum("bqd,bkd->bqk", q_i, k_i) / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        outs.append(jnp.einsum("bqk,bkd->bqd", p, kv[:, :, i, nope:]))
    return jnp.concatenate(outs, -1) @ layer["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _routed_plain(y, moe, cfg, held=None):
    """sigmoid scores, top-k of score + bias, the chosen SCORES over their
    sum times the scale, the held experts' part and the shared expert."""
    first, n_held = held if held else (0, cfg.num_experts)
    s = jax.nn.sigmoid(y @ moe["router"])
    _, top = jax.lax.top_k(s + moe.get("router_bias", 0.0), cfg.top_k)
    w = jnp.take_along_axis(s, top, -1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling
    out = jnp.zeros_like(y)
    for e in range(first, first + n_held):
        w_e = jnp.sum(jnp.where(top == e, w, 0.0), -1, keepdims=True)
        j = e - first
        out = out + w_e * _swiglu(y, moe["wg"][j], moe["wi"][j], moe["wo"][j])
    if "shared" in moe:
        sh = moe["shared"]
        out = out + _swiglu(y, sh["w_gate"], sh["w_up"], sh["w_down"])
    return out, top


def _moe_of(cfg, seed=0):
    layer = llama._init_layer(jax.random.PRNGKey(seed), cfg, True)
    moe = dict(layer["moe"])
    # a router that spreads its scores: N(0, 0.02) logits all sit at 0.5
    moe["router"] = moe["router"] * 40.0
    return moe


def _x(seed=1, b=B, s=S, d=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, d), F32)


# -- the defaults -------------------------------------------------------------

#: loss and summed |gradient| of three configurations on seeded weights and
#: tokens, as float hex, recorded from the parent commit (673a4a2) on this
#: backend: what the four accepted cells compute must not move by a bit
PARENT = {
    ("dense", True): ("0x1.8ec36e0000000p+2", "0x1.49e77a5958000p+8"),
    ("dense", False): ("0x1.8ec36e0000000p+2", "0x1.49e77a5958000p+8"),
    ("routed", True): ("0x1.901f960000000p+2", "0x1.64d6ae1b80000p+8"),
    ("routed", False): ("0x1.901f960000000p+2", "0x1.64d6ae1b80000p+8"),
    ("looped", True): ("0x1.88ec460000000p+2", "0x1.1f47cae3a8000p+9"),
    ("looped", False): ("0x1.88ec3c0000000p+2", "0x1.1f47cacaa8000p+9"),
}
OLD = {
    "dense": {},
    "routed": dict(num_experts=4, top_k=2, moe_every=1),
    "looped": dict(loop_passes=3, branch_norm=True, exit_gate_beta=0.1),
}


@pytest.mark.parametrize("kind,fused", sorted(PARENT))
def test_defaults_give_the_parents_loss_and_gradients_bit_for_bit(
        kind, fused):
    cfg = llama.LlamaConfig.tiny(
        n_layer=2, vocab_size=512, dtype=F32, **OLD[kind])
    assert (cfg.kv_lora_rank, cfg.experts_held, cfg.mtp_layers,
            cfg.first_k_dense, cfg.n_shared_experts, cfg.router_score,
            cfg.router_bias_rate, cfg.routed_scaling) == (
                0, 0, 0, 0, 0, "softmax", None, 1.0)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, 512, (2, 17)).astype(np.int32))
    loss, grads = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": toks}, cfg, fused_lm_head=fused))(params)
    total = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert (float(loss).hex(), float(total).hex()) == PARENT[kind, fused]


def test_old_leaves_draw_the_same_weights_beside_the_new_ones():
    """Latent attention, the shared expert and the prediction block take
    keys folded out of the old ones: a routed layer's router and experts,
    ``wo`` and the dense MLP are the plain model's."""
    plain = llama.init_params(jax.random.PRNGKey(3), llama.LlamaConfig.tiny(
        n_layer=2, dtype=F32, num_experts=4, top_k=2, moe_every=1,
        n_head=4, n_kv_head=4))
    new = llama.init_params(jax.random.PRNGKey(3), llama.LlamaConfig.tiny(
        n_layer=2, dtype=F32, num_experts=4, top_k=2, moe_every=1,
        n_head=4, n_kv_head=4, n_shared_experts=1, mtp_layers=1,
        router_bias_rate=1e-3))
    for a, b in zip(plain["layers"], new["layers"]):
        for name in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(a[name], b[name])
        for name in ("router", "wi", "wg", "wo"):
            np.testing.assert_array_equal(a["moe"][name], b["moe"][name])
        assert float(jnp.abs(b["moe"]["router_bias"]).max()) == 0.0
    np.testing.assert_array_equal(plain["embed"], new["embed"])
    assert new["mtp"]["w_eh"].shape == (128, 64)


def test_param_axes_name_every_leaf():
    cfg = _glm()
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    axes = llama.param_logical_axes(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    named = dict(jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda a: isinstance(a, tuple))[0])
    assert {jax.tree_util.keystr(p) for p, _ in flat} == {
        jax.tree_util.keystr(p) for p in named}
    for path, leaf in flat:
        assert len(named[path]) == leaf.ndim, jax.tree_util.keystr(path)


def test_parameter_count_at_published_widths_is_the_issues():
    """706,518,848 by the issue's leaf-by-leaf count, from shapes alone."""
    cfg = llama.LlamaConfig(
        vocab_size=19360, n_layer=5, n_head=20, n_kv_head=20, d_model=2048,
        d_ff=10240, num_experts=64, top_k=4, moe_every=1, first_k_dense=1,
        d_ff_expert=1536, n_shared_experts=1, router_score="sigmoid",
        router_bias_rate=1e-3, experts_held=8, mtp_layers=1,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert llama.num_params(shapes) == 706_518_848
    assert llama.num_params(shapes["layers"][0]) == 84_677_888
    assert llama.num_params(shapes["layers"][1]) == 106_829_120
    assert llama.num_params(shapes["mtp"]) == 115_223_872
    assert (cfg.head_dim, cfg.block_applications) == (256, 6)


# -- latent attention ---------------------------------------------------------


def _attention_case(cfg, s):
    layer = llama._init_layer(jax.random.PRNGKey(2), cfg, False)
    # gains off one, so that a norm left out would show
    layer["q_a_norm"] = layer["q_a_norm"] * 1.3
    layer["kv_a_norm"] = layer["kv_a_norm"] * 0.7
    y = _x(s=s, d=cfg.d_model)
    positions = jnp.broadcast_to(jnp.arange(s), (B, s))
    names = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo")
    cot = jax.random.normal(jax.random.PRNGKey(5), y.shape, F32)

    def system(y, leaves):
        out = llama._attention(
            y, dict(layer, **leaves), cfg, positions, "auto", None)
        return jnp.sum(out * cot), out

    def plain(y, leaves):
        out = _mla_plain(y, dict(layer, **leaves), cfg)
        return jnp.sum(out * cot), out

    leaves = {n: layer[n] for n in names}
    return system, plain, y, leaves


def test_latent_attention_matches_the_equations_forward_and_backward():
    cfg = _glm()
    assert cfg.head_dim == 32 != cfg.d_model // cfg.n_head
    system, plain, y, leaves = _attention_case(cfg, S)
    got = jax.value_and_grad(system, argnums=(0, 1), has_aux=True)(y, leaves)
    want = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(y, leaves)
    _close(got, want)


def test_flash_kernels_take_a_head_size_of_256_from_latent_attention(
        monkeypatch):
    """The three kernels in interpret mode at the published head size (192
    + 64 rotary), through the model's own call."""
    monkeypatch.setattr(
        llama, "flash_attention",
        lambda q, k, v, backend=None, **kw: fa.flash_attention(
            q, k, v, backend="pallas", interpret=True, **kw))
    cfg = _glm(n_head=2, n_kv_head=2, qk_nope_head_dim=192,
               qk_rope_head_dim=64, v_head_dim=256)
    system, plain, y, leaves = _attention_case(cfg, 128)
    got = jax.value_and_grad(system, argnums=(0, 1), has_aux=True)(y, leaves)
    want = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(y, leaves)
    assert got[0][1].shape == (B, 128, 64)
    _close(got, want, tol=2e-4)


def test_flash_kernels_ask_for_vmem_only_past_the_default():
    """S 8,192 at D 128 (every accepted cell) compiles as it always has;
    at D 256 the whole-sequence operands alone are the default limit."""
    assert fa._vmem_params(2 * 2 * 8192 * 128 * 2) == {}
    params = fa._vmem_params(2 * 2 * 8192 * 256 * 2)["compiler_params"]
    assert params.vmem_limit_bytes > 2 * 2 * 8192 * 256 * 2


@pytest.mark.parametrize("over,match", [
    # (values of a width of their own and one query matrix are built
    # since PR 61: tests/test_llama_kda_mla.py)
    (dict(v_head_dim=0), "v_head_dim > 0"),
    (dict(qk_rope_head_dim=7, v_head_dim=31), "qk_rope_head_dim"),
    (dict(q_lora_rank=-1), "q_lora_rank >= 0"),
    (dict(n_kv_head=2), "n_kv_head=2"),
    (dict(router_score="tanh"), "router_score='tanh'"),
    (dict(experts_held=12, experts_held_first=8), "experts_held=12"),
    (dict(mtp_layers=2), "mtp_layers=2"),
])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        _glm(**over)


# -- the router ---------------------------------------------------------------


@pytest.mark.parametrize("norm", [True, False], ids=["renorm", "raw"])
@pytest.mark.parametrize("scale", [1.0, 1.8])
def test_routed_block_matches_the_equations(norm, scale):
    cfg = _glm(experts_held=0, norm_topk_prob=norm, routed_scaling=scale)
    moe, x = _moe_of(cfg), _x()
    got, stats = llama._moe_swiglu(x, moe, cfg)
    want, top = _routed_plain(x, moe, cfg)
    _close(got, want)
    np.testing.assert_array_equal(
        np.sort(stats["experts"], -1), np.sort(top, -1))
    g_got = jax.grad(lambda x, m: jnp.sum(
        llama._moe_swiglu(x, m, cfg)[0] ** 2), argnums=(0, 1))(x, moe)
    g_want = jax.grad(lambda x, m: jnp.sum(
        _routed_plain(x, m, cfg)[0] ** 2), argnums=(0, 1))(x, moe)
    _close(g_got, g_want, tol=1e-4)


def test_the_bias_chooses_and_never_weighs():
    cfg = _glm(experts_held=0)
    moe, x = _moe_of(cfg), _x()
    _, before = llama._moe_swiglu(x, moe, cfg)
    favoured = 11
    assert not bool(jnp.all(jnp.any(before["experts"] == favoured, -1)))
    biased = dict(moe, router_bias=moe["router_bias"].at[favoured].set(5.0))
    out, after = llama._moe_swiglu(x, biased, cfg)
    # chosen for every token now ...
    assert bool(jnp.all(jnp.any(after["experts"] == favoured, -1)))
    # ... and weighed by its SCORE: a bias of 5 in a weight would be seen
    want, _ = _routed_plain(x, biased, cfg)
    _close(out, want)
    s = jax.nn.sigmoid(x @ moe["router"])
    w = jnp.take_along_axis(s, after["experts"], -1)
    np.testing.assert_allclose(
        jnp.sum(w / jnp.sum(w, -1, keepdims=True), -1), 1.0, rtol=1e-6)
    # no gradient reaches it
    g = jax.grad(lambda m: jnp.sum(llama._moe_swiglu(x, m, cfg)[0] ** 2))(
        biased)
    assert float(jnp.abs(g["router_bias"]).max()) == 0.0


def test_sequence_wise_balance_term_against_its_closed_form():
    cfg = _glm(experts_held=0)
    moe, x = _moe_of(cfg), _x()
    _, stats = llama._moe_swiglu(x, moe, cfg)
    s = jax.nn.sigmoid(x @ moe["router"])
    _, top = jax.lax.top_k(s, K)
    want = 0.0
    for b in range(B):
        f = np.zeros(E)
        for e in np.asarray(top[b]).reshape(-1):
            f[e] += E / (K * S)
        p = np.asarray(jnp.mean(s[b] / jnp.sum(s[b], -1, keepdims=True), 0))
        want += float(np.sum(f * p)) / B
    np.testing.assert_allclose(stats["moe_aux"], want, rtol=1e-5)
    # an even router reads one: f_e = P_e^-1 ... = 1 each way
    even = dict(moe, router=jnp.zeros_like(moe["router"]))
    np.testing.assert_allclose(
        llama._moe_swiglu(x, even, cfg)[1]["moe_aux"], 1.0, rtol=1e-5)


# -- the share ----------------------------------------------------------------


def _share_of(moe, first, held):
    part = {k: moe[k] for k in ("router", "router_bias")}
    part.update({k: moe[k][first:first + held] for k in ("wi", "wg", "wo")})
    return part


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips, two experts each: their routed parts plus the shared
    expert counted once are the layer that holds all sixteen, forward and
    in the gradient of the input; each share is the plain formula's."""
    whole_cfg = _glm(experts_held=0)
    moe, x = _moe_of(whole_cfg), _x()
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)

    def whole(x):
        return jnp.sum(llama._moe_swiglu(x, moe, whole_cfg)[0] * cot)

    def shares(x):
        total = _swiglu(x, *(moe["shared"][k]
                             for k in ("w_gate", "w_up", "w_down")))
        for first in range(0, E, 2):
            cfg = _glm(experts_held=2, experts_held_first=first)
            total = total + llama._moe_swiglu(
                x, _share_of(moe, first, 2), cfg)[0]
        return jnp.sum(total * cot)

    _close(jax.value_and_grad(shares)(x), jax.value_and_grad(whole)(x),
           tol=5e-5)
    held_pairs = 0
    for first in range(0, E, 2):
        cfg = _glm(experts_held=2, experts_held_first=first)
        part = _share_of(moe, first, 2)
        got, stats = llama._moe_swiglu(x, part, cfg)
        want, _ = _routed_plain(x, part, cfg, held=(first, 2))
        _close(got, want)
        # the counters stay in the router's numbering, all sixteen
        np.testing.assert_array_equal(
            stats["tokens_per_expert"],
            llama._moe_swiglu(x, moe, whole_cfg)[1]["tokens_per_expert"])
        assert int(stats["held_pairs"]) == int(
            stats["tokens_per_expert"][first:first + 2].sum())
        held_pairs += int(stats["held_pairs"])
    assert held_pairs == B * S * K


@pytest.mark.parametrize("k", [1, 4])
def test_skewed_routing_onto_held_experts_drops_nothing(k):
    """Every pick of every token lands on this chip's experts (with k = 1:
    on ONE of them): N x K pairs computed, the plain formula's result."""
    cfg = _glm(top_k=k, experts_held=4, experts_held_first=4)
    moe = _moe_of(_glm(experts_held=0))
    bias = jnp.zeros((E,), F32).at[4:4 + k].set(9.0)
    part = dict(_share_of(moe, 4, 4), router_bias=bias,
                shared=moe["shared"])
    x = _x()
    got, stats = llama._moe_swiglu(x, part, cfg)
    assert int(stats["held_pairs"]) == B * S * k
    assert int(stats["tokens_per_expert"][4]) == B * S
    want, _ = _routed_plain(x, part, cfg, held=(4, 4))
    _close(got, want)
    g = jax.grad(lambda x: jnp.sum(llama._moe_swiglu(x, part, cfg)[0] ** 2))(x)
    g_want = jax.grad(lambda x: jnp.sum(
        _routed_plain(x, part, cfg, held=(4, 4))[0] ** 2))(x)
    _close(g, g_want, tol=1e-4)


def test_no_pick_on_a_held_expert_leaves_the_shared_expert_alone():
    cfg = _glm(experts_held=4, experts_held_first=0)
    moe = _moe_of(_glm(experts_held=0))
    bias = jnp.zeros((E,), F32).at[8:12].set(9.0)  # all picks elsewhere
    part = dict(_share_of(moe, 0, 4), router_bias=bias, shared=moe["shared"])
    x = _x()
    got, stats = llama._moe_swiglu(x, part, cfg)
    assert int(stats["held_pairs"]) == 0
    _close(got, _swiglu(x, *(moe["shared"][k]
                             for k in ("w_gate", "w_up", "w_down"))))
    assert bool(jnp.isfinite(jax.grad(lambda x: jnp.sum(
        llama._moe_swiglu(x, part, cfg)[0] ** 2))(x)).all())


# -- the share's buffer ------------------------------------------------------

#: a block of 2 x 1024 tokens taking 2 of 8 experts, experts 2 and 3 held:
#: 4,096 picks, 1,024 on the held ones under an even router
BUF_N, BUF_E, BUF_K, BUF_FIRST = 2048, 8, 2, 2
BUF_BOUNDS = (1536, 4096)


def _buffer_cfg():
    return _glm(num_experts=BUF_E, top_k=BUF_K, experts_held=2,
                experts_held_first=BUF_FIRST, n_shared_experts=0)


def _picks_with(held_pairs):
    """``(x [2, 1024, 64], moe)`` whose router sends exactly ``held_pairs``
    of the 4,096 picks to experts 2 and 3: the router reads the stream's
    first eight dims, and every token carries its two picks there."""
    rng = np.random.RandomState(held_pairs)
    both = max(0, held_pairs - BUF_N)  # tokens with two held picks
    one = held_pairs - 2 * both
    absent = [e for e in range(BUF_E) if e not in (2, 3)]
    picks = np.empty((BUF_N, 2), np.int64)
    for n in range(BUF_N):
        if n < both:
            picks[n] = (2, 3) if n % 2 else (3, 2)
        elif n < both + one:
            picks[n] = (2 + n % 2, rng.choice(absent))
        else:
            picks[n] = rng.choice(absent, 2, replace=False)
    x = 0.1 * rng.standard_normal((BUF_N, 64)).astype(np.float32)
    x[:, :BUF_E] -= 4.0
    x[np.arange(BUF_N), picks[:, 0]] += 8.0
    x[np.arange(BUF_N), picks[:, 1]] += 7.0
    x = x[rng.permutation(BUF_N)]  # the held picks anywhere in the block
    moe = _share_of(_moe_of(_glm(
        num_experts=BUF_E, top_k=BUF_K, experts_held=0,
        n_shared_experts=0)), BUF_FIRST, 2)
    moe["router"] = jnp.eye(64, BUF_E, dtype=F32)
    return jnp.asarray(x).reshape(2, BUF_N // 2, 64), moe


def test_buffer_bounds_follow_from_the_shapes_alone():
    bounds = llama._moe_buffer_bounds
    assert bounds(BUF_N, BUF_K, BUF_E, 2) == BUF_BOUNDS
    # the two cells with a share: 31.25 % and 15.6 % of every pick first
    assert bounds(4 * 8192, 4, 32, 8) == (40960, 131072)
    assert bounds(2 * 8192, 4, 64, 8) == (10240, 65536)
    for sizes in (bounds(4 * 8192, 4, 32, 8), bounds(2 * 8192, 4, 64, 8)):
        assert all(r % 512 == 0 for r in sizes)
    # a first size of half the buffer is the largest that counts as one
    assert bounds(1024, 2, 8, 2) == (1024, 2048)
    assert bounds(1024, 2, 8, 4) == (2048,)
    # one size, the parent's program: every expert held (OLMoE's cell), a
    # toy block, decode's few rows
    assert bounds(8 * 4096, 8, 64, 64) == (262144,)
    assert bounds(B * S, K, E, HELD) == (B * S * K,)
    assert bounds(8, 4, 64, 8) == (32,)
    assert bounds(1, 4, 32, 8) == (4,)


@pytest.mark.parametrize("held_pairs,rows", [
    (0, 1536), (1, 1536), (1535, 1536), (1536, 4096), (1537, 4096),
    (3072, 4096), (4096, 4096)])
def test_a_sized_buffer_computes_what_the_full_one_does(
        monkeypatch, held_pairs, rows):
    """The first size ABOVE the held pairs is taken (a buffer exactly full
    has no dead last row for the picks past it to read), and at every size
    the block's output, its statistics and every gradient are those of the
    buffer that holds all 4,096 picks."""
    cfg = _buffer_cfg()
    x, moe = _picks_with(held_pairs)
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape, F32)

    def run(x, moe):  # jitted anew a call: the sizes are read at the trace
        def scalar(x, moe):
            out, stats = llama._moe_swiglu(x, moe, cfg)
            return jnp.sum(out * cot) + 0.1 * stats["moe_z"], (out, stats)
        return jax.jit(jax.grad(scalar, argnums=(0, 1), has_aux=True))(
            x, moe)

    (g_x, g_moe), (out, stats) = run(x, moe)
    assert int(stats["held_pairs"]) == held_pairs
    assert int(stats.pop("buffer_rows")) == rows
    monkeypatch.setattr(llama, "_moe_buffer_bounds",
                        lambda n, k, e, held: (n * k,))
    (g_x_full, g_moe_full), (out_full, stats_full) = run(x, moe)
    assert "buffer_rows" not in stats_full
    _close((out, stats, g_x, g_moe),
           (out_full, stats_full, g_x_full, g_moe_full), tol=1e-6)
    assert float(jnp.abs(g_moe["router"]).max()) > 0
    if held_pairs:
        for leaf in ("wg", "wi", "wo"):
            assert float(jnp.abs(g_moe[leaf]).max()) > 0
    else:
        assert float(jnp.abs(out).max()) == 0.0


def test_buffer_rows_come_back_one_a_routed_block():
    """``moe_buffer_rows`` beside ``moe_held_pairs``, through the aux dict
    and the loss's counters, under block remat too; absent where every
    expert is held and where the shapes leave one size."""
    cfg = dataclasses.replace(
        _glm(num_experts=BUF_E, top_k=BUF_K, experts_held=2),
        max_seq_len=1024, remat_block=True)
    assert llama._moe_buffer_bounds(2 * 1024, BUF_K, BUF_E, 2) == (
        1536, 4096)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": _tokens(s=1024)}
    (_, m), grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg, metrics=True),
        has_aux=True))(params)
    assert m["moe_buffer_rows"].shape == m["moe_held_pairs"].shape == (3,)
    assert m["moe_buffer_rows"].dtype == jnp.int32
    for held_pairs, rows in zip(m["moe_held_pairs"], m["moe_buffer_rows"]):
        assert int(rows) == (1536 if held_pairs < 1536 else 4096)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))
    # one size: the toy block of every other test here
    _, m = llama.loss_fn(params, {"tokens": _tokens()}, cfg, metrics=True)
    assert "moe_buffer_rows" not in m and "moe_held_pairs" in m
    whole = dataclasses.replace(cfg, experts_held=0)
    _, m = llama.loss_fn(llama.init_params(jax.random.PRNGKey(0), whole),
                         batch, whole, metrics=True)
    assert "moe_buffer_rows" not in m and "moe_held_pairs" not in m


def test_a_branch_of_the_choice_is_no_scope_of_the_program():
    """``lax.switch`` names a branch's instructions ``branch_<i>_fun``: the
    scope is the program's own inside it."""
    assert acc.phase_and_scope(
        "jit(train_step)/transpose(jvp(mtp))/checkpoint/"
        "rematted_computation/cond/branch_0_fun/moe_experts/mul") == [
            "recompute", "mtp"]
    assert acc.phase_and_scope(
        "jit(train_step)/jvp(cond)/branch_1_fun/moe_permute/gather") == [
            "forward", "moe_permute"]
    assert acc.inner_scope(
        "jit(train_step)/jvp(mtp)/cond/branch_2_fun/moe_experts/mul") == (
            "moe_experts")


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_grouped_matmul_with_sizes_that_sum_to_fewer_rows(backend):
    """The groups end before the buffer does: the rows inside them are the
    per-group products, forward and in both gradients; the rows past them
    are unspecified and masked by the caller."""
    rows, k, n = 1024, 128, 128
    dt = jnp.bfloat16
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, k), F32).astype(dt)
    w = (jax.random.normal(jax.random.PRNGKey(1), (3, k, n), F32)
         * 0.1).astype(dt)
    sizes = jnp.asarray([200, 0, 340], jnp.int32)
    live = (jnp.arange(rows) < 540)[:, None]

    def system(x, w):
        out = grouped_matmul_ragged(
            jnp.where(live, x, 0), w, sizes, backend=backend,
            interpret=backend == "pallas")
        return jnp.where(live, out, 0).astype(F32)

    def plain(x, w):
        x = x.astype(F32)
        out = jnp.zeros((rows, n), F32)
        out = out.at[:200].set(x[:200] @ w[0].astype(F32))
        return out.at[200:540].set(x[200:540] @ w[2].astype(F32))

    np.testing.assert_allclose(system(x, w), plain(x, w), rtol=2e-2,
                               atol=2e-2)
    cot = jax.random.normal(jax.random.PRNGKey(2), (rows, n), F32)
    got = jax.grad(lambda x, w: jnp.sum(system(x, w) * cot), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(plain(x, w) * cot), (0, 1))(x, w)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a.astype(F32)).all())
        np.testing.assert_allclose(
            a.astype(F32), b.astype(F32), rtol=5e-2,
            atol=5e-2 * float(jnp.abs(b.astype(F32)).max()))
    assert float(jnp.abs(got[0][540:].astype(F32)).max()) == 0.0
    assert float(jnp.abs(got[1][1].astype(F32)).max()) == 0.0


# -- multi-token prediction ---------------------------------------------------


def test_mtp_loss_takes_targets_two_ahead_and_masks_the_last_position():
    cfg = _glm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = _tokens()
    inp, tgt = toks[:, :-1], toks[:, 1:]
    x, aux = llama.forward_hidden(params, inp, cfg, next_tokens=tgt)
    assert x.shape == (2, B, S, cfg.d_model)
    assert sorted(aux["moe_experts"], key=str) == [1, 2, "mtp"]
    assert aux["moe_tokens_per_expert"].shape == (3, E)
    assert aux["moe_held_pairs"].shape == (3,)
    logits = (x @ params["lm_head"]).astype(F32)
    logp = jax.nn.log_softmax(logits, -1)
    main = -jnp.mean(jnp.take_along_axis(logp[0], tgt[..., None], -1))
    two_ahead = toks[:, 2:]  # t_{i+2} for the positions that have one
    mtp = -jnp.mean(jnp.take_along_axis(
        logp[1][:, :-1], two_ahead[..., None], -1))
    for fused in (True, False):
        ce, counters = llama.mtp_loss(
            x, params["lm_head"], tgt, cfg, fused_lm_head=fused,
            mtp_weight=0.3)
        np.testing.assert_allclose(ce, main + 0.3 * mtp, rtol=1e-5)
        np.testing.assert_allclose(counters["main_ce"], main, rtol=1e-5)
        np.testing.assert_allclose(counters["mtp_ce"], mtp, rtol=1e-5)
    # the block's stream at the last position weighs nothing
    moved = x.at[1, :, -1].add(3.0)
    np.testing.assert_allclose(
        llama.mtp_loss(moved, params["lm_head"], tgt, cfg)[0], ce, rtol=1e-6)
    # without the next tokens the block does not run
    alone, _ = llama.forward_hidden(params, inp, cfg)
    np.testing.assert_array_equal(alone, x[0])


def test_mtp_block_reads_the_embedding_of_the_next_token_and_the_last_layer():
    cfg = _glm(num_experts=0, experts_held=0, n_shared_experts=0,
               router_bias_rate=None, first_k_dense=0)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = _tokens()
    inp, tgt = toks[:, :-1], toks[:, 1:]
    x, _ = llama.forward_hidden(params, inp, cfg, next_tokens=tgt)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    z = params["embed"][inp]
    for layer in params["layers"]:
        z, _ = llama.block_apply(layer, z, cfg, positions)
    m = params["mtp"]
    u = jnp.concatenate([_rms(params["embed"][tgt], m["ln_e"]),
                         _rms(z, m["ln_h"])], -1) @ m["w_eh"]
    u, _ = llama.block_apply(m["block"], u, cfg, positions)
    _close(x[1], _rms(u, m["ln_f"]))
    _close(x[0], _rms(z, params["ln_f"]))


def test_one_head_call_takes_both_sets_of_rows(monkeypatch):
    calls = []
    real = llama.linear_softmax_cross_entropy_sum

    def counted(x, *a, **kw):
        calls.append(x.shape)
        return real(x, *a, **kw)

    monkeypatch.setattr(llama, "linear_softmax_cross_entropy_sum", counted)
    cfg = _glm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    llama.loss_fn(params, {"tokens": _tokens()}, cfg, fused_lm_head=True)
    assert calls == [(2, B, S, cfg.d_model)]


def test_loss_is_the_sum_of_its_parts_and_remat_changes_no_value():
    cfg = _glm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": _tokens()}
    loss, m = llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-4,
                            mtp_weight=0.3, metrics=True)
    np.testing.assert_allclose(
        loss, m["main_ce"] + 0.3 * m["mtp_ce"] + 1e-4 * m["moe_seq_aux"],
        rtol=1e-6)
    assert "moe_aux" not in m and m["moe_held_pairs"].shape == (3,)
    remat = dataclasses.replace(cfg, remat_block=True)
    got = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, remat))(params)
    want = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg))(params)
    _close(got, want, tol=1e-5)


# -- the rule -----------------------------------------------------------------


def _job(cfg, optimizer=None):
    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-4,
                             metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    return acc.accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optimizer or optax.adamw(1e-2, weight_decay=0.1),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(dp=1)), param_specs="planner",
        devices=jax.devices()[:1])


def _biases(params):
    return {name: leaf for name, leaf in (
        (jax.tree_util.keystr(p), x) for p, x in
        jax.tree_util.tree_flatten_with_path(params)[0])
        if name.endswith("['router_bias']")}


def test_rule_leaves_are_the_selection_biases_by_their_key_paths():
    cfg = _glm()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert set(llama.rule_leaves(cfg)) == set(_biases(params)) == {
        "['layers'][1]['moe']['router_bias']",
        "['layers'][2]['moe']['router_bias']",
        "['mtp']['block']['moe']['router_bias']"}
    assert llama.rule_leaves(_glm(router_bias_rate=None)) == ()
    assert llama.RULE_UPDATES == acc.RULE_UPDATES


def test_the_step_moves_the_bias_by_its_rule_and_by_nothing_else():
    """``b += rate * sign(mean(c) - c)`` from the step's own counts: no
    gradient, no moment, no weight decay."""
    cfg = _glm()
    job = _job(cfg)
    state = job.create_state(jax.random.PRNGKey(0))
    # off zero, so that a decay of 0.1 x 1e-2 would show
    state["params"] = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.5 if jax.tree_util.keystr(p).endswith(
            "['router_bias']") else x, state["params"])
    moments = {jax.tree_util.keystr(p) for p, _ in
               jax.tree_util.tree_flatten_with_path(state["opt_state"])[0]}
    assert not [m for m in moments if "router_bias" in m]
    assert [m for m in moments if "router']" in m]
    before = jax.device_get(_biases(state["params"]))
    router_before = np.asarray(state["params"]["layers"][1]["moe"]["router"])
    state, metrics = job.train_step(state, {"tokens": _tokens()})
    assert acc.RULE_UPDATES not in metrics
    after = jax.device_get(_biases(state["params"]))
    counts = np.asarray(metrics["moe_tokens_per_expert"], np.float32)
    for row, name in enumerate(llama.rule_leaves(cfg)):
        want = before[name] + np.float32(1e-3) * np.sign(
            counts[row].mean() - counts[row])
        np.testing.assert_array_equal(after[name], want)
    np.testing.assert_allclose(
        metrics["moe_router_bias_abs_max"], 0.501, rtol=1e-6)
    # the router beside it IS trained
    assert float(np.abs(np.asarray(
        state["params"]["layers"][1]["moe"]["router"])
        - router_before).max()) > 0
    state, metrics = job.train_step(state, {"tokens": _tokens(1)})
    assert np.isfinite(float(metrics["loss"]))
    assert job.program["scopes"] and "router_bias" in {
        v[1] for v in job.program["scopes"].values()}
    assert {"mla_q", "mla_kv", "mla_out", "moe_shared"} <= set(
        job.program["subscopes"].values())


def test_the_bias_survives_a_checkpoint_with_the_state(tmp_path,
                                                       monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "glm-rule")
    monkeypatch.setenv("DLROVER_TPU_PROCESS_ID", "0")
    monkeypatch.setenv("DLROVER_TPU_NUM_PROCESSES", "1")
    cfg = _glm()
    job = _job(cfg)
    state = job.create_state(jax.random.PRNGKey(0))
    for seed in range(2):
        state, _ = job.train_step(state, {"tokens": _tokens(seed)})
    saved = jax.device_get(state)
    ckpt = CheckpointEngine(str(tmp_path), job_name="glm-rule")
    try:
        ckpt.save_to_storage(2, state, meta={"step": 2})
        assert ckpt.wait(timeout=60)
        fresh = job.create_state(jax.random.PRNGKey(1))
        restored, meta = ckpt.load(target=fresh)
    finally:
        ckpt.close()
    assert meta["step"] == 2
    assert float(np.abs(saved["params"]["layers"][1]["moe"][
        "router_bias"]).max()) > 0
    got_l, tree = jax.tree_util.tree_flatten(jax.device_get(restored))
    want_l, tree2 = jax.tree_util.tree_flatten(saved)
    assert tree == tree2
    for a, b in zip(got_l, want_l):
        np.testing.assert_array_equal(a, b)
    # and the restored state trains on
    restored, metrics = job.train_step(restored, {"tokens": _tokens(2)})
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("case", ["undeclared", "unreturned", "unknown leaf",
                                  "grad_accum"])
def test_accelerate_refuses_a_rule_it_was_half_told_of(case):
    cfg = _glm()

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, metrics=case != "unreturned")

    strategy = acc.Strategy(mesh=MeshSpec(dp=1))
    if case == "unreturned":
        inner = loss

        def loss(params, batch):  # noqa: F811 - metrics without the rule
            return inner(params, batch), {"n": jnp.zeros(())}
    if case != "undeclared":
        loss.rule_leaves = llama.rule_leaves(cfg)
    if case == "unknown leaf":
        loss.rule_leaves = ("['layers'][0]['moe']['router_bias']",)
    if case == "grad_accum":
        strategy = dataclasses.replace(strategy, grad_accum=2)
    with pytest.raises(RuntimeError, match="rule_leaves|rule_updates"):
        acc.accelerate(
            loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
            optimizer=optax.adamw(1e-3),
            sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
            strategy=strategy, param_specs="planner",
            devices=jax.devices()[:1])


def test_a_step_without_a_rule_is_the_step_it_was():
    """No ``rule_leaves``: the optimizer is the caller's own object's
    state, leaf for leaf, and no ``subscopes`` table is journalled."""
    cfg = llama.LlamaConfig.tiny(n_layer=1, vocab_size=512, dtype=F32)
    tx = optax.adamw(1e-3)
    job = acc.accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg), optimizer=tx,
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(dp=1)), param_specs="planner",
        devices=jax.devices()[:1])
    state = job.create_state(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(state["opt_state"]) == (
        jax.tree_util.tree_structure(tx.init(state["params"])))
    assert "subscopes" not in job.program


def test_inner_scope_reads_the_innermost_program_scope():
    assert acc.inner_scope(
        "jit(train_step)/jvp(attention)/mla_q/dot_general") == "mla_q"
    assert acc.inner_scope(
        "jit(train_step)/transpose(jvp(mtp))/checkpoint/"
        "rematted_computation/attention/mla_kv/mul") == "mla_kv"
    assert acc.inner_scope(
        "jit(train_step)/jvp(attention)/dot_general") == ""
    assert acc.phase_and_scope(
        "jit(train_step)/jvp(attention)/mla_q/dot_general") == [
            "forward", "attention"]


# -- what cannot compute it says so -------------------------------------------

SETTINGS = {
    "kv_lora_rank": _glm(num_experts=0, experts_held=0, n_shared_experts=0,
                         router_bias_rate=None, mtp_layers=0),
    "experts_held": llama.LlamaConfig.tiny(
        num_experts=8, top_k=2, moe_every=1, experts_held=2),
    "mtp_layers": llama.LlamaConfig.tiny(mtp_layers=1),
}


@pytest.mark.parametrize("where", sorted(refusing_calls(None)))
@pytest.mark.parametrize("setting", sorted(SETTINGS))
def test_paths_without_the_latent_block_refuse_by_name(setting, where):
    with pytest.raises(ValueError, match=setting) as e:
        refusing_calls(SETTINGS[setting])[where]()
    assert REFUSING_PATH_NAMES[where] in str(e.value)
    assert "training path only" in str(e.value)
