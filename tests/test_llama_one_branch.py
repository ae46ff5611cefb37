"""Layers that are ONE branch each on the normal path (the Nemotron-H block):
a Mamba-2 mixer with B, C and the gated norm in groups, attention without
rotary position, a routed block of two-matrix ``relu(up x)^2`` experts
behind a sigmoid router with a selection bias beside a shared expert, or a
dense two-matrix MLP — each layer ``x + branch(norm(x))`` with one norm.
The model against the benchmark's plain reference
(``benchmark/reference/nemotron_h_ref.py``: float32, the sequential
recurrence, no kernels; it imports nothing of ``dlrover_tpu``) in hidden
states, loss and EVERY gradient leaf; the pieces against formulas written
out here; a chip's SHARE of the experts adding up to the whole layer with
the shared expert counted once; experts behind a Mamba-2 mixer in a layer of
two branches, which an earlier tree refused.

With the defaults nothing of it may show: ``tests/test_llama_mla_moe.py``
holds a dense, a routed and a looped config to the loss and gradients an
earlier commit gave, bit for bit, and runs here unchanged.  Every path that
cannot compute a new setting refuses it by name.
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import REFUSING_PATH_NAMES, refusing_calls

from dlrover_tpu.models import llama
from dlrover_tpu.ops import grouped_matmul
from dlrover_tpu.parallel.mesh import MeshSpec

acc = importlib.import_module("dlrover_tpu.parallel.accelerate")

F32 = jnp.float32
B, S, D = 2, 24, 32
EPS = 1e-5
AUX = 1e-4
PATTERN = "MEM*E-"
KINDS = {"M": "mamba", "*": "attention", "E": "moe", "-": "mlp"}


def _reference():
    """The benchmark's plain reference, found by path: it is no package of
    the program's and imports none of it."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "nemotron_h_ref.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _nano(**over):
    """Two Mamba-2 layers in four groups, one attention layer without
    rotary position, two routed layers — 16 sigmoid-routed relu2 experts
    top-3 scaled by 2.5, a selection bias, a shared expert twice as wide —
    and a dense relu2 layer, one branch each."""
    base = dict(
        vocab_size=512, n_layer=len(PATTERN), n_head=4, n_kv_head=2,
        d_model=D, d_ff=48, max_seq_len=64, dtype=F32, rms_eps=EPS,
        one_branch=True, mlp_form="relu2",
        layer_types=tuple(KINDS[c] for c in PATTERN),
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=4,
        mamba_chunk_size=16, rope=False, attn_head_dim=16,
        num_experts=16, top_k=3, d_ff_expert=24, n_shared_experts=2,
        router_score="sigmoid", routed_scaling=2.5, router_bias_rate=1e-3,
        balance_all_k=True)
    base.update(over)
    return llama.LlamaConfig(**base)


def _hf(cfg, **over):
    """``cfg`` in the HF keys the reference reads."""
    out = dict(
        hybrid_override_pattern=PATTERN, num_hidden_layers=cfg.n_layer,
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head, head_dim=cfg.head_dim,
        layer_norm_epsilon=cfg.rms_eps, mamba_num_heads=cfg.mamba_n_heads,
        mamba_head_dim=cfg.mamba_d_head, ssm_state_size=cfg.mamba_d_state,
        n_groups=cfg.mamba_n_groups, n_routed_experts=cfg.experts_here,
        published={"n_routed_experts": cfg.num_experts},
        num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling, n_group=1,
        mlp_hidden_act="relu2", tie_word_embeddings=False, rope_theta=1e4,
        moe_aux_weight=AUX)
    out.update(over)
    return out


def _tokens(seed=0, vocab=512, s=S, b=B):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (b, s + 1)).astype(np.int32))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _decisive(params, seed=7):
    """Gains, biases and the mixer's scalars moved off their neutral
    values, so that a term left out shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("ln1", "ln2", "ln_f", "norm", "D"):
            leaf = leaf + 0.3 * jax.random.normal(k, leaf.shape)
        elif name == "router_bias":
            leaf = 0.05 * jax.random.normal(k, leaf.shape)
        elif name == "router":
            leaf = leaf * 20.0  # scores spread, so that no two tie
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("fused", [False, True], ids=["logits", "fused"])
def test_the_model_matches_the_plain_reference(remat, fused):
    cfg = _nano(remat_block=remat)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = _tokens()
    hidden, aux = llama.forward_hidden(params, toks[:, :-1], cfg)
    (loss, counters), grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": toks}, cfg, moe_aux_weight=AUX,
                                fused_lm_head=fused, metrics=True),
        has_aux=True)(params)
    want_hidden, _, extra = REF.hidden_and_loss(params, toks, _hf(cfg))
    # the reference routes for itself and chooses what the model chose
    for i, chosen in aux["moe_experts"].items():
        own = extra["choices"][REF.experts_name(i)]
        assert np.array_equal(np.sort(np.asarray(chosen), -1),
                              np.sort(np.asarray(own), -1))
    (want, _), want_grads = jax.value_and_grad(
        lambda p: REF.hidden_and_loss(p, toks, _hf(cfg))[1:], has_aux=True)(
            params)
    assert _rel(hidden, want_hidden) < 1e-5
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    seen = 0
    for path, g in flat:
        if "router_bias" in jax.tree_util.keystr(path):
            continue  # chooses, never weighs: no gradient either way
        assert _rel(g, wanted[path]) < 5e-4, jax.tree_util.keystr(path)
        seen += 1
    # 2 x (norm + 8 of the mixer), 5 of attention, 2 x (norm, router, two
    # of the experts, two of the shared one), 3 of the dense layer, and the
    # embedding, the head and the final norm
    assert seen == 2 * 9 + 5 + 2 * 6 + 3 + 3
    assert counters["moe_tokens_per_expert"].shape == (2, 16)
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(1).tolist() == [
        B * S * 3] * 2
    assert counters["ssm_state_rms"].shape == (2,)
    assert float(counters["moe_router_bias_abs_max"]) > 0


def test_the_balance_term_enters_the_loss_at_its_weight():
    cfg = _nano()
    params = _decisive(llama.init_params(jax.random.PRNGKey(1), cfg))
    toks = _tokens(1)
    _, aux = llama.forward_hidden(params, toks[:, :-1], cfg)
    extra = REF.hidden_and_loss(params, toks, _hf(cfg))[2]
    assert float(AUX * aux["moe_aux"]) == pytest.approx(
        float(extra["scalars"]["moe_aux"]), rel=1e-5)
    with_term = llama.loss_fn(params, {"tokens": toks}, cfg,
                              moe_aux_weight=AUX)
    without = llama.loss_fn(params, {"tokens": toks}, cfg, moe_aux_weight=0.)
    assert float(with_term - without) == pytest.approx(
        float(AUX * aux["moe_aux"]), rel=1e-3)


@pytest.mark.parametrize("planted", REF.PLANTED)
def test_a_planted_fault_moves_the_reference_away(planted):
    """What the benchmark's comparison must find, at toy widths in float32:
    the reference with the fault is no longer the model — in the hidden
    states, or, for the rotary embedding, which a softmax as flat as an
    initialised one hides from the stream, in the gradient of ``wq``."""
    cfg = _nano()
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = _tokens()
    hidden, aux = llama.forward_hidden(params, toks[:, :-1], cfg)
    given = {REF.experts_name(i): e for i, e in aux["moe_experts"].items()}

    def reference(ref_cfg):
        def loss(wq):
            layers = list(params["layers"])
            layers[3] = dict(layers[3], wq=wq)
            hidden, loss, _ = REF.hidden_and_loss(
                dict(params, layers=layers), toks, ref_cfg, given=given)
            return loss, hidden
        (_, hidden), grad = jax.value_and_grad(loss, has_aux=True)(
            params["layers"][3]["wq"])
        return hidden, grad

    true_hidden, true_grad = reference(_hf(cfg))
    got_hidden, got_grad = reference(_hf(cfg, planted=planted))
    assert _rel(hidden, true_hidden) < 1e-5
    if planted == "rope_on":
        assert _rel(got_grad, true_grad) > 1e-1
    else:
        assert _rel(hidden, got_hidden) > 1e-2


def test_an_unknown_planted_fault_is_refused():
    cfg = _nano()
    with pytest.raises(ValueError, match="unknown planted fault"):
        REF.hidden_and_loss(None, _tokens(), _hf(cfg, planted="nothing"))


# -- one branch a layer -------------------------------------------------------


def test_a_layer_holds_one_norm_and_one_branch():
    cfg = _nano()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    mamba, routed, _, attention, _, dense = params["layers"]
    assert set(mamba) == {"ln1", "ssm"}
    assert set(attention) == {"ln1", "wq", "wk", "wv", "wo"}
    assert set(routed) == {"ln2", "moe"}
    assert set(routed["moe"]) == {"router", "router_bias", "wi", "wo",
                                  "shared"}
    assert set(routed["moe"]["shared"]) == {"w_up", "w_down"}
    assert set(dense) == {"ln2", "mlp"} and set(dense["mlp"]) == {
        "w_up", "w_down"}
    assert [cfg.mixer_kind(i) for i in range(6)] == [
        "mamba", None, "mamba", "attention", None, None]
    assert [cfg.mlp_routed(i) for i in range(6)] == [
        None, True, None, None, True, False]
    assert (cfg.ssm_layers, cfg.attention_layers, cfg.moe_layers,
            cfg.block_applications) == (2, 1, 2, 1)
    # the axes name every leaf, and nothing else
    axes = llama.param_logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == (
            jax.tree_util.tree_structure(jax.tree_util.tree_map(
                lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))))
    for leaf, names in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(
                axes, is_leaf=lambda a: isinstance(a, tuple))):
        assert len(names) == leaf.ndim


@pytest.mark.parametrize("i", range(len(PATTERN)))
def test_a_layer_is_its_branch_behind_its_norm(i):
    """``block_apply`` on layer ``i`` is ``x + branch(rms(x) * w)``, the
    branch computed alone."""
    cfg = _nano()
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    layer = params["layers"][i]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    got, stats = llama.block_apply(layer, x, cfg, positions)

    def rms(w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + EPS) * w

    kind = PATTERN[i]
    if kind == "M":
        branch, _ = llama._ssm_mixer(rms(layer["ln1"]), layer["ssm"], cfg)
        assert set(stats) == {"ssm_state_rms", "ssm_decay_min"}
    elif kind == "*":
        branch = llama._attention(rms(layer["ln1"]), layer, cfg, positions,
                                  "auto", None)
        assert stats == {}
    elif kind == "E":
        branch, _ = llama._moe_swiglu(rms(layer["ln2"]), layer["moe"], cfg)
        assert {"moe_aux", "experts", "tokens_per_expert"} <= set(stats)
    else:
        branch = llama._relu2(rms(layer["ln2"]), layer["mlp"], F32)
        assert stats == {}
    assert _rel(got, x + branch) < 1e-6


# -- the mixer in groups ------------------------------------------------------


@pytest.mark.parametrize("groups", [1, 2, 4, 8])
def test_the_grouped_norm_equals_a_loop_over_groups(groups):
    """``ops.gated_norm``'s ``jax.numpy`` form, gate then norm, as the mixer
    calls it where it has more groups than one."""
    from dlrover_tpu.ops.gated_norm import gated_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, 64)) * 3.0
    z = jax.random.normal(jax.random.PRNGKey(2), (B, S, 64))
    gain = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    norm = lambda groups: gated_norm(  # noqa: E731
        x, z, gain, group=64 // groups, eps=EPS, gate_first=True)
    got, gated = norm(groups), x * jax.nn.silu(z)
    width = 64 // groups
    parts = []
    for g in range(groups):
        part = gated[..., g * width:(g + 1) * width]
        parts.append(part / jnp.sqrt(
            jnp.mean(part * part, -1, keepdims=True) + EPS))
    want = jnp.concatenate(parts, -1) * gain
    assert _rel(got, want) < 1e-6
    if groups > 1:  # and it is not the norm over the whole width
        assert _rel(got, norm(1)) > 1e-2


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_mixer_in_groups_equals_the_loop_over_positions(groups):
    """``_ssm_mixer`` with B, C and the gated norm in ``groups`` groups
    against the reference's mixer (the sequential recurrence, the norm
    group by group), values and every gradient."""
    cfg = _nano(mamba_n_groups=groups)
    ssm = _decisive(
        {"ssm": llama._init_ssm(jax.random.PRNGKey(2), cfg)})["ssm"]
    u = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    hf = _hf(cfg)

    def plain(u, ssm):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([REF._mamba(row, ssm, hf, None, 8) for row in u])

    weights = jax.random.normal(jax.random.PRNGKey(5), (B, S, D))
    got, got_grads = jax.value_and_grad(
        lambda u, p: jnp.sum(llama._ssm_mixer(u, p, cfg)[0] * weights),
        argnums=(0, 1))(u, ssm)
    want, want_grads = jax.value_and_grad(
        lambda u, p: jnp.sum(plain(u, p) * weights), argnums=(0, 1))(u, ssm)
    assert _rel(llama._ssm_mixer(u, ssm, cfg)[0], plain(u, ssm)) < 2e-5
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-4)
    assert _rel(got_grads[0], want_grads[0]) < 5e-4
    for name in ssm:
        assert _rel(got_grads[1][name], want_grads[1][name]) < 5e-4, name


def test_the_inner_width_is_heads_times_head_size():
    """64 heads of 64 beside a stream of 2,688: 4,096, not ``mamba_expand``
    x ``d_model`` = 5,376, which an earlier tree demanded."""
    cfg = llama.LlamaConfig(
        d_model=2688, n_layer=1, layer_types=("mamba",), mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128, mamba_n_groups=8)
    assert (cfg.mamba_expand * cfg.d_model, cfg.mamba_d_inner,
            cfg.mamba_conv_dim) == (5376, 4096, 6144)
    shapes = jax.eval_shape(
        lambda: llama._init_ssm(jax.random.PRNGKey(0), cfg))
    assert shapes["in_proj"].shape == (2688, 10304)
    assert shapes["out_proj"].shape == (4096, 2688)
    assert shapes["norm"].shape == (4096,)


# -- the two-matrix MLP -------------------------------------------------------


def test_relu2_is_two_matrices_and_the_leaves_say_the_form():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (B, S, D))
    mlp = {"w_up": jax.random.normal(k[1], (D, 40)) * .2,
           "w_down": jax.random.normal(k[2], (40, D)) * .2}
    want = jnp.square(jnp.maximum(x @ mlp["w_up"], 0)) @ mlp["w_down"]
    assert _rel(llama._relu2(x, mlp, F32), want) < 1e-6
    assert _rel(llama._mlp(x, mlp, F32), want) < 1e-6
    gated = dict(mlp, w_gate=jax.random.normal(k[3], (D, 40)) * .2)
    assert _rel(llama._mlp(x, gated, F32),
                llama._swiglu(x, gated, F32)) == 0.0
    assert _rel(llama._mlp(x, gated, F32), want) > 1e-2


def test_relu2_leaves_draw_from_the_keys_the_gated_forms_do():
    """The form takes a leaf away and changes no draw: a SwiGLU model's
    ``w_up``, ``w_down``, ``wi``, ``wo`` are the two-matrix model's."""
    two = llama.init_params(jax.random.PRNGKey(0), _nano())
    three = llama.init_params(jax.random.PRNGKey(0), _nano(mlp_form="swiglu"))
    for a, b in zip(two["layers"], three["layers"]):
        for part in ("mlp", "moe"):
            if part not in a:
                continue
            assert set(b[part]) - set(a[part]) == {
                "w_gate" if part == "mlp" else "wg"}
            for name in a[part]:
                if name == "shared":
                    assert set(b[part][name]) - set(a[part][name]) == {
                        "w_gate"}
                    for leaf in a[part][name]:
                        assert np.array_equal(a[part][name][leaf],
                                              b[part][name][leaf])
                else:
                    assert np.array_equal(a[part][name], b[part][name])


def _routed_layer(cfg, seed=0):
    params = _decisive(llama.init_params(jax.random.PRNGKey(seed), cfg))
    return params["layers"][1]["moe"]


def _routed_whole(y, moe, cfg, experts=None, scaling=None):
    """The uncut formula, expert by expert: ``sum_e w_e down_e relu(up_e
    y)^2`` over ``experts`` (None: all) + the shared expert."""
    s = jax.nn.sigmoid(y @ moe["router"])
    _, idx = jax.lax.top_k(s + moe["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * (
        cfg.routed_scaling if scaling is None else scaling)
    combine = jnp.sum(
        w[..., None] * jax.nn.one_hot(idx, cfg.num_experts), -2)
    out = jnp.zeros_like(y)
    for e in (range(cfg.num_experts) if experts is None else experts):
        out = out + combine[..., e, None] * (
            jnp.square(jnp.maximum(y @ moe["wi"][e], 0)) @ moe["wo"][e])
    shared = moe["shared"]
    return out + jnp.square(
        jnp.maximum(y @ shared["w_up"], 0)) @ shared["w_down"]


def test_the_routed_block_is_the_formula_and_the_bias_never_weighs():
    cfg = _nano()
    moe = _routed_layer(cfg)
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, stats = llama._moe_swiglu(y, moe, cfg)
    assert _rel(got, _routed_whole(y, moe, cfg)) < 1e-5
    assert _rel(got, _routed_whole(y, moe, cfg, scaling=1.0)) > 1e-2
    # a bias that changes no choice changes nothing
    lifted = dict(moe, router_bias=moe["router_bias"] + 3.0)
    assert _rel(llama._moe_swiglu(y, lifted, cfg)[0], got) < 1e-6
    assert np.array_equal(llama._moe_swiglu(y, lifted, cfg)[1]["experts"],
                          stats["experts"])


@pytest.mark.parametrize("first", [0, 4, 12])
def test_a_share_computes_its_own_experts_part(first):
    cfg = _nano(experts_held=4, experts_held_first=first)
    moe = _routed_layer(_nano())
    held = dict(moe, **{k: moe[k][first:first + 4] for k in ("wi", "wo")})
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, stats = llama._moe_swiglu(y, held, cfg)
    want = _routed_whole(y, moe, cfg, experts=range(first, first + 4))
    assert _rel(got, want) < 1e-5
    per_expert = np.asarray(stats["tokens_per_expert"])
    assert int(stats["held_pairs"]) == per_expert[first:first + 4].sum()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 experts in 16 shares of one (the deployment's sixteen chips, 8 of
    128 each): the ROUTED parts that the sixteen chips compute, with what
    every chip computes alike — the shared expert — counted ONCE, add up to
    the whole layer of the uncut formula."""
    whole = _nano()
    moe = _routed_layer(whole)
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    alike = _routed_whole(y, moe, whole, experts=())  # the shared part
    total = alike
    for first in range(16):
        cfg = _nano(experts_held=1, experts_held_first=first)
        held = dict(moe, **{k: moe[k][first:first + 1]
                            for k in ("wi", "wo")})
        part, stats = llama._moe_swiglu(y, held, cfg)
        total = total + (part - alike)  # this chip's routed part alone
        assert stats["tokens_per_expert"].shape == (16,)
    assert _rel(total, _routed_whole(y, moe, whole)) < 1e-5
    assert _rel(total, llama._moe_swiglu(y, moe, whole)[0]) < 1e-5


def test_a_share_of_the_model_matches_the_reference_under_the_share():
    """Four of sixteen experts held: model and reference leave out what the
    absent twelve would add, alike, through every layer."""
    cfg = _nano(experts_held=4)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    assert params["layers"][1]["moe"]["wi"].shape[0] == 4
    toks = _tokens()
    hidden, aux = llama.forward_hidden(params, toks[:, :-1], cfg)
    want, _, _ = REF.hidden_and_loss(params, toks, _hf(cfg))
    assert _rel(hidden, want) < 1e-5
    assert aux["moe_held_pairs"].shape == (2,)
    whole = REF.hidden_and_loss(
        _decisive(llama.init_params(jax.random.PRNGKey(0), _nano())), toks,
        _hf(_nano()))[0]
    assert _rel(hidden, whole) > 1e-2


# -- experts behind a Mamba-2 mixer, two branches a layer ----------------------


def _two_branch(**over):
    base = dict(
        vocab_size=512, n_layer=3, n_head=4, n_kv_head=2, d_model=D, d_ff=48,
        max_seq_len=64, dtype=F32, rms_eps=EPS,
        layer_types=("mamba", "mamba", "attention"), mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16,
        num_experts=4, top_k=2, moe_every=1, d_ff_expert=24)
    base.update(over)
    return llama.LlamaConfig(**base)


@pytest.mark.parametrize("form", llama.MLP_FORMS)
def test_experts_follow_a_mamba_mixer(form):
    """What ``LlamaConfig`` refused by name until now: a layer whose mixer
    is the state-space one and whose MLP is routed is ``x1 = x +
    mixer(norm1(x))``, ``x1 + routed(norm2(x1))``, each half the one
    tested alone; the step differentiates and reports both halves'
    counters."""
    cfg = _two_branch(mlp_form=form)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    layer = params["layers"][0]
    assert {"ln1", "ssm", "ln2", "moe"} == set(layer)
    assert ("wg" in layer["moe"]) == (form == "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    got, stats = llama.block_apply(layer, x, cfg, positions)

    def rms(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + EPS) * w

    x1 = x + llama._ssm_mixer(rms(x, layer["ln1"]), layer["ssm"], cfg)[0]
    want = x1 + llama._moe_swiglu(rms(x1, layer["ln2"]), layer["moe"], cfg)[0]
    assert _rel(got, want) < 1e-6
    assert {"ssm_state_rms", "moe_aux", "tokens_per_expert"} <= set(stats)
    (loss, counters), grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": _tokens()}, cfg, metrics=True),
        has_aux=True)(params)
    assert np.isfinite(float(loss))
    assert counters["ssm_state_rms"].shape == (2,)
    assert counters["moe_tokens_per_expert"].shape == (3, 4)
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads["layers"][0]))


def test_a_swiglu_expert_is_not_a_relu2_expert():
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    relu2 = _two_branch(mlp_form="relu2")
    swiglu = _two_branch(mlp_form="swiglu")
    moe = llama.init_params(jax.random.PRNGKey(0), swiglu)["layers"][0]["moe"]
    two = {k: v for k, v in moe.items() if k != "wg"}
    assert _rel(llama._moe_swiglu(y, two, relu2)[0],
                llama._moe_swiglu(y, moe, swiglu)[0]) > 1e-1


# -- settings, counts, refusals ------------------------------------------------


def test_defaults_are_todays_and_name_no_one_branch_layer():
    cfg = llama.LlamaConfig()
    assert (cfg.one_branch, cfg.mlp_form) == (False, "swiglu")
    assert llama.MLP_KINDS == ("mlp", "moe")
    assert llama.MLP_FORMS == ("swiglu", "relu2")
    tiny = llama.LlamaConfig.tiny()
    assert [tiny.mlp_routed(i) for i in range(2)] == [False, False]
    assert set(llama.init_params(jax.random.PRNGKey(0), tiny)["layers"][0][
        "mlp"]) == {"w_gate", "w_up", "w_down"}
    assert llama.program_facts(tiny, 64) == {}


def test_published_keys_count_the_parameters_of_the_cut():
    """Nemotron-3-Nano-30B-A3B's widths, published layers 0-8, 8 of 128
    experts and 1/8 of the vocabulary, from shapes alone: the table of the
    configuration file."""
    cfg = llama.LlamaConfig(
        vocab_size=16384, n_layer=9, n_head=32, n_kv_head=2, d_model=2688,
        d_ff=1856, max_seq_len=8192, one_branch=True, mlp_form="relu2",
        layer_types=tuple(KINDS[c] for c in "MEMEM*EME"),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=8, mamba_chunk_size=128, rope=False,
        attn_head_dim=128, num_experts=128, top_k=6, d_ff_expert=1856,
        n_shared_experts=2, router_score="sigmoid", routed_scaling=2.5,
        router_bias_rate=1e-3, balance_all_k=True, experts_held=8)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert llama.num_params(shapes) == 666_963_456
    assert [llama.num_params(layer) for layer in shapes["layers"]] == [
        38_744_896, 100_125_440, 38_744_896, 100_125_440, 38_744_896,
        23_399_040, 100_125_440, 38_744_896, 100_125_440]
    moe = shapes["layers"][1]["moe"]
    assert moe["wi"].shape == (8, 2688, 1856)
    assert moe["router"].shape == (2688, 128)
    assert moe["shared"]["w_up"].shape == (2688, 3712)
    assert llama.program_facts(cfg, 8192) == {
        "ssm_layers": 4, "attention_layers": 1, "moe_layers": 4,
        "ssm_chunks_per_sequence": 64, "mlp_form": "relu2",
        "moe_expert_backend": "reference"}
    assert llama._moe_buffer_bounds(4 * 8192, 6, 128, 8) == (15360, 196608)
    # 6 x the matmul parameters a token meets (0.375 held picks a routed
    # layer), the one layer's attention over max_seq_len keys, the scan
    routed = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
    matmul = (4 * (2688 * 10304 + 4096 * 2688)
              + 2 * 2688 * 4096 + 2 * 2688 * 256 + 4 * routed
              + 2 * 16384 * 2688)
    want = (6.0 * matmul + 6.0 * 2 * 8192 * 4096
            + 3.0 * 4 * (4 * 4096 * 128 + 2 * 4 * 6144))
    assert llama.flops_per_token(cfg) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("form,mats", [("swiglu", 3), ("relu2", 2)])
def test_flops_count_the_matrices_of_the_form(form, mats):
    dense = llama.LlamaConfig.tiny(mlp_form=form)
    other = llama.LlamaConfig.tiny(mlp_form=form, d_ff=2 * 128)
    assert llama.flops_per_token(other) - llama.flops_per_token(dense) == (
        pytest.approx(6.0 * 2 * mats * 64 * 128))


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("mamba", "moe", "attention")), "n_layer=6"),
    (dict(layer_types=("mamba", "moe", "mamba", "attention", "moe",
                       "dense")), "layer_types"),
    (dict(one_branch=False), "under one_branch"),
    (dict(mlp_form="gelu"), "mlp_form='gelu'"),
    (dict(num_experts=0), "one_branch with layer_types"),
    (dict(layer_types=("mamba", "mlp", "mamba", "attention", "mlp", "mlp")),
     "one_branch with layer_types"),
    (dict(layer_types=()), "n_layer=6"),
    (dict(loop_passes=2, exit_gate_beta=0.1), "loop_passes=2"),
    (dict(mtp_layers=1), "mtp_layers=1"),
    (dict(branch_norm=True), "branch_norm=True"),
    (dict(mamba_n_heads=0), "mamba_n_heads"),
    (dict(mamba_n_groups=3), "mamba_n_groups=3"),
])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        _nano(**over)


#: each new setting alone, on a config every other row of the table lets by
NEW_SETTINGS = {
    "one_branch": (dict(one_branch=True, layer_types=("attention", "mlp")),
                   "one_branch=True", "layers that are one branch each"),
    "mlp_form": (dict(mlp_form="relu2"), "mlp_form='relu2'",
                 "an MLP that is not SwiGLU"),
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


def test_the_table_of_refusals_gained_a_row_a_setting():
    names = [row[0] for row in llama.TRAINING_PATH_ONLY]
    assert names[18:20] == ["one_branch", "mlp_form"]
    assert len(names) == len(set(names)) >= 20
    for name, computed, _ in llama.TRAINING_PATH_ONLY:
        if name != "layer_types":
            assert getattr(llama.LlamaConfig(), name) == computed, name


@pytest.mark.parametrize("kw", [
    dict(segment_ids=np.zeros((B, S), np.int32)),
    dict(attn_fn=lambda *a: None)], ids=["segment_ids", "attn_fn"])
def test_a_one_branch_mamba_layer_refuses_what_its_scan_does_not_know(kw):
    cfg = _nano()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with pytest.raises(NotImplementedError, match="'mamba' layer"):
        llama.block_apply(params["layers"][0], x, cfg, positions, **kw)


# -- the expert width the grouped kernel does not take -------------------------


@pytest.mark.parametrize("k,n,takes", [
    (2048, 1024, True), (2688, 1920, True), (2688, 1856, False),
    (1856, 2688, False), (2048, 64, False)])
def test_the_grouped_kernel_takes_whole_lane_vectors(k, n, takes,
                                                     monkeypatch):
    bf16 = jnp.bfloat16
    assert grouped_matmul.kernel_takes(bf16, 512, k, n) is takes
    assert not grouped_matmul.kernel_takes(bf16, 500, 2048, 1024)
    assert not grouped_matmul.kernel_takes(F32, 512, 2048, 1024)
    assert grouped_matmul.backend_for(bf16, k, n) == "reference"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    symmetric = takes and grouped_matmul.kernel_takes(bf16, 512, n, k)
    assert grouped_matmul.backend_for(bf16, k, n) == (
        "pallas" if symmetric else "reference")


def test_an_expert_1856_wide_runs_unpadded_through_the_ragged_dot():
    """Rows in groups through ``[8, 64, 1856]``: the dispatcher's own
    choice is the reference at this width, on any device, and the result
    is the per-group product."""
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(k[0], (512, 64)).astype(jnp.bfloat16)
    w = (jax.random.normal(k[1], (8, 64, 1856)) * .1).astype(jnp.bfloat16)
    sizes = jnp.asarray([64] * 8, jnp.int32)
    assert not grouped_matmul._kernel_fits(rows, w)
    out = grouped_matmul.grouped_matmul_ragged(rows, w, sizes)
    want = jnp.concatenate([
        rows[e * 64:(e + 1) * 64].astype(F32) @ w[e].astype(F32)
        for e in range(8)])
    assert out.shape == (512, 1856)
    assert _rel(out.astype(F32), want) < 1e-2


# -- the step: scopes, counters -----------------------------------------------


def test_the_step_journals_the_scopes_the_facts_and_the_counters():
    cfg = _nano(remat_block=True, experts_held=4)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=AUX,
                             metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    loss.program_facts = llama.program_facts(cfg, S)
    job = acc.accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(dp=1)), param_specs="planner",
        devices=jax.devices()[:1])
    assert len(loss.rule_leaves) == 2
    program = job.program
    assert (program["ssm_layers"], program["attention_layers"],
            program["moe_layers"], program["mlp_layers"]) == (2, 1, 2, 1)
    assert (program["mlp_form"], program["moe_expert_backend"]) == (
        "relu2", "reference")
    found = {tuple(v) for v in program["scopes"].values()}
    assert {("forward", "ssm"), ("backward", "ssm"),
            ("forward", "attention"), ("forward", "moe_experts"),
            ("backward", "moe_router"), ("forward", "mlp"),
            ("forward", "lm_head_loss")} <= found
    inner = set(program["subscopes"].values())
    assert {"ssm_in", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_out"} <= inner
    state = job.create_state(jax.random.PRNGKey(0))
    bias = np.asarray(state["params"]["layers"][1]["moe"]["router_bias"])
    state, metrics = job.train_step(state, {"tokens": _tokens()})
    assert {"ssm_state_rms", "ssm_decay_min", "moe_aux",
            "moe_tokens_per_expert", "moe_held_pairs",
            "moe_router_bias_abs_max"} <= set(metrics)
    assert float(metrics["moe_router_bias_abs_max"]) == pytest.approx(1e-3)
    moved = state["params"]["layers"][1]["moe"]["router_bias"]
    assert float(jnp.abs(moved - bias).max()) == pytest.approx(1e-3)
