"""Gated delta-rule layers beside output-gated attention on the normal path
(the Qwen3-Next block): the delta-rule mixer against a loop over positions,
forward and every gradient; the three changes to attention — the output
gate, rotation of a part of each head, gains stored as ``1 + w`` — one by
one against their absence; the gate on the shared expert; experts beside the
new mixer; a chip's SHARE of the experts adding up to the whole layer with
the shared expert counted once — each against a plain formula written out
here, in float32 on seeded weights.

With the defaults nothing of it may show: ``tests/test_llama_mla_moe.py``
holds a dense, a routed and a looped config to the loss and gradients an
earlier commit gave, bit for bit, and runs here unchanged.  Every path that
cannot compute a new setting refuses it by name.
"""

import dataclasses
import functools
import importlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import REFUSING_PATH_NAMES, refusing_calls

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import MeshSpec

acc = importlib.import_module("dlrover_tpu.parallel.accelerate")

F32 = jnp.float32
B, S, D = 2, 24, 32
EPS = 1e-6


def _next(**over):
    """Three delta-rule layers and one gated attention layer, every layer
    routed: 16 experts top-3 behind a softmax router, a gated shared
    expert, ``1 + w`` gains, a quarter of each head of 16 rotated, an
    untied head."""
    base = dict(
        vocab_size=512, n_layer=4, n_head=4, n_kv_head=2, d_model=D,
        d_ff=64, max_seq_len=64, dtype=F32, rms_eps=EPS, rope_theta=1e4,
        layer_types=("linear_attention",) * 3 + ("attention",),
        gdn_k_heads=2, gdn_v_heads=4, gdn_d_head=8, gdn_d_conv=4,
        attn_head_dim=16, attn_output_gate=True, partial_rotary_factor=0.25,
        norm_plus_one=True, qk_norm=True, qk_norm_per_head=True,
        num_experts=16, top_k=3, moe_every=1, d_ff_expert=16,
        n_shared_experts=1, shared_expert_gate=True, balance_all_k=True)
    base.update(over)
    return llama.LlamaConfig(**base)


def _tokens(seed=0, vocab=512, s=S, b=B):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (b, s + 1)).astype(np.int32))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _decisive(params, seed=0):
    """Gains off their initial value, a router 40 times and the delta
    rule's projections 10 times larger: at initialisation the softmax over
    the experts is flat, ``1 + w`` is 1 whatever reads it and the rule's
    state stays near empty."""
    key = jax.random.PRNGKey(seed)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        # crc32, not hash(): a str's hash differs from process to process,
        # and with it the draws (PYTHONHASHSEED=1 draws a near-tie in a
        # router whose pick flips between the two formulations)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        if name.endswith("['router']"):
            return 40.0 * a
        if name.endswith("['in_proj_qkvz']") or name.endswith(
                "['in_proj_ba']") or name.endswith("['shared_gate']"):
            return 10.0 * a
        if a.ndim == 1 and not name.endswith("['A_log']"):
            return a + 0.3 * jax.random.normal(k, a.shape)
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


# -- the plain formulas -------------------------------------------------------


def _norm0(x, w, plus_one=True):
    gain = 1.0 + w if plus_one else w
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * gain


def _gdn_by_position(u, gdn, cfg, norm_first=True):
    """The mixer one position at a time, as the published block writes it:
    the split per KEY head, the convolution over the flattened ``[q | k |
    v]``, the repeat of each key head under its value heads, the L2 norms,
    the recurrence, the norm BEFORE the gate."""
    b, s, _ = u.shape
    hk, hv, d = cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_d_head
    r, taps = hv // hk, cfg.gdn_d_conv
    qkvz = jnp.einsum("bsd,de->bse", u, gdn["in_proj_qkvz"],
                      precision="highest").reshape(b, s, hk, (2 + 2 * r) * d)
    ba = jnp.einsum("bsd,de->bse", u, gdn["in_proj_ba"],
                    precision="highest").reshape(b, s, hk, 2 * r)
    q, k = qkvz[..., :d], qkvz[..., d:2 * d]
    v = qkvz[..., 2 * d:(2 + r) * d]
    z = qkvz[..., (2 + r) * d:].reshape(b, s, hv, d)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, s, hv)
    a = ba[..., r:].reshape(b, s, hv)
    mixed = jnp.concatenate([x.reshape(b, s, -1) for x in (q, k, v)], -1)
    rows = []
    for t in range(s):
        c = jnp.zeros_like(mixed[:, 0])
        for tap in range(taps):
            src = t - (taps - 1) + tap
            if src >= 0:
                c = c + gdn["conv_w"][tap] * mixed[:, src]
        rows.append(jax.nn.silu(c))
    mixed = jnp.stack(rows, 1)
    q = mixed[..., :hk * d].reshape(b, s, hk, d)
    k = mixed[..., hk * d:2 * hk * d].reshape(b, s, hk, d)
    v = mixed[..., 2 * hk * d:].reshape(b, s, hv, d)
    g = -jnp.exp(gdn["A_log"]) * jax.nn.softplus(a + gdn["dt_bias"])
    q, k = jnp.repeat(q, r, 2), jnp.repeat(k, r, 2)
    unit = lambda x: x / jnp.sqrt(  # noqa: E731
        jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / np.sqrt(d), unit(k)
    state = jnp.zeros((b, hv, d, d), F32)
    outs = []
    for t in range(s):
        state = jnp.exp(g[:, t])[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k[:, t], state,
                          precision="highest")
        write = beta[:, t][..., None] * (v[:, t] - held)
        state = state + k[:, t][..., :, None] * write[..., None, :]
        outs.append(jnp.einsum("bhk,bhkv->bhv", q[:, t], state,
                               precision="highest"))
    o = jnp.stack(outs, 1)
    rms = lambda x: x / jnp.sqrt(  # noqa: E731
        jnp.mean(x * x, -1, keepdims=True) + EPS)
    y = (gdn["norm"] * rms(o) * jax.nn.silu(z) if norm_first
         else gdn["norm"] * rms(o * jax.nn.silu(z)))
    return jnp.einsum("bse,ed->bsd", y.reshape(b, s, hv * d),
                      gdn["out_proj"], precision="highest")


def _rope(x, theta, rotary):
    s, half = x.shape[1], rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def _attention_plain(u, layer, cfg, gate=True, rotary=None, plus_one=True):
    """GQA: each head's columns of ``wq`` are ``[q | gate]`` (with ``gate``),
    q and k normed head by head with the gain ``1 + w`` (with
    ``plus_one``), the first ``rotary`` dims of a head rotated, causal
    softmax at ``head_dim^-1/2``, the output times ``sigmoid(gate)``."""
    b, s, _ = u.shape
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    rotary = hd // 4 if rotary is None else rotary
    q = (u @ layer["wq"]).reshape(b, s, h, -1)
    q, g = (q[..., :hd], q[..., hd:]) if gate else (q, None)
    k = (u @ layer["wk"]).reshape(b, s, kv, hd)
    v = (u @ layer["wv"]).reshape(b, s, kv, hd)
    q = _norm0(q, layer["q_norm"], plus_one)
    k = _norm0(k, layer["k_norm"], plus_one)
    q, k = _rope(q, cfg.rope_theta, rotary), _rope(k, cfg.rope_theta, rotary)
    k, v = jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                                 -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if gate:
        out = out * jax.nn.sigmoid(g)
    return out.reshape(b, s, h * hd) @ layer["wo"]


def _routed_whole(y, moe, cfg, experts=None, shared=True, shared_gate=True):
    """The routed block over ``experts`` (default: all of them), every
    expert over every token with the weight 0 where it was not chosen, and
    (with ``shared``) the shared expert behind its gate."""
    p = jax.nn.softmax(y @ moe["router"], -1)
    w, chosen = jax.lax.top_k(p, cfg.top_k)
    w = w / jnp.sum(w, -1, keepdims=True)
    combine = jnp.sum(w[..., None] * jax.nn.one_hot(
        chosen, cfg.num_experts, dtype=F32), -2)
    out = jnp.zeros_like(y)
    for e in (range(cfg.num_experts) if experts is None else experts):
        hidden = jax.nn.silu(y @ moe["wg"][e]) * (y @ moe["wi"][e])
        out = out + combine[..., e, None] * (hidden @ moe["wo"][e])
    if shared:
        sh = moe["shared"]
        part = (jax.nn.silu(y @ sh["w_gate"]) * (y @ sh["w_up"])) @ sh[
            "w_down"]
        if shared_gate:
            part = jax.nn.sigmoid(y @ moe["shared_gate"]) * part
        out = out + part
    taken = jax.nn.one_hot(chosen, cfg.num_experts, dtype=F32)
    balance = cfg.num_experts * jnp.sum(
        jnp.mean(taken, (0, 1, 2)) * jnp.mean(p, (0, 1)))
    return out, balance


def _plain_loss(params, toks, cfg, aux_weight):
    """The whole model by the equations, float32."""
    inp, tgt = toks[:, :-1], toks[:, 1:]
    balance = 0.0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for layer, kind in zip(params["layers"], cfg.layer_types):
            u = _norm0(x, layer["ln1"])
            x = x + (_attention_plain(u, layer, cfg) if kind == "attention"
                     else _gdn_by_position(u, layer["gdn"], cfg))
            out, bal = _routed_whole(
                _norm0(x, layer["ln2"]), layer["moe"], cfg)
            x, balance = x + out, balance + bal
        logp = jax.nn.log_softmax(
            _norm0(x, params["ln_f"]) @ params["lm_head"], -1)
    return (-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))
            + aux_weight * balance)


# -- the mixer ----------------------------------------------------------------


def _gdn_leaves(seed=0, **over):
    cfg = _next(**over)
    gdn = llama._init_gdn(jax.random.PRNGKey(seed), cfg)
    # N(0, 0.02) projections give keys and values of 1e-2: 25 times larger
    # the gates leave 1/2, the decays differ and every term of a gradient
    # shows; a gain off one tells the norm's place
    gdn = dict(gdn, in_proj_qkvz=25.0 * gdn["in_proj_qkvz"],
               in_proj_ba=25.0 * gdn["in_proj_ba"],
               out_proj=25.0 * gdn["out_proj"],
               norm=1.0 + 0.3 * jnp.cos(jnp.arange(cfg.gdn_d_head, dtype=F32)))
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, D))
    return cfg, gdn, u


@pytest.mark.parametrize("s", [S, 64, 70])
def test_the_mixer_equals_the_loop_over_positions(s):
    cfg, gdn, _ = _gdn_leaves()
    u = jax.random.normal(jax.random.PRNGKey(3), (B, s, D))
    got, stats = llama._gdn_mixer(u, gdn, cfg)
    assert _rel(got, _gdn_by_position(u, gdn, cfg)) < 2e-5
    assert sorted(stats) == ["gdn_decay_min", "gdn_state_rms"]
    assert 0.0 < float(stats["gdn_state_rms"]) < 10.0
    assert 0.0 <= float(stats["gdn_decay_min"]) <= 1.0


@pytest.mark.parametrize("leaf", [
    "u", "in_proj_qkvz", "in_proj_ba", "conv_w", "A_log", "dt_bias", "norm",
    "out_proj"])
def test_the_mixer_has_the_loops_gradients(leaf):
    cfg, gdn, u = _gdn_leaves()

    def scalar(fn):
        def of(value):
            if leaf == "u":
                return jnp.sum(jnp.sin(fn(value, gdn)))
            return jnp.sum(jnp.sin(fn(u, dict(gdn, **{leaf: value}))))
        return of

    at = u if leaf == "u" else gdn[leaf]
    got = jax.grad(scalar(lambda u, g: llama._gdn_mixer(u, g, cfg)[0]))(at)
    want = jax.grad(scalar(lambda u, g: _gdn_by_position(u, g, cfg)))(at)
    assert _rel(got, want) < 1e-4


def test_the_mixer_is_causal():
    cfg, gdn, u = _gdn_leaves()
    later = u.at[:, S // 2:].add(1.0)
    a = llama._gdn_mixer(u, gdn, cfg)[0]
    b = llama._gdn_mixer(later, gdn, cfg)[0]
    assert float(jnp.max(jnp.abs(a[:, :S // 2] - b[:, :S // 2]))) < 1e-6
    assert float(jnp.max(jnp.abs(a[:, S // 2:] - b[:, S // 2:]))) > 1e-3


def test_the_norm_comes_before_the_gate_and_its_gain_is_plain():
    """Mamba-2's mixer in the tree gates first: here the two orders are
    told apart, and the gain is the leaf itself (not ``1 + w``), whatever
    ``norm_plus_one`` says of the block's norms."""
    cfg, gdn, u = _gdn_leaves()
    got = llama._gdn_mixer(u, gdn, cfg)[0]
    assert _rel(got, _gdn_by_position(u, gdn, cfg, norm_first=False)) > 5e-2
    other = dataclasses.replace(cfg, norm_plus_one=False)
    assert _rel(llama._gdn_mixer(u, gdn, other)[0], got) == 0.0


def test_a_key_head_serves_its_own_value_heads():
    """``repeat_interleave``: key head j under value heads ``j R .. j R + R
    - 1``.  With the first key head's q columns zero its query is zero
    behind the convolution (``silu(0) = 0``): the first ``R`` value heads
    put out nothing and the others do, read through an ``out_proj`` that is
    the identity (4 value heads of 8 = the stream's 32)."""
    cfg, gdn, u = _gdn_leaves()
    hv, d = cfg.gdn_v_heads, cfg.gdn_d_head
    r = hv // cfg.gdn_k_heads
    cut = dict(gdn, in_proj_qkvz=gdn["in_proj_qkvz"].at[:, :d].set(0.0),
               out_proj=jnp.eye(hv * d, D))
    out = llama._gdn_mixer(u, cut, cfg)[0].reshape(B, S, hv, d)
    assert float(jnp.max(jnp.abs(out[:, :, :r]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(out[:, :, r:]), axis=(0, 1, 3)))
                 ) > 1e-3
    assert _rel(out.reshape(B, S, D), _gdn_by_position(u, cut, cfg)) < 2e-5


def test_bf16_streams_keep_the_rule_in_float32():
    """In bf16 the mixer rounds its projections' outputs and the operands
    of the rule's matmuls against the state; ``g``, the cumulative sums,
    ``T`` and the state stay float32: the result is a rounding away from
    the float32 one."""
    cfg, gdn, u = _gdn_leaves()
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    got, stats = llama._gdn_mixer(u.astype(jnp.bfloat16), gdn, low)
    assert got.dtype == jnp.bfloat16
    assert stats["gdn_state_rms"].dtype == jnp.float32
    want = _gdn_by_position(u.astype(jnp.bfloat16).astype(F32), gdn, cfg)
    assert _rel(got.astype(F32), want) < 3e-2


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_mixer_through_the_convolutions_kernels_equals_the_numpy_form(
        dtype, monkeypatch):
    """``_gdn_mixer`` with ``ops.conv_silu``'s kernel pair (interpret mode)
    against the mixer with the ``jax.numpy`` form: the output, the stream's
    gradient and every leaf's, at the tolerances this file holds the mixer
    to against the loop over positions
    (``A_log``'s gradient is a sum over 1,024 positions that cancels: 3e-5
    here), on two row tiles of 128 channels (one key head of 32 under two
    value heads)."""
    from dlrover_tpu.ops import conv_silu

    s = 2 * conv_silu._ROW_TILE
    cfg, gdn, _ = _gdn_leaves(gdn_k_heads=1, gdn_v_heads=2, gdn_d_head=32,
                              max_seq_len=s, dtype=dtype)
    assert gdn["conv_w"].shape == (4, 128)
    u = jax.random.normal(jax.random.PRNGKey(5), (B, s, D)).astype(dtype)

    def loss(gdn, u):
        out, stats = llama._gdn_mixer(u, gdn, cfg)
        return jnp.sum(jnp.sin(out.astype(F32))), (out, stats)

    run = lambda: jax.value_and_grad(  # noqa: E731
        loss, (0, 1), has_aux=True)(gdn, u)
    (_, (want, want_stats)), want_grads = run()
    monkeypatch.setattr(llama, "causal_conv1d_silu", functools.partial(
        conv_silu.causal_conv1d_silu, backend="pallas", interpret=True))
    text = str(jax.make_jaxpr(jax.grad(lambda g_, u_: loss(g_, u_)[0]))(
        gdn, u))
    assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
    (_, (got, stats)), grads = run()
    tol = 1e-4 if dtype == F32 else 1e-2
    assert got.dtype == dtype and _rel(
        got.astype(F32), want.astype(F32)) < (2e-5 if dtype == F32 else tol)
    assert float(stats["gdn_state_rms"]) == pytest.approx(
        float(want_stats["gdn_state_rms"]), rel=tol)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    flat_w, tree_w = jax.tree_util.tree_flatten(want_grads)
    assert tree == tree_w
    for (path, g), w in zip(flat, flat_w):
        assert g.dtype == w.dtype
        assert _rel(g.astype(F32), w.astype(F32)) < tol, (
            jax.tree_util.keystr(path))


# -- attention: the three changes, one by one ---------------------------------


def _attention_layer(cfg, seed=0):
    layer = llama._init_layer(jax.random.PRNGKey(seed), cfg, False)
    dims = jnp.arange(cfg.head_dim, dtype=F32)
    # queries and keys 30 times larger prefer some keys; gains off their
    # initial value tell ``1 + w`` from ``w``
    return dict(layer, wq=30.0 * layer["wq"], wk=30.0 * layer["wk"],
                q_norm=layer["q_norm"] + 0.5 * jnp.cos(dims),
                k_norm=layer["k_norm"] + 0.5 * jnp.sin(dims))


def _attend(layer, cfg, u):
    positions = jnp.broadcast_to(jnp.arange(u.shape[1]), u.shape[:2])
    return llama._attention(u, layer, cfg, positions, "auto", None)


@pytest.mark.parametrize("gate", [True, False], ids=["gate", "no-gate"])
def test_the_output_gate_against_its_absence(gate):
    cfg = _next(attn_output_gate=gate)
    layer = _attention_layer(cfg)
    assert layer["wq"].shape == (D, 4 * 16 * (2 if gate else 1))
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, D))
    got = _attend(layer, cfg, u)
    assert _rel(got, _attention_plain(u, layer, cfg, gate=gate)) < 2e-5
    if gate:
        # without the gate, on the q columns alone, it is another result
        q_only = layer["wq"].reshape(D, 4, 32)[..., :16].reshape(D, 64)
        ungated = _attend(dict(layer, wq=q_only),
                          _next(attn_output_gate=False), u)
        assert _rel(got, ungated) > 5e-2


@pytest.mark.parametrize("factor,rotary", [(0.25, 4), (0.5, 8), (1.0, 16)])
def test_rotation_of_a_part_of_each_head(factor, rotary):
    cfg = _next(partial_rotary_factor=factor)
    assert cfg.rotary_dim == rotary
    layer = _attention_layer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, D))
    got = _attend(layer, cfg, u)
    assert _rel(got, _attention_plain(u, layer, cfg, rotary=rotary)) < 2e-5
    other = 16 if rotary != 16 else 4
    assert _rel(got, _attention_plain(u, layer, cfg, rotary=other)) > 1e-2


@pytest.mark.parametrize("plus_one", [True, False], ids=["1+w", "w"])
def test_gains_stored_as_one_plus_w(plus_one):
    cfg = _next(norm_plus_one=plus_one)
    layer = _attention_layer(cfg)
    # initialised 0 under ``1 + w``, 1 as ever: either way the gain is 1
    fresh = llama._init_layer(jax.random.PRNGKey(0), cfg, False)
    assert float(fresh["q_norm"][0]) == (0.0 if plus_one else 1.0)
    assert float(fresh["ln1"][0]) == float(fresh["ln2"][0]) == float(
        fresh["q_norm"][0])
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, D))
    got = _attend(layer, cfg, u)
    assert _rel(got, _attention_plain(u, layer, cfg,
                                      plus_one=plus_one)) < 2e-5
    assert _rel(got, _attention_plain(u, layer, cfg,
                                      plus_one=not plus_one)) > 1e-2


def test_the_head_size_is_set_apart_from_the_stream():
    cfg = _next()
    assert (cfg.head_dim, cfg.d_model // cfg.n_head) == (16, 8)
    layer = llama._init_layer(jax.random.PRNGKey(0), cfg, False)
    assert layer["wo"].shape == (64, D) and layer["wk"].shape == (D, 32)
    assert dataclasses.replace(cfg, attn_head_dim=0).head_dim == 8


@pytest.mark.parametrize("plus_one", [True, False], ids=["1+w", "w"])
def test_the_block_norms_and_the_final_norm_follow_the_setting(plus_one):
    cfg = _next(norm_plus_one=plus_one, num_experts=0, n_shared_experts=0,
                shared_expert_gate=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = _tokens()
    base = llama.loss_fn(params, {"tokens": toks}, cfg)
    # the same model with every gain written the other way
    shift = 1.0 if plus_one else -1.0

    def other_way(path, a):
        name = jax.tree_util.keystr(path)
        gains = ("['ln1']", "['ln2']", "['ln_f']", "['q_norm']", "['k_norm']")
        return a + shift if name.endswith(gains) else a

    flipped = jax.tree_util.tree_map_with_path(other_way, params)
    other = llama.loss_fn(flipped, {"tokens": toks},
                          dataclasses.replace(cfg, norm_plus_one=not plus_one))
    assert float(base) == pytest.approx(float(other), rel=1e-6)
    # the delta rule's gated norm is no ``1 + w``: its leaf stayed
    assert float(flipped["layers"][0]["gdn"]["norm"][0]) == 1.0


# -- the routed block beside the new mixer ------------------------------------


def _routed_layer(cfg, seed=0):
    layer = llama._init_layer(jax.random.PRNGKey(seed), cfg, True,
                              mixer="linear_attention")
    return _decisive(layer)["moe"]


@pytest.mark.parametrize("gate", [True, False], ids=["gate", "no-gate"])
def test_the_shared_expert_behind_its_gate(gate):
    cfg = _next(shared_expert_gate=gate)
    moe = _routed_layer(cfg)
    assert ("shared_gate" in moe) == gate
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, _ = llama._moe_swiglu(y, moe, cfg)
    want, _ = _routed_whole(y, moe, cfg, shared_gate=gate)
    assert _rel(got, want) < 1e-5
    if gate:
        assert moe["shared_gate"].shape == (D, 1)
        assert _rel(got, _routed_whole(y, moe, cfg, shared_gate=False)[0]
                    ) > 1e-2


@pytest.mark.parametrize("first", [0, 4, 12])
def test_a_share_computes_its_own_experts_part(first):
    cfg = _next(experts_held=4, experts_held_first=first)
    moe = _routed_layer(_next())
    held = dict(moe, **{k: moe[k][first:first + 4]
                        for k in ("wg", "wi", "wo")})
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    got, stats = llama._moe_swiglu(y, held, cfg)
    want, _ = _routed_whole(y, moe, cfg, experts=range(first, first + 4))
    assert _rel(got, want) < 1e-5
    per_expert = np.asarray(stats["tokens_per_expert"])
    assert int(stats["held_pairs"]) == per_expert[first:first + 4].sum()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 experts in 16 shares of one (the deployment's sixteen chips, 32 of
    512 each): the ROUTED parts that the sixteen chips compute, with what
    every chip computes alike — the shared expert behind its gate — counted
    ONCE, add up to the whole layer of the uncut formula."""
    whole = _next()
    moe = _routed_layer(whole)
    y = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    alike, _ = _routed_whole(y, moe, whole, experts=())  # the shared part
    total = alike
    for first in range(16):
        cfg = _next(experts_held=1, experts_held_first=first)
        held = dict(moe, **{k: moe[k][first:first + 1]
                            for k in ("wg", "wi", "wo")})
        part, stats = llama._moe_swiglu(y, held, cfg)
        total = total + (part - alike)  # this chip's routed part alone
        assert stats["tokens_per_expert"].shape == (16,)
    assert _rel(total, _routed_whole(y, moe, whole)[0]) < 1e-5
    assert _rel(total, llama._moe_swiglu(y, moe, whole)[0]) < 1e-5


# -- the model ----------------------------------------------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("fused", [False, True], ids=["logits", "fused"])
def test_the_loss_and_gradients_match_the_equations(remat, fused):
    cfg = _next(remat_block=remat)
    params = _decisive(llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = _tokens()
    (loss, counters), grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": toks}, cfg, moe_aux_weight=1e-3,
                                fused_lm_head=fused, metrics=True),
        has_aux=True)(params)
    want, want_grads = jax.value_and_grad(_plain_loss)(
        params, toks, cfg, 1e-3)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        assert _rel(g, wanted[path]) < 5e-4, jax.tree_util.keystr(path)
    assert len(flat) == 3 * 17 + 16 + 3
    # a delta-rule layer's routed MLP reports like any other, and the rule
    # reports beside it
    assert counters["moe_tokens_per_expert"].shape == (4, 16)
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(1).tolist() == [
        B * S * 3] * 4
    assert counters["gdn_state_rms"].shape == (3,)
    assert 0.0 <= float(counters["gdn_decay_min"]) <= 1.0


def test_the_mixer_and_the_mlp_of_a_layer_are_chosen_apart():
    cfg = _next(first_k_dense=1)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    held = [sorted(k for k in layer if k in (
        "gdn", "conv", "ssm", "wq", "moe", "mlp"))
        for layer in params["layers"]]
    assert held == [["gdn", "mlp"], ["gdn", "moe"], ["gdn", "moe"],
                    ["moe", "wq"]]
    assert (cfg.gdn_layers, cfg.conv_layers, cfg.ssm_layers,
            cfg.attention_layers, cfg.block_applications) == (3, 0, 0, 1, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    for layer in params["layers"]:
        out, stats = llama.block_apply(layer, x, cfg, positions)
        assert out.shape == x.shape
        assert ("moe_aux" in stats) == ("moe" in layer)
        assert ("gdn_state_rms" in stats) == ("gdn" in layer)
    # the axes tree names every leaf of the parameters
    axes = llama.param_logical_axes(cfg)
    jax.tree_util.tree_map(
        lambda a, p: None, axes, params,
        is_leaf=lambda a: isinstance(a, tuple))
    assert sorted(axes["layers"][1]["gdn"]) == [
        "A_log", "conv_w", "dt_bias", "in_proj_ba", "in_proj_qkvz", "norm",
        "out_proj"]
    assert axes["layers"][1]["moe"]["shared_gate"] == ("embed", None)
    assert "wo" not in axes["layers"][0] and "wo" in axes["layers"][3]


def test_defaults_are_todays_and_name_no_delta_rule_layer():
    cfg = llama.LlamaConfig()
    assert (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_d_head, cfg.gdn_d_conv,
            cfg.attn_head_dim, cfg.attn_output_gate,
            cfg.partial_rotary_factor, cfg.norm_plus_one,
            cfg.shared_expert_gate) == (0, 0, 0, 4, 0, False, 1.0, False,
                                        False)
    assert (cfg.gdn_layers, cfg.head_dim, cfg.rotary_dim) == (0, 128, 128)
    # 56 before this mixer, its nine, the one-branch layers' two, the
    # rotary table a kind of attention layer may have of its own, the
    # per-channel rule's three, the Mamba-1 mixer's four, the two layers
    # that make what crosses layers, differential attention, the norm's
    # form and the attention biases
    assert len(dataclasses.fields(llama.LlamaConfig)) == (
        56 + 9 + 2 + 1 + 3 + 4 + 2 + 1 + 2)
    assert tuple(llama.MIXER_KINDS) == (
        "attention", "mamba", "conv", "linear_attention",
        "window_attention", "kda", "mamba1", "gmu", "cross_attention")
    assert llama.program_facts(cfg, 4096) == {}
    assert llama.program_facts(_next(), 4096) == {
        "gdn_layers": 3, "attention_layers": 1,
        "gdn_chunks_per_sequence": 64}
    assert llama.program_facts(_next(), 100)["gdn_chunks_per_sequence"] == 2


def test_published_keys_count_the_parameters_of_the_cut():
    """The benchmark's cut of the published model (layers 0-3, 32 of 512
    experts held, an eighth of the vocabulary) from shapes alone."""
    cfg = llama.LlamaConfig(
        vocab_size=18992, n_layer=4, n_head=16, n_kv_head=2, d_model=2048,
        d_ff=5120, max_seq_len=8192, rope_theta=1e7, rms_eps=1e-6,
        layer_types=("linear_attention",) * 3 + ("attention",),
        gdn_k_heads=16, gdn_v_heads=32, gdn_d_head=128, gdn_d_conv=4,
        attn_head_dim=256, attn_output_gate=True, partial_rotary_factor=0.25,
        norm_plus_one=True, qk_norm=True, qk_norm_per_head=True,
        num_experts=512, top_k=10, moe_every=1, d_ff_expert=512,
        n_shared_experts=1, shared_expert_gate=True, balance_all_k=True,
        experts_held=32)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers"][0]["gdn"]) == 33_718_464
    assert count(shapes["layers"][0]["moe"]) == 104_859_648
    assert [count(layer) for layer in shapes["layers"]] == [
        138_582_208] * 3 + [132_127_232]
    assert count(shapes) == 625_667_136
    assert (cfg.head_dim, cfg.rotary_dim, cfg.gdn_conv_dim) == (256, 64, 8192)
    assert llama._moe_buffer_bounds(4 * 8192, 10, 512, 32) == (25600, 327680)
    # 6 x the matmul parameters of every layer as ONE dense MLP wide (the
    # estimator's convention), the causal square of the one attention
    # layer, head and lookup, and 3 x the rule's matmuls and taps; ``wq``
    # with the output gate's columns beside the queries' (2 x 4096 wide)
    mlp = 3 * 2048 * 5120
    gdn = 2048 * (12288 + 64) + 4096 * 2048 + mlp
    attn = 2048 * 2 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + mlp
    rule = 32 * (10 * 64 * 128 + 6 * 128 * 128) + 2 * 4 * 8192
    assert llama.flops_per_token(cfg) == (
        6.0 * (3 * gdn + attn + 2 * 18992 * 2048)
        + 6.0 * 2 * 8192 * 4096 + 3.0 * 3 * rule)


def test_initialisation_is_the_mixers_own():
    cfg = _next(d_model=256, gdn_v_heads=64, gdn_k_heads=32)
    gdn = llama.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["gdn"]
    taps = np.asarray(gdn["conv_w"])
    assert taps.shape == (4, (2 * 32 + 64) * 8)
    assert np.abs(taps).max() <= 0.5 and np.abs(taps).max() > 0.45
    a = np.exp(np.asarray(gdn["A_log"]))
    assert a.min() > 0.0 and a.max() < 16.0 and a.max() > 12.0
    assert np.array_equal(np.asarray(gdn["dt_bias"]), np.ones(64, np.float32))
    assert np.array_equal(np.asarray(gdn["norm"]), np.ones(8, np.float32))
    assert abs(float(np.std(gdn["in_proj_qkvz"])) - 0.02) < 2e-3
    assert abs(float(np.std(gdn["out_proj"])) - 0.02) < 2e-3


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("linear_attention", "attention", "window", "conv")),
     "layer_types"),
    (dict(gdn_k_heads=0), "gdn_k_heads=0"),
    (dict(gdn_v_heads=3), "gdn_v_heads=3"),
    (dict(gdn_d_conv=0), "gdn_d_conv=0"),
    (dict(num_experts=0, n_shared_experts=0, shared_expert_gate=False,
          loop_passes=2, exit_gate_beta=0.1), "loop_passes=2"),
    (dict(mtp_layers=1), "mtp_layers=1"),
    (dict(partial_rotary_factor=0.3), "partial_rotary_factor=0.3"),
    (dict(partial_rotary_factor=0.0), "partial_rotary_factor=0.0"),
    (dict(layer_types=(), branch_norm=True), "branch_norm=True"),
    (dict(n_shared_experts=0), "shared_expert_gate"),
])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        _next(**over)


def test_a_dense_stack_may_mix_all_four_kinds():
    cfg = _next(layer_types=("mamba", "attention", "conv", "linear_attention"),
                num_experts=0, n_shared_experts=0, shared_expert_gate=False,
                mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
                mamba_chunk_size=8)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    loss, counters = llama.loss_fn(
        params, {"tokens": _tokens()}, cfg, metrics=True)
    assert np.isfinite(float(loss))
    assert counters["ssm_state_rms"].shape == (1,)
    assert counters["gdn_state_rms"].shape == (1,)
    assert llama.program_facts(cfg, 64) == {
        "ssm_layers": 1, "ssm_chunks_per_sequence": 8, "conv_layers": 1,
        "gdn_layers": 1, "gdn_chunks_per_sequence": 1, "attention_layers": 1}


# -- the step: scopes, counters, a mesh ---------------------------------------


def _job(cfg, mesh=MeshSpec(dp=1), devices=1, batch=B):
    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-3,
                             metrics=True)

    loss.program_facts = llama.program_facts(cfg, S)
    return acc.accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-2),
        sample_batch={"tokens": np.zeros((batch, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=mesh), param_specs="planner",
        devices=jax.devices()[:devices])


def test_the_step_journals_the_scopes_and_hands_out_the_counters():
    cfg = _next(remat_block=True, experts_held=8)
    job = _job(cfg)
    assert {"gdn", "attention", "moe_router", "moe_permute", "moe_experts",
            "moe_combine", "moe_shared", "lm_head_loss"} <= {
        v[1] for v in job.program["scopes"].values()}
    by_inner = {}
    for name, inner in job.program["subscopes"].items():
        by_inner.setdefault(inner, set()).add(job.program["scopes"][name][0])
    # (the recomputation's copy is the AOT test's to find: the CPU
    # compiler merges it with the forward's)
    for inner in ("gdn_in", "gdn_conv", "gdn_scan", "gdn_gate", "gdn_out"):
        assert {"forward", "backward"} <= by_inner[inner], inner
    assert (job.program["gdn_layers"], job.program["attention_layers"],
            job.program["gdn_chunks_per_sequence"]) == (3, 1, 1)
    state = job.create_state(jax.random.PRNGKey(0))
    assert float(state["params"]["ln_f"][0]) == 0.0
    losses = []
    for _ in range(3):  # the same batch: its loss must fall
        state, metrics = job.train_step(state, {"tokens": _tokens()})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert np.asarray(metrics["moe_tokens_per_expert"]).shape == (4, 16)
    assert np.asarray(metrics["moe_held_pairs"]).shape == (4,)
    assert np.asarray(metrics["gdn_state_rms"]).shape == (3,)
    assert 0.0 <= float(metrics["gdn_decay_min"]) <= 1.0


def test_fsdp2_tp2_gives_the_one_device_loss_and_gradients():
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    # wide enough for the planner to shard the mixer's projections
    cfg = _next(d_model=128, d_ff=128, gdn_d_head=16)
    job = _job(cfg, mesh=MeshSpec(fsdp=2, tp=2), devices=4)
    params = job.create_state(jax.random.PRNGKey(0))["params"]
    plan = job.state_sharding["params"]["layers"][0]["gdn"]
    # the planner splits the large projections over both axes; the rule's
    # arithmetic is XLA's to partition
    assert plan["in_proj_qkvz"].spec[0] == "fsdp"
    toks = np.asarray(_tokens())
    batch = jax.make_array_from_process_local_data(
        job.batch_sharding["tokens"], toks)

    def loss(p, t):
        return llama.loss_fn(p, {"tokens": t}, cfg, moe_aux_weight=1e-3)

    with jax.set_mesh(job.mesh):
        got, got_grads = jax.jit(jax.value_and_grad(loss))(params, batch)
    alone = jax.tree_util.tree_map(np.asarray, params)
    want, want_grads = jax.jit(jax.value_and_grad(loss))(alone, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        assert _rel(np.asarray(g), wanted[path]) < 2e-4, (
            jax.tree_util.keystr(path))


# -- what cannot compute it says so -------------------------------------------

#: each new setting alone on a dense attention stack, so that it is the
#: first thing refused, and what the refusal says of it
NEW_SETTINGS = {
    "layer_types": (
        dict(layer_types=("linear_attention",) * 3 + ("attention",),
             attn_head_dim=0, attn_output_gate=False,
             partial_rotary_factor=1.0, norm_plus_one=False),
        "'linear_attention' entry (3 of 4 layers)"),
    "attn_head_dim": (
        dict(attn_output_gate=False, partial_rotary_factor=1.0,
             norm_plus_one=False), "attn_head_dim=16"),
    "attn_output_gate": (
        dict(attn_head_dim=0, partial_rotary_factor=1.0,
             norm_plus_one=False), "attn_output_gate=True"),
    "partial_rotary_factor": (
        dict(attn_head_dim=0, attn_output_gate=False, norm_plus_one=False),
        "partial_rotary_factor=0.25"),
    "norm_plus_one": (
        dict(attn_head_dim=0, attn_output_gate=False,
             partial_rotary_factor=1.0), "norm_plus_one=True"),
}


def _dense(**over):
    base = dict(layer_types=(), num_experts=0, n_shared_experts=0,
                shared_expert_gate=False)
    base.update(over)
    return _next(**base)


@pytest.mark.parametrize("setting", sorted(NEW_SETTINGS))
@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_the_refusal_names_the_setting_and_the_path(where, path, setting):
    over, said = NEW_SETTINGS[setting]
    cfg = _dense(**over)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    assert said in str(e.value)
    assert path in str(e.value) and "training path only" in str(e.value)


@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_the_refusal_names_the_shared_experts_gate(where, path):
    cfg = _next(layer_types=(), attn_head_dim=0, attn_output_gate=False,
                partial_rotary_factor=1.0, norm_plus_one=False)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    assert "shared_expert_gate=True" in str(e.value)
    assert path in str(e.value) and "a gate on the shared expert" in str(
        e.value)


def test_the_table_of_refusals_gained_a_row_a_setting():
    names = [row[0] for row in llama.TRAINING_PATH_ONLY]
    assert names[13:18] == ["attn_head_dim", "attn_output_gate",
                            "partial_rotary_factor", "norm_plus_one",
                            "shared_expert_gate"]
    assert len(names) == len(set(names)) >= 18
    for name, computed, _ in llama.TRAINING_PATH_ONLY:
        if name != "layer_types":
            assert getattr(llama.LlamaConfig(), name) == computed, name


@pytest.mark.parametrize("kw", [
    dict(segment_ids=np.zeros((B, S), np.int32)),
    dict(attn_fn=lambda *a: None)], ids=["segment_ids", "attn_fn"])
@pytest.mark.parametrize("kind,layer_types,more", [
    ("linear_attention", ("linear_attention",) * 3 + ("attention",), {}),
    ("conv", ("conv",) * 3 + ("attention",), {}),
    ("mamba", ("mamba",) * 3 + ("attention",), dict(
        num_experts=0, n_shared_experts=0, shared_expert_gate=False,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16)),
])
def test_a_recurrent_layer_refuses_what_it_does_not_know(
        kind, layer_types, more, kw):
    """One table names the kinds (``MIXER_KINDS``): the refusal reads the
    kind's own name whichever it is."""
    cfg = _next(layer_types=layer_types, **more)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with pytest.raises(NotImplementedError, match=f"'{kind}' layer"):
        llama.block_apply(params["layers"][0], x, cfg, positions, **kw)
