"""Mamba-1 mixers (the selective scan) beside window and full differential
attention, a memory that Gated Memory Units read across layers and keys and
values that cross-attention layers share, LayerNorm and attention biases
without position under a tied head: the program (``models/llama.py`` through
the benchmark's adapter) against the plain reference
``benchmark/reference/phi4flash_ref.py`` on seeded weights at the rehearsal
size, and each piece alone."""

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

from benchmark.adapters import phi4flash as ADAPTER  # noqa: E402
from benchmark.reference import phi4flash_ref as REF  # noqa: E402
from dlrover_tpu.models import llama  # noqa: E402

S = 96
KINDS = ("mamba1", "window_attention", "mamba1", "attention", "gmu",
         "cross_attention", "gmu", "cross_attention")


def _hf(**over) -> dict:
    """The rehearsal configuration: published layers 0, 1, 16, 17 and TWO
    periods of the cross-decoder (18-21), so that what crosses layers has
    two readers each."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi4flash-rehearsal.json")) as f:
        return dict(json.load(f), **over)


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 4096, (batch, S + 1)), jnp.int32)


def _moved(params, seed=11):
    """Every bias off zero and every gain off one (seeded), so that a bias
    dropped or a gain not applied shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if any(name.endswith(f"['{b}']") for b in (
                "bias", "bq", "bk", "bv", "bo")):
            return 0.1 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name.endswith("['gain']") or name.endswith("['subln']"):
            return leaf + 0.2 * jax.random.normal(
                next(keys), leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(moved, params)


def _mc(remat=False, **over):
    return dataclasses.replace(
        ADAPTER.model_config(_hf(), remat_block=remat, seq_len=S),
        dtype=jnp.float32, **over)


@pytest.fixture(scope="module", params=[True, False],
                ids=["remat", "no_remat"])
def compared(request):
    """System (float32 compute, so that the comparison is tight) and
    reference, forward and every leaf's gradient, on one seeded tree."""
    cfg, mc = _hf(), _mc(request.param)
    params = _moved(llama.init_params(jax.random.PRNGKey(7), mc))
    tokens = _tokens()

    def system(p):
        hidden, loss, extra = ADAPTER.hidden_and_loss(p, tokens, mc)
        return loss, (hidden, extra)

    (loss, (hidden, extra)), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)

    def reference(p):
        hidden_r, loss_r, extra_r = REF.hidden_and_loss(
            p, tokens, cfg, q_block=32, scan_block=32, row_block=32)
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
    assert mc.layer_types == KINDS
    assert (mc.memory_layer, mc.shared_kv_layer) == (2, 3)
    assert (mc.s6_d_inner, mc.s6_d_state, mc.s6_d_conv, mc.s6_dt_rank) == (
        128, 4, 4, 4)
    assert (mc.norm_form, mc.attn_bias, mc.rope, mc.tie_word_embeddings,
            mc.sliding_window) == ("layernorm", True, False, True, 16)
    # lambda_init by the PUBLISHED index: 0.3555 at 1, 0.7963 at 17
    assert mc.diff_attention == pytest.approx(
        [0.8 - 0.6 * np.exp(-0.3 * l) for l in (0, 1, 16, 17, 18, 19, 20,
                                                  21)])
    assert mc.diff_attention[1] == pytest.approx(0.3555, abs=1e-4)
    assert mc.diff_attention[3] == pytest.approx(0.7963, abs=1e-4)
    assert (mc.s6_layers, mc.gmu_layers, mc.cross_layers, mc.window_layers,
            mc.attention_layers) == (2, 2, 2, 1, 4)


def test_hidden_states_agree_with_the_reference(compared):
    assert _rel(compared["hidden"], compared["hidden_r"]) < 2e-4


def test_the_loss_is_the_cross_entropy_alone_and_agrees(compared):
    assert abs(float(compared["loss"] - compared["loss_r"])) < 2e-5 * float(
        compared["loss_r"])
    want, _ = llama.loss_fn(compared["params"], {"tokens": compared["tokens"]},
                            compared["mc"], metrics=True)
    assert float(compared["loss"]) == pytest.approx(float(want), rel=1e-6)


def test_what_crosses_layers_agrees_with_the_reference(compared):
    assert compared["extra"]["choices"] == {}
    for name, value in compared["extra"]["scalars"].items():
        assert float(value) == pytest.approx(
            float(compared["extra_r"]["scalars"][name]), rel=1e-4), name
    assert sorted(compared["extra"]["scalars"]) == sorted(
        ["memory_rms", "shared_k_rms", "shared_v_rms", "window_alone_least",
         "window_alone_most"]
        + [f"s6_scan_out_rms.{g}" for g in range(16)])
    for side in ("extra", "extra_r"):
        scalars = compared[side]["scalars"]
        assert float(scalars["window_alone_least"]) == 2.0
        assert float(scalars["window_alone_most"]) == 1.0


def test_every_leafs_gradient_agrees_with_the_reference(compared):
    flat, _ = jax.tree_util.tree_flatten_with_path(compared["grads"])
    flat_r = jax.tree_util.tree_leaves(compared["grads_r"])
    assert len(flat) == len(flat_r) > 100
    for (path, g), g_r in zip(flat, flat_r):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bk']"):
            # a key bias moves every score of a query alike, and a softmax
            # does not see that: no gradient, on either side
            assert float(jnp.max(jnp.abs(g))) < 1e-7, name
            assert float(jnp.max(jnp.abs(g_r))) < 1e-7, name
            continue
        assert float(jnp.linalg.norm(g_r.ravel())) > 0, name
        assert _rel(g, g_r) < 2e-3, name


# -- what crosses layers -----------------------------------------------------------


def _stack(params, mc, tokens, nudges):
    """``forward_hidden``'s loop by hand, with ``nudges[i]`` (a dict of
    ``memory`` / ``shared_kv`` offsets) added to what reader ``i`` is
    handed, or at the maker (key ``"made"``)."""
    B, Sq = tokens.shape
    x = params["embed"].astype(mc.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(Sq), (B, Sq))
    carried = {}
    for i, layer in enumerate(params["layers"]):
        kind = mc.mixer_kind(i)
        kw = {}
        if kind in llama.ATTENTION_KINDS:
            kw.update(attn_kind=kind, lambda_init=mc.diff_attention[i])
        if i == mc.memory_layer:
            kw["keep"] = "memory"
        if i == mc.shared_kv_layer:
            kw["keep"] = "shared_kv"
        off = nudges.get(i, {})
        if kind == "gmu":
            kw["memory"] = carried["memory"] + off.get("memory", 0.0)
        if kind == "cross_attention":
            k, v = carried["shared_kv"]
            dk, dv = off.get("shared_kv", (0.0, 0.0))
            kw["shared_kv"] = (k + dk, v + dv)
        x, stats = llama.block_apply(layer, x, mc, positions, **kw)
        made = stats.pop("carried", {})
        if "memory" in made:
            made["memory"] = made["memory"] + nudges.get(
                "made", {}).get("memory", 0.0)
        if "shared_kv" in made:
            dk, dv = nudges.get("made", {}).get("shared_kv", (0.0, 0.0))
            made["shared_kv"] = (made["shared_kv"][0] + dk,
                                 made["shared_kv"][1] + dv)
        carried.update(made)
    return jnp.sum(jnp.sin(llama._norm(x, params["ln_f"], mc)))


def test_the_hand_loop_is_forward_hidden(compared):
    mc, params = compared["mc"], compared["params"]
    tokens = compared["tokens"][:, :-1]
    hidden, aux = llama.forward_hidden(params, tokens, mc)
    assert float(_stack(params, mc, tokens, {})) == pytest.approx(
        float(jnp.sum(jnp.sin(hidden))), rel=1e-5)
    assert aux["carried"]["memory"].shape == (2, S, 128)
    k, v = aux["carried"]["shared_kv"]
    assert k.shape == v.shape == (2, S, 2, 16)


@pytest.mark.parametrize("what", ["memory", "shared_kv"])
def test_the_gradient_of_what_crosses_layers_sums_over_its_readers(
        compared, what):
    """Two GMUs read the memory and two cross layers the shared keys and
    values: the gradient at the maker is the sum of the gradients at the
    two readers, and neither reader's alone."""
    mc, params = compared["mc"], compared["params"]
    tokens = compared["tokens"][:, :-1]
    readers = [i for i, kind in enumerate(KINDS)
               if kind == ("gmu" if what == "memory" else "cross_attention")]
    assert len(readers) == 2
    zero = (jnp.zeros((2, S, 128)) if what == "memory"
            else (jnp.zeros((2, S, 2, 16)),) * 2)

    def at(where):
        return jax.grad(lambda z: _stack(
            params, mc, tokens, {where: {what: z}}))(zero)

    made, first, second = at("made"), at(readers[0]), at(readers[1])
    flat = lambda t: jnp.concatenate(  # noqa: E731
        [a.ravel() for a in jax.tree_util.tree_leaves(t)])
    assert _rel(flat(first) + flat(second), flat(made)) < 1e-5
    assert _rel(flat(first), flat(made)) > 1e-2
    assert _rel(flat(second), flat(made)) > 1e-2


def test_the_memory_is_the_scan_with_its_skip_before_its_gate(compared):
    """``stats["carried"]["memory"]`` of the memory layer is ``_s6_mixer``'s
    third result: ``y`` with ``D x``, before ``silu(z)``."""
    mc, params = compared["mc"], compared["params"]
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (2, S))
    _, stats = llama.block_apply(layer, x, mc, positions, keep="memory")
    u = llama._norm(x, layer["ln1"], mc)
    _, _, y = llama._s6_mixer(u, layer["s6"], mc)
    assert np.array_equal(np.asarray(stats["carried"]["memory"]),
                          np.asarray(y))
    no_skip = llama._s6_mixer(
        u, dict(layer["s6"], D=jnp.zeros_like(layer["s6"]["D"])), mc)[2]
    assert _rel(no_skip, y) > 1e-2
    # a layer that is not asked keeps nothing
    assert "carried" not in llama.block_apply(layer, x, mc, positions)[1]


def test_a_cross_layer_projects_queries_alone(compared):
    params = compared["params"]
    assert not {"wk", "wv", "bk", "bv"} & set(params["layers"][5])
    assert {"wq", "bq", "wo", "bo", "subln", "lambda_q1"} <= set(
        params["layers"][5])
    assert {"wk", "wv", "bk", "bv"} <= set(params["layers"][3])
    assert set(params["layers"][4]) == {"ln1", "ln2", "gmu", "mlp"}
    assert set(params["layers"][4]["gmu"]) == {"in_proj", "out_proj"}


# -- no position --------------------------------------------------------------------


@pytest.mark.parametrize("i", [1, 3], ids=["window", "full"])
def test_a_shift_of_all_positions_leaves_the_layer_unchanged(compared, i):
    mc, layer = compared["mc"], compared["params"]["layers"][i]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, 64), jnp.float32)

    def out(shift):
        positions = jnp.broadcast_to(jnp.arange(S), (2, S)) * 3 + shift
        return np.asarray(llama.block_apply(
            layer, x, mc, positions, attn_kind=KINDS[i],
            lambda_init=mc.diff_attention[i])[0])

    assert np.array_equal(out(0), out(977))


# -- the pieces ------------------------------------------------------------------------


def test_layernorm_subtracts_the_mean_and_adds_the_bias():
    mc = _mc()
    x = 3.0 + jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
    leaf = {"gain": jnp.linspace(0.5, 1.5, 64), "bias": jnp.linspace(-1, 1, 64)}
    want = ((x - x.mean(-1, keepdims=True))
            / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5)
            * leaf["gain"] + leaf["bias"])
    assert _rel(llama._norm(x, leaf, mc), want) < 1e-6
    plain = llama.LlamaConfig.tiny()
    assert np.array_equal(
        np.asarray(llama._norm(x, leaf["gain"], plain)),
        np.asarray(llama.rmsnorm(x, leaf["gain"], eps=plain.rms_eps)))


def test_the_reordered_heads_keep_the_flash_calls_gqa_map():
    """Query head ``h`` of the reordered 2 x (H / 2) reads key/value head
    ``h // (H / KV)``: pair p's first head k1 and v of pair ``p // 2``, its
    second head k2 and the same v."""
    H, KV, D = 8, 4, 2
    q = jnp.arange(H, dtype=jnp.float32).reshape(1, 1, H, 1) * jnp.ones(D)
    k = jnp.arange(KV, dtype=jnp.float32).reshape(1, 1, KV, 1) * jnp.ones(D)
    v = jnp.arange(KV * D, dtype=jnp.float32).reshape(1, 1, KV, D)
    q2, k2, v2 = llama._diff_heads(q, k, v)
    assert v2.shape == (1, 1, KV, 2 * D)
    for h in range(H):
        pair, second = h % (H // 2), h // (H // 2)
        assert float(q2[0, 0, h, 0]) == 2 * pair + second
        kv_head = h // (H // KV)
        assert float(k2[0, 0, kv_head, 0]) == 2 * (pair // 2) + second
        assert np.array_equal(
            np.asarray(v2[0, 0, kv_head]),
            np.asarray(v[0, 0, 2 * (pair // 2):2 * (pair // 2) + 2]).ravel())


def test_the_scopes_nest_inside_the_blocks_outermost(compared):
    import importlib

    acc = importlib.import_module("dlrover_tpu.parallel.accelerate")
    mc, params = compared["mc"], compared["params"]
    compiled = jax.jit(jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": compared["tokens"]}, mc, metrics=True)[0])).lower(
            params).compile()
    outer, inner = acc.scope_tables(compiled.as_text())
    above = {}
    for name, scope in inner.items():
        above.setdefault(scope, set()).add(outer[name][1])
    for scope in ("s6_in", "s6_conv", "s6_dt", "s6_scan", "s6_gate",
                  "s6_out"):
        assert above[scope] == {"s6"}, scope
    for scope in ("attn_diff", "attn_window", "attn_full", "attn_cross"):
        assert above[scope] == {"attention"}, scope
    assert "gmu" in {verdict[1] for verdict in outer.values()}
    phases = {outer[name][0] for name, scope in inner.items()
              if scope == "attn_diff"}
    assert {"forward", "backward"} <= phases


def test_the_tree_and_its_axes_hold_the_same_leaves(compared):
    mc, params = compared["mc"], compared["params"]
    axes = llama.param_logical_axes(mc)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(axes, is_leaf=is_axes))
    fresh = llama.init_params(jax.random.PRNGKey(0), mc)
    s6 = fresh["layers"][0]["s6"]
    assert s6["in_proj"].shape == (64, 256) and s6["x_proj"].shape == (128, 12)
    assert s6["dt_proj"].shape == (4, 128) and s6["A_log"].shape == (128, 4)
    assert np.allclose(np.exp(np.asarray(s6["A_log"])), [1, 2, 3, 4])
    assert np.array_equal(np.asarray(s6["D"]), np.ones(128, np.float32))
    step = np.log1p(np.exp(np.asarray(s6["dt_bias"])))  # softplus
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert abs(np.asarray(s6["dt_proj"])).max() <= 0.5  # 4^-1/2
    assert set(fresh["ln_f"]) == {"gain", "bias"}
    assert not np.asarray(fresh["layers"][1]["bq"]).any()
    assert "lm_head" not in fresh


def test_program_facts_count_the_kinds_and_what_they_hand_on():
    mc = dataclasses.replace(ADAPTER.model_config(
        _hf(sliding_window=512), remat_block=True, seq_len=16384),
        s6_d_inner=5120, n_kv_head=20, d_model=2560, n_head=40)
    facts = llama.program_facts(mc, 16384)
    assert facts == {
        "s6_layers": 2, "gmu_layers": 2, "cross_attention_layers": 2,
        "window_attention_layers": 1, "attention_layers": 4,
        "attn_full_pairs_per_sequence": 134_225_920,
        "attn_cross_pairs_per_sequence": 134_225_920,
        "attn_window_pairs_per_sequence": 8_257_792,
        "memory_bytes_per_sequence": 16384 * 5120 * 2,
        "shared_kv_bytes_per_sequence": 2 * 16384 * 1280 * 2,
        "s6_chunks_per_sequence": 128}
    assert llama.program_facts(llama.LlamaConfig.tiny(), 64) == {}


def test_flops_per_token_count_the_pairs_the_scan_and_no_cross_keys():
    mc = _mc()
    d, inner, n, rank, ff = 64, 128, 4, 4, 160
    mlp = 3 * d * ff
    s6 = d * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * d
    attention = 4 * d * d - 2 * d * 32  # wq, wo whole; wk, wv at 2 of 4
    keys = 3 * S + min(16, S)
    want = (6.0 * (2 * (s6 + mlp) + 2 * (2 * d * inner + mlp)
                   + 2 * (attention + mlp) + 2 * (2 * d * d + mlp)
                   + 2 * 4096 * d)
            + 6.0 * keys * 4 * (16 + 32)
            + 3.0 * 2 * (9 * inner * n + 2 * 4 * inner))
    assert llama.flops_per_token(mc) == pytest.approx(want, rel=1e-12)


# -- the refusals, by name ---------------------------------------------------------------


def _tiny(**over):
    base = dict(n_layer=4, rope=False, s6_d_inner=128, s6_d_state=4,
                s6_dt_rank=4,
                layer_types=("mamba1", "attention", "gmu", "cross_attention"),
                memory_layer=0, shared_kv_layer=1)
    return llama.LlamaConfig.tiny(**dict(base, **over))


def test_the_smallest_stack_of_every_kind_trains():
    cfg = _tiny(diff_attention=(0.2, 0.3, 0.4, 0.5), norm_form="layernorm",
                attn_bias=True, remat_block=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    loss, grads = jax.value_and_grad(llama.loss_fn)(
        params, {"tokens": _tokens()[:, :65] % 256}, cfg)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


_LATENT = dict(n_kv_head=4, kv_lora_rank=16, q_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16)


@pytest.mark.parametrize("build,match", [
    (lambda: _tiny(layer_types=("gmu", "mamba1", "attention",
                                "cross_attention"), memory_layer=1,
                   shared_kv_layer=2),
     "memory_layer=1 with 'gmu' layers \\[0\\]"),
    (lambda: _tiny(layer_types=("mamba1", "cross_attention", "attention",
                                "gmu"), shared_kv_layer=2),
     "shared_kv_layer=2 with 'cross_attention' layers \\[1\\]"),
    (lambda: _tiny(memory_layer=None), "memory_layer=None with 'gmu'"),
    (lambda: _tiny(shared_kv_layer=None),
     "shared_kv_layer=None with 'cross_attention'"),
    (lambda: _tiny(memory_layer=1), "memory_layer names a layer of "
     "\\('mamba1',\\)"),
    (lambda: _tiny(shared_kv_layer=0), "shared_kv_layer names a layer of"),
    (lambda: _tiny(loop_passes=2, exit_gate_beta=0.1), "loop_passes=2"),
    (lambda: _tiny(mtp_layers=1), "mtp_layers=1"),
    (lambda: llama.LlamaConfig.tiny(
        one_branch=True, layer_types=("mamba1", "mlp"), s6_d_inner=128,
        s6_dt_rank=4), "one_branch=True"),
    (lambda: _tiny(s6_d_inner=0), "a 'mamba1' layer needs positive"),
    (lambda: _tiny(s6_dt_rank=0), "mamba_n_heads and mamba_d_head are"),
    (lambda: llama.LlamaConfig.tiny(diff_attention=(0.2,)),
     "diff_attention of 1 entries with n_layer=2"),
    (lambda: llama.LlamaConfig.tiny(diff_attention=(0.2, 0.3), n_head=3,
                                    n_kv_head=3, d_model=48),
     "n_head=3 and n_kv_head=3"),
    (lambda: llama.LlamaConfig.tiny(diff_attention=(0.2, 0.3), **_LATENT),
     "diff_attention of 2 entries .* kv_lora_rank=16"),
    (lambda: llama.LlamaConfig.tiny(diff_attention=(0.2, 0.3),
                                    attn_output_gate=True),
     "attn_output_gate=True"),
    (lambda: llama.LlamaConfig.tiny(norm_form="batchnorm"),
     "norm_form='batchnorm' is none of"),
    (lambda: llama.LlamaConfig.tiny(norm_form="layernorm", branch_norm=True),
     "norm_form='layernorm' with branch_norm=True"),
    (lambda: llama.LlamaConfig.tiny(norm_form="layernorm",
                                    norm_plus_one=True),
     "norm_form='layernorm' with .*norm_plus_one=True"),
    (lambda: llama.LlamaConfig.tiny(norm_form="layernorm", **_LATENT),
     "norm_form='layernorm' with .*kv_lora_rank=16"),
    (lambda: llama.LlamaConfig.tiny(attn_bias=True, branch_norm=True),
     "attn_bias=True with branch_norm=True"),
    (lambda: llama.LlamaConfig.tiny(attn_bias=True, norm_plus_one=True),
     "attn_bias=True with .*norm_plus_one=True"),
    (lambda: llama.LlamaConfig.tiny(attn_bias=True, **_LATENT),
     "attn_bias=True with .*latent"),
    (lambda: llama.LlamaConfig.tiny(attn_bias=True, mtp_layers=1),
     "attn_bias=True with .*mtp_layers=1"),
], ids=["gmu_before_the_memory", "cross_before_the_kv", "gmu_without_memory",
        "cross_without_kv", "memory_of_no_scan", "kv_of_no_attention",
        "crossing_looped", "crossing_mtp", "crossing_one_branch",
        "s6_without_width", "s6_without_rank", "lambdas_of_another_depth",
        "odd_heads", "diff_latent", "diff_gate", "unknown_norm",
        "layernorm_sandwich", "layernorm_plus_one", "layernorm_latent",
        "bias_sandwich", "bias_plus_one", "bias_latent", "bias_mtp"])
def test_config_refuses_what_is_not_built(build, match):
    with pytest.raises(ValueError, match=match):
        build()


def test_a_recurrent_layer_refuses_document_boundaries(compared):
    mc, params = compared["mc"], compared["params"]
    x = jnp.zeros((1, S, 64))
    positions = jnp.zeros((1, S), jnp.int32)
    for i, kind in ((0, "mamba1"), (4, "gmu")):
        with pytest.raises(NotImplementedError, match=f"a {kind!r} layer"):
            llama.block_apply(params["layers"][i], x, mc, positions,
                              segment_ids=positions, memory=x)


_NEW_SETTINGS = {
    "norm_form": (dict(norm_form="layernorm"), "norm_form='layernorm'",
                  "a norm that is not RMSNorm"),
    "attn_bias": (dict(attn_bias=True), "attn_bias=True",
                  "biases on the attention projections"),
    "diff_attention": (dict(diff_attention=(0.2, 0.3)),
                       "diff_attention=(0.2, 0.3)", "differential attention"),
    "the_kinds": (dict(n_layer=4, rope=False, s6_d_inner=128, s6_dt_rank=4,
                       layer_types=("mamba1", "attention", "gmu",
                                    "cross_attention"),
                       memory_layer=0, shared_kv_layer=1),
                  "layer_types with a 'mamba1' entry (1 of 4 layers)",
                  "a layer whose mixer is not attention"),
}


@pytest.mark.parametrize("setting", sorted(_NEW_SETTINGS))
@pytest.mark.parametrize("where,path", sorted(REFUSING_PATH_NAMES.items()))
def test_every_other_path_refuses_the_new_settings(where, path, setting):
    """``llama_infer``, ``llama_pp`` and ``hf_convert`` refuse through
    ``TRAINING_PATH_ONLY``'s rows, with no edit of their own."""
    over, said_setting, what = _NEW_SETTINGS[setting]
    cfg = llama.LlamaConfig.tiny(**over)
    with pytest.raises(ValueError) as e:
        refusing_calls(cfg)[where]()
    said = str(e.value)
    assert said_setting in said and what in said
    assert path in said and "training path only" in said


def test_what_crosses_layers_is_refused_by_its_own_rows():
    rows = {name: (value, what)
            for name, value, what in llama.TRAINING_PATH_ONLY}
    assert rows["memory_layer"] == (
        None, "a scan output that later layers read")
    assert rows["shared_kv_layer"] == (
        None, "keys and values that later layers attend")
