"""The routed block of ``models/llama.py``: sorted by expert, dropless,
ragged.  Against a per-token loop over the chosen experts (forward and
``jax.grad``), with and without renormalisation, pads, a capacity that
drops; the published router's loss terms against closed forms; q/k RMSNorm;
``accelerate()``'s step handing out the block's counters; and the token
side — K rows a token gathered, weighed and summed, its backward on the
sorted side — against the gather to ``[N*K, C]`` and the einsum it
replaced, the kernel in interpret mode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama, llama_infer
from dlrover_tpu.ops import gather_sum as gather_sum_op

E, K, D, F = 8, 2, 16, 8


def _cfg(**over):
    base = dict(n_layer=1, d_model=D, d_ff=F, n_head=4, n_kv_head=2,
                num_experts=E, top_k=K, moe_every=1, dtype=jnp.float32)
    base.update(over)
    return llama.LlamaConfig.tiny(**base)


def _moe(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": jax.random.normal(k[0], (D, E)) * 0.5,
            "wg": jax.random.normal(k[1], (E, D, F)) * 0.3,
            "wi": jax.random.normal(k[2], (E, D, F)) * 0.3,
            "wo": jax.random.normal(k[3], (E, F, D)) * 0.3}


def _x(b=2, s=12, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, D))


def _per_token(x, moe, cfg, keep=None):
    """sum_k weight_k * expert_k(token), each token by itself: the weights
    of its chosen experts gathered per (token, k)."""
    toks = x.reshape(-1, D)
    probs = jax.nn.softmax(toks @ moe["router"], -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    if keep is not None:
        w = w * keep
    g = jnp.einsum("nd,nkdf->nkf", toks, moe["wg"][idx])
    u = jnp.einsum("nd,nkdf->nkf", toks, moe["wi"][idx])
    y = jnp.einsum("nkf,nkfd->nkd", jax.nn.silu(g) * u, moe["wo"][idx])
    return jnp.einsum("nkd,nk->nd", y, w).reshape(x.shape)


def _rank_in_expert(idx, valid=None):
    """numpy: each (token, k) pair's rank among the pairs of its expert,
    in token order, pads taking none."""
    idx = np.asarray(idx)
    seen = np.zeros(E, int)
    rank = np.zeros(idx.shape, int)
    for n in range(idx.shape[0]):
        for k in range(idx.shape[1]):
            if valid is not None and not valid[n]:
                rank[n, k] = 10**9
                continue
            rank[n, k] = seen[idx[n, k]]
            seen[idx[n, k]] += 1
    return rank


@pytest.mark.parametrize("renorm", [True, False], ids=["renorm", "raw"])
def test_block_matches_per_token_loop_forward_and_grad(renorm):
    cfg, moe, x = _cfg(norm_topk_prob=renorm), _moe(), _x()
    out, stats = llama._moe_swiglu(x, moe, cfg)
    np.testing.assert_allclose(out, _per_token(x, moe, cfg), atol=1e-5)
    assert stats["experts"].shape == (2, 12, K)
    assert stats["experts"].dtype == jnp.int32
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.grad(lambda x, m: jnp.sum(
        llama._moe_swiglu(x, m, cfg)[0] * r), argnums=(0, 1))(x, moe)
    ref = jax.grad(lambda x, m: jnp.sum(
        _per_token(x, m, cfg) * r), argnums=(0, 1))(x, moe)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_renormalised_and_raw_weights_differ():
    moe, x = _moe(), _x()
    a, _ = llama._moe_swiglu(x, moe, _cfg(norm_topk_prob=True))
    b, _ = llama._moe_swiglu(x, moe, _cfg(norm_topk_prob=False))
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)) > 0.1


def test_group_sizes_sum_to_the_valid_pairs():
    cfg, moe, x = _cfg(), _moe(), _x(b=1, s=16)
    _, stats = llama._moe_swiglu(x, moe, cfg)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.dtype == np.int32 and counts.sum() == 16 * K
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(stats["experts"]).ravel(),
                            minlength=E))
    valid = jnp.arange(16)[None, :] % 3 != 0  # 10 real tokens
    _, stats = llama._moe_swiglu(x, moe, cfg, valid=valid)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.sum() == 10 * K
    np.testing.assert_array_equal(counts, np.bincount(
        np.asarray(stats["experts"])[0][np.asarray(valid[0])].ravel(),
        minlength=E))


def test_pads_get_nothing_and_count_in_no_statistic():
    cfg, moe = _cfg(norm_topk_prob=False, balance_all_k=True), _moe()
    real = _x(b=1, s=6)
    out_ref, stats_ref = llama._moe_swiglu(real, moe, cfg)
    pads = 5.0 * _x(b=1, s=4, seed=3)
    x = jnp.concatenate([pads[:, :2], real[:, :3], pads[:, 2:], real[:, 3:]],
                        axis=1)
    valid = jnp.asarray([[False] * 2 + [True] * 3 + [False] * 2 + [True] * 3])
    out, stats = llama._moe_swiglu(x, moe, cfg, valid=valid)
    np.testing.assert_allclose(out[0][np.asarray(valid[0])], out_ref[0],
                               atol=1e-5)
    np.testing.assert_array_equal(out[0][~np.asarray(valid[0])], 0.0)
    for key in ("moe_aux", "moe_z"):
        assert float(stats[key]) == pytest.approx(float(stats_ref[key]),
                                                  rel=1e-5)
    np.testing.assert_array_equal(stats["tokens_per_expert"],
                                  stats_ref["tokens_per_expert"])
    # and no gradient reaches a pad
    g = jax.grad(lambda x: jnp.sum(
        llama._moe_swiglu(x, moe, cfg, valid=valid)[0] ** 2))(x)
    np.testing.assert_array_equal(g[0][~np.asarray(valid[0])], 0.0)


@pytest.mark.parametrize("capacity", [1, 3, None])
def test_a_capacity_drops_by_rank_and_none_drops_nothing(capacity):
    cfg, moe, x = _cfg(), _moe(), _x(b=1, s=24)
    out, stats = llama._moe_swiglu(x, moe, cfg, capacity=capacity)
    keep = None
    if capacity is not None:
        keep = jnp.asarray(
            _rank_in_expert(stats["experts"][0]) < capacity, jnp.float32)
        assert float(keep.mean()) < 1.0 or capacity == 3
    np.testing.assert_allclose(out, _per_token(x, moe, cfg, keep),
                               atol=1e-5)
    # the counter is what was routed, before any capacity
    assert int(stats["tokens_per_expert"].sum()) == 24 * K


def test_capacity_factor_of_the_config_and_the_decode_override():
    moe, x = _moe(), _x(b=1, s=24)
    tight = _cfg(capacity_factor=0.25)  # round(0.25 * 24 * 2 / 8) = 2
    out, stats = llama._moe_swiglu(x, moe, tight)
    keep = jnp.asarray(_rank_in_expert(stats["experts"][0]) < 2, jnp.float32)
    assert 0.0 < float(keep.mean()) < 1.0
    np.testing.assert_allclose(out, _per_token(x, moe, tight, keep),
                               atol=1e-5)
    free, _ = llama._moe_swiglu(x, moe, tight, capacity=24 * K)
    np.testing.assert_allclose(free, _per_token(x, moe, tight), atol=1e-5)
    assert _cfg().capacity_factor is None  # dropless unless asked


def test_pads_take_no_rank_under_a_capacity():
    cfg, moe = _cfg(top_k=1), _moe()
    x = _x(b=1, s=16)
    valid = jnp.arange(16)[None, :] >= 8
    out, stats = llama._moe_swiglu(x, moe, cfg, capacity=2, valid=valid)
    keep = jnp.asarray(_rank_in_expert(
        stats["experts"][0], np.asarray(valid[0])) < 2, jnp.float32)
    np.testing.assert_allclose(out, _per_token(x, moe, cfg, keep), atol=1e-5)


@pytest.mark.parametrize("all_k", [False, True], ids=["first", "all_k"])
def test_balance_and_z_terms_against_closed_forms(all_k):
    cfg, moe, x = _cfg(balance_all_k=all_k), _moe(), _x(b=2, s=32)
    _, stats = llama._moe_swiglu(x, moe, cfg)
    logits = np.asarray(x.reshape(-1, D) @ moe["router"], np.float64)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    picks = np.argsort(-probs, -1)[:, :K]
    taken = picks if all_k else picks[:, :1]
    f = np.bincount(taken.ravel(), minlength=E) / taken.size
    assert float(stats["moe_aux"]) == pytest.approx(
        E * float(np.sum(f * probs.mean(0))), rel=1e-5)
    lse = np.log(np.exp(logits).sum(-1))
    assert float(stats["moe_z"]) == pytest.approx(
        float(np.mean(lse ** 2)), rel=1e-5)


def test_an_even_router_reads_one_and_log_e_squared():
    cfg = _cfg(balance_all_k=True)
    moe = dict(_moe(), router=jnp.zeros((D, E)))
    _, stats = llama._moe_swiglu(_x(), moe, cfg)
    assert float(stats["moe_aux"]) == pytest.approx(1.0, rel=1e-6)
    assert float(stats["moe_z"]) == pytest.approx(np.log(E) ** 2, rel=1e-5)


def test_loss_fn_weighs_both_terms_and_hands_out_the_counters():
    cfg = _cfg(n_layer=2, norm_topk_prob=False, balance_all_k=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 250, (2, 17)))}
    plain = llama.loss_fn(params, batch, cfg, moe_aux_weight=0.0)
    loss, m = llama.loss_fn(params, batch, cfg, moe_aux_weight=0.5,
                            moe_z_weight=0.25, metrics=True)
    assert float(loss) == pytest.approx(
        float(plain) + 0.5 * float(m["moe_aux"]) + 0.25 * float(m["moe_z"]),
        rel=1e-6)
    assert m["moe_tokens_per_expert"].shape == (2, E)
    assert np.asarray(m["moe_tokens_per_expert"]).sum(1).tolist() == [
        2 * 16 * K] * 2
    # without the option a routed model's loss is the scalar it always was
    assert jnp.ndim(llama.loss_fn(params, batch, cfg)) == 0
    # and a dense model has no counters to hand out
    dense = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
    dense_loss = llama.loss_fn(
        llama.init_params(jax.random.PRNGKey(0), dense), batch, dense,
        metrics=True)
    assert jnp.ndim(dense_loss) == 0


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_forward_hidden_hands_out_the_experts_it_took(remat):
    cfg = _cfg(n_layer=2, moe_every=2, remat_block=remat)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 250, (2, 16)))
    hidden, aux = jax.jit(
        lambda p, t: llama.forward_hidden(p, t, cfg))(params, toks)
    assert sorted(aux["moe_experts"]) == [1]  # layer 0 is dense
    taken = aux["moe_experts"][1]
    assert taken.shape == (2, 16, K) and taken.dtype == jnp.int32
    assert aux["moe_tokens_per_expert"].shape == (1, E)
    np.testing.assert_array_equal(
        aux["moe_tokens_per_expert"][0],
        np.bincount(np.asarray(taken).ravel(), minlength=E))
    plain, plain_aux = llama.forward_hidden(
        params, toks, dataclasses.replace(cfg, remat_block=False))
    np.testing.assert_allclose(hidden, plain, atol=1e-5)
    np.testing.assert_array_equal(taken, plain_aux["moe_experts"][1])
    # a dense model's aux dict is what it always was
    dense = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
    _, dense_aux = llama.forward_hidden(
        llama.init_params(jax.random.PRNGKey(0), dense), toks, dense)
    assert sorted(dense_aux) == ["moe_aux"]


def _plain_attention(x, layer, cfg, qk_norm):
    """Causal softmax attention written out, RoPE on halves, with RMSNorm
    over the whole q and k projections where asked."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim

    def rms(v, w):
        return v * jax.lax.rsqrt(
            jnp.mean(v * v, -1, keepdims=True) + cfg.rms_eps) * w

    q, k, v = x @ layer["wq"], x @ layer["wk"], x @ layer["wv"]
    if qk_norm:
        q, k = rms(q, layer["q_norm"]), rms(k, layer["k_norm"])
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = llama._rope(q.reshape(b, s, h, hd), pos, cfg.rope_theta)
    k = llama._rope(k.reshape(b, s, kv, hd), pos, cfg.rope_theta)
    k = jnp.repeat(k, h // kv, 2)
    v = jnp.repeat(v.reshape(b, s, kv, hd), h // kv, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, h * hd) @ (
        layer["wo"])


@pytest.mark.parametrize("qk_norm", [False, True], ids=["off", "on"])
def test_qk_norm_over_the_whole_projection(qk_norm):
    cfg = _cfg(qk_norm=qk_norm)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    layer = params["layers"][0]
    assert ("q_norm" in layer) == qk_norm
    if qk_norm:
        assert layer["q_norm"].shape == (cfg.n_head * cfg.head_dim,)
        assert layer["k_norm"].shape == (cfg.n_kv_head * cfg.head_dim,)
        axes = llama.param_logical_axes(cfg)["layers"][0]
        assert axes["q_norm"] == axes["k_norm"] == (None,)
        # gains that are not one, so that a norm per head would differ
        layer = dict(layer,
                     q_norm=1.0 + 0.3 * jnp.sin(jnp.arange(16.0)),
                     k_norm=1.0 + 0.3 * jnp.cos(jnp.arange(8.0)))
    layer = dict(layer, wq=layer["wq"] * 20, wk=layer["wk"] * 20)
    x = _x(b=2, s=10)
    pos = jnp.broadcast_to(jnp.arange(10), (2, 10))
    got = llama._attention(x, layer, cfg, pos, "auto", None)
    np.testing.assert_allclose(
        got, _plain_attention(x, layer, cfg, qk_norm), atol=2e-5)
    if qk_norm:
        other = _plain_attention(x, layer, cfg, False)
        assert float(jnp.linalg.norm(got - other)
                     / jnp.linalg.norm(other)) > 0.05


def test_the_kv_cache_decoder_applies_the_qk_norms():
    cfg = _cfg(qk_norm=True, norm_topk_prob=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    params["layers"][0]["q_norm"] = 1.0 + 0.3 * jnp.sin(jnp.arange(16.0))
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 250, (2, 9)))
    logits, _ = llama.forward(params, toks, cfg)
    cache = llama_infer.init_cache(cfg, 2, 16)
    cached, cache = llama_infer.forward_step(params, toks[:, :6], cfg, cache)
    np.testing.assert_allclose(cached, logits[:, :6], atol=2e-4)
    for t in range(6, 9):  # one token at a time through the cache
        step, cache = llama_infer.forward_step(
            params, toks[:, t:t + 1], cfg, cache)
        np.testing.assert_allclose(step[:, 0], logits[:, t], atol=2e-4)


def _job(cfg, loss, grad_accum=None):
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    return accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch={"tokens": np.zeros((4, 17), np.int32)},
        strategy=Strategy(mesh=MeshSpec()), grad_accum=grad_accum,
        devices=jax.devices()[:1])


def _tokens():
    return {"tokens": np.random.RandomState(0).randint(
        0, 250, (4, 17)).astype(np.int32)}


def test_train_step_returns_the_counters_of_a_routed_model():
    cfg = _cfg(norm_topk_prob=False, balance_all_k=True)
    job = _job(cfg, lambda p, b: llama.loss_fn(
        p, b, cfg, moe_z_weight=1e-3, metrics=True))
    state = job.create_state(jax.random.PRNGKey(0))
    params = jax.device_get(state["params"])
    state, metrics = job.train_step(state, _tokens())
    assert sorted(metrics) == ["grad_norm", "loss", "moe_aux",
                               "moe_tokens_per_expert", "moe_z"]
    loss, want = llama.loss_fn(params, _tokens(), cfg, moe_z_weight=1e-3,
                               metrics=True)
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    np.testing.assert_array_equal(metrics["moe_tokens_per_expert"],
                                  want["moe_tokens_per_expert"])
    assert int(metrics["moe_tokens_per_expert"].sum()) == 4 * 16 * K


def test_train_step_of_a_dense_model_returns_what_it_returned():
    cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
    job = _job(cfg, lambda p, b: llama.loss_fn(p, b, cfg, metrics=True))
    state = job.create_state(jax.random.PRNGKey(0))
    _, metrics = job.train_step(state, _tokens())
    assert sorted(metrics) == ["grad_norm", "loss"]


def test_counters_add_up_over_grad_accum_microbatches():
    cfg = _cfg(norm_topk_prob=False, balance_all_k=True)
    loss = lambda p, b: llama.loss_fn(p, b, cfg, metrics=True)  # noqa: E731
    whole, split = _job(cfg, loss), _job(cfg, loss, grad_accum=2)
    state = whole.create_state(jax.random.PRNGKey(0))
    _, one = whole.train_step(state, _tokens())
    state = split.create_state(jax.random.PRNGKey(0))
    _, two = split.train_step(state, _tokens())
    # counts add up, the rest is averaged like the loss
    np.testing.assert_array_equal(two["moe_tokens_per_expert"],
                                  one["moe_tokens_per_expert"])
    assert float(two["moe_z"]) == pytest.approx(float(one["moe_z"]),
                                                rel=1e-4)
    assert float(two["loss"]) == pytest.approx(float(one["loss"]), rel=1e-4)


# -- the token side: gather K rows, weigh, sum ------------------------------


def _token_side(k, share, dtype, n=64, c=32, seed=3):
    """A routed block's indices as ``_moe_swiglu`` makes them, over random
    picks of 8 experts: ``share`` holds 2 of them in a buffer that ends
    before every pick (a clamped ``inverse``, zero weights for the absent
    experts' picks, ``live_rows`` under the buffer's rows) or, as
    ``"every_pick"``, in the buffer a skewed step falls back to; else
    every expert is held.  Returns ``(rows, weights, order, inverse, live_rows)``
    with the rows from ``live_rows`` on poisoned with NaN."""
    rng = np.random.RandomState(seed + k)
    e, held = 8, (2 if share else 8)
    expert = rng.randint(0, e, size=(n, k))
    flat = expert.reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    inverse = np.argsort(order).astype(np.int32)
    weights = np.where(expert < held, rng.rand(n, k) + 0.1, 0.0)
    total = n * k
    live = int((flat < held).sum())
    r = min(total, live + 5) if share is True else total
    rows = rng.randn(r, c)
    if share:
        rows[live:] = np.nan
    return (jnp.asarray(rows, dtype), jnp.asarray(weights, dtype),
            jnp.asarray(order[:r]), jnp.asarray(np.minimum(inverse, r - 1)),
            jnp.int32(live) if share else None)


def _gather_einsum(rows, weights, order, inverse, live_rows):
    """What the block ran before: the mask over the result, every pair's
    row gathered to ``[N*K, C]`` (a pair past the buffer reads its last,
    zero row), ``einsum nkc,nk->nc`` in float32."""
    n, k = weights.shape
    if live_rows is not None:
        rows = jnp.where(llama._live_mask(rows.shape[0], live_rows), rows, 0)
    per_pair = rows[inverse].reshape(n, k, -1)
    return jnp.einsum("nkc,nk->nc", per_pair, weights,
                      preferred_element_type=jnp.float32).astype(rows.dtype)


def _value_and_grads(fn, rows, weights, *indices):
    cot = jax.random.normal(jax.random.PRNGKey(9),
                            (weights.shape[0], rows.shape[1]), rows.dtype)

    def loss(rows, weights):
        out = fn(rows, weights, *indices)
        return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        rows, weights)
    return (out, *grads)


def _assert_close(got, want, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("share", [False, True, "every_pick"],
                         ids=["all_held", "share", "share_every_pick"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_combine_rows_against_the_gather_and_einsum(k, share, dtype):
    """Value, ``d rows`` and ``d weights``, by the rule on the sorted side
    (a buffer that ends before every pick) and by the one that keeps the
    gathered rows (a row for every pick); with a share the rows from
    ``live_rows`` on hold NaN and nothing of it leaks, forward or back."""
    args = _token_side(k, share, dtype)
    got = _value_and_grads(llama._combine_rows, *args)
    want = _value_and_grads(_gather_einsum, *args)
    if share:
        # the old form's d rows is masked where ours is 0 * finite
        assert not np.asarray(got[1], np.float32)[int(args[4]):].any()
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "share"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_dispatch_transpose_is_the_unweighted_gather_sum(k, share):
    """``_dispatch_rows``' backward against its rule as it stood — the
    mask's transpose over the cotangent, ``g[inverse]`` to ``[N*K, C]``,
    the sum over k in float32 — with the cotangent's rows from
    ``live_rows`` on poisoned."""
    rows, _, order, inverse, live_rows = _token_side(k, share, jnp.bfloat16)
    n = inverse.shape[0] // k
    tokens = jax.random.normal(jax.random.PRNGKey(2), (n, rows.shape[1]),
                               jnp.bfloat16)
    x, pull = jax.vjp(
        lambda t: llama._dispatch_rows(t, order, inverse, live_rows), tokens)
    want_x = tokens[order // k]
    g = rows  # finite below live_rows, NaN from there on
    if share:
        live = llama._live_mask(rows.shape[0], live_rows)
        want_x, g_masked = jnp.where(live, want_x, 0), jnp.where(live, g, 0)
    else:
        g_masked = g
    assert (x == want_x).all()
    want = jnp.sum(g_masked[inverse].reshape(n, k, -1), axis=1,
                   dtype=jnp.float32).astype(g.dtype)
    (got,) = pull(g)
    assert (got == want).all()


def test_combine_rows_check_grads():
    rows, weights, order, inverse, live_rows = _token_side(
        4, True, jnp.float32, n=12, c=8)
    rows = jnp.nan_to_num(rows)  # finite differences read every row
    jax.test_util.check_grads(
        lambda r, w: llama._combine_rows(r, w, order, inverse, live_rows),
        (rows, weights), order=1, modes=("rev",), atol=1e-2, rtol=1e-2)


@pytest.fixture
def small_tiles(monkeypatch):
    """Result tiles of 16 tokens and 8 rows in flight: several tiles, and
    several chunks a tile, at a toy token count."""
    monkeypatch.setattr(gather_sum_op, "_TILE_TOKENS", 16)
    monkeypatch.setattr(gather_sum_op, "_CHUNK", 8)


def _assert_kernel_is_reference(rows, index, weights):
    assert gather_sum_op._kernel_fits(rows, index)
    want = gather_sum_op.gather_sum(rows, index, weights, backend="reference")
    got = gather_sum_op.gather_sum(rows, index, weights, backend="pallas",
                                   interpret=True)
    assert got.shape == want.shape == (index.shape[0], rows.shape[1])
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert (got == want).all()
    return got


# 48 tokens: three result tiles, and no multiple of the 32 // k tokens the
# kernel's tile was when it followed k
@pytest.mark.parametrize("n", [64, 48])
@pytest.mark.parametrize("c", [1024, 384], ids=["8_lane_tiles",
                                                "3_lane_tiles"])
@pytest.mark.parametrize("share", [False, True, "every_pick"],
                         ids=["all_held", "share", "share_every_pick"])
@pytest.mark.parametrize("k", [1, 4, 6, 8, 10])
def test_gather_sum_kernel_is_its_reference_bit_for_bit(small_tiles, k, share,
                                                        c, n):
    """The Pallas kernel in interpret mode, at any K and any whole number
    of lane tiles: the dead picks start no DMA and contribute a selected
    zero (their rows hold NaN); with every expert held, and in the buffer
    a skewed step falls back to (``R = N*K``), a tile's picks are all live
    and take several chunks of rows in flight."""
    rows, weights, _, inverse, _ = _token_side(
        k, share, jnp.bfloat16, n=n, c=c)
    _assert_kernel_is_reference(rows, inverse.reshape(weights.shape), weights)


def _live_in(tokens, n, k, c=384, seed=5):
    """``(rows, index, weights)`` with every pick of ``tokens`` live, each
    on a row of its own, and every other pick dead on a row of NaN."""
    rng = np.random.RandomState(seed)
    live = np.zeros((n, k), bool)
    live[tokens] = True
    r = int(live.sum()) + 3
    index = np.full((n, k), r - 1, np.int32)
    index[live] = rng.permutation(r - 3)
    rows = rng.randn(r, c)
    rows[r - 3:] = np.nan
    weights = np.where(live, rng.rand(n, k) + 0.1, 0.0)
    return (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(index),
            jnp.asarray(weights, jnp.bfloat16))


@pytest.mark.parametrize("tokens", [
    slice(16, 32), slice(40, 41), slice(0, 1), slice(63, 64), slice(0, 0)],
    ids=["one_tile_whole", "one_token", "first_token", "last_token", "none"])
def test_gather_sum_kernel_where_the_live_picks_crowd(small_tiles, tokens):
    """What a loop over the live picks can get wrong: every live pick of
    the call in ONE tile of tokens (96 of them, twelve chunks of rows in
    flight, the tiles around it none), a token whose K picks are all live
    and no other, the first and the last token of the call, and a call
    with no live pick at all.  A tile without a live pick is zeros, and the
    rows of NaN are never read."""
    rows, index, weights = _live_in(tokens, n=64, k=6)
    got = np.asarray(_assert_kernel_is_reference(rows, index, weights),
                     np.float32)
    dead = np.ones(64, bool)
    dead[tokens] = False
    assert not got[dead].any() and got[~dead].all()


def test_gather_sum_kernel_sums_a_token_in_ascending_k(small_tiles):
    """One token, its K picks all live on rows that cancel unless they
    are added in ascending k: ``(big + small) - big`` in float32."""
    values = (2.0 ** 20, 1.0, -(2.0 ** 20), 2.0 ** -8)
    k = len(values)
    rows = jnp.asarray(np.stack([np.full(256, v) for v in values]),
                       jnp.bfloat16)
    index = jnp.zeros((16, k), jnp.int32).at[3].set(jnp.arange(k))
    weights = jnp.zeros((16, k), jnp.bfloat16).at[3].set(1.0)
    got = _assert_kernel_is_reference(rows, index, weights)
    acc = np.float32(0)
    for v in values:
        acc = np.float32(acc + np.float32(v))
    assert float(got[3, 0]) == float(jnp.asarray(acc, jnp.bfloat16))


@pytest.mark.parametrize("case", ["float32", "narrow", "ragged_tile", "mesh",
                                  "k_10", "21_lane_tiles", "three_tiles"])
def test_gather_sum_kernel_chooses_itself_by_shape(small_tiles, case):
    """bfloat16 rows of whole 128-column lane tiles, a power of two of at
    least 8 tokens that divides the token count, no free mesh axis:
    anything else is the ``jax.numpy`` form.  K is no part of the rule."""
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    width = {"narrow": 192, "21_lane_tiles": 2688}.get(case, 1024)
    tokens = {"ragged_tile": 60, "three_tiles": 24576}.get(case, 64)
    rows = jnp.zeros((16, width), dtype)
    index = jnp.zeros((tokens, 10 if case == "k_10" else 4), jnp.int32)
    if case == "mesh":
        from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh

        with jax.set_mesh(build_mesh(MeshSpec(fsdp=2), jax.devices()[:2])):
            assert not gather_sum_op._kernel_fits(rows, index)
    fits = case in ("mesh", "k_10", "21_lane_tiles", "three_tiles")
    assert gather_sum_op._kernel_fits(rows, index) == fits


@pytest.mark.parametrize("held", [8, 2], ids=["all_held", "share"])
def test_block_with_the_kernel_is_the_block_without(small_tiles, monkeypatch,
                                                    held):
    """``_moe_swiglu`` forward and gradients with ``gather_sum`` steered to
    the kernel (interpret mode) in the combine and in the dispatch's
    transpose, against the ``jax.numpy`` form."""
    d = 1024
    cfg = llama.LlamaConfig.tiny(
        n_layer=1, d_model=d, d_ff=F, n_head=4, n_kv_head=2, num_experts=E,
        top_k=K, moe_every=1, experts_held=held, dtype=jnp.bfloat16)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    moe = {"router": jax.random.normal(k[0], (d, E)) * 0.05,
           "wg": jax.random.normal(k[1], (held, d, F)) * 0.03,
           "wi": jax.random.normal(k[2], (held, d, F)) * 0.03,
           "wo": jax.random.normal(k[3], (held, F, d)) * 0.3}
    # a share: 1,024 pairs, a quarter held, in the first size of 512 rows
    x = jax.random.normal(k[4], (2, 64 if held == E else 256, d),
                          jnp.bfloat16)

    def run():
        def loss(x, moe):
            out, _ = llama._moe_swiglu(x, moe, cfg)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, moe)
        return jax.tree.leaves((out, grads))

    want = run()
    calls = []

    def steered(rows, index, weights):
        calls.append(rows.shape)
        return gather_sum_op.gather_sum(rows, index, weights,
                                        backend="pallas", interpret=True)
    monkeypatch.setattr(llama, "gather_sum", steered)
    got = run()
    if held == E:
        # every pick live: gathered whole, both ways, as before
        assert not calls
    else:
        assert llama._moe_buffer_bounds(512, K, E, held) == (512, 1024)
        # the combine and the dispatch's transpose, in each size's branch
        assert [rows for rows, _ in calls].count(512) >= 2
        assert [rows for rows, _ in calls].count(1024) >= 2
    for a, b in zip(got, want):
        assert (a == b).all()
