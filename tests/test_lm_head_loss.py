"""``llama.loss_fn`` through the reduced fused lm-head loss.

With ``fused_lm_head`` the loss takes ``ce`` from
``linear_softmax_cross_entropy_sum``, whose forward rule forms the head's
gradients in the scan that computes the loss.  These hold it to the formula
it replaced — the masked mean of the per-token fused op — for every way
``loss_fn`` is called and every path of ``accelerate()`` that wraps it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from test_ops import _eqns, head_matmuls_and_scans  # noqa: I100 - shared

from dlrover_tpu.models import llama
from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec

B, S = 4, 16


def _cfg(routed=False, **over):
    base = dict(n_layer=1, vocab_size=512, dtype=jnp.float32)
    if routed:
        base.update(num_experts=4, top_k=2, moe_every=1)
    base.update(over)
    return llama.LlamaConfig.tiny(**base)


def _batch(mask, vocab=512):
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, vocab, (B, S + 1)).astype(np.int32))}
    if mask != "none":
        # two documents and a padded tail in every row
        seg = np.zeros((B, S + 1), np.int32)
        seg[:, 7:] = 1
        seg[:, 13:] = -1
        batch["segment_ids"] = jnp.asarray(
            seg if mask == "s_plus_1" else seg[:, :-1])
    return batch


def _valid(batch):
    """``loss_fn``'s own mask, written out: pairs inside one document."""
    seg = batch.get("segment_ids")
    if seg is None:
        return None, None
    same = ((seg[:, 1:] == seg[:, :-1]) & (seg[:, :-1] >= 0)).astype(
        jnp.float32)
    if seg.shape[-1] == S + 1:
        return seg[:, :-1], same
    return seg, jnp.concatenate([same, jnp.zeros((B, 1))], axis=-1)


def per_token_loss_fn(params, batch, cfg, *, metrics=False, **kw):
    """``loss_fn`` as it stood before the reduced op: the per-token fused
    op, then the masked mean."""
    tokens, targets = llama.split_batch(batch)
    seg, valid = _valid(batch)
    x, aux = llama.forward_hidden(params, tokens, cfg, segment_ids=seg)
    per_tok = linear_softmax_cross_entropy(
        x, params["lm_head"].astype(cfg.dtype), targets)
    if valid is not None:
        ce = jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    else:
        ce = jnp.mean(per_tok)
    loss = ce + kw.get("moe_aux_weight", 1e-2) * aux["moe_aux"]
    if "moe_z" in aux:
        loss = loss + kw.get("moe_z_weight", 0.0) * aux["moe_z"]
    if metrics and "moe_z" in aux:
        return loss, {k: aux[k] for k in (
            "moe_tokens_per_expert", "moe_aux", "moe_z")}
    return loss


def _assert_trees_close(got, want, atol):
    got_l, tree = jax.tree_util.tree_flatten(got)
    want_l, tree2 = jax.tree_util.tree_flatten(want)
    assert tree == tree2
    for a, b in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


@pytest.mark.parametrize("routed", [False, True], ids=["dense", "routed"])
@pytest.mark.parametrize("mask", ["none", "s_plus_1", "s"])
def test_loss_and_every_gradient_equal_the_per_token_formula(mask, routed):
    cfg = _cfg(routed)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(mask)
    kw = dict(moe_z_weight=1e-3, metrics=routed)
    (got, got_g), (want, want_g) = (
        jax.value_and_grad(
            lambda p: f(p, batch, cfg, **kw), has_aux=routed)(params)
        for f in (
            lambda p, b, c, **k: llama.loss_fn(
                p, b, c, fused_lm_head=True, **k),
            per_token_loss_fn))
    if routed:
        (got, got_m), (want, want_m) = got, want
        _assert_trees_close(got_m, want_m, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert np.isfinite(float(got)) and float(got) > 1.0
    _assert_trees_close(got_g, want_g, atol=1e-5)
    # and the unfused branch agrees on the loss
    unfused = llama.loss_fn(params, batch, cfg, fused_lm_head=False, **kw)
    np.testing.assert_allclose(
        got, unfused[0] if routed else unfused, rtol=1e-5)


def _holds_scan(eqn):
    return eqn.primitive.name == "scan" or any(
        inner.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params)
        for inner in _eqns(sub))


@pytest.mark.parametrize("grad", [True, False])
def test_loss_fn_holds_three_head_matmuls_in_one_scan(grad):
    """Engagement: under ``value_and_grad`` of ``loss_fn`` the head is
    three vocab-sized matmuls in ONE scan (the per-token op had four in
    two); without a gradient one, and no [D, V] accumulator."""
    cfg = _cfg(vocab_size=4096)  # the default policy takes the fused path
    assert llama.uses_fused_lm_head(cfg)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch("s_plus_1", vocab=4096)

    def f(p):
        return llama.loss_fn(p, batch, cfg)

    fn = jax.value_and_grad(f) if grad else f
    assert head_matmuls_and_scans(
        fn, params, vocab=4096) == ((3, 1, True) if grad else (1, 1, False))
    # ... and that scan sits under the scope the benchmark reads
    scoped = [
        e for e in jax.make_jaxpr(fn)(params).jaxpr.eqns
        if "lm_head_loss" in str(e.source_info.name_stack)
        and _holds_scan(e)]
    assert len(scoped) == 1


def _job(loss, cfg, strategy):
    return accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.sgd(0.5),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=strategy,
        devices=jax.devices()[:strategy.mesh.num_devices])


#: every path of ``accelerate()`` that wraps the loss: (strategy, what
#: ``loss_fn`` is called with)
ACCELERATE_PATHS = {
    "plain": (Strategy(mesh=MeshSpec()), {}),
    "grad_accum": (Strategy(mesh=MeshSpec(), grad_accum=2), {}),
    "remat_full": (Strategy(mesh=MeshSpec(), remat="full"), {}),
    "remat_dots": (Strategy(mesh=MeshSpec(), remat="dots"), {}),
    "metrics": (Strategy(mesh=MeshSpec()), {"metrics": True}),
    "fsdp2_tp2": (Strategy(mesh=MeshSpec(fsdp=2, tp=2)), {}),
}


@pytest.mark.parametrize("path", sorted(ACCELERATE_PATHS))
def test_one_step_of_every_accelerate_path_matches_the_per_token_loss(path):
    """One SGD step from the same state: the loss it reports, the gradient
    norm and every updated parameter are those of the per-token formula."""
    strategy, kw = ACCELERATE_PATHS[path]
    routed = bool(kw)
    cfg = _cfg(routed)
    batch = {"tokens": np.asarray(_batch("none")["tokens"])}
    out = []
    for f in (lambda p, b: llama.loss_fn(
            p, b, cfg, fused_lm_head=True, **kw),
            lambda p, b: per_token_loss_fn(p, b, cfg, **kw)):
        job = _job(f, cfg, strategy)
        state = job.create_state(jax.random.PRNGKey(0))
        state, m = job.train_step(state, {
            "tokens": jax.device_put(
                batch["tokens"], job.batch_sharding["tokens"])})
        out.append((m, state["params"]))
    (got_m, got_p), (want_m, want_p) = out
    np.testing.assert_allclose(got_m["loss"], want_m["loss"], rtol=1e-5)
    np.testing.assert_allclose(
        got_m["grad_norm"], want_m["grad_norm"], rtol=1e-4)
    _assert_trees_close(got_p, want_p, atol=1e-5)
