"""A looped ``LlamaConfig`` (``loop_passes``, ``branch_norm``,
``exit_gate_beta``: the Ouro block) on the normal path, and the head op's
weight gradient it rests on.

With the defaults nothing of it may show: ``forward_hidden`` and ``loss_fn``
are held, bit for bit, to the formula they had before the loop, written out
here.  With the settings on: a shared layer's gradient is the sum over its
applications, the exit distribution is a distribution, block remat changes
no value, ``accelerate()`` hands out the loop's counters, and every path
that applies each layer once refuses the config by the setting's name.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import REFUSING_PATH_NAMES, refusing_calls
from test_lm_head_loss import (  # noqa: I100 - shared
    _assert_trees_close as _tree_close,
)

from dlrover_tpu.models import hf_convert, llama
from dlrover_tpu.ops.cross_entropy import (
    linear_softmax_cross_entropy,
    linear_softmax_cross_entropy_sum,
)
from dlrover_tpu.ops.rmsnorm import rmsnorm
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec

B, S, T = 2, 16, 3


def _cfg(**over):
    base = dict(n_layer=2, vocab_size=512, dtype=jnp.float32)
    base.update(over)
    return llama.LlamaConfig.tiny(**base)


def _looped(**over):
    return _cfg(loop_passes=T, branch_norm=True, exit_gate_beta=0.1, **over)


def _tokens(vocab=512, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (B, S + 1)).astype(np.int32))


def _tree_equal(got, want):
    got_l, tree = jax.tree_util.tree_flatten(got)
    want_l, tree2 = jax.tree_util.tree_flatten(want)
    assert tree == tree2
    for a, b in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the defaults: what the four accepted cells compute ----------------------


def _hidden_before_the_loop(params, tokens, cfg):
    """``forward_hidden`` as it stood before ``loop_passes``: one walk over
    the layers, one final norm."""
    b, s = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    moe_aux = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        x, stats = llama.block_apply(layer, x, cfg, positions)
        if stats:
            moe_aux = moe_aux + stats["moe_aux"]
    return rmsnorm(x, params["ln_f"], eps=cfg.rms_eps), moe_aux


def _loss_before_the_loop(params, batch, cfg, fused):
    tokens, targets = llama.split_batch(batch)
    x, moe_aux = _hidden_before_the_loop(params, tokens, cfg)
    w = params["lm_head"].astype(cfg.dtype)
    if fused:
        ce = linear_softmax_cross_entropy_sum(x, w, targets, None)
    else:
        ce = jnp.mean(llama.softmax_cross_entropy(
            (x @ w).astype(jnp.float32), targets))
    return ce + 1e-2 * moe_aux


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("routed", [False, True], ids=["dense", "routed"])
def test_defaults_compute_bit_for_bit_what_they_did(routed, fused):
    cfg = _cfg(**(dict(num_experts=4, top_k=2, moe_every=1) if routed
                  else {}))
    assert (cfg.loop_passes, cfg.branch_norm, cfg.exit_gate_beta) == (
        1, False, None)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert "exit_gate" not in params and "ln1_out" not in params["layers"][0]
    batch = {"tokens": _tokens()}
    hidden, aux = llama.forward_hidden(params, batch["tokens"][:, :-1], cfg)
    want_hidden, want_aux = _hidden_before_the_loop(
        params, batch["tokens"][:, :-1], cfg)
    assert hidden.shape == (B, S, cfg.d_model) and "exit_logits" not in aux
    _tree_equal((hidden, aux["moe_aux"]), (want_hidden, want_aux))
    got = jax.value_and_grad(lambda p: llama.loss_fn(
        p, batch, cfg, fused_lm_head=fused))(params)
    want = jax.value_and_grad(lambda p: _loss_before_the_loop(
        p, batch, cfg, fused))(params)
    _tree_equal(got, want)


def test_init_draws_the_same_weights_with_and_without_the_loop():
    """The gate takes the one key ``init_params`` had left over: every
    other leaf of a looped model is the plain model's."""
    plain = llama.init_params(jax.random.PRNGKey(3), _cfg())
    looped = llama.init_params(jax.random.PRNGKey(3), _looped())
    gate = looped.pop("exit_gate")
    assert gate["w"].shape == (64,) and float(gate["b"]) == 0.0
    for layer in looped["layers"]:
        for name in ("ln1_out", "ln2_out"):
            np.testing.assert_array_equal(layer.pop(name), np.ones(64))
    _tree_equal(looped, plain)
    axes = llama.param_logical_axes(_looped())
    assert axes["exit_gate"] == {"w": (None,), "b": ()}
    assert axes["layers"][0]["ln1_out"] == (None,)


# -- the loop ---------------------------------------------------------------


def _untied_loss(layers_by_pass, params, batch, cfg):
    """The looped loss with a separate copy of the layers for each pass:
    the same block, final norm, gate and head, no weight met twice."""
    tokens, targets = llama.split_batch(batch)
    b, s = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    streams, logits = [], []
    for layers in layers_by_pass:
        for layer in layers:
            x, _ = llama.block_apply(layer, x, cfg, positions)
        x = rmsnorm(x, params["ln_f"], eps=cfg.rms_eps)
        streams.append(x)
        logits.append(x @ params["exit_gate"]["w"] + params["exit_gate"]["b"])
    loss, _ = llama.exit_expectation_loss(
        jnp.stack(streams), jnp.stack(logits), params["lm_head"], targets,
        cfg, fused_lm_head=False)
    return loss


def test_a_shared_layers_gradient_is_the_sum_over_its_applications():
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    batch = {"tokens": _tokens()}
    loss, grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg))(params)
    untied = [params["layers"]] * T
    want_loss, per_pass = jax.value_and_grad(_untied_loss)(
        untied, params, batch, cfg)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert len(per_pass) == T
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_pass)
    _tree_close(grads["layers"], summed, atol=2e-6)
    # and no application's share is nothing
    for one in per_pass:
        assert float(jnp.linalg.norm(one[0]["wq"])) > 1e-4


@pytest.mark.parametrize("scale", [0.0, 1.0, 40.0])
def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest(
        scale):
    logits = scale * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 7))
    p = llama.exit_distribution(logits)
    assert p.shape == logits.shape and p.dtype == jnp.float32
    assert float(jnp.min(p)) >= 0.0
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-7)
    np.testing.assert_allclose(
        p[-1], jnp.prod(1.0 - lam[:-1], 0), atol=1e-7)
    # the last pass's own logit is not used
    moved = logits.at[-1].add(3.0)
    np.testing.assert_array_equal(llama.exit_distribution(moved), p)


def test_saturated_gates_leave_loss_and_gradients_finite():
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    params["exit_gate"]["b"] = jnp.asarray(200.0)  # everything exits at once
    loss, grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": _tokens()}, cfg))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


def test_forward_hidden_returns_every_pass_and_its_gate_logit():
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens()[:, :-1]
    streams, aux = llama.forward_hidden(params, toks, cfg)
    assert streams.shape == (T, B, S, cfg.d_model)
    assert aux["exit_logits"].shape == (T, B, S)
    assert aux["exit_logits"].dtype == jnp.float32
    # every pass ends in the final norm: unit RMS under gains of one
    np.testing.assert_allclose(
        jnp.sqrt(jnp.mean(jnp.square(streams), -1)), 1.0, atol=1e-3)
    logits, _ = llama.forward(params, toks, cfg)
    assert logits.shape == (T, B, S, cfg.vocab_size)


def test_loss_is_the_expectation_over_exit_steps_written_out():
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens()
    logits, aux = llama.forward(params, toks[:, :-1], cfg)
    ce = -jnp.take_along_axis(
        jax.nn.log_softmax(logits), toks[None, :, 1:, None], -1)[..., 0]
    lam = jax.nn.sigmoid(aux["exit_logits"])
    p = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                   (1 - lam[0]) * (1 - lam[1])])
    entropy = -jnp.sum(p * jnp.log(p), 0)
    want = jnp.mean(jnp.sum(p * ce, 0) - 0.1 * entropy)
    loss, m = llama.loss_fn(params, {"tokens": toks}, cfg, metrics=True)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(m["loop_ce"], jnp.mean(ce, (1, 2)), rtol=1e-6)
    np.testing.assert_allclose(
        m["loop_exit_prob"], jnp.mean(p, (1, 2)), rtol=1e-6)
    np.testing.assert_allclose(
        m["loop_exit_entropy"], jnp.mean(entropy), rtol=1e-6)
    # the scalar alone without metrics, the same number
    assert float(llama.loss_fn(params, {"tokens": toks}, cfg)) == float(loss)


@pytest.mark.parametrize("mask", ["s_plus_1", "s"])
def test_packed_sequences_weight_the_real_tokens_only(mask):
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens()
    seg = np.zeros((B, S + 1), np.int32)
    seg[:, 9:] = 1
    seg[:, 14:] = -1
    seg = jnp.asarray(seg if mask == "s_plus_1" else seg[:, :-1])
    batch = {"tokens": toks, "segment_ids": seg}
    fused, m = llama.loss_fn(params, batch, cfg, fused_lm_head=True,
                             metrics=True)
    unfused = llama.loss_fn(params, batch, cfg, fused_lm_head=False)
    np.testing.assert_allclose(fused, unfused, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(m["loop_exit_prob"]), 1.0, atol=1e-6)


def test_fused_and_unfused_heads_agree_on_every_gradient():
    cfg = _looped()
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    batch = {"tokens": _tokens()}
    got, want = (jax.value_and_grad(lambda p: llama.loss_fn(
        p, batch, cfg, fused_lm_head=fused))(params)
        for fused in (True, False))
    _tree_close(got, want, atol=2e-6)
    gate = got[1]["exit_gate"]
    assert float(jnp.linalg.norm(gate["w"])) > 1e-3 and float(
        jnp.abs(gate["b"])) > 1e-4


def test_remat_block_changes_no_value_of_a_looped_step():
    batch = {"tokens": _tokens()}
    out = []
    for remat in (False, True):
        cfg = _looped(remat_block=remat)
        params = llama.init_params(jax.random.PRNGKey(1), cfg)
        out.append(jax.jit(jax.value_and_grad(
            lambda p, cfg=cfg: llama.loss_fn(p, batch, cfg, metrics=True),
            has_aux=True))(params))
    _tree_close(out[1], out[0], atol=1e-6)
    # each block APPLICATION is rematerialised on its own
    cfg = _looped(remat_block=True)
    jaxpr = jax.make_jaxpr(lambda p: llama.loss_fn(p, batch, cfg))(
        llama.init_params(jax.random.PRNGKey(1), cfg))
    remats = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name in ("checkpoint", "remat2", "remat")]
    assert len(remats) == cfg.block_applications == T * 2


def test_accelerate_trains_a_looped_model_and_hands_out_its_counters():
    cfg = _looped(vocab_size=4096, remat_block=True)  # the fused head
    job = accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg, metrics=True),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-2),
        sample_batch={"tokens": np.zeros((4, S + 1), np.int32)},
        strategy=Strategy(mesh=MeshSpec(fsdp=2)), param_specs="planner",
        devices=jax.devices()[:2])
    state = job.create_state(jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(
        0, 4096, (4, S + 1)).astype(np.int32)
    batch = {"tokens": jax.device_put(toks, job.batch_sharding["tokens"])}
    losses = []
    for _ in range(8):
        state, m = job.train_step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
    assert set(m) == {"loss", "grad_norm", "loop_ce", "loop_exit_prob",
                      "loop_exit_entropy"}
    assert m["loop_ce"].shape == (T,) and m["loop_exit_prob"].shape == (T,)
    np.testing.assert_allclose(jnp.sum(m["loop_exit_prob"]), 1.0, atol=1e-5)
    assert 0.0 < float(m["loop_exit_entropy"]) <= np.log(T) + 1e-5
    assert "block_applications" in job.program
    scopes = {tuple(v)[1] for v in job.program["scopes"].values()}
    assert {"attention", "mlp", "final_norm", "lm_head_loss",
            "exit_gate"} <= scopes


def test_program_summary_counts_forward_applications_of_the_block():
    """The op_names of a looped step with block remat as the chip's
    compiler writes them (``tests/test_aot_compile.py`` compiles the real
    thing): two passes forward, each recomputed in front of its backward."""
    from dlrover_tpu.parallel.accelerate import program_summary

    call = ('  %k.{n} = bf16[8] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={{op_name="jit(train_step)/{path}'
            '/pallas_call"}}')
    fwd = "jvp(attention)/flash_fwd"
    remat = ("transpose(jvp(jvp()))/checkpoint/rematted_computation/"
             "attention/flash_fwd")
    bwd = "transpose(jvp(jvp()))/checkpoint/attention/flash_bwd_dq"
    norm = "jvp(attention)/rmsnorm_fwd"
    text = "\n".join(call.format(n=n, path=path) for n, path in enumerate(
        [fwd, norm, fwd, remat, bwd, remat, bwd]))
    got = program_summary(text)
    assert got["kernels"] == {
        "flash_fwd": 4, "rmsnorm_fwd": 1, "flash_bwd_dq": 2}
    assert got["block_applications"] == 2
    assert program_summary("")["block_applications"] == 0


# -- the head op's weights ----------------------------------------------------


def _head_case():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (40, 16))
    w = jax.random.normal(k[1], (16, 96)) * 0.3
    labels = jax.random.randint(k[2], (40,), 0, 96)
    theta = jax.random.normal(k[3], (40,))
    return x, w, labels, theta


def _weighted(op):
    """``sum_r sigmoid(theta_r) * ce_r`` through the reduced op or written
    out unfused."""
    def loss(theta, x, w, labels):
        weights = jax.nn.sigmoid(theta) / theta.shape[0]
        if op == "reduced":
            return linear_softmax_cross_entropy_sum(
                x, w, labels, weights, chunk_rows=16)
        # plain autodiff of sum(w * ce): the per-token fused op's backward
        # scan does not type inside a shard_map
        ce = -jnp.take_along_axis(
            jax.nn.log_softmax(x @ w), labels[:, None], -1)[:, 0]
        return jnp.sum(weights * ce)
    return loss


def test_head_op_hands_its_weights_their_gradient():
    x, w, labels, theta = _head_case()
    got, want = (jax.value_and_grad(_weighted(op), argnums=(0, 1, 2))(
        theta, x, w, labels) for op in ("reduced", "unfused"))
    _tree_close(got, want, atol=1e-6)
    assert float(jnp.linalg.norm(got[1][0])) > 1e-3
    # the cotangent is g x the row's own loss
    rows = linear_softmax_cross_entropy(x, w, labels, chunk_rows=16)
    g_weights = jax.grad(
        lambda wt: 3.0 * linear_softmax_cross_entropy_sum(
            x, w, labels, wt, chunk_rows=16))(jnp.ones((40,)) / 40)
    np.testing.assert_allclose(g_weights, 3.0 * rows, rtol=1e-6)


def test_head_op_weight_gradient_inside_the_int8_reductions_shard_map():
    """``accelerate()``'s int8-compressed dp reduction differentiates the
    loss inside a full-manual ``shard_map`` with the parameters cast to
    varying: the weights' cotangent has to type there too."""
    from jax.sharding import Mesh, PartitionSpec as P

    x, w, labels, theta = _head_case()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))

    def local(op):
        def fn(theta, x, w, labels):
            w = jax.lax.pcast(w, "dp", to="varying")
            loss, grads = jax.value_and_grad(_weighted(op), argnums=(0, 2))(
                theta, x, w, labels)
            return jax.lax.pmean((loss, grads), "dp")
        return fn

    out = []
    for op in ("reduced", "unfused"):
        # theta is per row: shard it with the rows
        out.append(jax.jit(jax.shard_map(
            local(op), mesh=mesh,
            in_specs=(P("dp"), P("dp"), P(), P("dp")),
            out_specs=(P(), (P("dp"), P()))))(theta, x, w, labels))
    _tree_close(out[0], out[1], atol=1e-6)


def test_constant_weights_give_the_values_they_gave():
    """No weights, or weights no parameter reaches: the scalar and the
    gradients of the mean of the per-token op; the row losses ride along
    for reading and carry no gradient."""
    x, w, labels, _ = _head_case()
    want = jax.value_and_grad(lambda x, w: jnp.mean(
        linear_softmax_cross_entropy(x, w, labels, chunk_rows=16)),
        argnums=(0, 1))(x, w)
    got = jax.value_and_grad(lambda x, w: linear_softmax_cross_entropy_sum(
        x, w, labels, chunk_rows=16), argnums=(0, 1))(x, w)
    _tree_close(got, want, atol=1e-6)

    def with_rows(x, w):
        total, rows = linear_softmax_cross_entropy_sum(
            x, w, labels, chunk_rows=16, with_row_losses=True)
        return total + 0.0 * jnp.sum(rows), rows

    (total, rows), grads = jax.value_and_grad(
        with_rows, argnums=(0, 1), has_aux=True)(x, w)
    np.testing.assert_array_equal(total, got[0])
    _tree_equal(grads, got[1])
    np.testing.assert_allclose(
        rows, linear_softmax_cross_entropy(x, w, labels, chunk_rows=16),
        rtol=1e-6)


# -- what cannot compute it says so -------------------------------------------

SETTINGS = {
    "loop_passes": dict(loop_passes=2, exit_gate_beta=0.1),
    "branch_norm": dict(branch_norm=True),
}


@pytest.mark.parametrize("where", sorted(refusing_calls(None)))
@pytest.mark.parametrize("setting", sorted(SETTINGS))
def test_paths_that_apply_each_layer_once_refuse_by_name(setting, where):
    cfg = _cfg(**SETTINGS[setting])
    with pytest.raises(ValueError, match=setting) as e:
        refusing_calls(cfg)[where]()
    assert REFUSING_PATH_NAMES[where] in str(e.value)
    assert "training path only" in str(e.value)


def test_hf_config_of_a_looped_model_is_refused_by_name():
    hf = type("HfConfig", (), dict(
        hidden_size=64, num_attention_heads=4, vocab_size=256,
        num_hidden_layers=2, intermediate_size=128, total_ut_steps=4))()
    with pytest.raises(ValueError, match="total_ut_steps=4"):
        hf_convert.config_from_hf(hf)
    hf.total_ut_steps = 1
    assert hf_convert.config_from_hf(hf).loop_passes == 1


@pytest.mark.parametrize("over,match", [
    (dict(loop_passes=4), "exit_gate_beta=None"),
    (dict(exit_gate_beta=0.1), "loop_passes=1"),
    (dict(loop_passes=2, exit_gate_beta=0.1, num_experts=4),
     "num_experts=4"),
])
def test_config_refuses_half_a_looped_model(over, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**over)


# -- the count the trainer's MFU print uses -----------------------------------


def test_flops_per_token_counts_passes_and_heads_as_the_benchmark_does():
    """``llama.flops_per_token`` and the benchmark adapter's count differ
    by convention in two known places (the trainer's charges the whole
    S x S attention square and the embedding as a matmul); what a pass
    adds is the same in both."""
    from benchmark.adapters import ouro

    hf = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=4, vocab_size=512, num_hidden_layers=2,
              total_ut_steps=4)
    seq = 128
    mc = functools.partial(
        _cfg, max_seq_len=seq, n_kv_head=4, branch_norm=True,
        exit_gate_beta=0.1)
    per_pass = (llama.flops_per_token(mc(loop_passes=4))
                - llama.flops_per_token(mc(loop_passes=3)))
    want = (ouro.model_flops_per_token(hf, seq)["total"]
            - ouro.model_flops_per_token(
                dict(hf, total_ut_steps=3), seq)["total"])
    # full square against causal pairs: 12 L S d against 6 L (S + 1) d
    square_minus_causal = 2 * 64 * (12 * seq - 6 * (seq + 1))
    assert per_pass - square_minus_causal == pytest.approx(want, rel=1e-12)
    # and the plain model's count is what it was
    plain = _cfg(max_seq_len=seq, n_kv_head=4)
    p_layer = 4 * 64 * 64 + 3 * 64 * 128
    assert llama.flops_per_token(plain) == 6.0 * (
        2 * p_layer + 2 * 512 * 64) + 6.0 * 2 * 2 * seq * 64
    assert dataclasses.replace(plain).block_applications == 2
