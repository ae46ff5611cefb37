"""Block remat keeps what the delta rule's forward kernel put out.

``_gdn_mixer`` has no checkpoint of its own, and ``cfg.remat_block``'s policy
saves the three arrays ``ops/gated_delta.py`` names in its forward rule
(``o``, the final state, the entering states) beside the flash kernel's two,
so the gradient program runs ``gdn_chunk_fwd`` once per delta-rule layer
application: not again in front of the block's backward, and not a third
time in front of the mixer's.  Here, on the CPU, in the manner of
``test_remat_keeps_flash.py``: the model's rule steered to the Pallas kernels
in interpret mode (the dispatcher would pick the ``jax.numpy`` form, which
names nothing), the kernel counted in the jaxpr, the saved residuals listed,
no value changed, and the names doing nothing where no policy asks for them.
The gated norm behind the rule (``ops.gated_norm``) is steered to its kernels
too: it names nothing and keeps nothing, so it runs again in block remat's
pass — ``gated_norm_fwd`` twice a layer and ``gated_norm_bwd`` once, the
counts a compiled step's kernel table is held to on the chip.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import name_p, saved_residuals
from jax._src.interpreters import partial_eval as pe
from test_lm_head_loss import (  # noqa: I100 - shared
    _assert_trees_close as _tree_close,
)
from test_ops import _eqns  # noqa: I100 - shared
from test_remat_keeps_flash import _kernel_calls  # noqa: I100 - shared

from dlrover_tpu.models import llama
from dlrover_tpu.ops import gated_delta as gd
from dlrover_tpu.ops import gated_norm as gn
from dlrover_tpu.parallel.accelerate import REMAT_POLICIES

#: two chunks of ``llama.GDN_CHUNK`` a sequence, so a state ENTERS a chunk
B, S = 2, 128
F32 = jnp.float32
#: one tile of the kernels: two value heads of 128 lanes under one key head
HV, D = 2, 128


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setattr(llama, "gated_delta_chunked", functools.partial(
        gd.gated_delta_chunked, backend="pallas", interpret=True))
    monkeypatch.setattr(llama, "gated_norm", functools.partial(
        gn.gated_norm, backend="pallas", interpret=True))


def _cfg(**over):
    """Two delta-rule layers and an attention layer (on the CPU its
    ``jax.numpy`` form, which names nothing either), dense MLPs."""
    base = dict(
        vocab_size=512, n_layer=3, n_head=4, n_kv_head=2, d_model=32,
        d_ff=64, max_seq_len=S, dtype=F32, rms_eps=1e-6,
        layer_types=("linear_attention", "linear_attention", "attention"),
        gdn_k_heads=1, gdn_v_heads=HV, gdn_d_head=D, gdn_d_conv=4)
    base.update(over)
    return llama.LlamaConfig(**base)


def _case(**over):
    cfg = _cfg(**over)
    toks = np.random.RandomState(0).randint(0, 512, (B, S + 1))
    return (cfg, llama.init_params(jax.random.PRNGKey(1), cfg),
            {"tokens": jnp.asarray(toks.astype(np.int32))})


def _loss(cfg, batch):
    return lambda p: llama.loss_fn(p, batch, cfg)


def _grad_jaxpr(cfg, params, batch):
    return jax.make_jaxpr(jax.grad(_loss(cfg, batch)))(params).jaxpr


def _names(jaxpr):
    return {eqn.params["name"] for eqn in _eqns(jaxpr)
            if eqn.primitive is name_p}


# -- (a) the kernel runs once per delta-rule layer application ----------------


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_the_gradient_runs_gdn_chunk_fwd_once_per_layer(kernels, remat):
    cfg, params, batch = _case(remat_block=remat)
    calls = _kernel_calls(_grad_jaxpr(cfg, params, batch))
    assert cfg.gdn_layers == 2
    assert calls["gdn_chunk_fwd"] == calls["gdn_chunk_bwd"] == 2, calls
    # the gated norm keeps nothing: forward, and again in block remat's pass
    assert calls["gated_norm_fwd"] == (4 if remat else 2), calls
    assert calls["gated_norm_bwd"] == 2, calls


def test_a_policy_without_the_names_runs_gdn_chunk_fwd_twice(
        kernels, monkeypatch):
    """What the count above is held against: the same remat keeping nothing
    of the kernel recomputes it in front of every block's backward — and no
    third time, since the mixer has no checkpoint of its own."""
    monkeypatch.setattr(llama, "GDN_SAVED_NAMES", ())
    cfg, params, batch = _case(remat_block=True)
    calls = _kernel_calls(_grad_jaxpr(cfg, params, batch))
    assert calls["gdn_chunk_fwd"] == 2 * cfg.gdn_layers
    assert calls["gdn_chunk_bwd"] == cfg.gdn_layers


# -- (b) what one checkpointed application keeps ------------------------------


def _inside(kept):
    return [(aval, why) for aval, why in kept
            if "from the argument" not in why]


def test_one_application_keeps_its_inputs_and_the_kernels_outputs(kernels):
    """Under the policy ``forward_hidden`` gives its checkpoint (the counts
    above hold it to that) a delta-rule layer's application keeps nothing
    of its own but what the kernel's forward rule names — of the three the
    two that its backward reads: the final state leaves the mixer under
    ``stop_gradient`` alone (``gdn_state_rms``), so nothing holds it."""
    cfg, params, _ = _case(remat_block=True)

    def one_application(layer, x, positions):
        out, _ = jax.checkpoint(
            lambda layer, x, positions: llama.block_apply(
                layer, x, cfg, positions),
            policy=jax.checkpoint_policies.save_only_these_names(
                *llama.FLASH_SAVED_NAMES, *gd.SAVED_NAMES))(
                    layer, x, positions)
        return jnp.sum(out)

    kept = saved_residuals(
        one_application, params["layers"][0],
        jnp.zeros((B, S, cfg.d_model), cfg.dtype),
        jnp.broadcast_to(jnp.arange(S), (B, S)))
    inside = _inside(kept)
    # (the gated norm's kernels are in the mixer here and add nothing: their
    # residuals are their inputs)
    # ``o [B, S, H Dv]`` leaves the rule as the primal output too, and JAX
    # passes such a residual through a ``reduce_precision`` that changes
    # nothing; the entering states ``[B, c, J, hb Dk, Dv]`` with the one
    # block of both heads
    assert sorted((aval.shape, aval.dtype) for aval, _ in inside) == sorted([
        ((B, S, HV * D), jnp.float32),
        ((B, S // llama.GDN_CHUNK, 1, HV * D, D), cfg.dtype)]), kept
    assert any(why.startswith("named 'gdn_entering'") for _, why in inside)


def test_a_backward_that_reads_all_three_outputs_keeps_all_three():
    """The op alone, its output and its final state both differentiated
    through: the policy keeps the three arrays the forward rule names and
    nothing else, and the gradient holds the forward kernel once."""
    from test_gated_delta import TILED, _operands

    ops = _operands(0, s=S, **TILED)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*gd.SAVED_NAMES))
    def rule(*ops):
        o, state, _ = gd.gated_delta_chunked(
            *ops, chunk=llama.GDN_CHUNK, backend="pallas", interpret=True)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.sin(state))

    inside = _inside(saved_residuals(rule, *ops))
    # (``o`` and the final state are the rule's primal outputs too: the
    # ``reduce_precision`` of the test above)
    assert sorted(aval.shape for aval, _ in inside) == sorted([
        (B, S, HV * D), (B, 1, HV * D, D),
        (B, S // llama.GDN_CHUNK, 1, HV * D, D)]), inside
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(rule, (0, 1, 2, 3, 4)))(
        *ops).jaxpr)
    assert calls == {"gdn_chunk_fwd": 1, "gdn_chunk_bwd": 1}


# -- (c) no value changes -----------------------------------------------------


def test_remat_block_changes_no_value_under_the_kernels(kernels):
    out = []
    for remat in (False, True):
        cfg, params, batch = _case(remat_block=remat)
        out.append(jax.jit(jax.value_and_grad(_loss(cfg, batch)))(params))
    _tree_close(out[1], out[0], atol=1e-6)


# -- (d) the names are identities where no policy asks for them ---------------


@pytest.mark.parametrize("remat", ["full", "dots", "offload"])
def test_whole_loss_policies_name_none_of_the_three(remat):
    policy = REMAT_POLICIES[remat]
    for name in gd.SAVED_NAMES:
        verdict = policy(name_p, name=name)
        assert verdict is False or verdict is pe.Recompute, name


def test_without_remat_the_lowered_step_is_the_same_with_and_without_names(
        kernels, monkeypatch):
    cfg, params, batch = _case(remat_block=False)

    def text():
        lowered = jax.jit(jax.value_and_grad(_loss(cfg, batch))).lower(
            params).as_text()
        # less the counter JAX appends to a private function's name
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered)

    named = text()
    monkeypatch.setattr(gd, "checkpoint_name", lambda x, name: x)
    assert text() == named


# -- (e) the path that runs decides, not a key --------------------------------


def test_the_numpy_form_emits_no_name_and_the_kernels_emit_three(monkeypatch):
    """The ``jax.numpy`` form (what ``_kernel_heads`` = 0 or a CPU runs)
    keeps its scan body's own checkpoint and names nothing: block remat
    recomputes it whole, as before."""
    cfg, params, batch = _case(remat_block=True)
    assert not _names(_grad_jaxpr(cfg, params, batch)) & set(gd.SAVED_NAMES)
    # heads of 64 lanes: asked for the kernels, the op runs the numpy form
    narrow = dataclasses.replace(cfg, gdn_d_head=64)
    assert gd._kernel_heads(llama.GDN_CHUNK, HV, 64, 64) == 0
    monkeypatch.setattr(llama, "gated_delta_chunked", functools.partial(
        gd.gated_delta_chunked, backend="pallas", interpret=True))
    jaxpr = _grad_jaxpr(narrow, llama.init_params(
        jax.random.PRNGKey(1), narrow), batch)
    assert not _names(jaxpr) & set(gd.SAVED_NAMES)
    assert not _kernel_calls(jaxpr)
    assert set(gd.SAVED_NAMES) <= _names(_grad_jaxpr(cfg, params, batch))
