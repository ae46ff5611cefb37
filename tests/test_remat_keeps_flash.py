"""Block remat keeps the flash kernel's output and log-sum-exp.

``cfg.remat_block`` wraps each block application in a ``jax.checkpoint``
whose policy saves the two arrays ``ops/flash_attention.py`` names in its
forward rules, so the gradient program runs ``flash_fwd`` once per block
application and not again in front of the block's backward.  Here, on the
CPU, with the model's attention steered to the Pallas kernels in interpret
mode (the dispatcher would pick the jnp reference, which has no such
rule): the kernel is counted in the jaxpr, the saved residuals are listed,
no value changes, and the names do nothing where no policy asks for them.
"""

import collections
import dataclasses
import importlib
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

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.accelerate import REMAT_POLICIES

fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

B, S = 2, 128
F32 = jnp.float32


@pytest.fixture(autouse=True)
def _kernels(monkeypatch):
    monkeypatch.setattr(
        llama, "flash_attention",
        lambda q, k, v, backend=None, **kw: fa.flash_attention(
            q, k, v, backend="pallas", interpret=True, **kw))


def _dense(**over):
    base = dict(n_layer=2, n_head=4, n_kv_head=4, vocab_size=512, dtype=F32)
    base.update(over)
    return llama.LlamaConfig.tiny(**base)


def _mla(**over):
    """Latent attention (head size 32, not ``d_model / n_head`` = 16), a
    routed block under a share of its experts and a prediction block."""
    base = dict(
        vocab_size=512, n_layer=2, n_head=4, n_kv_head=4, d_model=64,
        d_ff=160, max_seq_len=S, dtype=F32, num_experts=8, top_k=2,
        moe_every=1, first_k_dense=1, d_ff_expert=32, n_shared_experts=1,
        router_score="sigmoid", routed_scaling=1.8, router_bias_rate=1e-3,
        balance_per_sequence=True, experts_held=4, mtp_layers=1,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32)
    base.update(over)
    return llama.LlamaConfig(**base)


SHAPES = {
    "dense": lambda: (_dense(), False),
    "gqa_window": lambda: (_dense(n_kv_head=2, sliding_window=48), False),
    "segmented": lambda: (_dense(), True),
    "looped": lambda: (_dense(loop_passes=3, branch_norm=True,
                              exit_gate_beta=0.1), False),
    "mla_mtp": lambda: (_mla(), False),
}


def _batch(segmented, seed=0):
    toks = np.random.RandomState(seed).randint(
        0, 512, (B, S + 1)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    if segmented:
        # three packed documents a row, the cuts off the kernels' tiles
        seg = np.zeros((B, S + 1), np.int32)
        seg[:, 37:] = 1
        seg[:, 90:] = 2
        batch["segment_ids"] = jnp.asarray(seg)
    return batch


def _case(shape, **over):
    cfg, segmented = SHAPES[shape]()
    cfg = dataclasses.replace(cfg, **over)
    return cfg, llama.init_params(jax.random.PRNGKey(1), cfg), _batch(
        segmented)


def _loss(cfg, batch):
    return lambda p: llama.loss_fn(p, batch, cfg)


def _kernel_calls(jaxpr):
    """Every ``pallas_call`` of a jaxpr and of the jaxprs inside it, counted
    by the kernel's name."""
    return collections.Counter(
        eqn.params["name"] for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call")


# -- (a) the kernel runs once per block application ---------------------------


@pytest.mark.parametrize("shape", ["dense", "looped", "mla_mtp", "segmented"])
def test_the_gradient_runs_flash_fwd_once_per_block_application(shape):
    cfg, params, batch = _case(shape, remat_block=True)
    calls = _kernel_calls(
        jax.make_jaxpr(jax.grad(_loss(cfg, batch)))(params).jaxpr)
    n = cfg.block_applications
    assert n == {"dense": 2, "looped": 6, "mla_mtp": 3, "segmented": 2}[shape]
    assert calls["flash_fwd"] == n, calls
    assert calls["flash_bwd_dq"] == calls["flash_bwd_dkv"] == n


def test_a_policy_without_the_names_runs_flash_fwd_twice(monkeypatch):
    """What the count above is held against: the same remat keeping
    nothing of the kernel recomputes it in front of every backward."""
    monkeypatch.setattr(llama, "FLASH_SAVED_NAMES", ())
    cfg, params, batch = _case("dense", remat_block=True)
    calls = _kernel_calls(
        jax.make_jaxpr(jax.grad(_loss(cfg, batch)))(params).jaxpr)
    assert calls["flash_fwd"] == 2 * cfg.block_applications


@pytest.mark.parametrize("remat", ["full", "dots", "offload"])
def test_whole_loss_policies_name_neither_array(remat):
    """``Strategy(remat=...)``'s own policies keep what they kept: asked
    about the kernel's two names they answer as about any other name."""
    policy = REMAT_POLICIES[remat]

    def recomputed(name):
        verdict = policy(name_p, name=name)
        return verdict is False or verdict is pe.Recompute

    assert all(map(recomputed, fa.SAVED_NAMES + ("moe_gate_up",)))
    if remat == "offload":
        # (e) still the inter-block stream alone, and to the host
        kept = policy(name_p, name="block_out")
        assert (kept.src, kept.dst) == ("device", "pinned_host")
    else:
        assert recomputed("block_out")


# -- (b) what one checkpointed application keeps ------------------------------


@pytest.mark.parametrize("shape", ["dense", "segmented", "mla_mtp"])
def test_one_application_keeps_its_inputs_and_the_two_named_arrays(shape):
    """Under the policy ``forward_hidden`` gives its checkpoint (the counts
    above hold it to that) a block application keeps nothing of its own
    but the two arrays the kernel's forward rule names."""
    cfg, params, batch = _case(shape, remat_block=True)
    seg = batch.get("segment_ids")
    seg = None if seg is None else seg[:, :-1]

    def one_application(layer, x, positions, seg):
        out, _ = jax.checkpoint(
            lambda layer, x, positions, seg: llama.block_apply(
                layer, x, cfg, positions, segment_ids=seg),
            policy=jax.checkpoint_policies.save_only_these_names(
                *fa.SAVED_NAMES))(layer, x, positions, seg)
        return jnp.sum(out)

    kept = saved_residuals(
        one_application, params["layers"][-1],
        jnp.zeros((B, S, cfg.d_model), cfg.dtype),
        jnp.broadcast_to(jnp.arange(S), (B, S)), seg)
    inside = [(aval, why) for aval, why in kept
              if "from the argument" not in why]
    dv = cfg.v_head_dim if cfg.kv_lora_rank else cfg.head_dim
    # ``out`` leaves the rule as the primal output too, and JAX passes such
    # a residual through a ``reduce_precision`` that changes nothing
    assert sorted((aval.shape, aval.dtype) for aval, _ in inside) == sorted([
        ((B, cfg.n_head, S, dv), cfg.dtype),
        ((B, cfg.n_head, S), jnp.float32)]), kept
    assert any(why.startswith("named 'flash_lse'") for _, why in inside)


# -- (c) no value changes -----------------------------------------------------


@pytest.mark.parametrize(
    "shape", ["dense", "gqa_window", "segmented", "looped", "mla_mtp"])
def test_remat_block_changes_no_value_under_the_kernels(shape):
    out = []
    for remat in (False, True):
        cfg, params, batch = _case(shape, remat_block=remat)
        out.append(jax.jit(jax.value_and_grad(_loss(cfg, batch)))(params))
    _tree_close(out[1], out[0], atol=1e-6)


# -- (d) the names are identities where no policy asks for them ---------------


@pytest.mark.parametrize("shape", ["dense", "segmented"])
def test_without_remat_the_lowered_step_is_the_same_with_and_without_names(
        shape, monkeypatch):
    cfg, params, batch = _case(shape, remat_block=False)

    def lowered():
        return jax.jit(jax.value_and_grad(_loss(cfg, batch))).lower(
            params).as_text()

    def text():
        # less the counter JAX appends to a private function's name
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered())

    named = text()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert text() == named
