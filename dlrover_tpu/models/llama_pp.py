"""Pipeline-parallel Llama: stage partitioner + pipelined loss/grads.

The analogue of the reference's pipeline model partitioner + PipelineStage
(``atorch/pipeline_parallel/pipe_module.py``, ``PipelineStage.py``): Llama
blocks are grouped into ``n_stages`` equal stages with a stacked leading
stage axis sharded on the mesh's 'pp' axis; the embedding runs as the
stage-0 entry (``pre_fn``) and final-norm + lm-head + loss as the last-stage
exit (``post_fn``).  Schedules: differentiable GPipe
(:func:`pipeline_loss_fn`) or true 1F1B with recompute backward
(:func:`pipeline_train_grads` -> ``parallel.pipeline.pipeline_value_and_grad``).

Stage homogeneity: each stage must contain the same *pattern* of blocks
(e.g. with ``moe_every=2`` use layers-per-stage divisible by 2) so stage
trees stack.  The MoE aux loss is not propagated through the pipeline
(weight it 0 for parity checks).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy
from dlrover_tpu.ops.rmsnorm import rmsnorm
from dlrover_tpu.parallel.pipeline import (
    deinterleave_stage_grads,
    interleave_stage_params,
    pipeline_apply,
    pipeline_value_and_grad,
    pipeline_value_and_grad_interleaved,
    stack_stage_params,
)


def split_layer_groups(params: Dict, n_groups: int) -> list:
    """Llama layers -> ``n_groups`` contiguous equal groups (each a list
    of block trees).  L must divide evenly and every group must share a
    block pattern (dense/moe) so the group trees stack."""
    layers = params["layers"]
    L = len(layers)
    if L % n_groups != 0:
        raise ValueError(f"n_layer={L} not divisible by {n_groups} groups")
    per = L // n_groups
    return [layers[g * per:(g + 1) * per] for g in range(n_groups)]


def head_tail_params(params: Dict) -> Tuple[Dict, Dict]:
    """(pre, post) halves of the non-block params: embedding enters at
    the first (virtual) stage, final-norm + lm-head leave at the last."""
    return (
        {"embed": params["embed"]},
        {"ln_f": params["ln_f"], "lm_head": params["lm_head"]},
    )


def split_stage_params(
    params: Dict, n_stages: int
) -> Tuple[Any, Dict, Dict]:
    """Llama params -> (stacked_blocks [n_stages, ...], pre, post)."""
    stacked = stack_stage_params(split_layer_groups(params, n_stages))
    pre, post = head_tail_params(params)
    return stacked, pre, post


def merge_stage_grads(
    d_blocks: Any, d_pre: Dict, d_post: Dict, n_stages: int
) -> Dict:
    """Inverse of :func:`split_stage_params` for gradient trees."""
    layers = []
    per = len(d_blocks)  # list of per-position block trees, stage-stacked
    for s in range(n_stages):
        for i in range(per):
            layers.append(
                jax.tree_util.tree_map(lambda g: g[s], d_blocks[i])
            )
    return {
        "embed": d_pre["embed"],
        "layers": layers,
        "ln_f": d_post["ln_f"],
        "lm_head": d_post["lm_head"],
    }


def _stage_fn(cfg: LlamaConfig):
    llama.refuse_training_path_only(
        cfg, "the pipeline split (models.llama_pp)")

    def fn(stage_blocks, x):
        B = x.shape[0]
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), (B, x.shape[1]))
        for layer in stage_blocks:  # list of block trees (leading axis gone)
            x, _aux = llama.block_apply(layer, x, cfg, pos)
        return x

    return fn


def _pre_fn(cfg: LlamaConfig):
    def fn(pre, tokens):
        return pre["embed"].astype(cfg.dtype)[tokens]

    return fn


def _post_fn(cfg: LlamaConfig):
    def fn(post, x, targets):
        x = rmsnorm(x, post["ln_f"], eps=cfg.rms_eps)
        logits = (x @ post["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
        return jnp.mean(softmax_cross_entropy(logits, targets))

    return fn


def pipeline_loss_fn(
    params: Dict,
    batch: Dict[str, jax.Array],
    cfg: LlamaConfig,
    mesh: Mesh,
    *,
    n_microbatches: int,
    pp_axis: str = "pp",
) -> jax.Array:
    """Differentiable GPipe loss: split -> pipeline_apply -> head loss.
    Use under ``jax.value_and_grad`` like ``llama.loss_fn``."""
    tokens, targets = llama.split_batch(batch)
    n_stages = mesh.shape[pp_axis]
    stacked, pre, post = split_stage_params(params, n_stages)
    x = _pre_fn(cfg)(pre, tokens)
    out = pipeline_apply(
        _stage_fn(cfg), stacked, x, mesh,
        n_microbatches=n_microbatches, pp_axis=pp_axis,
    )
    return _post_fn(cfg)(post, out, targets)


def pipeline_train_grads(
    params: Dict,
    batch: Dict[str, jax.Array],
    cfg: LlamaConfig,
    mesh: Mesh,
    *,
    n_microbatches: int,
    n_chunks: int = 1,
    pp_axis: str = "pp",
) -> Tuple[jax.Array, Dict]:
    """1F1B loss + grads in ``params``' tree structure (the drop-in
    replacement for ``jax.value_and_grad(llama.loss_fn)`` when pipelining).

    ``n_chunks > 1`` selects the interleaved schedule: each physical
    stage hosts ``n_chunks`` virtual stages (layer groups), shrinking the
    pipeline bubble by ~``n_chunks`` at the price of ``n_chunks``x the
    ring hops (reference ``StageInterleaver``)."""
    tokens, targets = llama.split_batch(batch)
    n_stages = mesh.shape[pp_axis]
    if n_chunks <= 1:
        stacked, pre, post = split_stage_params(params, n_stages)
        loss, (d_blocks, d_pre, d_post) = pipeline_value_and_grad(
            _stage_fn(cfg),
            _pre_fn(cfg),
            _post_fn(cfg),
            stacked, pre, post, tokens, targets, mesh,
            n_microbatches=n_microbatches, pp_axis=pp_axis,
        )
        grads = merge_stage_grads(d_blocks, d_pre, d_post, n_stages)
        return loss, grads

    # Interleaved: layers split into S*V virtual stages in layer order;
    # virtual j lives on physical j % S.
    SV = n_stages * n_chunks
    virt = split_layer_groups(params, SV)
    stacked = interleave_stage_params(virt, n_stages)
    pre, post = head_tail_params(params)
    loss, (d_blocks, d_pre, d_post) = pipeline_value_and_grad_interleaved(
        _stage_fn(cfg),
        _pre_fn(cfg),
        _post_fn(cfg),
        stacked, pre, post, tokens, targets, mesh,
        n_microbatches=n_microbatches, n_chunks=n_chunks,
        pp_axis=pp_axis,
    )
    virt_grads = deinterleave_stage_grads(d_blocks, n_stages, n_chunks)
    grad_layers = []
    for j in range(SV):
        # virt_grads[j] is the list of this virtual stage's block trees.
        grad_layers.extend(virt_grads[j])
    grads = {
        "embed": d_pre["embed"],
        "layers": grad_layers,
        "ln_f": d_post["ln_f"],
        "lm_head": d_post["lm_head"],
    }
    return loss, grads


def strategy_loss_builder(cfg: LlamaConfig, *, devices=None,
                          n_microbatches=None, **loss_kw):
    """``accelerate(loss_fn_builder=...)`` bridge: candidates rewrite
    the MODEL the way the reference's opt_lib transforms do.

    - ``remat == "block"`` -> ``cfg.remat_block=True`` (per-block
      checkpointing inside the model: the stream at block boundaries
      and the flash kernel's output and log-sum-exp are kept, the rest
      of a block is recomputed);
    - ``mesh.pp > 1`` -> the GPipe pipelined loss over the candidate's
      own mesh (so the BO search can genuinely score pipeline points
      instead of treating the pp axis as replication);
    - otherwise the plain :func:`llama.loss_fn`.
    """
    import dataclasses as _dc

    from dlrover_tpu.parallel.mesh import build_mesh

    def builder(strategy):
        c = (
            _dc.replace(cfg, remat_block=True)
            if strategy.remat == "block" else cfg
        )
        spec = strategy.mesh
        if spec.pp > 1:
            # The pipelined loss has no moe_aux/fused-lm-head knobs: a
            # pp candidate silently training a DIFFERENT objective than
            # its dp peers would corrupt the search — reject loudly
            # (the sweep logs it and moves on).  moe_aux_weight=0.0 is
            # equivalent (the pipeline head never adds aux).
            unsupported = {
                k: v for k, v in loss_kw.items()
                if not (k == "moe_aux_weight" and v == 0.0)
            }
            if unsupported:
                raise ValueError(
                    "strategy_loss_builder: pipeline path cannot honor "
                    f"loss kwargs {sorted(unsupported)}"
                )
            mesh = build_mesh(spec, devices)  # defaults + normalizes
            M = n_microbatches or max(2, spec.pp)

            def pp_loss(params, batch):
                return pipeline_loss_fn(
                    params, batch, c, mesh, n_microbatches=M
                )

            return pp_loss

        def loss(params, batch):
            return llama.loss_fn(params, batch, c, **loss_kw)

        return loss

    return builder
