"""LoRA fine-tuning for the llama family.

Parity target: the reference's llama2 fine-tuning path trains with and
without LoRA (``atorch/examples/llama2`` — its headline FSDP numbers are
quoted "no LoRA" because LoRA is the default cheap mode).  TPU-first
shape: no module wrapping — LoRA is a PYTREE of (A, B) factors plus a
pure ``merge`` that computes ``W_eff = W + scale * (A @ B)`` for the
targeted projection leaves.  The merged tree feeds the UNCHANGED llama
loss/decode machinery, so every path (flash attention, remat,
pipeline, KV cache) works under LoRA for free; only the factors are
trainable (``optax.masked`` via :func:`trainable_mask`).

    lora = init_lora(rng, params, rank=8)
    loss = llama.loss_fn(merge(params, lora), batch, cfg)
    grads = jax.grad(lambda l: llama.loss_fn(merge(params, l), ...))(lora)
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

# Projection leaves LoRA can target (2-D [in, out] weights).
ATTN_TARGETS = ("wq", "wk", "wv", "wo")
MLP_TARGETS = ("w_gate", "w_up", "w_down")
DEFAULT_TARGETS = ATTN_TARGETS


def init_lora(
    rng: jax.Array,
    params: Dict,
    *,
    rank: int = 8,
    alpha: float = 16.0,
    targets: Sequence[str] = DEFAULT_TARGETS,
) -> Dict:
    """Per-layer (A, B) factors for every targeted projection.

    A ~ N(0, 1/rank) [in, r]; B = 0 [r, out] — the standard init: the
    merged model starts EXACTLY at the base model."""
    layers = []
    for layer in params["layers"]:
        cell: Dict[str, Any] = {}
        for name in targets:
            w = layer.get(name)
            if w is None and "mlp" in layer:
                w = layer["mlp"].get(name)
            if w is None or w.ndim != 2:
                continue
            rng, k = jax.random.split(rng)
            d_in, d_out = w.shape
            cell[name] = {
                "a": jax.random.normal(k, (d_in, rank), jnp.float32)
                / jnp.sqrt(rank),
                "b": jnp.zeros((rank, d_out), jnp.float32),
            }
        layers.append(cell)
    # scale rides the tree as an INEXACT scalar (jax.grad rejects int
    # leaves); trainable_mask excludes it from updates.
    return {
        "layers": layers,
        "scale": jnp.float32(alpha / rank),
    }


def merge(params: Dict, lora: Dict) -> Dict:
    """Base params + LoRA deltas -> a tree the llama fns consume as-is.

    Differentiable in ``lora`` (train with grads wrt the factors only);
    untouched leaves are passed through by reference, not copied."""
    if len(params["layers"]) != len(lora["layers"]):
        raise ValueError(
            f"LoRA tree has {len(lora['layers'])} layers, model has "
            f"{len(params['layers'])} (config drift?)"
        )
    scale = jax.lax.stop_gradient(lora["scale"])
    out = dict(params)
    new_layers = []
    for layer, cell in zip(params["layers"], lora["layers"]):
        nl = dict(layer)
        for name, ab in cell.items():
            delta = (ab["a"] @ ab["b"]) * scale
            if name in nl:
                nl[name] = nl[name] + delta.astype(nl[name].dtype)
            else:
                mlp = dict(nl["mlp"])
                mlp[name] = mlp[name] + delta.astype(mlp[name].dtype)
                nl["mlp"] = mlp
        new_layers.append(nl)
    out["layers"] = new_layers
    return out


def trainable_mask(lora: Dict) -> Dict:
    """optax.masked-compatible mask: True for the (A, B) factors, False
    for the scalar config leaves riding the tree."""
    return jax.tree_util.tree_map(
        lambda x: hasattr(x, "ndim") and x.ndim == 2, lora
    )


def num_lora_params(lora: Dict) -> int:
    return sum(
        int(x.size)
        for x in jax.tree_util.tree_leaves(lora)
        if hasattr(x, "ndim") and getattr(x, "ndim", 0) == 2
    )
