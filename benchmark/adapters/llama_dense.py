"""Adapter for dense Llama-family blocks (pre-norm RMSNorm, RoPE, GQA,
SwiGLU, untied head, optional sliding window): a configuration file in HF
keys -> the program's ``dlrover_tpu/models/llama.py``.

A configuration file names its adapter (``"adapter": "llama_dense"``) as a
traffic file names its runner; another architecture brings another file
here and edits none.  An adapter gives the harness:

- ``model_config(cfg, remat_block=, seq_len=)``: the program's own config
  object, or ``ValueError`` for a key it does not know — never a silent drop;
- ``init_fn(mc)``, ``loss_fn(mc)``: what ``accelerate()`` is given;
- ``hidden_and_loss(params, tokens, mc)``: the system's forward as the step
  runs it, for the comparison with the plain reference: ``(hidden, loss)``
  for a block like this one; ``(hidden, loss, extra)`` for one that makes
  discrete choices (a routed block), with the three limits that judge them
  as constants of that adapter (the contract: ``benchmark/run.py``);
- ``grad_leaves(params)`` / ``with_leaves``: the few parameter leaves whose
  gradients the comparison reads (a routed adapter adds its router and
  expert weights);
- ``model_flops_per_token`` and ``flash_least_seconds``: the yardstick's
  count of what this architecture's algorithm needs.
"""

from __future__ import annotations

from benchmark.harness import flops

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "hidden_size", "intermediate_size",
          "head_dim", "rope_theta", "rms_norm_eps", "sliding_window")
#: keys whose value must be the one the program computes
FIXED = {"hidden_act": ("silu",), "tie_word_embeddings": (False,),
         "attention_bias": (False,), "mlp_bias": (False,),
         "attention_dropout": (0, 0.0), "rope_scaling": (None,)}
#: keys that change nothing a training step computes
INERT = ("architectures", "model_type", "torch_dtype", "use_cache",
         "max_position_embeddings", "initializer_range", "bos_token_id",
         "eos_token_id", "pad_token_id", "transformers_version")

model_flops_per_token = flops.model_flops_per_token
flash_least_seconds = flops.flash_least_seconds


def model_config(cfg: dict, *, remat_block: bool, seq_len: int):
    """The configuration file as the program's ``LlamaConfig``, no width
    changed on the way.  Every key of the file that is not the benchmark's
    own metadata must be known here."""
    from benchmark.harness.common import CONFIG_META_KEYS
    from dlrover_tpu.models import llama

    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter llama_dense does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"llama_dense computes {key} in {allowed}, not {cfg[key]!r}")
    heads, hidden = cfg["num_attention_heads"], cfg["hidden_size"]
    if cfg.get("head_dim", hidden // heads) * heads != hidden:
        raise ValueError("LlamaConfig derives head_dim = hidden / heads")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=hidden,
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        sliding_window=int(cfg.get("sliding_window") or 0),
        remat_block=remat_block,
    )


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    from dlrover_tpu.models import llama

    return lambda params, batch: llama.loss_fn(params, batch, mc)


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, mean loss):
    ``llama.loss_fn``'s own path (kernels, bf16, the fused lm-head loss,
    block remat where the cell has it) with the hidden states kept."""
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy

    hidden, _ = llama.forward_hidden(params, tokens[:, :-1], mc)
    per_tok = linear_softmax_cross_entropy(
        hidden, params["lm_head"].astype(mc.dtype), tokens[:, 1:])
    return hidden.astype(jnp.float32), jnp.mean(per_tok)


def grad_leaves(params) -> dict:
    """The leaves whose gradients are compared: q, k and v projections of
    every layer (what the flash backward kernels produce: ``dq``; ``dk``
    and ``dv`` summed over each GQA group) and the embedding table (the
    gradient that has passed through every block's backward)."""
    leaves = {"embed": params["embed"]}
    for i, layer in enumerate(params["layers"]):
        for name in ("wq", "wk", "wv"):
            leaves[f"layers.{i}.{name}"] = layer[name]
    return leaves


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = [
        dict(layer, **{name: leaves[f"layers.{i}.{name}"]
                       for name in ("wq", "wk", "wv")})
        for i, layer in enumerate(params["layers"])]
    return dict(params, embed=leaves["embed"], layers=layers)
