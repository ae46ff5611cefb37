"""Adapter for the Granite-4.0-H block (HF model type ``granitemoehybrid``,
dense: ``num_local_experts`` 0): layers of two kinds by ``layer_types`` — a
Mamba-2 mixer (state-space duality, chunked) or grouped-query attention with
NO rotary position at the softmax scale ``attention_multiplier`` — each
followed by the same dense SwiGLU of ``shared_intermediate_size``; the four
Granite multipliers (embedding x 12, every branch x 0.22, logits / 8, scores
x 1/64); a head tied to the embedding: a configuration file in HF keys ->
the program's ``dlrover_tpu/models/llama.py``.

The adapter contract is in ``adapters/llama_dense.py``; this block makes no
discrete choice, so ``hidden_and_loss`` returns ``(hidden, loss)`` and the
comparison is the dense one: hidden states, loss and the gradient leaves at
the three standing tolerances of ``harness/model.py``.

The counts below know that ONE layer in ten runs the flash kernels:
``flash_roofline``'s reader multiplies ``flash_least_seconds`` by
``num_hidden_layers``, so it is scaled by ``attention layers / layers``
here, as ``adapters/glm4_moe_lite.py`` scales by 6/5.
"""

from __future__ import annotations

from benchmark.harness import flops

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "hidden_size", "shared_intermediate_size",
          "rms_norm_eps", "layer_types", "mamba_n_heads", "mamba_d_head",
          "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
          "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
          "attention_multiplier", "embedding_multiplier",
          "residual_multiplier", "logits_scaling", "tie_word_embeddings")
#: keys whose value must be the one the program computes
FIXED = {"hidden_act": ("silu",), "attention_bias": (False,),
         "model_type": ("granitemoehybrid",),
         "normalization_function": ("rmsnorm",),
         "position_embedding_type": ("nope",), "rope_scaling": (None,),
         "num_local_experts": (0,), "num_experts_per_tok": (0,),
         "tie_word_embeddings": (True,), "mamba_proj_bias": (False,)}
#: keys that change nothing a training step computes: without rotary
#: position ``rope_theta`` is read by nobody (the reference's planted
#: ``rope_on`` fault aside), and ``intermediate_size`` is the width of the
#: routed experts, of which a dense model has none
INERT = ("max_position_embeddings", "rope_theta", "intermediate_size")
#: what the program's ``LlamaConfig`` must be able to say
NEEDS = ("layer_types", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_n_groups", "mamba_d_conv", "mamba_expand",
         "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias", "rope",
         "attention_multiplier", "embedding_multiplier",
         "residual_multiplier", "logits_scaling", "tie_word_embeddings")

#: the peaks of ``ssd_least_seconds``' two floors are the device's own
#: (``harness/peaks.py``): 197 TFLOP/s and 819 GB/s on the v5e


def model_config(cfg: dict, *, remat_block: bool, seq_len: int):
    """The configuration file as the program's ``LlamaConfig``, no width
    changed on the way."""
    import dataclasses

    from benchmark.harness.common import CONFIG_META_KEYS
    from dlrover_tpu.models import llama

    # first of all: a program that cannot say these (the parent of the PR
    # that brought them) is refused by name, before anything is compiled
    missing = sorted(
        set(NEEDS) - {f.name for f in dataclasses.fields(llama.LlamaConfig)})
    if missing:
        raise ValueError(
            f"adapter granite_hybrid: this program's LlamaConfig has no "
            f"{missing}: it cannot compute the granitemoehybrid block")
    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter granite_hybrid does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"granite_hybrid computes {key} in {allowed}, "
                f"not {cfg[key]!r}")
    heads = cfg["num_attention_heads"]
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=cfg["hidden_size"],
        d_ff=cfg["shared_intermediate_size"],
        max_seq_len=seq_len,
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        layer_types=tuple(cfg["layer_types"]),
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        mamba_conv_bias=bool(cfg["mamba_conv_bias"]),
        mamba_proj_bias=bool(cfg["mamba_proj_bias"]),
        rope=False,
        attention_multiplier=float(cfg["attention_multiplier"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        tie_word_embeddings=True,
    )


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    """``llama.loss_fn`` returning the scan's counters beside the loss
    (``counters["step_metrics"]``: ``ssm_state_rms``, ``ssm_decay_min``);
    the function carries the counts of each kind of layer for the
    ``accelerate.program`` event (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, metrics=True)

    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, mean loss):
    ``llama.loss_fn``'s own path (the chunked scan, the flash kernels, bf16,
    the tied head behind 1 / ``logits_scaling`` into the fused loss, block
    remat where the cell has it) with the hidden states kept."""
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy_sum

    hidden, _ = llama.forward_hidden(params, tokens[:, :-1], mc)
    x, head = llama.head_operands(params, hidden, mc)
    loss = linear_softmax_cross_entropy_sum(
        x, head.astype(mc.dtype), tokens[:, 1:], None)
    return hidden.astype(jnp.float32), loss


#: the leaves whose gradients are compared: of the FIRST and the LAST
#: state-space layer every leaf of the mixer the scan's backward produces
#: (the two projections, the convolution, ``A_log``, ``dt_bias``, ``D``, the
#: gated norm's gain), of the attention layer q, k and v (the flash backward
#: kernels', without rotary and at the stated scale), and the embedding,
#: whose gradient is the sum of the lookup's and the tied head's
_SSM_LEAVES = ("in_proj", "out_proj", "conv_w", "A_log", "dt_bias", "D",
               "norm")
_ATTENTION_LEAVES = ("wq", "wk", "wv")


def _compared(params) -> dict:
    """``{layer index: (sub-dict or None, leaf names)}``."""
    ssm = [i for i, layer in enumerate(params["layers"]) if "ssm" in layer]
    picked = {i: ("ssm", _SSM_LEAVES) for i in {ssm[0], ssm[-1]}}
    picked.update({i: (None, _ATTENTION_LEAVES)
                   for i, layer in enumerate(params["layers"])
                   if "ssm" not in layer})
    return picked


def grad_leaves(params) -> dict:
    leaves = {"embed": params["embed"]}
    for i, (sub, names) in _compared(params).items():
        holder = params["layers"][i][sub] if sub else params["layers"][i]
        for name in names:
            leaves[f"layers.{i}.{name}"] = holder[name]
    return leaves


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = list(params["layers"])
    for i, (sub, names) in _compared(params).items():
        new = {name: leaves[f"layers.{i}.{name}"] for name in names}
        layers[i] = (dict(layers[i], **{sub: dict(layers[i][sub], **new)})
                     if sub else dict(layers[i], **new))
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part, and the layers of
    each kind."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, kv, hd = flops.heads(cfg)
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    ssm_layers = sum(kind == "mamba" for kind in cfg["layer_types"])
    return {
        "ssm_layers": ssm_layers,
        "attention_layers": cfg["num_hidden_layers"] - ssm_layers,
        "mlp": 3 * d * f,
        "ssm_proj": d * (inner + conv + cfg["mamba_n_heads"]) + inner * d,
        "attention_proj": d * h * hd + 2 * d * kv * hd + h * hd * d,
        "inner": inner, "conv": conv,
    }


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (``in_proj``, ``out_proj`` and the
    MLP of each state-space layer, the attention layer's four projections
    and MLP, the head's slice ONCE — the tied lookup is no matmul);
    attention over the causal pairs of the ONE attention layer at 32 heads
    of 64; and per state-space layer 3 x (the recurrence's update and read,
    ``4 H P N`` a token whatever the chunk, + the convolution's ``2 x taps
    x channels``).  The chunked form's further matmuls (``C B^T``, the
    masked product) are how THIS program computes the recurrence, not what
    the algorithm requires, and do not count."""
    c = _counts(cfg)
    params = (c["ssm_layers"] * c["ssm_proj"]
              + c["attention_layers"] * c["attention_proj"]
              + cfg["num_hidden_layers"] * c["mlp"]
              + cfg["hidden_size"] * cfg["vocab_size"])
    matmul = 6.0 * params
    h, _, hd = flops.heads(cfg)
    attn = (3.0 * 2 * 2 * h * hd * flops.attended_pairs(seq, 0)
            * c["attention_layers"] / seq)
    scan = 3.0 * c["ssm_layers"] * (
        4 * c["inner"] * cfg["mamba_d_state"]
        + 2 * cfg["mamba_d_conv"] * c["conv"])
    return {"matmul": matmul, "attention": attn, "scan": scan,
            "total": matmul + attn + scan}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ONE LAYER OF THE
    READER'S COUNT: ``flash_roofline`` multiplies by ``num_hidden_layers``
    (10), and a step runs the kernels in the attention layers alone (1), so
    one attention layer's least time (``harness/flops.py`` at 32/8 heads of
    64, no window) is scaled by 1 / 10."""
    c = _counts(cfg)
    one = flops.flash_least_seconds(cfg, batch, seq, peaks, shards=shards)
    scale = c["attention_layers"] / cfg["num_hidden_layers"]
    return dict(one, seconds=one["seconds"] * scale,
                flops=one["flops"] * scale, bytes=one["bytes"] * scale)


def ssd_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                      shards: int = 1) -> dict:
    """Least time one device could take for the scan of ONE state-space
    layer, forward and backward, at this batch: the larger of two floors.

    FLOPs: the recurrence itself.  Per token and head the state ``h [P, N]``
    is updated (``h = a h + dt x (x) B``: 2 P N) and read (``y = h C``: 2 P
    N), so ``4 H P N`` forward and, with the two transposed products of the
    backward, 3 x that.  Every exact algorithm does at least this much,
    whatever its chunk: the chunked form trades the per-position update for
    ``C B^T`` and the masked product inside a chunk and does MORE.  The
    decay, ``D x`` and the softplus are elementwise and left out.

    Bytes: forward ``x`` (H P), ``B`` and ``C`` (G N each) read in bf16 and
    ``dt`` (H) in float32, ``y`` (H P) written in bf16, once; backward those
    read again, ``dy`` read, and the four gradients (of ``x``, ``B``, ``C``,
    ``dt``) written.  The state never leaves the chip's fast memory in the
    least-time algorithm, and the convolution and the gate are other
    scopes' (``ssm_conv``, ``ssm_gate``).  ``shards``: devices the batch is
    divided over."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    tokens = batch * seq / shards
    flop = 3.0 * 4 * heads * p * n * tokens
    read = 2.0 * heads * p + 2 * 2.0 * groups * n + 4.0 * heads
    forward = read + 2.0 * heads * p
    backward = read + 2.0 * heads * p + read
    nbytes = (forward + backward) * tokens
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}
