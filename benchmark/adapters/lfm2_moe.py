"""Adapter for the LFM2-8B-A1B block (HF model type ``lfm2_moe``): layers of
two kinds by ``layer_types`` — a double-gated short convolution
(``conv_L_cache`` taps, no bias, no activation) or grouped-query attention
with an RMSNorm on each head's q and k before RoPE — each followed by a
dense SwiGLU (the first ``num_dense_layers`` layers) or by a float32 sigmoid
router over ``published.num_experts`` experts with a selection bias, the top
``num_experts_per_tok`` normalised over their sum + 1e-6, and NO shared
expert; a head tied to the embedding: a configuration file in HF keys -> the
program's ``dlrover_tpu/models/llama.py``.

THE SHARE.  The file's own ``num_experts`` is what THIS CHIP HOLDS (8,
experts 0-7 of a 4-way expert-parallel layer); the router's width (32) is
the source's, read from the file's ``published`` block.  The router scores,
chooses and normalises over all 32, the chip computes the pairs routed to
its 8, and what the absent experts would add is left out, program and
reference alike (``reference/lfm2_moe_ref.py``).  Every count below that is
a share of a roofline or of a peak counts the HELD pairs
(``num_experts_per_tok * held / width`` = 1 a token under even routing),
never all the router's picks.

The adapter contract is in ``adapters/llama_dense.py`` and, for the routed
half (``extra``, the three limits below), in ``benchmark/run.py``.  The
experts the system took come from the PROGRAM's own aux dict
(``llama.forward_hidden``); the loss has no further scalar
(``use_expert_bias`` is the auxiliary-loss-free scheme), so ``scalars`` is
empty.

The counts know that ONE layer in five runs the flash kernels and FOUR are
routed: ``flash_roofline``'s and ``moe.grouped_matmul_roofline``'s readers
multiply by ``num_hidden_layers``, so the two least times are scaled by 1/5
and 4/5 here, as ``adapters/glm4_moe_lite.py`` and
``adapters/granite_hybrid.py`` scale theirs.
"""

from __future__ import annotations

from benchmark.harness import flops

#: Share of tokens, per square root of the depth (5 layers: x 2.24), whose
#: chosen set of 4 experts of 32 may differ from the 4 largest of the
#: reference's own float32 ``sigmoid + bias``, in the worst routed block.
#: Rounding of the bf16 stream entering the router flips the tokens whose
#: 4th and 5th score nearly tie; the share grows with the depth (3.6-3.8 % in
#: the first routed block, 5.5-5.7 % in the fourth).  A mean over 8,192
#: tokens, so steady: on the v5e at published width (my chip runs, PR 46;
#: PERF.md section 4) the system read 5.10 % to 6.02 % over 36 seeds
#: (twenty-eight runs of the cell, judged after its two warm-up steps, and
#: eight states at initialisation), a standard deviation of 0.25 % as a
#: binomial count gives.  The nearest precision below the stated one, planted in the
#: reference (``harness/lfm2_probe.py``, two seeds each): fp8 e4m3 on what
#: enters the EXPERTS' matmuls alone 7.81 % and 8.07 %; on the stream
#: entering every routed block 10.95 % and 11.04 %; on the stream entering
#: every mixer and MLP 34.6 % and 35.7 % — none correct.  0.031 x sqrt(5) =
#: 6.93 % is 1.15x the most seen (5.5 standard deviations over the mean) and
#: 0.89 of the weakest stand-in's least.
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.031
#: The most, per square root of the depth, by which the reference's
#: ``s + b`` of an expert the system took may lie under that of the
#: reference's 4th.  A MAXIMUM over 32,768 (token, block) pairs, so its tail
#: is wide: the same 36 seeds read 1.04e-2 to 1.87e-2 (the stream is 1.6 %
#: away after five layers and a sigmoid's slope is 1/4; GLM's 1.0 % stream
#: read 5.3e-3 to 8.4e-3).  fp8 on the routed blocks' input alone reads
#: 2.84e-2 and 2.90e-2, 1.5x the system's largest: no limit between those
#: two has room for a maximum on both sides, and the share above is what
#: finds that stand-in.  fp8 on the stream entering every mixer and MLP
#: (Granite's stand-in) reads 1.05e-1 and 1.09e-1; one expert fewer
#: (``fault_probe``'s plant) 2.7e-1, ``norm_topk_prob`` flipped 4.7e-1.
#: 1.8e-2 x sqrt(5) = 4.02e-2 is 2.15x the most seen and 0.38 of that
#: stand-in's least.
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 1.8e-2
#: Relative limit on each further scalar of the loss.  This loss HAS none
#: (no balance term, no z term: ``scalars`` is empty and the harness reads
#: 0.0), so the limit judges nothing here; 5e-3 as OLMoE's and GLM's, for a
#: later term to be held to.
SCALAR_REL_TOL = 5e-3

#: assumed, each with its ground in the configuration file's ``assumed``
ROUTER_BIAS_RATE = 1e-3
ROUTER_NORM_EPS = 1e-6

#: HF's names of the two kinds of layer -> ``LlamaConfig.layer_types``'
KINDS = {"conv": "conv", "full_attention": "attention"}

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "rope_theta", "norm_eps", "num_experts",
          "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
          "num_dense_layers", "layer_types", "conv_L_cache")
#: keys whose value must be the one the program computes: a convolution
#: without bias, a router with the selection bias
FIXED = {"conv_bias": (False,), "model_type": ("lfm2_moe",),
         "use_expert_bias": (True,)}
#: keys that change nothing a training step computes
INERT = ("max_position_embeddings",)
#: what the program's ``LlamaConfig`` must be able to say
NEEDS = ("layer_types", "conv_taps", "qk_norm_per_head", "router_norm_eps",
         "first_k_dense", "d_ff_expert", "router_score", "router_bias_rate",
         "experts_held", "tie_word_embeddings")


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count (``published``),
    where the file's own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


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
            f"adapter lfm2_moe: this program's LlamaConfig has no "
            f"{missing}: it cannot compute the lfm2_moe block")
    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter lfm2_moe does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"lfm2_moe computes {key} in {allowed}, not {cfg[key]!r}")
    other = sorted(set(cfg["layer_types"]) - set(KINDS))
    if other:
        raise ValueError(
            f"lfm2_moe computes layer_types out of {sorted(KINDS)}, "
            f"not {other}")
    heads = cfg["num_attention_heads"]
    width, held = router_width(cfg), cfg["num_experts"]
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["norm_eps"]),
        remat_block=remat_block,
        layer_types=tuple(KINDS[kind] for kind in cfg["layer_types"]),
        conv_taps=cfg["conv_L_cache"],
        qk_norm=True,
        qk_norm_per_head=True,
        num_experts=width,
        top_k=cfg["num_experts_per_tok"],
        moe_every=1,
        first_k_dense=cfg["num_dense_layers"],
        d_ff_expert=cfg["moe_intermediate_size"],
        capacity_factor=None,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        router_score="sigmoid",
        routed_scaling=float(cfg["routed_scaling_factor"]),
        router_norm_eps=ROUTER_NORM_EPS,
        router_bias_rate=ROUTER_BIAS_RATE,
        # the chip's share: experts 0 .. held - 1 (0 = all of them)
        experts_held=held if held < width else 0,
        experts_held_first=0,
        tie_word_embeddings=True,
    )


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    """``llama.loss_fn`` with no balance and no z term, returning the routed
    blocks' counters beside the loss (``counters["step_metrics"]``) and the
    selection biases' next values; the function names those leaves
    (``rule_leaves``) for ``accelerate()``'s step builder and carries the
    counts of each kind of layer for the ``accelerate.program`` event
    (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, moe_aux_weight=0.0,
                             moe_z_weight=0.0, metrics=True)

    loss.rule_leaves = llama.rule_leaves(mc)
    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, mean loss,
    extra): ``llama.loss_fn``'s own path (the flash kernels, the sorted
    ragged experts, bf16, the tied head into the fused loss, block remat
    where the cell has it) with the hidden states kept, and from the
    program's aux dict the experts each routed block's router took."""
    import jax.numpy as jnp

    from benchmark.reference.lfm2_moe_ref import experts_name
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy_sum

    hidden, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    x, head = llama.head_operands(params, hidden, mc)
    loss = linear_softmax_cross_entropy_sum(
        x, head.astype(mc.dtype), tokens[:, 1:], None)
    extra = {
        "choices": {experts_name(i): experts
                    for i, experts in aux["moe_experts"].items()},
        "scalars": {},
    }
    return hidden.astype(jnp.float32), loss, extra


#: the leaves whose gradients are compared: of the FIRST and the LAST
#: convolution layer the mixer's three leaves (the first under a dense MLP,
#: the last under a routed one), of every attention layer q, k, v and the
#: two head gains (what the flash backward kernels produce, through the
#: per-head norm and the rotary pass), of the FIRST routed layer its router
#: (its gradient passes through the chosen experts' weights) and the held
#: experts, as GLM's adapter takes them, and the embedding, whose gradient
#: is the sum of the lookup's and the tied head's.
#:
#: Why one router and not four (my chip runs, PR 46; PERF.md section 4): a
#: router's gradient is the noisiest leaf there is — no balance term feeds
#: it, ONE comparison sequence gives an expert about 1,024 rows, and its
#: distance grows with the depth as the share of flipped picks does: 9.9 to
#: 11.4 % in the first routed block, 12.5-14.1, 14.1-15.2 and 13.4-16.6 % in
#: the next three (float32 on the same weights: 8e-7), against the standing
#: 17.9 %; the first block's over fourteen further seeds 9.7-12.3 %.
#: Fourteen of fourteen runs were correct with all four compared;
#: a leaf at 0.93 of a standing limit on a correct tree would still refuse
#: one sooner or later.  The later routers' forward is held by the two
#: limits above in every block.
_CONV_LEAVES = ("in_proj", "conv_w", "out_proj")
_ATTENTION_LEAVES = ("wq", "wk", "wv", "q_norm", "k_norm")
_MOE_LEAVES = ("router", "wg", "wi", "wo")


def _compared(params) -> list:
    """``[(layer index, sub-dict or None, leaf names)]``."""
    layers = params["layers"]
    conv = [i for i, layer in enumerate(layers) if "conv" in layer]
    picked = [(i, "conv", _CONV_LEAVES) for i in sorted({conv[0], conv[-1]})]
    picked += [(i, None, _ATTENTION_LEAVES)
               for i, layer in enumerate(layers) if "wq" in layer]
    routed = next(i for i, layer in enumerate(layers) if "moe" in layer)
    picked.append((routed, "moe", _MOE_LEAVES))
    return picked


def grad_leaves(params) -> dict:
    leaves = {"embed": params["embed"]}
    for i, sub, names in _compared(params):
        holder = params["layers"][i][sub] if sub else params["layers"][i]
        prefix = f"layers.{i}.{sub}." if sub else f"layers.{i}."
        for name in names:
            leaves[prefix + name] = holder[name]
    return leaves


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = list(params["layers"])
    for i, sub, names in _compared(params):
        prefix = f"layers.{i}.{sub}." if sub else f"layers.{i}."
        new = {name: leaves[prefix + name] for name in names}
        layers[i] = (dict(layers[i], **{sub: dict(layers[i][sub], **new)})
                     if sub else dict(layers[i], **new))
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part, and the layers of
    each kind."""
    d = cfg["hidden_size"]
    h, kv, hd = flops.heads(cfg)
    conv_layers = sum(kind == "conv" for kind in cfg["layer_types"])
    layers, dense_layers = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    return {
        "conv_layers": conv_layers,
        "attention_layers": layers - conv_layers,
        "dense_layers": dense_layers,
        "routed_blocks": layers - dense_layers,
        "conv_proj": d * 3 * d + d * d,
        "attention_proj": d * h * hd + 2 * d * kv * hd + h * hd * d,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        # of a token's picks, those that meet an expert held HERE, under
        # even routing
        "held_picks": cfg["num_experts_per_tok"] * cfg["num_experts"]
        / router_width(cfg),
    }


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (``in_proj`` and ``out_proj`` of
    each convolution layer, the attention layer's four projections, the
    dense layer's MLP, per routed block the router and the HELD share of
    the token's picks — 4 x 8/32 = 1 expert —, the head's slice ONCE: the
    tied lookup is no matmul); attention over the causal pairs of the ONE
    attention layer at 32 heads of 64; and per convolution layer 3 x the
    taps' ``2 x taps x channels``.  The two gates are elementwise and left
    out."""
    c = _counts(cfg)
    d = cfg["hidden_size"]
    routed = d * router_width(cfg) + c["held_picks"] * c["expert"]
    params = (c["conv_layers"] * c["conv_proj"]
              + c["attention_layers"] * c["attention_proj"]
              + c["dense_layers"] * 3 * d * cfg["intermediate_size"]
              + c["routed_blocks"] * routed
              + d * cfg["vocab_size"])
    matmul = 6.0 * params
    h, _, hd = flops.heads(cfg)
    attn = (3.0 * 2 * 2 * h * hd * flops.attended_pairs(seq, 0)
            * c["attention_layers"] / seq)
    taps = 3.0 * c["conv_layers"] * 2 * cfg["conv_L_cache"] * d
    return {"matmul": matmul, "attention": attn, "conv": taps,
            "total": matmul + attn + taps}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ONE LAYER OF THE
    READER'S COUNT: ``flash_roofline`` multiplies by ``num_hidden_layers``
    (5), and a step runs the kernels in the attention layers alone (1), so
    one attention layer's least time (``harness/flops.py`` at 32/8 heads of
    64, no window) is scaled by 1 / 5."""
    c = _counts(cfg)
    one = flops.flash_least_seconds(cfg, batch, seq, peaks, shards=shards)
    scale = c["attention_layers"] / cfg["num_hidden_layers"]
    return dict(one, seconds=one["seconds"] * scale,
                flops=one["flops"] * scale, bytes=one["bytes"] * scale)


def grouped_matmul_least_seconds(cfg: dict, batch: int, seq: int,
                                 peaks: dict, shards: int = 1) -> dict:
    """Least time for the three grouped matmuls of one routed block,
    forward and backward, over the HELD pairs (``adapters/olmoe.py`` has
    the count's derivation: 18 x rows x d x f FLOPs; 18 x rows x (d + f)
    bytes of rows and 24 x held experts x d x f of weights), per LAYER OF
    THE READER'S COUNT: ``moe.grouped_matmul_roofline`` multiplies by
    ``num_hidden_layers`` (5), and a step has ``routed_blocks`` of them
    (4), hence x 4 / 5.  The rows are those of EVEN routing (one held pick
    a token): what the routers really send here is
    ``moe.held_pair_share_pct``'s to say."""
    c = _counts(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * c["held_picks"] / shards
    scale = c["routed_blocks"] / cfg["num_hidden_layers"]
    flop = 18.0 * rows * d * f * scale
    nbytes = (18.0 * rows * (d + f)
              + 24.0 * cfg["num_experts"] * d * f / shards) * scale
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}
