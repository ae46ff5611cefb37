"""Adapter for the Kimi Linear block (HF model type ``kimi_linear``,
arXiv:2510.26692): layers of two kinds by the two lists of
``linear_attn_config`` (counted from 1) — Kimi Delta Attention layers
(``num_heads`` heads of ``head_dim``, three causal depthwise convolutions of
``short_conv_kernel_size`` taps, a delta rule whose decay is a number a key
CHANNEL, a low-rank sigmoid output gate) and latent-attention layers whose
queries come from ONE matrix (``q_lora_rank`` null), whose keys and queries
are ``qk_nope_head_dim + qk_rope_head_dim`` wide over values of
``v_head_dim``, and which carry no rotary position (``mla_use_nope``) —
layer 1's MLP dense, every later layer a float32 sigmoid router over
``published.num_experts`` experts with a selection bias, the top
``num_experts_per_token`` renormalised and scaled, beside one shared expert;
an untied head: a configuration file in HF keys -> the program's
``dlrover_tpu/models/llama.py``.

THE SHARE.  The file's own ``num_experts`` is what THIS CHIP HOLDS (8,
experts 0-7 of a 32-way expert-parallel layer); the router's width (256) is
the source's, read from the file's ``published`` block.  The router scores,
chooses and normalises over all 256, the chip computes the pairs routed to
its 8, and what the absent experts would add is left out, program and
reference alike (``reference/kimi_linear_ref.py``).  Every count below that
is a share of a roofline or of a peak counts the HELD pairs
(``num_experts_per_token * held / width`` = 0.25 a token under even
routing), never all the router's picks.

The adapter contract is in ``adapters/llama_dense.py`` and, for the routed
half (``extra``, the three limits below), in ``benchmark/run.py``.  The
experts the system took and the balance term come from the PROGRAM's own aux
dict (``llama.forward_hidden``).

The counts know that ONE layer in five runs the flash kernels, FOUR the
delta rule and FOUR are routed: ``flash_roofline``'s reader multiplies by
``num_hidden_layers``, so the flash least time is scaled by 1/5 here and the
grouped matmuls' by 4/5, as ``adapters/qwen3_next.py`` and
``adapters/glm4_moe_lite.py`` scale theirs.
"""

from __future__ import annotations

from benchmark.harness import flops

#: Share of tokens, per square root of the depth (5 layers: x 2.24), whose
#: chosen set of 8 experts of 256 may differ from the 8 largest of the
#: reference's own float32 ``sigmoid + bias``, in the worst routed block.
#: Rounding of the bf16 stream entering the router flips the tokens whose
#: 8th and 9th score nearly tie.  Readings on the v5e at published width
#: under :func:`init_fn`'s initialisation, 16,384 tokens a seed (my chip
#: runs, PR 61; PERF.md section 6): the system 5.86 % to 6.74 % over twelve
#: seeds (nine runs of the cell, judged after its two warm-up steps, and
#: ``harness/kimi_linear_probe.py`` at initialisation); the nearest precision
#: below the stated bf16, planted in the reference — fp8 e4m3 on the stream
#: entering every mixer 14.20 % and 14.82 %, on that entering every router
#: 25.14 % and 25.24 % — not correct.  0.045 x sqrt(5) = 10.06 % is 1.49x
#: the most seen and 0.71 of the weaker stand-in's least.  (At the program's
#: own N(0, 0.02) throughout the system read 16.9 % to 17.7 % over eight
#: seeds and the same stand-in 65 %.)
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.045
#: The most, per square root of the depth, by which the reference's ``s +
#: b`` of an expert the system took may lie under that of the reference's
#: 8th.  A MAXIMUM over 65,536 (token, block) pairs, so one token sets it:
#: the system read 3.36e-3 to 3.78e-3 on ten of those seeds, 4.21e-3 and
#: 4.96e-3 on two; fp8 on the routers' stream 1.70e-2 and 1.79e-2, which
#: this limit is for; fp8 on the mixers' stream 8.31e-3 and 8.67e-3, which
#: the share above finds (a stand-in has to fail one limit, not each).
#: 4e-3 x sqrt(5) = 8.94e-3 is 1.80x the most seen and 0.53 of the
#: routers' stand-in's least.  (No limit under the mixers' stand-in leaves
#: room: 5.59e-3, midway between the ten and it, is within 0.75 and 0.89 of
#: the two, and a run that reads false refuses whatever PR is being checked.)
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 4e-3
#: Relative limit on the further scalars: ``moe_seq_aux`` (1e-4 x the four
#: sequence-wise balance sums, each over 16,384 x 8 picks and 256 scores; at
#: most 1.1e-5 in every run, and a weight or a count off by 10 % is hundreds
#: of times out) and, what sets it, ``kda_rule_out_rms.<head>``
#: (``reference/kimi_linear_ref.py::rule_alone``: the RMS over the sequence
#: of each head's output of the RULE ALONE, the program's op against the
#: reference's recurrence on the same operands — the first layer's, 1 x
#: 16,384 x 32 x 128).  It is what holds the parts of the rule that stay in
#: float32: the model's own distances cannot (PERF.md section 6).  Readings,
#: the largest over the 32 heads, on the v5e with the kernel pair (my chip
#: runs, PR 61): the system 7.1e-5 to 9.7e-5 over five seeds; the nearest
#: precision below the stated float32, planted in the reference — the
#: decay's running sum in bfloat16 4.87e-4 and 5.47e-4, the state in
#: bfloat16 1.17e-3 — not correct.
#: On the CPU at that shape, where the op runs its ``jax.numpy`` form (my
#: runs, PR 61, six seeds): 2.3e-5 to 4.7e-5; 4.5e-4 to 5.4e-4; 3.7e-4 to
#: 1.9e-3.  2e-4 is 2.07x the most the chip read, 0.41 of the weaker
#: stand-in's least there and 0.53 of the least anywhere.
SCALAR_REL_TOL = 2e-4

#: assumed, each with its ground in the configuration file's ``assumed``
SEQ_AUX_WEIGHT = 1e-4
ROUTER_BIAS_RATE = 1e-3

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "rope_theta", "rms_norm_eps",
          "num_experts", "num_shared_experts", "num_experts_per_token",
          "moe_renormalize", "routed_scaling_factor",
          "first_k_dense_replace", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "linear_attn_config")
#: keys whose value must be the one the program computes: one query matrix,
#: no position in the latent layers, a sigmoid router of one group, every
#: layer past the dense ones routed, no prediction block
FIXED = {"model_type": ("kimi_linear",), "hidden_act": ("silu",),
         "q_lora_rank": (None,), "mla_use_nope": (True,),
         "moe_router_activation_func": ("sigmoid",), "moe_layer_freq": (1,),
         "num_expert_group": (1,), "topk_group": (1,),
         "use_grouped_topk": (True,), "num_nextn_predict_layers": (0,),
         "rope_scaling": (None,), "tie_word_embeddings": (False,)}
#: keys that change nothing a training step computes: ``head_dim`` is
#: ``hidden_size / num_attention_heads`` (72) and no layer has a head of it
INERT = ("head_dim", "model_max_length")
#: what the program's ``LlamaConfig`` must be able to say
NEEDS = ("kda_heads", "kda_d_head", "kda_d_conv", "kv_lora_rank",
         "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rope", "first_k_dense", "d_ff_expert",
         "n_shared_experts", "router_score", "routed_scaling",
         "router_bias_rate", "balance_per_sequence", "experts_held")


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count (``published``),
    where the file's own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_types(cfg: dict) -> tuple:
    """``LlamaConfig.layer_types``: layer i (from 0) is a "kda" layer where
    ``i + 1`` is in ``linear_attn_config.kda_layers``, an "attention" layer
    where it is in ``full_attn_layers``; the two lists divide the layers
    between them."""
    lists = cfg["linear_attn_config"]
    kda, full = set(lists["kda_layers"]), set(lists["full_attn_layers"])
    every = set(range(1, cfg["num_hidden_layers"] + 1))
    if kda & full or kda | full != every:
        raise ValueError(
            f"kimi_linear: kda_layers {sorted(kda)} and full_attn_layers "
            f"{sorted(full)} do not divide layers 1..{len(every)} between "
            "them")
    return tuple("kda" if i in kda else "attention" for i in sorted(every))


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
            f"adapter kimi_linear: this program's LlamaConfig has no "
            f"{missing}: it cannot compute the kimi_linear block")
    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter kimi_linear does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"kimi_linear computes {key} in {allowed}, not {cfg[key]!r}")
    heads, lin = cfg["num_attention_heads"], cfg["linear_attn_config"]
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
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        layer_types=layer_types(cfg),
        kda_heads=lin["num_heads"],
        kda_d_head=lin["head_dim"],
        kda_d_conv=lin["short_conv_kernel_size"],
        # latent attention with one query matrix and no position
        q_lora_rank=0,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope=False,
        num_experts=width,
        top_k=cfg["num_experts_per_token"],
        moe_every=1,
        first_k_dense=cfg["first_k_dense_replace"],
        d_ff_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        capacity_factor=None,
        norm_topk_prob=bool(cfg["moe_renormalize"]),
        router_score="sigmoid",
        routed_scaling=float(cfg["routed_scaling_factor"]),
        router_bias_rate=ROUTER_BIAS_RATE,
        balance_per_sequence=True,
        # the chip's share: experts 0 .. held - 1 (0 = all of them)
        experts_held=held if held < width else 0,
        experts_held_first=0,
    )


#: the initialisation (assumed; the configuration file): the embedding's rows
#: N(0, 1) where the program draws N(0, 0.02), and every projection back onto
#: the stream (``out_proj``, ``wo``, the MLPs' and the experts' down
#: matrices) N(0, 0.02 / sqrt(2 layers))
EMBED_STD = 1.0
BASE_STD = 0.02


def init_fn(mc):
    """``llama.init_params`` with the embedding at N(0, 1) and the output
    projections scaled by ``(2 n_layer)^-1/2``.  At N(0, 0.02) throughout
    the stream is what the first branches add, much the same for every
    token, and a sequence's tokens crowd onto few experts: the pairs that
    land on the 8 held ones then swing with the seed between 2,300 and 6,200
    a block around the even 4,096, blocks over the sized buffer's 5,120 rows
    fall back to the buffer of every pick, and six seeds read 15,346 to
    17,403 tokens/s (my chip runs, PR 61; ``adapters/mellum.py`` met the
    same).  With the tokens' own rows dominating the stream the routers see
    tokens that differ."""
    from dlrover_tpu.models import llama

    def scaled(tree, names, factor):
        return {k: (scaled(v, names, factor) if isinstance(v, dict)
                    else v * factor if k in names else v)
                for k, v in tree.items()}

    def init(rng):
        params = llama.init_params(rng, mc)  # N(0, BASE_STD), gains 1
        out = (2 * mc.n_layer) ** -0.5
        layers = [scaled(layer, ("wo", "out_proj", "w_down"), out)
                  for layer in params["layers"]]
        return dict(params, layers=layers,
                    embed=params["embed"] * (EMBED_STD / BASE_STD))

    return init


def loss_fn(mc):
    """``llama.loss_fn`` with the balance term at its assumed weight and no
    z term, returning the routed blocks' and the delta rule's counters
    beside the loss (``counters["step_metrics"]``) and the selection biases'
    next values; the function names those leaves (``rule_leaves``) and
    carries the counts of each kind of layer for the ``accelerate.program``
    event (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, moe_aux_weight=SEQ_AUX_WEIGHT,
                             moe_z_weight=0.0, metrics=True)

    loss.rule_leaves = llama.rule_leaves(mc)
    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, loss, extra):
    ``llama.loss_fn``'s own path (the chunked per-channel rule, the flash
    kernels at unequal widths, the sorted ragged experts, bf16, the fused
    loss, block remat where the cell has it) with the hidden states kept,
    and from the program's aux dict the experts each routed block's router
    took and the balance term."""
    import jax.numpy as jnp

    from benchmark.reference.kimi_linear_ref import experts_name, rule_alone
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy_sum
    from dlrover_tpu.ops.gated_delta import gated_delta_chunked

    hidden, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    x, head = llama.head_operands(params, hidden, mc)
    nll = linear_softmax_cross_entropy_sum(
        x, head.astype(mc.dtype), tokens[:, 1:], None)
    scalars = {"moe_seq_aux": SEQ_AUX_WEIGHT * aux["moe_aux"]}
    # the rule as the mixers call it (the op, its chunk, q, k and v in the
    # compute dtype), alone on the operands the reference is handed too
    scalars.update(rule_alone(
        params, tokens[:, :-1], mc.kda_d_head, mc.rms_eps,
        lambda q, k, v, g, beta: gated_delta_chunked(
            q.astype(mc.dtype), k.astype(mc.dtype), v.astype(mc.dtype), g,
            beta, llama.KDA_CHUNK)[0]))
    extra = {
        "choices": {experts_name(i): experts
                    for i, experts in aux["moe_experts"].items()},
        "scalars": scalars,
    }
    return hidden.astype(jnp.float32), nll + scalars["moe_seq_aux"], extra


#: the leaves whose gradients are compared: of the FIRST and the LAST KDA
#: layer every leaf of the mixer (the three projections and their taps, the
#: decay gate's two matrices, ``A_log``, ``dt_bias``, ``w_beta``, the output
#: gate's two and its bias, the gated norm's gain, ``out_proj``); of the
#: latent layer the one query matrix, both kv projections, the latent's
#: gain and ``wo`` (what the flash backward kernels produce at unequal
#: widths); of the FIRST routed block its router over the HELD experts'
#: columns (its gradient passes through the chosen experts' weights and the
#: balance term; an absent expert's column sums only the rows that took a
#: held expert beside it, a quarter as many, and reads half as far again:
#: :func:`_held_columns`), the held experts and the shared expert; and the embedding.
_KDA_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a", "f_b",
               "dt_bias", "A_log", "w_beta", "g_a", "g_b", "g_bias", "norm",
               "out_proj")
_MLA_LEAVES = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")
_MOE_LEAVES = ("router", "wg", "wi", "wo")
_SHARED_LEAVES = ("w_gate", "w_up", "w_down")


def _compared(params) -> list:
    """``[(layer index, path of sub-dicts, leaf names)]``."""
    layers = params["layers"]
    kda = [i for i, layer in enumerate(layers) if "kda" in layer]
    picked = [(i, ("kda",), _KDA_LEAVES) for i in sorted({kda[0], kda[-1]})]
    picked += [(i, (), _MLA_LEAVES)
               for i, layer in enumerate(layers) if "wkv_a" in layer]
    routed = next(i for i, layer in enumerate(layers) if "moe" in layer)
    picked.append((routed, ("moe",), _MOE_LEAVES))
    picked.append((routed, ("moe", "shared"), _SHARED_LEAVES))
    return picked


def _prefix(i: int, path: tuple) -> str:
    return ".".join(("layers", str(i)) + path) + "."


def _held_columns(holder: dict, name: str):
    """The columns of a compared leaf that are compared: of a router the
    held experts' (experts 0 .. held - 1, as many as ``wg`` has), of any
    other leaf all (None)."""
    return slice(0, holder["wg"].shape[0]) if name == "router" else None


def grad_leaves(params) -> dict:
    leaves = {"embed": params["embed"]}
    for i, path, names in _compared(params):
        holder = params["layers"][i]
        for key in path:
            holder = holder[key]
        for name in names:
            columns = _held_columns(holder, name)
            leaves[_prefix(i, path) + name] = (
                holder[name] if columns is None else holder[name][:, columns])
    return leaves


def _replaced(holder: dict, path: tuple, new: dict) -> dict:
    if path:
        return dict(holder, **{
            path[0]: _replaced(holder[path[0]], path[1:], new)})
    for name, leaf in new.items():
        columns = _held_columns(holder, name)
        if columns is not None:
            new = dict(new, **{name: holder[name].at[:, columns].set(leaf)})
    return dict(holder, **new)


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = list(params["layers"])
    for i, path, names in _compared(params):
        new = {name: leaves[_prefix(i, path) + name] for name in names}
        layers[i] = _replaced(layers[i], path, new)
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------

#: positions a chunk of the per-channel rule holds (``ops/gated_delta.py``):
#: the count of the rule's matmuls depends on it
CHUNK = 128


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part, and the layers of
    each kind."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kinds = layer_types(cfg)
    dense = cfg["first_k_dense_replace"]
    return {
        "kda_layers": kinds.count("kda"),
        "attention_layers": kinds.count("attention"),
        "dense_layers": dense,
        "routed_blocks": len(kinds) - dense,
        # q, k, v, out_proj; the two low-rank gates; beta
        "kda_proj": (4 * d * kh * kd + 2 * (d * kd + kd * kh * kd) + d * kh),
        # the one query matrix, the kv latent down and up, wo
        "mla": (d * h * qk + d * (cfg["kv_lora_rank"]
                                  + cfg["qk_rope_head_dim"])
                + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                             + cfg["v_head_dim"])
                + h * cfg["v_head_dim"] * d),
        "expert": 3 * d * cfg["moe_intermediate_size"],
        # of a token's picks, those that meet an expert held HERE, under
        # even routing
        "held_picks": cfg["num_experts_per_token"] * cfg["num_experts"]
        / router_width(cfg),
    }


def _rule_flops_per_token(cfg: dict) -> float:
    """The chunked rule's matmuls, forward, per token and layer, counted for
    the rule whatever implements it: per head ``k k^T`` and ``q k^T`` under
    the decay (2 Q D each), ``T`` against ``beta k exp(Gamma)`` and ``beta
    v`` (2 Q D each), the masked ``q k^T`` against ``u`` (2 Q D): ``10 Q
    D``; and the three products against the state (2 D^2 each): ``6 D^2``.
    That the per-channel decay makes ``k k^T`` several bounded products (a
    level a halving), the inverse and the elementwise work are left out."""
    lin = cfg["linear_attn_config"]
    d = lin["head_dim"]
    return lin["num_heads"] * (10.0 * CHUNK * d + 6.0 * d * d)


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (each KDA layer's four projections,
    two low-rank gates and beta; the latent layer's four; the dense layer's
    MLP; per routed block the router, the shared expert and the HELD share
    of the token's picks — 8 x 8/256 = 0.25 experts —; the head's slice;
    the lookup is no matmul); attention over the causal pairs of the ONE
    latent layer, scores at 192 and values at 128 a head; and per KDA layer
    3 x the chunked rule's matmuls and the three convolutions' ``2 x taps x
    channels``."""
    c = _counts(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    routed = (d * router_width(cfg)
              + cfg["num_shared_experts"] * c["expert"]
              + c["held_picks"] * c["expert"])
    params = (c["kda_layers"] * c["kda_proj"]
              + c["attention_layers"] * c["mla"]
              + c["dense_layers"] * 3 * d * cfg["intermediate_size"]
              + c["routed_blocks"] * routed
              + d * cfg["vocab_size"])
    matmul = 6.0 * params
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = (3.0 * 2 * h * (qk + cfg["v_head_dim"])
            * flops.attended_pairs(seq, 0) * c["attention_layers"] / seq)
    channels = 3 * lin["num_heads"] * lin["head_dim"]
    rule = 3.0 * c["kda_layers"] * (
        _rule_flops_per_token(cfg)
        + 2 * lin["short_conv_kernel_size"] * channels)
    return {"matmul": matmul, "attention": attn, "kda": rule,
            "total": matmul + attn + rule}


def _least(flop: float, nbytes: float, peaks: dict) -> dict:
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ONE LAYER OF THE
    READER'S COUNT: ``flash_roofline`` multiplies by ``num_hidden_layers``
    (5), and a step runs the kernels in the latent layer alone (1), so that
    layer's least time is scaled by 1 / 5.

    At the PUBLISHED widths, 192 for q and k and 128 for v and o, whatever
    lanes the kernels pad to (padding shows as a lower share, not as more
    work done).  FLOPs per attended pair and head: forward ``s`` (2 x 192)
    and ``p v`` (2 x 128); backward ``s``, ``dq``, ``dk`` (2 x 192 each) and
    ``dp``, ``dv`` (2 x 128 each) — ``s`` and ``dp`` once, as
    ``harness/flops.py`` counts: 2 x (4 x 192 + 3 x 128).  Bytes, bf16: q,
    k, v, o once forward; q, k, v, o, do read and dq, dk, dv written
    backward; keys and values expanded per head as the kernels see them."""
    c = _counts(cfg)
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    scale = c["attention_layers"] / cfg["num_hidden_layers"] / shards
    flop = (2.0 * (4 * qk + 3 * v) * h * flops.attended_pairs(seq, 0)
            * batch * scale)
    nbytes = 2.0 * batch * seq * h * (6 * qk + 6 * v) * scale
    return _least(flop, nbytes, peaks)


def grouped_matmul_least_seconds(cfg: dict, batch: int, seq: int,
                                 peaks: dict, shards: int = 1) -> dict:
    """Least time for the three grouped matmuls of one routed block,
    forward and backward, over the HELD pairs (``adapters/olmoe.py`` has
    the count's derivation: 18 x rows x d x f FLOPs; 18 x rows x (d + f)
    bytes of rows and 24 x held experts x d x f of weights), per LAYER OF
    THE READER'S COUNT: ``moe.grouped_matmul_roofline`` multiplies by
    ``num_hidden_layers`` (5), and a step has ``routed_blocks`` (4) of
    them.  The rows are those of EVEN routing (0.25 held picks a token):
    what the routers really send here is ``moe.held_pair_share_pct``'s to
    say."""
    c = _counts(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * c["held_picks"] / shards
    scale = c["routed_blocks"] / cfg["num_hidden_layers"]
    return _least(
        18.0 * rows * d * f * scale,
        (18.0 * rows * (d + f)
         + 24.0 * cfg["num_experts"] * d * f / shards) * scale, peaks)


def kda_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                      shards: int = 1) -> dict:
    """Least time one device could take for the per-channel delta rule of
    ONE KDA layer, forward and backward, at this batch: the larger of two
    floors, counted for the rule whatever implements it.

    FLOPs: the chunked rule's matmuls (:func:`_rule_flops_per_token`),
    forward, and with the backward's transposed products 3 x that.  No
    recomputation is counted, so the share cannot pass 100 %.

    Bytes, a head and token: forward ``q``, ``k``, ``v`` read in bf16 (2 D
    each), the float32 decay ``g`` (4 D) and ``beta`` (4) read, ``o``
    written in bf16 (2 D); backward those read again, ``do`` read (2 D), the
    three bf16 gradients (2 D each), the decay's float32 cotangent (4 D) and
    ``beta``'s (4) written: ``34 D + 12``.  The ``[Q, Q]`` arrays and the
    state never leave the chip's fast memory in the least-time algorithm;
    the convolutions and the gated norm are other scopes' (``kda_conv``,
    ``kda_gate``).  ``shards``: devices the batch is divided over."""
    lin = cfg["linear_attn_config"]
    tokens = batch * seq / shards
    return _least(
        3.0 * _rule_flops_per_token(cfg) * tokens,
        lin["num_heads"] * (34.0 * lin["head_dim"] + 12.0) * tokens, peaks)
