"""Adapter for the Qwen3-Next block (HF model type ``qwen3_next``): layers of
two kinds by ``full_attention_interval`` — three Gated DeltaNet layers
(``linear_num_key_heads`` key and ``linear_num_value_heads`` value heads of
``linear_key_head_dim``, a causal depthwise convolution of
``linear_conv_kernel_dim`` taps) to one grouped-query attention layer with an
output gate, ``1 + w`` RMSNorms on each head's q and k before RoPE and
``partial_rotary_factor`` of each head rotated — each followed by a float32
softmax router over ``published.num_experts`` experts, the top
``num_experts_per_tok`` renormalised, and a shared expert behind a sigmoid
gate of its own; every block norm and the final norm ``1 + w``; an untied
head: a configuration file in HF keys -> the program's
``dlrover_tpu/models/llama.py``.

THE SHARE.  The file's own ``num_experts`` is what THIS CHIP HOLDS (32,
experts 0-31 of a 16-way expert-parallel layer); the router's width (512) is
the source's, read from the file's ``published`` block.  The router scores,
chooses and normalises over all 512, the chip computes the pairs routed to
its 32, and what the absent experts would add is left out, program and
reference alike (``reference/qwen3_next_ref.py``).  Every count below that is
a share of a roofline or of a peak counts the HELD pairs
(``num_experts_per_tok * held / width`` = 0.625 a token under even routing),
never all the router's picks.

The adapter contract is in ``adapters/llama_dense.py`` and, for the routed
half (``extra``, the three limits below), in ``benchmark/run.py``.  The
experts the system took and the balance term come from the PROGRAM's own aux
dict (``llama.forward_hidden``).

The counts know that ONE layer in four runs the flash kernels, THREE the
delta rule and ALL FOUR are routed: ``flash_roofline``'s reader multiplies
by ``num_hidden_layers``, so the flash least time is scaled by 1/4 here, as
``adapters/granite_hybrid.py`` and ``adapters/lfm2_moe.py`` scale theirs.
"""

from __future__ import annotations

from benchmark.harness import flops

#: Share of tokens, per square root of the depth (4 layers: x 2), whose
#: chosen set of 10 experts of 512 may differ from the 10 largest of the
#: reference's own float32 softmax, in the worst routed block.  Rounding of
#: the bf16 stream entering the router flips the tokens whose 10th and 11th
#: probability nearly tie, and with ten picks among 512 probabilities that a
#: N(0, 0.02) router keeps within a factor of a few of 1/512, a third of the
#: tokens have such a tie somewhere: the share is large by nature here (LFM2's
#: four picks of 32 read 5-6 %) and grows with the depth, the worst block
#: the third or the fourth.  A mean over 8,192 tokens, so steady: on the v5e
#: at published width (my chip runs, PR 52; PERF.md section 4) the system
#: read 28.8 % to 30.8 % over the 17 seeds of PERF.md section 4 (runs of the
#: cell, judged after its two warm-up steps, and states at initialisation).
#: The nearest precision below the stated one, planted in the reference
#: (``harness/qwen3_next_probe.py``, two seeds each): fp8 e4m3 on the stream
#: entering every mixer 91.2 % to 91.6 %; the delta rule's cumulative sums
#: in bfloat16 64.7 % to 68.2 % (three seeds each) — none correct.  0.225 x sqrt(4) = 45 % is
#: 1.46x the most seen (twenty standard deviations over the mean) and 0.70
#: of the weaker stand-in's least.
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.225
#: The most, per square root of the depth, by which the reference's
#: probability of an expert the system took may lie under that of the
#: reference's 10th.  The chosen probabilities of a 512-wide softmax are
#: ~5e-3 and the stream is 2.5 % away after four layers.  A MAXIMUM over
#: 32,768 (token, block) pairs, so its tail is wide: the same seeds read
#: 9.0e-4 to 1.64e-3 (mean 1.1e-3, standard deviation 2e-4).  fp8 on the
#: stream reads 5.8e-3 and 4.8e-3, the cumulative sums in bfloat16 8.4e-3 and
#: 7.1e-3; the whole head rotated 2.2e-3 and 2.6e-3 (its gradient leaves find it at
#: 104 %), the output gate dropped 4.5e-3, one pick fewer 3.8e-3.  1.5e-3 x
#: sqrt(4) = 3.0e-3 is 1.83x the most seen and 0.62 of the stand-ins' least.
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 1.5e-3
#: Relative limit on the further scalar of the loss (``moe_aux``: 0.001 x
#: the four balance terms, each a mean over 8,192 x 10 picks and 512
#: probabilities).  Same seeds: at most 3.1e-6; the fp8 stand-in 3.1e-5.  A
#: weight or a count off by 10 % is 20x out; the decay dropped reads 5.0e-3,
#: ``1 + w`` read as ``w`` 6.4e-3.  It is no detector of precision: the
#: standing tolerances and the two limits above are.  5e-3 as OLMoE's, GLM's
#: and LFM2's.
SCALAR_REL_TOL = 5e-3

#: assumed, with its ground in the configuration file's ``assumed``
AUX_WEIGHT = 1e-3

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "hidden_size",
          "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "rope_theta", "rms_norm_eps",
          "partial_rotary_factor", "num_experts", "num_experts_per_tok",
          "norm_topk_prob", "full_attention_interval",
          "linear_conv_kernel_dim", "linear_key_head_dim",
          "linear_value_head_dim", "linear_num_key_heads",
          "linear_num_value_heads", "tie_word_embeddings")
#: keys whose value must be the one the program computes: every layer
#: routed, no dense-only layer, SwiGLU, no window, no rope scaling
FIXED = {"model_type": ("qwen3_next",), "decoder_sparse_step": (1,),
         "mlp_only_layers": ([],), "hidden_act": ("silu",),
         "use_sliding_window": (False,), "rope_scaling": (None,),
         "tie_word_embeddings": (False,)}
#: keys that change nothing a training step computes
INERT = ("max_position_embeddings",)
#: what the program's ``LlamaConfig`` must be able to say
NEEDS = ("gdn_k_heads", "gdn_v_heads", "gdn_d_head", "gdn_d_conv",
         "attn_head_dim", "attn_output_gate", "partial_rotary_factor",
         "norm_plus_one", "shared_expert_gate", "experts_held")


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count (``published``),
    where the file's own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_types(cfg: dict) -> tuple:
    """``LlamaConfig.layer_types``: layer i (from 0) is an attention layer
    where ``(i + 1) % full_attention_interval == 0``."""
    every = cfg["full_attention_interval"]
    return tuple("attention" if (i + 1) % every == 0 else "linear_attention"
                 for i in range(cfg["num_hidden_layers"]))


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
            f"adapter qwen3_next: this program's LlamaConfig has no "
            f"{missing}: it cannot compute the qwen3_next block")
    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter qwen3_next does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"qwen3_next computes {key} in {allowed}, not {cfg[key]!r}")
    if cfg["linear_value_head_dim"] != cfg["linear_key_head_dim"] or (
            cfg["shared_expert_intermediate_size"]
            % cfg["moe_intermediate_size"]):
        raise ValueError(
            "qwen3_next computes key and value heads of one size and a "
            "shared expert of a whole number of expert widths, not "
            f"{cfg['linear_key_head_dim']} / {cfg['linear_value_head_dim']} "
            f"and {cfg['shared_expert_intermediate_size']} / "
            f"{cfg['moe_intermediate_size']}")
    heads = cfg["num_attention_heads"]
    width, held = router_width(cfg), cfg["num_experts"]
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=cfg["hidden_size"],
        # no layer is dense (``mlp_only_layers`` empty): the dense width
        # is read by nothing
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        layer_types=layer_types(cfg),
        gdn_k_heads=cfg["linear_num_key_heads"],
        gdn_v_heads=cfg["linear_num_value_heads"],
        gdn_d_head=cfg["linear_key_head_dim"],
        gdn_d_conv=cfg["linear_conv_kernel_dim"],
        attn_head_dim=cfg["head_dim"],
        attn_output_gate=True,
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        norm_plus_one=True,
        qk_norm=True,
        qk_norm_per_head=True,
        num_experts=width,
        top_k=cfg["num_experts_per_tok"],
        moe_every=1,
        d_ff_expert=cfg["moe_intermediate_size"],
        n_shared_experts=(cfg["shared_expert_intermediate_size"]
                          // cfg["moe_intermediate_size"]),
        shared_expert_gate=True,
        capacity_factor=None,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        balance_all_k=True,
        # the chip's share: experts 0 .. held - 1 (0 = all of them)
        experts_held=held if held < width else 0,
        experts_held_first=0,
    )


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    """``llama.loss_fn`` with the balance term at its assumed weight and no
    z term, returning the routed blocks' and the delta rule's counters
    beside the loss (``counters["step_metrics"]``); the function carries the
    counts of each kind of layer for the ``accelerate.program`` event
    (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, moe_aux_weight=AUX_WEIGHT,
                             moe_z_weight=0.0, metrics=True)

    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, loss, extra):
    ``llama.loss_fn``'s own path (the chunked delta rule, the flash kernels,
    the sorted ragged experts, bf16, the fused loss, block remat where the
    cell has it) with the hidden states kept, and from the program's aux
    dict the experts each routed block's router took and the balance
    term."""
    import jax.numpy as jnp

    from benchmark.reference.qwen3_next_ref import experts_name
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy_sum

    hidden, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    x, head = llama.head_operands(params, hidden, mc)
    nll = linear_softmax_cross_entropy_sum(
        x, head.astype(mc.dtype), tokens[:, 1:], None)
    scalars = {"moe_aux": AUX_WEIGHT * aux["moe_aux"]}
    extra = {
        "choices": {experts_name(i): experts
                    for i, experts in aux["moe_experts"].items()},
        "scalars": scalars,
    }
    return hidden.astype(jnp.float32), nll + scalars["moe_aux"], extra


#: the leaves whose gradients are compared: of the FIRST and the LAST
#: delta-rule layer every leaf of the mixer (both projections, the taps,
#: ``A_log``, ``dt_bias``, the gated norm's gain, ``out_proj``); of the
#: attention layer q (with its gate half), k, v and the two head gains (what
#: the flash backward kernels produce, through the per-head norm, the
#: partial rotary pass and the output gate); of the FIRST routed block its
#: router (its gradient passes through the chosen experts' weights and the
#: balance term), the held experts, the shared expert's three leaves and
#: its gate; and the embedding.
_GDN_LEAVES = ("in_proj_qkvz", "in_proj_ba", "conv_w", "A_log", "dt_bias",
               "norm", "out_proj")
_ATTENTION_LEAVES = ("wq", "wk", "wv", "q_norm", "k_norm")
_MOE_LEAVES = ("router", "wg", "wi", "wo", "shared_gate")
_SHARED_LEAVES = ("w_gate", "w_up", "w_down")


def _compared(params) -> list:
    """``[(layer index, path of sub-dicts, leaf names)]``."""
    layers = params["layers"]
    gdn = [i for i, layer in enumerate(layers) if "gdn" in layer]
    picked = [(i, ("gdn",), _GDN_LEAVES)
              for i in sorted({gdn[0], gdn[-1]})]
    picked += [(i, (), _ATTENTION_LEAVES)
               for i, layer in enumerate(layers) if "wq" in layer]
    routed = next(i for i, layer in enumerate(layers) if "moe" in layer)
    picked.append((routed, ("moe",), _MOE_LEAVES))
    picked.append((routed, ("moe", "shared"), _SHARED_LEAVES))
    return picked


def _prefix(i: int, path: tuple) -> str:
    return ".".join(("layers", str(i)) + path) + "."


def grad_leaves(params) -> dict:
    leaves = {"embed": params["embed"]}
    for i, path, names in _compared(params):
        holder = params["layers"][i]
        for key in path:
            holder = holder[key]
        for name in names:
            leaves[_prefix(i, path) + name] = holder[name]
    return leaves


def _replaced(holder: dict, path: tuple, new: dict) -> dict:
    if not path:
        return dict(holder, **new)
    return dict(holder, **{
        path[0]: _replaced(holder[path[0]], path[1:], new)})


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = list(params["layers"])
    for i, path, names in _compared(params):
        new = {name: leaves[_prefix(i, path) + name] for name in names}
        layers[i] = _replaced(layers[i], path, new)
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------

#: positions a chunk of the rule holds (``ops/gated_delta.py``): the count
#: of the rule's matmuls depends on it
CHUNK = 64


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part, and the layers of
    each kind."""
    d = cfg["hidden_size"]
    h, kv, hd = flops.heads(cfg)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    gd = cfg["linear_key_head_dim"]
    kinds = layer_types(cfg)
    attention = sum(kind == "attention" for kind in kinds)
    return {
        "attention_layers": attention,
        "gdn_layers": len(kinds) - attention,
        "routed_blocks": len(kinds),
        # in_proj_qkvz, in_proj_ba, out_proj
        "gdn_proj": d * 2 * (hk + hv) * gd + d * 2 * hv + hv * gd * d,
        # q with its gate half, k, v, o
        "attention_proj": 2 * d * h * hd + 2 * d * kv * hd + h * hd * d,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["shared_expert_intermediate_size"] + d,
        # of a token's picks, those that meet an expert held HERE, under
        # even routing
        "held_picks": cfg["num_experts_per_tok"] * cfg["num_experts"]
        / router_width(cfg),
    }


def _rule_flops_per_token(cfg: dict) -> float:
    """The chunked rule's matmuls, forward, per token and layer: per value
    head ``k k^T`` and ``q k^T`` (2 Q D each), ``T`` against ``beta k
    exp(gamma)`` and ``beta v`` (2 Q D each), the masked ``q k^T`` against
    ``u`` (2 Q D): ``10 Q D``; and the three products against the state
    (``W S``, ``q S``, ``k^T u``: 2 D^2 each): ``6 D^2``.  The inverse
    itself (``2/3 Q^2`` a row at most) and the elementwise work are left
    out."""
    d = cfg["linear_key_head_dim"]
    return cfg["linear_num_value_heads"] * (10.0 * CHUNK * d + 6.0 * d * d)


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (the three projections of each
    delta-rule layer, the attention layer's four with the gate half of
    ``wq``, per routed block the router, the shared expert with its gate
    and the HELD share of the token's picks — 10 x 32/512 = 0.625 experts
    —, the head's slice; the lookup is no matmul); attention over the
    causal pairs of the ONE attention layer at 16 heads of 256; and per
    delta-rule layer 3 x the chunked rule's matmuls and the taps' ``2 x
    taps x channels``."""
    c = _counts(cfg)
    d = cfg["hidden_size"]
    routed = (d * router_width(cfg) + c["shared"]
              + c["held_picks"] * c["expert"])
    params = (c["gdn_layers"] * c["gdn_proj"]
              + c["attention_layers"] * c["attention_proj"]
              + c["routed_blocks"] * routed
              + d * cfg["vocab_size"])
    matmul = 6.0 * params
    h, _, hd = flops.heads(cfg)
    attn = (3.0 * 2 * 2 * h * hd * flops.attended_pairs(seq, 0)
            * c["attention_layers"] / seq)
    channels = (2 * cfg["linear_num_key_heads"]
                + cfg["linear_num_value_heads"]) * cfg["linear_key_head_dim"]
    rule = 3.0 * c["gdn_layers"] * (
        _rule_flops_per_token(cfg)
        + 2 * cfg["linear_conv_kernel_dim"] * channels)
    return {"matmul": matmul, "attention": attn, "gdn": rule,
            "total": matmul + attn + rule}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ONE LAYER OF THE
    READER'S COUNT: ``flash_roofline`` multiplies by ``num_hidden_layers``
    (4), and a step runs the kernels in the attention layer alone (1), so
    one attention layer's least time (``harness/flops.py`` at 16/2 heads of
    256, no window) is scaled by 1 / 4."""
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
    bytes of rows and 24 x held experts x d x f of weights).  Every layer
    is routed, so the reader's ``num_hidden_layers`` is the count of the
    blocks.  The rows are those of EVEN routing (0.625 held picks a token):
    what the routers really send here is ``moe.held_pair_share_pct``'s to
    say."""
    c = _counts(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * c["held_picks"] / shards
    flop = 18.0 * rows * d * f
    nbytes = (18.0 * rows * (d + f)
              + 24.0 * cfg["num_experts"] * d * f / shards)
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}


def gdn_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                      shards: int = 1) -> dict:
    """Least time one device could take for the gated delta rule of ONE
    delta-rule layer, forward and backward, at this batch: the larger of
    two floors, counted for the rule whatever implements it.

    FLOPs: the chunked rule's matmuls (:func:`_rule_flops_per_token`),
    forward, and with the backward's transposed products 3 x that.  No
    recomputation is counted (block remat runs the forward twice).

    Bytes: forward ``q`` and ``k`` (the key heads' ``D`` dims each) and
    ``v`` (the value heads') read in bf16, ``g`` and ``beta`` (a float32
    each a value head) read, ``o`` (the value heads' ``D``) written in
    bf16, once; backward those read again, ``do`` read, and the five
    gradients written.  The ``[Q, Q]`` arrays and the state never leave the
    chip's fast memory in the least-time algorithm; the convolution and the
    gated norm are other scopes' (``gdn_conv``, ``gdn_gate``).  ``shards``:
    devices the batch is divided over."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d = cfg["linear_key_head_dim"]
    tokens = batch * seq / shards
    flop = 3.0 * _rule_flops_per_token(cfg) * tokens
    read = 2.0 * (2 * hk + hv) * d + 2 * 4.0 * hv
    out = 2.0 * hv * d
    nbytes = ((read + out) + (read + out + read)) * tokens
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}
