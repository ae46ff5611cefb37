"""Adapter for the Mellum 2 block (HF model type ``mellum``: Qwen3-MoE's
block with ``layer_types`` and ``mlp_layer_types`` per layer and
``rope_parameters`` keyed by layer type): window (``sliding_attention``) and
full (``full_attention``) grouped-query attention layers in one stack, per-head
RMSNorms on q and k before the rotation, each kind on its own rotary table
(the plain one on the window layers, YaRN's blend with its attention factor
on the full ones), every layer followed by a float32 softmax router over
``published.num_experts`` experts, the top ``num_experts_per_tok``
renormalised, no shared expert; an untied head: a configuration file in HF
keys -> the program's ``dlrover_tpu/models/llama.py``.

THE SHARE.  The file's own ``num_experts`` is what THIS CHIP HOLDS (8,
experts 0-7 of an 8-way expert-parallel layer); the router's width (64) is
the source's, read from the file's ``published`` block.  The router scores,
chooses and normalises over all 64, the chip computes the pairs routed to its
8, and what the absent experts would add is left out, program and reference
alike (``reference/mellum_ref.py``).  Every count below that is a share of a
roofline or of a peak counts the HELD pairs (``num_experts_per_tok * held /
width`` = 1 a token under even routing), never all the router's picks.

The adapter contract is in ``adapters/llama_dense.py`` and, for the routed
half (``extra``, the three limits below), in ``benchmark/run.py``.  The
experts the system took and the balance term come from the PROGRAM's own aux
dict (``llama.forward_hidden``).

THE PAIRS.  Attention is charged for the (query, key) pairs each KIND of
layer attends — at S 16,384 a full layer 134,225,920 a sequence, a window
layer (1,024) 16,253,440 — never S^2 / 2 on a window layer:
``harness/flops.py`` reads one global ``sliding_window`` and would charge
every layer alike, so the three counts here are the adapter's own.
``flash_roofline``'s reader multiplies by ``num_hidden_layers``, so
:func:`flash_least_seconds` returns the MEAN layer's least time.
"""

from __future__ import annotations

from benchmark.harness import flops

#: Share of tokens, per square root of the depth (8 layers: x 2.83), whose
#: chosen set of 8 experts of 64 may differ from the 8 largest of the
#: reference's own float32 softmax, in the worst routed block.  Rounding of
#: the bf16 stream entering the router flips the tokens whose 8th and 9th
#: probability nearly tie (OLMoE's 8 of 64 reads 4.4-5.3 % at one layer); a
#: mean over 16,384 tokens, so steady.  On the v5e at published width and 1 x
#: 16,384 (my chip runs, PR 59; PERF.md section 6; sixteen seeds — fourteen
#: runs of the cell judged after its two warm-up steps, two states at
#: initialisation) the system read 4.58 % to 5.15 %.  The nearest precision below the stated
#: one, planted in the reference (``harness/mellum_probe.py``, two seeds):
#: bfloat16 in the router, the norms' statistics and the rotary tables 18.8 %
#: and 19.4 % — not correct, by this limit, the next and the gradient leaves
#: (78-86 % against 22.6).  0.035 x sqrt(8) = 9.9 % is 1.9x the most seen and
#: 0.53 of the stand-in's least.  The planted faults read 7.7 % (one held
#: expert's pairs dropped: found by the next limit and its leaves at 39 %) to
#: 100 % (one pick fewer, which these two limits alone find).
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.035
#: The most, per square root of the depth, by which the reference's
#: probability of an expert the system took may lie under that of the
#: reference's 8th.  A MAXIMUM over 131,072 (token, block) pairs, so its tail
#: is wider: the same sixteen seeds read 7.96e-4 to 9.99e-4.  The stand-in reads
#: 3.99e-3 and 4.32e-3; the faults 2.79e-3 (the q/k norms left out, whose
#: gains' gradients are not finite either) to 3.0e-2.  7e-4 x sqrt(8) =
#: 1.98e-3 is 2.0x the most seen and 0.50 of the stand-in's least.
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 7e-4
#: Relative limit on the further scalar of the loss (``moe_aux``: 0.001 x the
#: eight balance terms, each a mean over 16,384 x 8 picks and 64
#: probabilities).  Same seeds: at most 3.5e-7; the stand-in 6.5e-6.  A weight
#: or a count off by 10 % is 20x out.  It is no detector of precision: the
#: standing tolerances and the two limits above are.  5e-3 as OLMoE's, GLM's,
#: LFM2's and Qwen3-Next's.
SCALAR_REL_TOL = 5e-3

#: assumed, with its ground in the configuration file's ``assumed``
AUX_WEIGHT = 1e-3

#: the two kinds of layer by their HF names -> ``LlamaConfig.layer_types``
KINDS = {"sliding_attention": "window_attention",
         "full_attention": "attention"}

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "hidden_size",
          "intermediate_size", "moe_intermediate_size", "rms_norm_eps",
          "num_experts", "num_experts_per_tok", "norm_topk_prob",
          "layer_types", "sliding_window", "rope_parameters")
#: keys whose value must be the one the program computes: every layer routed
#: (``mlp_layer_types`` all ``sparse``: checked entry by entry below), no
#: bias, SwiGLU, an untied head; ``max_window_layers`` and
#: ``use_sliding_window`` say nothing ``layer_types`` does not
FIXED = {"model_type": ("mellum",), "hidden_act": ("silu",),
         "attention_bias": (False,), "tie_word_embeddings": (False,),
         "max_window_layers": (0,), "use_sliding_window": (True,)}
#: keys that change nothing a training step computes
INERT = ("max_position_embeddings",)
#: keys read entry by entry: each must be ``sparse``
ALL_SPARSE = ("mlp_layer_types",)
#: what the program's ``LlamaConfig`` must be able to say
NEEDS = ("rotary_by_kind", "layer_types", "sliding_window", "attn_head_dim",
         "qk_norm_per_head", "experts_held", "norm_topk_prob",
         "balance_all_k")


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count (``published``),
    where the file's own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_types(cfg: dict) -> tuple:
    """``LlamaConfig.layer_types`` of the file's ``layer_types``."""
    return tuple(KINDS[kind] for kind in cfg["layer_types"])


def _rotary(llama, rope: dict):
    """One ``rope_parameters`` entry as the program's ``Rotary``."""
    if rope["rope_type"] == "default":
        if set(rope) - {"rope_type", "rope_theta"}:
            raise ValueError(f"mellum: a default rope_parameters entry "
                             f"holds rope_theta alone, not {sorted(rope)}")
        return llama.Rotary(theta=float(rope["rope_theta"]))
    if rope["rope_type"] != "yarn":
        raise ValueError(
            f"mellum computes rope_type default or yarn, not "
            f"{rope['rope_type']!r}")
    return llama.Rotary(
        theta=float(rope["rope_theta"]), factor=float(rope["factor"]),
        original_max_position_embeddings=int(
            rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        attention_factor=float(rope["attention_factor"]))


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
            f"adapter mellum: this program's LlamaConfig has no {missing}: "
            "it cannot compute the mellum block")
    known = (set(MAPPED) | set(FIXED) | set(INERT) | set(ALL_SPARSE)
             | set(CONFIG_META_KEYS))
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter mellum does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"mellum computes {key} in {allowed}, not {cfg[key]!r}")
    layers = cfg["num_hidden_layers"]
    if cfg["mlp_layer_types"] != ["sparse"] * layers or (
            len(cfg["layer_types"]) != layers
            or set(cfg["layer_types"]) - set(KINDS)
            or set(cfg["rope_parameters"]) != set(cfg["layer_types"])):
        raise ValueError(
            f"mellum computes {layers} layers, each routed (mlp_layer_types "
            f"all 'sparse') and each of {tuple(KINDS)} with a "
            "rope_parameters entry for every kind present and no other, not "
            f"{cfg['mlp_layer_types']}, {cfg['layer_types']} and "
            f"{sorted(cfg['rope_parameters'])}")
    heads = cfg["num_attention_heads"]
    width, held = router_width(cfg), cfg["num_experts"]
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=layers,
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=cfg["hidden_size"],
        # no layer is dense: the dense width is read by nothing
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        layer_types=layer_types(cfg),
        # the window is the sliding layers' own: a stack without one has none
        sliding_window=(cfg["sliding_window"]
                        if "sliding_attention" in cfg["layer_types"] else 0),
        rotary_by_kind={KINDS[kind]: _rotary(llama, rope)
                        for kind, rope in cfg["rope_parameters"].items()},
        attn_head_dim=cfg["head_dim"],
        qk_norm=True,
        qk_norm_per_head=True,
        num_experts=width,
        top_k=cfg["num_experts_per_tok"],
        moe_every=1,
        d_ff_expert=cfg["moe_intermediate_size"],
        capacity_factor=None,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        balance_all_k=True,
        # the chip's share: experts 0 .. held - 1 (0 = all of them)
        experts_held=held if held < width else 0,
        experts_held_first=0,
    )


#: THE INITIALISATION (assumed; the row has no ``initializer_range``, and
#: the configuration file's ``assumed`` has the ground): embedding rows N(0,
#: 1) (PyTorch's ``nn.Embedding`` default), every projection that writes into
#: the residual stream (attention's ``wo``, an expert's ``wo``) N(0, 0.02 /
#: sqrt(2 x layers)) (GPT-2's and Megatron's scaled initialisation, at the
#: depth at hand), everything else ``llama.init_params``' N(0, 0.02), gains 1.
#: WHY NOT 0.02 THROUGHOUT: at random weights a softmax over 1,024 and more
#: keys averages its values, what all positions have in common survives the
#: average and what tells them apart does not, and with branches of rms 0.1-1
#: written onto a stream of rms 0.02 two layers suffice for every token of a
#: sequence to look alike to a router — all 16,384 tokens then take the SAME 8
#: of 64 experts (``moe.load_max_over_mean`` 7-8, its ceiling; my chip runs,
#: PR 59), whether they are among the 8 held here is the seed's draw, and the
#: routed layers' work swings from 0 to three times the even share: six
#: seeds read 17,946 to 21,576 tokens/s.  A trained model's routers are
#: balanced (its balance term sees to that), and this cell stands for one
#: chip of a deployment: with the stream carried by the embedding the held
#: experts see 11.9-13.2 % of the picks in every layer (12.5 % is even).
EMBED_STD = 1.0
BASE_STD = 0.02


def init_fn(mc):
    from dlrover_tpu.models import llama

    def init(rng):
        params = llama.init_params(rng, mc)  # N(0, BASE_STD), gains 1
        out = (2 * mc.n_layer) ** -0.5
        layers = [dict(layer, wo=layer["wo"] * out,
                       moe=dict(layer["moe"], wo=layer["moe"]["wo"] * out))
                  for layer in params["layers"]]
        return dict(params, layers=layers,
                    embed=params["embed"] * (EMBED_STD / BASE_STD))

    return init


def loss_fn(mc):
    """``llama.loss_fn`` with the balance term at its assumed weight and no
    z term, returning the routed blocks' counters beside the loss
    (``counters["step_metrics"]``); the function carries the counts of each
    kind of layer and their attended pairs for the ``accelerate.program``
    event (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, moe_aux_weight=AUX_WEIGHT,
                             moe_z_weight=0.0, metrics=True)

    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, loss, extra):
    ``llama.loss_fn``'s own path (the flash kernels under each kind's window
    and table, the sorted ragged experts, bf16, the fused loss, block remat
    where the cell has it) with the hidden states kept, and from the
    program's aux dict the experts each routed block's router took and the
    balance term."""
    import jax.numpy as jnp

    from benchmark.reference.mellum_ref import experts_name
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


#: the leaves whose gradients are compared: of the FIRST and the LAST window
#: layer and the FIRST and the LAST full layer q, k, v and the two head gains (what
#: the flash backward kernels produce under each kind's window, through the
#: per-head norm and each kind's rotary table); of the FIRST routed block
#: its router (its gradient passes through the chosen experts' weights and
#: the balance term) and the held experts; and the embedding.
_ATTENTION_LEAVES = ("wq", "wk", "wv", "q_norm", "k_norm")
_MOE_LEAVES = ("router", "wg", "wi", "wo")


def _compared(params) -> list:
    """``[(layer index, path of sub-dicts, leaf names)]``; the layers'
    kinds are not in the tree (both hold the same leaves), so positions
    stand for them at the published 3 : 1: layer 0 (a window layer), 3 (the
    first full one) and the last two (a window and a full one)."""
    n = len(params["layers"])
    picked = [(i, (), _ATTENTION_LEAVES)
              for i in sorted({0, min(3, n - 1), max(n - 2, 0), n - 1})]
    picked.append((0, ("moe",), _MOE_LEAVES))
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


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = list(params["layers"])
    for i, path, names in _compared(params):
        new = {name: leaves[_prefix(i, path) + name] for name in names}
        if path:
            new = {path[0]: dict(layers[i][path[0]], **new)}
        layers[i] = dict(layers[i], **new)
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------


def pairs_by_kind(cfg: dict, seq: int) -> dict:
    """``{HF layer type: (layers, attended pairs a sequence and layer)}``."""
    window = cfg["sliding_window"]
    return {kind: (cfg["layer_types"].count(kind),
                   flops.attended_pairs(
                       seq, window if kind == "sliding_attention" else 0))
            for kind in KINDS}


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part."""
    d = cfg["hidden_size"]
    h, kv, hd = flops.heads(cfg)
    return {
        # q, k, v, o
        "attention_proj": 2 * d * h * hd + 2 * d * kv * hd,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        # of a token's picks, those that meet an expert held HERE, under
        # even routing
        "held_picks": cfg["num_experts_per_tok"] * cfg["num_experts"]
        / router_width(cfg),
    }


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (per layer the four attention
    projections, the router and the HELD share of the token's picks — 8 x
    8/64 = one expert —, and the head's slice; the lookup is no matmul);
    attention over the pairs EACH KIND of layer attends at 32 heads of 128:
    2 matmuls a pair and head, 2 FLOPs a multiply-add, x 3 for forward and
    backward."""
    c = _counts(cfg)
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    params = (layers * (c["attention_proj"] + d * router_width(cfg)
                        + c["held_picks"] * c["expert"])
              + d * cfg["vocab_size"])
    matmul = 6.0 * params
    h, _, hd = flops.heads(cfg)
    pairs = sum(n * p for n, p in pairs_by_kind(cfg, seq).values())
    attn = 3.0 * 2 * 2 * h * hd * pairs / seq
    return {"matmul": matmul, "attention": attn, "total": matmul + attn}


def _flash_least(cfg: dict, batch: int, seq: int, peaks: dict, shards: int,
                 kinds: tuple) -> dict:
    """Least time for the flash forward and backward of ALL layers of
    ``kinds`` (HF names) in one step: per layer the larger of FLOPs over
    the peak and bytes over the bandwidth (``harness/flops.py`` has the
    count's derivation: 2 + 5 matmuls a pair; q, k, v, o once forward, q,
    k, v, o, do read and dq, dk, dv written backward, bf16), with the pairs
    THAT kind attends."""
    h, kv, hd = flops.heads(cfg)
    q_bytes = 2.0 * batch * seq * h * hd
    kv_bytes = 2.0 * batch * seq * kv * hd
    nbytes = (6 * q_bytes + 6 * kv_bytes) / shards
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0}
    for kind, (layers, pairs) in pairs_by_kind(cfg, seq).items():
        if kind not in kinds:
            continue
        flop = (2 + 5) * 2.0 * h * hd * pairs * batch / shards
        out["seconds"] += layers * max(flop / peaks["bf16_flops"],
                                       nbytes / peaks["hbm_bytes_per_s"])
        out["flops"] += layers * flop
        out["bytes"] += layers * nbytes
    out["bound"] = ("flops" if out["flops"] / peaks["bf16_flops"]
                    >= out["bytes"] / peaks["hbm_bytes_per_s"] else "bytes")
    return out


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ONE LAYER OF THE
    READER'S COUNT: ``flash_roofline`` multiplies by ``num_hidden_layers``,
    so this is the step's least time over both kinds (each layer charged
    for the pairs its kind attends) divided by the layers."""
    whole = _flash_least(cfg, batch, seq, peaks, shards, tuple(KINDS))
    layers = cfg["num_hidden_layers"]
    return dict(whole, seconds=whole["seconds"] / layers,
                flops=whole["flops"] / layers, bytes=whole["bytes"] / layers)


def flash_window_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                               shards: int = 1) -> dict:
    """Least time for the flash forward and backward of ALL the window
    layers of one step (``flash.window_roofline``'s numerator: 6 x
    16,253,440 pairs a sequence at the cell's sizes)."""
    return _flash_least(cfg, batch, seq, peaks, shards,
                        ("sliding_attention",))


def grouped_matmul_least_seconds(cfg: dict, batch: int, seq: int,
                                 peaks: dict, shards: int = 1) -> dict:
    """Least time for the three grouped matmuls of one routed block,
    forward and backward, over the HELD pairs (``adapters/olmoe.py`` has
    the count's derivation: 18 x rows x d x f FLOPs; 18 x rows x (d + f)
    bytes of rows and 24 x held experts x d x f of weights).  Every layer
    is routed, so the reader's ``num_hidden_layers`` is the count of the
    blocks.  The rows are those of EVEN routing (one held pick a token):
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
