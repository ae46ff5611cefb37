"""Adapter for the Trinity block (HF model type ``afmoe``): window
(``sliding_attention``) layers that rotate and full (``full_attention``)
layers that carry NO position in one stack, grouped-query attention with
per-head RMSNorms on q and k and a sigmoid gate per element on its output,
FOUR norms a block (sandwich norms), the embedding's rows times
``sqrt(hidden_size)`` (``mup_enabled``); ``num_dense_layers`` leading SwiGLU
layers, then in every layer a float32 sigmoid router over
``published.num_experts`` experts with a selection bias, the top
``num_experts_per_tok`` renormalised and scaled by ``route_scale``, a shared
expert for every token; an untied head: a configuration file in HF keys ->
the program's ``dlrover_tpu/models/llama.py``.

The reference writes ``q = x Wq`` and ``g = x Wg`` as two matrices; the
program's tree stores them as the halves of each head's ``[q | gate]``
columns of ONE leaf ``wq [hidden, heads x 2 x head_dim]``
(``LlamaConfig.attn_output_gate``): the same function, and the reference
reads the same leaf.

THE SHARE.  The file's own ``num_experts`` is what THIS CHIP HOLDS (16,
experts 0-15 of an 8-way expert-parallel layer); the router's width (128) is
the source's, read from the file's ``published`` block.  The router scores,
chooses and normalises over all 128, the chip computes the pairs routed to
its 16, and what the absent experts would add is left out BEFORE
``post_mlp_layernorm``, program and reference alike
(``reference/afmoe_ref.py``).  Every count below that is a share of a
roofline or of a peak counts the HELD pairs (``num_experts_per_tok * held /
width`` = 1 a token under even routing), never all the router's picks.

The adapter contract is in ``adapters/llama_dense.py`` and, for the routed
half (``extra``, the three limits below), in ``benchmark/run.py``.  The
experts the system took come from the PROGRAM's own aux dict
(``llama.forward_hidden``); the loss has no further scalar
(``load_balance_coeff`` is the rate of the bias's rule: ``assumed``), and the
two that ``extra`` carries are the window's edge read by itself
(``reference/afmoe_ref.py::window_alone``).

THE PAIRS.  Attention is charged for the (query, key) pairs each KIND of
layer attends — at S 16,384 a full layer 134,225,920 a sequence, a window
layer (2,048) 31,458,304 — never S^2 / 2 on a window layer.
``flash_roofline``'s reader multiplies by ``num_hidden_layers``, so
``flash_least_seconds`` (``adapters/mellum.py``'s, which reads the same
keys) returns the MEAN layer's least time; ``flash_window_least_seconds``
the window layers' of one step, 4 x 31,458,304 pairs a sequence here.
"""

from __future__ import annotations

from benchmark.adapters import mellum
from benchmark.harness import flops

#: Share of tokens, per square root of the depth (5 layers: x 2.24), whose
#: chosen set of 8 experts of 128 may differ from the 8 largest of the
#: reference's own float32 ``sigmoid + bias``, in the worst routed block (the
#: last one, every time: the rounding of four layers' bf16 matmuls reaches
#: its router, 1.2 % of the stream, and the 8th and 9th of 128 sigmoid
#: scores lie 0.02 apart).  A mean over 16,384 tokens, so steady.  Readings
#: on the v5e at published width and 1 x 16,384 (my chip runs, PR 65;
#: PERF.md section 6): the system 9.19 % to 9.64 % over twelve seeds
#: (ten runs of the cell judged after its two warm-up steps, two states
#: of ``harness/afmoe_probe.py`` at initialisation), 7.96 % on the state
#: whose biases have moved; the nearest precision below the stated one,
#: planted in the reference: the stream entering every router in fp8 e4m3
#: 22.55 % — which this limit and the next alone find —, the whole normed
#: stream in fp8 45.97 %, bfloat16 where the file states float32 72.33 % —
#: not correct.  0.065 x sqrt(5) = 14.53 % is 1.51x the most seen and 0.64
#: of the stand-ins' least.
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.065
#: The most, per square root of the depth, by which the reference's ``s +
#: b`` of an expert the system took may lie under that of the reference's
#: 8th.  A MAXIMUM over 65,536 (token, block) pairs, so its tail is wider.
#: Same runs: the system 6.20e-3 to 8.91e-3; fp8 on the routers' stream
#: 2.71e-2, on the whole stream 5.52e-2, bfloat16 for float32 1.27e-1; the
#: selection bias leaked into the weights 1.11e-2 (found by the router's
#: gradient leaf, 39.8 % against 17.9).  7.5e-3 x sqrt(5) = 1.68e-2 is 1.88x
#: the most seen and 0.62 of the stand-ins' least.
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 7.5e-3
#: Relative limit on the further scalars.  The loss has none
#: (``load_balance_coeff`` is no weight of a term: ``assumed``); the two
#: there are are ``reference/afmoe_ref.py::window_alone``'s: what the
#: program's flash op at the program's window reads on zero queries and keys
#: and values that flag every 2,048th position — 1 + 2,048 x the least and
#: 2,048 x the most over the queries past the first window, 2 and 1 where a
#: query sees exactly the last 2,048 keys.  The system reads both exactly
#: (1 / 2,048 is a power of two: distance 0.0 on every seed, my chip runs,
#: PR 65); the window one key short reads 1 for 2 (distance 1.0), one key
#: long 1.999 for 1.  It is what holds the window's edge: in the model one
#: key in 2,048 is worth 1 % of a branch, under bf16's rounding.  5e-3, the
#: routed adapters' standing limit, is 200x under the fault.
SCALAR_REL_TOL = 5e-3

#: the two kinds of layer by their HF names -> ``LlamaConfig.layer_types``,
#: and the pairs and least times of the flash kernels by kind: the Mellum
#: adapter's, which read the same three keys (``layer_types``,
#: ``sliding_window``, the heads)
KINDS = mellum.KINDS
pairs_by_kind = mellum.pairs_by_kind
flash_least_seconds = mellum.flash_least_seconds
flash_window_least_seconds = mellum.flash_window_least_seconds

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "hidden_size",
          "intermediate_size", "moe_intermediate_size", "rms_norm_eps",
          "num_experts", "num_experts_per_tok", "num_shared_experts",
          "num_dense_layers", "layer_types", "sliding_window", "rope_theta",
          "route_norm", "route_scale", "mup_enabled", "load_balance_coeff")
#: keys whose value must be the one the program computes: sigmoid scores,
#: no group limit on the top-k, no scaling of the rotary table, SwiGLU, an
#: untied head
FIXED = {"model_type": ("afmoe",), "hidden_act": ("silu",),
         "tie_word_embeddings": (False,), "rope_scaling": (None,),
         "score_func": ("sigmoid",), "n_group": (1,), "topk_group": (1,),
         "num_expert_groups": (1,), "num_limited_groups": (1,)}
#: keys that change nothing a training step computes:
#: ``global_attn_every_n_layers`` says of the SOURCE's 32 layers what
#: ``layer_types`` says layer by layer (the cut's own list — the dense layer
#: and one period — is what is computed); ``use_grouped_mm`` picks the
#: source's own kernel for the same sum
INERT = ("max_position_embeddings", "global_attn_every_n_layers",
         "use_grouped_mm")
#: what the program's ``LlamaConfig`` must be able to say: fields, and the
#: method by which a kind of layer says it carries no position
#: (``rotary_by_kind``'s ``None``)
NEEDS = ("rotary_by_kind", "unrotated", "layer_types", "sliding_window",
         "branch_norm", "attn_output_gate", "attn_head_dim",
         "qk_norm_per_head", "embedding_multiplier", "first_k_dense",
         "d_ff_expert", "n_shared_experts", "router_score", "routed_scaling",
         "router_bias_rate", "experts_held")


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count (``published``),
    where the file's own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_types(cfg: dict) -> tuple:
    """``LlamaConfig.layer_types`` of the file's ``layer_types``."""
    return tuple(KINDS[kind] for kind in cfg["layer_types"])


def model_config(cfg: dict, *, remat_block: bool, seq_len: int):
    """The configuration file as the program's ``LlamaConfig``, no width
    changed on the way."""
    from benchmark.harness.common import CONFIG_META_KEYS
    from dlrover_tpu.models import llama

    # first of all: a program that cannot say these (the parent of the PR
    # that brought them) is refused by name, before anything is compiled
    missing = sorted(set(NEEDS) - set(dir(llama.LlamaConfig)))
    if missing:
        raise ValueError(
            f"adapter afmoe: this program's LlamaConfig has no {missing}: "
            "it cannot compute the afmoe block")
    known = set(MAPPED) | set(FIXED) | set(INERT) | set(CONFIG_META_KEYS)
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter afmoe does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"afmoe computes {key} in {allowed}, not {cfg[key]!r}")
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    if (len(cfg["layer_types"]) != layers
            or set(cfg["layer_types"]) - set(KINDS)
            or not 0 <= dense < layers):
        raise ValueError(
            f"afmoe computes {layers} layers, each of {tuple(KINDS)}, the "
            f"first num_dense_layers (fewer than all) dense, not "
            f"{cfg['layer_types']} with num_dense_layers={dense}")
    heads = cfg["num_attention_heads"]
    width, held = router_width(cfg), cfg["num_experts"]
    rotary = {"sliding_attention": llama.Rotary(theta=float(cfg["rope_theta"])),
              "full_attention": None}  # the full layers carry no position
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=layers,
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        layer_types=layer_types(cfg),
        # the window is the sliding layers' own: a stack without one has none
        sliding_window=(cfg["sliding_window"]
                        if "sliding_attention" in cfg["layer_types"] else 0),
        rotary_by_kind={KINDS[kind]: rotary[kind]
                        for kind in set(cfg["layer_types"])},
        attn_head_dim=cfg["head_dim"],
        qk_norm=True,
        qk_norm_per_head=True,
        attn_output_gate=True,
        branch_norm=True,
        embedding_multiplier=(float(cfg["hidden_size"]) ** 0.5
                              if cfg["mup_enabled"] else 1.0),
        num_experts=width,
        top_k=cfg["num_experts_per_tok"],
        moe_every=1,
        first_k_dense=dense,
        d_ff_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        capacity_factor=None,
        norm_topk_prob=bool(cfg["route_norm"]),
        router_score="sigmoid",
        routed_scaling=float(cfg["route_scale"]),
        # read as the rate of the bias's rule (``assumed``)
        router_bias_rate=float(cfg["load_balance_coeff"]),
        # the chip's share: experts 0 .. held - 1 (0 = all of them)
        experts_held=held if held < width else 0,
        experts_held_first=0,
    )


#: THE INITIALISATION (assumed; the row has no ``initializer_range``, and
#: the configuration file's ``assumed`` has the ground): ``llama.init_params``
#: — every projection, the router, the embedding and the head N(0, 0.02),
#: every gain 1, the selection bias 0 — but for ONE gain a layer:
#: ``post_attention_layernorm`` (``ln1_out``) starts at 0.1.
#: WHY NOT 1 THROUGHOUT: at random weights a softmax over 2,048 and more keys
#: averages its values, what all positions have in common survives the
#: average and what tells them apart does not, and a sandwich norm hands that
#: common vector to the stream at rms 1 in EVERY layer, whatever the scale of
#: the weights (so no scaling of ``wo``, which served the Mellum cell, can
#: help here).  Beside an embedding of rms 0.9 the routers then see mostly
#: what the tokens of a sequence share: the fullest expert takes 2.6 to 5.5
#: times the mean, the 16 held ones 7.96 % to 15.12 % of the picks by seed
#: and layer (10,437 to 19,816 pairs against a sized buffer of 20,480 rows;
#: four seeds, my chip runs, PR 65), and the routed layers' work swings with
#: the seed's draw.  A trained model's routers are balanced (its bias rule
#: sees to that), and this cell stands for one chip of a deployment.  With
#: the attention branch entering at rms 0.1 the same four seeds read 11.57 %
#: to 12.86 % in every routed layer (12.5 % is even), the fullest expert 1.24
#: to 1.48 times the mean (0.25: 10.61-13.23 % and 1.47-2.01; 0.5: 9.68-13.82
#: %).  The attention layers' gradient leaves do not shrink with their
#: branch — a gradient leaf's distance is relative to itself — so the
#: comparison sees the attention path as before.
POST_ATTENTION_GAIN = 0.1


def init_fn(mc):
    from dlrover_tpu.models import llama

    def init(rng):
        params = llama.init_params(rng, mc)  # N(0, 0.02), gains 1, bias 0
        return dict(params, layers=[
            dict(layer, ln1_out=layer["ln1_out"] * POST_ATTENTION_GAIN)
            for layer in params["layers"]])

    return init


def loss_fn(mc):
    """``llama.loss_fn`` with no balance and no z term (the loss is the
    cross-entropy alone), returning the routed blocks' counters beside the
    loss (``counters["step_metrics"]``) and the selection biases' next
    values; the function names those leaves (``rule_leaves``) and carries
    the counts of each kind of layer, their attended pairs and the layers
    without position for the ``accelerate.program`` event
    (``program_facts``)."""
    from dlrover_tpu.models import llama

    def loss(params, batch):
        return llama.loss_fn(params, batch, mc, moe_aux_weight=0.0,
                             moe_z_weight=0.0, metrics=True)

    loss.rule_leaves = llama.rule_leaves(mc)
    loss.program_facts = llama.program_facts(mc, mc.max_seq_len)
    return loss


def hidden_and_loss(params, tokens, mc):
    """tokens [B, S+1] -> (final-norm hidden [B, S, d] f32, loss, extra):
    ``llama.loss_fn``'s own path (the flash kernels under each kind's window,
    rotation by kind, the gate, the four norms, the sorted ragged experts,
    bf16, the fused loss, block remat where the cell has it) with the hidden
    states kept, and from the program's aux dict the experts each routed
    block's router took."""
    import jax.numpy as jnp

    from benchmark.reference.afmoe_ref import experts_name, window_alone
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy_sum
    from dlrover_tpu.ops.flash_attention import flash_attention

    hidden, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    x, head = llama.head_operands(params, hidden, mc)
    nll = linear_softmax_cross_entropy_sum(
        x, head.astype(mc.dtype), tokens[:, 1:], None)
    # the flash op as a window layer calls it (its layout, the compute
    # dtype, the PROGRAM's window), alone on the operands the reference is
    # handed too
    window = mc.window_of("window_attention") if mc.window_layers else 0

    def attend(q, k, v):
        return flash_attention(
            *(a.astype(mc.dtype).transpose(0, 2, 1, 3) for a in (q, k, v)),
            causal=True, window=window).transpose(0, 2, 1, 3)

    extra = {
        "choices": {experts_name(i): experts
                    for i, experts in aux["moe_experts"].items()},
        "scalars": window_alone(
            window, tokens.shape[1] - 1,
            (mc.n_head, mc.n_kv_head, mc.head_dim), attend),
    }
    return hidden.astype(jnp.float32), nll, extra


#: the leaves whose gradients are compared: of the dense layer (a window
#: layer), the FIRST and the LAST routed window layer and the full layer
#: ``wq`` (queries and gate), k, v, ``wo``, the two head gains and the two
#: OUTPUT norms' gains (what the flash backward kernels produce under each
#: kind's window, through the gate, the per-head norm and the rotation or
#: none, and what the sandwich norms hand back); of the FIRST routed block
#: its router over the HELD experts' columns (its gradient passes through
#: the chosen experts' weights; without a balance term an absent expert's
#: column sums only the normaliser's share of the rows that took a held
#: expert beside it, few and small, and reads far on rounding alone:
#: :func:`_held_columns`, as ``adapters/kimi_linear.py``), the held experts
#: and the shared expert; and the embedding.
_ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln1_out",
                     "ln2_out")
_MOE_LEAVES = ("router", "wg", "wi", "wo")
_SHARED_LEAVES = ("w_gate", "w_up", "w_down")


def _compared(params) -> list:
    """``[(layer index, path of sub-dicts, leaf names)]``; the layers'
    kinds are not in the tree (both hold the same leaves), so positions
    stand for them: layer 0 (dense, a window layer), the first routed layer,
    and the last two (a window and the full one at the cut's 4 : 1)."""
    layers = params["layers"]
    n = len(layers)
    routed = next(i for i, layer in enumerate(layers) if "moe" in layer)
    picked = [(i, (), _ATTENTION_LEAVES)
              for i in sorted({0, routed, max(n - 2, 0), n - 1})]
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
    """``holder`` with ``new`` merged into the dict at ``path``."""
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
        layers[i] = _replaced(layers[i], path, {
            name: leaves[_prefix(i, path) + name] for name in names})
    return dict(params, embed=leaves["embed"], layers=layers)


# -- operations and bytes the algorithm needs -------------------------------


def _counts(cfg: dict) -> dict:
    """Parameters a token meets in a matmul, by part."""
    d = cfg["hidden_size"]
    h, kv, hd = flops.heads(cfg)
    return {
        # q with its gate, k, v, o
        "attention_proj": 3 * d * h * hd + 2 * d * kv * hd,
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "routed_layers": cfg["num_hidden_layers"] - cfg["num_dense_layers"],
        # of a token's picks, those that meet an expert held HERE, under
        # even routing
        "held_picks": cfg["num_experts_per_tok"] * cfg["num_experts"]
        / router_width(cfg),
    }


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token ON THIS CHIP: 6 x
    the matmul parameters a token meets (per layer the four attention
    projections, ``wq`` twice as wide for the gate; the dense layers' MLP;
    per routed layer the router, the shared expert and the HELD share of the
    token's picks — 8 x 16/128 = one expert —, and the head's slice; the
    lookup, the gate's multiply, the norms and the rotation are no matmul);
    attention over the pairs EACH KIND of layer attends at 32 heads of 128:
    2 matmuls a pair and head, 2 FLOPs a multiply-add, x 3 for forward and
    backward."""
    c = _counts(cfg)
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    routed = (d * router_width(cfg)
              + (cfg["num_shared_experts"] + c["held_picks"]) * c["expert"])
    params = (layers * c["attention_proj"]
              + cfg["num_dense_layers"] * c["dense_mlp"]
              + c["routed_layers"] * routed
              + d * cfg["vocab_size"])
    matmul = 6.0 * params
    h, _, hd = flops.heads(cfg)
    pairs = sum(n * p for n, p in pairs_by_kind(cfg, seq).values())
    attn = 3.0 * 2 * 2 * h * hd * pairs / seq
    return {"matmul": matmul, "attention": attn, "total": matmul + attn}


def grouped_matmul_least_seconds(cfg: dict, batch: int, seq: int,
                                 peaks: dict, shards: int = 1) -> dict:
    """Least time for the three grouped matmuls of one routed block,
    forward and backward, over the HELD pairs (``adapters/olmoe.py`` has
    the count's derivation: 18 x rows x d x f FLOPs; 18 x rows x (d + f)
    bytes of rows and 24 x held experts x d x f of weights), per LAYER OF
    THE READER'S COUNT: ``moe.grouped_matmul_roofline`` multiplies by
    ``num_hidden_layers`` (5) and a step has 4 routed blocks, so one
    block's least time is scaled by 4 / 5.  The rows are those of EVEN
    routing (one held pick a token): what the routers really send here is
    ``moe.held_pair_share_pct``'s to say."""
    c = _counts(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * c["held_picks"] / shards
    scale = c["routed_layers"] / cfg["num_hidden_layers"]
    flop = 18.0 * rows * d * f * scale
    nbytes = (18.0 * rows * (d + f)
              + 24.0 * cfg["num_experts"] * d * f / shards) * scale
    t_flops = flop / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flop, "bytes": nbytes}
