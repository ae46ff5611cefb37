"""Adapter for the Ouro looped language model (model type ``ouro``,
arXiv:2510.25741: a dense pre-norm block with a second RMSNorm on each
branch's output, RoPE, multi-head attention, SwiGLU, untied head; the whole
stack of layers runs ``total_ut_steps`` times on the same weights, every
pass ends in the final norm, an exit gate and the head, and the training
loss is the expectation over the pass a token exits at): a configuration
file in HF keys -> the program's ``dlrover_tpu/models/llama.py`` with
``loop_passes``, ``branch_norm`` and ``exit_gate_beta``.

The adapter contract is in ``adapters/llama_dense.py``.  This block makes
no discrete choice, so ``hidden_and_loss`` returns ``(hidden, loss)`` as a
dense block's does, and the standing tolerances of ``harness/model.py``
judge it: ``hidden`` is all T final-norm streams, so every pass is held
(equations: ``benchmark/reference/ouro_ref.py``).  Why no ``extra``: with
no choice to give, the harness's third program would compute the
reference's forward a second time (25 s of compiling in every run), and
the scalars it could judge are either held already (each pass's mean
cross-entropy follows from its stream and the shared head) or cannot be
held in bf16 (the mean exit probabilities read 0.7-6.9 % from the float32
reference's over 14 seeds on the v5e, and the same with the stream in
fp8: a shift of the gate's logit common to all tokens, no detector of
anything; PERF.md section 4).  The loop's counters are compared in float32
on the CPU (``benchmark/tests/test_ouro.py``); on the chip the gate is
held by its two gradient leaves and by the loss.
"""

from __future__ import annotations

from benchmark.harness import flops

#: arXiv:2510.25741, stage I: weight of the exit distribution's entropy
EXIT_ENTROPY_BETA = 0.1

#: keys this adapter maps into ``LlamaConfig``
MAPPED = ("vocab_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "hidden_size", "intermediate_size",
          "head_dim", "rope_theta", "rms_norm_eps", "total_ut_steps")
#: keys whose value must be the one the program computes
FIXED = {"hidden_act": ("silu",), "tie_word_embeddings": (False,),
         "rope_scaling": (None,), "sliding_window": (None, 0),
         "use_sliding_window": (False,), "model_type": ("ouro",)}
#: keys that change nothing a training step computes: the exit threshold
#: is inference's (training runs every pass), ``max_window_layers`` is read
#: only where ``use_sliding_window`` is set
INERT = ("max_position_embeddings", "early_exit_threshold",
         "max_window_layers")


def model_config(cfg: dict, *, remat_block: bool, seq_len: int):
    """The configuration file as the program's ``LlamaConfig``, no width
    changed on the way: every layer dense with the sandwich norm, the
    stack run ``total_ut_steps`` times, the exit gate with the paper's
    entropy weight."""
    import dataclasses

    from benchmark.harness.common import CONFIG_META_KEYS
    from dlrover_tpu.models import llama

    # first of all: a program that cannot say these (the parent of the PR
    # that brought them) is refused by name, before anything is compiled
    missing = sorted(
        {"loop_passes", "branch_norm", "exit_gate_beta"}
        - {f.name for f in dataclasses.fields(llama.LlamaConfig)})
    if missing:
        raise ValueError(
            f"adapter ouro: this program's LlamaConfig has no {missing}: "
            "it cannot compute a looped model")
    known = (set(MAPPED) | set(FIXED) | set(INERT) | {"layer_types"}
             | set(CONFIG_META_KEYS))
    unknown = sorted(set(cfg) - known)
    if unknown:
        raise ValueError(
            f"adapter ouro does not know the key(s) {unknown}: a "
            "configuration of another architecture names another adapter")
    for key, allowed in FIXED.items():
        if key in cfg and cfg[key] not in allowed:
            raise ValueError(
                f"ouro computes {key} in {allowed}, not {cfg[key]!r}")
    layers = cfg["num_hidden_layers"]
    if cfg.get("layer_types", ["full_attention"] * layers) != [
            "full_attention"] * layers:
        raise ValueError(
            f"ouro computes {layers} layers of full_attention, not "
            f"layer_types={cfg['layer_types']!r}")
    heads, hidden = cfg["num_attention_heads"], cfg["hidden_size"]
    if cfg.get("head_dim", hidden // heads) * heads != hidden:
        raise ValueError("LlamaConfig derives head_dim = hidden / heads")
    if cfg["total_ut_steps"] < 2:
        raise ValueError(
            f"ouro runs the stack more than once, not total_ut_steps="
            f"{cfg['total_ut_steps']}: a plain dense block names the "
            "llama_dense adapter")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"],
        n_layer=layers,
        n_head=heads,
        n_kv_head=cfg.get("num_key_value_heads", heads),
        d_model=hidden,
        d_ff=cfg["intermediate_size"],
        max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        remat_block=remat_block,
        loop_passes=cfg["total_ut_steps"],
        branch_norm=True,
        exit_gate_beta=EXIT_ENTROPY_BETA,
    )


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    """``llama.loss_fn`` returning the loop's counters beside the loss:
    ``accelerate()``'s step hands them out (``counters["step_metrics"]``)."""
    from dlrover_tpu.models import llama

    return lambda params, batch: llama.loss_fn(
        params, batch, mc, metrics=True)


def hidden_and_loss(params, tokens, mc):
    """``llama.loss_fn``'s own path (block remat where the cell has it,
    the ONE reduced head call over the T x N rows whose weights carry the
    gate's gradient) with the streams kept: ``hidden`` is the T final-norm
    streams stacked along the batch, ``[T*B, S, d]``."""
    import jax.numpy as jnp

    from dlrover_tpu.models import llama

    streams, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    loss, _ = llama.exit_expectation_loss(
        streams, aux["exit_logits"], params["lm_head"], tokens[:, 1:], mc)
    hidden = streams.reshape((-1,) + streams.shape[2:])
    return hidden.astype(jnp.float32), loss


_LAYER_LEAVES = ("wq", "wk", "wv")


def grad_leaves(params) -> dict:
    """Embedding and the q, k, v projections of every layer (what the
    flash backward kernels produce, here each the SUM over the T
    applications of its layer), and this architecture's own: the last
    layer's ``w_down`` (through its branch-output norm) and the exit gate
    (its gradient arrives through the head's row weights and the entropy
    term alone).  The gate's weight and bias are ONE leaf, ``[d + 1]``:
    the bias's gradient is a single number, the sum over all tokens of
    signed terms, and where that sum passes near zero its own relative
    distance means nothing (72 % at one seed of twenty on the v5e with
    every other leaf inside its tolerance; PERF.md section 6)."""
    import jax.numpy as jnp

    gate = params["exit_gate"]
    leaves = {"embed": params["embed"],
              "exit_gate": jnp.concatenate([gate["w"], gate["b"][None]])}
    for i, layer in enumerate(params["layers"]):
        for name in _LAYER_LEAVES:
            leaves[f"layers.{i}.{name}"] = layer[name]
    last = len(params["layers"]) - 1
    leaves[f"layers.{last}.mlp.w_down"] = params["layers"][last]["mlp"][
        "w_down"]
    return leaves


def with_leaves(params, leaves: dict):
    """``params`` with the leaves of :func:`grad_leaves` replaced."""
    layers = [
        dict(layer, **{name: leaves[f"layers.{i}.{name}"]
                       for name in _LAYER_LEAVES})
        for i, layer in enumerate(params["layers"])]
    last = len(layers) - 1
    layers[last] = dict(layers[last], mlp=dict(
        layers[last]["mlp"], w_down=leaves[f"layers.{last}.mlp.w_down"]))
    gate = leaves["exit_gate"]
    return dict(params, embed=leaves["embed"], layers=layers,
                exit_gate={"w": gate[:-1], "b": gate[-1]})


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token: a token passes
    ``total_ut_steps x num_hidden_layers`` block applications and as many
    heads (and exit gates, ``hidden_size`` multiply-adds each) as passes;
    attention over the causal pairs, no window, in every application.
    The four norms a layer are no matmul; recomputation never counts."""
    mp = flops.matmul_params(cfg)
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    matmul = 6.0 * passes * (
        layers * mp["layer"] + mp["head"] + cfg["hidden_size"])
    h, _, hd = flops.heads(cfg)
    attn = (3.0 * 2 * 2 * h * hd * flops.attended_pairs(seq, 0)
            * layers * passes / seq)
    return {"matmul": matmul, "attention": attn, "total": matmul + attn}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time one device could take for the flash forward and backward
    of ONE layer's ``total_ut_steps`` applications at this batch.  The
    reader (``layer_metrics/flash_roofline.py``) multiplies what this
    returns by ``num_hidden_layers`` and sets it against the summed time of
    all flash kernels in the step, which a looped step calls passes x
    layers times: one application's count would read a quarter of the
    truth."""
    one = flops.flash_least_seconds(cfg, batch, seq, peaks, shards)
    passes = cfg["total_ut_steps"]
    return dict(one, seconds=one["seconds"] * passes,
                flops=one["flops"] * passes, bytes=one["bytes"] * passes)
