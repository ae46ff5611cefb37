"""Adapter for the routed path the program has today
(``dlrover_tpu/models/llama.py`` with ``num_experts > 0``), for the
benchmark's own tests: no configuration file can name it (it is not under
``adapters/``); the real one comes with the OLMoE configuration.

Every layer routed (``moe_every=1``), the top-k weights renormalised (the
program always does), and a capacity of all tokens, so that nothing is
dropped: what ``routed_ref.py`` computes with ``norm_topk_prob: true``.

It keeps the routed half of the adapter contract (``benchmark/run.py``):
``hidden_and_loss`` returns ``extra`` with the experts the system chose and
the load-balance scalar of its loss, ``grad_leaves`` adds the router and
the expert weights, and the three limits below judge the choices.
"""

from __future__ import annotations

from benchmark.tests import routed_ref

#: Share of tokens, per square root of the depth, whose chosen set of experts
#: may differ from the k most probable of the reference's own float32
#: probabilities.  bf16 rounding of the stream entering the router (0.7 % at
#: one layer) flips the tokens whose k-th and (k+1)-th probability nearly
#: tie: one OLMoE layer at published widths (d 2048, 64 experts, top 8; 1,024
#: tokens, bf16 operands against float32; CPU, PR 25) 4.8 %, 4.3 % and 3.5 %
#: in three seeds (ISSUE 25: 4.8, 5.4, 5.3).  10 % is twice that; a router
#: computed in bf16 or from another stream flips far more.
CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER = 0.10
#: The most, per square root of the depth, by which the reference's
#: probability of an expert the system took may lie under that of the
#: reference's k-th.  Same measurement: 5.2e-4, 3.5e-4, 6.3e-4 (ISSUE 25:
#: 7.3e-4, 6.8e-4), where the median gap between the 8th and 9th probability
#: is 1.5e-3 and the mean probability of a chosen expert 0.050.  A system
#: that takes k-1 right experts and one at random shows 1e-2 and more.
#: 2e-3 is 2.7x the rounding and 5x under such a fault.
CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER = 2e-3
#: Relative limit on each further scalar of the loss (here the load-balance
#: sum).  It is a mean over all tokens, and a flipped first choice moves one
#: token's count: same measurement 0.8e-4, 3.4e-4, 8.7e-4 under the system's
#: choices.  5e-3 is 6x that; a coefficient or a count off by 10 % is 20x out.
SCALAR_REL_TOL = 5e-3

#: what ``llama.loss_fn`` weighs the load-balance sum with (its default)
AUX_WEIGHT = 1e-2


def model_config(cfg: dict, *, remat_block: bool, seq_len: int):
    from dlrover_tpu.models import llama

    if not cfg["norm_topk_prob"]:
        raise ValueError("models/llama.py always renormalises the top-k")
    n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], max_seq_len=seq_len,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]), remat_block=remat_block,
        num_experts=n_exp, top_k=top_k, moe_every=1,
        # capacity = round(factor * tokens * k / experts) = every token
        capacity_factor=n_exp / top_k)


def init_fn(mc):
    from dlrover_tpu.models import llama

    return lambda rng: llama.init_params(rng, mc)


def loss_fn(mc):
    from dlrover_tpu.models import llama

    return lambda params, batch: llama.loss_fn(
        params, batch, mc, moe_aux_weight=AUX_WEIGHT)


def hidden_and_loss(params, tokens, mc):
    """``llama.loss_fn``'s own path with the hidden states kept, and what
    the contract asks of a routed block beside them.  The program does not
    hand out the experts ``_moe_swiglu`` takes, and repeating its router
    beside it is not exact (another fusion, another last bit, another expert
    on a near tie): so the one ``top_k`` it calls is listened to while
    ``forward_hidden`` is traced.  A real adapter gets them from the program
    (PERF.md section 7)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy

    taken, top_k = [], jax.lax.top_k

    def listening(operand, k):
        values, experts = top_k(operand, k)
        taken.append(experts)
        return values, experts

    jax.lax.top_k = listening
    try:
        hidden, aux = llama.forward_hidden(params, tokens[:, :-1], mc)
    finally:
        jax.lax.top_k = top_k
    per_tok = linear_softmax_cross_entropy(
        hidden, params["lm_head"].astype(mc.dtype), tokens[:, 1:])
    loss = jnp.mean(per_tok) + AUX_WEIGHT * aux["moe_aux"]
    extra = {
        "choices": {
            routed_ref.experts_name(i): experts.reshape(
                hidden.shape[:2] + (mc.top_k,))
            for i, experts in enumerate(taken)},
        "scalars": {"moe_aux": aux["moe_aux"]},
    }
    return hidden.astype(jnp.float32), loss, extra


def grad_leaves(params) -> dict:
    """Embedding, q/k/v projections, and of every layer's routed block the
    router (its gradient passes through the weights of the chosen experts
    and the load-balance sum) and the three expert matrices."""
    leaves = {"embed": params["embed"]}
    for i, layer in enumerate(params["layers"]):
        for name in ("wq", "wk", "wv"):
            leaves[f"layers.{i}.{name}"] = layer[name]
        for name in ("router", "wg", "wi", "wo"):
            leaves[f"layers.{i}.moe.{name}"] = layer["moe"][name]
    return leaves


def with_leaves(params, leaves: dict):
    layers = []
    for i, layer in enumerate(params["layers"]):
        moe = dict(layer["moe"], **{
            name: leaves[f"layers.{i}.moe.{name}"]
            for name in ("router", "wg", "wi", "wo")})
        layers.append(dict(layer, moe=moe, **{
            name: leaves[f"layers.{i}.{name}"]
            for name in ("wq", "wk", "wv")}))
    return dict(params, embed=leaves["embed"], layers=layers)
