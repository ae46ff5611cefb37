"""Llama-family decoder LM — the flagship model (BASELINE.json configs[1]).

Functional JAX, TPU-first (analogue of the reference's Llama2 ATorch example
``atorch/examples/llama2`` + HF modeling it wraps): RMSNorm (fused Pallas op),
RoPE, grouped-query attention with pluggable attention backends
(XLA-fused reference / Pallas flash / ring for long context / Ulysses SP),
SwiGLU MLP, optional MoE layers (expert-parallel), weight-untied LM head.

Sharding: :func:`param_logical_axes` names every parameter with logical axes
('embed'/'heads'/'mlp'/'vocab'/'expert'), mapped to mesh axes by
``dlrover_tpu.parallel.sharding`` rules — DP/FSDP/TP/SP/EP are rule changes,
not model changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.conv_silu import causal_conv1d_silu
from dlrover_tpu.ops.cross_entropy import (
    linear_softmax_cross_entropy_sum,
    softmax_cross_entropy,
)
from dlrover_tpu.ops.flash_attention import (
    SAVED_NAMES as FLASH_SAVED_NAMES,
    flash_attention,
)
from dlrover_tpu.ops.gated_delta import (
    CHANNEL_CHUNK as KDA_CHUNK,
    CHANNEL_SAVED_NAMES as KDA_SAVED_NAMES,
    SAVED_NAMES as GDN_SAVED_NAMES,
    gated_delta_chunked,
)
from dlrover_tpu.ops.gated_norm import gated_norm
from dlrover_tpu.ops.gather_sum import gather_sum, weighted_sum
from dlrover_tpu.ops.grouped_matmul import (
    TILING,
    backend_for as expert_backend_for,
    grouped_matmul_ragged,
)
from dlrover_tpu.ops.rmsnorm import rmsnorm
from dlrover_tpu.ops.selective_scan import (
    CHUNK as S6_CHUNK,
    SAVED_NAMES as S6_SAVED_NAMES,
    selective_scan,
)
from dlrover_tpu.ops.ssd import causal_conv1d, ssd_chunked


#: the kinds of mixer a layer may have (``LlamaConfig.layer_types``), each
#: with its block scope, which is also the key of the layer dict that holds
#: its leaves (the attention leaves sit in the layer dict itself) and, with
#: ``_layers``, the name :func:`program_facts` counts its layers under.  A
#: new kind adds a row.  The attention kinds share scope and
#: :func:`_attention`: a "window_attention" layer attends the last
#: ``sliding_window`` positions where an "attention" layer of the same model
#: attends them all, and a "cross_attention" layer projects queries alone and
#: attends the keys and values ANOTHER layer computed
#: (``LlamaConfig.shared_kv_layer``).  "mamba1" is the Mamba-1 mixer (the
#: selective scan: "mamba" is Mamba-2's), "gmu" the Gated Memory Unit, which
#: reads another layer's scan output (``LlamaConfig.memory_layer``).
MIXER_KINDS = {"attention": "attention", "mamba": "ssm", "conv": "conv",
               "linear_attention": "gdn", "window_attention": "attention",
               "kda": "kda", "mamba1": "s6", "gmu": "gmu",
               "cross_attention": "attention"}
#: the attention kinds, each with the scope around its flash call INSIDE the
#: block's ``attention`` (entered where a model has layers of more than one)
ATTENTION_KINDS = {"attention": "attn_full", "window_attention": "attn_window",
                   "cross_attention": "attn_cross"}
#: the forms of the block's two norms and the final norm
#: (``LlamaConfig.norm_form``)
NORM_FORMS = ("rmsnorm", "layernorm")
#: what ``LlamaConfig.layer_types`` may name as a layer's ONLY branch where
#: ``one_branch``: the dense MLP and the routed block, each by the key of the
#: layer dict that holds its leaves
MLP_KINDS = ("mlp", "moe")
#: the forms of an MLP (``LlamaConfig.mlp_form``): ``down(silu(gate x) * up
#: x)``, three matrices, or ``down(relu(up x)^2)``, two
MLP_FORMS = ("swiglu", "relu2")
#: positions a chunk of the gated delta rule holds (``ops.gated_delta``): a
#: shape decision of the op, not a setting
GDN_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class Rotary:
    """The rotary table of one kind of attention layer
    (``LlamaConfig.rotary_by_kind``): the base and, where ``factor`` is not
    1, YaRN's blend (arXiv:2309.00071 as HF's ``_compute_yarn_parameters``
    computes it).  With ``f_j = theta^(-j / half)`` and ``d(n) = dim *
    ln(original_max_position_embeddings / (2 pi n)) / (2 ln theta)``: ``low
    = floor(d(beta_fast))``, ``high = ceil(d(beta_slow))`` clipped to ``[0,
    dim - 1]``, ``ramp_j = clip((j - low) / (high - low), 0, 1)`` and
    ``inv_freq_j = f_j / factor * ramp_j + f_j * (1 - ramp_j)``: the fast
    dims keep their frequency, the slow ones are stretched ``factor`` times.
    Cos and sin are both multiplied by ``attention_factor``, so the scores
    carry its square."""

    theta: float
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def correction_range(self, dim: int) -> tuple:
        """YaRN's ``(low, high)`` over the ``dim`` rotary dims of a head."""
        def d(rotations):
            return (dim * np.log(self.original_max_position_embeddings
                                 / (rotations * 2 * np.pi))
                    / (2 * np.log(self.theta)))

        return (max(int(np.floor(d(self.beta_fast))), 0),
                min(int(np.ceil(d(self.beta_slow))), dim - 1))

    def inv_freq(self, dim: int) -> jax.Array:
        """float32 ``[dim / 2]``; at ``factor`` 1 the plain table's, bit for
        bit (:func:`_rope` computes the same expression)."""
        half = dim // 2
        freqs = 1.0 / (
            self.theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
        if self.factor == 1.0:
            return freqs
        low, high = self.correction_range(dim)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / (high - low if high != low else 0.001), 0.0, 1.0)
        return freqs / self.factor * ramp + freqs * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32
    d_model: int = 4096
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # MoE: >0 turns every `moe_every`-th MLP into an expert layer.
    num_experts: int = 0
    top_k: int = 2
    moe_every: int = 2
    # None: dropless (every chosen pair is computed).  A number caps each
    # expert at round(factor * tokens * top_k / experts) pairs; the pairs
    # past it keep their rows in the grouped matmuls and lose their weight.
    capacity_factor: Optional[float] = None
    # The router as published models state it: top-k weights renormalised
    # to sum to one or left as the softmax gave them (OLMoE: left); the
    # load-balance term counting each token's first choice or all top_k
    # (the HF ``load_balancing_loss_func``).
    norm_topk_prob: bool = True
    balance_all_k: bool = False
    # RMSNorm (gains ``q_norm``/``k_norm``) over the WHOLE q and k
    # projections, before the split into heads and before RoPE (OLMoE).
    qk_norm: bool = False
    # With ``qk_norm``: each head's q and each head's k normalised over its
    # OWN ``head_dim`` dims, one gain of ``head_dim`` for all query heads
    # and one for all key heads (LFM2's ``q_layernorm``/``k_layernorm``),
    # before RoPE as well.
    qk_norm_per_head: bool = False
    # Sliding-window attention (>0: each position attends the last
    # `sliding_window` positions only — Mistral-style long-context;
    # flash path only, kernels skip out-of-window blocks).  Where
    # ``layer_types`` names "window_attention" layers it is THEIR window and
    # the "attention" layers attend every earlier position; where it names
    # none, every attention layer has it.
    sliding_window: int = 0
    # The rotary table of a KIND of attention layer where it is not the
    # plain one at ``rope_theta``: ``{kind: Rotary}`` (kept as a sorted
    # tuple of pairs), kinds of :data:`ATTENTION_KINDS` that ``layer_types``
    # names.  The tables are then built once a step and kind
    # (:func:`forward_hidden`), not once a layer.  ``{kind: None}``: the
    # layers of that kind carry NO rotary position (:meth:`unrotated`; no
    # table is built for them) beside a kind that rotates — window layers
    # that rotate among full layers that do not.  It combines with
    # everything the plain q, k and v projections combine with
    # (``branch_norm``, ``attn_output_gate``, ``attn_head_dim``, the q/k
    # norms, experts); latent attention and ``rope`` False refuse it.
    rotary_by_kind: tuple = ()
    # Per-block rematerialization: save the residual stream at layer
    # boundaries and the flash kernel's output and log-sum-exp (of a
    # delta-rule layer its kernel's three outputs: ``forward_hidden``),
    # recompute the projections' and the MLP's internals in the backward
    # pass.  The flash kernel's two outputs cost as much to recompute as
    # compute and are small to keep: per block application
    # ``B*S*(D + H*Dv)`` bf16 and ``4*B*H*S`` bytes stay, twice what the
    # stream alone takes where ``H*Dv == D``.  Far better peak-HBM than
    # whole-loss remat policies, which either save every dot output
    # (``dots_saveable``) or re-run a forward whose own intermediates
    # still peak the same (``nothing_saveable``).
    remat_block: bool = False
    # A looped model (Ouro, arXiv:2510.25741): the whole layer stack runs
    # ``loop_passes`` times on the SAME weights; every pass ends in the
    # final norm, whose output is that pass's result and the next pass's
    # input.
    loop_passes: int = 1
    # Sandwich norm: an RMSNorm on each branch's OUTPUT too (gains
    # ``ln1_out`` / ``ln2_out``, plain gains initialised 1; scope
    # ``branch_norm`` inside the branch's own), before the residual add.  Of
    # a routed block it norms the combined result — the shared expert and
    # the pairs of the experts held here, so with ``experts_held`` a PARTIAL
    # sum.  It runs beside a looped stack, experts (``experts_held`` too),
    # block remat, ``attn_output_gate``, ``attn_head_dim`` and the q/k
    # norms; still refused beside it: ``one_branch``, ``norm_plus_one`` (the
    # output gains are stored plain) and ``partial_rotary_factor`` (never
    # run under it).
    branch_norm: bool = False
    # The exit gate of a looped model: ``sigmoid(z_t @ w + b)`` per token
    # and pass (``params["exit_gate"]``) makes a distribution over the
    # pass a token leaves at, and the loss is the expectation of the
    # passes' cross-entropies under it minus this weight times its
    # entropy (:func:`exit_distribution`, :func:`loss_fn`).  None: no gate.
    exit_gate_beta: Optional[float] = None
    # Latent attention (DeepSeek-V2/V3, GLM-4.7-Flash; ``kv_lora_rank`` > 0
    # turns it on): queries through a ``q_lora_rank``-wide normed latent,
    # keys and values out of ONE ``kv_lora_rank``-
    # wide normed latent a token, each head ``qk_nope_head_dim`` dims
    # without position + ``qk_rope_head_dim`` rotary dims whose key part is
    # one vector a token under every head.  The head size is their sum,
    # whatever ``d_model / n_head`` is; training expands keys and values per
    # head, so the flash kernels see plain MHA, with values ``v_head_dim``
    # wide whatever that sum is (:func:`_mla_qkv`).  ``q_lora_rank`` 0: the
    # queries come from ONE matrix (``wq``), no latent and no norm of their
    # own (Kimi Linear).  With ``rope`` False neither part is rotated: the
    # ``qk_rope_head_dim`` dims stay the token's one shared key part and
    # carry no position.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The routed layout past the stride: the first ``first_k_dense`` layers
    # stay dense (width ``d_ff``) whatever ``moe_every`` says; an expert is
    # ``d_ff_expert`` wide (0: ``d_ff``); ``n_shared_experts`` > 0 adds one
    # SwiGLU of ``n_shared_experts * d_ff_expert`` that every token runs
    # (``moe["shared"]``).
    first_k_dense: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    # The router's scores: "softmax" over the experts, or "sigmoid" of each
    # logit (DeepSeek-V3 ``noaux_tc``: the top-k weights are the chosen
    # scores over their sum + ``router_norm_eps`` where ``norm_topk_prob``;
    # LFM2 states 1e-6).  Either way times ``routed_scaling``.
    router_score: str = "softmax"
    routed_scaling: float = 1.0
    router_norm_eps: float = 1e-20
    # A selection bias per expert (``moe["router_bias"]``, float32 [E]): it
    # is added to the scores for the top-k CHOICE and never to a weight,
    # takes no gradient, and is moved by a rule after every step,
    # ``b_e += rate * sign(mean(c) - c_e)`` with ``c_e`` the pairs the step
    # routed to expert e (:func:`loss_fn` hands the new values out under
    # :data:`RULE_UPDATES`; :func:`rule_leaves` names the leaves).  None:
    # no bias.
    router_bias_rate: Optional[float] = None
    # The load-balance term per SEQUENCE (DeepSeek-V3 eq. 17-20) in place of
    # the batch-wide one: ``mean_b sum_e f_be * P_be``, ``f_be = E / (K S)
    # * #{t: e in T_t}``, ``P_be = mean_t s_te / sum_e' s_te'``.
    balance_per_sequence: bool = False
    # The chip's SHARE of the experts (expert parallelism cut to one chip):
    # the router knows ``num_experts``, this layer holds the
    # ``experts_held`` of them that start at ``experts_held_first`` (0: all)
    # and computes the pairs routed to those; what the absent experts would
    # add is left out of the layer's result.  No pair routed to a held
    # expert is dropped, whatever the imbalance.
    experts_held: int = 0
    experts_held_first: int = 0
    # Multi-token prediction (DeepSeek-V3 section 2.2; 0 or 1): one further
    # block with weights of its own (``params["mtp"]``) over
    # ``w_eh [norm(embed(t_{i+1})); norm(z_i)]``, ``z`` the last layer's
    # output, predicts ``t_{i+2}`` through the shared head
    # (:func:`forward_hidden`, :func:`loss_fn`).
    mtp_layers: int = 0
    # The kind of each layer's MIXER, "attention", "mamba", "conv" or
    # "linear_attention" (a tuple of ``n_layer`` names; empty: every layer
    # is an attention layer), under the same pre-norm and residual add;
    # which MLP follows (dense or routed) is :meth:`is_moe_layer`'s,
    # whatever the mixer.  A "conv" layer's mixer is LFM2's
    # double-gated short convolution (:func:`_conv_mixer`): ``[B | C | X]
    # = u in_proj``, a causal depthwise convolution of ``conv_taps`` taps
    # over ``B * X``, times ``C``, ``out_proj``; no bias, no activation.  A
    # "mamba" layer's mixer is the Mamba-2 one (:func:`_ssm_mixer`,
    # ``ops.ssd``): ``mamba_n_heads`` heads of ``mamba_d_head`` — together
    # the mixer's inner width (:attr:`mamba_d_inner`), whatever
    # ``mamba_expand * d_model`` is: ``mamba_expand`` is the source's name
    # for their ratio where it holds (Granite: 2, exact; Nemotron-H states 2
    # and reads heads x head size) and nothing here reads it —, a state of
    # ``mamba_d_state`` per head dim, B, C and the gated norm in
    # ``mamba_n_groups`` groups,
    # a causal depthwise convolution ``mamba_d_conv`` wide (with a bias
    # where ``mamba_conv_bias``) and chunks of ``mamba_chunk_size``
    # positions.  ``mamba_proj_bias`` must stay False: the two projections
    # have no bias here.
    layer_types: tuple = ()
    conv_taps: int = 3
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # False: attention without rotary position (``position_embedding_type:
    # "nope"``) in EVERY attention layer, latent ones too.  A model of which
    # only one kind of layer is without keeps this True and says which in
    # ``rotary_by_kind`` (``{kind: None}``).
    rope: bool = True
    # The softmax scale of attention; None: ``1 / sqrt(head_dim)``.
    attention_multiplier: Optional[float] = None
    # Scalars on the stream (Granite): the embedding's rows times
    # ``embedding_multiplier``, every branch added as ``x +
    # residual_multiplier * branch(norm(x))``, the logits divided by
    # ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The head reads ``embed`` transposed: ONE leaf, no ``lm_head``, and
    # the gradient of ``embed`` is the sum of both uses.
    tie_word_embeddings: bool = False
    # A "linear_attention" layer's mixer is the Gated DeltaNet one
    # (:func:`_gdn_mixer`, ``ops.gated_delta``): ``gdn_k_heads`` key heads
    # and ``gdn_v_heads`` value heads (a multiple; each key head serves
    # ``gdn_v_heads / gdn_k_heads`` of them) of ``gdn_d_head`` dims, behind
    # a causal depthwise convolution of ``gdn_d_conv`` taps without bias.
    # Experts may follow it, as they may a "conv" mixer.
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_d_head: int = 0
    gdn_d_conv: int = 4
    # A "kda" layer's mixer is Kimi Delta Attention (:func:`_kda_mixer`,
    # arXiv:2510.26692): ``kda_heads`` heads of ``kda_d_head`` dims, keys and
    # values alike, q, k and v each from a projection of its own behind a
    # causal depthwise convolution of ``kda_d_conv`` taps without bias, and
    # the delta rule with a decay per key CHANNEL
    # (``ops.gated_delta.gated_delta_chunked`` with ``g [B, S, H, D]``).  The
    # decay's and the output gate's low-rank projections are ``kda_d_head``
    # wide in the middle.
    kda_heads: int = 0
    kda_d_head: int = 0
    kda_d_conv: int = 4
    # The attention layers' head size where it is not ``d_model / n_head``
    # (0: it is; latent attention states its own).
    attn_head_dim: int = 0
    # A gate on the attention output: ``wq`` is twice as wide, each head's
    # columns ``[q | gate]``, and ``out = (attn * sigmoid(gate)) wo``, the
    # gate per element (scope ``attn_gate`` inside ``attention``).  With
    # ``attn_head_dim`` a setting of the plain q, k and v projections of a
    # stack that runs once: both run under two norms a block or four
    # (``branch_norm``), and are refused beside latent attention, the
    # prediction block and a looped stack.
    attn_output_gate: bool = False
    # The share of a head's dims that rotate, the FIRST ``head_dim *
    # partial_rotary_factor`` of them in pairs ``(j, j + half)``; the rest
    # carry no position.
    partial_rotary_factor: float = 1.0
    # Gains stored as ``w`` and applied as ``1 + w``, initialised 0: the
    # block's two norms, the final norm and the per-head q/k norms.
    norm_plus_one: bool = False
    # The shared expert behind a gate of its own: ``sigmoid(h @ w_sg) *
    # Shared(h)``, ``w_sg [d_model, 1]`` (``moe["shared_gate"]``).
    shared_expert_gate: bool = False
    # Layers that are ONE branch each (Nemotron-H): ``x + branch(norm(x))``
    # with one norm a layer, the branch a mixer (the layer holds ``ln1`` and
    # the mixer's leaves, no MLP) or an MLP (``ln2`` and ``mlp`` or ``moe``,
    # no mixer).  ``layer_types`` then names, beside the mixers, the two MLP
    # kinds (:data:`MLP_KINDS`): "mlp" the dense one, "moe" the routed block,
    # which are the routed layers whatever ``moe_every`` and
    # ``first_k_dense`` say.
    one_branch: bool = False
    # The form of every MLP (:data:`MLP_FORMS`) — dense, expert and shared
    # expert: "swiglu", ``down(silu(gate x) * up x)`` (leaves ``w_gate``,
    # ``w_up``, ``w_down``; experts ``wg``, ``wi``, ``wo``), or "relu2",
    # ``down(relu(up x)^2)``, TWO matrices and no gate leaf.
    mlp_form: str = "swiglu"
    # A "mamba1" layer's mixer is the Mamba-1 one (:func:`_s6_mixer`,
    # ``ops.selective_scan``): ``s6_d_inner`` channels, each with a state of
    # ``s6_d_state`` numbers that decays at a rate of ITS OWN (``A [d_inner,
    # d_state]``), the step ``dt`` a channel out of a projection of rank
    # ``s6_dt_rank``, B and C shared by all channels, behind a causal
    # depthwise convolution of ``s6_d_conv`` taps with a bias.  The sizes are
    # its own: ``mamba_n_heads`` / ``mamba_d_head`` mean Mamba-2.
    s6_d_inner: int = 0
    s6_d_state: int = 16
    s6_d_conv: int = 4
    s6_dt_rank: int = 0
    # What crosses layers (SambaY, arXiv:2507.06607), by layer index.  The
    # scan output ``y`` of layer ``memory_layer`` (a "mamba1" layer; with its
    # ``D`` skip, before its gate) is the MEMORY that every later "gmu" layer
    # reads: ``(memory * silu(u in_proj)) out_proj``.  The keys and values of
    # layer ``shared_kv_layer`` (an "attention" or "window_attention" layer;
    # as its flash call takes them) are what every later "cross_attention"
    # layer attends, causally over every earlier position, with queries,
    # ``wo`` and differential leaves of its own.  :func:`forward_hidden`
    # carries both from block to block; under ``remat_block`` they are
    # outputs of the block that makes them and inputs of the blocks that read
    # them.  A "gmu" layer at or before ``memory_layer``, a "cross_attention"
    # layer at or before ``shared_kv_layer``, and either beside ``loop_passes
    # > 1``, ``one_branch`` or ``mtp_layers`` are refused.
    memory_layer: Optional[int] = None
    shared_kv_layer: Optional[int] = None
    # Differential attention (arXiv:2410.05258) in EVERY attention layer of
    # any kind; the tuple holds ``lambda_init`` of each layer (``n_layer``
    # floats; a layer that is no attention layer's is read by nobody), which
    # a model states by its PUBLISHED layer index: ``0.8 - 0.6 exp(-0.3 l)``.
    # Query heads ``(2p, 2p + 1)`` are a pair, key heads ``(2r, 2r + 1)``
    # too, value heads ``(2r, 2r + 1)`` are joined to one of twice the
    # width, and query pair ``p`` reads key/value pair ``p // (n_head /
    # n_kv_head)``: ``o = (softmax(q1 k1^T) - lambda softmax(q2 k2^T)) v``,
    # ``lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) +
    # lambda_init`` (four leaves of ``head_dim``), then an RMSNorm per pair
    # over the ``2 head_dim`` (gain ``subln``) times ``1 - lambda_init``.
    # Both softmaxes run through ONE flash call, heads reordered so that its
    # GQA map holds (scope ``attn_diff`` holds the rest).  Refused beside
    # latent attention, ``attn_output_gate`` and odd head counts.
    diff_attention: tuple = ()
    # The form of the block's two norms and the final norm
    # (:data:`NORM_FORMS`): "layernorm" subtracts the mean and adds a bias,
    # and ``ln1``, ``ln2`` and ``ln_f`` then hold ``{"gain", "bias"}``.
    # ``attn_bias``: a bias on each of the attention projections (``bq``,
    # ``bk``, ``bv``, ``bo``).  Both are refused beside ``branch_norm``,
    # ``norm_plus_one``, latent attention, a looped stack and the prediction
    # block: they have never run beside them.
    norm_form: str = "rmsnorm"
    attn_bias: bool = False

    def __post_init__(self):
        if (self.loop_passes > 1) != (self.exit_gate_beta is not None):
            raise ValueError(
                f"LlamaConfig: loop_passes={self.loop_passes} with "
                f"exit_gate_beta={self.exit_gate_beta}: a looped model's "
                "loss is the expectation over its exit gate, and a gate "
                "needs more than one pass to choose from")
        if self.loop_passes > 1 and self.num_experts > 0:
            raise ValueError(
                f"LlamaConfig: loop_passes={self.loop_passes} with "
                f"num_experts={self.num_experts}: the routed block's "
                "counters are kept per layer, not per (pass, layer)")
        if self.kv_lora_rank > 0:
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim) <= 0 or self.qk_rope_head_dim % 2 or (
                       self.q_lora_rank < 0):
                raise ValueError(
                    f"LlamaConfig: kv_lora_rank={self.kv_lora_rank} needs "
                    "q_lora_rank >= 0 (0: one query matrix), "
                    "qk_nope_head_dim > 0, an even qk_rope_head_dim > 0 and "
                    f"v_head_dim > 0, not {self.q_lora_rank}, "
                    f"{self.qk_nope_head_dim}, {self.qk_rope_head_dim} and "
                    f"{self.v_head_dim}")
            if self.n_kv_head != self.n_head or self.qk_norm:
                raise ValueError(
                    f"LlamaConfig: kv_lora_rank={self.kv_lora_rank} with "
                    f"n_kv_head={self.n_kv_head} (of {self.n_head}) or "
                    f"qk_norm={self.qk_norm}: latent attention expands one "
                    "key and one value per query head and norms its latents")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"LlamaConfig: router_score={self.router_score!r} is "
                "neither 'softmax' nor 'sigmoid'")
        if not (0 <= self.experts_held_first
                and self.experts_held_first + self.experts_held
                <= max(self.num_experts, 0)) or self.experts_held < 0:
            raise ValueError(
                f"LlamaConfig: experts_held={self.experts_held} from "
                f"experts_held_first={self.experts_held_first} is no slice "
                f"of num_experts={self.num_experts}")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and self.loop_passes > 1):
            raise ValueError(
                f"LlamaConfig: mtp_layers={self.mtp_layers} with "
                f"loop_passes={self.loop_passes}: one prediction block "
                "after a stack that runs once is what is built")

        if self.qk_norm_per_head and (
                not self.qk_norm or self.kv_lora_rank > 0):
            raise ValueError(
                f"LlamaConfig: qk_norm_per_head={self.qk_norm_per_head} "
                f"with qk_norm={self.qk_norm} and kv_lora_rank="
                f"{self.kv_lora_rank}: it is the form of the q/k norm of "
                "plain q and k projections, and says nothing without one")

        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_types
        names = tuple(MIXER_KINDS) + (MLP_KINDS if self.one_branch else ())
        if (kinds or self.one_branch) and (
                len(kinds) != self.n_layer or set(kinds) - set(names)):
            raise ValueError(
                f"LlamaConfig: layer_types={kinds} is not n_layer="
                f"{self.n_layer} names out of {names} (the MLP kinds "
                f"{MLP_KINDS} name a layer's only branch, under one_branch)")
        if self.mlp_form not in MLP_FORMS:
            raise ValueError(
                f"LlamaConfig: mlp_form={self.mlp_form!r} is none of "
                f"{MLP_FORMS}")
        if self.window_layers and (
                self.sliding_window <= 0 or self.kv_lora_rank > 0):
            raise ValueError(
                f"LlamaConfig: 'window_attention' layers with "
                f"sliding_window={self.sliding_window} or kv_lora_rank="
                f"{self.kv_lora_rank}: the kind attends the last "
                "sliding_window > 0 positions through the plain q, k and v "
                "projections, not latent attention's")
        by_kind = self.rotary_by_kind
        object.__setattr__(self, "rotary_by_kind", tuple(sorted(
            by_kind.items() if isinstance(by_kind, dict) else by_kind)))
        for kind, rotary in self.rotary_by_kind:
            if (kind not in ATTENTION_KINDS or not self.layers_of(kind)
                    or not self.rope or self.kv_lora_rank > 0):
                raise ValueError(
                    f"LlamaConfig: rotary_by_kind names {kind!r} with "
                    f"layer_types={kinds}, rope={self.rope} and "
                    f"kv_lora_rank={self.kv_lora_rank}: a rotary table "
                    f"belongs to a kind of {tuple(ATTENTION_KINDS)} that "
                    "the model has a layer of, under rotary position on "
                    "the plain q and k projections")
            if rotary is None:  # the kind carries no rotary position
                continue
            if not isinstance(rotary, Rotary) or rotary.factor < 1.0 or (
                    rotary.factor != 1.0
                    and rotary.original_max_position_embeddings <= 0):
                raise ValueError(
                    f"LlamaConfig: rotary_by_kind[{kind!r}]={rotary!r} is "
                    "no Rotary with factor >= 1 and, where it scales, "
                    "original_max_position_embeddings > 0, nor None (no "
                    "rotary position)")
        if self.one_branch and (
                ("moe" in kinds) != (self.num_experts > 0)
                or self.loop_passes > 1 or self.mtp_layers
                or self.branch_norm):
            raise ValueError(
                f"LlamaConfig: one_branch with layer_types={kinds}, "
                f"num_experts={self.num_experts}, loop_passes="
                f"{self.loop_passes}, mtp_layers={self.mtp_layers} or "
                f"branch_norm={self.branch_norm}: the routed layers are the "
                "'moe' entries (some, if there are experts; none, if not), "
                "the stack runs once, and the prediction block and the "
                "sandwich norms are built for layers of two branches")
        if self.conv_layers and (
                self.conv_taps <= 0 or self.loop_passes > 1
                or self.mtp_layers):
            raise ValueError(
                f"LlamaConfig: 'conv' layers with conv_taps="
                f"{self.conv_taps}, loop_passes={self.loop_passes} or "
                f"mtp_layers={self.mtp_layers}: the convolution has at "
                "least one tap and the stack runs once, with no prediction "
                "block")
        if self.ssm_layers:
            if (min(self.mamba_n_heads, self.mamba_d_head,
                    self.mamba_d_state, self.mamba_d_conv,
                    self.mamba_chunk_size, self.mamba_n_groups) <= 0
                    or self.mamba_n_heads % self.mamba_n_groups
                    or self.mamba_proj_bias):
                raise ValueError(
                    f"LlamaConfig: a 'mamba' layer needs positive "
                    f"mamba_n_heads x mamba_d_head ({self.mamba_n_heads} x "
                    f"{self.mamba_d_head}: the mixer's inner width, "
                    "whatever mamba_expand x d_model is), heads that "
                    f"mamba_n_groups={self.mamba_n_groups} divides, "
                    "positive mamba_d_state, mamba_d_conv and "
                    "mamba_chunk_size, and mamba_proj_bias False")
            if self.loop_passes > 1 or self.mtp_layers:
                raise ValueError(
                    f"LlamaConfig: 'mamba' layers with loop_passes="
                    f"{self.loop_passes} or mtp_layers={self.mtp_layers}: "
                    "the stack runs once, with no prediction block "
                    "(experts beside a 'mamba' mixer are built, as beside "
                    "every other kind)")
        if self.gdn_layers and (
                min(self.gdn_k_heads, self.gdn_d_head, self.gdn_d_conv) <= 0
                or self.gdn_v_heads % max(self.gdn_k_heads, 1)
                or self.gdn_v_heads <= 0 or self.loop_passes > 1
                or self.mtp_layers):
            raise ValueError(
                f"LlamaConfig: 'linear_attention' layers with gdn_k_heads="
                f"{self.gdn_k_heads}, gdn_v_heads={self.gdn_v_heads}, "
                f"gdn_d_head={self.gdn_d_head}, gdn_d_conv="
                f"{self.gdn_d_conv}, loop_passes={self.loop_passes} or "
                f"mtp_layers={self.mtp_layers}: the value heads are a "
                "positive multiple of the key heads, the sizes positive, "
                "and the stack runs once, with no prediction block")
        if self.kda_layers and (
                min(self.kda_heads, self.kda_d_head, self.kda_d_conv) <= 0
                or self.loop_passes > 1 or self.mtp_layers
                or self.one_branch):
            raise ValueError(
                f"LlamaConfig: 'kda' layers with kda_heads={self.kda_heads}, "
                f"kda_d_head={self.kda_d_head}, kda_d_conv={self.kda_d_conv}, "
                f"loop_passes={self.loop_passes}, mtp_layers="
                f"{self.mtp_layers} or one_branch={self.one_branch}: the "
                "sizes are positive and the stack runs once on layers of two "
                "branches, with no prediction block")
        rotary = self.head_dim * self.partial_rotary_factor
        if self.partial_rotary_factor != 1.0 and (
                not 0 < rotary < self.head_dim or rotary % 2):
            raise ValueError(
                f"LlamaConfig: partial_rotary_factor="
                f"{self.partial_rotary_factor} of head_dim={self.head_dim}: "
                "the rotary dims are an even number of a head's dims")
        # settings of the plain q, k and v projections of a stack that runs
        # once; the two that touch the norms or were never run under four
        # norms a block are refused beside ``branch_norm`` as well
        once = (self.kv_lora_rank == 0 and not self.mtp_layers
                and self.loop_passes == 1)
        for name, off in (("attn_head_dim", 0), ("attn_output_gate", False)):
            if getattr(self, name) != off and not once:
                raise ValueError(
                    f"LlamaConfig: {name}={getattr(self, name)!r} with "
                    f"kv_lora_rank={self.kv_lora_rank}, mtp_layers="
                    f"{self.mtp_layers} or loop_passes={self.loop_passes}: "
                    "it is a setting of the plain q, k and v projections "
                    "and of a stack that runs once (latent attention states "
                    "its own head size and splits no gate off its queries; "
                    "the prediction block and a looped stack have not run "
                    "with it)")
        for name, off in (("partial_rotary_factor", 1.0),
                          ("norm_plus_one", False)):
            if getattr(self, name) != off and (self.branch_norm or not once):
                raise ValueError(
                    f"LlamaConfig: {name}={getattr(self, name)!r} with "
                    f"kv_lora_rank={self.kv_lora_rank}, mtp_layers="
                    f"{self.mtp_layers}, loop_passes={self.loop_passes} or "
                    f"branch_norm={self.branch_norm}: it is a setting of "
                    "the plain q, k and v projections and of a stack that "
                    "runs once with two norms a block (the output norms' "
                    "gains are stored plain, and no rotation of a part of a "
                    "head has run under them)")
        if self.shared_expert_gate and self.n_shared_experts <= 0:
            raise ValueError(
                "LlamaConfig: shared_expert_gate with n_shared_experts="
                f"{self.n_shared_experts}: there is no shared expert to "
                "gate")
        if self.s6_layers and min(
                self.s6_d_inner, self.s6_d_state, self.s6_d_conv,
                self.s6_dt_rank) <= 0:
            raise ValueError(
                f"LlamaConfig: a 'mamba1' layer needs positive s6_d_inner, "
                f"s6_d_state, s6_d_conv and s6_dt_rank, not "
                f"{self.s6_d_inner}, {self.s6_d_state}, {self.s6_d_conv} and "
                f"{self.s6_dt_rank} (mamba_n_heads and mamba_d_head are "
                "Mamba-2's)")
        crossing = (self.s6_layers or self.gmu_layers or self.cross_layers
                    or self.memory_layer is not None
                    or self.shared_kv_layer is not None)
        if crossing and (self.loop_passes > 1 or self.one_branch
                         or self.mtp_layers):
            raise ValueError(
                f"LlamaConfig: 'mamba1', 'gmu' or 'cross_attention' layers, "
                f"memory_layer={self.memory_layer} or shared_kv_layer="
                f"{self.shared_kv_layer} with loop_passes={self.loop_passes}, "
                f"one_branch={self.one_branch} or mtp_layers="
                f"{self.mtp_layers}: what crosses layers is carried through "
                "a stack of two-branch layers that runs once, with no "
                "prediction block")
        for setting, maker, reader in (
                ("memory_layer", ("mamba1",), "gmu"),
                ("shared_kv_layer", ("attention", "window_attention"),
                 "cross_attention")):
            made = getattr(self, setting)
            readers = [i for i in range(self.n_layer)
                       if self.mixer_kind(i) == reader]
            if made is None and not readers:
                continue
            if (made is None or not 0 <= made < self.n_layer
                    or self.mixer_kind(made) not in maker
                    or any(i <= made for i in readers)):
                raise ValueError(
                    f"LlamaConfig: {setting}={made} with {reader!r} layers "
                    f"{readers} and layer_types={kinds}: {setting} names a "
                    f"layer of {maker}, and every {reader!r} layer comes "
                    "after it (it reads what that layer made)")
        object.__setattr__(self, "diff_attention", tuple(
            float(x) for x in self.diff_attention))
        if self.diff_attention and (
                len(self.diff_attention) != self.n_layer
                or self.kv_lora_rank > 0 or self.attn_output_gate
                or self.n_head % 2 or self.n_kv_head % 2):
            raise ValueError(
                f"LlamaConfig: diff_attention of {len(self.diff_attention)} "
                f"entries with n_layer={self.n_layer}, kv_lora_rank="
                f"{self.kv_lora_rank}, attn_output_gate="
                f"{self.attn_output_gate}, n_head={self.n_head} and "
                f"n_kv_head={self.n_kv_head}: it holds lambda_init of every "
                "layer and pairs the heads of the plain q, k and v "
                "projections (even counts), without latent attention or the "
                "output gate")
        if self.norm_form not in NORM_FORMS:
            raise ValueError(
                f"LlamaConfig: norm_form={self.norm_form!r} is none of "
                f"{NORM_FORMS}")
        for name, off in (("norm_form", "rmsnorm"), ("attn_bias", False)):
            if getattr(self, name) != off and (
                    self.branch_norm or self.norm_plus_one
                    or self.kv_lora_rank > 0 or self.loop_passes > 1
                    or self.mtp_layers):
                raise ValueError(
                    f"LlamaConfig: {name}={getattr(self, name)!r} with "
                    f"branch_norm={self.branch_norm}, norm_plus_one="
                    f"{self.norm_plus_one}, kv_lora_rank={self.kv_lora_rank}, "
                    f"loop_passes={self.loop_passes} or mtp_layers="
                    f"{self.mtp_layers}: it has never run beside a norm on "
                    "each branch's output, gains stored as 1 + w, latent "
                    "attention, a looped stack or the prediction block")

    def mixer_kind(self, i: int) -> Optional[str]:
        """Layer ``i``'s mixer: one of :data:`MIXER_KINDS`, or None where
        the layer's one branch is an MLP (``one_branch``)."""
        kind = self.layer_types[i] if self.layer_types else "attention"
        return None if kind in MLP_KINDS else kind

    def mlp_routed(self, i: int) -> Optional[bool]:
        """Layer ``i``'s MLP: routed (True), dense (False), or None where
        the layer's one branch is a mixer (``one_branch``)."""
        if self.one_branch and self.mixer_kind(i) is not None:
            return None
        return self.is_moe_layer(i)

    def layers_of(self, kind: str) -> int:
        """Layers whose mixer is of ``kind`` (of :data:`MIXER_KINDS`)."""
        return sum(self.mixer_kind(i) == kind for i in range(self.n_layer))

    @property
    def ssm_layers(self) -> int:
        """Layers whose mixer is the state-space one."""
        return self.layers_of("mamba")

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is the gated short convolution."""
        return self.layers_of("conv")

    @property
    def gdn_layers(self) -> int:
        """Layers whose mixer is the gated delta rule."""
        return self.layers_of("linear_attention")

    @property
    def kda_layers(self) -> int:
        """Layers whose mixer is the delta rule with a per-channel decay."""
        return self.layers_of("kda")

    @property
    def s6_layers(self) -> int:
        """Layers whose mixer is the Mamba-1 one (the selective scan)."""
        return self.layers_of("mamba1")

    @property
    def gmu_layers(self) -> int:
        """Layers whose mixer is the Gated Memory Unit."""
        return self.layers_of("gmu")

    @property
    def cross_layers(self) -> int:
        """Layers of the "cross_attention" kind."""
        return self.layers_of("cross_attention")

    @property
    def attention_layers(self) -> int:
        """Layers whose mixer is attention, of any kind."""
        return sum(self.layers_of(kind) for kind in ATTENTION_KINDS)

    @property
    def window_layers(self) -> int:
        """Layers of the "window_attention" kind."""
        return self.layers_of("window_attention")

    def window_of(self, kind: str) -> int:
        """The window a layer of an attention ``kind`` attends (0: every
        earlier position): ``sliding_window`` for the "window_attention"
        kind and, where the model has no layer of that kind, for all."""
        if kind == "window_attention" or not self.window_layers:
            return self.sliding_window
        return 0

    def unrotated(self, kind: str) -> bool:
        """Whether the layers of an attention ``kind`` carry no rotary
        position: all of them where ``rope`` is False, else the kinds that
        ``rotary_by_kind`` maps to None."""
        return not self.rope or (kind, None) in self.rotary_by_kind

    @property
    def unrotated_layers(self) -> int:
        """Attention layers without rotary position, of either kind."""
        return sum(self.layers_of(kind) for kind in ATTENTION_KINDS
                   if self.unrotated(kind))

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of the delta rule's convolution: q, k and v side by
        side."""
        return (2 * self.gdn_k_heads + self.gdn_v_heads) * self.gdn_d_head

    @property
    def rotary_dim(self) -> int:
        """The dims of a head that rotate (the first ones)."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the convolution: x, B and C side by side."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank > 0:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.n_head

    @property
    def value_head_dim(self) -> int:
        """The width of a head's values, and of its share of ``wo``'s rows:
        latent attention states its own, every other model's is the
        head's."""
        return self.v_head_dim if self.kv_lora_rank > 0 else self.head_dim

    @property
    def block_applications(self) -> int:
        """Attention blocks a token passes through: the attention layers x
        passes, and the multi-token-prediction block."""
        return self.attention_layers * self.loop_passes + self.mtp_layers

    @property
    def expert_width(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def experts_here(self) -> int:
        """Experts whose weights this layer holds."""
        return self.experts_held or self.num_experts

    def is_moe_layer(self, i: int) -> bool:
        """Single source of truth for MoE placement (init_params,
        param_logical_axes must agree)."""
        if self.one_branch:
            return self.layer_types[i] == "moe"
        return self.num_experts > 0 and i >= self.first_k_dense and (
            i % self.moe_every == self.moe_every - 1
        )

    @property
    def moe_layers(self) -> int:
        """Layers whose MLP is the routed block."""
        return sum(self.is_moe_layer(i) for i in range(self.n_layer))

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def tiny(cls, **over) -> "LlamaConfig":
        base = dict(
            vocab_size=256, n_layer=2, n_head=4, n_kv_head=2, d_model=64,
            d_ff=128, max_seq_len=128,
        )
        base.update(over)
        return cls(**base)

    @classmethod
    def small_300m(cls) -> "LlamaConfig":
        return cls(
            vocab_size=32000, n_layer=12, n_head=16, n_kv_head=16,
            d_model=1024, d_ff=2816, max_seq_len=2048,
        )

    @classmethod
    def medium_800m(cls) -> "LlamaConfig":
        """~780M params: d_model 1536 keeps matmuls MXU-sized (the 300M
        config's 1024-wide GEMMs leave systolic-array lanes idle)."""
        return cls(
            vocab_size=32000, n_layer=24, n_head=16, n_kv_head=16,
            d_model=1536, d_ff=4096, max_seq_len=2048,
        )


#: the four vectors of a differential attention layer's ``lambda``
_LAMBDA_LEAVES = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _dense(key, fan_in, fan_out, std=0.02):
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) * std


def _init_ssm(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A Mamba-2 mixer's parameters as the reference implementation draws
    them: projections N(0, 0.02); the convolution PyTorch's ``Conv1d``
    default, uniform in +-1/sqrt(``mamba_d_conv``), stored ``[taps,
    channels]``; ``A_log = log(1..H)``; ``D`` and the gated norm's gain 1;
    ``dt_bias`` the inverse softplus of a log-uniform draw in [1e-3,
    1e-1]."""
    k = jax.random.split(key, 5)
    H, inner, conv = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    bound = cfg.mamba_d_conv ** -0.5
    dt = jnp.exp(jax.random.uniform(
        k[3], (H,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    ssm = {
        "in_proj": _dense(k[0], cfg.d_model, inner + conv + H),
        "conv_w": jax.random.uniform(
            k[1], (cfg.mamba_d_conv, conv), jnp.float32, -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((inner,), jnp.float32),
        "out_proj": _dense(k[4], inner, cfg.d_model),
    }
    if cfg.mamba_conv_bias:
        ssm["conv_b"] = jax.random.uniform(
            k[2], (conv,), jnp.float32, -bound, bound)
    return ssm


def _init_conv(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A gated short convolution's parameters: projections N(0, 0.02), the
    taps PyTorch's ``Conv1d`` default as :func:`_init_ssm` draws them,
    stored ``[taps, channels]``."""
    k = jax.random.split(key, 3)
    bound = cfg.conv_taps ** -0.5
    return {
        "in_proj": _dense(k[0], cfg.d_model, 3 * cfg.d_model),
        "conv_w": jax.random.uniform(
            k[1], (cfg.conv_taps, cfg.d_model), jnp.float32, -bound, bound),
        "out_proj": _dense(k[2], cfg.d_model, cfg.d_model),
    }


def _init_gdn(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A Gated DeltaNet mixer's parameters: projections N(0, 0.02); the
    convolution PyTorch's ``Conv1d`` default as :func:`_init_ssm` draws it,
    stored ``[taps, channels]``; ``A_log = log U(0, 16)``; ``dt_bias`` and
    the gated norm's gain 1."""
    k = jax.random.split(key, 5)
    hk, hv, d = cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_d_head
    bound = cfg.gdn_d_conv ** -0.5
    return {
        # per KEY head [q | k | v of its value heads | z of them]
        "in_proj_qkvz": _dense(k[0], cfg.d_model, 2 * (hk + hv) * d),
        # per key head [b of its value heads | a of them]
        "in_proj_ba": _dense(k[1], cfg.d_model, 2 * hv),
        "conv_w": jax.random.uniform(
            k[2], (cfg.gdn_d_conv, cfg.gdn_conv_dim), jnp.float32,
            -bound, bound),
        "dt_bias": jnp.ones((hv,), jnp.float32),
        "A_log": jnp.log(jax.random.uniform(
            k[3], (hv,), jnp.float32, 1e-6, 16.0)),
        "norm": jnp.ones((d,), jnp.float32),
        "out_proj": _dense(k[4], hv * d, cfg.d_model),
    }


def _init_kda(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A Kimi Delta Attention mixer's parameters: projections N(0, 0.02),
    the output gate's bias 0; the three convolutions PyTorch's ``Conv1d``
    default as :func:`_init_ssm` draws them, stored ``[taps, channels]``;
    ``A_log = log U(1, 16)`` a head; ``dt_bias`` a channel, the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1] (as :func:`_init_ssm`'s);
    the gated norm's gain 1."""
    k = jax.random.split(key, 14)
    H, D, C = cfg.kda_heads, cfg.kda_d_head, cfg.d_model
    bound = cfg.kda_d_conv ** -0.5
    taps = lambda key: jax.random.uniform(  # noqa: E731
        key, (cfg.kda_d_conv, H * D), jnp.float32, -bound, bound)
    dt = jnp.exp(jax.random.uniform(
        k[12], (H * D,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "wq": _dense(k[0], C, H * D), "wk": _dense(k[1], C, H * D),
        "wv": _dense(k[2], C, H * D),
        "conv_q": taps(k[3]), "conv_k": taps(k[4]), "conv_v": taps(k[5]),
        # the decay's gate, low rank: f_b(f_a(x)) + dt_bias, a key channel
        "f_a": _dense(k[6], C, D), "f_b": _dense(k[7], D, H * D),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            k[13], (H,), jnp.float32, 1.0, 16.0)),
        "w_beta": _dense(k[8], C, H),
        # the output gate, low rank with a bias: g_b(g_a(x)) + g_bias
        "g_a": _dense(k[9], C, D), "g_b": _dense(k[10], D, H * D),
        "g_bias": jnp.zeros((H * D,), jnp.float32),
        "norm": jnp.ones((D,), jnp.float32),
        "out_proj": _dense(k[11], H * D, C),
    }


def _init_s6(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A Mamba-1 mixer's parameters as the reference implementation draws
    them: ``in_proj``, ``x_proj`` and ``out_proj`` N(0, 0.02); the
    convolution and its bias PyTorch's ``Conv1d`` default as
    :func:`_init_ssm` draws them, stored ``[taps, channels]``; ``dt_proj``
    uniform in +-``s6_dt_rank^-1/2``; ``dt_bias`` the inverse softplus of a
    log-uniform draw in [1e-3, 1e-1]; ``A_log = log(1..N)`` in every channel;
    ``D`` 1."""
    k = jax.random.split(key, 7)
    inner, N, R = cfg.s6_d_inner, cfg.s6_d_state, cfg.s6_dt_rank
    bound = cfg.s6_d_conv ** -0.5
    dt = jnp.exp(jax.random.uniform(
        k[5], (inner,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        # [x | z]
        "in_proj": _dense(k[0], cfg.d_model, 2 * inner),
        "conv_w": jax.random.uniform(
            k[1], (cfg.s6_d_conv, inner), jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(
            k[2], (inner,), jnp.float32, -bound, bound),
        # [delta | B | C]
        "x_proj": _dense(k[3], inner, R + 2 * N),
        "dt_proj": jax.random.uniform(
            k[4], (R, inner), jnp.float32, -R ** -0.5, R ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (inner, N)),
        "D": jnp.ones((inner,), jnp.float32),
        "out_proj": _dense(k[6], inner, cfg.d_model),
    }


def _init_gmu(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """A Gated Memory Unit's two projections, N(0, 0.02); it is as wide as
    the memory it reads (``s6_d_inner``)."""
    k = jax.random.split(key, 2)
    return {"in_proj": _dense(k[0], cfg.d_model, cfg.s6_d_inner),
            "out_proj": _dense(k[1], cfg.s6_d_inner, cfg.d_model)}


def _gain(w, cfg: "LlamaConfig"):
    """A norm's gain as applied: the leaf, or ``1 + w`` where
    ``cfg.norm_plus_one``."""
    return w + 1.0 if cfg.norm_plus_one else w


def _gain_leaf(width: int, cfg: "LlamaConfig"):
    """A gain of 1 as its leaf is stored: ones, or zeros where
    ``cfg.norm_plus_one``."""
    return (jnp.zeros if cfg.norm_plus_one else jnp.ones)(
        (width,), jnp.float32)


def _norm_leaf(width: int, cfg: "LlamaConfig"):
    """The leaf of one of the block's two norms or of the final norm at its
    initial values: a gain (:func:`_gain_leaf`), or ``{"gain", "bias"}`` where
    ``cfg.norm_form`` is "layernorm"."""
    if cfg.norm_form == "layernorm":
        return {"gain": jnp.ones((width,), jnp.float32),
                "bias": jnp.zeros((width,), jnp.float32)}
    return _gain_leaf(width, cfg)


def _norm(x, leaf, cfg: "LlamaConfig"):
    """One of the block's two norms or the final norm, in the form
    ``cfg.norm_form`` names: the RMSNorm kernel, or LayerNorm — the mean
    subtracted, over ``sqrt(var + rms_eps)``, gain and bias, in float32 and
    rounded once (an elementwise chain XLA fuses)."""
    if cfg.norm_form == "rmsnorm":
        return rmsnorm(x, _gain(leaf, cfg), eps=cfg.rms_eps)
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + cfg.rms_eps)
    return (x32 * inv * leaf["gain"] + leaf["bias"]).astype(x.dtype)


def _init_layer(key: jax.Array, cfg: LlamaConfig, routed: Optional[bool],
                mixer: Optional[str] = "attention") -> Dict:
    """One block's parameters: the mixer's (``mixer``, one of
    :data:`MIXER_KINDS`) and the MLP's (``routed`` or dense), chosen apart;
    a layer of ONE branch (``cfg.one_branch``) has no mixer half (``mixer``
    None: no ``ln1``) or no MLP half (``routed`` None: no ``ln2``).
    The leaves every earlier configuration has draw from the same eight
    keys as ever; what latent attention, the shared expert, a state-space
    mixer (``layer["ssm"]``) and a convolution mixer (``layer["conv"]``),
    a delta-rule mixer (``layer["gdn"]``; with a per-channel decay
    ``layer["kda"]``; each of the four in place of the
    attention leaves) and the shared expert's gate add draws from keys
    folded out of the layer's.  A gain is 1, or 0 where
    ``cfg.norm_plus_one``.  Where ``cfg.mlp_form`` is "relu2" an MLP has no
    gate leaf (``w_gate``; an expert's ``wg``): two matrices, drawn from the
    keys the three-matrix form draws its up and down ones from."""
    k = jax.random.split(key, 8)
    more = jax.random.split(jax.random.fold_in(key, 1), 5)
    hd = cfg.head_dim
    # (a Mamba-1 mixer ``layer["s6"]``, a Gated Memory Unit ``layer["gmu"]``,
    # the attention projections' biases and the differential leaves draw
    # from keys folded out of the layer's as well)
    attention = mixer in ATTENTION_KINDS
    gated = cfg.mlp_form == "swiglu"
    layer = {}
    if mixer is not None:
        layer["ln1"] = _norm_leaf(cfg.d_model, cfg)
    if mixer == "mamba":
        layer["ssm"] = _init_ssm(jax.random.fold_in(key, 2), cfg)
    elif mixer == "mamba1":
        layer["s6"] = _init_s6(jax.random.fold_in(key, 7), cfg)
    elif mixer == "gmu":
        layer["gmu"] = _init_gmu(jax.random.fold_in(key, 8), cfg)
    elif mixer == "conv":
        layer["conv"] = _init_conv(jax.random.fold_in(key, 3), cfg)
    elif mixer == "linear_attention":
        layer["gdn"] = _init_gdn(jax.random.fold_in(key, 4), cfg)
    elif mixer == "kda":
        layer["kda"] = _init_kda(jax.random.fold_in(key, 6), cfg)
    elif attention and cfg.kv_lora_rank > 0:
        if cfg.q_lora_rank > 0:
            layer["wq_a"] = _dense(k[0], cfg.d_model, cfg.q_lora_rank)
            layer["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
            layer["wq_b"] = _dense(more[0], cfg.q_lora_rank, cfg.n_head * hd)
        else:
            layer["wq"] = _dense(k[0], cfg.d_model, cfg.n_head * hd)
        layer["wkv_a"] = _dense(
            k[1], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        layer["kv_a_norm"] = jnp.ones((cfg.kv_lora_rank,), jnp.float32)
        layer["wkv_b"] = _dense(
            more[1], cfg.kv_lora_rank,
            cfg.n_head * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    elif attention:
        # with the output gate each head's columns are [q | gate]
        layer["wq"] = _dense(
            k[0], cfg.d_model,
            cfg.n_head * hd * (2 if cfg.attn_output_gate else 1))
        if mixer != "cross_attention":  # it reads another layer's
            layer["wk"] = _dense(k[1], cfg.d_model, cfg.n_kv_head * hd)
            layer["wv"] = _dense(k[2], cfg.d_model, cfg.n_kv_head * hd)
    if attention:
        layer["wo"] = _dense(
            k[3], cfg.n_head * cfg.value_head_dim, cfg.d_model)
    if attention and cfg.attn_bias:
        widths = {"bq": cfg.n_head * hd, "bk": cfg.n_kv_head * hd,
                  "bv": cfg.n_kv_head * hd, "bo": cfg.d_model}
        for name, width in widths.items():
            if "w" + name[1] in layer:
                layer[name] = jnp.zeros((width,), jnp.float32)
    if attention and cfg.diff_attention:
        lam = jax.random.split(jax.random.fold_in(key, 9), 4)
        for name, lam_key in zip(_LAMBDA_LEAVES, lam):
            layer[name] = 0.1 * jax.random.normal(lam_key, (hd,), jnp.float32)
        layer["subln"] = jnp.ones((2 * hd,), jnp.float32)
    if routed is not None:
        layer["ln2"] = _norm_leaf(cfg.d_model, cfg)
    if cfg.qk_norm and attention:
        per_head = cfg.qk_norm_per_head
        layer["q_norm"] = _gain_leaf(
            hd if per_head else cfg.n_head * hd, cfg)
        layer["k_norm"] = _gain_leaf(
            hd if per_head else cfg.n_kv_head * hd, cfg)
    if cfg.branch_norm:
        layer["ln1_out"] = jnp.ones((cfg.d_model,), jnp.float32)
        layer["ln2_out"] = jnp.ones((cfg.d_model,), jnp.float32)
    if routed:
        held, width = cfg.experts_here, cfg.expert_width
        layer["moe"] = {
            "router": _dense(k[4], cfg.d_model, cfg.num_experts),
            "wi": jax.random.normal(
                k[5], (held, cfg.d_model, width), jnp.float32) * 0.02,
            "wo": jax.random.normal(
                k[7], (held, width, cfg.d_model), jnp.float32) * 0.02,
        }
        if gated:
            layer["moe"]["wg"] = jax.random.normal(
                k[6], (held, cfg.d_model, width), jnp.float32) * 0.02
        if cfg.router_bias_rate is not None:
            layer["moe"]["router_bias"] = jnp.zeros(
                (cfg.num_experts,), jnp.float32)
        if cfg.n_shared_experts > 0:
            shared = cfg.n_shared_experts * width
            layer["moe"]["shared"] = {
                "w_up": _dense(more[3], cfg.d_model, shared),
                "w_down": _dense(more[4], shared, cfg.d_model),
            }
            if gated:
                layer["moe"]["shared"]["w_gate"] = _dense(
                    more[2], cfg.d_model, shared)
        if cfg.shared_expert_gate:
            layer["moe"]["shared_gate"] = _dense(
                jax.random.fold_in(key, 5), cfg.d_model, 1)
    elif routed is not None:
        layer["mlp"] = {
            "w_up": _dense(k[5], cfg.d_model, cfg.d_ff),
            "w_down": _dense(k[6], cfg.d_ff, cfg.d_model),
        }
        if gated:
            layer["mlp"]["w_gate"] = _dense(k[4], cfg.d_model, cfg.d_ff)
    return layer


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Dict:
    keys = jax.random.split(rng, cfg.n_layer + 3)
    params: Dict = {
        "embed": _dense(keys[0], cfg.vocab_size, cfg.d_model),
        "lm_head": _dense(keys[1], cfg.d_model, cfg.vocab_size),
        "ln_f": _norm_leaf(cfg.d_model, cfg),
        "layers": [
            _init_layer(keys[2 + i], cfg, cfg.mlp_routed(i),
                        mixer=cfg.mixer_kind(i))
            for i in range(cfg.n_layer)],
    }
    if cfg.tie_word_embeddings:
        del params["lm_head"]
    if cfg.exit_gate_beta is not None:
        params["exit_gate"] = {
            "w": jax.random.normal(
                keys[cfg.n_layer + 2], (cfg.d_model,), jnp.float32) * 0.02,
            "b": jnp.zeros((), jnp.float32),
        }
    if cfg.mtp_layers:
        k_eh, k_block = jax.random.split(jax.random.fold_in(rng, 1))
        params["mtp"] = {
            "ln_e": jnp.ones((cfg.d_model,), jnp.float32),
            "ln_h": jnp.ones((cfg.d_model,), jnp.float32),
            "w_eh": _dense(k_eh, 2 * cfg.d_model, cfg.d_model),
            # of the routed kind where the model has routed layers at all
            "block": _init_layer(k_block, cfg, cfg.num_experts > 0),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
    return params


def param_logical_axes(cfg: LlamaConfig) -> Dict:
    """Logical-axis names per parameter (consumed by
    ``parallel.sharding.tree_logical_to_specs``)."""

    gated = cfg.mlp_form == "swiglu"

    def mlp_axes() -> Dict:
        ax = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
        if gated:
            ax["w_gate"] = ("embed", "mlp")
        return ax

    # one of the block's two norms or the final norm (:func:`_norm_leaf`)
    norm = ({"gain": (None,), "bias": (None,)}
            if cfg.norm_form == "layernorm" else (None,))

    def layer_axes(has_moe: Optional[bool],
                   mixer: Optional[str] = "attention") -> Dict:
        """As :func:`_init_layer`: ``mixer`` None is a layer without a
        mixer half, ``has_moe`` None one without an MLP half."""
        ax = {"ln1": norm, "wo": ("heads", "embed"), "ln2": norm}
        attention = mixer in ATTENTION_KINDS
        if mixer is None:
            del ax["ln1"], ax["wo"]
        if has_moe is None:
            del ax["ln2"]
        if mixer == "conv":
            # ``in_proj`` by columns, ``out_proj`` by rows, the taps along
            # their channels: a depthwise convolution mixes no channels, so
            # ``tp`` needs no exchange between the taps and ``out_proj``
            del ax["wo"]
            ax["conv"] = {"in_proj": ("embed", "mlp"),
                          "conv_w": (None, "mlp"),
                          "out_proj": ("mlp", "embed")}
        elif mixer == "mamba":
            # the large dimensions over ``fsdp``, the rest replicated: the
            # mixer has no ``tp`` rule yet
            del ax["wo"]
            ax["ssm"] = {
                "in_proj": ("embed", None), "conv_w": (None, None),
                "dt_bias": (None,), "A_log": (None,), "D": (None,),
                "norm": (None,), "out_proj": (None, "embed")}
            if cfg.mamba_conv_bias:
                ax["ssm"]["conv_b"] = (None,)
        elif mixer == "linear_attention":
            # as the state-space mixer: no ``tp`` rule yet
            del ax["wo"]
            ax["gdn"] = {
                "in_proj_qkvz": ("embed", None), "in_proj_ba": ("embed", None),
                "conv_w": (None, None), "dt_bias": (None,), "A_log": (None,),
                "norm": (None,), "out_proj": (None, "embed")}
        elif mixer == "mamba1":
            # as the state-space mixer: no ``tp`` rule yet
            del ax["wo"]
            ax["s6"] = {
                "in_proj": ("embed", None), "conv_w": (None, None),
                "conv_b": (None,), "x_proj": (None, None),
                "dt_proj": (None, None), "dt_bias": (None,),
                "A_log": (None, None), "D": (None,),
                "out_proj": (None, "embed")}
        elif mixer == "gmu":
            del ax["wo"]
            ax["gmu"] = {"in_proj": ("embed", None),
                         "out_proj": (None, "embed")}
        elif mixer == "kda":
            # as the state-space mixer: no ``tp`` rule yet
            del ax["wo"]
            ax["kda"] = dict(
                {name: ("embed", None) for name in (
                    "wq", "wk", "wv", "f_a", "w_beta", "g_a")},
                **{name: (None, None) for name in (
                    "conv_q", "conv_k", "conv_v", "f_b", "g_b")},
                **{name: (None,) for name in (
                    "dt_bias", "A_log", "g_bias", "norm")},
                out_proj=(None, "embed"))
        elif attention and cfg.kv_lora_rank > 0:
            ax.update(wkv_a=("embed", None), kv_a_norm=(None,),
                      wkv_b=(None, "heads"))
            if cfg.q_lora_rank > 0:
                ax.update(wq_a=("embed", None), q_a_norm=(None,),
                          wq_b=(None, "heads"))
            else:
                ax["wq"] = ("embed", "heads")
        elif attention:
            ax.update(wq=("embed", "heads"), wk=("embed", "heads"),
                      wv=("embed", "heads"))
            if mixer == "cross_attention":
                del ax["wk"], ax["wv"]
        if attention and cfg.attn_bias:
            ax.update({"b" + name[1]: (None,)
                       for name in ("wq", "wk", "wv", "wo") if name in ax})
        if attention and cfg.diff_attention:
            ax.update({name: (None,) for name in _LAMBDA_LEAVES},
                      subln=(None,))
        if cfg.qk_norm and attention:
            ax["q_norm"] = (None,)
            ax["k_norm"] = (None,)
        if cfg.branch_norm:
            ax["ln1_out"] = (None,)
            ax["ln2_out"] = (None,)
        if has_moe:
            ax["moe"] = {
                "router": (None, None),
                "wi": ("expert", "embed", "expert_mlp"),
                "wo": ("expert", "expert_mlp", "embed"),
            }
            if gated:
                ax["moe"]["wg"] = ("expert", "embed", "expert_mlp")
            if cfg.router_bias_rate is not None:
                ax["moe"]["router_bias"] = (None,)
            if cfg.n_shared_experts > 0:
                ax["moe"]["shared"] = mlp_axes()
            if cfg.shared_expert_gate:
                ax["moe"]["shared_gate"] = ("embed", None)
        elif has_moe is not None:
            ax["mlp"] = mlp_axes()
        return ax

    layers = []
    for i in range(cfg.n_layer):
        layers.append(layer_axes(cfg.mlp_routed(i), cfg.mixer_kind(i)))
    axes = {
        "embed": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "ln_f": norm,
        "layers": layers,
    }
    if cfg.tie_word_embeddings:
        del axes["lm_head"]
    if cfg.exit_gate_beta is not None:
        axes["exit_gate"] = {"w": (None,), "b": ()}
    if cfg.mtp_layers:
        axes["mtp"] = {
            "ln_e": (None,), "ln_h": (None,), "w_eh": (None, "embed"),
            "block": layer_axes(cfg.num_experts > 0), "ln_f": (None,),
        }
    return axes


def _rotary_table(positions: jax.Array, rotary: Rotary, dim: int) -> tuple:
    """``(cos, sin)``, float32 ``[B, S, 1, dim / 2]``, of integer
    ``positions [B, S]``: the angles ``position * inv_freq_j``, both scaled
    by ``rotary.attention_factor`` where it is not 1."""
    freqs = rotary.inv_freq(dim)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    if rotary.attention_factor != 1.0:
        cos, sin = (cos * rotary.attention_factor,
                    sin * rotary.attention_factor)
    return cos, sin


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2) by a table of
    :func:`_rotary_table`."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2) by the plain table at
    ``theta``, built here."""
    return _rotate(x, *_rotary_table(positions, Rotary(theta), x.shape[-1]))


def _rope_part(x: jax.Array, positions: jax.Array, cfg: "LlamaConfig",
               table: Optional[tuple] = None):
    """:func:`_rope` over the first ``cfg.rotary_dim`` dims of each head
    (all of them at ``partial_rotary_factor`` 1), pairs ``(j, j +
    rotary_dim / 2)``; the other dims pass untouched.  ``table``: the
    layer's kind's ``(cos, sin)`` where the step built one
    (``cfg.rotary_by_kind``), in place of the plain one at
    ``cfg.rope_theta``."""
    def turn(part):
        if table is not None:
            return _rotate(part, *table)
        return _rope(part, positions, cfg.rope_theta)

    rot = cfg.rotary_dim
    if rot == x.shape[-1]:
        return turn(x)
    return jnp.concatenate([turn(x[..., :rot]), x[..., rot:]], axis=-1)


def qk_normed(q, k, layer, cfg: "LlamaConfig"):
    """q [..., H*D], k [..., KV*D] as projected -> the same, RMS-normalised
    over the whole projection width where ``cfg.qk_norm`` (the kernel
    computes in float32 and, under a mesh, takes the normalised dim whole
    in every shard, so the mean is over all heads under ``tp`` too), or
    head by head where ``cfg.qk_norm_per_head`` too.  The training block
    and the KV-cache decoder both call it."""
    if not cfg.qk_norm:
        return q, k
    q_gain, k_gain = _gain(layer["q_norm"], cfg), _gain(layer["k_norm"], cfg)
    if cfg.qk_norm_per_head:
        return (_rms_per_head(q, q_gain, cfg), _rms_per_head(k, k_gain, cfg))
    return (rmsnorm(q, q_gain, eps=cfg.rms_eps),
            rmsnorm(k, k_gain, eps=cfg.rms_eps))


def _rms_per_head(x, gain, cfg: "LlamaConfig"):
    """``x [..., heads * head_dim]`` -> the same, each head RMS-normalised
    over its own ``head_dim`` dims in float32 with the one ``gain
    [head_dim]``: elementwise work on 64-wide rows that XLA fuses with the
    rotary pass behind it (the RMSNorm kernel takes rows of the stream's
    width), and local to a head, so to a ``tp`` shard."""
    heads = x.reshape(x.shape[:-1] + (-1, cfg.head_dim)).astype(jnp.float32)
    inv = jax.lax.rsqrt(
        jnp.mean(jnp.square(heads), axis=-1, keepdims=True) + cfg.rms_eps)
    return (heads * inv * gain).astype(x.dtype).reshape(x.shape)


def _mla_qkv(x, layer, cfg: LlamaConfig, positions) -> tuple:
    """Latent attention's projections: normed ``x [B, S, C]`` -> ``(q, k,
    v)``, ``q`` and ``k [B, S, H, head_dim]``, ``v [B, S, H, v_head_dim]``,
    plain multi-head operands for any attention backend.  Per token: ``c_q
    = rms(x wq_a)``, ``[q_nope_i; q_rope_i] = c_q wq_b`` per head i (at
    ``q_lora_rank`` 0 ``x wq``, one matrix); ``[c_kv; k_rope] = x wkv_a``,
    ``c_kv = rms(c_kv)``, ``[k_nope_i; v_i] = c_kv wkv_b``; ``q_i =
    [q_nope_i; rope(q_rope_i)]``, ``k_i = [k_nope_i; rope(k_rope)]`` with
    the token's one ``k_rope`` under every head.  RoPE turns the pairs
    ``(j, j + qk_rope_head_dim / 2)`` of the rotary dims, as :func:`_rope`
    does everywhere (HF's ``rotate_half``); where ``cfg.rope`` is False
    neither is turned and the model has no position of its own.  Scopes
    ``mla_q`` and ``mla_kv`` sit inside the block's ``attention``."""
    B, S, _ = x.shape
    H, dt = cfg.n_head, cfg.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla_q"):
        if cfg.q_lora_rank > 0:
            c_q = rmsnorm(x @ layer["wq_a"].astype(dt), layer["q_a_norm"],
                          eps=cfg.rms_eps)
            q = c_q @ layer["wq_b"].astype(dt)
        else:
            q = x @ layer["wq"].astype(dt)
        q = q.reshape(B, S, H, nope + rope)
        if cfg.rope:
            q = jnp.concatenate(
                [q[..., :nope],
                 _rope(q[..., nope:], positions, cfg.rope_theta)], axis=-1)
    with jax.named_scope("mla_kv"):
        down = x @ layer["wkv_a"].astype(dt)
        c_kv = rmsnorm(down[..., :cfg.kv_lora_rank], layer["kv_a_norm"],
                       eps=cfg.rms_eps)
        k_rope = down[..., None, cfg.kv_lora_rank:]  # [B, S, 1, rope]
        if cfg.rope:
            k_rope = _rope(k_rope, positions, cfg.rope_theta)
        kv = (c_kv @ layer["wkv_b"].astype(dt)).reshape(
            B, S, H, nope + cfg.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))],
            axis=-1)
        v = kv[..., nope:]
    return q, k, v


def _diff_heads(q, k, v):
    """The heads of differential attention reordered so that ONE flash call
    with its GQA map (query head ``h`` reads key/value head ``h // (H /
    KV)``) computes both softmaxes of every pair: ``q [B, S, H, D]`` -> all
    the pairs' first heads, then all their second ones; ``k [B, S, KV, D]``
    likewise; ``v [B, S, KV, D]`` -> each pair's two heads joined, ``[B, S,
    KV / 2, 2 D]``, once under the first keys and once under the second."""
    first_then_second = lambda a: jnp.concatenate(  # noqa: E731
        [a[:, :, 0::2], a[:, :, 1::2]], axis=2)
    B, S, KV, D = v.shape
    v = v.reshape(B, S, KV // 2, 2 * D)
    return (first_then_second(q), first_then_second(k),
            jnp.concatenate([v, v], axis=2))


def _diff_combine(out, layer, cfg: LlamaConfig, lambda_init: float):
    """What follows differential attention's flash call (scope
    ``attn_diff``): ``out [B, S, H, 2 D]``, the pairs' first heads then
    their second ones -> ``[B, S, H / 2, 2 D]``: ``o1 - lambda o2``, an
    RMSNorm per pair (gain ``subln``), times ``1 - lambda_init``, in
    float32 and rounded once."""
    f32 = jnp.float32
    with jax.named_scope("attn_diff"):
        dots = [jnp.sum(layer[a] * layer[b]) for a, b in (
            _LAMBDA_LEAVES[:2], _LAMBDA_LEAVES[2:])]
        lam = jnp.exp(dots[0]) - jnp.exp(dots[1]) + lambda_init
        pairs = out.shape[2] // 2
        o = out[:, :, :pairs].astype(f32) - lam * out[:, :, pairs:].astype(f32)
        inv = jax.lax.rsqrt(
            jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_eps)
        return (o * inv * (layer["subln"] * (1.0 - lambda_init))).astype(
            out.dtype)


def _attention(
    x, layer, cfg: LlamaConfig, positions, attn_impl: str, mesh,
    segment_ids=None, kind: str = "attention", rotary=None,
    shared_kv=None, lambda_init: float = 0.0, with_kv: bool = False,
):
    """The attention kinds (:data:`ATTENTION_KINDS`) -> ``out``, or with
    ``with_kv`` ``(out, (k, v))``: ``kind`` sets the window
    (``cfg.window_of``), whether q and k rotate at all (``cfg.unrotated``) and, in a model with layers of more than one,
    the scope around the flash call; ``rotary`` is the kind's ``(cos, sin)``
    where the step built one (``cfg.rotary_by_kind``).  The output gate's
    multiply sits under ``attn_gate``, inside the block's ``attention``.
    ``(k, v)`` are the keys and values as the flash call's reordering takes
    them (``[B, S, KV, D]``: after the bias, the norms and the rotation),
    which is what a "cross_attention" layer is handed as ``shared_kv`` in
    place of projections of its own.  Under ``cfg.diff_attention`` the heads
    are paired (:func:`_diff_heads`, :func:`_diff_combine` with this layer's
    ``lambda_init``)."""
    B, S, C = x.shape
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt = cfg.dtype
    latent = cfg.kv_lora_rank > 0
    gate = None

    def projected(name):
        y = x @ layer["w" + name].astype(dt)
        return y + layer["b" + name].astype(dt) if cfg.attn_bias else y

    if latent:
        q, k, v = _mla_qkv(x, layer, cfg, positions)
    elif shared_kv is not None:
        q, (k, v) = projected("q"), shared_kv
    else:
        q, k, v = projected("q"), projected("k"), projected("v")
    if cfg.attn_output_gate:
        # each head's columns are [q | gate]
        q = q.reshape(B, S, H, 2 * D)
        q, gate = q[..., :D].reshape(B, S, H * D), q[..., D:]
    if shared_kv is not None:
        q = q.reshape(B, S, H, D)
    elif not latent:
        q, k = qk_normed(q, k, layer, cfg)
        q, k = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        if not cfg.unrotated(kind):
            q = _rope_part(q, positions, cfg, rotary)
            k = _rope_part(k, positions, cfg, rotary)
        v = v.reshape(B, S, KV, D)
    made = (k, v)
    if cfg.diff_attention:
        q, k, v = _diff_heads(q, k, v)
    if cfg.attention_multiplier is not None:
        # every backend scales the scores by 1 / sqrt(D): the rest of the
        # stated scale goes onto q (Granite: 1/64 at D = 64, so 1/8, exact)
        q = q * (cfg.attention_multiplier * D ** 0.5)
    if KV != H and attn_impl in ("ring", "ulysses") and mesh is not None:
        # Ring/Ulysses shard over heads and need the full head count; the
        # flash path handles GQA in-kernel (no materialized repeat).
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    if v.shape[-1] != D and attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"v_head_dim={v.shape[-1]} under {D}-wide q and k requires the "
            f"flash attention path, not {attn_impl!r}")
    window = cfg.window_of(kind)
    if window > 0 and attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"sliding_window (a {kind!r} layer's window of {window}) "
            f"requires the flash attention path, not {attn_impl!r}"
        )
    if attn_impl == "ring" and mesh is not None:
        if segment_ids is not None:
            raise NotImplementedError(
                "packed sequences (segment_ids) require the flash "
                "attention path, not ring"
            )
        from dlrover_tpu.parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, mesh, causal=True)
    elif attn_impl == "ulysses" and mesh is not None:
        if segment_ids is not None:
            raise NotImplementedError(
                "packed sequences (segment_ids) require the flash "
                "attention path, not ulysses"
            )
        from dlrover_tpu.parallel.sequence import ulysses_attention

        out = ulysses_attention(q, k, v, mesh, causal=True)
    else:
        # [B,S,H,D] -> [B,H,S,D] for the flash kernel; where the model has
        # both kinds the call says which it is (``attn_window`` /
        # ``attn_full``, inside the block's ``attention``)
        with (jax.named_scope(ATTENTION_KINDS[kind])
              if cfg.window_layers or cfg.cross_layers
              else contextlib.nullcontext()):
            o = flash_attention(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
                segment_ids=segment_ids,
                backend=None if attn_impl == "auto" else attn_impl,
                window=window,
            )
            out = o.transpose(0, 2, 1, 3)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    if cfg.diff_attention:
        out = _diff_combine(out, layer, cfg, lambda_init)
    out = out.reshape(B, S, -1)
    with (jax.named_scope("mla_out") if latent
          else contextlib.nullcontext()):
        out = out @ layer["wo"].astype(dt)
        if cfg.attn_bias:
            out = out + layer["bo"].astype(dt)
        return (out, made) if with_kv else out


def _ssm_mixer(u, ssm, cfg: LlamaConfig) -> tuple:
    """The Mamba-2 mixer on the normed stream ``u [B, S, C]`` -> ``(out [B,
    S, C], stats)``.  ``[z | xBC | dt] = u in_proj``; ``xBC = silu(conv(xBC)
    + b)``, causal, depthwise; ``[x | B | C] = xBC``; ``dt = softplus(dt +
    dt_bias)`` in float32, unclamped; ``A = -exp(A_log)``; the scan
    (``ops.ssd.ssd_chunked`` at ``cfg.mamba_chunk_size``) gives ``y_t = h_t
    C_t + D x_t``; ``y = rms(y * silu(z)) * norm`` — the gate BEFORE the
    norm, the mean square taken over each of ``cfg.mamba_n_groups`` groups
    of the width by itself (``ops.gated_norm``, gate and norm one pass; one
    group: the RMSNorm kernel over the whole width); ``out = y out_proj``.
    Scopes ``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate`` and
    ``ssm_out`` sit inside the block's ``ssm``.  ``stats``:
    ``ssm_state_rms`` (of the state the sequence leaves) and
    ``ssm_decay_min`` (the least ``exp(sum dt A)`` over a chunk: 0 says a
    chunk's decay underflowed float32)."""
    B, S, _ = u.shape
    H, P, G, N = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                  cfg.mamba_d_state)
    inner, conv, dt = cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.dtype
    with jax.named_scope("ssm_in"):
        zxbcdt = u @ ssm["in_proj"].astype(dt)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv]
        step = zxbcdt[..., inner + conv:]
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv1d_silu(xbc, ssm["conv_w"], ssm.get("conv_b"))
    with jax.named_scope("ssm_scan"):
        step = jax.nn.softplus(step.astype(jnp.float32) + ssm["dt_bias"])
        y, state, decay_min = ssd_chunked(
            xbc[..., :inner].reshape(B, S, H, P), step,
            -jnp.exp(ssm["A_log"]),
            xbc[..., inner:inner + G * N].reshape(B, S, G, N),
            xbc[..., inner + G * N:].reshape(B, S, G, N),
            cfg.mamba_chunk_size, D=ssm["D"])
        stats = jax.lax.stop_gradient({
            "ssm_state_rms": jnp.sqrt(jnp.mean(jnp.square(state))),
            "ssm_decay_min": decay_min})
    with jax.named_scope("ssm_gate"):
        y = y.reshape(B, S, inner)
        if G == 1:
            y = y * jax.nn.silu(z.astype(jnp.float32))
            y = rmsnorm(y.astype(dt), ssm["norm"], eps=cfg.rms_eps)
        else:
            y = gated_norm(y, z, ssm["norm"], group=inner // G,
                           eps=cfg.rms_eps, gate_first=True)
    with jax.named_scope("ssm_out"):
        return y @ ssm["out_proj"].astype(dt), stats


def _s6_mixer(u, s6, cfg: LlamaConfig) -> tuple:
    """The Mamba-1 mixer on the normed stream ``u [B, S, C]`` -> ``(out [B,
    S, C], stats, y)``.  ``[x | z] = u in_proj``; ``x = silu(conv(x) + b)``,
    causal, depthwise (``ops.conv_silu``); ``[delta | B | C] = x x_proj``
    (``s6_dt_rank``, ``s6_d_state``, ``s6_d_state`` columns); ``dt =
    softplus(delta dt_proj + dt_bias)`` in float32 a channel; ``A =
    -exp(A_log)`` ``[d_inner, d_state]``; the selective scan
    (``ops.selective_scan``) gives ``y_t = s_t C_t + D x_t`` in float32;
    ``out = (y * silu(z)) out_proj``.  ``y`` — with the ``D`` skip, BEFORE
    the gate — is returned too: it is the memory where this layer is
    ``cfg.memory_layer``.  Scopes ``s6_in``, ``s6_conv``, ``s6_dt``,
    ``s6_scan``, ``s6_gate`` and ``s6_out`` sit inside the block's ``s6``.
    ``stats``: ``s6_state_rms`` (of the state the sequence leaves) and
    ``s6_decay_min`` (the least ``exp(sum dt A)`` over a chunk, channel and
    state).  Block remat keeps the scan's output and the states that enter
    its chunks (``ops.selective_scan.SAVED_NAMES``: ``B S d_inner (4 + 4
    d_state / S6_CHUNK)`` bytes a layer), so ``s6_scan_fwd`` runs once a
    step and layer."""
    inner, N, R = cfg.s6_d_inner, cfg.s6_d_state, cfg.s6_dt_rank
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("s6_in"):
        xz = u @ s6["in_proj"].astype(dt)
        x, z = xz[..., :inner], xz[..., inner:]
    with jax.named_scope("s6_conv"):
        x = causal_conv1d_silu(x, s6["conv_w"], s6["conv_b"])
    with jax.named_scope("s6_dt"):
        dbc = x @ s6["x_proj"].astype(dt)
        step = jax.nn.softplus(
            (dbc[..., :R] @ s6["dt_proj"].astype(dt)).astype(f32)
            + s6["dt_bias"])
    with jax.named_scope("s6_scan"):
        y, state, decay_min = selective_scan(
            x, step, -jnp.exp(s6["A_log"]), dbc[..., R:R + N],
            dbc[..., R + N:], s6["D"])
        stats = jax.lax.stop_gradient({
            "s6_state_rms": jnp.sqrt(jnp.mean(jnp.square(state))),
            "s6_decay_min": decay_min})
    with jax.named_scope("s6_gate"):
        gated = (y * jax.nn.silu(z.astype(f32))).astype(dt)
    with jax.named_scope("s6_out"):
        return gated @ s6["out_proj"].astype(dt), stats, y


def _gmu_mixer(u, gmu, memory, cfg: LlamaConfig):
    """The Gated Memory Unit on the normed stream ``u [B, S, C]`` and the
    memory ``[B, S, s6_d_inner]`` (``cfg.memory_layer``'s scan output, in
    ``cfg.dtype``) -> ``[B, S, C]``: ``(memory * silu(u in_proj))
    out_proj``, the gate in float32 and rounded once; no scan, no
    convolution, no bias."""
    dt, f32 = cfg.dtype, jnp.float32
    gate = jax.nn.silu((u @ gmu["in_proj"].astype(dt)).astype(f32))
    return (memory.astype(f32) * gate).astype(dt) @ gmu["out_proj"].astype(dt)


def _conv_mixer(u, conv, cfg: LlamaConfig):
    """LFM2's double-gated short convolution on the normed stream ``u [B,
    S, C]`` -> ``[B, S, C]``.  ``[B | C | X] = u in_proj`` (thirds of
    ``3 C`` columns, in that order); ``c_t = sum_k w_k * (B * X)_{t - (K -
    1) + k}``, causal and depthwise over ``K = cfg.conv_taps`` taps with
    zeros before the sequence (``ops.ssd.causal_conv1d``); ``out = (C * c)
    out_proj``.  No bias and no activation.  The two gates and the taps are
    one elementwise chain in float32, rounded once.  Scopes ``conv_in``,
    ``conv_gate`` and ``conv_out`` sit inside the block's ``conv``."""
    d, dt = cfg.d_model, cfg.dtype
    f32 = jnp.float32
    with jax.named_scope("conv_in"):
        bcx = u @ conv["in_proj"].astype(dt)
    with jax.named_scope("conv_gate"):
        gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        y = gate_c.astype(f32) * causal_conv1d(
            gate_b.astype(f32) * x.astype(f32), conv["conv_w"])
    with jax.named_scope("conv_out"):
        return y.astype(dt) @ conv["out_proj"].astype(dt)


def _gdn_mixer(u, gdn, cfg: LlamaConfig) -> tuple:
    """The Gated DeltaNet mixer on the normed stream ``u [B, S, C]`` ->
    ``(out [B, S, C], stats)``.  ``in_proj_qkvz``'s output viewed per KEY
    head ``[q | k | v | z]`` (``D``, ``D``, ``R D``, ``R D`` columns with
    ``R`` value heads a key head), ``in_proj_ba``'s ``[b | a]`` (``R``
    each); ``[q | k | v]`` flattened, through a causal depthwise convolution
    without bias and ``silu``; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
    softplus(a + dt_bias)`` in float32 per value head; q and k
    L2-normalised over a head's ``D`` dims in float32 (``x / sqrt(sum x^2 +
    1e-6)``), q scaled by ``D^-1/2``, each key head repeated under its
    ``R`` value heads; the gated delta rule
    (``ops.gated_delta.gated_delta_chunked`` at :data:`GDN_CHUNK`); ``y =
    norm * rms(o) * silu(z)`` per head in float32 — the norm BEFORE the
    gate, a plain gain (``ops.gated_norm``, a head a group); ``out = y
    out_proj``.  Scopes ``gdn_in``, ``gdn_conv``, ``gdn_scan``, ``gdn_gate``
    and ``gdn_out`` sit inside the
    block's ``gdn``.  ``stats``: ``gdn_state_rms`` (of the state the
    sequence leaves) and ``gdn_decay_min`` (the least ``exp(sum g)`` over a
    chunk: 0 says a chunk's decay underflowed float32, which the rule
    allows).

    The mixer has no checkpoint of its own: it is rematerialised as the
    rest of its block is (``cfg.remat_block``: once, in front of the block's
    backward; else not at all).  Of it block remat keeps what the rule's
    forward kernel put out and the backward reads
    (``ops.gated_delta.SAVED_NAMES``: ``o`` in float32 and the states that
    entered the chunks in ``cfg.dtype``, ``B S hv D (4 + 2 D / GDN_CHUNK)``
    bytes a layer at bf16; the final state leaves under ``stop_gradient``
    alone and nothing holds it), so ``gdn_chunk_fwd`` runs once a step and
    layer; the projections, the convolution, the norms and the gate around
    it run again.  On the ``jax.numpy`` form of the rule nothing is named
    and all of it does."""
    B, S, _ = u.shape
    hk, hv, D = cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_d_head
    R, dt, f32 = hv // hk, cfg.dtype, jnp.float32

    with jax.named_scope("gdn_in"):
        qkvz = (u @ gdn["in_proj_qkvz"].astype(dt)).reshape(
            B, S, hk, (2 + 2 * R) * D)
        ba = (u @ gdn["in_proj_ba"].astype(dt)).reshape(B, S, hk, 2 * R)
    z = qkvz[..., (2 + R) * D:].reshape(B, S, hv * D)
    with jax.named_scope("gdn_conv"):
        flat = lambda a: a.reshape(B, S, -1)  # noqa: E731
        qkv = jnp.concatenate(
            [flat(qkvz[..., :D]), flat(qkvz[..., D:2 * D]),
             flat(qkvz[..., 2 * D:(2 + R) * D])], axis=-1)
        qkv = causal_conv1d_silu(qkv, gdn["conv_w"])
    with jax.named_scope("gdn_scan"):
        beta = jax.nn.sigmoid(ba[..., :R].astype(f32)).reshape(B, S, hv)
        g = -jnp.exp(gdn["A_log"]) * jax.nn.softplus(
            ba[..., R:].astype(f32).reshape(B, S, hv) + gdn["dt_bias"])

        def unit(a, scale):
            a = a.reshape(B, S, hk, D).astype(f32)
            a = a * (jax.lax.rsqrt(
                jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
                * scale)
            return jnp.repeat(a.astype(dt), R, axis=2)

        o, state, decay_min = gated_delta_chunked(
            unit(qkv[..., :hk * D], D ** -0.5),
            unit(qkv[..., hk * D:2 * hk * D], 1.0),
            qkv[..., 2 * hk * D:].reshape(B, S, hv, D), g, beta, GDN_CHUNK)
        stats = jax.lax.stop_gradient({
            "gdn_state_rms": jnp.sqrt(jnp.mean(jnp.square(state))),
            "gdn_decay_min": decay_min})
    with jax.named_scope("gdn_gate"):
        # ``o`` in the flat form the rule's kernel writes, a head a group
        y = gated_norm(o.reshape(B, S, hv * D), z, jnp.tile(gdn["norm"], hv),
                       group=D, eps=cfg.rms_eps, gate_first=False)
    with jax.named_scope("gdn_out"):
        return y @ gdn["out_proj"].astype(dt), stats


def _kda_mixer(u, kda, cfg: LlamaConfig) -> tuple:
    """The Kimi Delta Attention mixer (arXiv:2510.26692) on the normed
    stream ``u [B, S, C]`` -> ``(out [B, S, C], stats)``.  ``q``, ``k`` and
    ``v`` each from a projection of its own (``wq``, ``wk``, ``wv``: ``H D``
    columns) through a causal depthwise convolution without bias and ``silu``
    (``ops.conv_silu``, three calls); ``beta = sigmoid(u w_beta)`` a head;
    the decay a key CHANNEL, ``g = -exp(A_log[h]) * softplus((u f_a) f_b +
    dt_bias)`` in float32 ``[B, S, H, D]``; the delta rule whose state's
    rows decay each on its own (``ops.gated_delta.gated_delta_chunked`` at
    :data:`KDA_CHUNK`), which is handed the convolutions' q and k and ``g``
    as they are: the L2 norms over a head's ``D`` dims in float32 (``x /
    sqrt(sum x^2 + 1e-6)``, q scaled by ``D^-1/2``: its ``unit_scales``) and
    the cumulative sum of ``g`` are each chunk's own, on the tile the rule's
    kernels hold anyway, and of ``kda_scan`` XLA keeps ``g``, ``beta`` and
    the statistics; ``y =
    norm * rms(o) * sigmoid((u g_a) g_b + g_bias)`` per head in float32 —
    the norm BEFORE the gate, the gate a sigmoid (``ops.gated_norm``, a head
    a group); ``out = y out_proj``.  Scopes ``kda_in``, ``kda_conv``,
    ``kda_scan``, ``kda_gate`` and ``kda_out`` sit inside the block's
    ``kda``.  ``stats``: ``kda_state_rms`` (of the state the sequence
    leaves) and ``kda_decay_min`` (the least ``exp(sum g)`` over a chunk and
    channel: 0 says a channel's decay underflowed float32 in a chunk, which
    the rule allows).

    As :func:`_gdn_mixer` the mixer has no checkpoint of its own; block remat
    keeps what the rule's forward kernel put out
    (``ops.gated_delta.CHANNEL_SAVED_NAMES``: ``o`` in float32 and the states
    that entered the chunks in ``cfg.dtype``, ``B S H D (4 + 2 D /
    KDA_CHUNK)`` bytes a layer at bf16), so ``kda_chunk_fwd`` runs once a
    step and layer."""
    B, S, _ = u.shape
    H, D = cfg.kda_heads, cfg.kda_d_head
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("kda_in"):
        q, k, v = (u @ kda[name].astype(dt) for name in ("wq", "wk", "wv"))
        f = (u @ kda["f_a"].astype(dt)) @ kda["f_b"].astype(dt)
        z = ((u @ kda["g_a"].astype(dt)) @ kda["g_b"].astype(dt)
             + kda["g_bias"].astype(dt))
        b = u @ kda["w_beta"].astype(dt)
    with jax.named_scope("kda_conv"):
        q, k, v = (causal_conv1d_silu(x, kda[name]) for x, name in (
            (q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    with jax.named_scope("kda_scan"):
        beta = jax.nn.sigmoid(b.astype(f32))
        # formed FLAT, as the projection put ``f`` out and as the rule's
        # kernels read it: by heads it is another tiling, 268 MB moved
        g = -jnp.repeat(jnp.exp(kda["A_log"]), D) * jax.nn.softplus(
            f.astype(f32) + kda["dt_bias"])
        heads = lambda a: a.reshape(B, S, H, D)  # noqa: E731
        o, state, decay_min = gated_delta_chunked(
            heads(q), heads(k), heads(v), heads(g), beta, KDA_CHUNK,
            unit_scales=(D ** -0.5, 1.0))
        stats = jax.lax.stop_gradient({
            "kda_state_rms": jnp.sqrt(jnp.mean(jnp.square(state))),
            "kda_decay_min": decay_min})
    with jax.named_scope("kda_gate"):
        y = gated_norm(o.reshape(B, S, H * D), z, jnp.tile(kda["norm"], H),
                       group=D, eps=cfg.rms_eps, gate_first=False,
                       activation="sigmoid")
    with jax.named_scope("kda_out"):
        return y @ kda["out_proj"].astype(dt), stats


def _swiglu(x, mlp, dt):
    g = x @ mlp["w_gate"].astype(dt)
    u = x @ mlp["w_up"].astype(dt)
    return (jax.nn.silu(g) * u) @ mlp["w_down"].astype(dt)


def _relu2(x, mlp, dt):
    """``down(relu(up x)^2)``: the two-matrix MLP (``cfg.mlp_form``
    "relu2")."""
    u = x @ mlp["w_up"].astype(dt)
    return jnp.square(jax.nn.relu(u)) @ mlp["w_down"].astype(dt)


def _mlp(x, mlp, dt):
    """The MLP of the form its leaves say (``cfg.mlp_form``): with a gate
    matrix :func:`_swiglu`, without one :func:`_relu2`."""
    return (_swiglu if "w_gate" in mlp else _relu2)(x, mlp, dt)


def _live_mask(rows: int, live_rows):
    return (jnp.arange(rows, dtype=jnp.int32) < live_rows)[:, None]


@jax.custom_vjp
def _dispatch_rows(tokens, order, inverse, live_rows):
    """``tokens`` [N, C] -> the rows of the (token, k) pairs at the first
    ``R = len(order)`` sorted positions, [R, C]: pair ``p = n*K + k`` sits
    at ``inverse[p]``, position ``i`` holds pair ``order[i]``; the rows
    from ``live_rows`` on (None: there are none) are zero.  The pairs are a
    permutation, so the transpose is :func:`gather_sum` by ``inverse``
    ([N*K], below ``R`` everywhere: the caller clamps it) with weights of
    one — K rows a token gathered and summed, never a scatter-add — and a
    pair at or past ``live_rows`` is no term of it: the cotangent's rows
    there are never read."""
    x = tokens[order // (inverse.shape[0] // tokens.shape[0])]
    if live_rows is not None:
        # a grouped matmul leaves the rows past its groups unwritten,
        # forward and backward: nothing of them may reach a sum
        x = jnp.where(_live_mask(order.shape[0], live_rows), x, 0)
    return x


def _dispatch_rows_fwd(tokens, order, inverse, live_rows):
    return (_dispatch_rows(tokens, order, inverse, live_rows),
            (inverse, live_rows, tokens.shape[0]))


def _gather_k(rows, index, weights, live_rows):
    """``(gather_sum(rows, index, weights), the gathered rows or None)``, by
    the arrangement that moves fewer rows.  Under a share of the experts
    (``live_rows`` given) most picks are dead: their rows are never read,
    nothing gathered is kept, the kernel's time follows the LIVE picks at
    any K (1.05 ms where XLA takes 5.4 at ten picks: PERF.md, PR 56).  With
    every expert held all ``[N*K, C]`` are gathered and kept for backward."""
    if live_rows is not None:
        return gather_sum(rows, index, weights), None
    picked = rows[index.reshape(-1)]
    return weighted_sum(picked.reshape(*index.shape, -1), weights), picked


def _dispatch_rows_bwd(res, g):
    inverse, live_rows, n = res
    index = inverse.reshape(n, -1)
    here = None if live_rows is None else (index < live_rows).astype(g.dtype)
    return _gather_k(g, index, here, live_rows)[0], None, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(rows, weights, order, inverse, live_rows):
    """The transpose of :func:`_dispatch_rows` with weights: ``out[n] =
    sum_k weights[n, k] * rows[inverse[n*K + k]]``, [R, C] -> [N, C]
    (:func:`gather_sum`: float32 products and sum from operands in
    ``rows``' dtype, one rounding; a pick of weight zero contributes a
    selected zero and its row is never read).

    Under a share of the experts (``live_rows`` given) nothing of ``N*K``
    rows is kept for the backward pass, which runs on the sorted side:
    with ``gs`` the dispatch gather of the cotangent (R rows), ``d rows =
    w_sorted * gs`` and ``d weights`` is the R row dots ``sum_c rows *
    gs`` in float32, gathered by ``inverse`` (``N*K`` scalars); the dots
    of the rows from ``live_rows`` on, which may hold anything, are
    selected zeros.  With every expert held (``R = N*K``, every pick
    live) the sorted side is no smaller than the token side: the gathered
    rows are kept (they are the forward's own), ``d weights`` is their dot
    with the cotangent and ``d rows`` one gather of the weighted cotangent
    by ``order`` — 4 ms a step less than the sorted side's pass and its
    two gathers of ``N*K`` scalars at 8 x 4,096 tokens, 8 picks (PERF.md
    section 6, PR 48)."""
    return _combine_rows_fwd(rows, weights, order, inverse, live_rows)[0]


def _combine_rows_fwd(rows, weights, order, inverse, live_rows):
    out, picked = _gather_k(rows, inverse.reshape(weights.shape), weights,
                            live_rows)
    kept = rows if picked is None else picked
    return out, (kept, weights, order, inverse, live_rows)


def _combine_rows_bwd(res, g):
    kept, weights, order, inverse, live_rows = res
    (n, k), f32 = weights.shape, jnp.float32
    if live_rows is None:  # every pick's row, gathered going forward
        # the weighted sum's own transposes, as JAX derives them
        weighted, dots = jax.vjp(
            lambda picked, w: weighted_sum(picked.reshape(n, k, -1), w),
            kept, weights)[1](g)
        d_rows = weighted[order]
    else:
        gs = g[order // k].astype(f32)
        w_sorted = weights.reshape(-1)[order].astype(f32)
        dots = jnp.sum(kept.astype(f32) * gs, axis=-1, keepdims=True)
        dots = jnp.where(_live_mask(kept.shape[0], live_rows), dots, 0.0)
        dots = dots[inverse, 0].reshape(n, k)
        d_rows = (w_sorted[:, None] * gs).astype(kept.dtype)
    return d_rows, dots.astype(weights.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@functools.partial(
    jax.checkpoint, static_argnums=(5, 6),
    policy=jax.checkpoint_policies.save_only_these_names("moe_gate_up"))
def _expert_ffn(rows, wg, wi, wo, group_sizes, dt, backend):
    """The three grouped matmuls over the sorted pair rows (``backend``:
    ``ops.grouped_matmul``'s, None for its own choice).  Kept for the
    backward pass: the rows and the two ``[N*K, F]`` products; recomputed
    there: the bf16 casts of the weights and ``silu(g) * u`` (elementwise
    passes, in place of 0.8 GB of bf16 weights and a third ``[N*K, F]``
    buffer at OLMoE's widths).  ``wg`` None is the two-matrix form
    (``cfg.mlp_form`` "relu2"): ``relu(rows wi)^2 wo``, the one product
    kept."""
    def product(a, w):
        return grouped_matmul_ragged(a, w.astype(dt), group_sizes,
                                     backend=backend)

    if wg is None:
        u = checkpoint_name(product(rows, wi), "moe_gate_up")
        return product(jnp.square(jax.nn.relu(u)), wo)
    g = checkpoint_name(product(rows, wg), "moe_gate_up")
    u = checkpoint_name(product(rows, wi), "moe_gate_up")
    return product(jax.nn.silu(g) * u, wo)


def _moe_buffer_bounds(n: int, k: int, e: int, held: int) -> tuple:
    """The row counts a routed block's sorted buffer may take, ascending,
    ``n * k`` (every pick) last: a pure function of the block's shapes.
    A chip that holds ``held < e`` experts computes about ``n * k * held /
    e`` rows, so the first size is that with a quarter of slack, a multiple
    of the grouped matmul's row tile (40,960 of 131,072 at 8 of 32 experts,
    10,240 of 65,536 at 8 of 64).  Where it is not under half of ``n * k``
    — every expert held, a toy shape, decode's few rows — the one size is
    ``n * k`` and :func:`_moe_swiglu` chooses nothing.  Two sizes and no
    third between them: each size is a program of its own in every pass
    of every routed block, and a warm start reads and loads them all."""
    total, tile = n * k, TILING[0]
    if held >= e:
        return (total,)
    first = -(-5 * total * held // (4 * e * tile)) * tile
    return (first, total) if 2 * first <= total else (total,)


def _routed_sum(ffn, rows: int, tokens, gate_vals, order, inverse,
                live_rows):
    """A routed block's sorted side in a buffer of ``rows`` rows, and the
    sum over k: the token rows of the first ``rows`` sorted pairs gathered
    (:func:`_dispatch_rows`, scope ``moe_permute``), ``ffn`` over them
    (``moe_experts``), and each token's K rows of the result gathered,
    weighed by the router and summed, ``[N, C]`` (:func:`_combine_rows`,
    ``moe_combine``): the token side is that one operation and its
    transpose, and under a share of the experts no array of ``N*K`` rows
    by ``C`` is built for it.
    ``live_rows`` (None: every row is computed) is how many rows ``ffn``'s
    groups cover, and ``rows`` must be ABOVE it unless it is ``N*K``: the
    rows from there on are zero going in, what ``ffn`` leaves of them is
    never read (their pairs' weights are zero), and a pair past the buffer
    is given the last of them."""
    if rows < inverse.shape[0]:
        order, inverse = order[:rows], jnp.minimum(inverse, rows - 1)
    with jax.named_scope("moe_permute"):
        x = _dispatch_rows(tokens, order, inverse, live_rows)
    with jax.named_scope("moe_experts"):
        y = ffn(x)
    with jax.named_scope("moe_combine"):
        return _combine_rows(y, gate_vals.astype(tokens.dtype), order,
                             inverse, live_rows)


def _routed_sum_at(rows: int, dt, tokens, gate_vals, weights, indices):
    order, inverse, group_sizes, live_rows = indices
    # The buffer of every pick is where a skewed step falls back to, and a
    # balanced router never takes it: its products are XLA's own ragged
    # dot, a fifth of the executable that the kernels' twelve programs a
    # block are (8.7 MB against 1.8 serialized at 8 of 32 experts; AOT),
    # which a warm start would read and load for nothing.
    backend = "reference" if rows == inverse.shape[0] else None
    return _routed_sum(
        lambda x: _expert_ffn(x, *weights, group_sizes, dt, backend), rows,
        tokens, gate_vals, order, inverse, live_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed_sum_sized(bounds, dt, size, tokens, gate_vals, weights, indices):
    """:func:`_routed_sum` over the plain experts (``weights``: wg, wi,
    wo) in a buffer of ``bounds[size]`` rows, ``size`` an int32 of the
    step's own; ``indices`` is ``(order, inverse, group_sizes,
    live_rows)``.

    Differentiated as ONE conditional a pass: what is kept for the
    backward pass is the arguments, and the backward rule chooses again
    and runs the chosen size's forward and transpose together.  A plain
    ``lax.switch`` under ``grad`` hands every size's residuals out of
    every branch (the others' as zeros: 2 GB of peak memory and as many
    bytes of writes a block at 4 x 8,192 tokens, 8 of 32 experts).
    The routed forward then runs twice a step with block remat or
    without: once forward and once inside the backward rule (the block's
    recomputation needs nothing of this function's result, and XLA drops
    its copy) — less its last step there: the combine's backward needs
    the experts' rows and nothing of the combine's result."""
    return jax.lax.switch(
        size, [functools.partial(_routed_sum_at, rows, dt)
               for rows in bounds], tokens, gate_vals, weights, indices)


def _routed_sum_sized_fwd(bounds, dt, size, *operands):
    return _routed_sum_sized(bounds, dt, size, *operands), (size, operands)


def _routed_sum_sized_bwd(bounds, dt, res, g):
    size, operands = res

    def pull(rows):
        def run(g, tokens, gate_vals, weights, indices):
            # a checkpoint, so that JAX names the two halves what they are
            at = jax.checkpoint(functools.partial(
                _routed_sum_at, rows, dt, indices=indices))
            return jax.vjp(at, tokens, gate_vals, weights)[1](g)
        return run

    grads = jax.lax.switch(
        size, [pull(rows) for rows in bounds], g, *operands)
    return (None, *grads, None)


_routed_sum_sized.defvjp(_routed_sum_sized_fwd, _routed_sum_sized_bwd)


def _moe_swiglu(x, moe, cfg: LlamaConfig, capacity: Optional[int] = None,
                valid=None):
    """Routed block, sorted and ragged (its experts SwiGLU, or the
    two-matrix form where they hold no ``wg``): the ``N*K`` (token, expert)
    pairs are sorted by expert (stable, so a pair's rank inside its
    expert's group follows the token order), the token rows gathered in
    that order, ``wg``/``wi``/``wo`` applied as grouped matmuls over the
    ragged groups (``ops.grouped_matmul``), and each token's K rows of the
    result gathered where the sort put them, weighed by the router and
    summed (``ops.gather_sum``; its backward runs on the sorted side).
    Nothing of size ``N x E x capacity`` or ``N*K x E`` is built, forward
    or backward, and no pair is dropped unless a capacity is asked for.

    Returns ``(out [B, S, C], stats)`` with ``stats`` the layer's
    ``moe_aux`` (load-balance term ``E * sum_e f_e * P_e``: ``f_e`` the
    share of tokens whose first choice is ``e``, or with
    ``cfg.balance_all_k`` the mean over tokens and the k picks; ``P_e`` the
    mean router probability), ``moe_z`` (router z-loss, the mean over
    tokens of ``logsumexp(logits)**2``), ``experts`` (int32 ``[B, S, K]``,
    what the one ``top_k`` took) and ``tokens_per_expert`` (int32 ``[E]``,
    pairs routed to each expert before any capacity).

    ``capacity`` (default: from ``cfg.capacity_factor``, None = dropless)
    survives only as a mask: a pair whose rank in its group is
    ``>= capacity`` keeps its row and loses its weight.  Decode passes a
    no-drop value, which overrides a configured factor.

    ``valid`` [B, S] bool marks real tokens in packed-sequence training:
    pads sort behind every expert's group, so they take no rank and no
    capacity, their weight is zero and they count in no statistic (their
    rows ride at the end of the last group, computed and unused).

    The router's variants (``cfg.router_score``, ``moe["router_bias"]``,
    ``cfg.routed_scaling``, ``cfg.balance_per_sequence``) and the shared
    expert (``moe["shared"]``, scope ``moe_shared``, added for every
    token) are described at :class:`LlamaConfig`.  With a SHARE of the
    experts (``cfg.experts_held`` < E) the router still scores, chooses
    and normalises over all E; the pairs sort with the held experts
    first and the grouped matmuls' groups end with the last held expert's
    pairs.  The sorted side — the dispatch gather and its mask, the three
    grouped matmuls with ``silu(g) * u`` — runs in a buffer of the first
    of :func:`_moe_buffer_bounds`' sizes that is above the held pairs
    (``jax.lax.switch``; ``N*K`` when a skewed step outgrows the others),
    so it is sized by the rows computed and not by every pick: no pair is
    dropped and every computed row's arithmetic is the full buffer's.  The
    rows between the held pairs and the buffer's end are gathered and
    masked to zero going in (a kernel leaves them unwritten coming out,
    forward and backward); a pick there or past the buffer has weight zero,
    so the token side never reads its row: it contributes a selected
    zero.  ``stats["held_pairs"]``
    counts the pairs computed here and ``stats["buffer_rows"]`` the size
    taken (absent where the shapes leave one size: the program is then the
    one without a choice); ``tokens_per_expert`` stays ``[E]``, in the
    router's numbering."""
    B, S, C = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N = B * S
    dt = cfg.dtype
    f32 = jnp.float32
    tokens = x.reshape(N, C)
    valid_n = None if valid is None else valid.reshape(N)
    if capacity is None and cfg.capacity_factor is not None:
        capacity = int(max(1, round(cfg.capacity_factor * N * K / E)))
    held, first = cfg.experts_here, cfg.experts_held_first
    share = held < E  # the pairs of absent experts are not computed here
    with jax.named_scope("moe_router"):
        logits = tokens.astype(f32) @ moe["router"]
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, -1, keepdims=True)
        else:
            scores = probs = jax.nn.softmax(logits, axis=-1)
        if "router_bias" in moe:
            # the bias chooses and never weighs
            _, gate_idx = jax.lax.top_k(
                scores + jax.lax.stop_gradient(moe["router_bias"]), K)
            gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
        else:
            gate_vals, gate_idx = jax.lax.top_k(scores, K)
        if cfg.norm_topk_prob:
            if cfg.router_score == "sigmoid":
                gate_vals = gate_vals / (
                    jnp.sum(gate_vals, -1, keepdims=True)
                    + cfg.router_norm_eps)
            else:
                gate_vals = gate_vals / jnp.maximum(
                    jnp.sum(gate_vals, -1, keepdims=True), 1e-9
                )
        if cfg.routed_scaling != 1.0:
            gate_vals = gate_vals * cfg.routed_scaling
    with jax.named_scope("moe_permute"):
        pair_expert = gate_idx.reshape(N * K)
        if share:
            # the held experts sort first, in their own order
            pair_expert = (pair_expert - first) % E
        if valid_n is not None:
            # pads sort behind the last expert's group
            pair_expert = jnp.where(
                jnp.repeat(valid_n, K), pair_expert, E)
        pairs = jnp.arange(N * K, dtype=jnp.int32)
        sorted_expert, order = jax.lax.sort(
            (pair_expert, pairs), num_keys=1, is_stable=True)
        _, inverse = jax.lax.sort((order, pairs), num_keys=1)
        ends = jnp.searchsorted(
            sorted_expert, jnp.arange(E, dtype=jnp.int32), side="right"
        ).astype(jnp.int32)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
        counts = ends - starts  # valid pairs per expert
        if share:
            # the groups end with the last held expert's pairs; the rows
            # behind them (absent experts' pairs, pads) are not computed
            group_sizes, held_pairs = counts[:held], ends[held - 1]
        else:
            # the groups the matmuls run over cover every row: pads ride
            # at the end of the last one
            group_sizes = counts.at[E - 1].add(N * K - ends[E - 1])
            held_pairs = None
        if capacity is not None or valid_n is not None or share:
            keep_sorted = sorted_expert < held
            if capacity is not None:
                rank = pairs - starts[jnp.minimum(sorted_expert, E - 1)]
                keep_sorted = keep_sorted & (rank < capacity)
            gate_vals = jnp.where(
                keep_sorted[inverse].reshape(N, K), gate_vals, 0.0)
        tokens = tokens.astype(dt)

    # wg, wi, wo; no wg in the two-matrix form
    weights = (moe.get("wg"), moe["wi"], moe["wo"])

    def ffn(rows):
        return _expert_ffn(rows, *weights, group_sizes, dt, None)

    bounds = _moe_buffer_bounds(N, K, E, held)
    if len(bounds) == 1:
        out = _routed_sum(ffn, bounds[0], tokens, gate_vals, order,
                          inverse, held_pairs)
    else:
        with jax.named_scope("moe_permute"):
            # the first size ABOVE the held pairs, so that the buffer's
            # last row is a dead one; a step too skewed takes every pick
            size = sum(held_pairs >= rows for rows in bounds[:-1])
        # outside every scope: a branch's instructions name their own
        out = _routed_sum_sized(
            bounds, dt, size, tokens, gate_vals, weights,
            (order, inverse, group_sizes, held_pairs))
    if "shared" in moe:
        with jax.named_scope("moe_shared"):
            shared = _mlp(tokens.astype(dt), moe["shared"], dt)
            if "shared_gate" in moe:
                shared = (shared.astype(f32) * jax.nn.sigmoid(
                    (tokens @ moe["shared_gate"].astype(dt)).astype(f32))
                ).astype(dt)
            out = out + shared
    with jax.named_scope("moe_router"):
        # the two loss terms, over real tokens only
        w = jnp.ones((N,), f32) if valid_n is None else valid_n.astype(f32)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        me = jnp.sum(probs * w[:, None], axis=0) / denom
        # pairs per expert in the router's own numbering, held or not
        per_expert = jnp.roll(counts, first) if share else counts
        if cfg.balance_all_k:
            ce = per_expert.astype(f32) / (denom * K)
        else:
            ce = jnp.sum(
                jax.nn.one_hot(gate_idx[:, 0], E, dtype=f32) * w[:, None],
                axis=0) / denom
        lse = jax.nn.logsumexp(logits, axis=-1)
        if cfg.balance_per_sequence:
            # per row of the batch: f over the K picks, P the mean share
            # of the score, their product summed over experts
            w_bs = w.reshape(B, S)
            rows_b = jnp.maximum(jnp.sum(w_bs, axis=1), 1.0)  # [B]
            taken = jnp.sum(
                jax.nn.one_hot(gate_idx.reshape(B, S, K), E, dtype=f32),
                axis=2) * w_bs[..., None]  # [B, S, E]
            f_be = jnp.sum(taken, axis=1) * (E / K) / rows_b[:, None]
            p_be = jnp.sum(probs.reshape(B, S, E) * w_bs[..., None],
                           axis=1) / rows_b[:, None]
            balance = jnp.mean(jnp.sum(f_be * p_be, axis=-1))
        else:
            balance = E * jnp.sum(me * ce)
        stats = {
            "moe_aux": balance,
            "moe_z": jnp.sum(jnp.square(lse) * w) / denom,
            "experts": gate_idx.reshape(B, S, K),
            "tokens_per_expert": per_expert,
        }
        if share:
            stats["held_pairs"] = held_pairs
        if len(bounds) > 1:
            stats["buffer_rows"] = jnp.asarray(bounds, jnp.int32)[size]
    return out.reshape(B, S, C), stats


def block_apply(
    layer: Dict,
    x: jax.Array,
    cfg: LlamaConfig,
    positions: jax.Array,
    *,
    attn_impl: str = "auto",
    mesh=None,
    segment_ids=None,
    attn_fn=None,  # (h, layer, cfg, positions) -> attn out; overrides
    moe_capacity: Optional[int] = None,
    attn_kind: str = "attention",
    rotary=None,
    memory=None,
    shared_kv=None,
    keep: Optional[str] = None,
    lambda_init: float = 0.0,
) -> tuple:
    """One transformer block: (x, layer) -> (x, stats).  The mixer is the
    one the layer dict holds (:data:`MIXER_KINDS`) — a state-space one
    (``"ssm"``, scope ``ssm``), a gated short convolution (``"conv"``, scope
    ``conv``), a gated delta rule (``"gdn"``, scope ``gdn``; with a decay per
    key channel ``"kda"``, scope ``kda``) or attention
    (the attention leaves, scope ``attention``) — and the MLP the one it
    holds, routed (``"moe"``) or dense (``"mlp"``), each chosen apart from
    the other; a layer of ONE branch (``cfg.one_branch``) holds the mixer's
    half (``ln1`` and the mixer) or the MLP's (``ln2`` and the MLP) and the
    other half is not run: the same wiring with a half absent.  ``stats``
    is what the mixer reports (:func:`_ssm_mixer`:
    ``ssm_state_rms``, ``ssm_decay_min``; :func:`_gdn_mixer`:
    ``gdn_state_rms``, ``gdn_decay_min``; :func:`_kda_mixer`:
    ``kda_state_rms``, ``kda_decay_min``; the other two nothing) with what
    a routed MLP's :func:`_moe_swiglu` reports (``moe_aux``, ``moe_z``,
    ``experts``, ``tokens_per_expert``); empty for a dense attention layer.
    The unit the pipeline stage partitioner groups (``models.llama_pp``).
    ``attn_fn`` swaps the attention implementation (the KV-cache decoder
    plugs in here, so train and decode share one block wiring).  The two
    attention kinds hold the same leaves, so ``attn_kind`` (of
    :data:`ATTENTION_KINDS`) says which an attention layer is, and
    ``rotary`` hands it its kind's ``(cos, sin)`` where the step built
    one, ``lambda_init`` its differential attention's (``cfg.diff_attention``
    of its index).  What crosses layers: ``memory`` is handed to a "gmu"
    layer and ``shared_kv`` to a "cross_attention" layer, and the layer that
    MAKES one returns it under ``stats["carried"]`` where ``keep`` names it
    (``"memory"``: a "mamba1" layer's scan output in ``cfg.dtype``;
    ``"shared_kv"``: an attention layer's ``(k, v)``) — inputs and outputs
    of the block, so of its checkpoint under ``cfg.remat_block``."""
    # The scopes (``attention``, ``mlp``, and the routed block's four:
    # ``moe_router`` with its norm, ``moe_permute``, ``moe_experts``,
    # ``moe_combine`` with the residual add) go into every instruction's
    # ``op_name`` of the compiled step: ``accelerate.program_summary``
    # reads them back, outermost scope only, hence siblings.  A branch's
    # output norm (``cfg.branch_norm``) sits in its branch's scope, under
    # ``branch_norm`` there.
    def add(x, branch):
        """``x + residual_multiplier * branch``; at 1.0 the add alone."""
        if cfg.residual_multiplier != 1.0:
            branch = branch * cfg.residual_multiplier
        return x + branch

    def out_norm(branch, gain: str):
        """The norm on a branch's output where ``cfg.branch_norm``."""
        if not cfg.branch_norm:
            return branch
        with jax.named_scope("branch_norm"):
            return rmsnorm(branch, layer[gain], eps=cfg.rms_eps)

    stats, carried = {}, {}
    if "ln1" in layer:  # the mixer's half; a one-branch MLP layer has none
        named, kind = next(
            (row for row in MIXER_KINDS.items() if row[1] in layer),
            ("attention", "attention"))
        if kind != "attention" and (
                segment_ids is not None or attn_fn is not None):
            raise NotImplementedError(
                f"block_apply: a {named!r} layer with segment_ids or a "
                "custom attn_fn: the scan and the convolution know no "
                "document boundary and no cache")
        # outermost ``ssm`` / ``conv`` / ``gdn`` / ``kda`` / ``s6`` / ``gmu``
        # as ``attention`` is for the other kind; the mixer's own scopes nest
        # inside it (``subscopes``)
        with jax.named_scope(kind):
            h = _norm(x, layer["ln1"], cfg)
            if kind == "ssm":
                mixed, stats = _ssm_mixer(h, layer["ssm"], cfg)
            elif kind == "s6":
                mixed, stats, y = _s6_mixer(h, layer["s6"], cfg)
                if keep == "memory":
                    carried["memory"] = y.astype(cfg.dtype)
            elif kind == "gmu":
                mixed = _gmu_mixer(h, layer["gmu"], memory, cfg)
            elif kind == "gdn":
                mixed, stats = _gdn_mixer(h, layer["gdn"], cfg)
            elif kind == "kda":
                mixed, stats = _kda_mixer(h, layer["kda"], cfg)
            elif kind == "conv":
                mixed = _conv_mixer(h, layer["conv"], cfg)
            elif attn_fn is not None:
                mixed = attn_fn(h, layer, cfg, positions)
            else:
                mixed = _attention(
                    h, layer, cfg, positions, attn_impl, mesh, segment_ids,
                    attn_kind, rotary, shared_kv, lambda_init,
                    with_kv=keep == "shared_kv")
                if keep == "shared_kv":
                    mixed, carried["shared_kv"] = mixed
            x = add(x, out_norm(mixed, "ln1_out"))
    if carried:
        stats = dict(stats, carried=carried)
    if "ln2" not in layer:  # a one-branch mixer layer: no MLP's half
        return x, stats
    if "moe" in layer:
        with jax.named_scope("moe_router"):
            h = _norm(x, layer["ln2"], cfg)
        delta, routed = _moe_swiglu(
            h, layer["moe"], cfg, capacity=moe_capacity,
            valid=None if segment_ids is None else segment_ids >= 0,
        )
        stats = dict(stats, **routed)
        with jax.named_scope("moe_combine"):
            x = add(x, out_norm(delta, "ln2_out"))
        return x, stats
    with jax.named_scope("mlp"):
        h = _norm(x, layer["ln2"], cfg)
        x = add(x, out_norm(_mlp(h, layer["mlp"], cfg.dtype), "ln2_out"))
    return x, stats


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """[B, S] segment ids -> [B, S] within-segment positions (rope resets
    at every packed-sequence boundary)."""
    S = segment_ids.shape[-1]
    idx = jnp.arange(S)
    change = jnp.concatenate(
        [
            jnp.ones(segment_ids.shape[:-1] + (1,), bool),
            segment_ids[..., 1:] != segment_ids[..., :-1],
        ],
        axis=-1,
    )
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(change, idx, 0), axis=-1
    )
    return idx - start


def forward_hidden(
    params: Dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    attn_impl: str = "auto",
    mesh=None,
    segment_ids=None,
    next_tokens=None,
) -> tuple:
    """tokens [B, S] -> (final-norm hidden [B, S, D], aux dict).

    The aux dict always holds ``moe_aux`` (the load-balance terms summed
    over the routed layers; 0 for a dense model).  A routed model adds
    ``moe_z`` (the z-loss terms, summed), ``moe_experts`` (``{layer
    index: int32 [B, S, K]}``, the experts each routed layer's router
    took — under block remat too) and ``moe_tokens_per_expert`` (int32
    ``[routed layers, E]``).

    ``segment_ids`` [B, S] enables packed-sequence training: attention is
    restricted to same-segment pairs (flash-kernel mask) and rope
    positions reset at each segment boundary.

    A looped model (``cfg.loop_passes`` = T > 1) runs the layers T times
    on the same weights, each pass ended by the final norm, and returns
    the T normed streams stacked, ``[T, B, S, D]`` (pass t's is pass
    t+1's input), with ``aux["exit_logits"]`` (float32 ``[T, B, S]``, the
    exit gate's logit on each).  Each block APPLICATION is rematerialised
    and named ``block_out`` on its own.

    What block remat (``cfg.remat_block``) keeps of a block application
    beside its inputs: the flash kernel's output and log-sum-exp
    (``ops.flash_attention.SAVED_NAMES``) and the delta rule's kernel's
    output, final state and entering states
    (``ops.gated_delta.SAVED_NAMES``; under a per-channel decay
    ``CHANNEL_SAVED_NAMES``) and the selective scan's output and entering
    states (``ops.selective_scan.SAVED_NAMES``) — what costs as much to
    recompute as
    to compute and is small to keep, so neither forward kernel runs again
    in front of the block's backward.  Everything else of the block does.

    With ``cfg.mtp_layers`` and ``next_tokens`` ([B, S], token i+1 under
    position i: the targets) the multi-token-prediction block runs too
    (scope ``mtp``): ``u_i = [rms(embed(t_{i+1}), ln_e); rms(z_i, ln_h)]
    @ w_eh`` with ``z`` the last layer's output BEFORE the final norm, one
    block of its own, its own final norm; the result is the two normed
    streams stacked, ``[2, B, S, D]`` (main, then the block's, which
    predicts token i+2).  Its routed block's statistics come last in every
    per-block entry of the aux dict, and its experts under the key
    ``"mtp"``.  A model with a share of the experts adds
    ``moe_held_pairs`` (int32 ``[routed blocks]``) and, where its blocks
    choose their buffer (:func:`_moe_buffer_bounds`), ``moe_buffer_rows``
    (the same shape: the rows each block's sorted buffer took).

    A model with state-space layers (``cfg.layer_types``) adds
    ``ssm_state_rms`` (float32 ``[mamba layers]``: the RMS of the state each
    layer's scan leaves) and ``ssm_decay_min`` (the least decay over a
    chunk, any layer and head), one with delta-rule layers
    ``gdn_state_rms`` and ``gdn_decay_min`` likewise, one with "kda" layers
    ``kda_state_rms`` and ``kda_decay_min``, one with "mamba1" layers
    ``s6_state_rms`` and ``s6_decay_min``.  What crosses layers
    (``cfg.memory_layer``'s scan output, ``cfg.shared_kv_layer``'s keys and
    values) is kept here from the block that makes it, handed to every later
    block that reads it (:func:`block_apply`) and returned under
    ``aux["carried"]`` (``{"memory": [B, S, s6_d_inner], "shared_kv": (k,
    v)}``); its gradient is the sum over its readers.  The embedding's rows
    are scaled by
    ``cfg.embedding_multiplier`` here; the head's side of a tied or scaled
    head is :func:`head_operands`'."""
    B, S = tokens.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    if segment_ids is not None:
        positions = segment_positions(segment_ids)
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    moe_aux = jnp.zeros((), jnp.float32)
    moe_z = jnp.zeros((), jnp.float32)
    experts, per_expert, held_pairs, buffer_rows = {}, [], [], []
    # what the recurrent mixers report, by their scope
    state_rms = {"ssm": [], "gdn": [], "kda": [], "s6": []}
    decay_min = {"ssm": [], "gdn": [], "kda": [], "s6": []}

    def collect(block, stats):
        """A routed block's statistics into the aux dict's entries."""
        nonlocal moe_aux, moe_z
        moe_aux = moe_aux + stats["moe_aux"]
        moe_z = moe_z + stats["moe_z"]
        experts[block] = stats["experts"]
        per_expert.append(stats["tokens_per_expert"])
        if "held_pairs" in stats:
            held_pairs.append(stats["held_pairs"])
        if "buffer_rows" in stats:
            buffer_rows.append(stats["buffer_rows"])

    def applier(**of_the_kind):
        fn = functools.partial(
            block_apply, attn_impl=attn_impl, mesh=mesh,
            segment_ids=segment_ids, **of_the_kind)
        if cfg.remat_block:
            fn = jax.checkpoint(
                fn, static_argnums=(2,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *FLASH_SAVED_NAMES, *GDN_SAVED_NAMES, *KDA_SAVED_NAMES,
                    *S6_SAVED_NAMES))
        return fn

    apply = applier()
    appliers = {}

    def apply_layer(i: int):
        """The block as layer ``i`` applies it: what is static of it — its
        attention kind where that is not the plain one (the kinds share a
        block), what it keeps for later layers (``cfg.memory_layer``,
        ``cfg.shared_kv_layer``), its ``lambda_init`` — bound once per
        distinct set."""
        kind, static = cfg.mixer_kind(i), {}
        if kind in ATTENTION_KINDS and kind != "attention":
            static["attn_kind"] = kind
        if i == cfg.memory_layer:
            static["keep"] = "memory"
        elif i == cfg.shared_kv_layer:
            static["keep"] = "shared_kv"
        if cfg.diff_attention and kind in ATTENTION_KINDS:
            static["lambda_init"] = cfg.diff_attention[i]
        if not static:
            return apply
        key = tuple(sorted(static.items()))
        if key not in appliers:
            appliers[key] = applier(**static)
        return appliers[key]

    # a kind with a rotary table of its own is handed it, built here once
    # for all its layers (none for a kind without rotary position:
    # ``cfg.unrotated``)
    carried = {}  # what crosses layers: "memory", "shared_kv"
    with jax.named_scope("rotary"):
        tables = {kind: {"rotary": _rotary_table(
            positions, rotary, cfg.rotary_dim)}
            for kind, rotary in cfg.rotary_by_kind if rotary is not None}
    streams, exit_logits = [], []
    for _ in range(cfg.loop_passes):
        for i, layer in enumerate(params["layers"]):
            kind = cfg.mixer_kind(i)
            handed = dict(tables.get(kind, {}))
            if kind == "gmu":
                handed["memory"] = carried["memory"]
            elif kind == "cross_attention":
                handed["shared_kv"] = carried["shared_kv"]
            x, stats = apply_layer(i)(layer, x, cfg, positions, **handed)
            carried.update(stats.pop("carried", {}))
            # Identity unless a remat policy references the name: lets
            # Strategy(remat="offload") park the inter-block residual
            # stream in host DRAM (reference
            # selective_offloading_checkpoint.py:252) while everything
            # inside the block rematerializes.
            x = checkpoint_name(x, "block_out")
            if "moe_aux" in stats:
                collect(i, stats)
            for scope in state_rms:
                if f"{scope}_state_rms" in stats:
                    state_rms[scope].append(stats[f"{scope}_state_rms"])
                    decay_min[scope].append(stats[f"{scope}_decay_min"])
        z = x  # the last layer's output, what the prediction block reads
        with jax.named_scope("final_norm"):
            x = _norm(x, params["ln_f"], cfg)
        if cfg.loop_passes > 1:
            streams.append(x)
            with jax.named_scope("exit_gate"):
                gate = params["exit_gate"]
                # a [.., D] x [D] product in float32, exactly: 2 D
                # operations a token, and the loss's weights hang on it
                exit_logits.append(jnp.einsum(
                    "bsd,d->bs", x.astype(jnp.float32), gate["w"],
                    precision="highest") + gate["b"])
    if cfg.mtp_layers and next_tokens is not None:
        with jax.named_scope("mtp"):
            mtp = params["mtp"]
            u = jnp.concatenate([
                rmsnorm(params["embed"].astype(dt)[next_tokens],
                        mtp["ln_e"], eps=cfg.rms_eps),
                rmsnorm(z, mtp["ln_h"], eps=cfg.rms_eps),
            ], axis=-1) @ mtp["w_eh"].astype(dt)
            u, stats = apply(mtp["block"], u, cfg, positions)
            u = checkpoint_name(u, "block_out")
            if stats:
                collect("mtp", stats)
            u = rmsnorm(u, mtp["ln_f"], eps=cfg.rms_eps)
        x = jnp.stack([x, u])
    out_aux = {"moe_aux": moe_aux}
    if carried:
        out_aux["carried"] = carried
    if streams:
        x = jnp.stack(streams)
        out_aux["exit_logits"] = jnp.stack(exit_logits)
    if per_expert:
        out_aux.update(moe_z=moe_z, moe_experts=experts,
                       moe_tokens_per_expert=jnp.stack(per_expert))
    if held_pairs:
        out_aux["moe_held_pairs"] = jnp.stack(held_pairs)
    if buffer_rows:
        out_aux["moe_buffer_rows"] = jnp.stack(buffer_rows)
    for scope, rms in state_rms.items():
        if rms:
            out_aux[f"{scope}_state_rms"] = jnp.stack(rms)
            out_aux[f"{scope}_decay_min"] = jnp.min(
                jnp.stack(decay_min[scope]))
    return x, out_aux


def forward(
    params: Dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    attn_impl: str = "auto",
    mesh=None,
    segment_ids=None,
    next_tokens=None,
) -> tuple:
    """tokens [B, S] -> (logits [B, S, vocab] fp32, aux dict); of a
    looped model every pass's logits, ``[T, B, S, vocab]``; with
    ``next_tokens`` of a model with a prediction block the main logits
    and the block's, ``[2, B, S, vocab]``."""
    x, aux = forward_hidden(
        params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
        segment_ids=segment_ids, next_tokens=next_tokens,
    )
    with jax.named_scope("lm_head_loss"):  # the head's matmul is the
        # unfused loss's larger half
        x, head = head_operands(params, x, cfg)
        logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux


def head_operands(params: Dict, x: jax.Array, cfg: LlamaConfig) -> tuple:
    """``(x, head [D, V])`` as the head's matmul takes them: the float32
    head is ``lm_head``, or ``embed`` transposed where
    ``cfg.tie_word_embeddings`` (the one leaf's second use); ``logits /
    cfg.logits_scaling`` is folded into the normed stream, so that the
    fused loss never sees the logits (Granite: 1/8, exact)."""
    head = (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])
    if cfg.logits_scaling != 1.0:
        x = x * (1.0 / cfg.logits_scaling)
    return x, head


def uses_fused_lm_head(cfg: LlamaConfig) -> bool:
    """Default policy for routing the loss through the chunked fused
    lm-head cross-entropy (single source of truth — bench reporting and
    ``loss_fn`` must agree on what was actually measured)."""
    return cfg.vocab_size >= 4096


def split_batch(batch: Dict[str, jax.Array]) -> tuple:
    """{"tokens": [B,S+1]} or {"tokens","targets"} -> (tokens, targets)."""
    if "targets" in batch:
        return batch["tokens"], batch["targets"]
    return batch["tokens"][:, :-1], batch["tokens"][:, 1:]


def loss_fn(
    params: Dict,
    batch: Dict[str, jax.Array],  # {"tokens": [B,S+1]} or tokens/targets
    cfg: LlamaConfig,
    *,
    attn_impl: str = "auto",
    mesh=None,
    moe_aux_weight: float = 1e-2,
    moe_z_weight: float = 0.0,
    fused_lm_head: Optional[bool] = None,
    metrics: bool = False,
    mtp_weight: float = 0.3,
) -> jax.Array:
    """Next-token loss, plus ``moe_aux_weight`` x the routed layers'
    load-balance terms and ``moe_z_weight`` x their router z-losses.
    With ``metrics`` a routed model returns ``(loss, counters)`` with the
    counters of its routed blocks (``moe_tokens_per_expert`` int32
    ``[routed layers, E]``, ``moe_aux``, ``moe_z``): ``accelerate()``'s
    step hands them out beside ``loss`` and ``grad_norm``.  A dense model
    returns the scalar alone either way; one with state-space layers
    returns ``ssm_state_rms`` ``[mamba layers]`` and ``ssm_decay_min``, one
    with delta-rule layers ``gdn_state_rms`` and ``gdn_decay_min``, one
    with "kda" layers ``kda_state_rms`` and ``kda_decay_min``, one with
    "mamba1" layers ``s6_state_rms`` and ``s6_decay_min``.
    The head is ``lm_head``, or ``embed`` transposed where
    ``cfg.tie_word_embeddings``, behind ``1 / cfg.logits_scaling``
    (:func:`head_operands`).

    A looped model's loss is the expectation over the pass a token exits
    at: ``mean_r [sum_t p_t[r] * ce_t[r] - beta * H(p[r])]`` with ``p``
    from :func:`exit_distribution` of the gate's logits (float32),
    ``ce_t`` pass t's cross-entropy and ``beta = cfg.exit_gate_beta``.
    The T x N rows go through the head ONCE, with row weights
    ``p_t[r] / N`` that the gate's gradient flows through.  With
    ``metrics`` it returns ``(loss, {"loop_ce": [T], "loop_exit_prob":
    [T], "loop_exit_entropy": scalar})``, means over the real tokens.

    A model with a prediction block (``cfg.mtp_layers``) adds
    ``mtp_weight`` x the mean cross-entropy of the block's stream against
    the token TWO ahead, over the positions that have one
    (:func:`mtp_loss`; counters ``main_ce``, ``mtp_ce``).  Further
    counters of a routed model, where the setting is on: ``moe_seq_aux``
    in place of ``moe_aux`` (``cfg.balance_per_sequence``),
    ``moe_held_pairs`` (a share of the experts), ``moe_buffer_rows``
    (a share whose blocks choose their buffer's size),
    ``moe_router_bias_abs_max`` and, under :data:`RULE_UPDATES`, the
    selection biases' next values (``cfg.router_bias_rate``; scope
    ``router_bias``), which ``accelerate()``'s step writes into the
    leaves :func:`rule_leaves` names.
    ``fused_lm_head`` (default: auto — on for large
    vocabs) routes the projection through the chunked fused lm-head
    cross-entropy so the [B, S, vocab] logits never hit HBM.  A
    ``batch["segment_ids"]`` entry ([B, S] or [B, S+1] matching tokens)
    enables packed-sequence training.  Prefer the [B, S+1] form (what
    ``data.packing.pack_sequences`` returns at ``seq_len = S+1``): it is
    lossless, while the [B, S] form cannot see the last position's
    target segment and conservatively masks that token's loss."""
    tokens, targets = split_batch(batch)
    seg_full = batch.get("segment_ids")
    seg = valid = None
    if seg_full is not None:
        S = tokens.shape[-1]
        if seg_full.shape[-1] == S + 1:
            seg = seg_full[:, :-1]  # align with the input tokens
            # A position's target is the NEXT token: drop pairs that
            # cross a packed-sequence boundary — and padding (segment
            # < 0, e.g. the packer's -1 fill), or pad->pad pairs would
            # train "predict pad from pad" and deflate the loss.
            valid = (
                (seg_full[:, 1:] == seg_full[:, :-1])
                & (seg_full[:, :-1] >= 0)
            ).astype(jnp.float32)
        else:
            seg = seg_full
            # [B, S] form can't see the target of the LAST position (it
            # lives at S, outside this view) — mask it conservatively;
            # pass the [B, S+1] form to keep that token's loss.
            valid = jnp.concatenate(
                [
                    (
                        (seg[:, 1:] == seg[:, :-1]) & (seg[:, :-1] >= 0)
                    ).astype(jnp.float32),
                    jnp.zeros(seg.shape[:-1] + (1,), jnp.float32),
                ],
                axis=-1,
            )
    if fused_lm_head is None:
        fused_lm_head = uses_fused_lm_head(cfg)
    if cfg.loop_passes > 1:
        x, aux = forward_hidden(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg,
        )
        x, head = head_operands(params, x, cfg)
        loss, counters = exit_expectation_loss(
            x, aux["exit_logits"], head, targets, cfg,
            valid=valid, fused_lm_head=fused_lm_head)
        return (loss, counters) if metrics else loss
    counters = {}
    if cfg.mtp_layers:
        x, aux = forward_hidden(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg, next_tokens=targets,
        )
        x, head = head_operands(params, x, cfg)
        ce, counters = mtp_loss(
            x, head, targets, cfg, valid=valid,
            fused_lm_head=fused_lm_head, mtp_weight=mtp_weight)
    elif fused_lm_head:
        x, aux = forward_hidden(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg,
        )
        with jax.named_scope("lm_head_loss"):
            # The row weights are known here, so the reduced op forms
            # the head's gradients in its forward scan.
            x, head = head_operands(params, x, cfg)
            ce = linear_softmax_cross_entropy_sum(
                x, head.astype(cfg.dtype), targets,
                None if valid is None
                else valid / jnp.maximum(jnp.sum(valid), 1.0),
            )
    else:
        logits, aux = forward(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg,
        )
        with jax.named_scope("lm_head_loss"):
            per_tok = softmax_cross_entropy(logits, targets)
            if valid is not None:
                ce = jnp.sum(per_tok * valid) / jnp.maximum(
                    jnp.sum(valid), 1.0)
            else:
                ce = jnp.mean(per_tok)
    loss = ce + moe_aux_weight * aux["moe_aux"]
    if "moe_z" in aux:
        loss = loss + moe_z_weight * aux["moe_z"]
    if not metrics:
        return loss
    for name in ("ssm_state_rms", "ssm_decay_min", "gdn_state_rms",
                 "gdn_decay_min", "kda_state_rms", "kda_decay_min",
                 "s6_state_rms", "s6_decay_min"):
        if name in aux:
            counters[name] = aux[name]
    if "moe_z" in aux:
        counters.update(
            moe_tokens_per_expert=aux["moe_tokens_per_expert"],
            moe_z=aux["moe_z"])
        counters["moe_seq_aux" if cfg.balance_per_sequence
                 else "moe_aux"] = aux["moe_aux"]
        for name in ("moe_held_pairs", "moe_buffer_rows"):
            if name in aux:
                counters[name] = aux[name]
        if cfg.router_bias_rate is not None:
            with jax.named_scope("router_bias"):
                updates = router_bias_rule(
                    params, aux["moe_tokens_per_expert"], cfg)
                counters[RULE_UPDATES] = updates
                counters["moe_router_bias_abs_max"] = jnp.max(jnp.stack(
                    [jnp.max(jnp.abs(b)) for b in updates.values()]))
    return (loss, counters) if counters else loss


#: the key of a loss function's metrics under which ``accelerate()``'s step
#: looks for the next values of the leaves a rule moves
#: (``parallel.accelerate.RULE_UPDATES``; the same string)
RULE_UPDATES = "rule_updates"


def _router_bias_paths(cfg: LlamaConfig) -> list:
    """``(key path string, path into params)`` of every selection bias, in
    the order of ``aux["moe_tokens_per_expert"]``'s rows."""
    if cfg.router_bias_rate is None or cfg.num_experts <= 0:
        return []
    paths = [("layers", i, "moe", "router_bias")
             for i in range(cfg.n_layer) if cfg.is_moe_layer(i)]
    if cfg.mtp_layers:
        paths.append(("mtp", "block", "moe", "router_bias"))
    keys = jax.tree_util
    return [(keys.keystr(tuple(
        keys.SequenceKey(k) if isinstance(k, int) else keys.DictKey(k)
        for k in path)), path) for path in paths]


def rule_leaves(cfg: LlamaConfig) -> tuple:
    """The leaves of ``params`` that a rule moves and no gradient does, as
    ``jax.tree_util.keystr`` writes their paths: the routers' selection
    biases.  A loss function handed to ``accelerate()`` carries them as its
    ``rule_leaves`` attribute; the optimizer then holds no moment for them
    and decays nothing of them."""
    return tuple(name for name, _ in _router_bias_paths(cfg))


def router_bias_rule(params: Dict, tokens_per_expert: jax.Array,
                     cfg: LlamaConfig) -> Dict:
    """``{leaf path: b + rate * sign(mean(c) - c)}`` for every routed
    block's selection bias ``b`` and the pairs ``c [E]`` the step routed
    to each expert (``tokens_per_expert [routed blocks, E]``): an expert
    under the mean load becomes likelier to be chosen, one over it less
    (DeepSeek-V3's auxiliary-loss-free balancing)."""
    out = {}
    for row, (name, path) in enumerate(_router_bias_paths(cfg)):
        bias = params
        for key in path:
            bias = bias[key]
        load = tokens_per_expert[row].astype(jnp.float32)
        out[name] = jax.lax.stop_gradient(
            bias + cfg.router_bias_rate * jnp.sign(jnp.mean(load) - load))
    return out


def mtp_loss(x, lm_head, targets, cfg: LlamaConfig, *, valid=None,
             fused_lm_head: bool = True, mtp_weight: float = 0.3) -> tuple:
    """The cross-entropy of a model with a prediction block from what
    :func:`forward_hidden` returned (``x [2, B, S, D]``: the main stream,
    the block's) -> ``(L_main + mtp_weight * L_mtp, counters)``.  Position
    i of the block's stream predicts token i+2, the NEXT position's
    target; the last position has none and weighs nothing, nor does a
    position whose next is no real token (``valid`` [B, S]).  Both sets of
    rows go through the head in ONE call, with row weights."""
    with jax.named_scope("lm_head_loss"):
        real = (jnp.ones(targets.shape, jnp.float32) if valid is None
                else valid)
        two_ahead = real * jnp.concatenate(
            [real[:, 1:], jnp.zeros_like(real[:, :1])], axis=1)
        share = jnp.stack([
            real / jnp.maximum(jnp.sum(real), 1.0),
            two_ahead / jnp.maximum(jnp.sum(two_ahead), 1.0)])
        labels = jnp.stack([targets, jnp.roll(targets, -1, axis=1)])
        weights = share * jnp.array([1.0, mtp_weight], jnp.float32)[
            :, None, None]
        if fused_lm_head:
            ce, rows = linear_softmax_cross_entropy_sum(
                x, lm_head.astype(cfg.dtype), labels, weights,
                with_row_losses=True)
            per_tok = rows.reshape(labels.shape)
        else:
            logits = (x @ lm_head.astype(cfg.dtype)).astype(jnp.float32)
            per_tok = softmax_cross_entropy(logits, labels)
            ce = jnp.sum(per_tok * weights)
        each = jnp.sum(jax.lax.stop_gradient(per_tok) * share, axis=(1, 2))
    return ce, {"main_ce": each[0], "mtp_ce": each[1]}


def exit_distribution(exit_logits: jax.Array) -> jax.Array:
    """Gate logits ``[T, ...]`` -> the distribution ``p [T, ...]`` over the
    pass a token exits at, float32: with ``lam_t = sigmoid(logit_t)``,
    ``p_t = lam_t * prod_{j<t} (1 - lam_j)`` for ``t < T`` and ``p_T`` the
    remainder ``prod_{j<T} (1 - lam_j)`` (the last pass's own logit is not
    used), so that ``p`` sums to one whatever the logits."""
    lam = jax.nn.sigmoid(exit_logits[:-1].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)  # still running after pass t
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([lam * before, stay[-1:]])


def exit_expectation_loss(x, exit_logits, lm_head, targets,
                          cfg: LlamaConfig, *, valid=None,
                          fused_lm_head: bool = True) -> tuple:
    """A looped model's loss from what :func:`forward_hidden` returned
    (``x [T, B, S, D]``, ``exit_logits [T, B, S]``; :func:`loss_fn` has
    the formula) -> ``(loss, counters)``.  ``valid`` [B, S] weights the
    real tokens.  Scopes stay siblings: ``exit_gate`` holds the
    distribution, the row weights and the entropy term, ``lm_head_loss``
    the head."""
    with jax.named_scope("exit_gate"):
        p = exit_distribution(exit_logits)
        # each real token's share of the mean
        share = (jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
                 if valid is None
                 else valid / jnp.maximum(jnp.sum(valid), 1.0))
        # p log p -> 0 as p -> 0, with a finite gradient
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
        mean_entropy = jnp.sum(entropy * share)
    labels = jnp.broadcast_to(targets, p.shape)
    with jax.named_scope("lm_head_loss"):
        if fused_lm_head:
            ce, rows = linear_softmax_cross_entropy_sum(
                x, lm_head.astype(cfg.dtype), labels, p * share,
                with_row_losses=True)
            per_tok = rows.reshape(labels.shape)
        else:
            logits = (x @ lm_head.astype(cfg.dtype)).astype(jnp.float32)
            per_tok = softmax_cross_entropy(logits, labels)
            ce = jnp.sum(per_tok * p * share)
    loss = ce - cfg.exit_gate_beta * mean_entropy
    per_tok = jax.lax.stop_gradient(per_tok)
    return loss, {
        "loop_ce": jnp.sum(per_tok * share, axis=(1, 2)),
        "loop_exit_prob": jnp.sum(p * share, axis=(1, 2)),
        "loop_exit_entropy": mean_entropy,
    }


#: What ``forward_hidden`` / ``loss_fn`` alone compute: ``(setting, the
#: value every other path computes, what it is)``, in the order refused.
#: The pipeline split, the KV caches and their decoder and the HF layout
#: table apply each attention layer once, project q, k and v from
#: ``wq``/``wk``/``wv`` under rotary position at ``1 / sqrt(head_dim)``,
#: hold every expert, and know one head of their own and no scalar on the
#: stream.  For ``layer_types`` the value is the one kind of layer they
#: know (a state-space or a convolution mixer's decode needs recurrent
#: state beside keys and values, a window kind beside a full one a cache of
#: two entry sizes).  A new architecture adds a row.
TRAINING_PATH_ONLY = (
    ("loop_passes", 1, "layers applied more than once"),
    ("branch_norm", False, "a norm on each branch's output"),
    ("exit_gate_beta", None, "the exit gate"),
    ("kv_lora_rank", 0, "latent attention"),
    ("experts_held", 0, "a share of the experts"),
    ("mtp_layers", 0, "the multi-token-prediction block"),
    ("layer_types", "attention",
     "a layer whose mixer is not attention over every earlier position"),
    ("rope", True, "attention without rotary position"),
    ("attention_multiplier", None, "attention at a stated scale"),
    ("embedding_multiplier", 1.0, "a scalar on the embedding"),
    ("residual_multiplier", 1.0, "a scalar on each branch"),
    ("logits_scaling", 1.0, "a scalar on the logits"),
    ("tie_word_embeddings", False, "a head tied to the embedding"),
    ("attn_head_dim", 0, "a head size that is not d_model / n_head"),
    ("attn_output_gate", False, "a gate on the attention output"),
    ("partial_rotary_factor", 1.0, "rotation of a part of each head"),
    ("norm_plus_one", False, "gains stored as 1 + w"),
    ("shared_expert_gate", False, "a gate on the shared expert"),
    ("one_branch", False, "layers that are one branch each"),
    ("mlp_form", "swiglu", "an MLP that is not SwiGLU"),
    ("rotary_by_kind", (),
     "a rotary table of a kind of layer's own, or a kind without position"),
    ("memory_layer", None, "a scan output that later layers read"),
    ("shared_kv_layer", None, "keys and values that later layers attend"),
    ("diff_attention", (), "differential attention"),
    ("norm_form", "rmsnorm", "a norm that is not RMSNorm"),
    ("attn_bias", False, "biases on the attention projections"),
)


def refuse_training_path_only(cfg: LlamaConfig, where: str) -> None:
    """``ValueError`` naming the first setting of
    :data:`TRAINING_PATH_ONLY` that ``where`` does not compute: run on
    such a config it would compute another model without a word."""
    for name, computed, what in TRAINING_PATH_ONLY:
        value = getattr(cfg, name)
        if name == "layer_types":
            kind = next((k for k in MIXER_KINDS
                         if k != computed and k in value), None)
            if kind is None:
                continue
            met = sum(k == kind for k in value)
            said = (f"layer_types with a {kind!r} entry ({met} of "
                    f"{cfg.n_layer} layers)")
        elif value == computed:
            continue
        elif name == "kv_lora_rank":
            # the forms of latent attention, each by its name
            said = (f"kv_lora_rank={value!r} with q_lora_rank="
                    f"{cfg.q_lora_rank}, v_head_dim={cfg.v_head_dim} under "
                    f"{cfg.head_dim}-wide keys and rope={cfg.rope}")
            what += "".join(text for met, text in (
                (cfg.q_lora_rank == 0, ", its queries from one matrix"),
                (cfg.v_head_dim != cfg.head_dim,
                 ", values of another width than the keys"),
                (not cfg.rope, ", no rotary position on either part"))
                if met)
        else:
            said = f"{name}={value!r}"
        raise ValueError(
            f"{where} does not compute {said}: {what}, training path only "
            "(llama.forward_hidden / loss_fn)")


def program_facts(cfg: LlamaConfig, seq_len: int) -> Dict:
    """What the compiled step's text cannot say of a model whose layers
    are not all attention layers, for the ``accelerate.program`` event (a
    loss function carries it as its ``program_facts`` attribute): how many
    layers are of each kind, and the chunks the scan carries a state over
    in a sequence of ``seq_len``.  Where the layers are ONE branch each
    (``cfg.one_branch``) the MLP kinds are layers of their own and are
    counted too (``mlp_layers``, ``moe_layers``), with the form of the MLPs
    (``mlp_form``) and, of a routed model, the backend its experts' grouped
    matmuls take at their widths on this device (``moe_expert_backend``:
    ``ops.grouped_matmul.backend_for``; the fall-back buffer of every pick
    takes the reference whatever the widths).  A model with
    "window_attention" layers says how many they are
    (``window_attention_layers``, of ``attention_layers``) and the pairs one
    sequence attends in a layer of each kind
    (``attn_window_pairs_per_sequence``, ``attn_full_pairs_per_sequence``),
    and one of whose attention kinds ONE carries no rotary position
    (``rotary_by_kind``) how many layers that is
    (``unrotated_attention_layers``).  A model with "mamba1", "gmu" or
    "cross_attention" layers counts them (``s6_layers``, ``gmu_layers``,
    ``cross_attention_layers``) and says what its blocks hand on, in bytes a
    sequence (``memory_bytes_per_sequence``,
    ``shared_kv_bytes_per_sequence``).  Empty for every other model."""
    facts = {f"{scope}_layers": cfg.layers_of(kind)
             for kind, scope in MIXER_KINDS.items()
             if kind not in ATTENTION_KINDS and cfg.layers_of(kind)}
    if cfg.window_layers:
        # the kinds run under the ``attention`` scope: the count of the
        # window kind's layers beside that of all, and the (query, key)
        # pairs a layer of each kind attends in a sequence, by the scope
        # around its flash call
        facts["window_attention_layers"] = cfg.window_layers
        for kind, scope in ATTENTION_KINDS.items():
            if kind != "cross_attention" or cfg.cross_layers:
                facts[f"{scope}_pairs_per_sequence"] = attended_pairs(
                    seq_len, cfg.window_of(kind))
    if cfg.cross_layers:
        facts["cross_attention_layers"] = cfg.cross_layers
    # what crosses layers, in bytes a sequence: the memory in ``cfg.dtype``,
    # the shared keys and values (``n_kv_head`` heads of ``head_dim`` each)
    size = jnp.dtype(cfg.dtype).itemsize
    if cfg.memory_layer is not None:
        facts["memory_bytes_per_sequence"] = seq_len * cfg.s6_d_inner * size
    if cfg.shared_kv_layer is not None:
        facts["shared_kv_bytes_per_sequence"] = (
            2 * seq_len * cfg.n_kv_head * cfg.head_dim * size)
    if cfg.rope and cfg.unrotated_layers:
        facts["unrotated_attention_layers"] = cfg.unrotated_layers
    if facts:
        facts["attention_layers"] = cfg.attention_layers
    # the chunks a recurrent mixer's scan carries its state over
    for scope, chunk in (("ssm", cfg.mamba_chunk_size), ("gdn", GDN_CHUNK),
                         ("kda", KDA_CHUNK), ("s6", S6_CHUNK)):
        if f"{scope}_layers" in facts:
            facts[f"{scope}_chunks_per_sequence"] = -(-seq_len // chunk)
    if cfg.one_branch:
        facts.update({f"{kind}_layers": cfg.layer_types.count(kind)
                      for kind in MLP_KINDS if kind in cfg.layer_types},
                     attention_layers=cfg.attention_layers,
                     mlp_form=cfg.mlp_form)
        if cfg.moe_layers:
            facts["moe_expert_backend"] = expert_backend_for(
                cfg.dtype, cfg.d_model, cfg.expert_width)
    return facts


def attended_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one causal sequence: query ``t`` attends the
    keys ``s`` with ``0 <= t - s < window`` (``window`` 0: every ``s <=
    t``)."""
    if window <= 0 or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def num_params(params: Dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: LlamaConfig) -> float:
    """~6 * non-embedding params + attention FLOPs (for MFU accounting).
    A looped model runs every layer and the head ``loop_passes`` times a
    token, and its exit gate (``2 * d_model`` a pass) with them.  A
    state-space layer counts its two projections and its MLP, and per token
    the recurrence's update and read (``4 * H * P * N``) and the
    convolution's taps; a convolution layer its two projections, its MLP
    and its taps; a delta-rule layer its three projections, its MLP, its
    taps and the chunked rule's matmuls (per value head and token, forward:
    ``k k^T``, ``q k^T`` and the two products with ``T`` and the one with
    ``u`` over a chunk's ``Q`` positions, ``10 Q D``, and the three against
    the state, ``6 D^2``); a "kda" layer likewise, with its four
    projections, two low-rank gates and three convolutions; a "mamba1" layer
    its four projections, its MLP, its taps and the recurrence's ``~9 d_inner
    d_state`` a token; a "gmu" layer its two projections and its MLP; a
    "cross_attention" layer no key and no value projection; differential
    attention ``head_dim + 2 head_dim`` a query head and attended pair (a
    pair's two value heads are joined).  An MLP is three
    matrices or, at ``mlp_form``
    "relu2", two.  Where ``cfg.one_branch`` a mixer layer counts no MLP, a
    "mlp" layer its MLP alone and a "moe" layer its router, its shared
    expert and the share of a token's ``top_k`` picks that meet an expert
    held here (every other routed model's routed layers count as dense ones
    of ``d_ff``, as they always have).  A "window_attention" layer's scores
    are counted over its window's keys; an output gate's columns of ``wq``
    with the queries'.  Rotation, by kind or not at all, is no matmul and
    counts nothing."""
    mats = 3 if cfg.mlp_form == "swiglu" else 2
    if cfg.kv_lora_rank > 0:  # latent attention's five projections
        qkv = (
            # the queries through their latent, or from one matrix
            (cfg.d_model * cfg.q_lora_rank
             + cfg.q_lora_rank * cfg.n_head * cfg.head_dim
             if cfg.q_lora_rank > 0
             else cfg.d_model * cfg.n_head * cfg.head_dim)
            + cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * cfg.n_head
            * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    else:
        qkv = (
            # wq, with the output gate's columns beside each head's
            cfg.d_model * cfg.n_head * cfg.head_dim
            * (2 if cfg.attn_output_gate else 1)
            + 2 * cfg.d_model * cfg.n_kv_head * cfg.head_dim)  # wk, wv
    # the MLP behind every mixer; a one-branch layer's mixer has none
    mlp = 0 if cfg.one_branch else mats * cfg.d_model * cfg.d_ff
    p_layer = (
        qkv
        + cfg.n_head * cfg.value_head_dim * cfg.d_model  # wo
        + mlp
    )
    head = cfg.vocab_size * cfg.d_model
    if cfg.exit_gate_beta is not None:
        head += cfg.d_model
    dense = (cfg.block_applications * p_layer + cfg.loop_passes * head
             + cfg.vocab_size * cfg.d_model
             # a "cross_attention" layer projects no keys and no values
             - cfg.cross_layers * 2 * cfg.d_model * cfg.n_kv_head
             * cfg.head_dim)
    # a "window_attention" layer meets ``sliding_window`` keys a query, not
    # the sequence's all (one global window on every layer counts the whole
    # sequence, as it always has)
    keys = (cfg.block_applications * cfg.max_seq_len
            - cfg.window_layers * cfg.loop_passes
            * max(cfg.max_seq_len - cfg.sliding_window, 0))
    # scores over a head's key dims, the output over its value dims (under
    # differential attention a pair's two joined: twice the head's)
    attn = keys * cfg.n_head * (cfg.head_dim + (
        2 * cfg.head_dim if cfg.diff_attention else cfg.value_head_dim))
    inner, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
    p_ssm = (cfg.d_model * (inner + conv + cfg.mamba_n_heads)  # in_proj
             + inner * cfg.d_model  # out_proj
             + mlp)
    scan = 4 * inner * cfg.mamba_d_state + 2 * cfg.mamba_d_conv * conv
    p_conv = 4 * cfg.d_model * cfg.d_model + mlp  # in_proj, out_proj
    taps = 2 * cfg.conv_taps * cfg.d_model
    hv, gd = cfg.gdn_v_heads, cfg.gdn_d_head
    p_gdn = (cfg.d_model * (2 * (cfg.gdn_k_heads + hv) * gd + 2 * hv)
             + hv * gd * cfg.d_model + mlp)
    rule = (hv * (10 * GDN_CHUNK * gd + 6 * gd * gd)
            + 2 * cfg.gdn_d_conv * cfg.gdn_conv_dim)
    # a "kda" layer: q, k, v and the output projection, the two low-rank
    # gates and beta; the rule as the delta-rule layer's at its own chunk,
    # and three convolutions
    kh, kd = cfg.kda_heads, cfg.kda_d_head
    p_kda = (cfg.d_model * (3 * kh * kd + 2 * kd + kh) + 2 * kd * kh * kd
             + kh * kd * cfg.d_model + mlp)
    rule_kda = (kh * (10 * KDA_CHUNK * kd + 6 * kd * kd)
                + 2 * cfg.kda_d_conv * 3 * kh * kd)
    # a "mamba1" layer: ``in_proj`` (x and z), ``x_proj``, ``dt_proj`` and
    # ``out_proj``, the recurrence (per channel and state: the decay's
    # product and ``exp``, the update's three and the read's two, ~9) and
    # the taps; a "gmu" layer its two projections
    si, sn = cfg.s6_d_inner, cfg.s6_d_state
    p_s6 = (cfg.d_model * 2 * si + si * (cfg.s6_dt_rank + 2 * sn)
            + cfg.s6_dt_rank * si + si * cfg.d_model + mlp)
    scan_s6 = 9 * si * sn + 2 * cfg.s6_d_conv * si
    p_gmu = 2 * cfg.d_model * si + mlp
    alone = 0.0  # the layers whose one branch is an MLP
    if cfg.one_branch:
        expert = mats * cfg.d_model * cfg.expert_width
        routed = (cfg.d_model * cfg.num_experts
                  + cfg.n_shared_experts * expert
                  + cfg.top_k * cfg.experts_here / max(cfg.num_experts, 1)
                  * expert)
        alone = (cfg.layer_types.count("mlp") * mats * cfg.d_model * cfg.d_ff
                 + cfg.moe_layers * routed)
    return (6.0 * (dense + cfg.ssm_layers * p_ssm + cfg.conv_layers * p_conv
                   + cfg.gdn_layers * p_gdn + cfg.kda_layers * p_kda
                   + cfg.s6_layers * p_s6 + cfg.gmu_layers * p_gmu + alone)
            + 6.0 * attn
            + 3.0 * (cfg.ssm_layers * scan + cfg.conv_layers * taps
                     + cfg.gdn_layers * rule + cfg.kda_layers * rule_kda
                     + cfg.s6_layers * scan_s6))
