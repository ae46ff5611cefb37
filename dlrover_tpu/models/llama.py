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

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.cross_entropy import (
    linear_softmax_cross_entropy,
    softmax_cross_entropy,
)
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.ops.rmsnorm import rmsnorm


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
    capacity_factor: float = 1.25
    # Sliding-window attention (>0: each position attends the last
    # `sliding_window` positions only — Mistral-style long-context;
    # flash path only, kernels skip out-of-window blocks).
    sliding_window: int = 0
    # Per-block rematerialization: save only the residual stream at layer
    # boundaries, recompute attention/MLP internals in the backward pass.
    # Far better peak-HBM than whole-loss remat policies, which either
    # save every dot output (``dots_saveable``) or re-run a forward whose
    # own intermediates still peak the same (``nothing_saveable``).
    remat_block: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    def is_moe_layer(self, i: int) -> bool:
        """Single source of truth for MoE placement (init_params,
        param_logical_axes and init_fp8_states must agree)."""
        return self.num_experts > 0 and (
            i % self.moe_every == self.moe_every - 1
        )

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


def _dense(key, fan_in, fan_out, std=0.02):
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) * std


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Dict:
    keys = jax.random.split(rng, cfg.n_layer + 3)
    params: Dict = {
        "embed": _dense(keys[0], cfg.vocab_size, cfg.d_model),
        "lm_head": _dense(keys[1], cfg.d_model, cfg.vocab_size),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [],
    }
    hd = cfg.head_dim
    for i in range(cfg.n_layer):
        k = jax.random.split(keys[2 + i], 8)
        layer = {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "wq": _dense(k[0], cfg.d_model, cfg.n_head * hd),
            "wk": _dense(k[1], cfg.d_model, cfg.n_kv_head * hd),
            "wv": _dense(k[2], cfg.d_model, cfg.n_kv_head * hd),
            "wo": _dense(k[3], cfg.n_head * hd, cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if cfg.is_moe_layer(i):
            layer["moe"] = {
                "router": _dense(k[4], cfg.d_model, cfg.num_experts),
                "wi": jax.random.normal(
                    k[5], (cfg.num_experts, cfg.d_model, cfg.d_ff),
                    jnp.float32) * 0.02,
                "wg": jax.random.normal(
                    k[6], (cfg.num_experts, cfg.d_model, cfg.d_ff),
                    jnp.float32) * 0.02,
                "wo": jax.random.normal(
                    k[7], (cfg.num_experts, cfg.d_ff, cfg.d_model),
                    jnp.float32) * 0.02,
            }
        else:
            layer["mlp"] = {
                "w_gate": _dense(k[4], cfg.d_model, cfg.d_ff),
                "w_up": _dense(k[5], cfg.d_model, cfg.d_ff),
                "w_down": _dense(k[6], cfg.d_ff, cfg.d_model),
            }
        params["layers"].append(layer)
    return params


def param_logical_axes(cfg: LlamaConfig) -> Dict:
    """Logical-axis names per parameter (consumed by
    ``parallel.sharding.tree_logical_to_specs``)."""

    def layer_axes(has_moe: bool) -> Dict:
        ax = {
            "ln1": (None,),
            "wq": ("embed", "heads"),
            "wk": ("embed", "heads"),
            "wv": ("embed", "heads"),
            "wo": ("heads", "embed"),
            "ln2": (None,),
        }
        if has_moe:
            ax["moe"] = {
                "router": (None, None),
                "wi": ("expert", "embed", "expert_mlp"),
                "wg": ("expert", "embed", "expert_mlp"),
                "wo": ("expert", "expert_mlp", "embed"),
            }
        else:
            ax["mlp"] = {
                "w_gate": ("embed", "mlp"),
                "w_up": ("embed", "mlp"),
                "w_down": ("mlp", "embed"),
            }
        return ax

    layers = []
    for i in range(cfg.n_layer):
        layers.append(layer_axes(cfg.is_moe_layer(i)))
    return {
        "embed": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "ln_f": (None,),
        "layers": layers,
    }


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2)."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def _fp8_proj(x, w, st, dt):
    """[..., K] @ [K, N] through ops.fp8.fp8_dot (delayed scaling).
    Returns (out [..., N] in compute dtype, new Fp8State)."""
    from dlrover_tpu.ops.fp8 import fp8_dot

    out, new = fp8_dot(
        x.reshape(-1, x.shape[-1]), w.astype(dt), st
    )
    return out.reshape(x.shape[:-1] + (w.shape[-1],)), new


def _attention(
    x, layer, cfg: LlamaConfig, positions, attn_impl: str, mesh,
    segment_ids=None, fp8_layer=None,
):
    """Returns ``(out, new_fp8_layer)``; ``new_fp8_layer`` is None unless
    ``fp8_layer`` (a dict of ``ops.fp8.Fp8State`` for wq/wk/wv/wo) routes
    the projections through e4m3/e5m2 fp8_dot — the reference's
    ``Fp8Optimization`` rewrite of eligible linears
    (``atorch/auto/opt_lib/amp_optimization.py:396``) as a functional
    strategy knob."""
    B, S, C = x.shape
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt = cfg.dtype
    new_fp8 = None
    if fp8_layer is not None:
        new_fp8 = {}
        q, new_fp8["wq"] = _fp8_proj(x, layer["wq"], fp8_layer["wq"], dt)
        k, new_fp8["wk"] = _fp8_proj(x, layer["wk"], fp8_layer["wk"], dt)
        v, new_fp8["wv"] = _fp8_proj(x, layer["wv"], fp8_layer["wv"], dt)
        q = q.reshape(B, S, H, D)
        k = k.reshape(B, S, KV, D)
        v = v.reshape(B, S, KV, D)
    else:
        q = (x @ layer["wq"].astype(dt)).reshape(B, S, H, D)
        k = (x @ layer["wk"].astype(dt)).reshape(B, S, KV, D)
        v = (x @ layer["wv"].astype(dt)).reshape(B, S, KV, D)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    if KV != H and attn_impl in ("ring", "ulysses") and mesh is not None:
        # Ring/Ulysses shard over heads and need the full head count; the
        # flash path handles GQA in-kernel (no materialized repeat).
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    if cfg.sliding_window > 0 and attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            "sliding_window requires the flash attention path"
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
        # [B,S,H,D] -> [B,H,S,D] for the flash kernel.
        o = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=True,
            segment_ids=segment_ids,
            backend=None if attn_impl == "auto" else attn_impl,
            window=cfg.sliding_window,
        )
        out = o.transpose(0, 2, 1, 3)
    out = out.reshape(B, S, H * D)
    if fp8_layer is not None:
        out, new_fp8["wo"] = _fp8_proj(out, layer["wo"],
                                       fp8_layer["wo"], dt)
        return out, new_fp8
    return out @ layer["wo"].astype(dt), None


def _swiglu(x, mlp, dt, fp8_mlp=None):
    """Returns ``(out, new_fp8_mlp)``; fp8 routing as in
    :func:`_attention` when ``fp8_mlp`` carries Fp8States for
    w_gate/w_up/w_down."""
    if fp8_mlp is not None:
        new = {}
        g, new["w_gate"] = _fp8_proj(x, mlp["w_gate"],
                                     fp8_mlp["w_gate"], dt)
        u, new["w_up"] = _fp8_proj(x, mlp["w_up"], fp8_mlp["w_up"], dt)
        out, new["w_down"] = _fp8_proj(
            jax.nn.silu(g) * u, mlp["w_down"], fp8_mlp["w_down"], dt
        )
        return out, new
    g = x @ mlp["w_gate"].astype(dt)
    u = x @ mlp["w_up"].astype(dt)
    return (jax.nn.silu(g) * u) @ mlp["w_down"].astype(dt), None


def _moe_swiglu(x, moe, cfg: LlamaConfig, capacity: Optional[int] = None,
                valid=None, fp8_moe=None):
    """Expert-parallel SwiGLU MoE (dense capacity dispatch, see
    ``parallel.moe`` for the mechanism).  ``capacity`` overrides the
    config-derived expert capacity — decode passes a no-drop value,
    since at T=1 the rounded capacity is so coarse that two batch rows
    landing on one expert would silently drop the second.

    ``valid`` [B, S] bool marks real tokens in packed-sequence training:
    pad positions are excluded from expert routing — they take no
    capacity slots (the position-ordered cumsum would otherwise let a
    pad displace a real token that follows it in the flattened order)
    and contribute nothing to the load-balance aux statistics.

    ``fp8_moe`` (a dict of ``ops.fp8.Fp8State`` for wg/wi/wo) routes the
    expert projections — the bulk of a MoE model's FLOPs — through the
    batched e4m3/e5m2 path (``ops.fp8.fp8_batched_dot``); the router and
    the dispatch/combine einsums stay in fp32/compute dtype (they are
    permutation-weighted sums, not GEMM hot spots).  Returns a third
    element (the new fp8 dict) when set — the reference rewrites every
    eligible expert linear the same way
    (``atorch/auto/opt_lib/amp_optimization.py:396``)."""
    B, S, C = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N = B * S
    dt = cfg.dtype
    tokens = x.reshape(N, C)
    logits = tokens.astype(jnp.float32) @ moe["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9
    )
    valid_n = None if valid is None else valid.reshape(N)
    if capacity is None:
        capacity = int(max(1, round(cfg.capacity_factor * N * K / E)))
    onehot_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)
    if valid_n is not None:
        # Pads claim no expert slot: drop them before the capacity
        # cumsum so they can't displace later real tokens.
        onehot_e = onehot_e * valid_n[:, None, None].astype(jnp.int32)
    # Rank within the expert: the -1 must come AFTER the sum over E —
    # inside it, every non-selected expert column contributes a spurious
    # -1 (pos = rank - (E-1)), and rank-0 assignments land on pos -1
    # where one_hot() is all-zero: each expert's first token silently
    # vanished from the dispatch.
    pos = (jnp.cumsum(onehot_e.reshape(N * K, E), axis=0)
           * onehot_e.reshape(N * K, E)).reshape(N, K, E).sum(-1) - 1
    keep = pos < capacity
    if valid_n is not None:
        keep = keep & valid_n[:, None]
    dispatch = (
        jax.nn.one_hot(gate_idx, E, dtype=dt)[..., None]
        * jax.nn.one_hot(pos, capacity, dtype=dt)[..., None, :]
        * keep[..., None, None].astype(dt)
    )  # [N, K, E, C]
    xin = jnp.einsum("nd,nkec->ecd", tokens.astype(dt), dispatch)
    if fp8_moe is not None:
        from dlrover_tpu.ops.fp8 import fp8_batched_dot

        new_fp8 = {}
        g, new_fp8["wg"] = fp8_batched_dot(
            xin, moe["wg"].astype(dt), fp8_moe["wg"]
        )
        u, new_fp8["wi"] = fp8_batched_dot(
            xin, moe["wi"].astype(dt), fp8_moe["wi"]
        )
        h = jax.nn.silu(g) * u
        xout, new_fp8["wo"] = fp8_batched_dot(
            h, moe["wo"].astype(dt), fp8_moe["wo"]
        )
    else:
        new_fp8 = None
        g = jnp.einsum("ecd,edf->ecf", xin, moe["wg"].astype(dt))
        u = jnp.einsum("ecd,edf->ecf", xin, moe["wi"].astype(dt))
        h = jax.nn.silu(g) * u
        xout = jnp.einsum("ecf,efd->ecd", h, moe["wo"].astype(dt))
    combine = dispatch * gate_vals[..., None, None].astype(dt)
    out = jnp.einsum("ecd,nkec->nd", xout, combine)
    # Aux load-balance loss, returned via a side dict by forward().
    if valid_n is None:
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0
        )
    else:
        w = valid_n.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        me = jnp.sum(probs * w[:, None], axis=0) / denom
        ce = jnp.sum(
            jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
            * w[:, None], axis=0,
        ) / denom
    aux = E * jnp.sum(me * ce)
    if fp8_moe is not None:
        return out.reshape(B, S, C), aux, new_fp8
    return out.reshape(B, S, C), aux


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
    fp8_layer=None,
) -> tuple:
    """One transformer block: (x, layer) -> (x, moe_aux scalar).  The unit
    the pipeline stage partitioner groups (``models.llama_pp``).
    ``attn_fn`` swaps the attention implementation (the KV-cache decoder
    plugs in here, so train and decode share one block wiring).

    With ``fp8_layer`` (per-layer Fp8State dict from
    :func:`init_fp8_states`) the attention/MLP projections run through
    fp8_dot and the return becomes a 3-tuple
    ``(x, moe_aux, new_fp8_layer)``; on MoE layers the expert
    projections (the bulk of the layer's FLOPs) go through the batched
    fp8 grouped dot as well — only the router and dispatch/combine stay
    in the compute dtype."""
    # The scopes (``attention``, ``mlp``/``moe``; each with its norm and
    # its residual add) go into every instruction's ``op_name`` of the
    # compiled step: ``accelerate.program_summary`` reads them back.
    with jax.named_scope("attention"):
        h = rmsnorm(x, layer["ln1"], eps=cfg.rms_eps)
        if attn_fn is not None:
            if fp8_layer is not None:
                raise ValueError(
                    "block_apply: fp8_layer is not supported with a "
                    "custom attn_fn (fp8 is a training-path strategy; "
                    "the KV-cache decode path stays in the compute dtype)"
                )
            attn, new_fp8_attn = attn_fn(h, layer, cfg, positions), None
        else:
            attn, new_fp8_attn = _attention(
                h, layer, cfg, positions, attn_impl, mesh, segment_ids,
                fp8_layer=fp8_layer,
            )
        x = x + attn
    if "moe" in layer:
        with jax.named_scope("moe"):
            h = rmsnorm(x, layer["ln2"], eps=cfg.rms_eps)
            res = _moe_swiglu(
                h, layer["moe"], cfg, capacity=moe_capacity,
                valid=None if segment_ids is None else segment_ids >= 0,
                fp8_moe=None if fp8_layer is None else fp8_layer["moe"],
            )
            if fp8_layer is not None:
                delta, aux, new_fp8_attn["moe"] = res
                return x + delta, aux, new_fp8_attn
            delta, aux = res
            return x + delta, aux
    with jax.named_scope("mlp"):
        h = rmsnorm(x, layer["ln2"], eps=cfg.rms_eps)
        out_m, new_fp8_mlp = _swiglu(
            h, layer["mlp"], cfg.dtype,
            fp8_mlp=None if fp8_layer is None else fp8_layer["mlp"],
        )
        x = x + out_m
    if fp8_layer is not None:
        new_fp8_attn["mlp"] = new_fp8_mlp
        return x, jnp.zeros((), jnp.float32), new_fp8_attn
    return x, jnp.zeros((), jnp.float32)


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


def init_fp8_states(cfg: LlamaConfig):
    """Per-layer delayed-scaling Fp8State pytree for :func:`loss_fn`'s
    ``fp8_states`` (one state per rewritten linear: wq/wk/wv/wo, plus
    w_gate/w_up/w_down on dense-MLP layers and the stacked wg/wi/wo
    expert tensors on MoE layers).  Thread through the train
    state and feed each step's output back in — the functional analogue
    of the reference's TE amax history
    (``atorch/auto/opt_lib/amp_optimization.py:396``)."""
    from dlrover_tpu.ops.fp8 import Fp8State

    states = []
    for i in range(cfg.n_layer):
        st = {k: Fp8State.init() for k in ("wq", "wk", "wv", "wo")}
        if cfg.is_moe_layer(i):
            st["moe"] = {
                k: Fp8State.init() for k in ("wg", "wi", "wo")
            }
        else:
            st["mlp"] = {
                k: Fp8State.init()
                for k in ("w_gate", "w_up", "w_down")
            }
        states.append(st)
    return states


def forward_hidden(
    params: Dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    attn_impl: str = "auto",
    mesh=None,
    segment_ids=None,
    fp8_states=None,
) -> tuple:
    """tokens [B, S] -> (final-norm hidden [B, S, D], aux dict).

    ``segment_ids`` [B, S] enables packed-sequence training: attention is
    restricted to same-segment pairs (flash-kernel mask) and rope
    positions reset at each segment boundary.  ``fp8_states`` (from
    :func:`init_fp8_states`) routes the block linears through fp8 and
    adds the updated states to the aux dict as ``aux["fp8_states"]``."""
    B, S = tokens.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    if segment_ids is not None:
        positions = segment_positions(segment_ids)
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    moe_aux = jnp.zeros((), jnp.float32)
    apply = functools.partial(
        block_apply, attn_impl=attn_impl, mesh=mesh,
        segment_ids=segment_ids,
    )
    if cfg.remat_block:
        apply = jax.checkpoint(apply, static_argnums=(2,))
    new_fp8 = [] if fp8_states is not None else None
    for i, layer in enumerate(params["layers"]):
        if fp8_states is None:
            x, aux = apply(layer, x, cfg, positions)
        else:
            x, aux, nf = apply(
                layer, x, cfg, positions, fp8_layer=fp8_states[i]
            )
            new_fp8.append(nf)
        # Identity unless a remat policy references the name: lets
        # Strategy(remat="offload") park the inter-block residual
        # stream in host DRAM (reference
        # selective_offloading_checkpoint.py:252) while everything
        # inside the block rematerializes.
        x = checkpoint_name(x, "block_out")
        moe_aux = moe_aux + aux
    with jax.named_scope("final_norm"):
        x = rmsnorm(x, params["ln_f"], eps=cfg.rms_eps)
    out_aux = {"moe_aux": moe_aux}
    if new_fp8 is not None:
        out_aux["fp8_states"] = new_fp8
    return x, out_aux


def forward(
    params: Dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    attn_impl: str = "auto",
    mesh=None,
    segment_ids=None,
    fp8_states=None,
) -> tuple:
    """tokens [B, S] -> (logits [B, S, vocab] fp32, aux dict)."""
    x, aux = forward_hidden(
        params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
        segment_ids=segment_ids, fp8_states=fp8_states,
    )
    with jax.named_scope("lm_head_loss"):  # the head's matmul is the
        # unfused loss's larger half
        logits = (
            x @ params["lm_head"].astype(cfg.dtype)
        ).astype(jnp.float32)
    return logits, aux


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
    fused_lm_head: Optional[bool] = None,
    fp8_states=None,
) -> jax.Array:
    """Next-token loss.  ``fused_lm_head`` (default: auto — on for large
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
    if fused_lm_head:
        x, aux = forward_hidden(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg, fp8_states=fp8_states,
        )
        with jax.named_scope("lm_head_loss"):
            per_tok = linear_softmax_cross_entropy(
                x, params["lm_head"].astype(cfg.dtype), targets
            )
    else:
        logits, aux = forward(
            params, tokens, cfg, attn_impl=attn_impl, mesh=mesh,
            segment_ids=seg, fp8_states=fp8_states,
        )
        with jax.named_scope("lm_head_loss"):
            per_tok = softmax_cross_entropy(logits, targets)
    with jax.named_scope("lm_head_loss"):
        if valid is not None:
            ce = jnp.sum(per_tok * valid) / jnp.maximum(
                jnp.sum(valid), 1.0)
        else:
            ce = jnp.mean(per_tok)
    loss = ce + moe_aux_weight * aux["moe_aux"]
    if fp8_states is not None:
        # (loss, new_fp8_states): use under value_and_grad(has_aux=True)
        # and feed the states back in next step (delayed scaling).
        return loss, aux["fp8_states"]
    return loss


def num_params(params: Dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: LlamaConfig) -> float:
    """~6 * non-embedding params + attention FLOPs (for MFU accounting)."""
    p_layer = (
        cfg.d_model * cfg.n_head * cfg.head_dim  # wq
        + 2 * cfg.d_model * cfg.n_kv_head * cfg.head_dim  # wk, wv
        + cfg.n_head * cfg.head_dim * cfg.d_model  # wo
        + 3 * cfg.d_model * cfg.d_ff  # swiglu
    )
    dense = cfg.n_layer * p_layer + 2 * cfg.vocab_size * cfg.d_model
    attn = 2 * cfg.n_layer * cfg.max_seq_len * cfg.n_head * cfg.head_dim
    return 6.0 * dense + 6.0 * attn
