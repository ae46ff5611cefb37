"""KV-cache autoregressive decoding for Llama.

The inference half the reference delegates to an external engine (its RL
stack shells out to vllm, ``atorch/atorch/rl/model_engine``) — TPU-first
here: a functional KV cache (one [B, KV, max_len, D] pair per layer kept
compact at the GQA kv-head count), a prefill step that scores the whole
prompt at once, and a ``lax.scan`` decode loop that reuses the cache so
each new token costs O(S) attention instead of the RL engine's
O(S^2)-per-token full recompute.

    cache = init_cache(cfg, batch, max_len)
    tokens = generate(params, cfg, prompts, max_new_tokens=64,
                      rng=jax.random.PRNGKey(0))
"""

from __future__ import annotations

import collections
import functools
import threading
import time

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.log import logger
from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import LlamaConfig, _rope
from dlrover_tpu.ops.rmsnorm import rmsnorm


def init_cache(
    cfg: LlamaConfig, batch: int, max_len: int, *,
    ring_len: Optional[int] = None,
    quant_kv: bool = False,
    ring: bool = True,
) -> Dict:
    """Zeroed per-layer k/v cache (compact KV-head count) + write offset.

    With ``cfg.sliding_window > 0`` the cache is a ROLLING buffer of
    ``ring_len`` slots (default ``max_len``): writes wrap modulo the
    buffer and a per-slot absolute-position array drives the masks, so
    decode memory is O(window), not O(total sequence).  Constraints for
    a chunk of T new tokens: ``T <= ring_len`` always, and
    ``window + T - 1 <= ring_len`` when continuing past a non-empty
    cache (single-token decode only needs ``ring_len >= window``).

    ``quant_kv``: store k/v as int8 with a per-(sequence, head, slot)
    absmax scale — decode is HBM-bandwidth-bound, so halving the cache
    bytes speeds the token loop AND doubles the servable context (the
    fp8/int8 kv-cache mode of the serving engine the reference RL stack
    delegates to).  The attention reads the int8 codes directly (an
    operand dtype-convert fuses into the dot) and applies the scales to
    the small score/probability tensors — by construction nothing
    cache-sized is materialized in full precision.

    ``ring=False`` gives a windowed model a DENSE cache instead: the
    sliding-window mask still applies in attention (the ring is purely
    a memory optimization — O(window) instead of O(sequence)), but a
    dense layout supports ragged per-row offsets and rewind-by-offset,
    which is what the continuous-batching server and speculative
    decoding need.  Memory cost: the full max_len rows."""
    llama.refuse_training_path_only(cfg, "the KV cache (models.llama_infer)")
    KV, D = cfg.n_kv_head, cfg.head_dim
    L = max_len
    if cfg.sliding_window > 0 and ring and ring_len is not None:
        L = min(max_len, ring_len)

    def _layer() -> Dict:
        if quant_kv:
            return {
                "k": jnp.zeros((batch, KV, L, D), jnp.int8),
                "v": jnp.zeros((batch, KV, L, D), jnp.int8),
                "ks": jnp.zeros((batch, KV, L), jnp.float32),
                "vs": jnp.zeros((batch, KV, L), jnp.float32),
            }
        return {
            "k": jnp.zeros((batch, KV, L, D), cfg.dtype),
            "v": jnp.zeros((batch, KV, L, D), cfg.dtype),
        }

    cache = {
        "layers": [_layer() for _ in range(cfg.n_layer)],
        "offset": jnp.zeros((), jnp.int32),
    }
    if cfg.sliding_window > 0 and ring:
        # Absolute position held by each ring slot (-1 = unwritten).
        cache["pos"] = jnp.full((L,), -1, jnp.int32)
    return cache


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[B, KV, T, D] -> (int8 codes, f32 absmax scale [B, KV, T])."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    codes = jnp.clip(
        jnp.round(xf / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def _cached_attention(x, layer, cfg, cache_layer, offset, positions,
                      slot_pos=None):
    """x: [B, T, C] new tokens; attends to cache[:offset] + itself.

    ``offset`` may be a scalar (all sequences aligned) or a [B] vector
    (ragged batch): each sequence writes its T-token chunk at its OWN
    slots ``offset[b]..offset[b]+T-1`` and masks causally against its
    own positions.

    ``slot_pos`` (ring mode, sliding-window models): the ALREADY-updated
    per-slot absolute positions; writes wrap modulo the buffer length
    and masks key on these positions instead of the slot index."""
    B, T, C = x.shape
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt = cfg.dtype
    q, k = llama.qk_normed(
        x @ layer["wq"].astype(dt), x @ layer["wk"].astype(dt), layer, cfg)
    v = (x @ layer["wv"].astype(dt)).reshape(B, T, KV, D)
    q = _rope(q.reshape(B, T, H, D), positions, cfg.rope_theta)
    k = _rope(k.reshape(B, T, KV, D), positions, cfg.rope_theta)

    quant = "ks" in cache_layer

    def _write(cur: jax.Array, new: jax.Array) -> jax.Array:
        """Write ``new`` [B, KV, T, ...] into cache array ``cur``
        [B, KV, L, ...] at the mode's slots (slot axis = 2).  Shared by
        the code arrays and (in quant mode) their scale arrays so the
        three write modes are spelled once."""
        if jnp.ndim(offset) == 1:
            # Ragged mode: sequence b's chunk lands at ITS slots
            # offset[b]..offset[b]+T-1 (one batched scatter; positions
            # == slot indices, so the standard kpos <= qpos mask below
            # stays correct per row).
            if T == 1:
                return cur.at[jnp.arange(B), :, offset].set(
                    new[:, :, 0]
                )
            b_idx = jnp.arange(B)[:, None]  # [B, 1]
            slots = offset[:, None] + jnp.arange(T)[None, :]  # [B, T]
            # new is [B, KV, T, ...]; index (b, t) pairs over the slot
            # axis with KV broadcast.
            return cur.at[b_idx, :, slots].set(
                jnp.moveaxis(new, 2, 1)  # [B, T, KV, ...]
            )
        if slot_pos is not None:
            ring_slots = slot_pos[0]
            if T == 1:
                # Decode hot path: a single contiguous slot — XLA
                # lowers a dynamic_update_slice far better than an
                # indexed scatter.
                start = (0, 0, ring_slots[0]) + (0,) * (new.ndim - 3)
                return jax.lax.dynamic_update_slice(cur, new, start)
            return cur.at[:, :, ring_slots].set(new)
        # Dense: the new k/v land at [offset, offset+T).
        start = (0, 0, offset) + (0,) * (new.ndim - 3)
        return jax.lax.dynamic_update_slice(cur, new, start)

    k_t = k.transpose(0, 2, 1, 3)  # [B, KV, T, D]
    v_t = v.transpose(0, 2, 1, 3)
    new_layer = dict(cache_layer)
    if quant:
        k_codes, k_scale = _quantize_kv(k_t)
        v_codes, v_scale = _quantize_kv(v_t)
        new_layer["k"] = _write(cache_layer["k"], k_codes)
        new_layer["v"] = _write(cache_layer["v"], v_codes)
        new_layer["ks"] = _write(cache_layer["ks"], k_scale)
        new_layer["vs"] = _write(cache_layer["vs"], v_scale)
        # The einsums below read the int8 CODES (a dtype convert on a
        # dot operand reliably fuses into the dot's read stream); the
        # scales — constant over D — are applied to the tiny [.., T, L]
        # score and probability tensors instead, so no full-size
        # [B, KV, L, D] dequantized product exists even if XLA declines
        # to fuse an elementwise producer into the MXU op.
        k_eff = new_layer["k"].astype(dt)
        v_eff = new_layer["v"].astype(dt)
    else:
        new_layer["k"] = _write(cache_layer["k"], k_t.astype(dt))
        new_layer["v"] = _write(cache_layer["v"], v_t.astype(dt))
        k_eff, v_eff = new_layer["k"], new_layer["v"]

    if slot_pos is not None:
        slot_pos = slot_pos[1]

    max_len = k_eff.shape[2]
    rep = H // KV
    # Grouped attention against the COMPACT cache, in its stored dtype:
    # no [B, H, max_len, D] repeat and no fp32 cache copy is ever
    # materialized — the einsums accumulate in fp32 via
    # preferred_element_type (only q, [B,KV,rep,T,D] with tiny T, is
    # upcast).
    qf = (
        q.transpose(0, 2, 1, 3)
        .reshape(B, KV, rep, T, D)
        .astype(k_eff.dtype)
    )
    s = jnp.einsum(
        "bgrtd,bgkd->bgrtk", qf, k_eff,
        preferred_element_type=jnp.float32,
    ) / np.sqrt(D)
    if quant:
        # s_k = (q . codes_k) * scale_k  ==  q . (codes_k * scale_k)
        s = s * new_layer["ks"][:, :, None, None, :]
    # Causal over absolute positions; unwritten slots are masked (ring
    # mode: pos -1; dense mode: slot index beyond offset+T).
    if slot_pos is not None:
        kpos = slot_pos[None, None, None, None, :]
    else:
        kpos = jnp.arange(max_len)[None, None, None, None, :]
    qpos = positions[:, None, None, :, None]
    s = jnp.where((kpos >= 0) & (kpos <= qpos), s, -1e30)
    if cfg.sliding_window > 0:
        # Sliding window: only the last `sliding_window` positions are
        # visible.
        s = jnp.where(qpos - kpos < cfg.sliding_window, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        # sum_k p_k * (codes_vk * vs_k)  ==  sum_k (p_k * vs_k) * codes_vk
        p = p * new_layer["vs"][:, :, None, None, :]
    out = jnp.einsum(
        "bgrtk,bgkd->bgrtd", p.astype(v_eff.dtype), v_eff,
        preferred_element_type=jnp.float32,
    )
    out = (
        out.reshape(B, H, T, D)
        .transpose(0, 2, 1, 3)
        .reshape(B, T, H * D)
        .astype(dt)
    )
    return out @ layer["wo"].astype(dt), new_layer


def forward_step(
    params: Dict,
    tokens: jax.Array,  # [B, T] new tokens
    cfg: LlamaConfig,
    cache: Dict,
    *,
    assume_empty_cache: bool = False,  # ring mode: offset-0 prefill
) -> Tuple[jax.Array, Dict]:
    """Score ``tokens`` continuing the cached context.  Returns
    (logits [B, T, vocab] fp32, updated cache).

    Reuses ``llama.block_apply`` with the cached attention plugged in,
    so the block wiring (norm/residual/mlp-or-moe order) cannot drift
    from the training forward.  MoE layers run with a no-drop capacity:
    at T=1 the config-derived capacity rounds so coarsely that batch
    rows colliding on an expert would be silently dropped."""
    llama.refuse_training_path_only(
        cfg, "the cached decoder (models.llama_infer)")
    B, T = tokens.shape
    dt = cfg.dtype
    offset = cache["offset"]
    x = params["embed"].astype(dt)[tokens]
    if jnp.ndim(offset) == 1:
        # Ragged batch: per-sequence write slots/positions — T=1 is the
        # decode hot path; T>1 scores a chunk continuing each row at
        # its OWN offset (batched speculative verify, chunked ragged
        # continuation).  (Ragged PREFILL from zero needs no special
        # handling — pad tokens written at their slot positions are
        # causally invisible to every later real query.)
        if "pos" in cache:
            raise ValueError(
                "ragged offsets are not supported with the sliding-"
                "window ring cache"
            )
        positions = offset[:, None] + jnp.broadcast_to(
            jnp.arange(T), (B, T)
        )
    else:
        positions = offset + jnp.broadcast_to(jnp.arange(T), (B, T))
    no_drop_capacity = B * T * cfg.top_k
    ring = None
    if "pos" in cache:  # ring mode (sliding-window models)
        L = cache["pos"].shape[0]
        W = cfg.sliding_window
        if T > L:
            raise ValueError(
                f"chunk of {T} tokens exceeds the {L}-slot ring cache"
            )
        if T > 1 and W + T - 1 > L and not assume_empty_cache:
            # A multi-token chunk on a NON-empty ring would overwrite
            # keys still inside earlier queries' windows (silently wrong
            # logits). Prefill at offset 0 is safe — callers declare it.
            raise ValueError(
                f"continuation chunk of {T} tokens needs ring_len >= "
                f"window + T - 1 = {W + T - 1}, have {L}; pass "
                "assume_empty_cache=True only for the offset-0 prefill"
            )
        slots = (offset + jnp.arange(T)) % L
        if T == 1:
            slot_pos = jax.lax.dynamic_update_slice(
                cache["pos"], offset[None] + jnp.arange(1), (slots[0],)
            )
        else:
            slot_pos = cache["pos"].at[slots].set(
                offset + jnp.arange(T)
            )
        ring = (slots, slot_pos)
    new_layers = []
    for layer, cache_layer in zip(params["layers"], cache["layers"]):
        cell = {}

        def attn_fn(h, layer_, cfg_, positions_, _cache=cache_layer,
                    _cell=cell):
            out, _cell["cache"] = _cached_attention(
                h, layer_, cfg_, _cache, offset, positions_,
                slot_pos=ring,
            )
            return out

        x, _aux = llama.block_apply(
            layer, x, cfg, positions,
            attn_fn=attn_fn, moe_capacity=no_drop_capacity,
        )
        new_layers.append(cell["cache"])
    x = rmsnorm(x, params["ln_f"], eps=cfg.rms_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    new_cache = {"layers": new_layers, "offset": offset + T}
    if ring is not None:
        new_cache["pos"] = ring[1]
    return logits, new_cache


def shard_params_for_decode(params: Dict, cfg: LlamaConfig, mesh):
    """Tensor-parallel serving layout: device_put ``params`` onto
    ``mesh`` (axis name ``'tp'``) with column-parallel wq/wk/wv and
    mlp-in, row-parallel wo/w_down, vocab-sharded lm_head — the layout
    vllm's TP serving uses, expressed as shardings instead of module
    surgery.  The decode computation itself needs no changes: jit the
    usual :func:`generate`/:func:`forward_step` and GSPMD partitions the
    einsums and inserts the row-parallel reductions (computation
    follows the data).  Returns (sharded_params, specs).

    GQA note: the KV cache follows the kv-head einsum operands, so tp
    greater than ``cfg.n_kv_head`` still works (XLA gathers k/v) but
    shards only the q-head work."""
    from dlrover_tpu.parallel import sharding as sh

    # Only the overrides: neutralize the training axes that have no
    # mesh axis here (batch/embed/expert); heads/mlp/vocab already map
    # to 'tp' in DEFAULT_RULES and keep tracking it.
    rules: sh.Rules = {"batch": None, "embed": None, "expert": None}
    specs = sh.tree_logical_to_specs(
        llama.param_logical_axes(cfg), rules
    )
    return sh.shard_tree(params, specs, mesh), specs


def _filter_logits(scaled: jax.Array, top_k: int,
                   top_p: float) -> jax.Array:
    """[B, V] temperature-scaled logits -> same with everything outside
    the top-k / top-p nucleus set to -inf (the top token always
    survives)."""
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p > 0.0:
        # Nucleus: keep the smallest prefix of the sorted
        # distribution whose mass reaches top_p.
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]  # descending
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p
        n_keep = jnp.maximum(1, jnp.sum(keep_sorted, axis=-1))
        cutoff = jnp.take_along_axis(
            srt, (n_keep - 1)[:, None], axis=-1
        )
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return scaled


def _make_sampler(temperature: float, top_k: int, top_p: float):
    """(logits [B, V], rng) -> [B] token picker: greedy at T=0, else
    categorical with optional top-k truncation / top-p nucleus."""

    def pick(logits_1, sub):
        if temperature <= 0.0:
            return jnp.argmax(logits_1, axis=-1)
        return jax.random.categorical(
            sub, _filter_logits(logits_1 / temperature, top_k, top_p)
        )

    return pick


def generate(
    params: Dict,
    cfg: LlamaConfig,
    prompts: jax.Array,  # [B, P] prompt token ids
    *,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,  # 0 = greedy
    top_k: int = 0,
    top_p: float = 0.0,  # 0 = off; else nucleus sampling
    quant_kv: bool = False,  # int8 kv cache (see init_cache)
) -> jax.Array:
    """[B, P + max_new_tokens] — prompt + sampled continuation.

    Prefill scores the prompt in one pass; decode is a ``lax.scan`` of
    single-token steps against the KV cache.  ``temperature=0`` is
    greedy (deterministic); otherwise categorical sampling with optional
    top-k truncation and/or top-p (nucleus) filtering — the sampling
    surface of the serving engine the reference RL stack delegates to.
    """
    if max_new_tokens == 0:
        return prompts
    B, P = prompts.shape
    max_len = P + max_new_tokens
    ring_len = None
    if cfg.sliding_window > 0:
        # Rolling buffer: prefill needs P slots, decode needs `window`
        # retained keys — memory O(max(P, window)), not O(P + N).
        ring_len = max(P, cfg.sliding_window)
    cache = init_cache(cfg, B, max_len, ring_len=ring_len,
                       quant_kv=quant_kv)
    logits, cache = forward_step(
        params, prompts, cfg, cache, assume_empty_cache=True
    )
    if rng is None:
        rng = jax.random.PRNGKey(0)

    pick = _make_sampler(temperature, top_k, top_p)
    rng, sub = jax.random.split(rng)
    first = pick(logits[:, -1, :], sub).astype(prompts.dtype)

    def step(carry, _):
        cache, tok, rng = carry
        logits, cache = forward_step(params, tok[:, None], cfg, cache)
        rng, sub = jax.random.split(rng)
        nxt = pick(logits[:, -1, :], sub).astype(tok.dtype)
        return (cache, nxt, rng), tok

    # Each step scores the carried token and samples the next; the scan
    # emits the SCORED token, so the outputs are exactly the generated
    # sequence [first, t2, ..., tN] (the final carry is an N+1-th sample
    # past the requested window — dropped).
    _, toks = jax.lax.scan(
        step, (cache, first, rng), None, length=max_new_tokens
    )
    return jnp.concatenate(
        [prompts, jnp.moveaxis(toks, 0, 1)], axis=1
    )


def generate_ragged(
    params: Dict,
    cfg: LlamaConfig,
    prompts: jax.Array,  # [B, P] right-padded prompt token ids
    prompt_lens: jax.Array,  # [B] true prompt lengths (1..P)
    *,
    max_new_tokens: int,
    eos_token: int = -1,  # >=0: per-sequence stop on this token
    pad_token: int = 0,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    quant_kv: bool = False,  # int8 kv cache (see init_cache)
) -> Tuple[jax.Array, jax.Array]:
    """Ragged batched decode: per-sequence lengths, per-sequence EOS.

    Returns ``(tokens [B, P + max_new_tokens], lengths [B])`` where row
    b holds ``prompt_b`` (its true ``prompt_lens[b]`` tokens), then its
    continuation immediately after (no pad gap), then ``pad_token``;
    ``lengths[b]`` is the total valid length.  The decode loop is a
    ``lax.while_loop`` that EXITS as soon as every sequence has emitted
    ``eos_token`` — a batch of short answers does not pay for
    ``max_new_tokens`` steps (the role per-sequence scheduling plays in
    the serving engine the reference RL stack delegates to,
    ``atorch/rl/model_engine/model_engine.py:35``).

    Correctness of the ragged PREFILL needs no masking tricks: padded
    tail tokens are written at their slot positions, and every later
    real query q for sequence b sits at position ``>=`` those slots only
    after they have been overwritten by real decode writes — until then
    the causal mask ``kpos <= qpos`` hides exactly the pad entries that
    are still stale, because sequence b's next query position IS its
    first stale slot.
    """
    B, P = prompts.shape
    N = max_new_tokens
    if N == 0:
        return prompts, jnp.asarray(prompt_lens, jnp.int32)
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    # ring=False: windowed models run ragged on a DENSE cache (window
    # masking still applies; the ring layout cannot take per-row
    # offsets).
    cache = init_cache(cfg, B, P + N, quant_kv=quant_kv, ring=False)
    logits, cache = forward_step(params, prompts, cfg, cache)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    pick = _make_sampler(temperature, top_k, top_p)

    # First token: sampled from each sequence's OWN last-prompt logit.
    last = jnp.take_along_axis(
        logits, (prompt_lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    rng, sub = jax.random.split(rng)
    first = pick(last, sub).astype(prompts.dtype)

    # Per-sequence decode offsets: sequence b continues at its length.
    cache = dict(cache, offset=prompt_lens)

    def cond(c):
        i, _, _, done, _, _ = c
        return (i < N) & ~jnp.all(done)

    def body(c):
        # ``done`` means "this row's EOS is already RECORDED" — the EOS
        # token itself must land in the buffer before the row freezes.
        i, buf, tok, done, cache, rng = c
        buf = buf.at[:, i].set(jnp.where(done, pad_token, tok))
        done_next = done | (
            (tok == eos_token) if eos_token >= 0
            else jnp.zeros((B,), bool)
        )
        logits, new_cache = forward_step(params, tok[:, None], cfg, cache)
        rng, sub = jax.random.split(rng)
        nxt = pick(logits[:, -1, :], sub).astype(tok.dtype)
        # Finished rows freeze: offset stops advancing so their cache
        # rows stop changing (their compute rides along masked).
        frozen = jnp.where(done_next, cache["offset"],
                           new_cache["offset"])
        new_cache = dict(new_cache, offset=frozen)
        return (i + 1, buf, jnp.where(done_next, tok, nxt),
                done_next, new_cache, rng)

    buf = jnp.full((B, N), pad_token, prompts.dtype)
    i, buf, _, done, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.zeros((), jnp.int32), buf, first,
         jnp.zeros((B,), bool), cache, rng),
    )
    # Number of valid generated tokens per row: the column of the first
    # pad-after-generation; EOS itself is kept as a valid token.
    written = jnp.minimum(
        jnp.where(
            jnp.any(buf == eos_token, axis=1) if eos_token >= 0
            else jnp.zeros((B,), bool),
            jnp.argmax(buf == eos_token, axis=1) + 1,
            i,
        ),
        i,
    ).astype(jnp.int32)

    # Compact each row: prompt tokens then continuation, no pad gap.
    j = jnp.arange(P + N)[None, :]
    gen_idx = jnp.clip(j - prompt_lens[:, None], 0, N - 1)
    gen_vals = jnp.take_along_axis(buf, gen_idx, axis=1)
    prompt_padded = jnp.pad(prompts, ((0, 0), (0, N)))
    lens = prompt_lens + written
    out = jnp.where(j < prompt_lens[:, None], prompt_padded, gen_vals)
    out = jnp.where(j < lens[:, None], out, pad_token)
    return out, lens


def _spec_accept_round(
    p: np.ndarray,  # [k+1, V] target probs at each speculated position
    q: np.ndarray,  # [k, V] draft probs the proposals were drawn from
    d: np.ndarray,  # [k] proposals
    rng: "np.random.Generator",
) -> Tuple[int, int]:
    """Rejection-sampling acceptance (Leviathan et al.): accept the
    i-th proposal with prob ``min(1, p_i[d_i] / q_i[d_i])``; on the
    first rejection draw the replacement from the residual
    ``norm(max(0, p_i - q_i))``; if all ``k`` survive, draw a bonus
    token from ``p_{k+1}``.  Returns ``(j, next_token)`` — ``j``
    accepted proposals plus the round's final token.  The emitted
    sequence is distributed EXACTLY as sequential target sampling,
    whatever the draft proposes (a bad draft only costs acceptance
    rate, never correctness)."""
    V = p.shape[1]
    k = len(d)
    for i in range(k):
        di = int(d[i])
        if rng.random() < p[i, di] / max(float(q[i, di]), 1e-30):
            continue
        resid = np.clip(p[i] - q[i], 0.0, None)
        s = float(resid.sum())
        if s <= 0.0:
            # p == q to numerical precision: the residual is empty;
            # any draw from p is distribution-correct.
            resid, s = p[i], float(p[i].sum())
        return i, int(rng.choice(V, p=resid / s))
    return k, int(rng.choice(V, p=p[k] / float(p[k].sum())))


def _spec_accept_batch(
    p: np.ndarray,  # [B, k+1, V] target probs per row/slot
    q: np.ndarray,  # [B, k, V] draft probs per row/slot
    d: np.ndarray,  # [B, k] draft proposals
    done: np.ndarray,  # [B] frozen rows (consume draws, results ignored)
    np_rng: "np.random.Generator",
    k_row: Optional[np.ndarray] = None,  # [B] per-row width <= k
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized rejection-sampling acceptance over the batch — the
    numpy-batched form of :func:`_spec_accept_round` (the scalar
    executable spec; a Monte-Carlo test asserts both implement the same
    law).  One pass, no per-row Python, so the serving hot loop pays a
    single host sync per round.  Returns ``(j, tok)``: per row the
    accepted-prefix length and the round's final sampled token.  Frozen
    rows draw uniforms they ignore; each active row's law is unchanged
    (independent draws).

    ``k_row`` (ISSUE 11, per-request adaptive k): row b behaves as a
    ``k_row[b]``-proposal round — proposals beyond its width are
    ignored, and a row accepting its full width draws the bonus token
    from the target law at that position (``k_row[b] == 0`` is plain
    target sampling).  The SAME uniforms are consumed with or without
    truncation, so each stream's law is exactly the scalar spec's at
    its own width."""
    B, k = d.shape
    V = p.shape[-1]
    rows = np.arange(B)
    cols = np.arange(k)
    p_sel = p[rows[:, None], cols[None, :], d]  # [B, k]
    q_sel = q[rows[:, None], cols[None, :], d]  # [B, k]
    acc = np_rng.random((B, k)) < p_sel / np.maximum(q_sel, 1e-30)
    # First rejected position (k if none): the accepted-prefix length.
    j = acc.astype(np.int64).cumprod(axis=1).sum(axis=1)
    if k_row is not None:
        kw = np.minimum(np.asarray(k_row, np.int64), k)
        j = np.minimum(j, kw)
    else:
        kw = np.full(B, k, np.int64)
    j = np.where(done, 0, j)
    # Rejected rows draw from the residual law at position j; fully
    # accepting rows (at their own width) draw the bonus token from
    # the target's p at that position.
    p_j = p[rows, j]  # [B, V]
    q_j = q[rows, np.minimum(j, k - 1)]  # [B, V]
    resid = np.where((j < kw)[:, None], np.clip(p_j - q_j, 0.0, None),
                     p_j)
    s = resid.sum(axis=1)
    # p == q to numerical precision: the residual is empty; any draw
    # from p is distribution-correct.
    empty = s <= 0.0
    if empty.any():
        resid = np.where(empty[:, None], p_j, resid)
        s = resid.sum(axis=1)
    # Inverse-CDF sample, one uniform per row.
    tok = (
        np.cumsum(resid, axis=1) < (np_rng.random(B) * s)[:, None]
    ).sum(axis=1)
    return j, np.minimum(tok, V - 1)


def generate_speculative(
    params: Dict,
    cfg: LlamaConfig,
    draft_params: Dict,
    draft_cfg: LlamaConfig,
    prompts: jax.Array,  # [1, P] — single-sequence (low-latency serving)
    *,
    max_new_tokens: int,
    k: int = 4,
    quant_kv: bool = False,
    temperature: float = 0.0,  # 0 = greedy; >0 = rejection sampling
    top_k: int = 0,
    top_p: float = 0.0,
    eos_token: int = -1,  # >=0: stop after emitting this token
    rng: Optional[jax.Array] = None,
    stats: Optional[Dict] = None,  # out-param: rounds, tokens_per_round
) -> jax.Array:
    """Single-stream speculative decoding: a small DRAFT model proposes
    ``k`` tokens per round; the TARGET model scores all of them in ONE
    chunked forward.  At ``temperature=0`` the longest argmax-matching
    prefix (+ the target's own next token) is accepted — output is
    EXACTLY the target model's greedy decode.  At ``temperature>0``
    proposals pass through rejection sampling
    (:func:`_spec_accept_round`) — output is distributed exactly as the
    target model's sampled decode.  Either way the draft only changes
    how many target forwards it takes (the speculative-decoding role of
    the serving engine the reference RL stack delegates to).

    The machinery lives in :func:`generate_speculative_batched` (this
    is its B=1 case); see there for the cache-rewind design, the
    filtered-law guarantee for ``top_k``/``top_p``, and the numerics
    caveat on chunked-vs-incremental scoring.

    ``eos_token >= 0`` stops at the first EOS: the result is then
    [1, P + n] with n <= max_new_tokens, ending at the EOS (variable
    length — this is a host-driven serving loop, not a fixed-shape
    jitted program)."""
    B, P = prompts.shape
    if B != 1:
        raise ValueError(
            f"speculative decode is single-sequence (got batch {B}); "
            "use generate_speculative_batched for ragged batches"
        )
    if max_new_tokens == 0:
        return prompts
    out, lens = generate_speculative_batched(
        params, cfg, draft_params, draft_cfg, prompts,
        jnp.asarray([P], jnp.int32),
        max_new_tokens=max_new_tokens, k=k, quant_kv=quant_kv,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token=eos_token, rng=rng, stats=stats,
    )
    return out[:, : int(lens[0])]


@functools.lru_cache(maxsize=32)
def _spec_programs(cfg: LlamaConfig, draft_cfg: LlamaConfig, k: int,
                   temperature: float, top_k: int, top_p: float) -> Dict:
    """Compiled speculative-decoding programs, memoized per
    (configs, k, sampling knobs): RL rollouts call
    generate_speculative_batched once per PPO iteration, and without
    this memo every call would re-trace and re-XLA-compile the draft
    scan, the (k+1)-token verify, and the catch-up step (jax.jit caches
    by function identity).  LlamaConfig is frozen/hashable."""
    sample = temperature > 0.0

    @jax.jit
    def prefill_t(tp, prompts, cache):
        return forward_step(tp, prompts, cfg, cache)

    @jax.jit
    def prefill_d(dp, prompts, cache):
        return forward_step(dp, prompts, draft_cfg, cache)

    @jax.jit
    def draft_roll(dp, cache, tok, key):
        def body(carry, sub):
            cache, tok = carry
            lg, cache = forward_step(dp, tok[:, None], draft_cfg, cache)
            lg1 = lg[:, -1, :]
            if sample:
                filt = _filter_logits(lg1 / temperature, top_k, top_p)
                nxt = jax.random.categorical(
                    sub, filt, axis=-1
                ).astype(tok.dtype)
                probs = jax.nn.softmax(filt, axis=-1)  # [B, V]
                return (cache, nxt), (nxt, probs)
            nxt = jnp.argmax(lg1, axis=-1).astype(tok.dtype)
            return (cache, nxt), nxt

        (cache, _), ys = jax.lax.scan(
            body, (cache, tok), jax.random.split(key, k)
        )
        toks, q = ys if sample else (ys, None)
        # toks [k, B] -> [B, k]; q [k, B, V] -> [B, k, V]
        return (
            jnp.moveaxis(toks, 0, 1),
            None if q is None else jnp.moveaxis(q, 0, 1),
            cache,
        )

    @jax.jit
    def target_verify(tp, cache, chunk):
        lg, cache = forward_step(tp, chunk, cfg, cache)
        if sample:
            filt = _filter_logits(
                lg.reshape(-1, lg.shape[-1]) / temperature, top_k, top_p
            ).reshape(lg.shape)
            return jax.nn.softmax(filt, axis=-1), cache  # [B, k+1, V]
        return jnp.argmax(lg, axis=-1).astype(chunk.dtype), cache

    @jax.jit
    def draft_catch_up(dp, cache, tok):
        _, cache = forward_step(dp, tok[:, None], draft_cfg, cache)
        return cache

    return {
        "prefill_t": prefill_t, "prefill_d": prefill_d,
        "draft_roll": draft_roll, "target_verify": target_verify,
        "draft_catch_up": draft_catch_up,
    }


def _spec_decode_round(
    progs: Dict,
    params: Dict,
    draft_params: Dict,
    cache_t: Dict,
    cache_d: Dict,
    cur: jax.Array,  # [B] current input token per row
    done: np.ndarray,  # [B] frozen rows (ride along masked)
    k: int,
    sample: bool,
    np_rng: "np.random.Generator",
    sub: jax.Array,  # draft-sampling key (dead in the greedy trace)
    max_off: Optional[np.ndarray] = None,  # [B] per-row offset bound
    k_row: Optional[np.ndarray] = None,  # [B] per-row width <= k
) -> Tuple[list, np.ndarray, Dict, Dict]:
    """ONE speculative round over a ragged batch: draft k proposals per
    row, one chunked (k+1)-token verify at per-row offsets, per-row
    acceptance, cache rewind + full-acceptance catch-up.  Frozen rows
    keep their offsets (their compute rides along masked).  Returns
    ``(accepted_rows, nxt, cache_t, cache_d)``: ``accepted_rows[b]`` is
    the round's emitted tokens for row b (empty when frozen) BEFORE any
    EOS/budget truncation — truncation only marks rows done, it never
    changes cache state, so callers (the batched generator, the
    speculative DecodeServer) own it.  ``k_row`` truncates each row to
    its own speculation width (see :func:`_spec_accept_batch`)."""
    B = int(cur.shape[0])
    n_dev = cache_t["offset"]  # [B] handle; fetched with the round's sync
    d, q, cache_d = progs["draft_roll"](draft_params, cache_d, cur, sub)
    chunk = jnp.concatenate([cur[:, None], d], axis=1)  # [B, k+1]
    g, cache_t = progs["target_verify"](params, cache_t, chunk)
    # ONE host sync per round: acceptance below is pure numpy over the
    # batch dimension (per-row Python loops + separate np.asarray syncs
    # serialized the serving hot loop on the host — r4 advisor).  Frozen
    # rows consume RNG draws they ignore; each active row's law is
    # unchanged (independent uniforms).
    rows = np.arange(B)
    cur_h = np.asarray(cur)
    if sample:
        n, d_host, g_raw, q_raw = jax.device_get((n_dev, d, g, q))
        g_host = np.asarray(g_raw, np.float64)  # [B, k+1, V]
        q_host = np.asarray(q_raw, np.float64)  # [B, k, V]
        j, tok = _spec_accept_batch(g_host, q_host, d_host, done, np_rng,
                                    k_row=k_row)
        nxt = np.where(done, cur_h, tok).astype(cur_h.dtype)
    else:
        n, d_host, g_host = jax.device_get((n_dev, d, g))  # g [B, k+1]
        match = (d_host == g_host[:, :k]).astype(np.int64)
        j = match.cumprod(axis=1).sum(axis=1)  # longest matching prefix
        if k_row is not None:
            # Per-row width: the greedy law at width k_b emits the
            # matched prefix up to k_b plus the target's own token at
            # the truncation point — still exactly the target's greedy
            # stream, whatever the draft proposed beyond the width.
            j = np.minimum(j, np.asarray(k_row, np.int64))
        j = np.where(done, 0, j)
        nxt = np.where(done, cur_h, g_host[rows, j]).astype(cur_h.dtype)
    n = np.asarray(n)
    # Per-row rewind; frozen rows keep their old offset.  ``max_off``
    # clamps rows finishing this round (emission stops at their budget/
    # EOS, so the clamp never loses live context) — without it a
    # full-acceptance final round leaves a frozen offset past the
    # capacity-checked bound, and later ride-along rounds would scatter
    # beyond max_len (silently dropped today, corruption under any
    # dense-write lowering).
    new_n = np.where(done, n, n + 1 + j)
    if max_off is not None:
        new_n = np.minimum(new_n, max_off)
    full = (~done) & (j == k)
    if full.any():
        # Batched 1-token catch-up: full-acceptance rows write the
        # missing d_k at slot n+k; everyone else harmlessly writes its
        # next token's kv at its own next slot.
        tok_cu = np.where(full, d_host[:, k - 1], nxt).astype(
            cur_h.dtype
        )
        pos_cu = np.where(full, n + k, new_n)
        cache_d = dict(cache_d, offset=jnp.asarray(pos_cu, jnp.int32))
        cache_d = progs["draft_catch_up"](
            draft_params, cache_d, jnp.asarray(tok_cu)
        )
    cache_d = dict(cache_d, offset=jnp.asarray(new_n, jnp.int32))
    cache_t = dict(cache_t, offset=jnp.asarray(new_n, jnp.int32))
    accepted_rows = [
        [] if done[b] else list(d_host[b, : j[b]]) + [nxt[b]]
        for b in range(B)
    ]
    return accepted_rows, nxt, cache_t, cache_d


def generate_speculative_batched(
    params: Dict,
    cfg: LlamaConfig,
    draft_params: Dict,
    draft_cfg: LlamaConfig,
    prompts: jax.Array,  # [B, P] right-padded prompts
    prompt_lens: jax.Array,  # [B] true prompt lengths
    *,
    max_new_tokens: int,
    k: int = 4,
    quant_kv: bool = False,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_token: int = -1,
    pad_token: int = 0,
    rng: Optional[jax.Array] = None,
    stats: Optional[Dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched speculative decoding over a RAGGED batch: every row
    drafts ``k`` proposals, ONE (k+1)-token ragged verify scores all
    rows at their own offsets, and acceptance is per-row — combining
    :func:`generate_speculative`'s draft/verify economics with
    :func:`generate_ragged`'s per-sequence lengths and EOS exit (the
    batched speculative mode of the serving engine the reference RL
    stack delegates to).

    Output contract matches :func:`generate_ragged`: ``(tokens
    [B, P + max_new_tokens], lengths [B])``, row b = prompt then
    continuation then ``pad_token``.  The output law per row equals
    :func:`generate` with the same sampling knobs (greedy exactness at
    ``temperature=0``; rejection sampling otherwise).

    Cache bookkeeping is the per-row generalization of the
    single-stream version: rejection rewinds that row's offset (dense-
    cache slot masking hides its stale writes); rows that accepted all
    ``k`` get their missing ``d_k`` kv written by a batched 1-token
    catch-up whose other rows write their next token's kv early
    (harmless — the next roll rewrites the same value).  Finished rows
    freeze their offset and ride along masked."""
    B, P = prompts.shape
    N = max_new_tokens
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    if N == 0:
        return prompts, prompt_lens
    sample = temperature > 0.0
    if rng is None:
        rng = jax.random.PRNGKey(0)
    rng, seed_key = jax.random.split(rng)
    np_rng = np.random.default_rng(
        int(jax.random.randint(seed_key, (), 0, 2**31 - 1))
    )
    max_len = P + N + k + 2
    progs = _spec_programs(cfg, draft_cfg, k, temperature, top_k, top_p)
    # ring=False: windowed models speculate on a DENSE cache — offset
    # rewind relies on slot masking to hide stale writes, which a ring
    # layout cannot provide (wrapped writes destroy live keys).
    cache_t = init_cache(cfg, B, max_len, quant_kv=quant_kv,
                         ring=False)
    cache_d = init_cache(draft_cfg, B, max_len, quant_kv=quant_kv,
                         ring=False)
    logits, cache_t = progs["prefill_t"](params, prompts, cache_t)
    _, cache_d = progs["prefill_d"](draft_params, prompts, cache_d)
    pick = _make_sampler(temperature, top_k, top_p)
    last = jnp.take_along_axis(
        logits, (prompt_lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    rng, first_key = jax.random.split(rng)
    cur = pick(last, first_key).astype(prompts.dtype)
    # Per-row ragged offsets: each row continues at its true length.
    off = prompt_lens
    cache_t = dict(cache_t, offset=off)
    cache_d = dict(cache_d, offset=off)

    buf = np.full((B, N), pad_token, dtype=np.asarray(prompts).dtype)
    emitted = np.zeros(B, np.int64)
    done = np.zeros(B, bool)
    cur_h = np.asarray(cur)
    if eos_token >= 0:
        hit = cur_h == eos_token
    else:
        hit = np.zeros(B, bool)
    for b in range(B):
        buf[b, 0] = cur_h[b]
    emitted[:] = 1
    done |= hit
    rounds = 0
    active_row_rounds = 0  # sum over rounds of non-frozen rows
    greedy_key = jax.random.PRNGKey(0)  # dead in the greedy trace
    while not done.all() and (emitted < N).any():
        if sample:
            rng, sub = jax.random.split(rng)
        else:
            sub = greedy_key
        active_row_rounds += int((~done).sum())
        accepted_rows, nxt, cache_t, cache_d = _spec_decode_round(
            progs, params, draft_params, cache_t, cache_d, cur, done,
            k, sample, np_rng, sub,
            max_off=np.asarray(prompt_lens) + N,
        )
        # Emit per row (truncated at EOS and at the N budget).
        new_done = done.copy()
        for b in range(B):
            if done[b]:
                continue
            accepted = accepted_rows[b]
            if eos_token >= 0:
                for i, t in enumerate(accepted):
                    if int(t) == eos_token:
                        accepted = accepted[: i + 1]
                        new_done[b] = True
                        break
            room = N - int(emitted[b])
            if len(accepted) >= room:
                accepted = accepted[:room]
                new_done[b] = True
            for t in accepted:
                buf[b, emitted[b]] = t
                emitted[b] += 1
        done = new_done
        cur = jnp.asarray(nxt)
        rounds += 1
    if stats is not None:
        stats["rounds"] = rounds
        # Normalize by ACTIVE row-rounds, not rounds*B: frozen (done)
        # rows ride along masked for most of a ragged batch's rounds and
        # would dilute the per-row acceptance signal (r4 advisor).
        stats["tokens_per_round"] = (
            float(emitted.sum() - B) / active_row_rounds
            if active_row_rounds else 0.0
        )
    # Assemble the generate_ragged output contract.
    full_buf = np.full((B, P + N), pad_token, buf.dtype)
    prompts_h = np.asarray(prompts)
    lens = np.zeros(B, np.int64)
    pl = np.asarray(prompt_lens)
    for b in range(B):
        full_buf[b, : pl[b]] = prompts_h[b, : pl[b]]
        full_buf[b, pl[b]: pl[b] + emitted[b]] = buf[b, : emitted[b]]
        lens[b] = pl[b] + emitted[b]
    return (
        jnp.asarray(full_buf),
        jnp.asarray(lens, jnp.int32),
    )


def prefix_fingerprint(tokens) -> str:
    """Fingerprint of a shared prefix template: what requests carry for
    prefix-aware routing (ISSUE 8) and what keys the per-replica
    template store.  Canonical definition lives jax-free in
    ``serving.replica`` (the journal's prompt-hash family); this
    delegate keeps the model-side surface in one import."""
    from dlrover_tpu.serving.replica import prefix_fingerprint as _fp

    return _fp(tokens)


class KvSegmentError(ValueError):
    """A packed KV segment failed verification (torn bytes, CRC
    mismatch, or a shape/dtype/config mismatch with the importing
    server).  The decode side must NEVER admit such a segment — the
    fleet re-prefills instead (``ServeKvReject``)."""

    #: Duck-typed marker the replica runner branches on (the control
    #: plane must not import this jax-loaded module to classify an
    #: exception; test fakes raise their own marker-carrying error).
    KV_REJECT = True


KV_SEGMENT_VERSION = 1


def pack_kv_segment(layers, n: int, first_token: int,
                    quant: bool, block_size: int = 0) -> Tuple[bytes, int]:
    """Pack a prefilled KV segment for the prefill->decode handoff
    (ISSUE 8).  ``layers`` is the per-layer list of HOST arrays sliced
    to the ``n`` written slots (``[1, KV, n, D]`` codes — int8 +
    per-slot f32 scales when ``quant``, the model dtype otherwise).

    ``block_size > 0`` (ISSUE 19, paged servers) frames the payload as
    a BLOCK LIST instead of one monolithic byte run: the slot axis is
    split into ``ceil(n / block_size)`` fixed-size blocks (last block
    zero-padded), each block's bytes framed contiguously with its OWN
    CRC-32 in the meta — so a torn transfer is localized to a block,
    and a paged decode server can write the frames straight into pool
    blocks.  :func:`unpack_kv_segment` reassembles either framing into
    the same trimmed per-layer arrays; the consumer never cares which
    rode the wire.

    Returns ``(payload, fp32_bytes)``: a self-describing msgpack blob
    with the data CRC-32 embedded (verified by
    :func:`unpack_kv_segment`, the replica-ring payload contract), and
    the segment's un-quantized fp32 size — the int8 transfer saving is
    ``len(payload) / fp32_bytes``."""
    import msgpack
    import zlib

    keys = sorted(layers[0]) if layers else []
    shapes = {}
    meta_extra: Dict[str, Any] = {}
    if block_size > 0:
        bs = int(block_size)
        nblk = -(-int(n) // bs)
        for kk in keys:
            arr = layers[0][kk]
            shapes[kk] = [
                list(arr.shape[:2]) + [bs] + list(arr.shape[3:]),
                str(arr.dtype),
            ]
        frames = []
        bcrc = []
        for b in range(nblk):
            parts = []
            for lay in layers:
                for kk in keys:
                    arr = np.ascontiguousarray(lay[kk])
                    blk = arr[:, :, b * bs: (b + 1) * bs]
                    if blk.shape[2] < bs:
                        pad = [(0, 0)] * blk.ndim
                        pad[2] = (0, bs - blk.shape[2])
                        blk = np.pad(blk, pad)
                    parts.append(np.ascontiguousarray(blk).tobytes())
            frame = b"".join(parts)
            bcrc.append(zlib.crc32(frame))
            frames.append(frame)
        data = b"".join(frames)
        meta_extra = {"bs": bs, "nblk": nblk, "bcrc": bcrc}
        n_units = nblk
    else:
        chunks = []
        for kk in keys:
            arr = layers[0][kk]
            shapes[kk] = [list(arr.shape), str(arr.dtype)]
        for lay in layers:
            for kk in keys:
                arr = np.ascontiguousarray(lay[kk])
                if list(arr.shape) != shapes[kk][0]:
                    raise ValueError(
                        f"ragged KV segment: layer {kk} shape "
                        f"{arr.shape} != {shapes[kk][0]}"
                    )
                chunks.append(arr.tobytes())
        data = b"".join(chunks)
        n_units = 1
    # fp32 equivalent: the k/v codes at 4 bytes/element (scale arrays
    # only exist in the quant layout; they have no fp32 counterpart).
    fp32_bytes = 0
    for kk in ("k", "v"):
        if kk in shapes:
            fp32_bytes += n_units * len(layers) * int(
                np.prod(shapes[kk][0])
            ) * 4
    meta = {
        "v": KV_SEGMENT_VERSION,
        "n": int(n),
        "first": int(first_token),
        "quant": bool(quant),
        "layers": len(layers),
        "keys": keys,
        "shapes": shapes,
        **meta_extra,
    }
    payload = msgpack.packb(
        {"meta": meta, "crc": zlib.crc32(data), "data": data},
        use_bin_type=True,
    )
    return payload, fp32_bytes


def unpack_kv_segment(payload: bytes) -> Dict[str, Any]:
    """Verify + unpack a :func:`pack_kv_segment` blob.  Raises
    :class:`KvSegmentError` on ANY damage (unparseable envelope, CRC
    mismatch, inconsistent sizes) — a torn segment must be rejected,
    never decoded from.  Returns ``{"layers": [...], "n", "first",
    "quant"}`` with per-layer HOST arrays."""
    import msgpack
    import zlib

    try:
        obj = msgpack.unpackb(payload, raw=False)
        meta = obj["meta"]
        crc = int(obj["crc"])
        data = obj["data"]
        keys = list(meta["keys"])
        shapes = meta["shapes"]
        n_layers = int(meta["layers"])
    except Exception as e:
        raise KvSegmentError(f"undecodable KV segment: {e}") from None
    if meta.get("v") != KV_SEGMENT_VERSION:
        raise KvSegmentError(
            f"KV segment version {meta.get('v')} != "
            f"{KV_SEGMENT_VERSION}"
        )
    if zlib.crc32(data) != crc:
        raise KvSegmentError("KV segment CRC mismatch (torn payload)")
    sizes = {
        kk: int(np.prod(shapes[kk][0])) * np.dtype(shapes[kk][1]).itemsize
        for kk in keys
    }
    n = int(meta["n"])
    if "bs" in meta:
        # Block-list framing (ISSUE 19): per-block CRC first — a torn
        # transfer is localized to the block that tore — then the
        # blocks reassemble along the slot axis and trim to ``n``.
        import zlib as _zlib

        bs = int(meta["bs"])
        nblk = int(meta["nblk"])
        bcrc = list(meta["bcrc"])
        frame_size = sum(sizes.values()) * n_layers
        if bs < 1 or nblk < 1 or len(bcrc) != nblk or \
                not (nblk - 1) * bs < n <= nblk * bs:
            raise KvSegmentError(
                f"KV segment block meta incoherent: n={n} bs={bs} "
                f"nblk={nblk} crcs={len(bcrc)}"
            )
        if frame_size * nblk != len(data):
            raise KvSegmentError(
                f"KV segment size mismatch: {nblk} blocks of "
                f"{frame_size} bytes promised, have {len(data)}"
            )
        per_block: list = []
        for b in range(nblk):
            frame = data[b * frame_size: (b + 1) * frame_size]
            if _zlib.crc32(frame) != int(bcrc[b]):
                raise KvSegmentError(
                    f"KV segment block {b}/{nblk} CRC mismatch "
                    "(torn block)"
                )
            off = 0
            lays = []
            for _ in range(n_layers):
                lay = {}
                for kk in keys:
                    shape, dt = shapes[kk]
                    lay[kk] = np.frombuffer(
                        frame, dtype=np.dtype(dt),
                        count=int(np.prod(shape)), offset=off,
                    ).reshape(shape)
                    off += sizes[kk]
                lays.append(lay)
            per_block.append(lays)
        layers = [
            {
                kk: np.concatenate(
                    [per_block[b][li][kk] for b in range(nblk)], axis=2
                )[:, :, :n]
                for kk in keys
            }
            for li in range(n_layers)
        ]
        return {
            "layers": layers, "n": n,
            "first": int(meta["first"]),
            "quant": bool(meta["quant"]),
            "block_size": bs, "blocks": nblk,
        }
    if sum(sizes.values()) * n_layers != len(data):
        raise KvSegmentError(
            f"KV segment size mismatch: meta promises "
            f"{sum(sizes.values()) * n_layers} bytes, have {len(data)}"
        )
    layers = []
    off = 0
    for _ in range(n_layers):
        lay = {}
        for kk in keys:
            shape, dt = shapes[kk]
            lay[kk] = np.frombuffer(
                data, dtype=np.dtype(dt), count=int(np.prod(shape)),
                offset=off,
            ).reshape(shape)
            off += sizes[kk]
        layers.append(lay)
    return {
        "layers": layers,
        "n": n,
        "first": int(meta["first"]),
        "quant": bool(meta["quant"]),
    }


def _adapt_spec_k(cur_k: int, draft_k: int, acc: float) -> int:
    """The adaptive-speculation policy, pure so the arithmetic is
    directly testable (and registered as a sim-bound policy —
    graftcheck DET70x keeps it ambient-effect-free).  ``acc`` is measured tokens-per-active-row-round
    in [1, cur_k+1].  A weak draft (acc near 1) makes every round pay
    cur_k wasted draft forwards — halve.  A strong draft saturating its
    window (acc near cur_k+1) earns a bigger one — double, CAPPED at
    the construction-time ``draft_k``: serve()'s cache-headroom
    capacity check was sized with draft_k, and growing past it would
    let a full-acceptance round scatter beyond max_len."""
    if acc < 1.0 + 0.3 * cur_k and cur_k > 1:
        return max(1, cur_k // 2)
    if acc > 1.0 + 0.8 * cur_k and cur_k < draft_k:
        return min(draft_k, cur_k * 2)
    return cur_k


def _spec_k_request(ewma: float, draft_k: int, break_even: float) -> int:
    """Per-STREAM speculation width from its measured acceptance EWMA
    (ISSUE 11) — pure, so the serving arithmetic is directly testable.
    ``ewma`` is the stream's accepted-tokens-per-round (0 = no
    measurement yet: start at full width and let the first rounds
    decide).  Below ``break_even`` — the measured round-cost ratio
    ``(t_draft_roll + t_verify) / t_plain_step`` from
    one CPU run of the three components — drafting costs more
    target-equivalent time than it saves, so the stream decodes PLAIN
    (k = 0): a bad draft can never make a request slower than a
    spec-less replica serves it.  Above break-even the stream keeps a
    width it actually fills (capped at ``draft_k``: the cache headroom
    was sized with it)."""
    if ewma <= 0.0:
        return draft_k
    if ewma < break_even:
        return 0
    return max(1, min(draft_k, int(ewma)))


def _spec_remote_round(
    progs: Dict,
    params: Dict,
    cache_t: Dict,
    cur: jax.Array,  # [B] current input token per row
    done: np.ndarray,  # [B] frozen rows
    d_host: np.ndarray,  # [B, k] proposals (remote draft; zeros ok)
    q_host: Optional[np.ndarray],  # [B, k, V] draft probs (sampled)
    k: int,
    sample: bool,
    np_rng: "np.random.Generator",
    k_row: Optional[np.ndarray] = None,
    max_off: Optional[np.ndarray] = None,
) -> Tuple[list, np.ndarray, Dict]:
    """ONE speculative round whose proposals arrived from a REMOTE
    draft replica (ISSUE 11): the target-side half of
    :func:`_spec_decode_round` — chunked verify, per-row acceptance,
    cache rewind — with no local draft cache to maintain (the draft
    replica keeps its own per-stream cache and catches up from the
    context deltas the next roll ships).  Acceptance laws are shared
    with the local path, so the emitted stream per row is identical to
    sequential target decoding whatever the remote draft proposes."""
    B = int(cur.shape[0])
    n_dev = cache_t["offset"]
    chunk = jnp.concatenate(
        [cur[:, None], jnp.asarray(d_host, jnp.int32)], axis=1
    )  # [B, k+1]
    g, cache_t = progs["target_verify"](params, cache_t, chunk)
    rows = np.arange(B)
    cur_h = np.asarray(cur)
    if sample:
        n, g_raw = jax.device_get((n_dev, g))
        g_h = np.asarray(g_raw, np.float64)  # [B, k+1, V]
        j, tok = _spec_accept_batch(
            g_h, np.asarray(q_host, np.float64), d_host, done, np_rng,
            k_row=k_row,
        )
        nxt = np.where(done, cur_h, tok).astype(cur_h.dtype)
    else:
        n, g_h = jax.device_get((n_dev, g))  # g [B, k+1]
        match = (d_host == g_h[:, :k]).astype(np.int64)
        j = match.cumprod(axis=1).sum(axis=1)
        if k_row is not None:
            j = np.minimum(j, np.asarray(k_row, np.int64))
        j = np.where(done, 0, j)
        nxt = np.where(done, cur_h, g_h[rows, j]).astype(cur_h.dtype)
    n = np.asarray(n)
    new_n = np.where(done, n, n + 1 + j)
    if max_off is not None:
        new_n = np.minimum(new_n, max_off)
    cache_t = dict(cache_t, offset=jnp.asarray(new_n, jnp.int32))
    accepted_rows = [
        [] if done[b] else list(d_host[b, : j[b]]) + [nxt[b]]
        for b in range(B)
    ]
    return accepted_rows, nxt, cache_t


# -- paged KV: block-table memory for the decode hot path (ISSUE 19) -----
#
# The slotted server reserves one contiguous [max_len] cache row per
# slot, so admitted-batch occupancy is bounded by WORST-CASE sequence
# length — most of that memory is stranded headroom.  The paged arena
# (the vllm/PagedAttention idiom) decouples a request's logical KV from
# physical placement: the cache is a pool of fixed-size blocks
# ([n_blocks + 1, KV, block_size, D] per layer, one shared block-id
# space across layers; the +1 row is a scratch block that absorbs
# writes through unallocated table entries), and each slot maps logical
# block i to a physical block through a host-owned [slots, max_blocks]
# table.  The decode/chunk/prefill jits re-index through the table:
# gather ``pool[table]`` -> the SAME dense [B, KV, max_len, D] view the
# slotted jits compute on (so the attention math — and the greedy token
# stream — is byte-identical by construction), then scatter the view
# back through the table.  Stale bytes in not-yet-written block slots
# are invisible: the causal mask sends every position > offset to
# -1e30 before softmax, an exactly-0.0 weight on both the score*ks and
# p*vs paths.

def _paged_block_split(x: jax.Array, n_blocks: int,
                       block_size: int) -> jax.Array:
    """[KV, L(, D)] -> [n_blocks, KV, block_size(, D)] (L >= nb*bs)."""
    x = x[:, : n_blocks * block_size]
    x = x.reshape(
        (x.shape[0], n_blocks, block_size) + x.shape[2:]
    )
    return jnp.moveaxis(x, 0, 1)


def _paged_dense_view(pool_layers: list, table: jax.Array) -> list:
    """Gather the per-slot dense cache view through the block table:
    pool [NB+1, KV, BS, ...] + table [B, MB] -> [B, KV, MB*BS, ...]."""
    out = []
    for pl in pool_layers:
        lay = {}
        for kk, arr in pl.items():
            g = arr[table]                      # [B, MB, KV, BS, ...]
            g = jnp.moveaxis(g, 2, 1)           # [B, KV, MB, BS, ...]
            lay[kk] = g.reshape(
                g.shape[:2] + (g.shape[2] * g.shape[3],) + g.shape[4:]
            )
        out.append(lay)
    return out


def _paged_scatter_back(pool_layers: list, dense_layers: list,
                        table: jax.Array) -> list:
    """Inverse of :func:`_paged_dense_view`: write the dense view back
    through the table.  Table entries may repeat (CoW-shared prefix
    blocks, the scratch sentinel): shared blocks are only ever written
    VALUES THEY ALREADY HOLD (writes land at >= the sharer's first
    owned position), so duplicate-index resolution order cannot change
    the result; the scratch block absorbs every write through an
    unallocated entry and is never meaningfully read (causal mask)."""
    B, MB = table.shape
    out = []
    for pl, dl in zip(pool_layers, dense_layers):
        lay = {}
        for kk, arr in pl.items():
            d = dl[kk]                          # [B, KV, MB*BS, ...]
            d = d.reshape(
                d.shape[:2] + (MB, d.shape[2] // MB) + d.shape[3:]
            )
            d = jnp.moveaxis(d, 1, 2)           # [B, MB, KV, BS, ...]
            lay[kk] = arr.at[table].set(d)
        out.append(lay)
    return out


def _paged_row_view(pool_layers: list, table_s: jax.Array) -> list:
    """One slot's dense [1, KV, MB*BS, ...] view (table_s: [MB])."""
    out = []
    for pl in pool_layers:
        lay = {}
        for kk, arr in pl.items():
            g = arr[table_s]                    # [MB, KV, BS, ...]
            g = jnp.moveaxis(g, 0, 1)           # [KV, MB, BS, ...]
            lay[kk] = g.reshape(
                (g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:]
            )[None]
        out.append(lay)
    return out


def _paged_row_scatter(pool_layers: list, dense_layers: list,
                       table_s: jax.Array) -> list:
    """Write one slot's dense [1, KV, MB*BS, ...] rows back through its
    table row (same duplicate-index safety as the batch scatter)."""
    MB = table_s.shape[0]
    out = []
    for pl, dl in zip(pool_layers, dense_layers):
        lay = {}
        for kk, arr in pl.items():
            d = dl[kk][0]                       # [KV, MB*BS, ...]
            lay[kk] = arr.at[table_s].set(
                _paged_block_split(d, MB, d.shape[1] // MB)
            )
        out.append(lay)
    return out


def init_paged_pool(cfg: LlamaConfig, n_blocks: int, block_size: int,
                    *, quant_kv: bool = False) -> Dict:
    """Zeroed paged KV pool: per-layer [n_blocks + 1, KV, block_size,
    D] arrays (+ absmax scales under ``quant_kv``), one block-id space
    shared by every layer (block i is backed at row i of EVERY layer's
    arrays, the vllm layout).  Row ``n_blocks`` is the scratch block —
    never allocated; unassigned table entries point here so stray
    writes land somewhere harmless."""
    llama.refuse_training_path_only(
        cfg, "the paged KV pool (models.llama_infer)")
    KV, D = cfg.n_kv_head, cfg.head_dim
    NB = n_blocks + 1

    def _layer() -> Dict:
        if quant_kv:
            return {
                "k": jnp.zeros((NB, KV, block_size, D), jnp.int8),
                "v": jnp.zeros((NB, KV, block_size, D), jnp.int8),
                "ks": jnp.zeros((NB, KV, block_size), jnp.float32),
                "vs": jnp.zeros((NB, KV, block_size), jnp.float32),
            }
        return {
            "k": jnp.zeros((NB, KV, block_size, D), cfg.dtype),
            "v": jnp.zeros((NB, KV, block_size, D), cfg.dtype),
        }

    return {"layers": [_layer() for _ in range(cfg.n_layer)]}


class PagedKvArena:
    """Host-side allocator for the paged KV pool: the free list, the
    per-slot block table, and the per-block refcounts that make
    copy-on-write prefix sharing safe.  Pure bookkeeping — no device
    arrays; the serve loop uploads ``table`` per dispatch and the jits
    re-index through it.

    Conservation law (the tier-1 invariant): every block is either on
    the free list or referenced (by a slot table or a held template) —
    ``free_blocks + used_blocks == n_blocks`` always, where
    ``used_blocks`` counts each physical block ONCE however many
    tables share it.  The chaos site ``serving.block_leak`` models a
    dropped free (refcount reaches zero but the block never returns to
    the list); :meth:`scavenge` — run every serve-loop iteration — is
    the defense that rebuilds the free list from the refcounts, so the
    law holds after any chaos run."""

    def __init__(self, n_blocks: int, block_size: int, slots: int,
                 max_len: int):
        if max_len % block_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of block_size "
                f"{block_size} (the gathered dense view must match the "
                "slotted cache shape exactly for byte-identity)"
            )
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.slots = int(slots)
        self.max_blocks = max_len // block_size
        #: Scratch sentinel: one past the last allocatable block (the
        #: pool arrays carry an extra physical row for it).
        self.scratch = self.n_blocks
        self.leaks_repaired = 0
        self.reset()

    def reset(self) -> None:
        self.table = np.full(
            (self.slots, self.max_blocks), self.scratch, np.int32
        )
        self.lens = np.zeros((self.slots,), np.int64)
        self.ref = np.zeros((self.n_blocks,), np.int64)
        self._free = list(range(self.n_blocks - 1, -1, -1))

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Physical blocks referenced at least once (shared prefix
        blocks count ONCE — this is real memory, not table entries)."""
        return int((self.ref > 0).sum())

    def table_tokens(self) -> int:
        """Total LOGICAL tokens of table capacity currently mapped
        (``sum(table lens)`` in block units x block_size) — the
        admitted-batch footprint the occupancy metric reports."""
        return int(self.lens.sum()) * self.block_size

    def blocks_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.block_size)

    def conserved(self) -> bool:
        """``free_blocks + used_blocks == n_blocks`` AND the free list
        agrees with the refcounts — the invariant the block-leak chaos
        site attacks and :meth:`scavenge` defends."""
        return (
            len(self._free) + self.used_blocks == self.n_blocks
            and all(self.ref[b] == 0 for b in self._free)
        )

    def scavenge(self) -> int:
        """Rebuild the free list from the refcounts, reclaiming any
        block whose frees were dropped (the ``serving.block_leak``
        fault).  Returns the number of leaked blocks repaired."""
        free = [b for b in range(self.n_blocks) if self.ref[b] == 0]
        leaked = len(free) - len(self._free)
        if leaked > 0:
            self.leaks_repaired += leaked
        self._free = free
        return max(0, leaked)

    def _take(self) -> int:
        blk = self._free.pop()
        self.ref[blk] = 1
        return blk

    def alloc_upto(self, s: int, tokens: int) -> bool:
        """Grow slot ``s``'s table to cover ``tokens`` logical
        positions (grow-on-demand: a request only ever holds the
        blocks its CURRENT offset + this round's writes need).  False
        — with no state change — when the pool cannot cover it."""
        need = min(self.blocks_for(tokens), self.max_blocks)
        add = need - int(self.lens[s])
        if add <= 0:
            return True
        if add > len(self._free):
            return False
        for _ in range(add):
            self.table[s, self.lens[s]] = self._take()
            self.lens[s] += 1
        return True

    def share(self, s: int, blocks: list) -> None:
        """Map slot ``s``'s first logical blocks onto ``blocks``
        (prefix sharing: refcount up, zero copies).  Only legal on an
        empty slot row."""
        assert self.lens[s] == 0
        for i, b in enumerate(blocks):
            self.table[s, i] = b
            self.ref[b] += 1
        self.lens[s] = len(blocks)

    def hold(self, n: int) -> Optional[list]:
        """Allocate ``n`` blocks owned by a prefix TEMPLATE (refcount
        held by the store, not any slot).  None if the pool is too
        tight — the caller falls back to an untemplated admission."""
        if n > len(self._free):
            return None
        return [self._take() for _ in range(n)]

    def release(self, blocks: list) -> None:
        """Drop a template's hold on ``blocks`` (store eviction)."""
        for b in blocks:
            self._drop_ref(int(b))

    def _drop_ref(self, blk: int) -> None:
        self.ref[blk] -= 1
        if self.ref[blk] == 0:
            from dlrover_tpu import chaos
            if chaos.inject("serving.block_leak", block=blk):
                # Fault: the free is dropped — the block is referenced
                # by nobody and on no list.  scavenge() repairs.
                return
            self._free.append(blk)

    def free_slot(self, s: int) -> None:
        """Return slot ``s``'s blocks (abort, deadline shed, finish,
        preemption): refcount down, back on the free list at zero —
        shared prefix blocks survive for their other holders."""
        for i in range(int(self.lens[s])):
            self._drop_ref(int(self.table[s, i]))
        self.table[s, :] = self.scratch
        self.lens[s] = 0


class DecodeServer:
    """Continuous-batching greedy/sampled decode over fixed slots — the
    role vllm plays for the reference's RL engine
    (``atorch/rl/model_engine/model_engine.py:35``): admission of new
    prompts into slots as sequences finish, so a stream of requests
    keeps every slot busy instead of waiting for the batch's slowest
    member.

    TPU shape: ONE jitted single-token step over all ``slots`` (ragged
    per-slot offsets), plus one jitted per-bucket prefill that scores a
    new prompt into a single slot's cache rows.  The host loop only
    schedules; every FLOP runs under jit at static shapes.

        srv = DecodeServer(params, cfg, slots=8, max_len=512,
                           eos_token=2)
        outs = srv.serve(list_of_prompt_arrays, max_new_tokens=128)
    """

    def __init__(
        self,
        params: Dict,
        cfg: LlamaConfig,
        *,
        slots: int = 8,
        max_len: int = 512,
        eos_token: int = -1,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        prompt_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256),
        seed: int = 0,
        quant_kv: bool = False,  # int8 kv cache (see init_cache)
        draft: Optional[Tuple[Dict, LlamaConfig]] = None,
        draft_k: int = 4,
        adapt_k: bool = False,  # shrink/regrow k from measured acceptance
        adapt_every: int = 16,  # rounds per adaptation window
        # Per-REQUEST adaptive k (ISSUE 11, the serving mode): each
        # stream carries its own acceptance EWMA and speculation width
        # (``_spec_k_request``); below ``spec_break_even`` the stream
        # decodes plain (k=0, probed again every ``spec_probe_every``
        # of its rounds), so a bad draft can never make a request
        # slower than a spec-less replica.  Mutually exclusive with the
        # global ``adapt_k`` window policy.
        adapt_k_per_request: bool = False,
        spec_break_even: float = 0.0,  # 0 = 1 + 0.6*draft_k (measured
        # shape of a CPU run's break-even at k=4; the chip's: not measured)
        spec_probe_every: int = 32,
        spec_ewma_alpha: float = 0.25,
        # Remote-draft speculation (ISSUE 11): the server may be handed
        # a draft PROPOSAL handle (``set_remote_draft``) whose rolls
        # run on a separate draft replica; declaring the intent at
        # construction sizes the cache-write headroom for speculative
        # overshoot even before a draft is attached.
        spec_remote: bool = False,
        # Plain (non-speculative) decode: tokens per dispatch.  K > 1
        # runs K steps under one lax.scan dispatch — K x fewer device
        # round-trips and host emit loops.  The cost is admission
        # latency (a slot finishing mid-chunk waits out the remainder
        # before its slot re-admits) and up to K-1 wasted writes per
        # finishing slot (covered by the capacity check's headroom;
        # finished slots are re-zeroed at admission).
        decode_chunk: int = 1,
        # Warm prefix templates retained (ISSUE 8): the incremental
        # path caches one prefilled template per prefix fingerprint so
        # requests sharing a system prompt admit with a row copy + one
        # chunk score instead of a full prefill; the gateway routes
        # fp-carrying requests to replicas already holding the
        # template.  LRU-bounded — each template is n_layer full cache
        # rows of memory.
        prefix_cache_cap: int = 4,
        # Paged KV (ISSUE 19): the cache becomes a pool of fixed-size
        # blocks plus a per-slot block table; admission reserves only
        # the blocks a request needs NOW and grows on demand, prefix
        # templates share blocks copy-on-write, and abort/finish
        # return blocks to the pool instantly.  ``pool_blocks``
        # defaults to slots * max_len / block_size — exactly the
        # slotted layout's memory, so paged-vs-slotted comparisons are
        # at matched memory unless the caller says otherwise.  Greedy
        # output is byte-identical to slotted mode (the jits gather a
        # dense view through the table and run the SAME attention
        # program).
        paged: bool = False,
        block_size: int = 16,
        pool_blocks: Optional[int] = None,
    ):
        # Sliding-window models serve on a DENSE cache (init_cache
        # ring=False): the window mask still applies in attention; the
        # ring layout's O(window) memory is incompatible with the
        # per-slot ragged offsets and rewinds this server relies on.
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.eos_token = eos_token
        self.quant_kv = quant_kv
        # ``draft=(draft_params, draft_cfg)``: serve() steps via
        # speculative rounds (draft proposes draft_k, ONE chunked
        # ragged verify over all slots, per-slot acceptance) —
        # continuous batching x speculation, the full vllm-spec-decode
        # shape.  Token law per request is unchanged.
        self.draft = draft
        self.draft_k = draft_k
        self.adapt_k = adapt_k
        self.adapt_every = max(1, adapt_every)
        self.adapt_k_per_request = adapt_k_per_request
        if adapt_k and adapt_k_per_request:
            raise ValueError(
                "adapt_k (global window) and adapt_k_per_request "
                "(per-stream EWMA) are mutually exclusive policies"
            )
        self.spec_break_even = (
            float(spec_break_even) if spec_break_even > 0
            else 1.0 + 0.6 * draft_k
        )
        self.spec_probe_every = max(1, int(spec_probe_every))
        self.spec_ewma_alpha = float(spec_ewma_alpha)
        self.spec_remote = bool(spec_remote)
        #: Remote draft-proposal handle (``propose(reqs, k, sample=,
        #: close=) -> {rid: {"d": [k] ints, "q": [k, V] or None}}``);
        #: set/cleared by the replica runner as draft replicas come and
        #: go.  Any handle failure degrades THIS serve loop to plain
        #: decode until a DIFFERENT handle is attached.
        self._remote_draft: Optional[Any] = None
        #: Reusable [slots, draft_k, V] draft-prob buffer for sampled
        #: remote rounds (a fresh float64 alloc per round would be MBs
        #: of churn at production vocab sizes; stale values in rows a
        #: round does not ship are never read past their width).
        self._spec_q_buf: Optional[np.ndarray] = None
        if spec_remote and draft is not None:
            raise ValueError(
                "spec_remote does not compose with a local draft "
                "model (one proposal source per server)"
            )
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got "
                             f"{decode_chunk}")
        if decode_chunk > 1 and (draft is not None or spec_remote):
            # Speculative rounds already batch k+1 tokens per dispatch;
            # silently ignoring the flag would let a user believe they
            # are benchmarking the K-dispatch lever while measuring
            # plain speculative rounds.
            raise ValueError(
                "decode_chunk > 1 does not compose with a draft model "
                "(speculative rounds already batch tokens per "
                "dispatch); set one or the other"
            )
        self.decode_chunk = decode_chunk
        # Telemetry of the last serve() call, reset at the top of every
        # serve(): the speculative path reports rounds / acceptance /
        # the k trajectory; the plain and decode_chunk paths report
        # rounds and emitted tokens.
        self.last_stats: Dict[str, Any] = {}
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self._np_rng = np.random.default_rng(seed + 1)
        self.buckets = tuple(
            b for b in sorted(prompt_buckets) if b <= max_len
        )
        self._pick = _make_sampler(temperature, top_k, top_p)
        self._prefill_jit: Dict[Any, Any] = {}
        # Host-managed sampling stream: every step/prefill consumes a
        # FRESH subkey (a constant key would make non-greedy serving
        # degenerate — identical noise each step collapses samples into
        # short loops).
        self._rng = jax.random.PRNGKey(seed)
        # Incremental admission surface (ISSUE 5): ``submit`` enqueues
        # (rid, prompt, max_new_tokens) and the serve loop admits from
        # this deque as slots free — the fleet replica feeds gateway
        # grants in while decoding, instead of handing the full prompt
        # list up front.  The lock makes submit/cancel safe from a
        # second thread, though the fleet runner is single-threaded.
        self._pending: "collections.deque" = collections.deque()
        self._pending_mu = threading.Lock()
        self._abort_rids: set = set()
        # Prefix-template store (ISSUE 8): fp -> {"prefix", "p0",
        # "layers": {role: template layers}}, LRU order.  Hit/miss
        # counts feed the replica's poll stats so the gateway's
        # residency map self-corrects.
        self.prefix_cache_cap = max(1, int(prefix_cache_cap))
        # Paged KV arena (ISSUE 19).
        self.paged = bool(paged)
        self.block_size = int(block_size)
        if self.paged:
            if block_size < 1:
                raise ValueError(f"block_size must be >= 1, got "
                                 f"{block_size}")
            if max_len % self.block_size:
                raise ValueError(
                    f"paged mode needs max_len ({max_len}) to be a "
                    f"multiple of block_size ({block_size}): the "
                    "gathered view must match the slotted cache shape "
                    "exactly for byte-identical output"
                )
        self.pool_blocks = (
            int(pool_blocks) if pool_blocks is not None
            else slots * (max_len // self.block_size)
        ) if self.paged else 0
        self.kv_arena: Optional[PagedKvArena] = (
            PagedKvArena(self.pool_blocks, self.block_size, slots,
                         max_len)
            if self.paged else None
        )
        #: Preemptions this serve call (paged grow-on-demand sheds the
        #: youngest slot when the pool runs dry; the request requeues
        #: at the FRONT and greedy decode regenerates its stream).
        self.preemptions = 0
        #: rid -> tokens already delivered via on_token before a
        #: preemption (re-admission suppresses re-emitting them).
        self._preempt_emitted: Dict[Any, int] = {}
        #: Monotone serve-call counter: paged prefix templates
        #: materialize pool blocks per RUN (the pool is rebuilt each
        #: serve call) and tag them with this.
        self._paged_run_seq = 0
        self._prefix_store: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        # Prefill-role exports (ISSUE 8): rid -> prefilled slot rows
        # awaiting export_kv (host arrays; dropped on export).
        self._kv_exports: Dict[Any, Dict[str, Any]] = {}
        # Per-request speculation telemetry (ISSUE 11): finished
        # requests park their accepted-tokens-per-round here until the
        # runner pops them into the ServeDone/journal record.  Bounded
        # oldest-first — the runner pops immediately, the cap only
        # guards a caller that never does.
        self._req_stats_out: "collections.OrderedDict" = \
            collections.OrderedDict()
        # Live views for the replica runner's poll report (valid while
        # a serve loop runs; empty otherwise).
        self._live_active: Any = None
        self._live_slot_req: Any = None

        def step(params, cache, toks, active, sub):
            logits, new_cache = forward_step(
                params, toks[:, None], cfg, cache
            )
            nxt = self._pick(logits[:, -1, :], sub)
            # Inactive slots freeze (offset unchanged -> cache rows
            # stable while awaiting admission).
            frozen = jnp.where(
                active, new_cache["offset"], cache["offset"]
            )
            return dict(new_cache, offset=frozen), nxt.astype(toks.dtype)

        self._step = jax.jit(step)

        def chunk_step(params, cache, toks, active, sub):
            # decode_chunk steps under ONE dispatch (lax.scan): each
            # dispatch costs host latency, and the host emit loop costs
            # more — K tokens per round divides both by K.
            def body(carry, key):
                cache, toks = carry
                cache, nxt = step(params, cache, toks, active, key)
                return (cache, nxt), nxt

            (cache, toks), ys = jax.lax.scan(
                body, (cache, toks),
                jax.random.split(sub, self.decode_chunk),
            )
            return cache, toks, jnp.moveaxis(ys, 0, 1)  # [B, K]

        self._chunk_step = jax.jit(chunk_step)

        if self.paged:
            # The decode hot path re-indexed through the block table
            # (ISSUE 19): gather pool[table] -> the SAME dense view the
            # slotted jits compute on, run the identical step program,
            # scatter the view back.  One compiled program per shape,
            # memoized like every other jit here; the chunk variant
            # amortizes the gather/scatter over decode_chunk steps.
            def step_paged(params, pool_layers, table, offset, toks,
                           active, sub):
                dense = {
                    "layers": _paged_dense_view(pool_layers, table),
                    "offset": offset,
                }
                new_dense, nxt = step(params, dense, toks, active, sub)
                return (
                    _paged_scatter_back(
                        pool_layers, new_dense["layers"], table
                    ),
                    new_dense["offset"], nxt,
                )

            self._step_paged = jax.jit(step_paged)

            def chunk_step_paged(params, pool_layers, table, offset,
                                 toks, active, sub):
                dense = {
                    "layers": _paged_dense_view(pool_layers, table),
                    "offset": offset,
                }
                dense, toks, ys = chunk_step(
                    params, dense, toks, active, sub
                )
                return (
                    _paged_scatter_back(
                        pool_layers, dense["layers"], table
                    ),
                    dense["offset"], toks, ys,
                )

            self._chunk_step_paged = jax.jit(chunk_step_paged)

            # Whole-cache gather/scatter, for the speculative rounds:
            # the spec programs (_spec_decode_round and friends) run
            # unchanged on the gathered dense view, then the view
            # scatters back — two extra dispatches per spec round buy
            # zero drift from the slotted acceptance laws.
            def gather_all(pool_layers, table, offset):
                return {
                    "layers": _paged_dense_view(pool_layers, table),
                    "offset": offset,
                }

            self._paged_gather = jax.jit(gather_all)
            self._paged_scatter = jax.jit(_paged_scatter_back)

    def block_stats(self) -> Optional[Dict[str, Any]]:
        """Live block-pool telemetry (None on a slotted server): what
        the replica folds into its gateway poll so admission and
        autoscale see real memory headroom instead of slot counts."""
        arena = self.kv_arena
        if arena is None:
            return None
        used = arena.used_blocks
        return {
            "total_blocks": arena.n_blocks,
            "free_blocks": arena.free_blocks,
            "block_occupancy": used / max(1, arena.n_blocks),
            "preemptions": self.preemptions,
        }

    def _next_key(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt of {n} tokens exceeds largest bucket "
            f"{self.buckets[-1]}"
        )

    def _write_slack(self) -> int:
        """Cache-write headroom past the emission budget: speculative
        rounds (local OR remote draft) overshoot by up to draft_k+1
        slots before the rewind; chunked decode writes up to
        decode_chunk-1 slots past a mid-chunk finish.  An out-of-range
        scatter is silently DROPPED by JAX, so every capacity check
        must include this."""
        return (
            (self.draft_k + 1) if self.spec_capable
            else self.decode_chunk - 1
        )

    @property
    def spec_capable(self) -> bool:
        """This server can run speculative rounds: a local draft model,
        or the declared intent to accept a remote draft handle — what
        the replica advertises in ``ServeReplicaRegister.spec``."""
        return self.draft is not None or self.spec_remote

    def set_remote_draft(self, handle) -> None:
        """Attach (or with ``None`` detach) a remote draft-proposal
        handle.  Only legal on a ``spec_remote`` server — the cache
        headroom and capacity checks were sized for speculation at
        construction; attaching a draft to an unsized server could
        scatter past max_len."""
        if handle is not None and not self.spec_remote:
            raise ValueError(
                "set_remote_draft on a server built without "
                "spec_remote=True (capacity headroom not sized for "
                "speculative overshoot)"
            )
        self._remote_draft = handle

    def pop_request_stats(self, rid) -> Optional[Dict[str, Any]]:
        """Consume the per-request speculation telemetry recorded when
        ``rid`` finished: ``{"tokens_per_round", "spec_rounds",
        "k_last"}`` — what the runner folds into the ServeDone report
        and the journal record (so a replay reports the SAME
        acceptance the request earned live).  None for requests that
        never ran speculative rounds."""
        with self._pending_mu:
            return self._req_stats_out.pop(rid, None)

    def check_capacity(self, prompt_len: int, max_new_tokens: int,
                       prefix_len: int = 0) -> None:
        """Raise ValueError if a request of this shape could ever write
        past ``max_len`` (shared by serve()'s upfront sweep and
        submit()'s per-request admission check)."""
        need = prefix_len + prompt_len + max_new_tokens + \
            self._write_slack()
        if need > self.max_len:
            raise ValueError(
                (f"prefix {prefix_len} + " if prefix_len else "")
                + f"prompt {prompt_len} + max_new_tokens "
                f"{max_new_tokens} + headroom {self._write_slack()} "
                f"= {need} exceeds max_len {self.max_len}"
            )
        if self.paged:
            # Pool-wide law: a request that could never fit the WHOLE
            # pool (even alone) must reject at submit, not livelock
            # the admission loop waiting for blocks that cannot exist.
            blocks = self.kv_arena.blocks_for(need)
            if blocks > self.pool_blocks:
                raise ValueError(
                    f"request needs {blocks} KV blocks "
                    f"({need} tokens at block_size "
                    f"{self.block_size}) but the pool holds only "
                    f"{self.pool_blocks}"
                )

    def submit(self, rid, prompt, max_new_tokens: int,
               prefix_len: int = 0, prefix_fp: str = "") -> None:
        """Enqueue one request for incremental admission: the running
        serve loop (``serve_incremental``) admits it the next time a
        slot frees.  ``rid`` is the caller's request key (any hashable
        — the fleet uses gateway request-id strings).  Raises
        ValueError immediately if the request can never fit.

        ``prefix_len > 0`` declares ``prompt[:prefix_len]`` a shared
        template (ISSUE 8): admission rides the per-fingerprint prefix
        store — a warm template admits with a row copy + one chunk
        score; a cold one is prefilled once and retained (LRU) for the
        next request carrying the same fingerprint.  Results are
        byte-identical to the untemplated path."""
        p = np.asarray(prompt, np.int32)
        self.check_capacity(len(p), max_new_tokens)
        extra = None
        if prefix_len:
            if not 0 < int(prefix_len) < len(p):
                raise ValueError(
                    f"prefix_len {prefix_len} out of range for a "
                    f"{len(p)}-token prompt"
                )
            extra = {
                "prefix_len": int(prefix_len),
                "prefix_fp": prefix_fp
                or prefix_fingerprint(p[: int(prefix_len)]),
            }
        with self._pending_mu:
            self._pending.append((rid, p, int(max_new_tokens), extra))

    def cancel(self, rid) -> bool:
        """Drop a not-yet-admitted request (deadline expiry at the
        gateway).  Returns False when ``rid`` is unknown or already
        decoding — in-flight work is never interrupted."""
        with self._pending_mu:
            for i, item in enumerate(self._pending):
                if item[0] == rid:
                    del self._pending[i]
                    return True
        return False

    def abort(self, rid) -> bool:
        """Mid-decode load shedding (gateway deadline expiry): a
        pending ``rid`` is dropped immediately; an ACTIVE one is freed
        at the loop's next admission point, its partial output
        discarded — no ``on_finish``, no results entry, the slot
        re-admits.  Returns False for an unknown (or already finished)
        rid."""
        if self.cancel(rid):
            return True
        if rid in self.active_rids():
            with self._pending_mu:
                self._abort_rids.add(rid)
            return True
        return False

    def _pop_pending(self):
        with self._pending_mu:
            return self._pending.popleft() if self._pending else None

    def pending_count(self) -> int:
        with self._pending_mu:
            return len(self._pending)

    def pending_rids(self) -> list:
        with self._pending_mu:
            return [item[0] for item in self._pending]

    def active_rids(self) -> list:
        """Request ids currently decoding in slots (live only while a
        serve loop runs)."""
        act, req = self._live_active, self._live_slot_req
        if act is None or req is None:
            return []
        return [req[s] for s in range(self.slots) if act[s]]

    def free_slots(self) -> int:
        """Slots a new admission could use right now: total minus
        decoding minus already-queued — the load signal the replica
        reports to the gateway's least-loaded router."""
        act = self._live_active
        busy = int(act.sum()) if act is not None else 0
        return max(0, self.slots - busy - self.pending_count())

    # -- prefix templates & prefill/decode disaggregation (ISSUE 8) ------

    def warm_prefix_fps(self) -> list:
        """Fingerprints of the prefix templates currently held warm —
        what the replica reports in its gateway poll so the router can
        steer matching requests here."""
        with self._pending_mu:
            return list(self._prefix_store)

    def clear_prefix_templates(self) -> None:
        """Drop every warm template and zero the hit/miss counters —
        warmup hygiene: a compile-warming dummy must not occupy the
        LRU, report warm to the router, or skew the hit-rate."""
        with self._pending_mu:
            for entry in self._prefix_store.values():
                self._release_template_blocks(entry)
            self._prefix_store.clear()
            self.prefix_hits = 0
            self.prefix_misses = 0

    def _roles(self):
        roles = [("t", self.params, self.cfg)]
        if self.draft is not None:
            roles.append(("d", self.draft[0], self.draft[1]))
        return roles

    def _template_layers(self, role, mparams, mcfg, pref_dev, P0):
        """Prefill ``pref_dev`` [1, P0] into a fresh 1-row cache and
        return its layers — THE template build, shared by the batch
        path (``_build_prefix_templates``) and the fingerprint store.
        Memoized per (role, prefix length); only the cache is returned,
        so XLA dead-code-eliminates the lm_head matmul."""
        tc = init_cache(mcfg, 1, self.max_len,
                        quant_kv=self.quant_kv, ring=False)
        jkey = ("tmpl_prefill", role, P0)
        if jkey not in self._prefill_jit:
            def fn(p, pr, c, _cfg=mcfg):
                return forward_step(p, pr, _cfg, c)[1]

            self._prefill_jit[jkey] = jax.jit(fn)
        return self._prefill_jit[jkey](mparams, pref_dev, tc)["layers"]

    def _ensure_prefix_template(self, prefix, fp: str) -> Dict[str, Any]:
        """Template-store lookup/build for one fingerprint: a hit
        returns the warm entry (LRU-refreshed); a miss — or an entry
        whose stored prefix MISMATCHES the fingerprint's claimed tokens
        (collision, stale reuse) — prefills the template once and
        retains it, evicting the coldest past ``prefix_cache_cap``."""
        prefix = np.asarray(prefix, np.int32)
        # Store mutations ride _pending_mu (the readers —
        # warm_prefix_fps from a poll thread, clear_prefix_templates —
        # already do); the template BUILD runs outside the lock, it is
        # seconds of XLA on a cold fingerprint.
        with self._pending_mu:
            entry = self._prefix_store.get(fp)
            if entry is not None and (
                entry["p0"] != len(prefix)
                or not np.array_equal(entry["prefix"], prefix)
            ):
                # Fingerprint mismatch: never serve another prefix's
                # rows.
                del self._prefix_store[fp]
                self._release_template_blocks(entry)
                entry = None
            if entry is not None:
                self.prefix_hits += 1
                self._prefix_store.move_to_end(fp)
                return entry
            self.prefix_misses += 1
        P0 = len(prefix)
        pref_dev = jnp.asarray(prefix)[None, :]
        layers = {
            role: self._template_layers(role, mparams, mcfg,
                                        pref_dev, P0)
            for role, mparams, mcfg in self._roles()
        }
        entry = {"prefix": prefix, "p0": P0, "layers": layers}
        with self._pending_mu:
            self._prefix_store[fp] = entry
            while len(self._prefix_store) > self.prefix_cache_cap:
                _, old = self._prefix_store.popitem(last=False)
                self._release_template_blocks(old)
        return entry

    def _release_template_blocks(self, entry: Dict[str, Any]) -> None:
        """Return an evicted template's pool blocks (paged mode): the
        store's refcount hold drops; blocks a live slot still SHARES
        survive on that slot's own refcount."""
        pb = entry.pop("_paged", None)
        if (
            pb is not None
            and self.kv_arena is not None
            and pb.get("run") == self._paged_run_seq
        ):
            self.kv_arena.release(pb["ids"])

    def prefill_request(self, rid, prompt, max_new_tokens: int,
                        prefix_len: int = 0,
                        prefix_fp: str = "") -> int:
        """Prefill-role entry (ISSUE 8): score ``prompt`` into a fresh
        1-row cache (prefix templates honoured), sample the first
        token, and stage the written rows for :meth:`export_kv`.
        Returns the first token.  Host-synchronous — a prefill replica
        does nothing else with its slots."""
        if self.draft is not None:
            raise ValueError(
                "prefill/decode disaggregation does not compose with "
                "a draft model (the draft cache is not shipped)"
            )
        p = np.asarray(prompt, np.int32)
        n = len(p)
        self.check_capacity(n, max_new_tokens)
        C = self.buckets[-1]
        tmpl = None
        p0 = 0
        if prefix_len and n > C:
            p0 = int(prefix_len)
            if not 0 < p0 < n:
                raise ValueError(
                    f"prefix_len {prefix_len} out of range for a "
                    f"{n}-token prompt"
                )
            fp = prefix_fp or prefix_fingerprint(p[:p0])
            tmpl = self._ensure_prefix_template(p[:p0], fp)
        if tmpl is None and n <= C:
            # One bucketed prefill, memoized per bucket size.
            b = self._bucket(n)
            jkey = ("solo", b)
            if jkey not in self._prefill_jit:
                def fn(params, padded, plen, key):
                    c = init_cache(self.cfg, 1, self.max_len,
                                   quant_kv=self.quant_kv, ring=False)
                    logits, c = forward_step(params, padded, self.cfg, c)
                    first = self._pick(logits[0, plen - 1][None, :],
                                       key)[0]
                    return c["layers"], first

                self._prefill_jit[jkey] = jax.jit(fn)
            padded = np.zeros((b,), np.int32)
            padded[:n] = p
            layers, first = self._prefill_jit[jkey](
                self.params, jnp.asarray(padded)[None, :],
                jnp.asarray(n, jnp.int32), self._next_key(),
            )
        else:
            # Chunked prefill on the 1-row cache: every chunk is FULL,
            # the final window shifts back to [n-C, n) — the re-score
            # is value-identical (complete prefix, causal attention;
            # see admit_one_cache's derivation).
            if tmpl is not None:
                layers = tmpl["layers"]["t"]
                c_start = min(C * (p0 // C), n - C)
            else:
                layers = init_cache(
                    self.cfg, 1, self.max_len,
                    quant_kv=self.quant_kv, ring=False,
                )["layers"]
                c_start = 0
            jkey = ("solo_chunk", C)
            if jkey not in self._prefill_jit:
                def fn(params, layers_, chunk, off):
                    logits, c = forward_step(
                        params, chunk, self.cfg,
                        {"layers": layers_, "offset": off},
                    )
                    return c["layers"], logits[0]

                self._prefill_jit[jkey] = jax.jit(fn)
            step = self._prefill_jit[jkey]
            last = None
            for c0 in range(c_start, n, C):
                start = c0 if c0 + C <= n else n - C
                layers, logits = step(
                    self.params, layers,
                    jnp.asarray(p[start: start + C])[None, :],
                    jnp.asarray(start, jnp.int32),
                )
                if start + C >= n:
                    last = logits[(n - 1) - start]
            first = self._pick(last[None, :], self._next_key())[0]
        layers_host = [
            {kk: np.asarray(cl[kk])[:, :, :n] for kk in cl}
            for cl in layers
        ]
        first = int(first)
        self._kv_exports[rid] = {
            "layers": layers_host, "n": n, "first": first,
        }
        return first

    def export_kv(self, rid) -> Tuple[bytes, int]:
        """Package the staged prefill rows of ``rid`` for the handoff:
        ``(payload, fp32_bytes)`` from :func:`pack_kv_segment` (int8
        codes + per-slot scales when ``quant_kv``; CRC embedded).  The
        staged entry is consumed — a lost payload re-prefills."""
        info = self._kv_exports.pop(rid, None)
        if info is None:
            raise ValueError(f"no staged prefill for request {rid!r}")
        return pack_kv_segment(
            info["layers"], info["n"], info["first"], self.quant_kv,
            # Paged servers ship a BLOCK LIST (per-block CRCs; the
            # decode side writes frames straight into pool blocks).
            block_size=self.block_size if self.paged else 0,
        )

    def import_kv(self, rid, payload: bytes, prompt,
                  max_new_tokens: int) -> None:
        """Decode-role admission from a shipped KV segment: verify
        (:func:`unpack_kv_segment` CRC + shape/dtype/config coherence
        against THIS server), pad the rows to the slot length, and
        enqueue for the serve loop to write into a freeing slot.
        Raises :class:`KvSegmentError` on any mismatch — a torn or
        foreign segment is never decoded from."""
        if self.draft is not None:
            raise ValueError(
                "KV import does not compose with a draft model (the "
                "draft cache is not shipped)"
            )
        seg = unpack_kv_segment(payload)
        p = np.asarray(prompt, np.int32)
        n = seg["n"]
        if n != len(p):
            raise KvSegmentError(
                f"KV segment covers {n} tokens but the grant prompt "
                f"has {len(p)}"
            )
        if seg["quant"] != self.quant_kv:
            raise KvSegmentError(
                f"KV segment quant={seg['quant']} but this server has "
                f"quant_kv={self.quant_kv}"
            )
        self.check_capacity(n, max_new_tokens)
        cfg = self.cfg
        want_keys = {"k", "v", "ks", "vs"} if self.quant_kv else \
            {"k", "v"}
        if len(seg["layers"]) != cfg.n_layer:
            raise KvSegmentError(
                f"KV segment has {len(seg['layers'])} layers, model "
                f"has {cfg.n_layer}"
            )
        ref = init_cache(cfg, 1, 1, quant_kv=self.quant_kv, ring=False)
        ref_layer = ref["layers"][0]
        padded = []
        for lay in seg["layers"]:
            if set(lay) != want_keys:
                raise KvSegmentError(
                    f"KV segment keys {sorted(lay)} != "
                    f"{sorted(want_keys)}"
                )
            out = {}
            for kk, arr in lay.items():
                want_dt = np.dtype(ref_layer[kk].dtype)
                # Expectation from the REFERENCE layout, never from the
                # untrusted payload's own ndim — a mis-declared meta
                # must reject cleanly here, not crash the jitted
                # writeback inside the serve loop.
                want_shape = (1, cfg.n_kv_head, n) + (
                    (cfg.head_dim,) if ref_layer[kk].ndim == 4 else ()
                )
                if arr.shape != want_shape or \
                        np.dtype(arr.dtype) != want_dt:
                    raise KvSegmentError(
                        f"KV segment {kk}: shape {arr.shape} dtype "
                        f"{arr.dtype} != expected {want_shape} "
                        f"{want_dt}"
                    )
                if self.paged:
                    # Paged admission writes whole blocks: pad only to
                    # the block boundary, not the full slot length.
                    tail = self.kv_arena.blocks_for(n) \
                        * self.block_size - n
                else:
                    tail = self.max_len - n
                pad = [(0, 0)] * arr.ndim
                pad[2] = (0, tail)
                out[kk] = np.pad(arr, pad)
            padded.append(out)
        extra = {"kv": {
            "layers": padded, "n": n, "first": seg["first"],
        }}
        with self._pending_mu:
            self._pending.append(
                (rid, p, int(max_new_tokens), extra)
            )

    @staticmethod
    def _slot_subcache(cache: Dict, s) -> list:
        """Per-layer [1, ...] views of slot ``s``'s cache rows.
        Iterates the layer dict's KEYS so the int8 layout's scale
        arrays ("ks"/"vs") ride along with "k"/"v" (every cache array
        is [slots, ...]-leading)."""
        return [
            {
                kk: jax.lax.dynamic_slice_in_dim(cl[kk], s, 1, 0)
                for kk in cl
            }
            for cl in cache["layers"]
        ]

    @staticmethod
    def _slot_writeback(cache: Dict, sub_layers: list, s) -> list:
        """Write per-layer [1, ...] sub-rows back into slot ``s``."""
        return [
            {
                kk: jax.lax.dynamic_update_slice_in_dim(
                    cl[kk], sc[kk], s, 0
                )
                for kk in cl
            }
            for cl, sc in zip(cache["layers"], sub_layers)
        ]

    def _remote_propose(self, handle, k: int, k_arr, active, slot_req,
                        slot_prompt, slot_out, draft_mark, draft_open,
                        draft_close, sample: bool):
        """Collect per-stream context deltas and fetch one round of
        proposals from the remote draft handle (ISSUE 11).  Streams
        unknown to the draft ship their full prompt (``open``); known
        ones ship only the tokens emitted since the last roll — the
        draft catches its cache up from exactly that delta.  Returns
        ``(d [B, k], q [B, k, V] | None, k_arr)`` with rows the draft
        dropped (evicted stream) forced to width 0 for this round, or
        ``None`` on a handle failure — the caller degrades to plain
        decode, it never stalls."""
        import numpy as onp

        B = self.slots
        reqs = []
        shipped = []
        for s in range(B):
            if not active[s] or (k_arr is not None and k_arr[s] == 0):
                continue
            # rids normalize to str on the wire (msgpack map keys);
            # batch-mode int rids must round-trip identically.
            entry: Dict[str, Any] = {"rid": str(slot_req[s])}
            if draft_open[s]:
                entry["ctx"] = [
                    int(t) for t in slot_out[s][draft_mark[s]:]
                ]
            else:
                entry["open"] = [int(t) for t in slot_prompt[s]]
                entry["ctx"] = [int(t) for t in slot_out[s]]
            reqs.append(entry)
            shipped.append(s)
        close, draft_close[:] = list(draft_close), []
        try:
            props = handle.propose(reqs, k, sample=sample, close=close)
        except Exception as e:  # noqa: BLE001 - degrade, never stall
            draft_close.extend(close)  # undelivered; retry on re-attach
            logger.warning("remote draft proposal failed: %s", e)
            return None
        V = self.cfg.vocab_size
        d = onp.zeros((B, k), onp.int64)
        q = None
        if sample:
            # Width-0 / dropped rows never read their q past their
            # width — the uniform filler (and any stale probs from a
            # previous round) only keeps the batched arithmetic
            # finite, so the buffer is reused across rounds.
            if self._spec_q_buf is None:
                self._spec_q_buf = onp.full(
                    (B, self.draft_k, V), 1.0 / V, onp.float64
                )
            q = self._spec_q_buf[:, :k]
        if k_arr is None:
            k_arr = onp.where(
                onp.asarray(active, bool), k, 0
            ).astype(onp.int64)
        else:
            k_arr = onp.asarray(k_arr, onp.int64).copy()
        props = props or {}
        for s in shipped:
            got = props.get(str(slot_req[s]))
            if got is None:
                # The draft dropped/evicted this stream: plain law for
                # the round; re-open (full context) on the next roll.
                k_arr[s] = 0
                draft_open[s] = False
                continue
            dk = onp.asarray(got["d"], onp.int64).reshape(-1)[:k]
            d[s, : len(dk)] = dk
            if len(dk) < k:
                k_arr[s] = min(int(k_arr[s]), len(dk))
            if sample:
                qk = onp.asarray(got.get("q"), onp.float64)
                if qk.ndim != 2 or qk.shape[1] != V:
                    # A malformed proposal law is a broken draft, not a
                    # dropped stream: the worker already advanced its
                    # cache by this ctx, so re-shipping would corrupt
                    # its offsets — fail the handle instead.
                    logger.warning(
                        "remote draft returned malformed probs for "
                        "%s; dropping the draft", slot_req[s],
                    )
                    return None
                qn = min(k, qk.shape[0])
                q[s, :qn] = qk[:qn]
            draft_mark[s] = len(slot_out[s])
            draft_open[s] = True
        return d, q, k_arr

    def _prefill(self, bucket: int, cfg: Optional[LlamaConfig] = None):
        """Jitted: score one right-padded prompt into slot ``s``'s cache
        rows; returns (cache, first sampled token).  ``cfg`` defaults
        to the target model's (pass the draft's to admit into the
        draft cache)."""
        cfg = cfg or self.cfg

        def fn(params, cache, s, prompt, plen, key):
            # Fresh zero rows for this slot (slot reuse must not see a
            # previous occupant's keys beyond the causal mask).
            sub = {
                "layers": [
                    {kk: jnp.zeros_like(c[kk]) for kk in c}
                    for c in self._slot_subcache(cache, s)
                ],
                "offset": jnp.zeros((), jnp.int32),
            }
            logits, sub = forward_step(params, prompt[None, :], cfg, sub)
            last = logits[0, plen - 1, :]
            first = self._pick(last[None, :], key)[0]
            new_layers = self._slot_writeback(cache, sub["layers"], s)
            new_offset = cache["offset"].at[s].set(plen)
            return dict(cache, layers=new_layers, offset=new_offset), first

        return jax.jit(fn)

    def _prefill_chunk(self, C: int,
                       cfg: Optional[LlamaConfig] = None):
        """Jitted: score ONE full [1, C] chunk continuing slot ``s``'s
        sub-cache at offset ``off`` (``zero_first`` wipes the slot's
        rows for fresh admission).  Returns (cache, chunk logits
        [C, V]).  Looping this admits prompts of ANY length with one
        compiled program (see ``admit_chunked`` for the final-chunk
        window shift that keeps every write in bounds)."""
        cfg = cfg or self.cfg

        def fn(params, cache, s, chunk, off, zero_first):
            sub = {
                "layers": [
                    {
                        kk: jnp.where(
                            zero_first, jnp.zeros_like(c[kk]), c[kk]
                        )
                        for kk in c
                    }
                    for c in self._slot_subcache(cache, s)
                ],
                "offset": off,
            }
            logits, sub = forward_step(params, chunk, cfg, sub)
            new_layers = self._slot_writeback(cache, sub["layers"], s)
            new_offset = cache["offset"].at[s].set(off + C)
            return (
                dict(cache, layers=new_layers, offset=new_offset),
                logits[0],
            )

        return jax.jit(fn)

    def serve(self, prompts, max_new_tokens: int, on_finish=None,
              on_token=None, shared_prefix=None):
        """Decode every prompt (a list of 1-D int arrays); returns a
        list of 1-D arrays (prompt + continuation, EOS included).

        ``on_finish(rid, tokens)`` fires the moment request ``rid``
        completes (its slot is freed for re-admission) — the hook
        elastic serving journals completions through, so a worker kill
        mid-serve only costs the in-flight requests (replayed on
        restart), never the finished ones.

        ``on_token(rid, token)`` fires for every emitted token the
        round it lands on the host — token streaming (the role of
        vllm's streaming API), including each request's FIRST token
        (sampled at prefill).  With ``decode_chunk=K`` or a draft,
        tokens arrive in bursts of up to K / k+1 per round — that is
        the latency the dispatch batching buys throughput with.

        ``shared_prefix`` (1-D int array): PREFIX CACHING, the role of
        vllm's automatic prefix caching for the common case of one
        system prompt shared by every request.  The prefix prefills
        ONCE into a template; each admission copies the template's kv
        rows into its slot (one dynamic_update_slice per layer — a
        memory move, no FLOPs) and chunk-scores only from the first
        chunk containing its own tokens.  Results and the output law
        are EXACTLY ``serve([prefix + p for p in prompts])``; admission
        cost drops from O(prefix + prompt) to O(chunk + prompt) scoring
        FLOPs per request."""
        import numpy as onp

        prefix = None
        if shared_prefix is not None:
            prefix = onp.asarray(shared_prefix, onp.int32)
            if prefix.ndim != 1 or prefix.size == 0:
                raise ValueError(
                    "shared_prefix must be a non-empty 1-D token array"
                )
        P0 = 0 if prefix is None else len(prefix)
        for rid, prompt in enumerate(prompts):
            try:
                self.check_capacity(len(prompt), max_new_tokens, P0)
            except ValueError as e:
                raise ValueError(f"request {rid}: {e}") from None
        with self._pending_mu:
            if self._pending:
                # serve() and the incremental surface are exclusive
                # modes: silently clearing would DROP submitted
                # requests with no error and no on_finish.  (Checked
                # BEFORE the prefix-template prefill below — the error
                # must be immediate and free, not after seconds of
                # discarded XLA work.)
                raise RuntimeError(
                    f"serve() cannot run with {len(self._pending)} "
                    "incremental submission(s) queued; drain or "
                    "cancel them first (serve()/serve_incremental "
                    "are exclusive modes)"
                )
        templates = self._build_prefix_templates(prefix, prompts)
        with self._pending_mu:
            for rid, prompt in enumerate(prompts):
                self._pending.append(
                    (rid, onp.asarray(prompt, onp.int32),
                     int(max_new_tokens), None)
                )
        results = self._run(
            on_finish=on_finish, on_token=on_token,
            prefix=prefix, templates=templates,
        )
        return [results[i] for i in range(len(prompts))]

    def serve_incremental(self, tick=None, on_finish=None,
                          on_token=None, idle_wait: float = 0.002):
        """Serve requests fed in by :meth:`submit` — the fleet
        replica's decode loop (ISSUE 5).  ``tick()`` is called once per
        loop iteration (the admission point): the replica runner polls
        the gateway there, submits new grants, flushes token streams
        and reports completions.  Returning ``False`` from ``tick``
        drains the loop — in-flight and already-submitted requests
        finish, then the call returns (the scale-down contract: no
        admitted request ever observes the shrink).  With no pending or
        active work the loop idles at ``idle_wait`` granularity until
        ``tick`` stops it.  Completions are delivered via ``on_finish``
        ONLY (the batch-mode result dict is not retained — it would
        grow without bound over a replica's lifetime); returns {}."""
        return self._run(
            on_finish=on_finish, on_token=on_token,
            prefix=None, templates={}, tick=tick, idle_wait=idle_wait,
        )

    def _build_prefix_templates(self, prefix, prompts) -> Dict[str, Any]:
        """Prefix templates: the shared prefix prefilled ONCE per model
        into a 1-row cache with the server's row length, so admission
        can copy whole slot rows (zeros beyond P0 included — the copy
        doubles as the fresh-slot zeroing)."""
        templates: Dict[str, Any] = {}
        P0 = 0 if prefix is None else len(prefix)
        if prefix is not None and any(
            P0 + len(p) > self.buckets[-1] for p in prompts
        ):
            # (gated: if every combined prompt fits one bucket, every
            # admission scratch-prefills and the template would be
            # built for nothing)
            pref_dev = jnp.asarray(prefix)[None, :]
            for role, mparams, mcfg in self._roles():
                templates[role] = self._template_layers(
                    role, mparams, mcfg, pref_dev, P0
                )
        return templates

    def _run(self, on_finish=None, on_token=None, prefix=None,
             templates=None, tick=None, idle_wait: float = 0.002):
        """The decode loop shared by :meth:`serve` (batch mode: the
        pending queue is pre-filled and runs to drain) and
        :meth:`serve_incremental` (``tick`` feeds the queue while the
        loop runs).  Admission draws from ``self._pending``; every
        request carries its OWN max_new_tokens budget."""
        import numpy as onp

        # Telemetry contract: last_stats describes THIS call for every
        # decode path (stale stats from a previous speculative serve
        # must not survive into a plain one).
        self.last_stats = {}
        cfg = self.cfg
        B = self.slots
        templates = templates or {}
        P0 = 0 if prefix is None else len(prefix)
        results: Dict[Any, Any] = {}
        arena = self.kv_arena
        table_dev: Any = None  # device copy of arena.table, lazy
        if self.paged:
            # Fresh pool per serve call (the slotted path rebuilds its
            # cache per call too); templates re-materialize their
            # blocks lazily under the new run tag.
            arena.reset()
            self._paged_run_seq += 1
            self.preemptions = 0
            pool = init_paged_pool(
                cfg, self.pool_blocks, self.block_size,
                quant_kv=self.quant_kv,
            )
            cache = {
                "layers": pool["layers"],
                "offset": jnp.zeros((B,), jnp.int32),
            }
        else:
            cache = init_cache(cfg, B, self.max_len,
                               quant_kv=self.quant_kv, ring=False)
            cache = dict(cache, offset=jnp.zeros((B,), jnp.int32))

        def table_device():
            nonlocal table_dev
            if table_dev is None:
                table_dev = jnp.asarray(arena.table)
            return table_dev

        def table_dirty():
            nonlocal table_dev
            table_dev = None
        cache_d = None
        if self.draft is not None:
            cache_d = init_cache(self.draft[1], B, self.max_len,
                                 quant_kv=self.quant_kv, ring=False)
            cache_d = dict(cache_d, offset=jnp.zeros((B,), jnp.int32))
        toks = jnp.zeros((B,), jnp.int32)
        active = onp.zeros((B,), bool)
        slot_req: list = [None] * B  # request id per slot
        slot_prompt: list = [None] * B  # prefix+prompt per slot
        slot_out: list = [None] * B
        budget = [0] * B
        # Paged bookkeeping: the original queue item per slot (so a
        # preemption can requeue it verbatim), admission order (the
        # preemption victim policy sheds the YOUNGEST — vllm's
        # recompute-last), and per-slot counts of already-delivered
        # tokens to mute after a preempted request re-admits.
        slot_item: list = [None] * B
        admit_seq = [0] * B
        slot_mute = [0] * B
        admit_counter = 0
        # Per-slot offset bound (speculative rounds clamp finishing
        # rows here; see _spec_decode_round's max_off).
        slot_bound = onp.zeros((B,), onp.int64)
        # Per-slot speculation state (ISSUE 11): per-REQUEST width and
        # acceptance EWMA (adapt_k_per_request), per-request telemetry,
        # and the remote-draft context-sync marks (how many of the
        # slot's emitted tokens the draft replica has already scored).
        req_k = [self.draft_k] * B
        req_ewma = [0.0] * B
        req_rounds = [0] * B       # spec rounds this request rode
        req_tokens = [0] * B       # tokens those rounds accepted
        req_plain = [0] * B        # consecutive plain rounds at k == 0
        draft_mark = [0] * B       # slot_out tokens shipped to draft
        draft_open = [False] * B   # stream opened at the remote draft
        draft_close: list = []     # finished rids to close remotely

        def copy_template(c, tmpl_layers, slot, p0, role):
            """Slot rows := template rows (one dynamic_update_slice per
            layer array); slot offset := p0.  The template ARRAYS and
            the prefix length both ride as traced args — the compiled
            copy is memoized across serve() calls and across the
            fingerprint store's many templates."""
            jkey = ("tmplcopy", role)
            if jkey not in self._prefill_jit:
                def fn(cache, tmpl, s, p0_):
                    new_layers = self._slot_writeback(cache, tmpl, s)
                    return dict(
                        cache, layers=new_layers,
                        offset=cache["offset"].at[s].set(p0_),
                    )

                self._prefill_jit[jkey] = jax.jit(fn)
            return self._prefill_jit[jkey](
                c, tmpl_layers, jnp.asarray(slot),
                jnp.asarray(p0, jnp.int32),
            )

        # -- paged admission (ISSUE 19) -------------------------------
        batch_tmpl_memo: Dict[str, Any] = {}

        def blk_writer(nblk):
            """Jit that writes a dense [1, KV, >=nblk*BS, ...] row's
            first nblk blocks into pool blocks ``ids`` — template
            materialization and KV-segment import share it."""
            tk = ("blk_write", nblk)
            if tk not in self._prefill_jit:
                def ftb(pool_layers, row_layers, ids_):
                    out = []
                    for pl, rl in zip(pool_layers, row_layers):
                        lay = {}
                        for kk, v in pl.items():
                            lay[kk] = v.at[ids_].set(
                                _paged_block_split(
                                    jnp.asarray(rl[kk])[0], nblk,
                                    self.block_size,
                                )
                            )
                        out.append(lay)
                    return out

                self._prefill_jit[tk] = jax.jit(ftb)
            return self._prefill_jit[tk]

        def paged_template_ids(tmpl_t_layers, p0, store_entry):
            """Materialize (once per RUN — the pool is rebuilt each
            serve call) a prefix template's pool blocks from its dense
            1-row layers.  The store holds a refcount on them until
            eviction; admissions SHARE the fully-before-rescore blocks
            and copy the rest.  None when the pool is too tight — the
            caller admits untemplated instead."""
            nonlocal cache
            memo = store_entry if store_entry is not None \
                else batch_tmpl_memo
            pb = memo.get("_paged")
            if pb is not None and pb.get("run") == self._paged_run_seq:
                return pb["ids"]
            nblk = arena.blocks_for(p0)
            ids = arena.hold(nblk)
            if ids is None:
                return None
            cache = dict(cache, layers=blk_writer(nblk)(
                cache["layers"], tmpl_t_layers,
                jnp.asarray(ids, jnp.int32),
            ))
            memo["_paged"] = {"run": self._paged_run_seq, "ids": ids}
            return ids

        def drop_template_holds():
            """Release every template's materialized pool blocks (the
            admission gate's last resort when even an UNtemplated
            admission can't fit): check_capacity guarantees any single
            accepted request fits the bare pool, so after this the
            empty batch always re-admits."""
            for memo in [batch_tmpl_memo] + list(
                self._prefix_store.values()
            ):
                pb = memo.pop("_paged", None)
                if pb is not None and \
                        pb.get("run") == self._paged_run_seq:
                    arena.release(pb["ids"])

        def admit_paged(slot, prompt, n, tmpl, p0, store_entry):
            """Paged-target admission: SHARE whole template blocks
            strictly below the first re-scored position w0 (refcount
            up, zero copies — partial prefix overlap finally counts),
            COPY the template blocks in [w0, p0) — they are about to
            be re-written by the chunk re-score, which is exactly
            copy-on-first-divergent-write at block granularity — and
            allocate only the blocks the prompt needs now.  The token
            law matches the dense path byte-for-byte: positions below
            w0 carry template values, positions in [w0, n) carry the
            same chunk-program values dense admission writes."""
            nonlocal cache
            C = self.buckets[-1]
            BSZ = self.block_size
            ids = None
            w0 = 0
            if tmpl is not None and p0:
                w0 = min(C * (p0 // C), n - C)
                if w0 > 0:
                    ids = paged_template_ids(tmpl["t"], p0, store_entry)
            jkey = ("paged_chunk",)
            if jkey not in self._prefill_jit:
                def fnc(params, pool_layers, table_s, chunk, off,
                        zero_first):
                    sub_layers = [
                        {
                            kk: jnp.where(
                                zero_first, jnp.zeros_like(v), v
                            )
                            for kk, v in lay.items()
                        }
                        for lay in _paged_row_view(pool_layers, table_s)
                    ]
                    logits, sub = forward_step(
                        params, chunk, cfg,
                        {"layers": sub_layers, "offset": off},
                    )
                    return (
                        _paged_row_scatter(
                            pool_layers, sub["layers"], table_s
                        ),
                        logits[0],
                    )

                self._prefill_jit[jkey] = jax.jit(fnc)
            chunk_fn = self._prefill_jit[jkey]
            if ids is not None:
                share_n = w0 // BSZ
                arena.share(slot, ids[:share_n])
                copy_src = ids[share_n: arena.blocks_for(p0)]
            else:
                copy_src = []
            if not arena.alloc_upto(slot, n):
                raise RuntimeError(
                    "paged admission allocation failed after the "
                    "free-block gate — arena accounting bug"
                )
            table_dirty()
            if copy_src:
                dst = [
                    int(arena.table[slot, (w0 // BSZ) + i])
                    for i in range(len(copy_src))
                ]
                ck = ("paged_copy", len(copy_src))
                if ck not in self._prefill_jit:
                    def fcp(pool_layers, src, dst_):
                        return [
                            {
                                kk: v.at[dst_].set(v[src])
                                for kk, v in lay.items()
                            }
                            for lay in pool_layers
                        ]

                    self._prefill_jit[ck] = jax.jit(fcp)
                cache = dict(cache, layers=self._prefill_jit[ck](
                    cache["layers"],
                    jnp.asarray(copy_src, jnp.int32),
                    jnp.asarray(dst, jnp.int32),
                ))
            tbl_s = table_device()[slot]
            if ids is None and n <= self.buckets[-1]:
                b = self._bucket(n)
                sk = ("paged_solo", b)
                if sk not in self._prefill_jit:
                    def fns(params, pool_layers, table_s, padded,
                            plen, key):
                        # Mirror _prefill's trace on the row view:
                        # fresh zero rows, scalar offset, same pick.
                        sub = {
                            "layers": [
                                {
                                    kk: jnp.zeros_like(v)
                                    for kk, v in lay.items()
                                }
                                for lay in _paged_row_view(
                                    pool_layers, table_s
                                )
                            ],
                            "offset": jnp.zeros((), jnp.int32),
                        }
                        logits, sub = forward_step(
                            params, padded[None, :], cfg, sub
                        )
                        last = logits[0, plen - 1, :]
                        first = self._pick(last[None, :], key)[0]
                        return (
                            _paged_row_scatter(
                                pool_layers, sub["layers"], table_s
                            ),
                            first,
                        )

                    self._prefill_jit[sk] = jax.jit(fns)
                padded = onp.zeros((b,), onp.int32)
                padded[:n] = prompt
                new_layers, first = self._prefill_jit[sk](
                    self.params, cache["layers"], tbl_s,
                    jnp.asarray(padded), jnp.asarray(n, jnp.int32),
                    self._next_key(),
                )
                cache = dict(cache, layers=new_layers)
            else:
                # Chunked prefill through the table (fresh blocks when
                # untemplated; from w0 when sharing — the first chunk
                # must NOT zero, that would wipe shared blocks).
                c_start = w0 if ids is not None else 0
                zero_ok = ids is None
                last = None
                for c0 in range(c_start, n, C):
                    start = c0 if c0 + C <= n else n - C
                    piece = prompt[start: start + C]
                    new_layers, logits = chunk_fn(
                        self.params, cache["layers"], tbl_s,
                        jnp.asarray(piece)[None],
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(zero_ok and start == 0),
                    )
                    cache = dict(cache, layers=new_layers)
                    if start + C >= n:
                        last = logits[(n - 1) - start]
                first = self._pick(last[None, :], self._next_key())[0]
            cache = dict(
                cache, offset=cache["offset"].at[slot].set(n)
            )
            return first

        def paged_admit_need(item, bare=False) -> int:
            """Blocks this admission takes from the pool RIGHT NOW
            (the ISSUE 19 admission law — not a full-slot
            reservation).  ``bare`` prices the untemplated fallback."""
            rid_, prompt_, mnt_, extra_ = item
            extra_ = extra_ or {}
            if "kv" in extra_:
                return arena.blocks_for(extra_["kv"]["n"])
            n = len(prompt_) + (P0 if prefix is not None else 0)
            need = arena.blocks_for(n)
            if bare:
                return need
            p0, entry = 0, None
            C = self.buckets[-1]
            if prefix is not None and n > C and templates:
                p0, entry = P0, batch_tmpl_memo
            elif extra_.get("prefix_len") and len(prompt_) > C:
                p0 = int(extra_["prefix_len"])
                with self._pending_mu:
                    entry = self._prefix_store.get(
                        extra_.get("prefix_fp") or ""
                    )
            if p0:
                w0 = min(C * (p0 // C), n - C)
                if w0 > 0:
                    pb = (entry or {}).get("_paged")
                    if pb and pb.get("run") == self._paged_run_seq:
                        # Warm template: the shared blocks arrive free.
                        need -= w0 // self.block_size
                    else:
                        # Cold: materializing the template costs its
                        # blocks too.
                        need += arena.blocks_for(p0)
            return max(0, need)

        def preempt(victim):
            """Shed a slot when the pool runs dry (grow-on-demand's
            escape hatch): its blocks return to the pool instantly and
            the request re-queues at the FRONT.  Greedy decode
            regenerates the identical stream; tokens already delivered
            through on_token are muted on re-admission."""
            rid = slot_req[victim]
            self.preemptions += 1
            if draft_open[victim]:
                draft_close.append(rid)
            self._preempt_emitted[rid] = len(slot_out[victim])
            with self._pending_mu:
                self._pending.appendleft(slot_item[victim])
            arena.free_slot(victim)
            table_dirty()
            active[victim] = False
            slot_req[victim] = None
            slot_prompt[victim] = None
            slot_out[victim] = None

        def ensure_round_blocks(round_need: int) -> None:
            """Grow every active slot to cover this round's writes —
            INCLUDING the speculative / chunked overshoot, whose
            accepted prefix becomes real KV after the rewind — before
            the dispatch.  Oldest admissions grow first; when the pool
            cannot cover someone, the youngest admission is preempted
            (vllm's recompute-last policy) until the rest fit."""
            off = onp.asarray(cache["offset"])
            order = sorted(
                (s for s in range(B) if active[s]),
                key=lambda s: admit_seq[s],
            )
            for s in order:
                while active[s] and not arena.alloc_upto(
                    s, int(off[s]) + round_need
                ):
                    if arena.scavenge():
                        continue
                    victim = max(
                        (v for v in range(B) if active[v]),
                        key=lambda v: admit_seq[v],
                    )
                    preempt(victim)
            table_dirty()

        def admit_one_cache(slot, prompt, n, c, mparams, mcfg, role,
                            tmpl=None, p0=0):
            """Prefill ``prompt`` into ``c``'s slot rows under one
            model (target or draft); returns (new cache, first sampled
            token — meaningful for the target only; the draft role uses
            a CONSTANT key so its discarded pick never shifts the
            sampling stream).  ``tmpl`` (a {role: layers} dict):
            ``prompt`` is the prefix+request combined array; slot rows
            start as a copy of the prefix template and chunk scoring
            begins at the first chunk containing a non-prefix token
            (positions re-scored inside that chunk recompute identical
            kv — complete prefix, causal attention)."""
            use_template = tmpl is not None
            if use_template or n > self.buckets[-1]:
                # Chunked prefill: every chunk is FULL — the final
                # chunk's window shifts back to [n-C, n), re-scoring
                # already-written positions.  The re-score is value-
                # identical because by the time the window shifts back,
                # every cache slot before it is already correctly
                # populated and attention is causal: position t's k/v
                # recompute from the same complete prefix that produced
                # them the first time.  (NOT because k/v depend only on
                # token+position — for layers > 0 they depend on the
                # whole prefix through the residual stream; re-scoring
                # with an INCOMPLETE prefix would not be identical.)
                # So no chunk pads past the prompt or writes beyond slot
                # n-1 (a padded tail could run past max_len, where the
                # dense write's dynamic_update_slice CLAMPS the start
                # and silently corrupts live rows).
                C = self.buckets[-1]
                jkey = ("chunk", role)
                if jkey not in self._prefill_jit:
                    self._prefill_jit[jkey] = self._prefill_chunk(
                        C, mcfg
                    )
                step = self._prefill_jit[jkey]
                c_start = 0
                if use_template:
                    c = copy_template(c, tmpl[role], slot, p0, role)
                    # Skip chunks fully inside the prefix (their kv
                    # just arrived via the template copy); the copy
                    # also zeroed the slot, so no chunk needs
                    # zero_first.  Clamp to n - C so at least one
                    # chunk always runs — an EMPTY request prompt with
                    # p0 a multiple of C would otherwise skip the loop
                    # entirely and leave no last-logits to sample the
                    # first token from.
                    c_start = min(C * (p0 // C), n - C)
                last = None
                for c0 in range(c_start, n, C):
                    start = c0 if c0 + C <= n else n - C
                    piece = prompt[start: start + C]
                    c, logits = step(
                        mparams, c, slot, jnp.asarray(piece)[None],
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(start == 0 and not use_template),
                    )
                    if start + C >= n:
                        last = logits[(n - 1) - start]
                # True prompt length, not the chunk-rounded offset.
                c = dict(c, offset=c["offset"].at[slot].set(n))
                if role != "t":
                    return c, None
                return c, self._pick(last[None, :], self._next_key())[0]
            b = self._bucket(n)
            padded = onp.zeros((b,), onp.int32)
            padded[:n] = prompt
            jkey = (b, role)
            if jkey not in self._prefill_jit:
                self._prefill_jit[jkey] = self._prefill(b, mcfg)
            key = (self._next_key() if role == "t"
                   else jax.random.PRNGKey(0))
            return self._prefill_jit[jkey](
                mparams, c, slot, jnp.asarray(padded),
                jnp.asarray(n, jnp.int32), key,
            )

        def seat(slot, rid, prompt, n, mnt, first):
            """Shared post-admission bookkeeping: the slot is live,
            its first token (sampled at prefill or shipped with the KV
            segment) is emitted, EOS/budget-0 finishes immediately."""
            nonlocal admit_counter
            slot_bound[slot] = n + mnt
            active[slot] = True
            slot_req[slot] = rid
            slot_prompt[slot] = prompt
            slot_out[slot] = [int(first)]
            budget[slot] = mnt - 1
            admit_counter += 1
            admit_seq[slot] = admit_counter
            # A preempted request regenerates its stream from scratch;
            # tokens the caller already received stay muted (greedy
            # decode makes the regenerated prefix identical).
            slot_mute[slot] = self._preempt_emitted.pop(rid, 0)
            # Fresh per-request speculation state: every request
            # starts at full width and earns its own EWMA.
            req_k[slot] = self.draft_k
            req_ewma[slot] = 0.0
            req_rounds[slot] = req_tokens[slot] = req_plain[slot] = 0
            draft_mark[slot] = 0
            draft_open[slot] = False
            if slot_mute[slot] > 0:
                slot_mute[slot] -= 1
            elif on_token is not None:
                on_token(rid, int(first))
            if int(first) == self.eos_token or budget[slot] <= 0:
                finish(slot)

        def admit_imported(slot, rid, prompt, mnt, kvinfo):
            """Admission from a shipped KV segment (ISSUE 8): the
            verified, max_len-padded rows are written straight into
            the slot — a memory move, zero prefill FLOPs; decode
            continues from the segment's first token."""
            nonlocal cache, toks
            if self.paged:
                # Paged target: the import rows are padded to the block
                # boundary — allocate exactly the blocks the segment
                # occupies and block-write them (same writer the
                # templates use).
                n_ = int(kvinfo["n"])
                if not arena.alloc_upto(slot, n_):
                    raise RuntimeError(
                        "paged import allocation failed after the "
                        "free-block gate — arena accounting bug"
                    )
                table_dirty()
                nblk = arena.blocks_for(n_)
                ids = [int(arena.table[slot, i]) for i in range(nblk)]
                cache = dict(
                    cache,
                    layers=blk_writer(nblk)(
                        cache["layers"], kvinfo["layers"],
                        jnp.asarray(ids, jnp.int32),
                    ),
                    offset=cache["offset"].at[slot].set(n_),
                )
            else:
                jkey = ("kvimport",)
                if jkey not in self._prefill_jit:
                    def fn(c, arrs, s, n_):
                        new_layers = self._slot_writeback(c, arrs, s)
                        return dict(
                            c, layers=new_layers,
                            offset=c["offset"].at[s].set(n_),
                        )

                    self._prefill_jit[jkey] = jax.jit(fn)
                cache = self._prefill_jit[jkey](
                    cache, kvinfo["layers"], jnp.asarray(slot),
                    jnp.asarray(kvinfo["n"], jnp.int32),
                )
            toks = toks.at[slot].set(kvinfo["first"])
            seat(slot, rid, prompt, kvinfo["n"], mnt, kvinfo["first"])

        def admit(slot, item, paged_no_tmpl=False):
            rid, prompt, mnt, extra = item
            extra = extra or {}
            slot_item[slot] = item
            if "kv" in extra:
                admit_imported(slot, rid, prompt, mnt, extra["kv"])
                return
            tmpl = None
            p0 = 0
            store_entry = None
            if prefix is not None:
                # Output contract matches serve([prefix + p ...]).
                prompt = onp.concatenate([prefix, prompt])
                # Short combined prompts fit one bucketed prefill
                # anyway — the template saves nothing there;
                # scratch-prefill them.
                if len(prompt) > self.buckets[-1] and templates:
                    tmpl, p0 = templates, P0
            elif extra.get("prefix_len") and \
                    len(prompt) > self.buckets[-1]:
                # Incremental path (ISSUE 8): per-request template from
                # the fingerprint store — warm admits copy rows, cold
                # ones prefill the template once and warm the replica.
                entry = self._ensure_prefix_template(
                    prompt[: extra["prefix_len"]],
                    extra.get("prefix_fp")
                    or prefix_fingerprint(prompt[: extra["prefix_len"]]),
                )
                tmpl, p0 = entry["layers"], entry["p0"]
                store_entry = entry
            n = len(prompt)
            nonlocal cache, cache_d, toks
            if self.paged:
                first = admit_paged(
                    slot, prompt, n,
                    None if paged_no_tmpl else tmpl,
                    p0, store_entry,
                )
            else:
                cache, first = admit_one_cache(
                    slot, prompt, n, cache, self.params, self.cfg, "t",
                    tmpl=tmpl, p0=p0,
                )
            if self.draft is not None:
                # The draft's tiny cache stays dense even under paged
                # target KV — it is a constant-size side array, not the
                # stranded-memory cost the arena exists to reclaim.
                cache_d, _ = admit_one_cache(
                    slot, prompt, n, cache_d, self.draft[0],
                    self.draft[1], "d", tmpl=tmpl, p0=p0,
                )
            toks = toks.at[slot].set(first.astype(toks.dtype))
            seat(slot, rid, prompt, n, mnt, first)

        def finish(slot):
            rid = slot_req[slot]
            out = onp.concatenate(
                [slot_prompt[slot], onp.asarray(slot_out[slot], onp.int32)]
            )
            if self.spec_capable and req_rounds[slot]:
                # Park the request's earned acceptance for the runner
                # to fold into ServeDone + the journal (ISSUE 11).
                with self._pending_mu:
                    self._req_stats_out[rid] = {
                        "tokens_per_round": (
                            req_tokens[slot] / req_rounds[slot]
                        ),
                        "spec_rounds": req_rounds[slot],
                        "k_last": req_k[slot],
                    }
                    while len(self._req_stats_out) > 512:
                        self._req_stats_out.popitem(last=False)
            if draft_open[slot]:
                draft_close.append(rid)
            if tick is None:
                # Batch mode returns the result dict; the incremental
                # loop delivers via on_finish ONLY — retaining every
                # completion would grow without bound for the life of
                # a fleet replica.
                results[rid] = out
            if self.paged:
                # Blocks return to the pool the instant the slot
                # frees — the next admission can take them this same
                # loop iteration.
                arena.free_slot(slot)
                table_dirty()
            active[slot] = False
            slot_req[slot] = None
            slot_prompt[slot] = None
            slot_out[slot] = None
            if on_finish is not None:
                on_finish(rid, out)

        def emit_rows(rows):
            """THE per-slot emit/finish law, shared by every decode
            path (1-token step, K-token chunk, speculative round):
            append each of slot s's new tokens until its EOS or budget,
            then free the slot; the path's remaining tokens for a
            finished slot are discarded (rows re-zero at admission,
            capacity slack covered the extra writes).  Returns the
            number of tokens actually appended (the emitted-token
            telemetry for the non-speculative paths)."""
            appended = 0
            for s in range(B):
                if not active[s]:
                    continue
                for t in rows[s]:
                    slot_out[s].append(int(t))
                    appended += 1
                    budget[s] -= 1
                    if slot_mute[s] > 0:
                        # Re-serving after a paged preemption: this
                        # token was already delivered before the shed.
                        slot_mute[s] -= 1
                    elif on_token is not None:
                        on_token(slot_req[s], int(t))
                    if (
                        int(t) == self.eos_token
                        or budget[s] <= 0
                    ):
                        finish(s)
                        break
            return appended

        sample = self.temperature > 0.0
        greedy_key = jax.random.PRNGKey(0)  # dead in the greedy trace
        cur_k = self.draft_k
        # Acceptance telemetry (whole serve + current adaptation
        # window): tokens_per_round over ACTIVE row-rounds is the
        # speculation-efficiency signal adapt_k steers on.
        spec_rounds = spec_row_rounds = spec_tokens = 0
        spec_fallback_rounds = 0  # plain dispatches by a spec server
        spec_draft_failures = 0   # remote-draft handle failures
        win_row_rounds = win_tokens = 0
        plain_rounds = plain_tokens = 0
        k_history = [cur_k]
        remote_seen: Any = None   # handle identity (re-attach resets)
        remote_dead = False

        def publish_stats():
            """Refresh ``last_stats`` from the running counters —
            called every loop iteration so an incremental tick (the
            fleet replica's poll) reports LIVE telemetry, not the
            previous call's final numbers."""
            if self.spec_capable:
                self.last_stats = {
                    "path": "spec",
                    "rounds": spec_rounds,
                    "active_row_rounds": spec_row_rounds,
                    "accepted_tokens": spec_tokens,
                    "tokens_per_round": (
                        spec_tokens / spec_row_rounds
                        if spec_row_rounds else 0.0
                    ),
                    "k_final": cur_k,
                    "k_history": k_history,
                    # Plain dispatches this spec-capable server ran —
                    # every stream below break-even, no draft attached,
                    # or the remote draft dead (ISSUE 11).
                    "spec_fallback_rounds": spec_fallback_rounds,
                    "spec_draft_failures": spec_draft_failures,
                }
            else:
                self.last_stats = {
                    "path": ("decode_chunk" if self.decode_chunk > 1
                             else "plain"),
                    "rounds": plain_rounds,
                    "emitted_tokens": plain_tokens,
                    "tokens_per_round": (
                        plain_tokens / plain_rounds
                        if plain_rounds else 0.0
                    ),
                }
            if self.paged:
                # The stats-drift fix (ISSUE 19 satellite): under
                # paged mode ``occupancy`` IS block-pool utilization —
                # tokens held, not slots seated — so gateway admission
                # and autoscale hysteresis see real memory headroom
                # with no discontinuity at the flag flip.
                used = int(arena.used_blocks)
                self.last_stats.update(
                    paged=True,
                    total_blocks=arena.n_blocks,
                    free_blocks=arena.free_blocks,
                    block_occupancy=used / max(1, arena.n_blocks),
                    occupancy=used / max(1, arena.n_blocks),
                    preemptions=self.preemptions,
                    leaks_repaired=arena.leaks_repaired,
                )
            else:
                self.last_stats["occupancy"] = (
                    float(active.sum()) / max(1, B)
                )

        self._live_active = active
        self._live_slot_req = slot_req
        while True:
            publish_stats()
            keep = True
            if tick is not None:
                keep = tick() is not False
            if self._abort_rids:
                with self._pending_mu:
                    doomed, self._abort_rids = self._abort_rids, set()
                for s in range(B):
                    if active[s] and slot_req[s] in doomed:
                        # Shed the slot: partial output discarded, no
                        # on_finish; admission re-zeros the rows.
                        if draft_open[s]:
                            draft_close.append(slot_req[s])
                        if self.paged:
                            # Abort/deadline shed returns blocks to
                            # the pool INSTANTLY (ISSUE 19c) — the
                            # chaos site inside _drop_ref models a
                            # lost free here.
                            arena.free_slot(s)
                            table_dirty()
                        active[s] = False
                        slot_req[s] = None
                        slot_prompt[s] = None
                        slot_out[s] = None
            if self.paged:
                # Leak-repair sweep (the conservation law's defense):
                # any block whose refcount says free but which sits on
                # no free list — e.g. a chaos-dropped free — is
                # rebuilt into the pool before admission prices it.
                arena.scavenge()
            for s in range(B):
                if not active[s]:
                    item = self._pop_pending()
                    if item is None:
                        break
                    no_tmpl = False
                    if self.paged:
                        need = paged_admit_need(item)
                        if arena.free_blocks < need:
                            if active.any():
                                # The blocks it needs NOW aren't
                                # free: wait for decode to release
                                # some before seating it.
                                with self._pending_mu:
                                    self._pending.appendleft(item)
                                break
                            # Empty batch: the request MUST admit —
                            # give up the template (and, if still
                            # tight, every template's held blocks)
                            # rather than livelock.
                            no_tmpl = True
                            bare = paged_admit_need(item, bare=True)
                            if arena.free_blocks < bare:
                                drop_template_holds()
                    admit(s, item, no_tmpl)
            if not active.any():
                if self.pending_count() == 0:
                    if tick is None or not keep:
                        break
                    # Idle incremental loop: nothing to decode until
                    # the next tick feeds the queue.
                    time.sleep(idle_wait)
                continue
            rd = self._remote_draft
            if rd is not remote_seen:
                # A (re)attached draft handle: fresh streams (the new
                # draft holds no caches), fresh chance after a failure.
                remote_seen = rd
                remote_dead = False
                for s in range(B):
                    draft_open[s] = False
                    draft_mark[s] = 0
            spec_live = self.draft is not None or (
                rd is not None and not remote_dead
            )
            if spec_live:
                # Per-row widths (ISSUE 11 per-request adaptive k): a
                # stream below break-even rides at width 0 (plain law,
                # zero draft work charged to it) and is re-probed at
                # width 1 every spec_probe_every of its plain rounds.
                if self.adapt_k_per_request:
                    k_arr = onp.zeros(B, onp.int64)
                    for s in range(B):
                        if not active[s]:
                            continue
                        ks = req_k[s]
                        if ks == 0 and \
                                req_plain[s] >= self.spec_probe_every:
                            ks = 1
                            req_plain[s] = 0
                        k_arr[s] = ks
                    round_k = int(k_arr.max()) if B else 0
                else:
                    round_k = cur_k
                    k_arr = None
                spec_live = round_k > 0
            if spec_live:
                progs = _spec_programs(
                    cfg,
                    self.draft[1] if self.draft is not None else cfg,
                    round_k, self.temperature, self.top_k, self.top_p,
                )
                if self.paged:
                    # Paged target under speculation: grow every slot
                    # to cover the round's k+1 verify writes, then run
                    # the UNCHANGED spec round on a gathered dense
                    # view and scatter the result back through the
                    # table — two extra dispatches buy byte-exact
                    # reuse of the whole acceptance machinery.
                    ensure_round_blocks(round_k + 1)
                    if not active.any():
                        continue
                    pool_layers = cache["layers"]
                    dense = self._paged_gather(
                        pool_layers, table_device(), cache["offset"]
                    )
                else:
                    pool_layers = None
                    dense = cache
                if self.draft is not None:
                    # Local draft: one batched roll over all slots,
                    # one chunked ragged verify, per-slot acceptance;
                    # idle slots ride along frozen (done mask).
                    accepted_rows, nxt, dense, cache_d = \
                        _spec_decode_round(
                            progs, self.params, self.draft[0], dense,
                            cache_d, toks, ~active, round_k, sample,
                            self._np_rng,
                            self._next_key() if sample else greedy_key,
                            max_off=slot_bound, k_row=k_arr,
                        )
                else:
                    # Remote draft (ISSUE 11): context deltas out,
                    # proposals back over the draft replica's segment
                    # path; ANY failure degrades to plain decode (a
                    # dead draft must never stall the serve loop).
                    got = self._remote_propose(
                        rd, round_k, k_arr, active, slot_req,
                        slot_prompt, slot_out, draft_mark, draft_open,
                        draft_close, sample,
                    )
                    if got is None:
                        remote_dead = True
                        spec_draft_failures += 1
                        continue
                    d_host, q_host, k_arr = got
                    accepted_rows, nxt, dense = _spec_remote_round(
                        progs, self.params, dense, toks, ~active,
                        d_host, q_host, round_k, sample, self._np_rng,
                        k_row=k_arr, max_off=slot_bound,
                    )
                if self.paged:
                    cache = {
                        "layers": self._paged_scatter(
                            pool_layers, dense["layers"],
                            table_device(),
                        ),
                        "offset": dense["offset"],
                    }
                else:
                    cache = dense
                toks = jnp.asarray(nxt)
                # Acceptance BEFORE EOS/budget truncation — what the
                # draft earned, the signal k adapts on.  Only rows
                # that actually SPECULATED this round (width > 0)
                # count: width-0 riders earn exactly 1 plain token
                # each and would dilute tokens_per_round toward 1.0,
                # starving the DraftRole/arbiter signal of the value
                # the speculating streams really get.
                round_spec_rows = 0
                round_tokens = 0
                # Per-request EWMA + width BEFORE emit (emit can free
                # the slot; seat() resets the arrays on re-admission).
                for s in range(B):
                    if not active[s]:
                        continue
                    width = round_k if k_arr is None else int(k_arr[s])
                    if width <= 0:
                        req_plain[s] += 1
                        continue
                    earned = len(accepted_rows[s])
                    round_spec_rows += 1
                    round_tokens += earned
                    req_rounds[s] += 1
                    req_tokens[s] += earned
                    if self.adapt_k_per_request:
                        a = self.spec_ewma_alpha
                        req_ewma[s] = (
                            float(earned) if req_ewma[s] <= 0.0
                            else a * earned + (1 - a) * req_ewma[s]
                        )
                        req_k[s] = _spec_k_request(
                            req_ewma[s], self.draft_k,
                            self.spec_break_even,
                        )
                emit_rows(accepted_rows)
                spec_rounds += 1
                spec_row_rounds += round_spec_rows
                spec_tokens += round_tokens
                win_row_rounds += round_spec_rows
                win_tokens += round_tokens
                if (
                    self.adapt_k
                    and spec_rounds % self.adapt_every == 0
                    and win_row_rounds
                ):
                    new_k = _adapt_spec_k(
                        cur_k, self.draft_k,
                        win_tokens / win_row_rounds,
                    )
                    if new_k != cur_k:
                        cur_k = new_k
                        k_history.append(cur_k)
                    win_row_rounds = win_tokens = 0
                continue
            if self.spec_capable:
                # A spec-capable server running a plain dispatch:
                # every stream below break-even, no draft attached
                # yet, or the remote draft dead — the degradation the
                # gateway's spec_fallbacks counter measures.
                spec_fallback_rounds += 1
                for s in range(B):
                    if active[s]:
                        req_plain[s] += 1
            if self.decode_chunk > 1:
                if self.paged:
                    ensure_round_blocks(self.decode_chunk)
                    if not active.any():
                        continue
                    new_layers, offs, toks, chunk = \
                        self._chunk_step_paged(
                            self.params, cache["layers"],
                            table_device(), cache["offset"], toks,
                            jnp.asarray(active), self._next_key(),
                        )
                    cache = {"layers": new_layers, "offset": offs}
                else:
                    cache, toks, chunk = self._chunk_step(
                        self.params, cache, toks, jnp.asarray(active),
                        self._next_key(),
                    )
                plain_rounds += 1
                plain_tokens += emit_rows(onp.asarray(chunk))  # [B, K]
                continue
            if self.paged:
                ensure_round_blocks(1)
                if not active.any():
                    continue
                new_layers, offs, nxt = self._step_paged(
                    self.params, cache["layers"], table_device(),
                    cache["offset"], toks, jnp.asarray(active),
                    self._next_key(),
                )
                cache = {"layers": new_layers, "offset": offs}
            else:
                cache, nxt = self._step(
                    self.params, cache, toks, jnp.asarray(active),
                    self._next_key(),
                )
            toks = nxt
            plain_rounds += 1
            plain_tokens += emit_rows(onp.asarray(nxt)[:, None])
        self._live_active = None
        self._live_slot_req = None
        publish_stats()
        return results


def serve_journaled(
    server: "DecodeServer",
    prompts: list,
    max_new_tokens: int,
    journal_path: str,
    on_serve=None,
) -> list:
    """Elastic serving: an append-only completion journal + idempotent
    replay — the serving analogue of the trainer's flash checkpoint.

    A KV cache dies with its process, so the recovery unit for serving
    is the REQUEST, not device state: every completed request is
    fsync'd to ``journal_path`` (one JSON line, keyed by request id
    AND a hash of the prompt tokens) the moment its slot frees; a
    restarted worker loads the journal, skips finished requests whose
    prompt hash still matches, and re-serves only the in-flight
    remainder.  The hash keying makes replay safe against journal-path
    reuse: running a DIFFERENT prompt list against an old journal
    re-serves everything instead of returning stale completions.  Replay is
    byte-identical because greedy decode is deterministic AND the
    server's compiled program shapes are fixed by its construction
    (``slots``/buckets), not by the request subset: each slot row's
    result is computationally independent of what rides in the other
    slots, so serving fewer requests after a restart reproduces each
    remaining request exactly — at any dtype.  (Comparing against a
    B=1 solo decode is a DIFFERENT program shape, where bf16 argmax
    can flip near ties — that's why the tests pin float32.)  A torn
    final line from a SIGKILL mid-append is ignored and that request is
    simply replayed.  The reference has no elastic serving story at all
    (its RL stack shells out to a vllm the job master never supervises,
    atorch/rl/model_engine/model_engine.py:35) — this composes the
    continuous-batching server with the same kill-tolerance contract
    the trainer gets from agent restart + warm restore.

    Returns the full result list in request order.  ``on_serve(rid,
    tokens)`` additionally fires for every newly served (non-replayed)
    completion — progress reporting for the elastic agent's hang
    detector.
    """
    import hashlib as _hashlib
    import json as _json
    import os as _os

    if server.temperature > 0.0:
        # Replay determinism is the whole contract: a restarted worker
        # re-serves only the in-flight subset, so a sampling server's
        # RNG stream and admission order differ across incarnations and
        # the results would silently mix two different draws.
        raise ValueError(
            "serve_journaled requires a greedy server "
            "(temperature=0): sampled replay after a restart is not "
            "byte-identical"
        )
    def _phash(p) -> str:
        return _hashlib.sha1(
            np.asarray(p, np.int32).tobytes()
        ).hexdigest()[:16]

    # Journal records are keyed by (rid, prompt hash), not rid alone:
    # rerunning against an existing journal with a DIFFERENT prompt
    # list must re-serve, not silently replay the old run's completion
    # for a colliding rid.  Records whose hash mismatches (or predates
    # the hash field) are ignored and the request is simply re-served.
    want = {rid: _phash(p) for rid, p in enumerate(prompts)}
    done: Dict[int, np.ndarray] = {}
    try:
        with open(journal_path, "r+") as f:
            content = f.read()
            # Torn tail from a kill mid-append: TRUNCATE to the last
            # complete line before any new append — otherwise the next
            # record concatenates onto the partial one and both become
            # unparseable (losing a FINISHED request on a later
            # restart).
            cut = content.rfind("\n") + 1
            if cut < len(content):
                f.truncate(cut)
            for line in content[:cut].split("\n"):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = _json.loads(line)
                except ValueError:
                    continue  # a torn line persisted by an old writer
                rid = int(rec["rid"])
                if want.get(rid) != rec.get("ph"):
                    continue  # different prompt set: stale record
                done[rid] = np.asarray(rec["tokens"], np.int32)
    except OSError:
        pass
    todo = [
        (rid, p) for rid, p in enumerate(prompts) if rid not in done
    ]
    if todo:
        jf = open(journal_path, "a")
        try:
            def _journal(local_rid, tokens):
                rid = todo[local_rid][0]
                jf.write(_json.dumps({
                    "rid": rid,
                    "ph": want[rid],
                    "tokens": [int(t) for t in tokens],
                }) + "\n")
                jf.flush()
                _os.fsync(jf.fileno())
                done[rid] = np.asarray(tokens, np.int32)
                if on_serve is not None:
                    on_serve(rid, tokens)

            server.serve(
                [p for _, p in todo], max_new_tokens,
                on_finish=_journal,
            )
        finally:
            jf.close()
    return [done[r] for r in range(len(prompts))]
