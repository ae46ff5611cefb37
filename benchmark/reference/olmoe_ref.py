"""Plain reference of the OLMoE block (arXiv:2409.02060; HF
``modeling_olmoe.py``, model type ``olmoe``) and its training loss.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  Independent of ``dlrover_tpu/models/llama.py``:
it takes the same parameter tree (that is the interface, not shared code)
and HF key names for sizes.

One layer, ``x [B, S, d]`` the residual stream, ``rms(v, w) = v /
sqrt(mean(v^2) + eps) * w`` over the last axis::

    y  = rms(x, ln1)
    q  = rms(y @ wq, q_norm)          # over the WHOLE projection (heads x
    k  = rms(y @ wk, k_norm)          # head_dim = 2048), before the split
    v  = y @ wv                       # into heads and before RoPE
    q, k = rope(split(q)), rope(split(k))     # pairs (i, i + head_dim/2)
    x  = x + softmax(causal(q k^T / sqrt(head_dim))) v @ wo
    y  = rms(x, ln2)
    l  = y @ router                   # [B, S, 64], float32
    p  = softmax(l)
    T  = the 8 largest of p           # NOT renormalised (norm_topk_prob
    x  = x + sum_{e in T} p_e * (silu(y @ wg_e) * (y @ wi_e)) @ wo_e   # false)

The q/k RMSNorm is no key of ``config.json``: the ``olmoe`` model type
applies it unconditionally (``OlmoeAttention.q_norm``/``k_norm``), so it is
listed under ``assumed`` in the configuration file.  ``clip_qkv`` is null
in this configuration and nothing is clipped.

Loss = mean next-token cross-entropy (untied head)
+ ``router_aux_loss_coef`` (0.01, the HF config's default) x sum over
layers of ``E * sum_e f_e * P_e`` with ``f_e`` the mean over tokens AND
over the 8 picks of "expert e was taken" and ``P_e`` the mean router
probability (HF ``load_balancing_loss_func``)
+ ``router_z_loss_coef`` (0.001, the OLMoE paper's) x sum over layers of
``mean_tokens logsumexp(l)^2``.

Departures from the HF forward, each with no effect on a value except the
first:

- HF concatenates the router logits of ALL layers and takes ``f_e`` and
  ``P_e`` over layers and tokens together, one term for the model.  Here the
  term is computed per layer and summed.  At one layer (the benchmark's
  cut) the two are the same number; at L layers HF's single term is the
  product of two means over layers, this sum is L times the mean of the
  per-layer products.  The system computes the per-layer sum, as the OLMoE
  paper writes the loss; ``hf_convert`` users who need HF's scalar should
  know it.
- HF's released modelling code has no z-loss; the paper trains with it.
- The experts run as a scan over all 64 with a ``[B, S, E]`` combine weight
  (0 where not taken), each body a ``jax.checkpoint``, so that memory is one
  expert's and ``jax.grad`` of this reference fits beside the training
  state; attention runs over blocks of query rows, each against all keys
  with the mask written out, and the loss over blocks of positions, each a
  ``jax.checkpoint`` too, so that neither the S x S scores of 16 heads nor
  the S x vocabulary logits exist at once.

It keeps the routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's chosen
experts by name) it computes those experts, weighted by its OWN float32
probabilities of them.  Either way it returns ``(hidden, loss, extra)`` with
the ``choices`` it would have made itself, its ``scalars`` (``moe_aux``,
``moe_z``: the two router terms AS THEY ENTER THE LOSS, weights included,
so that a wrong weight shows as well as a wrong count) and the ``probs``
the choices were made from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the HF ``OlmoeConfig`` default, which the checkpoint's config.json keeps
ROUTER_AUX_LOSS_COEF = 0.01
#: arXiv:2409.02060, section 4.1: z-loss weight 0.001
ROUTER_Z_LOSS_COEF = 0.001


def experts_name(i: int) -> str:
    return f"layers.{i}.experts"


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (d, d + D/2)."""
    s, d = x.shape[1], x.shape[3]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(y, layer, cfg, q_block=1024):
    b, s, _ = y.shape
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _rmsnorm(y @ layer["wq"], layer["q_norm"], eps)
    k = _rmsnorm(y @ layer["wk"], layer["k_norm"], eps)
    q = _rope(q.reshape(b, s, h, hd), theta)
    k = jnp.repeat(_rope(k.reshape(b, s, kv, hd), theta), h // kv, 2)
    v = jnp.repeat((y @ layer["wv"]).reshape(b, s, kv, hd), h // kv, 2)
    outs = []
    for start in range(0, s, min(q_block, s)):
        @jax.checkpoint
        def rows(qb, k, v, first):
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(hd))
            causal = jnp.arange(s)[None, :] <= (
                first + jnp.arange(qb.shape[1]))[:, None]
            p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        outs.append(rows(q[:, start:start + q_block], k, v, start))
    return jnp.concatenate(outs, 1).reshape(b, s, h * hd) @ layer["wo"]


def _routed(y, moe, cfg, given):
    """y [B, S, d] -> (out, own choice, probs, load-balance term, z term)."""
    n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = y @ moe["router"]
    probs = jax.nn.softmax(logits, -1)
    _, own = jax.lax.top_k(probs, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(probs, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]

    @jax.checkpoint
    def one_expert(out, e):
        gate = jax.nn.silu(y @ moe["wg"][e])
        return out + combine[..., e, None] * (
            (gate * (y @ moe["wi"][e])) @ moe["wo"][e]), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(n_exp))
    # f_e: mean over tokens and over the k picks; P_e: mean probability
    balance = n_exp * jnp.sum(
        jnp.mean(taken, (0, 1, 2)) * jnp.mean(probs, (0, 1)))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, own, probs, balance, z


def _mean_nll(x, lm_head, targets, block=1024):
    """Mean next-token cross-entropy over blocks of positions."""
    s = x.shape[1]
    block = min(block, s)
    total = jnp.zeros((), F32)
    for start in range(0, s, block):
        @jax.checkpoint
        def nll(xb, tb):
            logp = jax.nn.log_softmax(xb @ lm_head, -1)
            return -jnp.sum(jnp.take_along_axis(logp, tb[..., None], -1))

        total = total + nll(x[:, start:start + block],
                            targets[:, start:start + block])
    return total / targets.size


def hidden_and_loss(params, tokens, cfg: dict, given=None):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, loss,
    extra).  ``cfg``: HF keys."""
    eps = cfg["rms_norm_eps"]
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = z_loss = jnp.zeros((), F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, layer in enumerate(params["layers"]):
            x = x + _attention(_rmsnorm(x, layer["ln1"], eps), layer, cfg)
            name = experts_name(i)
            out, own, probs, bal, z = _routed(
                _rmsnorm(x, layer["ln2"], eps), layer["moe"], cfg,
                None if given is None else given[name])
            x = x + out
            extra["choices"][name], extra["probs"][name] = own, probs
            balance, z_loss = balance + bal, z_loss + z
        x = _rmsnorm(x, params["ln_f"], eps)
        nll = _mean_nll(x, params["lm_head"], tgt)
    # the two router terms as they enter the loss, weights included
    extra["scalars"] = {
        "moe_aux":
            cfg.get("router_aux_loss_coef", ROUTER_AUX_LOSS_COEF) * balance,
        "moe_z": cfg.get("router_z_loss_coef", ROUTER_Z_LOSS_COEF) * z_loss}
    return x, nll + sum(extra["scalars"].values()), extra
