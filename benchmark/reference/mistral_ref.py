"""Plain reference of the Mistral-7B block (arXiv:2310.06825; HF
``modeling_mistral.py``): pre-norm RMSNorm, rotary embedding on halves,
grouped-query attention under a causal sliding-window mask, SwiGLU, untied
head, mean next-token cross-entropy.  Straightforward ``jax.numpy`` in
float32 at ``highest`` matmul precision: no kernels, no remat, no cache.

Independent of ``dlrover_tpu/models/llama.py``: it takes the same parameter
tree (that is the interface, not shared code) and HF key names for sizes.
Departures from a textbook forward, for memory only and with no effect on
any value: attention runs over blocks of query rows (``q_block``), each
against all keys with the mask written out, and the loss over blocks of
positions, so neither the S x S scores of 32 heads nor the S x vocabulary
logits exist at once; and each layer, attention block and loss block is a
``jax.checkpoint``, so that ``jax.grad`` of this reference (the gradient
check) fits beside the training state on one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x [S, H, D]: rotate the pairs (d, d + D/2) by position * theta^-2d/D."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, q_block):
    """q [S, H, D], k, v [S, KV, D] -> [S, H, D].  Head h reads KV head
    h // (H / KV); query i attends keys max(0, i - window + 1) .. i."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        qpos = start + jnp.arange(q_block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        ok = kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, q_block))
    return out.reshape(s, h, d)


def _mean_nll(x, head, tgt, block):
    """Mean next-token cross-entropy of x [S, d] against tgt [S]."""
    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    s = x.shape[0]
    nll = jax.lax.map(jax.checkpoint(one_block),
                      (x.reshape(s // block, block, -1),
                       tgt.reshape(s // block, block)))
    return jnp.mean(nll)


def hidden_and_loss(params, tokens, cfg: dict, q_block: int = 512):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, mean loss).
    ``cfg``: the configuration file's dict (HF keys)."""
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

    def one_sequence(seq):
        inp, tgt = seq[:-1], seq[1:]
        s = inp.shape[0]
        qb = min(q_block, s)

        def block(x, layer):
            y = _rmsnorm(x, f32(layer["ln1"]), eps)
            q = _rope((y @ f32(layer["wq"])).reshape(s, h, hd), theta)
            k = _rope((y @ f32(layer["wk"])).reshape(s, kv, hd), theta)
            v = (y @ f32(layer["wv"])).reshape(s, kv, hd)
            a = _attention(q, k, v, window, qb).reshape(s, h * hd)
            x = x + a @ f32(layer["wo"])
            y = _rmsnorm(x, f32(layer["ln2"]), eps)
            mlp = layer["mlp"]
            gate = jax.nn.silu(y @ f32(mlp["w_gate"])) * (y @ f32(mlp["w_up"]))
            return x + gate @ f32(mlp["w_down"])

        x = f32(params["embed"])[inp]
        for layer in params["layers"]:
            x = jax.checkpoint(block)(x, layer)
        x = _rmsnorm(x, f32(params["ln_f"]), eps)
        return x, _mean_nll(x, f32(params["lm_head"]), tgt, qb)

    with jax.default_matmul_precision("highest"):
        hidden, losses = jax.lax.map(one_sequence, tokens)
    return hidden, jnp.mean(losses)
