"""Plain reference of a routed (mixture-of-experts) Llama-family block, for
the benchmark's own tests: no configuration file can name it (it is not
under ``reference/``); the real ones come with their configurations.

Pre-norm RMSNorm, rotary embedding on halves, causal grouped-query
attention (with RMSNorm over the whole q and k projections where a layer
has ``q_norm``/``k_norm``, as OLMoE's block does), and in EVERY layer a
softmax router in float32, the top ``num_experts_per_tok`` of
``num_experts`` SwiGLU experts, their probabilities renormalised where
``norm_topk_prob`` and left as they are where not, no capacity (no token is
dropped); untied head; loss = mean next-token cross-entropy +
``router_aux_loss_coef`` x the load-balance sum that
``dlrover_tpu/models/llama.py::_moe_swiglu`` computes today (experts x
sum over experts of mean probability x share of tokens whose FIRST choice
it is, over all tokens of the batch, summed over layers).

It keeps the routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's chosen
experts by name) it computes those experts, weighted by its OWN float32
probabilities of them.  Either way it returns ``(hidden, loss, extra)``
with the ``choices`` it would have made itself, its ``scalars`` and the
``probs`` the choices were made from.

``dtype`` is for the tests alone: ``bfloat16`` rounds every matmul operand
and the residual stream as a training step does (accumulation in float32),
which makes this file a stand-in system at widths the CPU could not run the
program's dispatch at.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def experts_name(i: int) -> str:
    return f"layers.{i}.experts"


def _rmsnorm(x, w, eps):
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (d, d + D/2)."""
    s, d = x.shape[1], x.shape[3]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_and_loss(params, tokens, cfg: dict, given=None, dtype=F32):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, loss,
    extra).  ``cfg``: HF keys."""
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=F32)

    def attention(y, layer):
        b, s, _ = y.shape
        q, k = mm("bsd,de->bse", y, layer["wq"]), mm(
            "bsd,de->bse", y, layer["wk"])
        if "q_norm" in layer:
            q = _rmsnorm(q, layer["q_norm"], eps)
            k = _rmsnorm(k, layer["k_norm"], eps)
        q = _rope(q.reshape(b, s, h, hd), theta)
        k = jnp.repeat(_rope(k.reshape(b, s, kv, hd), theta), h // kv, 2)
        v = jnp.repeat(mm("bsd,de->bse", y, layer["wv"]).reshape(
            b, s, kv, hd), h // kv, 2)
        scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return mm("bse,ed->bsd", mm("bhqk,bkhd->bqhd", p, v).reshape(
            b, s, h * hd), layer["wo"])

    def routed(y, moe, given_here):
        """y [B, S, d] -> (out, own choice, probs, load-balance term)."""
        probs = jax.nn.softmax(jnp.einsum(
            "bsd,de->bse", y.astype(F32), moe["router"].astype(F32)), -1)
        _, own = jax.lax.top_k(probs, top_k)
        chosen = own if given_here is None else given_here
        w = jnp.take_along_axis(probs, chosen, -1)
        if cfg["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        # [B, S, E]: the weight of each expert in each token, 0 if not taken
        combine = jnp.sum(
            w[..., None] * jax.nn.one_hot(chosen, n_exp, dtype=F32), -2)

        def one_expert(out, e):
            gate = jax.nn.silu(mm("bsd,df->bsf", y, moe["wg"][e]))
            up = mm("bsd,df->bsf", y, moe["wi"][e])
            return out + combine[..., e, None] * mm(
                "bsf,fd->bsd", gate * up, moe["wo"][e]), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros(y.shape, F32),
                              jnp.arange(n_exp))
        first = jax.nn.one_hot(chosen[..., 0], n_exp, dtype=F32)
        balance = n_exp * jnp.sum(
            jnp.mean(probs, (0, 1)) * jnp.mean(first, (0, 1)))
        return out, own, probs, balance

    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(dtype)[inp]
        for i, layer in enumerate(params["layers"]):
            x = (x + attention(_rmsnorm(x, layer["ln1"], eps), layer)
                 ).astype(dtype)
            name = experts_name(i)
            out, own, probs, bal = routed(
                _rmsnorm(x, layer["ln2"], eps), layer["moe"],
                None if given is None else given[name])
            x = (x + out).astype(dtype)
            extra["choices"][name], extra["probs"][name] = own, probs
            balance = balance + bal
        x = _rmsnorm(x, params["ln_f"], eps)
        logp = jax.nn.log_softmax(
            mm("bsd,dv->bsv", x, params["lm_head"]), -1)
    nll = -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))
    extra["scalars"]["moe_aux"] = balance
    return x, nll + cfg.get("router_aux_loss_coef", 0.01) * balance, extra
