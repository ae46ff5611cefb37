"""Plain reference of the Ouro looped language model (arXiv:2510.25741,
"Scaling Latent Reasoning via Looped Language Models"; HF
``modeling_ouro.py``, model type ``ouro``) and its stage-I training loss.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, the causal mask written out, a loop over passes around a
loop over layers.  Independent of ``dlrover_tpu/models/llama.py``: it takes the
same parameter tree (that is the interface, not shared code) and HF key
names for sizes.

One pass of the stack, ``h [B, S, d]`` the residual stream, ``rms(v, g) =
v / sqrt(mean(v^2) + eps) * g`` over the last axis, for layer l = 1..L::

    h = h + rms(attn_l(rms(h, ln1_l)), ln1_out_l)            # sandwich
    n = rms(h, ln2_l)                                        # norm: four
    h = h + rms((silu(n @ w_gate_l) * (n @ w_up_l)) @ w_down_l, ln2_out_l)

    attn_l(n): q, k, v = n @ wq, n @ wk, n @ wv              # no bias
               q, k = rope(split(q)), rope(split(k))   # pairs (i, i + hd/2)
               softmax(causal(q k^T / sqrt(hd))) v @ wo

The loop, ``T = total_ut_steps``, the SAME L layers in every pass::

    h = embed[tokens]
    for t = 1..T:   h = L layers(h);  z_t = rms(h, ln_f);  h = z_t
                    logits_t = z_t @ lm_head
                    lam_t = sigmoid(z_t @ exit_gate.w + exit_gate.b)

    p_1 = lam_1;  p_t = lam_t * prod_{j<t}(1 - lam_j)  (1 < t < T);
    p_T = prod_{j<T}(1 - lam_j)            # the remainder; lam_T unused

    loss = mean_r [ sum_t p_t[r] * CE(logits_t[r], y[r]) - beta * H(p[r]) ]
    H(p) = - sum_t p_t log p_t,  beta = 0.1

What ``config.json`` does not say is recalled from the paper and the HF
modelling code, without a network, and listed under ``assumed`` in the
configuration file.  Departures and choices known to the builder:

- HF names the four gains of a layer ``input_layernorm``,
  ``input_layernorm_2``, ``post_attention_layernorm``,
  ``post_attention_layernorm_2``; here ``ln1``, ``ln1_out``, ``ln2``,
  ``ln2_out`` (the program's parameter tree).
- The final norm closes EVERY pass and its output feeds the next pass (the
  HF loop applies ``self.norm`` inside the loop over ``total_ut_steps``);
  a variant that norms only for the head and loops the raw stream is not
  what is computed here.
- HF's ``early_exit_gate`` is a ``Linear(hidden, 1)`` with bias; here the
  weight is a ``[d]`` vector and the bias a scalar.
- The loss is the paper's stage-I objective (expected task loss under the
  exit distribution minus ``beta`` times its entropy: a uniform prior's KL
  up to a constant), ``beta`` 0.1.  The HF modelling code returns the
  last pass's cross-entropy alone; the later stage that trains the gate
  against the loss improvement of each pass is not computed.
- Inference (exit when the cumulated ``p`` passes ``early_exit_threshold``)
  is not computed: training runs all T passes.
- ``jax.checkpoint`` around each block application, each block of query
  rows and each block of head positions changes no value: it is there so
  that ``jax.grad`` of this reference fits beside the training state.
- The loop over passes is a ``jax.lax.scan`` with its body under
  ``jax.checkpoint`` (the layers inside it a Python loop), which changes
  no value either: with both loops in Python the 32 block applications and
  their 32 backward bodies compile into an executable of 319 MB that takes
  minutes to build in every run and that the chip machine's compile cache
  (192 MiB) cannot hold (my chip run, PR 33); the scan compiles one pass.
  (Scanning the layers too, over stacked parameters, costs a 1.6 GB copy
  and 8.3 GB of temporaries: the backward then does not fit beside the
  training state; AOT, PR 33.)

This block makes no discrete choice, so the adapter returns ``(hidden,
loss)`` and the harness asks no more of this file; it returns ``(hidden,
loss, counters)`` all the same, for the float32 comparison of the loop's
counters on the CPU (``benchmark/tests/test_ouro.py``): each pass's mean
cross-entropy ``loop_ce`` ``[T]``, mean exit probability
``loop_exit_prob`` ``[T]`` and the mean entropy of the exit distribution
``loop_exit_entropy``, the names and shapes of the program's own
(``llama.exit_expectation_loss``).

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/ouro_probe.py``, ``benchmark/tests/test_ouro.py``: the
comparison must find each): ``"three_passes"`` runs one pass fewer and
hands the last one out twice, ``"no_branch_norm"`` drops the two
branch-output norms, ``"no_remainder"`` takes ``p_T = lam_T * prod`` like
every other pass, ``"fp8_stream"`` rounds the values of the stream entering
each pass to float8 e4m3 (the nearest precision below bf16, as a plain-jnp
stand-in; gradients pass straight through the rounding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: arXiv:2510.25741, stage I: weight of the exit distribution's entropy
EXIT_ENTROPY_BETA = 0.1
PLANTED = ("three_passes", "no_branch_norm", "no_remainder", "fp8_stream")


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


def _attention(n, layer, cfg, q_block=1024):
    b, s, _ = n.shape
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    theta = float(cfg["rope_theta"])
    q = _rope((n @ layer["wq"]).reshape(b, s, h, hd), theta)
    k = jnp.repeat(
        _rope((n @ layer["wk"]).reshape(b, s, kv, hd), theta), h // kv, 2)
    v = jnp.repeat((n @ layer["wv"]).reshape(b, s, kv, hd), h // kv, 2)
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


def _block(h, layer, cfg, branch_norm):
    eps = cfg["rms_norm_eps"]
    a = _attention(_rmsnorm(h, layer["ln1"], eps), layer, cfg)
    if branch_norm:
        a = _rmsnorm(a, layer["ln1_out"], eps)
    h = h + a
    n, mlp = _rmsnorm(h, layer["ln2"], eps), layer["mlp"]
    m = (jax.nn.silu(n @ mlp["w_gate"]) * (n @ mlp["w_up"])) @ mlp["w_down"]
    if branch_norm:
        m = _rmsnorm(m, layer["ln2_out"], eps)
    return h + m


def _pass_nll(z, lm_head, targets, block=1024):
    """Per-token cross-entropy [B, S] of one pass, over blocks of
    positions so that the S x vocabulary logits never exist at once."""
    s = z.shape[1]
    block = min(block, s)
    out = []
    for start in range(0, s, block):
        @jax.checkpoint
        def nll(zb, tb):
            logp = jax.nn.log_softmax(zb @ lm_head, -1)
            return -jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]

        out.append(nll(z[:, start:start + block],
                       targets[:, start:start + block]))
    return jnp.concatenate(out, 1)


def exit_distribution(lam, remainder=True):
    """lam [T, ...] -> p [T, ...], written out pass by pass."""
    p, stay = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0]):
        last = t == lam.shape[0] - 1
        p.append(stay if last and remainder else lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p)


def hidden_and_loss(params, tokens, cfg: dict):
    """tokens [B, S+1] int -> (the T final-norm streams stacked along the
    batch ``[T*B, S, d]`` f32, loss, counters).  ``cfg``: HF keys."""
    eps, passes = cfg["rms_norm_eps"], cfg["total_ut_steps"]
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(f"ouro_ref: unknown planted fault {planted!r}")
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    gate = params["exit_gate"]
    block = jax.checkpoint(
        lambda h, layer: _block(h, layer, cfg, planted != "no_branch_norm"))

    @jax.checkpoint  # or the outer scan keeps every pass's residuals
    @jax.checkpoint  # or the scan keeps every pass's residuals
    def one_pass(h, _):
        if planted == "fp8_stream":
            # the VALUES are rounded, behind a barrier (XLA may otherwise
            # keep the excess precision of a convert pair); the gradient
            # passes unrounded (an unscaled e4m3 cast would flush it)
            rounded = jax.lax.optimization_barrier(
                h.astype(jnp.float8_e4m3fn)).astype(F32)
            h = h + jax.lax.stop_gradient(rounded - h)
        for layer in params["layers"]:
            h = block(h, layer)
        z = _rmsnorm(h, params["ln_f"], eps)
        lam = jax.nn.sigmoid(z @ gate["w"] + gate["b"])
        return z, (z, lam, _pass_nll(z, params["lm_head"], tgt))

    run = passes - 1 if planted == "three_passes" else passes
    with jax.default_matmul_precision("highest"):
        _, (streams, lam, nll) = jax.lax.scan(
            one_pass, params["embed"][inp], None, length=run)
    if run < passes:  # the planted fault hands the last pass out twice
        streams, lam, nll = (jnp.concatenate([a, a[-1:]])
                             for a in (streams, lam, nll))
    p = exit_distribution(lam, planted != "no_remainder")  # [T, B, S]
    # p log p is 0 at p = 0 (and its gradient is kept finite there)
    entropy = -jnp.sum(p * jnp.log(jnp.where(p > 0, p, 1.0)), 0)  # [B, S]
    beta = cfg.get("exit_entropy_beta", EXIT_ENTROPY_BETA)
    loss = jnp.mean(jnp.sum(p * nll, 0) - beta * entropy)
    counters = {"loop_ce": jnp.mean(nll, (1, 2)),
                "loop_exit_prob": jnp.mean(p, (1, 2)),
                "loop_exit_entropy": jnp.mean(entropy)}
    return streams.reshape((-1,) + streams.shape[2:]), loss, counters
