"""Plain reference of the LFM2-8B-A1B block (HF model type ``lfm2_moe``,
recalled without a network) and its training loss, under ONE CHIP'S SHARE of
a 4-way expert-parallel layer.  Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no kernels, no sort, no cache.  Independent of
``dlrover_tpu/models/llama.py``: it takes the same parameter tree (that is
the interface, not shared code; ``conv_w`` is stored ``[taps, channels]``,
PyTorch's ``[C, 1, K]`` transposed) and HF key names for sizes.

``rms(v, w) = v / sqrt(mean(v^2) + norm_eps) * w`` over the last axis, no
bias anywhere, ``x [B, S, d]`` the residual stream.  Block ``i``::

    h = x + Op_i(rms(x, ln1))          Op by layer_types[i]
    y = h + FFN_i(rms(h, ln2))

After the last block one ``rms(., ln_f)``; the head is the embedding
transposed (tied: one leaf), ``logits = z @ embed^T``; the loss is the mean
next-token cross-entropy over the rows this chip's slice of the vocabulary
holds, and nothing else (``use_expert_bias`` is the auxiliary-loss-free
scheme: no balance term, no z term).

``Op = conv`` (the double-gated short convolution), for the normed ``u``::

    [B | C | X] = u @ in_proj          thirds of 3 d columns, in that order
    z   = B * X
    c_t = sum_{k=0..K-1} w_k * z_{t-(K-1)+k}     depthwise, causal, zeros
                                                 before the sequence, K = 3
    Op  = (C * c) @ out_proj                     no activation anywhere

``Op = full_attention``: ``q = u @ wq`` (32 heads of 64), ``k = u @ wk``,
``v = u @ wv`` (8 heads of 64); each head's q and each head's k
RMS-normalised over its OWN 64 dims with one gain of 64 (``q_norm``,
``k_norm``), THEN RoPE (theta ``rope_theta``, the pairs ``(j, j + 32)``),
causal softmax at ``1 / sqrt(64)``, ``@ wo``.

``FFN`` is SwiGLU of ``intermediate_size`` in the first ``num_dense_layers``
layers and the routed block in every later one::

    s   = sigmoid(y @ router)                      [B, S, 32], float32
    T   = the 4 largest of s + b                   b: the selection bias
    w_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-6)
    FFN = sum_{e in T and HELD} w_e SwiGLU_e(y)    no shared expert

The share: the router is ``published.num_experts`` (32) wide and the top 4
are taken and normalised over all 32; this chip HOLDS experts ``0 ..
num_experts - 1`` (8), computes those of a token's picks and leaves out what
the absent experts would add.  That partial result is the layer's output and
goes on to the next layer, here as in the program.  The bias update is the
optimizer step's and is not computed here.

Departures, for memory only and with no effect on any value: every block is
a ``jax.checkpoint``; attention runs as a scan over blocks of 512 query
rows, each against all keys with the mask written out; the held experts run
as a scan, each over every token with the weight 0 where it was not chosen;
the loss runs over blocks of positions — so that ``jax.grad`` of this
reference at 8,192 positions fits beside the training state on one chip.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's four experts
of 32 per routed block, by name) it computes THOSE, weighted by its own
float32 scores of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]``
the ``s + b`` the choice was made from, ``extra["scalars"]`` empty (the
loss has no further term).

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/lfm2_probe.py``, ``benchmark/tests/test_lfm2.py``: the
comparison must find each).  Faults: ``"qk_norm_whole_width"`` (q and k
normalised over all heads' dims at once, the gain of 64 tiled),
``"c_x_exchanged"`` (``(X * conv(B * C))``: ``B * X`` commutes, ``C`` does
not), ``"conv_ahead"`` (the convolution reads t-1..t+1: not causal).
Lower-precision stand-ins, float8 e4m3 being the nearest precision below the
stated bf16 (gradients pass straight through the rounding):
``"fp8_experts"`` rounds what enters each of the experts' three matmuls —
the rows and the weights; ``"fp8_routed_stream"`` rounds the normed stream
entering every routed block, so the router's input and the experts' rows
(GLM's ``fp8_router_stream`` with the experts behind it); ``"fp8_stream"``
rounds the normed stream entering every mixer and every MLP, dense or
routed (Granite's ``fp8_stream``: the activations of a model trained in
fp8).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("qk_norm_whole_width", "c_x_exchanged", "conv_ahead")
STAND_INS = ("fp8_experts", "fp8_routed_stream", "fp8_stream")
PLANTED = FAULTS + STAND_INS
#: the constant HF's router adds to the sum of the chosen scores (assumed;
#: the configuration file)
ROUTER_NORM_EPS = 1e-6
ATTENTION = "full_attention"


def experts_name(i) -> str:
    return f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier (XLA may keep the
    excess precision of a convert pair); the gradient passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (d, d + D/2)."""
    s, d = x.shape[1], x.shape[3]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, w_gate, w_up, w_down, low=None):
    """``(silu(y w_gate) * (y w_up)) w_down``; with ``low`` every operand of
    the three matmuls is rounded to that dtype first."""
    r = (lambda a: a) if low is None else (lambda a: _rounded(a, low))
    hidden = jax.nn.silu(r(y) @ r(w_gate)) * (r(y) @ r(w_up))
    return r(hidden) @ r(w_down)


def _conv_mixer(u, conv, cfg):
    """The gated short convolution on the normed stream ``u [B, S, d]``."""
    planted = cfg.get("planted")
    d, taps = u.shape[-1], conv["conv_w"].shape[0]
    bcx = u @ conv["in_proj"]
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    if planted == "c_x_exchanged":
        gate_c, x = x, gate_c
    z = gate_b * x
    # out_t = sum_k w[k] z[t - (taps - 1) + k + ahead], zeros outside
    ahead = 1 if planted == "conv_ahead" else 0
    s = z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1 - ahead, ahead), (0, 0)))
    c = sum(zp[:, k:k + s] * conv["conv_w"][k] for k in range(taps))
    return (gate_c * c) @ conv["out_proj"]


def _attention(y, layer, cfg, q_block=512):
    b, s, _ = y.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q, k, v = y @ layer["wq"], y @ layer["wk"], y @ layer["wv"]
    if cfg.get("planted") == "qk_norm_whole_width":
        q = _rmsnorm(q, jnp.tile(layer["q_norm"], h), eps)
        k = _rmsnorm(k, jnp.tile(layer["k_norm"], kv), eps)
        q, k = q.reshape(b, s, h, hd), k.reshape(b, s, kv, hd)
    else:
        q = _rmsnorm(q.reshape(b, s, h, hd), layer["q_norm"], eps)
        k = _rmsnorm(k.reshape(b, s, kv, hd), layer["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    # each key and value head under its h / kv query heads
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v.reshape(b, s, kv, hd), h // kv, axis=2)
    scale = 1.0 / jnp.sqrt(F32(hd))
    q_block = min(q_block, s)

    @jax.checkpoint
    def rows(_, block):
        qb, first = block  # [B, q_block, H, D], the block's first position
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        causal = jnp.arange(s)[None, :] <= (
            first + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, v)

    # a scan over blocks of query rows, each against all keys: one block's
    # scores exist at a time, forward and backward
    blocks = q.reshape(b, s // q_block, q_block, h, hd)
    _, out = jax.lax.scan(
        rows, None, (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, s, q_block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * hd) @ layer["wo"]


def _routed(y, moe, cfg, given):
    """y [B, S, d] -> (out, own choice, selection scores)."""
    n_exp, held = router_width(cfg), cfg["num_experts"]
    s = jax.nn.sigmoid(y @ moe["router"])
    select = s + moe["router_bias"]
    _, own = jax.lax.top_k(select, cfg["num_experts_per_tok"])
    chosen = own if given is None else given
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_NORM_EPS)
    w = w * cfg["routed_scaling_factor"]
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]
    low = (jnp.float8_e4m3fn if cfg.get("planted") == "fp8_experts"
           else None)

    @jax.checkpoint
    def one_expert(out, e):
        return out + combine[..., e, None] * _swiglu(
            y, moe["wg"][e], moe["wi"][e], moe["wo"][e], low), None

    # the held experts are the first `held` of the router's numbering;
    # a pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    return out, own, select


def _block(x, layer, kind, cfg, given):
    eps, planted = cfg["norm_eps"], cfg.get("planted")
    u = _rmsnorm(x, layer["ln1"], eps)
    if planted == "fp8_stream":
        u = _rounded(u, jnp.float8_e4m3fn)
    x = x + (_attention(u, layer, cfg) if kind == ATTENTION
             else _conv_mixer(u, layer["conv"], cfg))
    y = _rmsnorm(x, layer["ln2"], eps)
    if planted == "fp8_stream" or (
            planted == "fp8_routed_stream" and "moe" in layer):
        y = _rounded(y, jnp.float8_e4m3fn)
    if "moe" not in layer:
        mlp = layer["mlp"]
        return x + _swiglu(y, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]), None
    out, own, select = _routed(y, layer["moe"], cfg, given)
    return x + out, (own, select)


def _mean_nll(x, head, targets, block=1024):
    """Mean next-token cross-entropy, over blocks of positions."""
    s = x.shape[1]
    block = min(block, s)
    total = jnp.zeros((), F32)
    for start in range(0, s, block):
        @jax.checkpoint
        def nll(xb, tb):
            logp = jax.nn.log_softmax(xb @ head, -1)
            return -jnp.sum(
                jnp.take_along_axis(logp, tb[..., None], -1)[..., 0])

        sl = slice(start, start + block)
        total = total + nll(x[:, sl], targets[:, sl])
    return total / targets.size


def hidden_and_loss(params, tokens, cfg: dict, given=None):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, loss,
    extra).  ``cfg``: HF keys."""
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, (layer, kind) in enumerate(
                zip(params["layers"], cfg["layer_types"])):
            name = experts_name(i)
            pick = None if given is None else given.get(name)
            x, routed = jax.checkpoint(
                lambda x, layer, pick, kind=kind: _block(
                    x, layer, kind, cfg, pick))(x, layer, pick)
            if routed is not None:
                extra["choices"][name], extra["probs"][name] = routed
        hidden = _rmsnorm(x, params["ln_f"], cfg["norm_eps"])
        loss = _mean_nll(hidden, params["embed"].T, tgt)
    return hidden, loss, extra
