"""Plain reference of the Granite-4.0-H block (HF ``granitemoehybrid``, dense:
``num_local_experts`` 0; the Mamba-2 mixer as HF's Bamba / ``mamba2``
modelling code writes it, arXiv:2405.21060), recalled without a network.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no chunked form, no cache.

Per layer, on the stream ``x`` with ``m = residual_multiplier``::

    x = x + m * mixer(rms(x, ln1))        mixer by layer_types[i]
    x = x + m * swiglu(rms(x, ln2))       the shared MLP of every layer

``x_0 = embed[tokens] * embedding_multiplier``; the head is ``embed``
transposed (``tie_word_embeddings``) and ``logits = (rms(x, ln_f) @ embed^T)
/ logits_scaling``; the loss is the mean next-token cross-entropy over the
rows this chip's slice of the vocabulary holds.

The ``"mamba"`` mixer, for the normed stream ``u``::

    [z | xBC | dt] = u @ in_proj              widths d_inner | d_inner + 2GN | H
    xBC = silu(conv(xBC) + b)                 causal, depthwise, 4 taps: t-3..t
    [x | B | C] = xBC                         x as [H, P]; B, C per group
    dt = softplus(dt + dt_bias)               no clamp (time_step_limit 0, inf)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,   A = -exp(A_log), h_0 = 0
    y_t = h_t C_t + D x_t
    out = (rms(y * silu(z)) * norm) @ out_proj      gate BEFORE the norm

The recurrence is the SEQUENTIAL one, a ``lax.scan`` over positions — the
system computes the chunked dual form (``dlrover_tpu/ops/ssd.py``), so the
two share no algorithm.  The ``"attention"`` mixer is GQA with NO rotary
position (``position_embedding_type: "nope"``) and the softmax scale
``attention_multiplier`` (1/64, not 1/sqrt(64)), the causal mask written
out.

Independent of ``dlrover_tpu/models/llama.py``: it takes the same parameter
tree (that is the interface, not shared code; ``conv_w`` is stored ``[taps,
channels]``, PyTorch's ``[C, 1, K]`` transposed) and HF key names for sizes.
Departures, for memory only and with no effect on any value: the scan runs
in blocks of ``scan_block`` positions, attention over blocks of query rows
and the loss over blocks of positions, each block and each layer a
``jax.checkpoint``, so that ``jax.grad`` of this reference at 8,192
positions fits beside the training state on one chip.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/granite_probe.py``, ``benchmark/tests/test_granite.py``:
the comparison must find each).  Faults: ``"conv_shifted"`` (the convolution
reads t-4..t-1), ``"no_D"`` (the skip ``D x_t`` dropped),
``"gate_after_norm"`` (``rms(y) * norm * silu(z)``), ``"rope_on"`` (rotary
position on q and k at ``rope_theta``).  Lower-precision stand-ins:
``"fp8_stream"`` rounds the values of the stream entering every layer to
float8 e4m3 (gradients pass straight through), ``"bf16_scan"`` keeps the
cumulative sums of ``dt A`` inside each chunk of ``mamba_chunk_size``
positions and every decay ``exp(.)`` in bfloat16, as a chunked scan computed
in the stream's precision would.  A ``residual_multiplier`` (or any other
key) changed in the dict handed in is a fault of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("conv_shifted", "no_D", "gate_after_norm", "rope_on")
STAND_INS = ("fp8_stream", "bf16_scan")
PLANTED = FAULTS + STAND_INS


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier (XLA may keep the
    excess precision of a convert pair); the gradient passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _conv(x, w, b, shift):
    """x [S, C], w [K, C]: out_t = sum_k w[k] x[t - (K - 1) + k - shift],
    zeros before the sequence; K shifted adds."""
    k_taps, s = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k_taps - 1 + shift, 0), (0, 0)))
    out = sum(xp[k:k + s] * w[k] for k in range(k_taps))
    return out if b is None else out + b


def _decays(dt, a, chunk, planted):
    """``exp(dt_t A)`` [S, H]; the ``bf16_scan`` stand-in takes it from
    bfloat16 cumulative sums inside each chunk, and in bfloat16."""
    if planted != "bf16_scan":
        return jnp.exp(dt * a)
    s = dt.shape[0]
    pad = -s % chunk
    da = jnp.pad(dt * a, ((0, pad), (0, 0))).reshape(-1, chunk, dt.shape[1])
    cs = _rounded(jnp.cumsum(da, axis=1), jnp.bfloat16)
    before = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs[:, :-1]], axis=1)
    return _rounded(jnp.exp(cs - before), jnp.bfloat16).reshape(
        s + pad, -1)[:s]


def _recurrence(x, dt, decay, b, c, scan_block):
    """x [S, H, P], dt and decay [S, H], b and c [S, H, N] -> y [S, H, P]:
    ``h_t = decay_t h_{t-1} + dt_t x_t (x) b_t``, ``y_t = h_t c_t``, one
    position at a time, in checkpointed blocks of ``scan_block``."""
    s, heads, p = x.shape
    n = b.shape[-1]
    pad = -s % scan_block

    def step(h, inputs):
        x_t, dt_t, a_t, b_t, c_t = inputs
        h = (a_t[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    @jax.checkpoint
    def block(h, inputs):
        return jax.lax.scan(step, h, inputs)

    blocks = lambda a: jnp.pad(  # noqa: E731 - a padded step leaves h as is
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, scan_block) + a.shape[1:])
    decay = jnp.pad(decay, ((0, pad), (0, 0)), constant_values=1.0)
    _, y = jax.lax.scan(
        block, jnp.zeros((heads, p, n), F32),
        (blocks(x), blocks(dt), decay.reshape(-1, scan_block, heads),
         blocks(b), blocks(c)))
    return y.reshape(s + pad, heads, p)[:s]


def _mamba(u, ssm, cfg, planted, scan_block):
    """The mixer on one sequence's normed stream u [S, d]."""
    s = u.shape[0]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = heads * p
    zxbcdt = u @ ssm["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + inner + 2 * groups * n]
    dt = zxbcdt[:, inner + inner + 2 * groups * n:]
    xbc = jax.nn.silu(_conv(xbc, ssm["conv_w"], ssm.get("conv_b"),
                            1 if planted == "conv_shifted" else 0))
    x = xbc[:, :inner].reshape(s, heads, p)
    per_head = lambda a: jnp.repeat(  # noqa: E731 - group g serves H/G heads
        a.reshape(s, groups, n), heads // groups, axis=1)
    b = per_head(xbc[:, inner:inner + groups * n])
    c = per_head(xbc[:, inner + groups * n:])
    dt = jax.nn.softplus(dt + ssm["dt_bias"])
    decay = _decays(dt, -jnp.exp(ssm["A_log"]), cfg["mamba_chunk_size"],
                    planted)
    y = _recurrence(x, dt, decay, b, c, min(scan_block, s))
    if planted != "no_D":
        y = y + ssm["D"][:, None] * x
    y, gate = y.reshape(s, inner), jax.nn.silu(z)
    if planted == "gate_after_norm":
        y = _rmsnorm(y, ssm["norm"], cfg["rms_norm_eps"]) * gate
    else:
        y = _rmsnorm(y * gate, ssm["norm"], cfg["rms_norm_eps"])
    return y @ ssm["out_proj"]


def _rope(x, theta):
    """x [S, H, D]: rotate the pairs (d, d + D/2); the ``rope_on`` fault."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(y, layer, cfg, planted, q_block):
    """GQA on one sequence's normed stream y [S, d]: no position, scale
    ``attention_multiplier``, query i attends keys 0 .. i."""
    s = y.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    q = (y @ layer["wq"]).reshape(s, h, hd)
    k = (y @ layer["wk"]).reshape(s, kv, hd)
    v = (y @ layer["wv"]).reshape(s, kv, hd)
    if planted == "rope_on":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * cfg[
            "attention_multiplier"]
        ok = kpos[None, :] <= (start + jnp.arange(q_block))[:, None]
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, q_block))
    return out.reshape(s, h * hd) @ layer["wo"]


def _mean_nll(x, head, tgt, scale, block):
    """Mean next-token cross-entropy of x [S, d] against tgt [S], logits
    ``x @ head / scale``, over blocks of positions."""
    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ head / scale, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    s = x.shape[0]
    nll = jax.lax.map(jax.checkpoint(one_block),
                      (x.reshape(s // block, block, -1),
                       tgt.reshape(s // block, block)))
    return jnp.mean(nll)


def hidden_and_loss(params, tokens, cfg: dict, q_block: int = 512,
                    scan_block: int = 128):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, mean loss).
    ``cfg``: the configuration file's dict (HF keys)."""
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(
            f"granite_hybrid_ref: unknown planted fault {planted!r}")
    if not cfg["tie_word_embeddings"] or cfg["position_embedding_type"] != (
            "nope") or cfg["num_local_experts"]:
        raise ValueError(
            "granite_hybrid_ref computes a tied head, attention without "
            "position and a dense MLP")
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)

    def one_sequence(seq):
        inp, tgt = seq[:-1], seq[1:]
        qb = min(q_block, inp.shape[0])

        def block(x, layer):
            y = _rmsnorm(x, layer["ln1"], eps)
            if "ssm" in layer:
                mixed = _mamba(y, layer["ssm"], cfg, planted, scan_block)
            else:
                mixed = _attention(y, layer, cfg, planted, qb)
            x = x + m * mixed
            y = _rmsnorm(x, layer["ln2"], eps)
            mlp = layer["mlp"]
            gate = jax.nn.silu(y @ mlp["w_gate"]) * (y @ mlp["w_up"])
            return x + m * (gate @ mlp["w_down"])

        x = params["embed"][inp] * cfg["embedding_multiplier"]
        for kind, layer in zip(cfg["layer_types"], params["layers"]):
            if ("ssm" in layer) != (kind == "mamba"):
                raise ValueError(
                    "granite_hybrid_ref: the parameters' layers are not of "
                    f"the kinds layer_types={cfg['layer_types']} names")
            if planted == "fp8_stream":
                x = _rounded(x, jnp.float8_e4m3fn)
            x = jax.checkpoint(block)(x, layer)
        x = _rmsnorm(x, params["ln_f"], eps)
        return x, _mean_nll(x, params["embed"].T, tgt,
                            cfg["logits_scaling"], qb)

    with jax.default_matmul_precision("highest"):
        hidden, losses = jax.lax.map(one_sequence, tokens)
    return hidden, jnp.mean(losses)
