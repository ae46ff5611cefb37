"""Plain reference of the Qwen3-Next block (HF model type ``qwen3_next``,
recalled without a network) and its training loss, under ONE CHIP'S SHARE of
a 16-way expert-parallel layer.  Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no kernels, no sort, no cache, and no chunked
form of the delta rule but in two stand-ins planted on purpose.  Independent
of ``dlrover_tpu/``: it takes the same parameter tree (that is the
interface, not shared code; ``conv_w`` is stored ``[taps, channels]``,
PyTorch's ``[C, 1, K]`` transposed) and HF key names for sizes.

``N0(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)`` over the last
axis (``Qwen3NextRMSNorm``: the leaf is ``w``, initialised 0), no bias
anywhere, ``x [B, S, C]`` the residual stream.  Layer ``i`` (from 0) is
``full_attention`` where ``(i + 1) % full_attention_interval == 0``, else
``linear_attention``.  Block::

    h = x + Mixer_i(N0(x; ln1))
    y = h + MoE(N0(h; ln2))

After the last block one ``N0(., ln_f)``; ``logits = z @ lm_head`` (untied);
the loss is the mean next-token cross-entropy over the rows this chip's slice
of the vocabulary holds, plus ``router_aux_loss_coef`` (assumed 0.001) times
the routers' balance terms summed over the layers.

``Mixer = linear_attention`` (Gated DeltaNet; ``Hk`` key heads, ``Hv = R Hk``
value heads, ``D = linear_key_head_dim = linear_value_head_dim``), for the
normed ``u``::

    [q | k | v | z] = view(u @ in_proj_qkvz, [Hk, D + D + R D + R D])
    [b | a]         = view(u @ in_proj_ba,   [Hk, R + R])
    [q | k | v]    <- silu(conv([q | k | v] flattened))    depthwise, causal,
                      linear_conv_kernel_dim taps, zeros before the sequence,
                      no bias
    beta = sigmoid(b)          g = -exp(A_log) * softplus(a + dt_bias)
    q, k <- each key head under its R value heads (repeat_interleave),
            x / sqrt(sum x^2 + 1e-6) over its D dims; q <- q / sqrt(D)
    per head, S [D, D] from 0, one position at a time:
        S <- exp(g_t) S;  m = k_t^T S;  S <- S + k_t (x) beta_t (v_t - m)
        o_t = q_t^T S
    y   = norm * (o / sqrt(mean(o^2) + eps)) * silu(z)     per head of D; a
          plain gain (initialised 1, NOT 1 + w), the norm BEFORE the gate
    Mixer = y @ out_proj

``Mixer = full_attention`` (``H`` query heads, ``KV`` key-value heads,
``head_dim``)::

    [q | gate] = view(u @ wq, [H, 2 head_dim])     halved per head
    k = u @ wk,  v = u @ wv
    q = N0(q; q_norm),  k = N0(k; k_norm)          over a head's dims, BEFORE
    RoPE at rope_theta on the first head_dim * partial_rotary_factor dims of
    each head, pairs (j, j + half of those); the other dims untouched
    causal softmax at head_dim^-1/2
    Mixer = (attn * sigmoid(gate)) @ wo

``MoE``: ``p = softmax(h @ router)`` over all ``published.num_experts`` in
float32; the ``num_experts_per_tok`` largest; divided by their sum
(``norm_topk_prob``); an expert is ``down(silu(gate h) * up h)``::

    MoE = sum_{e in T and HELD} p_e E_e(h) + sigmoid(h @ shared_gate) Shared(h)

The share: this chip HOLDS experts ``0 .. num_experts - 1`` (32 of 512),
computes those of a token's picks and leaves out what the absent experts
would add; the shared expert and its gate are whole.  That partial result is
the layer's output and goes on to the next layer, here as in the program.
The balance term is over the router's whole width: ``E sum_e f_e P_e`` with
``f_e`` the mean over tokens and the k picks, ``P_e`` the mean probability.

Departures, for memory only and with no effect on any value: every block is
a ``jax.checkpoint``; the recurrence runs in checkpointed blocks of
positions; attention runs as a scan over blocks of 512 query rows, each
against all keys with the mask written out; the held experts run as a scan,
each over every token with the weight 0 where it was not chosen; the loss
runs over blocks of positions — so that ``jax.grad`` of this reference at
8,192 positions fits beside the training state on one chip.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's experts per
routed block, by name) it computes THOSE, weighted by its own float32
probabilities of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]``
the softmax the choice was made from, ``extra["scalars"]`` the balance term
as it enters the loss.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/qwen3_next_probe.py``,
``benchmark/tests/test_qwen3_next.py``: the comparison must find each).
Faults: ``"beta_left_out"`` (the write is ``k (x) (v - m)``),
``"decay_dropped"`` (``g = 0``), ``"gate_dropped"`` (the attention output
without its gate), ``"rope_whole_head"`` (every dim of a head rotates),
``"gain_as_w"`` (``N0`` multiplies by ``w``, not ``1 + w``),
``"norm_after_gate"`` (the delta rule's output gated BEFORE its norm, as
Mamba-2's is), ``"shared_gate_dropped"`` (the shared expert added whole).
Lower-precision stand-ins: ``"fp8_stream"`` rounds the normed stream
entering every mixer to float8 e4m3, the nearest precision below the stated
bf16 (gradients pass straight through the rounding); ``"bf16_gamma"`` and
``"bf16_T"`` compute the delta rule in its chunked form (the only place this
file has one) with, of what the program keeps in float32 there, the
cumulative sums ``gamma`` of ``g`` inside a chunk or the inverse ``T = (I +
A)^-1`` rounded to bfloat16, the nearest precision below.  The comparison
finds the first two at published width on the chip and CANNOT SEE the third
(:data:`UNSEEN`): a ``T`` in bfloat16 moves the float32 reference by less
than a thousandth of what the system's own bf16 matmuls do (PERF.md section
4), so it is reported and judged by nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("beta_left_out", "decay_dropped", "gate_dropped",
          "rope_whole_head", "gain_as_w", "norm_after_gate",
          "shared_gate_dropped")
STAND_INS = ("fp8_stream", "bf16_gamma")
#: a stand-in no limit of the comparison can tell from the true reference
UNSEEN = ("bf16_T",)
PLANTED = FAULTS + STAND_INS + UNSEEN
#: HF's default for the family; the catalog's config drops the key (assumed;
#: the configuration file)
ROUTER_AUX_LOSS_COEF = 1e-3
ATTENTION = "full_attention"


def experts_name(i) -> str:
    return f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_types(cfg: dict) -> list:
    every = cfg["full_attention_interval"]
    return [ATTENTION if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def _norm0(x, w, cfg):
    """``N0``: the gain is ``1 + w``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    gain = w if cfg.get("planted") == "gain_as_w" else 1.0 + w
    return x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * gain


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier (XLA may keep the
    excess precision of a convert pair); the gradient passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _rope(x, theta, rotary):
    """x [B, S, H, D]: rotate the pairs (d, d + rotary/2) of the first
    ``rotary`` dims; the others pass."""
    s = x.shape[1]
    half = rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _delta_rule(q, k, v, g, beta, planted, scan_block=128):
    """q, k, v [B, S, H, D], g, beta [B, S, H] -> o [B, S, H, D]: the
    recurrence one position at a time, in checkpointed blocks of
    ``scan_block`` positions."""
    b, s, h, d = v.shape
    scan_block = min(scan_block, s)
    pad = -s % scan_block

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs  # [B, H, D] x 3, [B, H] x 2
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        write = v_t - held
        if planted != "beta_left_out":
            write = b_t[..., None] * write
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    # a padded position has g = 0, beta = 0 and k = 0: it leaves the state
    blocks = lambda a: jnp.pad(  # noqa: E731
        jnp.moveaxis(a, 1, 0), ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    ).reshape((-1, scan_block) + a.shape[:1] + a.shape[2:])
    _, out = jax.lax.scan(
        block, jnp.zeros((b, h, k.shape[-1], d), F32),
        tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape((s + pad, b, h, d))[:s], 0, 1)


def _delta_rule_chunked_low(q, k, v, g, beta, low, chunk=64):
    """THE ``bf16_T`` AND ``bf16_gamma`` STAND-INS, nothing else runs it:
    the same rule in chunks of ``chunk`` positions (the WY form), everything
    in float32 but the one array ``low`` names — the inverse ``T`` or the
    cumulative sums ``gamma`` — whose values are rounded to bfloat16."""
    from jax.scipy.linalg import solve_triangular

    b, s, h, d = v.shape
    pad = -s % chunk
    rows = lambda a: jnp.moveaxis(jnp.pad(  # noqa: E731
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (b, -1, chunk) + a.shape[2:]), 1, 0)
    eye = jnp.eye(chunk, dtype=F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def one_chunk(state, inputs):
        q_c, k_c, v_c, g_c, b_c = inputs  # [B, Q, H, D] x 3, [B, Q, H] x 2
        gamma = jnp.cumsum(g_c, axis=1)
        if low == "bf16_gamma":
            gamma = _rounded(gamma, jnp.bfloat16)
        diff = gamma[:, :, None] - gamma[:, None, :]  # [B, i, j, H]
        decay = jnp.where(lower[None, :, :, None], jnp.exp(jnp.where(
            lower[None, :, :, None], diff, 0.0)), 0.0)
        kk = jnp.einsum("bihd,bjhd->bijh", k_c, k_c)
        a = jnp.where(jnp.tril(lower, -1)[None, :, :, None],
                      b_c[:, :, None] * kk * decay, 0.0)
        a = jnp.moveaxis(a, 3, 1)  # [B, H, i, j]
        t = solve_triangular(eye + a, jnp.broadcast_to(eye, a.shape),
                             lower=True, unit_diagonal=True)
        if low == "bf16_T":
            t = _rounded(t, jnp.bfloat16)
        grown = jnp.exp(gamma)[..., None]
        w = jnp.einsum("bhij,bjhd->bihd", t, k_c * b_c[..., None] * grown)
        u = jnp.einsum("bhij,bjhd->bihd", t, v_c * b_c[..., None])
        new = u - jnp.einsum("bihk,bhkv->bihv", w, state)
        qk = jnp.einsum("bihd,bjhd->bijh", q_c, k_c) * decay
        out = (jnp.einsum("bihk,bhkv->bihv", q_c * grown, state)
               + jnp.einsum("bijh,bjhv->bihv", qk, new))
        total = gamma[:, -1]  # [B, H]
        to_end = jnp.exp(total[:, None] - gamma)[..., None]
        state = (jnp.exp(total)[..., None, None] * state
                 + jnp.einsum("bjhk,bjhv->bhkv", k_c * to_end, new))
        return state, out

    _, out = jax.lax.scan(
        one_chunk, jnp.zeros((b, h, k.shape[-1], d), F32),
        tuple(rows(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, h, d)[:, :s]


def _conv(x, w):
    """Depthwise causal convolution: x [B, S, C], w [K, C]; tap K - 1 meets
    position t itself; zeros before the sequence."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, k:k + s] * w[k] for k in range(taps))


def _gated_delta_net(u, gdn, cfg):
    """The linear-attention mixer on the normed stream ``u [B, S, C]``."""
    planted = cfg.get("planted")
    b, s, _ = u.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d = cfg["linear_key_head_dim"]
    if cfg["linear_value_head_dim"] != d:
        raise ValueError("qwen3_next_ref: key and value heads of one size")
    r = hv // hk
    qkvz = (u @ gdn["in_proj_qkvz"]).reshape(b, s, hk, (2 + 2 * r) * d)
    ba = (u @ gdn["in_proj_ba"]).reshape(b, s, hk, 2 * r)
    q, k = qkvz[..., :d], qkvz[..., d:2 * d]
    v = qkvz[..., 2 * d:(2 + r) * d]
    z = qkvz[..., (2 + r) * d:].reshape(b, s, hv, d)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, s, hv)
    a = ba[..., r:].reshape(b, s, hv)
    flat = lambda x: x.reshape(b, s, -1)  # noqa: E731
    mixed = jax.nn.silu(_conv(
        jnp.concatenate([flat(q), flat(k), flat(v)], -1), gdn["conv_w"]))
    q = mixed[..., :hk * d].reshape(b, s, hk, d)
    k = mixed[..., hk * d:2 * hk * d].reshape(b, s, hk, d)
    v = mixed[..., 2 * hk * d:].reshape(b, s, hv, d)
    g = -jnp.exp(gdn["A_log"]) * jax.nn.softplus(a + gdn["dt_bias"])
    if planted == "decay_dropped":
        g = jnp.zeros_like(g)
    # repeat_interleave: key head j serves value heads j r .. j r + r - 1
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
    q, k = unit(q) * d ** -0.5, unit(k)
    if planted in ("bf16_T", "bf16_gamma"):
        o = _delta_rule_chunked_low(q, k, v, g, beta, planted)
    else:
        o = _delta_rule(q, k, v, g, beta, planted)
    rms = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(jnp.square(x), -1, keepdims=True) + cfg["rms_norm_eps"])
    if planted == "norm_after_gate":
        y = gdn["norm"] * rms(o * jax.nn.silu(z))
    else:
        y = gdn["norm"] * rms(o) * jax.nn.silu(z)
    return y.reshape(b, s, hv * d) @ gdn["out_proj"]


def _attention(u, layer, cfg, q_block=512):
    planted = cfg.get("planted")
    b, s, _ = u.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    qg = (u @ layer["wq"]).reshape(b, s, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (u @ layer["wk"]).reshape(b, s, kv, hd)
    v = (u @ layer["wv"]).reshape(b, s, kv, hd)
    q, k = _norm0(q, layer["q_norm"], cfg), _norm0(k, layer["k_norm"], cfg)
    rotary = hd if planted == "rope_whole_head" else int(
        hd * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta, rotary), _rope(k, theta, rotary)
    # each key and value head under its h / kv query heads
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scale = hd ** -0.5
    q_block = min(q_block, s)

    @jax.checkpoint
    def rows(_, block):
        qb, first = block  # [B, q_block, H, D], the block's first position
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        causal = jnp.arange(s)[None, :] <= (
            first + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = q.reshape(b, s // q_block, q_block, h, hd)
    _, out = jax.lax.scan(
        rows, None, (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, s, q_block)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    if planted != "gate_dropped":
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(b, s, h * hd) @ layer["wo"]


def _routed(y, moe, cfg, given):
    """y [B, S, d] -> (out, own choice, probs, balance term)."""
    n_exp, held = router_width(cfg), cfg["num_experts"]
    probs = jax.nn.softmax(y @ moe["router"], -1)
    _, own = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    chosen = own if given is None else given
    w = jnp.take_along_axis(probs, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]

    @jax.checkpoint
    def one_expert(out, e):
        return out + combine[..., e, None] * _swiglu(
            y, moe["wg"][e], moe["wi"][e], moe["wo"][e]), None

    # the held experts are the first `held` of the router's numbering;
    # a pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    shared = _swiglu(y, moe["shared"]["w_gate"], moe["shared"]["w_up"],
                     moe["shared"]["w_down"])
    if cfg.get("planted") != "shared_gate_dropped":
        shared = jax.nn.sigmoid(y @ moe["shared_gate"]) * shared
    # f_e: mean over tokens and over the k picks; P_e: mean probability
    balance = n_exp * jnp.sum(
        jnp.mean(taken, (0, 1, 2)) * jnp.mean(probs, (0, 1)))
    return out + shared, own, probs, balance


def _block(x, layer, kind, cfg, given):
    u = _norm0(x, layer["ln1"], cfg)
    if cfg.get("planted") == "fp8_stream":
        u = _rounded(u, jnp.float8_e4m3fn)
    x = x + (_attention(u, layer, cfg) if kind == ATTENTION
             else _gated_delta_net(u, layer["gdn"], cfg))
    out, own, probs, balance = _routed(
        _norm0(x, layer["ln2"], cfg), layer["moe"], cfg, given)
    return x + out, (own, probs, balance)


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
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(
            f"qwen3_next_ref: unknown planted fault {planted!r}")
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, (layer, kind) in enumerate(
                zip(params["layers"], layer_types(cfg))):
            name = experts_name(i)
            pick = None if given is None else given.get(name)
            x, (own, probs, bal) = jax.checkpoint(
                lambda x, layer, pick, kind=kind: _block(
                    x, layer, kind, cfg, pick))(x, layer, pick)
            extra["choices"][name], extra["probs"][name] = own, probs
            balance = balance + bal
        hidden = _norm0(x, params["ln_f"], cfg)
        nll = _mean_nll(hidden, params["lm_head"], tgt)
    # the balance term as it enters the loss, weight included
    extra["scalars"] = {"moe_aux": cfg.get(
        "router_aux_loss_coef", ROUTER_AUX_LOSS_COEF) * balance}
    return hidden, nll + extra["scalars"]["moe_aux"], extra
