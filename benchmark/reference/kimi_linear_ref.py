"""Plain reference of the Kimi Linear block (HF model type ``kimi_linear``,
arXiv:2510.26692, recalled without a network) and its training loss, under
ONE CHIP'S SHARE of a 32-way expert-parallel layer.  Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no
sort, no cache, and no chunked form of the delta rule — the recurrence runs
one position at a time.  Independent of ``dlrover_tpu/``: it takes the same
parameter tree (that is the interface, not shared code; the taps are stored
``[taps, channels]``, PyTorch's ``[C, 1, K]`` transposed) and HF key names
for sizes.

``rms(v; w) = v / sqrt(mean(v^2) + rms_norm_eps) * w`` over the last axis,
no bias unless said, ``x [B, S, C]`` the residual stream.  The layers are
counted from 1 as ``linear_attn_config`` counts them: those in
``kda_layers`` have the KDA mixer, those in ``full_attn_layers`` the MLA one.
Block::

    h = x + Mixer(rms(x; ln1))
    y = h + MLP(rms(h; ln2))

After the last block ``rms(.; ln_f)``; ``logits = z @ lm_head`` (untied); the
loss is the mean next-token cross-entropy over the rows this chip's slice of
the vocabulary holds, plus ``1e-4`` (assumed) times the routers'
sequence-wise balance terms summed over the routed layers.

``Mixer = KDA`` (Kimi Delta Attention; ``H`` heads of ``D``, keys and values
alike), for the normed ``u``::

    q = l2norm_D(silu(conv(u wq))) * D^-1/2     k = l2norm_D(silu(conv(u wk)))
    v = silu(conv(u wv))           three depthwise causal convolutions of
                                   short_conv_kernel_size taps, zeros before
                                   the sequence, no bias;
                                   l2norm(x) = x / sqrt(sum x^2 + 1e-6)
    g    = -exp(A_log[h]) * softplus((u f_a) f_b + dt_bias)     [B, S, H, D]
    beta = sigmoid(u w_beta)                                    [B, S, H]
    per head, S [D, D] from 0, one position at a time:
        S <- diag(exp(g_t)) S         each ROW of the state by its own decay
        m  = k_t^T S;   S <- S + k_t (x) beta_t (v_t - m);   o_t = q_t^T S
    y   = norm * (o / sqrt(mean(o^2) + eps)) * sigmoid((u g_a) g_b + g_bias)
    Mixer = y @ out_proj

``Mixer = MLA`` (``H`` heads; ``q_lora_rank`` null: one query matrix;
``mla_use_nope``: NO rotary position on either part)::

    q = view(u @ wq, [H, nope + rope])
    [c (kv_lora_rank); k_pe (rope)] = u @ wkv_a;   c = rms(c; kv_a_norm)
    [k_nope_i (nope); v_i (v_head_dim)] = c @ wkv_b          per head i
    k_i = [k_nope_i; k_pe]            the token's ONE k_pe under every head
    Mixer = concat_i softmax(causal(q_i k_i^T / sqrt(nope + rope))) v_i @ wo

``MLP`` is SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and the routed block in every later one::

    s   = sigmoid(y @ router)                   [B, S, 256], float32
    T   = the 8 largest of s + b                b: the selection bias
    w_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-20)
    MLP = sum_{e in T and HELD} w_e SwiGLU_e(y) + SwiGLU_shared(y)

The share: the router is ``published.num_experts`` (256) wide and the top 8
are taken and normalised over all 256; this chip HOLDS experts ``0 ..
num_experts - 1`` (8), computes those of a token's picks and leaves out what
the absent experts would add.  That partial result is the layer's output and
goes on to the next layer, here as in the program.  ``held_first`` in the
dict handed to the reference moves the held slice (the share test).

Departures, for memory only and with no effect on any value: every block is
a ``jax.checkpoint``; the recurrence runs in checkpointed blocks of
positions; each mixer runs its heads in checkpointed groups of eight (the
heads meet only in the sum of their parts of the output projection);
attention runs as a scan over blocks of 512 query rows, each against all keys
with the mask written out (8 heads x 512 x 16,384 float32 scores are 268
MB); the held experts run as a scan, each over every token
with the weight 0 where it was not chosen; the loss runs over blocks of
positions — so that ``jax.grad`` of this reference at 16,384 positions fits
beside the training state on one chip.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's experts per
routed block, by name) it computes THOSE, weighted by its own float32
scores of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]``
the ``s + b`` the choice was made from, ``extra["scalars"]`` the balance
term as it enters the loss and :func:`rule_alone`'s number a head.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/kimi_linear_probe.py``,
``benchmark/tests/test_kimi_linear.py``: the comparison must find each).
Faults: ``"decay_per_head"`` (every channel of a head decays by the head's
mean ``g``: the scalar rule), ``"k_pe_rotated"`` (RoPE at ``rope_theta`` on
the key's shared part), ``"v_padded"`` (each head's values padded to the
keys' width and the output NOT sliced: the heads' columns reach ``wo``
shifted), ``"gate_silu"`` (the output gate ``silu``, as the scalar rule's
mixer has it), ``"gate_bias_dropped"``, ``"beta_left_out"``.  Lower-precision
stand-ins, the nearest precision below the stated one.  Of the stated bf16:
``"fp8_stream"`` (the normed stream entering every mixer rounded to float8
e4m3) and ``"fp8_router_stream"`` (that entering every router, as
``reference/glm4_moe_lite_ref.py``'s): the second readings of the adapter's
two choice limits.  Of what the rule keeps in float32: ``"bf16_gamma"`` (the
decay's running sum inside a block of 128 positions rounded to bfloat16,
each step's decay the difference of two rounded sums) and ``"bf16_state"``
(the state rounded to bfloat16 after every position): the second readings
of the adapter's scalar limit, through :func:`rule_alone`'s numbers — the
model's own distances do not tell them from the system's bf16 (PERF.md
section 6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("decay_per_head", "k_pe_rotated", "v_padded", "gate_silu",
          "gate_bias_dropped", "beta_left_out")
STAND_INS = ("fp8_stream", "fp8_router_stream", "bf16_gamma", "bf16_state")
PLANTED = FAULTS + STAND_INS
#: arXiv:2412.19437 eqs. 17-20, the weight of the family's recipe (assumed;
#: the configuration file).  A ``seq_aux_weight`` in the dict handed to the
#: reference replaces it.
SEQ_AUX_WEIGHT = 1e-4
#: positions the ``bf16_gamma`` stand-in sums the decay over (the program's
#: chunk)
GAMMA_BLOCK = 128


def experts_name(i) -> str:
    return f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def layer_kinds(cfg: dict) -> list:
    """"kda" or "mla" per layer, from the two lists (counted from 1)."""
    lists = cfg["linear_attn_config"]
    kinds = ["kda" if i + 1 in lists["kda_layers"] else "mla"
             for i in range(cfg["num_hidden_layers"])]
    full = sorted(i + 1 for i, kind in enumerate(kinds) if kind == "mla")
    if full != sorted(lists["full_attn_layers"]):
        raise ValueError(
            f"kimi_linear_ref: kda_layers {lists['kda_layers']} and "
            f"full_attn_layers {lists['full_attn_layers']} do not divide "
            f"layers 1..{cfg['num_hidden_layers']} between them")
    return kinds


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier (XLA may keep the
    excess precision of a convert pair); the gradient passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (d, d + D/2)."""
    s, half = x.shape[1], x.shape[3] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _conv(x, w):
    """Depthwise causal convolution: x [B, S, C], w [K, C]; tap K - 1 meets
    position t itself; zeros before the sequence."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, k:k + s] * w[k] for k in range(taps))


def _delta_rule(q, k, v, g, beta, planted, scan_block=GAMMA_BLOCK):
    """q, k, v, g [B, S, H, D], beta [B, S, H] -> o [B, S, H, D]: the
    recurrence one position at a time, in checkpointed blocks of
    ``scan_block`` positions."""
    b, s, h, d = v.shape
    scan_block = min(scan_block, s)
    pad = -s % scan_block

    def step(carry, inputs):
        state, gamma = carry
        q_t, k_t, v_t, g_t, b_t = inputs  # [B, H, D] x 4, [B, H]
        if planted == "bf16_gamma":
            # the running sum of the block rounded; a step's decay is the
            # difference of two rounded sums
            summed = gamma + g_t
            decay = jnp.exp(_rounded(summed, jnp.bfloat16)
                            - _rounded(gamma, jnp.bfloat16))
            gamma = summed
        else:
            decay = jnp.exp(g_t)
        state = decay[..., None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        write = v_t - held
        if planted != "beta_left_out":
            write = b_t[..., None] * write
        state = state + k_t[..., :, None] * write[..., None, :]
        if planted == "bf16_state":
            state = _rounded(state, jnp.bfloat16)
        return (state, gamma), jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def block(state, inputs):
        (state, _), out = jax.lax.scan(
            step, (state, jnp.zeros((b, h, d), F32)), inputs)
        return state, out

    # a padded position has g = 0, beta = 0 and k = 0: it leaves the state
    blocks = lambda a: jnp.pad(  # noqa: E731
        jnp.moveaxis(a, 1, 0), ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    ).reshape((-1, scan_block) + a.shape[:1] + a.shape[2:])
    _, out = jax.lax.scan(
        block, jnp.zeros((b, h, k.shape[-1], d), F32),
        tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape((s + pad, b, h, d))[:s], 0, 1)


#: heads a checkpointed pass of a mixer computes at once (memory only)
HEAD_GROUP = 8


def _by_head_groups(fn, h: int, like, by_columns=(), by_rows=(), per_head=()):
    """``sum over groups of fn(slices of the group's heads)``: the leaves of
    a mixer cut into groups of :data:`HEAD_GROUP` heads — ``by_columns
    [.., h x]`` along the columns, ``by_rows [h x, ..]`` along the rows,
    ``per_head [h]`` — and ``fn(columns, rows, numbers) -> [B, S, C]``, the
    group's part of the mixer's output (every head's part of ``out_proj``'s
    or ``wo``'s product is a summand), run as a checkpointed scan so that
    one group's ``[B, S, heads, D]`` arrays exist at a time.  No value
    changes: the heads never meet but in that sum."""
    n = h // HEAD_GROUP if h % HEAD_GROUP == 0 else 1
    columns = tuple(jnp.moveaxis(
        w.reshape(w.shape[:-1] + (n, -1)), -2, 0) for w in by_columns)
    rows = tuple(w.reshape((n, -1) + w.shape[1:]) for w in by_rows)
    numbers = tuple(w.reshape(n, -1) for w in per_head)

    @jax.checkpoint
    def one(total, group):
        return total + fn(*group), None

    return jax.lax.scan(one, jnp.zeros_like(like),
                        (columns, rows, numbers))[0]


def _rule_operands(u, f_low, d, wq, wk, wv, conv_q, conv_k, conv_v, f_b,
                   dt_bias, w_beta, a_log):
    """What the delta rule of some heads takes, from the normed stream ``u``
    and the decay gate's low-rank half ``f_low = u f_a`` (every head's):
    ``q, k, v, g [B, S, heads, D]`` and ``beta [B, S, heads]``."""
    b, s, _ = u.shape
    heads = lambda x: x.reshape(b, s, -1, d)  # noqa: E731
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
    q, k, v = (heads(jax.nn.silu(_conv(u @ w, taps)))
               for w, taps in ((wq, conv_q), (wk, conv_k), (wv, conv_v)))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        heads(f_low @ f_b + dt_bias))
    return (unit(q) * d ** -0.5, unit(k), v, g, jax.nn.sigmoid(u @ w_beta))


#: the leaves of a KDA mixer that :func:`_rule_operands` takes a head
#: group's columns of, in its order (``A_log``, a number a head, comes last)
_OPERAND_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_b",
                   "dt_bias", "w_beta")


def _kda(u, kda, cfg):
    """The KDA mixer on the normed stream ``u [B, S, C]``."""
    planted = cfg.get("planted")
    b, s, _ = u.shape
    lin = cfg["linear_attn_config"]
    d = lin["head_dim"]
    # the two low-rank gates' first halves are every head's
    f_low, g_low = u @ kda["f_a"], u @ kda["g_a"]

    def group(columns, rows, numbers):
        *of_operands, g_b, g_bias = columns
        (out_proj,), (a_log,) = rows, numbers
        q, k, v, g, beta = _rule_operands(u, f_low, d, *of_operands, a_log)
        if planted == "decay_per_head":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        o = _delta_rule(q, k, v, g, beta, planted)
        z = g_low @ g_b
        if planted != "gate_bias_dropped":
            z = z + g_bias
        gate = (jax.nn.silu if planted == "gate_silu"
                else jax.nn.sigmoid)(z)
        y = _rms(o, kda["norm"], cfg["rms_norm_eps"]) * gate.reshape(o.shape)
        return y.reshape(b, s, -1) @ out_proj

    return _by_head_groups(
        group, lin["num_heads"], u,
        by_columns=[kda[name] for name in _OPERAND_LEAVES + ("g_b", "g_bias")],
        by_rows=[kda["out_proj"]], per_head=[kda["A_log"]])


#: positions under which :func:`rule_alone` gives no numbers: a head's RMS
#: over a toy sequence sums too few terms to hold a fifth decimal
RULE_ALONE_MIN_POSITIONS = 1024


def rule_alone(params, tokens, head_dim: int, eps: float, rule) -> dict:
    """THE RULE BY ITSELF, on operands both sides are handed alike: ``{name:
    the RMS of a head's output over the sequence}`` of ``rule(q, k, v, g,
    beta) -> o [B, S, heads, D]`` on the float32 operands of the FIRST
    layer's rule at ``tokens [B, S]`` (a KDA layer: its input is the
    embedding's rows, which nothing upstream has rounded), ``q``, ``k`` and
    ``v`` at the values bfloat16 holds — what the program's kernel is handed
    — a group of :data:`HEAD_GROUP` heads at a time.  The reference calls it
    with its recurrence, the adapter with the program's op; empty where the
    first layer is no KDA layer or the sequence is a toy's.

    Why it exists: in the model, what the rule keeps in float32 — the
    decay's running sum, the state — is worth 1 % of its output (the delta
    rule corrects an error of the state along every key it meets again), as
    much as the bf16 matmuls upstream of it, so no distance between a
    system and a reference that each compute their own operands tells a
    bfloat16 state from a float32 one.  On the same operands the kernel's
    own rounding is 0.35 % of ``o`` and either stand-in moves single heads'
    RMS ten times as far as that does (PERF.md section 6)."""
    layer = params["layers"][0]
    if "kda" not in layer or tokens.shape[1] < RULE_ALONE_MIN_POSITIONS:
        return {}
    kda = {k: jax.lax.stop_gradient(v.astype(F32))
           for k, v in layer["kda"].items()}
    n_heads = kda["A_log"].shape[0]
    # the operands at ``highest``; the rule at whatever precision it sets
    highest = jax.default_matmul_precision("highest")
    with highest:
        u = jax.lax.stop_gradient(_rms(
            params["embed"].astype(F32)[tokens], layer["ln1"].astype(F32),
            eps))
        f_low = u @ kda["f_a"]

    def group(columns, _, numbers):
        a_log, heads = numbers
        with highest:
            q, k, v, g, beta = _rule_operands(
                u, f_low, head_dim, *columns, a_log)
        q, k, v = (_rounded(a, jnp.bfloat16) for a in (q, k, v))
        o = rule(q, k, v, g, beta).astype(F32)
        # the group's heads' numbers at their places, zeros elsewhere
        return jnp.zeros((n_heads,), F32).at[heads.astype(int)].set(
            jnp.sqrt(jnp.mean(jnp.square(o), axis=(0, 1, 3))))

    rms = _by_head_groups(
        group, n_heads, jnp.zeros((n_heads,), F32),
        by_columns=[kda[name] for name in _OPERAND_LEAVES],
        per_head=[kda["A_log"], jnp.arange(n_heads, dtype=F32)])
    return {f"kda_rule_out_rms.{h}": rms[h] for h in range(n_heads)}


def _mla(u, layer, cfg, q_block=512):
    planted = cfg.get("planted")
    b, s, _ = u.shape
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    down = u @ layer["wkv_a"]
    c = _rms(down[..., :rank], layer["kv_a_norm"], cfg["rms_norm_eps"])
    k_pe = down[..., None, rank:]  # [B, S, 1, rope]: no position
    if planted == "k_pe_rotated":
        k_pe = _rope(k_pe, float(cfg["rope_theta"]))
    scale = 1.0 / jnp.sqrt(F32(nope + rope))
    q_block = min(q_block, s)
    rows_pad = -s % q_block

    def group(columns, rows, _):
        (wq, wkv_b), (wo,) = columns, rows
        q = (u @ wq).reshape(b, s, -1, nope + rope)
        h = q.shape[2]
        kv = (c @ wkv_b).reshape(b, s, h, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
        v = kv[..., nope:]
        if planted == "v_padded":
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + rope - vd),))

        @jax.checkpoint
        def some_rows(_, block):
            qb, first = block  # [B, q_block, h, D], the first position
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
            causal = jnp.arange(s)[None, :] <= (
                first + jnp.arange(q_block))[:, None]
            p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return None, jnp.einsum("bhqk,bkhd->bqhd", p, v)

        blocks = jnp.pad(
            q, ((0, 0), (0, rows_pad), (0, 0), (0, 0))).reshape(
                b, -1, q_block, h, nope + rope)
        _, out = jax.lax.scan(
            some_rows, None, (jnp.moveaxis(blocks, 1, 0),
                              jnp.arange(0, s + rows_pad, q_block)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s + rows_pad, -1)[:, :s]
        # (``v_padded``: the heads' columns not sliced back to v_head_dim)
        return out[..., :h * vd] @ wo

    return _by_head_groups(
        group, cfg["num_attention_heads"], u,
        by_columns=[layer["wq"], layer["wkv_b"]], by_rows=[layer["wo"]])


def _routed(y, moe, cfg, given):
    """y [B, S, d] -> (out, own choice, selection scores, balance term)."""
    n_exp, held = router_width(cfg), cfg["num_experts"]
    first = cfg.get("held_first", 0)
    top_k = cfg["num_experts_per_token"]
    into_router = y
    if cfg.get("planted") == "fp8_router_stream":
        into_router = _rounded(y, jnp.float8_e4m3fn)
    s = jax.nn.sigmoid(into_router @ moe["router"])
    select = s + moe["router_bias"]
    _, own = jax.lax.top_k(select, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]

    @jax.checkpoint
    def one_expert(out, e):
        return out + combine[..., first + e, None] * _swiglu(
            y, moe["wg"][e], moe["wi"][e], moe["wo"][e]), None

    # the held experts are ``held`` of the router's numbering from
    # ``first``; a pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    if not cfg.get("shared_left_out"):
        shared = moe["shared"]
        out = out + _swiglu(y, shared["w_gate"], shared["w_up"],
                            shared["w_down"])
    # per sequence: f over the k picks (all experts, held or not), P the
    # mean share of the score
    f = jnp.mean(jnp.sum(taken, 2), 1) * (n_exp / top_k)  # [B, E]
    p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), 1)  # [B, E]
    balance = jnp.mean(jnp.sum(f * p, -1))
    return out, own, select, balance


def _block(x, layer, kind, cfg, given):
    eps = cfg["rms_norm_eps"]
    u = _rms(x, layer["ln1"], eps)
    if cfg.get("planted") == "fp8_stream":
        u = _rounded(u, jnp.float8_e4m3fn)
    x = x + (_kda(u, layer["kda"], cfg) if kind == "kda"
             else _mla(u, layer, cfg))
    y = _rms(x, layer["ln2"], eps)
    if "moe" not in layer:
        mlp = layer["mlp"]
        return x + _swiglu(y, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]), None
    out, own, select, balance = _routed(y, layer["moe"], cfg, given)
    return x + out, (own, select, balance)


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
            f"kimi_linear_ref: unknown planted fault {planted!r}")
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, (layer, kind) in enumerate(
                zip(params["layers"], layer_kinds(cfg))):
            name = experts_name(i)
            pick = None if given is None else given.get(name)
            x, routed = jax.checkpoint(
                lambda x, layer, pick, kind=kind: _block(
                    x, layer, kind, cfg, pick))(x, layer, pick)
            if routed is not None:
                own, select, bal = routed
                extra["choices"][name], extra["probs"][name] = own, select
                balance = balance + bal
        hidden = _rms(x, params["ln_f"], cfg["rms_norm_eps"])
        nll = _mean_nll(hidden, params["lm_head"], tgt)
    # the balance term as it enters the loss, weight included
    extra["scalars"] = {"moe_seq_aux": cfg.get(
        "seq_aux_weight", SEQ_AUX_WEIGHT) * balance}

    def recurrence(*operands):
        with jax.default_matmul_precision("highest"):
            return _delta_rule(*operands, planted)

    extra["scalars"].update(rule_alone(
        params, inp, cfg["linear_attn_config"]["head_dim"],
        cfg["rms_norm_eps"], recurrence))
    return hidden, nll + extra["scalars"]["moe_seq_aux"], extra
