"""Plain reference of the Phi-4-mini-flash-reasoning block (HF model type
``phi4flash``; the architecture is SambaY, arXiv:2507.06607, its attention
differential, arXiv:2410.05258, its state-space layers Mamba-1,
arXiv:2312.00752) and its training loss; recalled without a network, each
line not carried by a ``config.json`` key listed in the configuration file's
``assumed``.  Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no chunked scan, no cache.  Independent of
``dlrover_tpu/``: it takes the same parameter tree (that is the interface,
not shared code) and HF key names for sizes.

The stream: ``h_0 = embed[t]`` (no scalar).  Layer ``l`` of the model::

    h'  = h  + Mix_l(LN(h;  ln1))            input_layernorm
    h'' = h' + MLP(LN(h'; ln2))              post_attention_layernorm

``LN(x; g, b) = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g + b``;
after the last layer ``LN(., ln_f)``, then the head ``embed^T`` (tied, no
bias, no scaling); the loss is the mean next-token cross-entropy over the
rows this chip's slice of the vocabulary holds, and nothing else.  No rotary
or other position in any layer.  ``MLP(u) = (silu(u w_gate) * (u w_up))
w_down`` (the source's ``fc1`` is ``[w_gate | w_up]``, gate first), no bias.

The kind of a kept layer follows from its PUBLISHED index ``l``
(``assumed.values.published_layers`` of the configuration file) by the
published rule (:func:`kind_of`): even ``l`` a Mamba-family mixer, odd ``l``
an attention-family one; ``l < 16`` Mamba-1 / window attention, 16 the
Mamba-1 whose scan output is the MEMORY, 17 the full attention whose keys and values are SHARED, ``l
>= 18`` even a GMU, odd cross-attention).

``"mamba1"`` / ``"mamba1_memory"`` (``d_inner`` 2 x hidden, ``d_state`` 16,
``d_conv`` 4, ``dt_rank`` hidden / 16), for the normed ``u``::

    [x | z] = u in_proj                         no bias
    x = silu(conv(x) + conv_b)                  causal, depthwise: t-3 .. t
    [delta | B | C] = x x_proj                  dt_rank | d_state | d_state
    dt = softplus(delta dt_proj + dt_bias)      a channel
    s_t = exp(dt_t (x) A) o s_{t-1} + (dt_t o x_t) (x) B_t,  A = -exp(A_log)
    y_t = s_t C_t + D o x_t                     s_0 = 0
    out = (y o silu(z)) out_proj

one position at a time (a ``lax.scan``).  The memory is ``M = y``: with the
``D`` skip, BEFORE the gate.  ``"gmu"``: ``out = (M o silu(u in_proj))
out_proj``, no bias.

``"window"`` / ``"full_kv"`` (differential attention; ``H`` query heads, ``KV``
key-value heads of ``D = hidden / H``)::

    q = u wq + bq,  k = u wk + bk,  v = u wv + bv    (the source's one Wqkv)
    pairs: (q1, q2) = query heads (2p, 2p + 1); (k1, k2) = key heads (2r, 2r +
        1); v_r = [value head 2r | value head 2r + 1] (2 D wide); query pair
        p reads key/value pair r = p // (H / KV)
    A_i = softmax(q_i k_i^T / sqrt(D) + mask)
    lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2)
             + lambda_init(l),   lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
        with l the PUBLISHED layer index (``published_layers`` of the file)
    o = (A_1 - lambda A_2) v_r
    o = o / sqrt(mean(o^2) + layer_norm_eps) * subln * (1 - lambda_init(l))
    out = concat(pairs) wo + bo

mask: causal, and in ``"window"`` layers ``0 <= t - s < sliding_window``.
``"cross"``: ``q = u wq + bq`` with the layer's own lambda vectors, ``subln``,
``wo`` and ``bo``; ``k``, ``v`` are the ``"full_kv"`` layer's, as computed
there; every ``s <= t``.

Departures, for memory only and with no effect on any value: every layer is
a ``jax.checkpoint``; the recurrence runs in checkpointed blocks of
``scan_block`` positions; attention as a ``lax.map`` over blocks of query
rows against all keys with the mask written out; the MLP and the loss over
blocks of positions — so that ``jax.grad`` of this reference at 16,384
positions fits beside the training state on one chip.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``harness/phi4flash_probe.py``: each must read ``ok: false``): the faults of
:data:`FAULTS` and the lower-precision stand-in of :data:`STAND_INS`.

It returns ``(hidden, loss, extra)``: this block makes no discrete choice, so
``extra["choices"]`` and ``extra["probs"]`` are empty, and ``extra["scalars"]``
holds what crosses layers as the harness can compare it — the root mean
squares of the memory and of the shared keys and values — the scan ALONE
(:func:`scan_alone`) and the window's edge ALONE (:func:`window_alone`;
``given`` is taken and ignored).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16

#: what ``cfg["planted"]`` may name: the memory taken after the gate; the
#: memory without the ``D`` skip; a cross layer attending keys and values
#: projected from ITS OWN input (by the shared layer's matrices);
#: ``lambda_init`` by the cut's index in place of the published one; ``subln`` dropped; ``(1 -
#: lambda_init)`` dropped; the window one key short; RMS (no mean, no bias) in
#: place of LayerNorm; the attention biases dropped; ``B`` and ``C`` swapped;
#: ``dt_bias`` dropped; the GMU's ``silu`` a sigmoid
FAULTS = ("memory_after_gate", "memory_without_D", "cross_on_own_kv",
          "lambda_init_by_cut_index", "subln_dropped",
          "one_minus_lambda_init_dropped", "window_off_by_one",
          "rms_for_layernorm", "attention_bias_dropped", "B_C_swapped",
          "dt_bias_dropped", "gmu_sigmoid")
#: the nearest precision below the stated float32 of the scan: the state and
#: each step's decay rounded to bfloat16
STAND_INS = ("bf16_scan_state",)
PLANTED = FAULTS + STAND_INS

MAMBA, MAMBA_MEMORY, WINDOW, FULL_KV, GMU, CROSS = (
    "mamba1", "mamba1_memory", "window", "full_kv", "gmu", "cross")
KINDS = (MAMBA, MAMBA_MEMORY, WINDOW, FULL_KV, GMU, CROSS)


def kind_of(published_layer: int) -> str:
    """The published model's kind of layer at an index (``mb_per_layer`` 2;
    the hinge at 16 and 17)."""
    mamba = published_layer % 2 == 0
    if published_layer < 16:
        return MAMBA if mamba else WINDOW
    if published_layer <= 17:
        return MAMBA_MEMORY if mamba else FULL_KV
    return GMU if mamba else CROSS


def lambda_init(published_layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published_layer)


def scalars_of(memory, shared_k, shared_v) -> dict:
    """What crosses layers, as scalars the harness compares."""
    rms = lambda a: jnp.sqrt(jnp.mean(jnp.square(a.astype(F32))))  # noqa: E731
    return {"memory_rms": rms(memory), "shared_k_rms": rms(shared_k),
            "shared_v_rms": rms(shared_v)}


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier; the gradient
    passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _norm(x, leaf, eps, planted):
    if planted == "rms_for_layernorm":
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * leaf["gain"]
    x = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * leaf["gain"] + leaf["bias"]


def _block(s: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides ``s``."""
    return max(b for b in range(1, min(want, s) + 1) if s % b == 0)


def _by_blocks(fn, x, block):
    """``fn`` over blocks of ``x``'s rows, each a ``jax.checkpoint``."""
    s = x.shape[0]
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(s // block, block, -1))
    return out.reshape(s, -1)


def _conv(x, w, b):
    """x [S, C], w [K, C]: out_t = sum_k w[k] x[t - (K - 1) + k] + b, zeros
    before the sequence."""
    taps, s = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[k:k + s] * w[k] for k in range(taps)) + b


def _recurrence(x, dt, a, b, c, scan_block, planted):
    """x, dt [S, Dn], a [Dn, N], b, c [S, N] -> ``s_t C_t`` [S, Dn], one
    position at a time, in checkpointed blocks of ``scan_block``."""
    s, dn = x.shape
    pad = -s % scan_block
    low = planted == "bf16_scan_state"

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t[:, None] * a)
        if low:
            decay = _rounded(decay, BF16)
        state = decay * state + (dt_t * x_t)[:, None] * b_t[None, :]
        if low:
            state = _rounded(state, BF16)
        return state, state @ c_t

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    blocks = lambda v: jnp.pad(  # noqa: E731 - a padded step (dt 0) is a no-op
        v, ((0, pad), (0, 0))).reshape(-1, scan_block, v.shape[1])
    _, y = jax.lax.scan(block, jnp.zeros(a.shape, F32),
                        (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return y.reshape(s + pad, dn)[:s]


def _scan_operands(u, s6, planted=None):
    """One sequence's normed stream ``u [S, d]`` -> ``(x, z, dt, A, B, C)``
    of its scan."""
    n = s6["A_log"].shape[1]
    rank = s6["dt_proj"].shape[0]
    inner = s6["D"].shape[0]
    xz = u @ s6["in_proj"]
    x, z = xz[:, :inner], xz[:, inner:]
    x = jax.nn.silu(_conv(x, s6["conv_w"], s6["conv_b"]))
    dbc = x @ s6["x_proj"]
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    if planted == "B_C_swapped":
        b, c = c, b
    dt = dbc[:, :rank] @ s6["dt_proj"]
    if planted != "dt_bias_dropped":
        dt = dt + s6["dt_bias"]
    return x, z, jax.nn.softplus(dt), -jnp.exp(s6["A_log"]), b, c


#: groups of channels :func:`scan_alone` reads a root mean square of
SCAN_ALONE_GROUPS = 16


def scan_alone(params, tokens, eps: float, scan) -> dict:
    """THE SCAN BY ITSELF, on operands both sides are handed alike: ``{name:
    the RMS over the sequence of a group of channels' output}`` of ``scan(x,
    dt, A, B, C) -> s_t C_t [B, S, Dn]`` (NO ``D`` skip) on the float32
    operands of the FIRST layer's scan at ``tokens [B, S]`` (a Mamba-1 layer:
    its input is the embedding's rows, which nothing upstream has rounded),
    ``x``, ``B`` and ``C`` at the values bfloat16 holds — what the program's
    op is handed.  The reference calls it with its recurrence, the adapter
    with the program's op; empty where the first layer is no Mamba-1 layer.

    Why it exists: in the model ``y = s C + D x`` with ``D`` 1, and at seeded
    weights the state's part is a hundredth of ``y``: no distance between a
    system and a reference that each compute their own operands tells a
    bfloat16 state or decay from a float32 one (the stand-in moves the
    reference's own hidden states by 3e-7 at toy widths).  Without the skip
    and on the same operands, a decay rounded to bfloat16 — ``exp(dt A)``
    near 1 has 2^-9 to round to, a time constant of its own — moves whole
    channels' output."""
    layer = params["layers"][0]
    if "s6" not in layer:
        return {}
    s6 = {k: jax.lax.stop_gradient(v.astype(F32))
          for k, v in layer["s6"].items()}
    with jax.default_matmul_precision("highest"):
        u = jax.lax.stop_gradient(_norm(
            params["embed"].astype(F32)[tokens],
            {k: v.astype(F32) for k, v in layer["ln1"].items()}, eps, None))
        x, _, dt, a, b, c = jax.vmap(
            lambda seq: _scan_operands(seq, s6), out_axes=(0, 0, 0, None, 0,
                                                            0))(u)
    x, b, c = (_rounded(v, BF16) for v in (x, b, c))
    y = scan(x, dt, a, b, c).astype(F32)
    groups = y.reshape(y.shape[:2] + (SCAN_ALONE_GROUPS, -1))
    rms = jnp.sqrt(jnp.mean(jnp.square(groups), axis=(0, 1, 3)))
    return {f"s6_scan_out_rms.{g}": rms[g]
            for g in range(SCAN_ALONE_GROUPS)}


def _visible(qpos, kpos, window: int):
    """Which keys ``kpos [K]`` the queries ``qpos [Q]`` attend: ``0 <= t -
    s`` and, where ``window`` > 0, ``t - s < window`` -> bool ``[Q, K]``."""
    back = qpos[:, None] - kpos[None, :]
    return (back >= 0) & (back < window) if window else back >= 0


def window_alone(window: int, seq: int, heads: tuple, attend) -> dict:
    """THE WINDOW BY ITSELF, on operands both sides are handed alike: what
    ``attend(q, k, v) -> o [1, S, H, Dv]`` (causal, the last ``window`` keys;
    ``heads`` = ``(H, KV, D, Dv)``) reads where q and k are zeros — every
    visible key then weighs the same — and v flags the positions ``s %
    window == 0``, the same in every channel.  A query ``t >= window - 1``
    sees exactly ONE flagged key among exactly ``window``: ``o = 1 /
    window``, a power of two at the cell's 512, exact in bfloat16 and in a
    float32 sum.  One key fewer and the queries whose oldest key is the
    flagged one read 0; one more and some read two.  ->
    ``{"window_alone_least": 1 + window * min o, "window_alone_most": window
    * max o}`` over those queries: 2 and 1.  The reference calls it with its
    masked softmax, the adapter with the program's flash op at the program's
    window and the widths a window layer hands it (64-wide q and k under
    128-wide v).  Empty for a stack without a window layer and for a
    sequence inside one window.

    Why it exists: under the model's softmax one key in 512 is worth 0.2 %
    of a branch's output, less than the bf16 matmuls upstream (the window one
    key short reads ``ok`` on the chip: hidden states 2.7 % away, the worst
    leaf 5.8 %), so no distance between a system and a reference tells ``t -
    s < 512`` from ``t - s < 511``."""
    if not 0 < window <= seq:
        return {}
    h, kv, d, dv = heads
    flagged = (jnp.arange(seq) % window == 0).astype(F32)
    v = jnp.broadcast_to(flagged[None, :, None, None], (1, seq, kv, dv))
    # behind a barrier: the operands are constants, and the compiler would
    # otherwise try to fold 16,384 positions of attention at compile time
    q, k, v = jax.lax.optimization_barrier((
        jnp.zeros((1, seq, h, d), F32), jnp.zeros((1, seq, kv, d), F32), v))
    o = attend(q, k, v).astype(F32)[:, window - 1:]
    return {"window_alone_least": 1.0 + window * jnp.min(o),
            "window_alone_most": window * jnp.max(o)}


def _attend_plain(q, k, v, window: int, q_block: int):
    """Plain causal GQA of ``q [1, S, H, D]`` over ``k [1, S, KV, D]``, ``v
    [1, S, KV, Dv]`` under this file's mask, for :func:`window_alone`."""
    _, s, h, d = q.shape
    kv = k.shape[2]
    q = q[0].reshape(s, kv, h // kv, d)
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        scores = jnp.einsum("qrgd,krd->rgqk", qb, k[0]) / math.sqrt(d)
        ok = _visible(start + jnp.arange(q_block), kpos, window)
        probs = jax.nn.softmax(
            jnp.where(ok[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("rgqk,krd->qrgd", probs, v[0])

    o = jax.lax.map(one_block, jnp.arange(0, s, q_block))
    return o.reshape(1, s, h, -1)


def _mamba(u, s6, cfg, planted, scan_block):
    """One sequence's normed stream u [S, d] -> (out [S, d], memory [S,
    d_inner])."""
    x, z, dt, a, b, c = _scan_operands(u, s6, planted)
    y = _recurrence(x, dt, a, b, c, min(scan_block, u.shape[0]), planted)
    skipped = y + s6["D"] * x
    gated = skipped * jax.nn.silu(z)
    memory = {"memory_after_gate": gated,
              "memory_without_D": y}.get(planted, skipped)
    return gated @ s6["out_proj"], memory


def _projected(u, layer, name, planted):
    out = u @ layer["w" + name]
    if planted != "attention_bias_dropped":
        out = out + layer["b" + name]
    return out


def _attention(u, layer, kv, window, lam_init, cfg, planted, q_block):
    """Differential attention of one sequence: ``u [S, d]`` the normed
    stream (queries), ``kv = (k, v) [S, KV * D]`` each."""
    s = u.shape[0]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    r, group = kvh // 2, h // kvh  # key/value pairs; query pairs on each
    eps = cfg["layer_norm_eps"]
    # [S, kv pair, query pair of it, first or second head, D]
    q = _projected(u, layer, "q", planted).reshape(s, r, group, 2, d)
    k = kv[0].reshape(s, r, 2, d)
    v = kv[1].reshape(s, r, 2 * d)
    lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
           - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
           + lam_init)
    kpos = jnp.arange(s)
    if planted == "window_off_by_one" and window:
        window = window - 1

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        ok = _visible(start + jnp.arange(q_block), kpos, window)
        probs = []
        for i in (0, 1):
            scores = jnp.einsum("qrgd,krd->rgqk", qb[:, :, :, i],
                                k[:, :, i]) / math.sqrt(d)
            probs.append(jax.nn.softmax(
                jnp.where(ok[None, None], scores, -jnp.inf), -1))
        return jnp.einsum("rgqk,krd->qrgd", probs[0] - lam * probs[1], v)

    o = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, q_block))
    o = o.reshape(s, r * group, 2 * d)
    if planted != "subln_dropped":
        o = o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * layer["subln"]
    if planted != "one_minus_lambda_init_dropped":
        o = o * (1.0 - lam_init)
    out = o.reshape(s, -1) @ layer["wo"]
    return out if planted == "attention_bias_dropped" else out + layer["bo"]


def _mlp(u, mlp, block):
    def rows(ub):
        return (jax.nn.silu(ub @ mlp["w_gate"]) * (ub @ mlp["w_up"])
                ) @ mlp["w_down"]

    return _by_blocks(rows, u, block)


def _mean_nll(x, head, tgt, block):
    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    s = x.shape[0]
    nll = jax.lax.map(jax.checkpoint(one_block),
                      (x.reshape(s // block, block, -1),
                       tgt.reshape(s // block, block)))
    return jnp.mean(nll)


def hidden_and_loss(params, tokens, cfg: dict, given=None, q_block: int = 64,
                    scan_block: int = 128, row_block: int = 2048):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, mean loss,
    extra).  ``cfg``: the configuration file's dict (HF keys and
    ``assumed.values.published_layers``)."""
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(f"phi4flash_ref: unknown planted fault {planted!r}")
    published = cfg["assumed"]["values"]["published_layers"]
    kinds = [kind_of(index) for index in published]
    if len(published) != len(params["layers"]) or (
            not cfg["tie_word_embeddings"] or cfg["mb_per_layer"] != 2):
        raise ValueError(
            f"phi4flash_ref computes a tied head over {len(params['layers'])} "
            "layers, each with its published index (mb_per_layer 2), not "
            f"published_layers={published}")
    eps = cfg["layer_norm_eps"]
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)

    def one_sequence(seq):
        inp, tgt = seq[:-1], seq[1:]
        s = inp.shape[0]
        qb, rb = _block(s, q_block), _block(s, row_block)
        carried = {}

        def block(x, layer, carried, kind, index, cut_index):
            u = _norm(x, layer["ln1"], eps, planted)
            made = {}
            lam = lambda_init(cut_index if planted
                              == "lambda_init_by_cut_index" else index)
            if kind in (MAMBA, MAMBA_MEMORY):
                mixed, memory = _mamba(u, layer["s6"], cfg, planted,
                                       scan_block)
                if kind == MAMBA_MEMORY:
                    made["memory"] = memory
            elif kind == GMU:
                gate = u @ layer["gmu"]["in_proj"]
                gate = (jax.nn.sigmoid(gate) if planted == "gmu_sigmoid"
                        else jax.nn.silu(gate))
                mixed = (carried["memory"] * gate) @ layer["gmu"]["out_proj"]
            elif kind == CROSS:
                kv = carried["shared_kv"]
                if planted == "cross_on_own_kv":
                    kv = tuple(_projected(u, carried["shared_layer"], name,
                                          planted) for name in ("k", "v"))
                mixed = _attention(u, layer, kv, 0, lam, cfg, planted, qb)
            else:
                kv = tuple(_projected(u, layer, name, planted)
                           for name in ("k", "v"))
                if kind == FULL_KV:
                    made["shared_kv"] = kv
                mixed = _attention(
                    u, layer, kv,
                    cfg["sliding_window"] if kind == WINDOW else 0, lam, cfg,
                    planted, qb)
            x = x + mixed
            u = _norm(x, layer["ln2"], eps, planted)
            return x + _mlp(u, layer["mlp"], rb), made

        x = params["embed"][inp]
        for cut_index, (kind, index, layer) in enumerate(
                zip(kinds, published, params["layers"])):
            x, made = jax.checkpoint(block, static_argnums=(3, 4, 5))(
                x, layer, carried, kind, index, cut_index)
            carried = dict(carried, **made)
            if kind == FULL_KV:
                carried["shared_layer"] = {
                    name: layer[name]
                    for name in ("wk", "wv", "bk", "bv")}
        x = _norm(x, params["ln_f"], eps, planted)
        return (x, _mean_nll(x, params["embed"].T, tgt, rb),
                scalars_of(carried["memory"], *carried["shared_kv"]))

    with jax.default_matmul_precision("highest"):
        hidden, losses, scalars = jax.lax.map(one_sequence, tokens)
        scalars = jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.mean(jnp.square(a))), scalars)
        scalars.update(scan_alone(
            params, tokens[:, :-1], eps,
            lambda x, dt, a, b, c: jax.vmap(
                lambda xs, dts, bs, cs: _recurrence(
                    xs, dts, a, bs, cs, min(scan_block, xs.shape[0]),
                    planted))(x, dt, b, c)))
        # the window as the file states it; what the mask goes by is the
        # reference's own (one key short where that is planted)
        seq = tokens.shape[1] - 1
        stated = cfg["sliding_window"] if WINDOW in kinds else 0
        h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["hidden_size"] // h
        scalars.update(window_alone(
            stated, seq, (h, kvh, d, 2 * d),
            lambda q, k, v: _attend_plain(
                q, k, v, stated - (planted == "window_off_by_one"),
                _block(seq, q_block))))
    return hidden, jnp.mean(losses), {
        "choices": {}, "probs": {}, "scalars": scalars}
