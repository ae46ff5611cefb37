"""Plain reference of the Trinity block (HF model type ``afmoe``; recalled
without a network, each line not carried by a ``config.json`` key listed in
the configuration file's ``assumed``) and its training loss, under ONE CHIP'S
SHARE of an 8-way expert-parallel layer.  Straightforward ``jax.numpy`` in
float32 at ``highest`` matmul precision: no kernels, no sort, no cache.
Independent of ``dlrover_tpu/``: it takes the same parameter tree (that is the
interface, not shared code) and HF key names for sizes; the masks, the rotary
table, the gate and the routing are written here from the formulas, not
imported.

``rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w`` over the last axis in
float32, a plain gain initialised 1; no bias anywhere; ``x [B, S, C]`` the
residual stream, ``h_0 = embed[t] * sqrt(hidden_size)`` (``mup_enabled``; no
other scalar, on branches or logits).  Layer ``i``, FOUR norms::

    a  = Attn_i(rms(x;  ln1))            input_layernorm
    x' = x  + rms(a; ln1_out)            post_attention_layernorm
    m  = MLP_i(rms(x'; ln2))             pre_mlp_layernorm
    y  = x' + rms(m; ln2_out)            post_mlp_layernorm

After the last layer one ``rms(., ln_f)``; ``logits = z @ lm_head`` (untied);
the loss is the mean next-token cross-entropy over the rows this chip's slice
of the vocabulary holds, and NOTHING else: ``load_balance_coeff`` is read as
the rate of the selection bias's rule, which is no term of the loss
(``assumed``).

``Attn_i``, for the normed ``u`` (``H`` query heads, ``KV`` key-value heads,
``head_dim`` D, a group of ``H / KV`` query heads on each key head)::

    q = u @ Wq [H, D],  g = u @ Wg [H, D],  k = u @ wk [KV, D],  v = u @ wv
        (the tree stores Wq and Wg as the halves of each head's ``[q | gate]``
        columns of ONE leaf ``wq [C, H * 2D]``: the same function)
    q = rms(q; q_norm),  k = rms(k; k_norm)     over EACH head's D dims, one
        gain of D for all query heads and one for all key heads, BEFORE any
        rotation
    layer_types[i] == "sliding_attention": q, k rotated over all D dims in
        pairs (j, j + D/2), ``inv_freq_j = rope_theta^(-j / (D/2))``, no
        scaling; position t attends the keys s with 0 <= t - s < sliding_window
    layer_types[i] == "full_attention": NO rotation; every s <= t
    causal softmax at D^-1/2
    Attn = (concat(heads) * sigmoid(g)) @ wo     the gate per ELEMENT of H*D

``MLP_i``: layers ``0 .. num_dense_layers - 1`` a SwiGLU ``down(silu(gate u) *
up u)`` at ``intermediate_size``.  Every later layer, in float32::

    s = sigmoid(u @ router)                 over all ``published.num_experts``
    T = the num_experts_per_tok largest of  s + b      (``router_bias``: no
        gradient, zeros at initialisation, added to the CHOICE only)
    w = s[T] / (sum(s[T]) + 1e-20) * route_scale        (``route_norm``)
    MLP = Shared(u) + sum_{e in T and HELD} w_e E_e(u)

each of ``Shared``, ``E_e`` a SwiGLU at ``moe_intermediate_size``;
``n_group = topk_group = 1``: no group limit; no capacity: no pair dropped.

The share: this chip HOLDS experts ``0 .. num_experts - 1`` (16 of 128),
computes those of a token's picks and leaves out what the absent experts
would add — BEFORE ``ln2_out``, which then norms the partial sum.  That is
the layer's output and goes on to the next layer, here as in the program.

Departures from the HF forward, for memory only and with no effect on any
value: every layer is a ``jax.checkpoint``; attention runs as a ``lax.map``
over blocks of query rows, each against the keys it can see (a window layer's
block reads ``q_block + sliding_window`` keys, a full layer's all) with the
mask written out; the held experts run one after another (a scan), each over
every token with the weight 0 where it was not chosen; the loss runs over
blocks of positions — so that ``jax.grad`` of this reference at 16,384
positions fits beside the training state on one chip.  The key heads are not
repeated: the einsum carries the group axis.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``harness/afmoe_probe.py``: each must read ``ok: false``): :data:`FAULTS`
and the lower-precision stand-ins of :data:`STAND_INS`.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's experts per
routed block, by name) it computes THOSE, weighted by its own float32 scores
of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]`` the
``s + b`` the choice was made from, ``extra["scalars"]``
:func:`window_alone`'s two numbers (the loss has no further term).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn

#: what ``cfg["planted"]`` may name: a full layer rotated; the window layers
#: left unrotated; the window one key short (``t - s < w - 1``); the gate
#: dropped; the gate one scalar a head (the mean of its logits) in place of
#: one per element; either output norm dropped; the selection bias added to
#: the weights too; ``route_scale`` dropped; the embedding's multiplier
#: dropped
FAULTS = ("full_rotated", "window_unrotated", "window_off_by_one",
          "gate_dropped", "gate_per_head", "post_attention_norm_dropped",
          "post_mlp_norm_dropped", "bias_in_weight", "route_scale_dropped",
          "embedding_multiplier_dropped")
#: the nearest precision below the stated one.  Of the stated bfloat16: the
#: normed stream entering every attention block and every MLP, or every
#: router alone, rounded to float8 e4m3.  Of the stated float32: bfloat16 in
#: the router (its input, its matmul and its sigmoid), the norms' statistics
#: and the rotary table
STAND_INS = ("fp8_stream", "fp8_router_stream", "bf16_stated_f32")
PLANTED = FAULTS + STAND_INS

SLIDING, FULL = "sliding_attention", "full_attention"
#: added to the sum of the chosen scores (assumed: the family's convention)
ROUTE_NORM_EPS = 1e-20


def experts_name(i: int) -> str:
    return f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``num_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["num_experts"]


def _rms(x, w, eps, low=False):
    """Over the last axis; ``low``: the statistics in bfloat16 (a
    stand-in)."""
    if low:
        xl = x.astype(BF16)
        var = jnp.mean(jnp.square(xl), axis=-1, keepdims=True)
        return (xl * jax.lax.rsqrt(var + BF16(eps))).astype(F32) * w
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _fp8(x):
    """``x`` at the values float8 e4m3 holds; behind a barrier: XLA
    otherwise keeps the convert pair's excess precision."""
    return jax.lax.optimization_barrier(x.astype(FP8)).astype(F32)


def rotary_table(theta: float, dim: int, seq: int, low=False) -> tuple:
    """``(cos, sin)``, ``[S, dim / 2]``; ``low``: everything in bfloat16 (a
    stand-in)."""
    dt = BF16 if low else F32
    half = dim // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(seq).astype(dt)[:, None] * inv.astype(dt)[None, :]
    return jnp.cos(ang).astype(F32), jnp.sin(ang).astype(F32)


def _rotated(x, table):
    """x [B, S, heads, D]: the pairs (j, j + D/2) turned by ``table``."""
    cos, sin = (t[None, :, None, :] for t in table)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _window(cfg, kind, planted) -> int:
    """The keys a layer of ``kind`` looks back over (0: all)."""
    window = (cfg.get("sliding_window") or 0) if kind == SLIDING else 0
    return window - (window > 0 and planted == "window_off_by_one")


def _attend(q, k, v, window: int, q_block: int):
    """Causal softmax attention at D^-1/2 of ``q [B, S, H, D]`` over ``k``,
    ``v [B, S, KV, D]``: position t attends the keys s with ``0 <= t - s``
    and, where ``window`` > 0, ``t - s < window``.  -> ``[B, S, H, D]``."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    # the keys a block of queries can see end with the block's last query:
    # all of the sequence, or a window and a block of them
    q_block = math.gcd(q_block, s)  # whole blocks, at most ``q_block`` rows
    span = min(s, q_block + window) if window else s
    front = ((0, 0), (span - q_block, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, front), jnp.pad(v, front)
    qs = jnp.moveaxis(q.reshape(b, s // q_block, q_block, kv, h // kv, d),
                      1, 0)
    firsts = jnp.arange(0, s, q_block)

    @jax.checkpoint
    def rows(block):
        qb, first = block  # [B, q_block, KV, G, D], the first query's t
        kb = jax.lax.dynamic_slice_in_dim(kp, first, span, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, first, span, 1)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * d ** -0.5
        t = first + jnp.arange(q_block)[:, None]
        at = first + q_block - span + jnp.arange(span)[None, :]
        back = t - at
        seen = (at >= 0) & (back >= 0)
        if window:
            seen &= back < window
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vb)

    out = jnp.moveaxis(jax.lax.map(rows, (qs, firsts)), 0, 1)
    return out.reshape(b, s, h, d)


def window_alone(window: int, seq: int, heads: tuple, attend) -> dict:
    """THE WINDOW BY ITSELF, on operands both sides are handed alike: what
    ``attend(q, k, v) -> o [1, S, H, D]`` (causal, the last ``window`` keys;
    ``heads`` = ``(H, KV, D)``) reads where q and k are zeros — every
    visible key then weighs the same — and v flags the positions ``s %
    window == 0``, the same in every channel.  A query ``t >= window - 1``
    sees exactly ONE flagged key among exactly ``window``: ``o = 1 /
    window``, a power of two at the cell's 2,048, exact in bfloat16 and in a
    float32 sum.  One key fewer and the queries whose oldest key is the
    flagged one read 0; one more and some read two.  ->
    ``{"window_alone_least": 1 + window * min o, "window_alone_most": window
    * max o}`` over those queries: 2 and 1.  The reference calls it with its
    masked softmax, the adapter with the program's flash op at the program's
    window.  Empty for a stack without a window layer (``window`` 0) and for
    a sequence inside one window.

    Why it exists: under the model's softmax one key in 2,048 is worth
    about 1 % of a branch's output, less than the bf16 matmuls upstream, so
    no distance between a system and a reference tells ``t - s < 2048``
    from ``t - s < 2047``."""
    if not 0 < window <= seq:
        return {}
    h, kv, d = heads
    flagged = (jnp.arange(seq) % window == 0).astype(F32)
    v = jnp.broadcast_to(flagged[None, :, None, None], (1, seq, kv, d))
    # behind a barrier: the operands are constants, and the compiler would
    # otherwise try to fold 16,384 positions of attention at compile time
    q, k, v = jax.lax.optimization_barrier((
        jnp.zeros((1, seq, h, d), F32), jnp.zeros((1, seq, kv, d), F32), v))
    o = attend(q, k, v).astype(F32)[:, window - 1:]
    return {"window_alone_least": 1.0 + window * jnp.min(o),
            "window_alone_most": window * jnp.max(o)}


def _attention(u, layer, cfg, kind, planted, q_block):
    b, s, _ = u.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, low = cfg["rms_norm_eps"], planted == "bf16_stated_f32"
    # each head's columns of the one leaf are [q | gate]
    qg = (u @ layer["wq"]).reshape(b, s, h, 2 * d)
    q, g = qg[..., :d], qg[..., d:]
    k = (u @ layer["wk"]).reshape(b, s, kv, d)
    v = (u @ layer["wv"]).reshape(b, s, kv, d)
    q = _rms(q, layer["q_norm"], eps, low)
    k = _rms(k, layer["k_norm"], eps, low)
    rotates = kind == SLIDING
    if planted == "full_rotated":
        rotates = True
    elif planted == "window_unrotated":
        rotates = False
    if rotates:
        table = rotary_table(cfg["rope_theta"], d, s, low)
        q, k = _rotated(q, table), _rotated(k, table)
    out = _attend(q, k, v, _window(cfg, kind, planted), q_block)
    out = out.reshape(b, s, h, d)
    if planted == "gate_per_head":
        g = jnp.mean(g, -1, keepdims=True)
    if planted != "gate_dropped":
        out = out * jax.nn.sigmoid(g)
    return out.reshape(b, s, h * d) @ layer["wo"]


def _routed(u, moe, cfg, planted, given):
    """u [B, S, d] -> (out, own choice, the ``s + b`` chosen from)."""
    width, top_k = router_width(cfg), cfg["num_experts_per_tok"]
    held = cfg["num_experts"]
    if planted == "bf16_stated_f32":
        s = jax.nn.sigmoid(
            u.astype(BF16) @ moe["router"].astype(BF16)).astype(F32)
    elif planted in ("fp8_router_stream", "fp8_stream"):
        s = jax.nn.sigmoid(_fp8(u) @ moe["router"])
    else:
        s = jax.nn.sigmoid(u @ moe["router"])
    select = s + moe["router_bias"]
    _, own = jax.lax.top_k(select, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(
        select if planted == "bias_in_weight" else s, chosen, -1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_NORM_EPS)
    if planted != "route_scale_dropped":
        w = w * cfg["route_scale"]
    taken = jax.nn.one_hot(chosen, width, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]
    if planted == "fp8_stream":
        u = _fp8(u)

    @jax.checkpoint
    def expert(out, e):
        return out + combine[..., e, None] * _swiglu(
            u, moe["wg"][e], moe["wi"][e], moe["wo"][e]), None

    # the held experts are the router's first ``held``, one after another;
    # a pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(held))
    shared = moe["shared"]
    out = out + _swiglu(u, shared["w_gate"], shared["w_up"],
                        shared["w_down"])
    return out, own, select


def _mean_nll(x, lm_head, targets, block=2048):
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


def hidden_and_loss(params, tokens, cfg: dict, given=None, q_block=64):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, loss,
    extra).  ``cfg``: HF keys, ``num_experts`` the experts held here and
    ``published.num_experts`` the router's width."""
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(f"afmoe_ref: unknown planted fault {planted!r}")
    if len(cfg["layer_types"]) != len(params["layers"]) or (
            set(cfg["layer_types"]) - {SLIDING, FULL}):
        raise ValueError(
            f"afmoe_ref: layer_types {cfg['layer_types']} is not one of "
            f"{(SLIDING, FULL)} for each of {len(params['layers'])} layers")
    eps, low = cfg["rms_norm_eps"], planted == "bf16_stated_f32"
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        if cfg["mup_enabled"] and planted != "embedding_multiplier_dropped":
            x = x * math.sqrt(cfg["hidden_size"])
        for i, layer in enumerate(params["layers"]):
            kind = cfg["layer_types"][i]
            routed = i >= cfg["num_dense_layers"]
            name = experts_name(i)

            @jax.checkpoint
            def block(x, layer, pick, kind=kind, routed=routed):
                u = _rms(x, layer["ln1"], eps, low)
                if planted == "fp8_stream":
                    u = _fp8(u)
                a = _attention(u, layer, cfg, kind, planted, q_block)
                if planted != "post_attention_norm_dropped":
                    a = _rms(a, layer["ln1_out"], eps, low)
                x = x + a
                u = _rms(x, layer["ln2"], eps, low)
                own = select = None
                if routed:
                    m, own, select = _routed(
                        u, layer["moe"], cfg, planted, pick)
                else:
                    if planted == "fp8_stream":
                        u = _fp8(u)
                    mlp = layer["mlp"]
                    m = _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
                if planted != "post_mlp_norm_dropped":
                    m = _rms(m, layer["ln2_out"], eps, low)
                return x + m, own, select

            x, own, select = block(
                x, layer, None if given is None or not routed
                else given[name])
            if routed:
                extra["choices"][name], extra["probs"][name] = own, select
        x = _rms(x, params["ln_f"], eps, low)
        nll = _mean_nll(x, params["lm_head"], tgt)
        # the window as the file states it; what ``attend`` masks by is the
        # reference's own (one key short where that is planted)
        stated = (cfg.get("sliding_window") or 0
                  if SLIDING in cfg["layer_types"] else 0)
        extra["scalars"] = window_alone(
            stated, inp.shape[1],
            (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["head_dim"]),
            lambda q, k, v: _attend(
                q, k, v, _window(cfg, SLIDING, planted), q_block))
    return x, nll, extra
