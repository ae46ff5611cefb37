"""Plain reference of the Mellum 2 block (HF model type ``mellum``: Qwen3-MoE's
block with the two per-layer lists of transformers v5, ``layer_types`` and
``mlp_layer_types``, and ``rope_parameters`` keyed by layer type; recalled
without a network) and its training loss, under ONE CHIP'S SHARE of an 8-way
expert-parallel layer.  Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no kernels, no sort, no cache.  Independent of
``dlrover_tpu/``: it takes the same parameter tree (that is the interface,
not shared code) and HF key names for sizes; the masks, the rotary tables and
the routing are written here from the formulas, not imported.

``rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w`` over the last axis in
float32, a plain gain initialised 1; no bias anywhere; ``x [B, S, C]`` the
residual stream.  Layer ``i``::

    h = x + Attn_i(rms(x; ln1))
    y = h + MoE(rms(h; ln2))

After the last layer one ``rms(., ln_f)``; ``logits = z @ lm_head`` (untied);
the loss is the mean next-token cross-entropy over the rows this chip's slice
of the vocabulary holds, plus ``router_aux_loss_coef`` (assumed 0.001) times
the routers' balance terms summed over the layers.

``Attn_i``, for the normed ``u`` (``H`` query heads, ``KV`` key-value heads,
``head_dim`` D, a group of ``H / KV`` query heads on each key head)::

    q = u @ wq [H, D],  k = u @ wk [KV, D],  v = u @ wv [KV, D]
    q = rms(q; q_norm),  k = rms(k; k_norm)     over EACH head's D dims, one
        gain of D for all query heads and one for all key heads, BEFORE the
        rotation (Qwen3's convention; no config key: ``assumed``)
    q, k <- rotated over all D dims in pairs (j, j + D/2) by the table of
        the layer's type (below)
    position t attends the keys s with  0 <= t - s < w   where
        layer_types[i] == "sliding_attention" (w = sliding_window; HF's mask
        ``s > t - w``), and every  0 <= t - s  where it is "full_attention"
    causal softmax at D^-1/2;  Attn = concat(heads) @ wo

The table of a layer type, from ``rope_parameters[type]``: ``f_j =
rope_theta^(-j / (D/2))``, j = 0 .. D/2 - 1.  ``rope_type: default``:
``inv_freq = f``, cos and sin as they are.  ``rope_type: yarn`` (HF
``_compute_yarn_parameters``): ``d(n) = D ln(original_max_position_embeddings
/ (2 pi n)) / (2 ln rope_theta)``, ``low = floor(d(beta_fast))``, ``high =
ceil(d(beta_slow))`` clipped to [0, D - 1], ``ramp_j = clip((j - low) / (high
- low), 0, 1)``, ``inv_freq_j = f_j / factor * ramp_j + f_j * (1 - ramp_j)``,
and cos AND sin both times ``attention_factor``: a full layer's scores carry
its square.  Angles are ``position * inv_freq`` in float32 from integer
positions.

``MoE`` (every layer: ``mlp_layer_types`` all ``sparse``): ``p = softmax(u @
router)`` over all ``published.num_experts`` in float32; the
``num_experts_per_tok`` largest; divided by their sum (``norm_topk_prob``);
an expert is ``down(silu(gate u) * up u)``; no shared expert::

    MoE = sum_{e in T and HELD} p_e E_e(u)

The share: this chip HOLDS experts ``0 .. num_experts - 1`` (8 of 64),
computes those of a token's picks and leaves out what the absent experts
would add.  That partial result is the layer's output and goes on to the next
layer, here as in the program.  The balance term is over the router's whole
width: ``E sum_e f_e P_e`` with ``f_e`` the mean over tokens and the k picks,
``P_e`` the mean probability (HF ``load_balancing_loss_func``, per layer and
summed, as ``reference/olmoe_ref.py`` explains).

Departures from the HF forward, for memory only and with no effect on any
value: every layer is a ``jax.checkpoint``; attention runs as a
``lax.map`` over blocks of query rows, each against the keys it can see (a
window layer's block reads ``q_block + sliding_window`` keys, a full layer's
all) with the mask written out; the held experts run one after another (a scan), each
over every token with the weight 0 where it was not chosen; the loss runs
over blocks of positions — so that ``jax.grad`` of this reference at 16,384
positions fits beside the training state on one chip.  The key heads are
not repeated: the einsum carries the group axis.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``harness/mellum_probe.py``: each must read ``ok: false``): :data:`FAULTS`
and the lower-precision stand-in of :data:`STAND_INS`.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's experts per
routed block, by name) it computes THOSE, weighted by its own float32
probabilities of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]``
the softmax the choice was made from, ``extra["scalars"]`` the balance term
as it enters the loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
#: assumed (Qwen3-MoE's default; the row has no key): see the configuration
#: file's ``assumed``
ROUTER_AUX_LOSS_COEF = 0.001

#: what ``cfg["planted"]`` may name: the window put on the full layers too;
#: the window layers left full; the plain table on the full layers; cos and
#: sin of the full layers not multiplied by ``attention_factor``; the
#: per-head q/k norms left out; the pairs of the LAST held expert dropped
FAULTS = ("window_on_full", "window_left_full", "plain_table_on_full",
          "attention_factor_dropped", "qk_norm_dropped", "expert_dropped")
#: the nearest precision below the stated one: bfloat16 where the file says
#: float32 — the router (its input, its matmul and its softmax), the norms'
#: statistics and the rotary tables (frequencies, angles, cos and sin)
STAND_INS = ("bf16_stated_f32",)
PLANTED = FAULTS + STAND_INS

SLIDING, FULL = "sliding_attention", "full_attention"


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


def yarn_range(rope: dict, dim: int) -> tuple:
    """``(low, high)`` of HF's ``_compute_yarn_parameters``."""
    def d(rotations):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    return (max(math.floor(d(rope["beta_fast"])), 0),
            min(math.ceil(d(rope["beta_slow"])), dim - 1))


def rotary_table(rope: dict, dim: int, seq: int, low=False) -> tuple:
    """``(cos, sin)``, ``[S, dim / 2]``, of one layer type's
    ``rope_parameters`` entry; ``low``: everything in bfloat16 (a
    stand-in)."""
    dt = BF16 if low else F32
    half = dim // 2
    j = jnp.arange(half, dtype=F32)
    inv = float(rope["rope_theta"]) ** (-j / half)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        lo, hi = yarn_range(rope, dim)
        ramp = jnp.clip((j - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
        inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"mellum_ref: rope_type {rope['rope_type']!r}")
    ang = (jnp.arange(seq).astype(dt)[:, None] * inv.astype(dt)[None, :])
    return ((jnp.cos(ang) * dt(scale)).astype(F32),
            (jnp.sin(ang) * dt(scale)).astype(F32))


def _rotated(x, table):
    """x [B, S, heads, D]: the pairs (j, j + D/2) turned by ``table``."""
    cos, sin = (t[None, :, None, :] for t in table)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, layer, cfg, kind, planted, q_block):
    b, s, _ = u.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, low = cfg["rms_norm_eps"], planted == "bf16_stated_f32"
    q = (u @ layer["wq"]).reshape(b, s, h, d)
    k = (u @ layer["wk"]).reshape(b, s, kv, d)
    v = (u @ layer["wv"]).reshape(b, s, kv, d)
    if planted != "qk_norm_dropped":
        q = _rms(q, layer["q_norm"], eps, low)
        k = _rms(k, layer["k_norm"], eps, low)
    rope = dict(cfg["rope_parameters"][kind])
    if kind == FULL and planted == "plain_table_on_full":
        rope = cfg["rope_parameters"][SLIDING]
    if kind == FULL and planted == "attention_factor_dropped":
        rope["attention_factor"] = 1.0
    table = rotary_table(rope, d, s, low)
    q, k = _rotated(q, table), _rotated(k, table)
    window = cfg.get("sliding_window") or 0
    if (kind == FULL and planted != "window_on_full") or (
            kind == SLIDING and planted == "window_left_full"):
        window = 0
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
    return out.reshape(b, s, h * d) @ layer["wo"]


def _routed(u, moe, cfg, planted, given):
    """u [B, S, d] -> (out, own choice, probs, balance term)."""
    width, top_k = router_width(cfg), cfg["num_experts_per_tok"]
    held = cfg["num_experts"]
    if planted == "bf16_stated_f32":
        probs = jax.nn.softmax(
            u.astype(BF16) @ moe["router"].astype(BF16), -1).astype(F32)
    else:
        probs = jax.nn.softmax(u @ moe["router"], -1)
    _, own = jax.lax.top_k(probs, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(probs, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    taken = jax.nn.one_hot(chosen, width, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]

    @jax.checkpoint
    def expert(out, e):
        gate = jax.nn.silu(u @ moe["wg"][e])
        return out + combine[..., e, None] * (
            (gate * (u @ moe["wi"][e])) @ moe["wo"][e]), None

    # the held experts are the router's first ``held``, one after another
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        jnp.arange(held - (planted == "expert_dropped")))
    balance = width * jnp.sum(
        jnp.mean(taken, (0, 1, 2)) * jnp.mean(probs, (0, 1)))
    return out, own, probs, balance


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
        raise ValueError(f"mellum_ref: unknown planted fault {planted!r}")
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("mellum_ref: every layer is routed (``sparse``), "
                         f"not {cfg['mlp_layer_types']}")
    eps, low = cfg["rms_norm_eps"], planted == "bf16_stated_f32"
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, layer in enumerate(params["layers"]):
            kind = cfg["layer_types"][i]
            name = experts_name(i)

            @jax.checkpoint
            def block(x, layer, pick, kind=kind):
                h = x + _attention(_rms(x, layer["ln1"], eps, low), layer,
                                   cfg, kind, planted, q_block)
                out, own, probs, bal = _routed(
                    _rms(h, layer["ln2"], eps, low), layer["moe"], cfg,
                    planted, pick)
                return h + out, own, probs, bal

            x, own, probs, bal = block(
                x, layer, None if given is None else given[name])
            extra["choices"][name], extra["probs"][name] = own, probs
            balance = balance + bal
        x = _rms(x, params["ln_f"], eps, low)
        nll = _mean_nll(x, params["lm_head"], tgt)
    extra["scalars"] = {
        "moe_aux":
            cfg.get("router_aux_loss_coef", ROUTER_AUX_LOSS_COEF) * balance}
    return x, nll + extra["scalars"]["moe_aux"], extra
