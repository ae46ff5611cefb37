"""Plain reference of the GLM-4.7-Flash block (HF model type
``glm4_moe_lite``: the DeepSeek-V3 block at small widths) and its training
loss, under ONE CHIP'S SHARE of an 8-way expert-parallel layer.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  Independent of ``dlrover_tpu/models/llama.py``:
it takes the same parameter tree (that is the interface, not shared code)
and HF key names for sizes.

``rms(v, w) = v / sqrt(mean(v^2) + eps) * w`` over the last axis, no bias
anywhere, ``x [B, S, d]`` the residual stream.  One block::

    y   = rms(x, ln1)
    c_q = rms(y @ wq_a, q_a_norm)                    # 768
    [q_nope_i (192); q_rope_i (64)] = c_q @ wq_b     # per head i of 20
    [c_kv (512); k_rope (64)]       = y @ wkv_a
    c_kv = rms(c_kv, kv_a_norm)
    [k_nope_i (192); v_i (256)]     = c_kv @ wkv_b   # per head i
    q_i = [q_nope_i; rope(q_rope_i)]
    k_i = [k_nope_i; rope(k_rope)]     # the token's ONE k_rope, every head
    x   = x + concat_i softmax(causal(q_i k_i^T / sqrt(256))) v_i @ wo
    y   = rms(x, ln2)
    x   = x + FFN(y)

``rope`` turns the pairs ``(j, j + 32)`` of the 64 rotary dims by
``pos * theta^(-j/32)`` (HF ``rotate_half``; assumed, see the configuration
file).  ``FFN`` is SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and the routed block in every later one::

    s   = sigmoid(y @ router)                        # [B, S, 64], float32
    T   = the 4 largest of s + b                     # b: the selection bias
    w_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-20)
    FFN = sum_{e in T and HELD} w_e SwiGLU_e(y) + SwiGLU_shared(y)

The share: the router is ``published.n_routed_experts`` (64) wide and the
top 4 are taken and normalised over all 64; this chip HOLDS experts
``0 .. n_routed_experts - 1`` (8), computes those of a token's picks and
leaves out what the absent experts would add.  That partial result is the
layer's output and goes on to the next layer, here as in the program.

Multi-token prediction: with ``z`` the last layer's output (before the
final norm), ``u_i = [rms(Emb(t_{i+1}), ln_e); rms(z_i, ln_h)] @ w_eh``, one
further block of the routed kind with weights of its own (and the same
share), ``rms(., mtp.ln_f)``, the shared head: ``L_mtp = mean_i CE(.,
t_{i+2})`` over the positions that have a ``t_{i+2}``.

Loss = ``L_main + 0.3 L_mtp + 1e-4 sum over routed blocks of the
sequence-wise balance term`` ``mean_b sum_e f_be P_be``, ``f_be = 64 / (4 S)
#{t: e in T_t}``, ``P_be = mean_t s_te / sum_e' s_te'`` (DeepSeek-V3 eqs.
17-20).  The bias update is the optimizer step's and is not computed here.

Memory: every block is a ``jax.checkpoint``; attention runs as a scan over
blocks of 512 query rows, each against all keys with the mask written out
(20 heads x 512 x 8,192 float32 scores are 335 MB; 1,024 rows did not fit
beside the training state); the held experts run as a scan; the loss runs
over blocks of positions.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's four experts
of 64 per routed block, by name) it computes THOSE, weighted by its own
float32 scores of them.  Either way it returns ``(hidden, loss, extra)``:
``hidden`` the main final-norm stream and the prediction block's normed
stream stacked along the batch, ``extra["choices"]`` what it would have
chosen itself, ``extra["probs"]`` the ``s + b`` the choice was made from,
``extra["scalars"]`` the balance term and ``L_mtp`` AS THEY ENTER THE LOSS.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: arXiv:2412.19437 / arXiv:2508.06471 (assumed; the configuration file).
#: No key of config.json holds them; a ``seq_aux_weight`` / ``mtp_weight``
#: in the dict handed to the reference replaces them (a planted fault).
SEQ_AUX_WEIGHT = 1e-4
MTP_WEIGHT = 0.3
MTP = "mtp"
#: ``cfg["planted"]`` of this value makes the reference compute something
#: else ON PURPOSE: the stream entering every router rounded to fp8 (e4m3),
#: the nearest precision below the stated bf16 — the second reading the
#: adapter's two choice limits are set from
#: (``benchmark/harness/glm_probe.py``).  Never set by a cell.
FP8_ROUTER_STREAM = "fp8_router_stream"


def experts_name(i) -> str:
    return f"{MTP}.experts" if i == MTP else f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``n_routed_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["n_routed_experts"]


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


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _attention(y, layer, cfg, q_block=512):
    b, s, _ = y.shape
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = _rmsnorm(y @ layer["wq_a"], layer["q_a_norm"], eps)
    q = (c_q @ layer["wq_b"]).reshape(b, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    down = y @ layer["wkv_a"]
    rank = cfg["kv_lora_rank"]
    c_kv = _rmsnorm(down[..., :rank], layer["kv_a_norm"], eps)
    k_rope = _rope(down[..., None, rank:], theta)  # [B, S, 1, rope]
    kv = (c_kv @ layer["wkv_b"]).reshape(b, s, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / jnp.sqrt(F32(nope + rope))
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
    blocks = q.reshape(b, s // q_block, q_block, h, nope + rope)
    _, out = jax.lax.scan(
        rows, None, (jnp.moveaxis(blocks, 1, 0),
                     jnp.arange(0, s, q_block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * vd) @ layer["wo"]


def _routed(y, moe, cfg, given):
    """y [B, S, d] -> (out, own choice, selection scores, balance term)."""
    n_exp, held = router_width(cfg), cfg["n_routed_experts"]
    top_k = cfg["num_experts_per_tok"]
    into_router = y
    if cfg.get("planted") == FP8_ROUTER_STREAM:
        # behind a barrier: XLA otherwise keeps the convert pair's excess
        # precision
        into_router = jax.lax.optimization_barrier(
            y.astype(jnp.float8_e4m3fn)).astype(F32)
    s = jax.nn.sigmoid(into_router @ moe["router"])
    select = s + moe["router_bias"]
    _, own = jax.lax.top_k(select, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [B, S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [B, S, E]

    @jax.checkpoint
    def one_expert(out, e):
        return out + combine[..., e, None] * _swiglu(
            y, moe["wg"][e], moe["wi"][e], moe["wo"][e]), None

    # the held experts are the first `held` of the router's numbering;
    # a pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    shared = moe["shared"]
    out = out + _swiglu(y, shared["w_gate"], shared["w_up"], shared["w_down"])
    # per sequence: f over the k picks (all experts, held or not), P the
    # mean share of the score
    f = jnp.mean(jnp.sum(taken, 2), 1) * (n_exp / top_k)  # [B, E]
    p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), 1)  # [B, E]
    balance = jnp.mean(jnp.sum(f * p, -1))
    return out, own, select, balance


def _block(x, layer, cfg, given):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rmsnorm(x, layer["ln1"], eps), layer, cfg)
    y = _rmsnorm(x, layer["ln2"], eps)
    if "moe" not in layer:
        mlp = layer["mlp"]
        return x + _swiglu(y, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]), None
    out, own, select, balance = _routed(y, layer["moe"], cfg, given)
    return x + out, (own, select, balance)


def _weighted_nll(x, lm_head, targets, weights, block=1024):
    """``sum weights * CE`` over blocks of positions."""
    s = x.shape[1]
    block = min(block, s)
    total = jnp.zeros((), F32)
    for start in range(0, s, block):
        @jax.checkpoint
        def nll(xb, tb, wb):
            logp = jax.nn.log_softmax(xb @ lm_head, -1)
            return -jnp.sum(
                wb * jnp.take_along_axis(logp, tb[..., None], -1)[..., 0])

        sl = slice(start, start + block)
        total = total + nll(x[:, sl], targets[:, sl], weights[:, sl])
    return total


def hidden_and_loss(params, tokens, cfg: dict, given=None):
    """tokens [B, S+1] int -> (hidden [2B, S, d] f32: the main final-norm
    stream, then the prediction block's; loss; extra).  ``cfg``: HF keys."""
    eps = cfg["rms_norm_eps"]
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    b, s = inp.shape
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)

    def run(x, layer, name):
        nonlocal balance
        pick = None if given is None else given.get(name)
        x, routed = jax.checkpoint(
            lambda x, layer, pick: _block(x, layer, cfg, pick))(
                x, layer, pick)
        if routed is not None:
            own, select, bal = routed
            extra["choices"][name], extra["probs"][name] = own, select
            balance = balance + bal
        return x

    with jax.default_matmul_precision("highest"):
        x = params["embed"][inp]
        for i, layer in enumerate(params["layers"]):
            x = run(x, layer, experts_name(i))
        main = _rmsnorm(x, params["ln_f"], eps)
        mtp = params["mtp"]
        u = jnp.concatenate([
            _rmsnorm(params["embed"][tgt], mtp["ln_e"], eps),
            _rmsnorm(x, mtp["ln_h"], eps)], -1) @ mtp["w_eh"]
        u = run(u, mtp["block"], experts_name(MTP))
        ahead = _rmsnorm(u, mtp["ln_f"], eps)
        every = jnp.full((b, s), 1.0 / (b * s), F32)
        nll_main = _weighted_nll(main, params["lm_head"], tgt, every)
        # position i of the block predicts token i+2; the last has none
        has = jnp.concatenate(
            [jnp.full((b, s - 1), 1.0 / (b * (s - 1)), F32),
             jnp.zeros((b, 1), F32)], 1)
        nll_mtp = _weighted_nll(
            ahead, params["lm_head"], jnp.roll(tgt, -1, 1), has)
    # the two further terms as they enter the loss, weights included
    extra["scalars"] = {
        "moe_seq_aux": cfg.get("seq_aux_weight", SEQ_AUX_WEIGHT) * balance,
        "mtp": cfg.get("mtp_weight", MTP_WEIGHT) * nll_mtp}
    return (jnp.concatenate([main, ahead], 0),
            nll_main + sum(extra["scalars"].values()), extra)
