"""Plain reference of the Nemotron-H block (HF model type ``nemotron_h``:
``NemotronHBlock``, ``NemotronHMamba2Mixer``, ``MambaRMSNormGated``,
``NemotronHAttention``, ``NemotronHMOE``, ``NemotronHTopkRouter``,
``NemotronHMLP``), recalled without a network, and its training loss under
ONE CHIP'S SHARE of a 16-way expert-parallel layer.  Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no
chunked form, no sort, no cache.

Every layer is ONE branch behind ONE norm, its kind the layer's character of
``hybrid_override_pattern``::

    x = x + branch(rms(x, w))        rms(v, w) = v / sqrt(mean(v^2) + eps) * w

``M``, the Mamba-2 mixer (``H = mamba_num_heads`` heads of ``P =
mamba_head_dim``, state ``N = ssm_state_size``, ``G = n_groups``), for the
normed stream ``u``::

    [z | xBC | dt] = u @ in_proj              widths H P | H P + 2 G N | H
    xBC = silu(conv(xBC) + b)                 causal, depthwise, taps t-3..t
    [x | B | C] = xBC                         head h reads group h // (H / G)
    dt = softplus(dt + dt_bias)               no clamp (time_step_limit 0, inf)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,   A = -exp(A_log), h_0 = 0
    y_t = h_t C_t + D x_t
    out = (norm * groupnorm_G(y * silu(z))) @ out_proj

the gate BEFORE the norm, and the mean square taken over each of the ``G``
groups of ``H P / G`` by itself.  The recurrence is the SEQUENTIAL one, a
``lax.scan`` over positions — the system computes the chunked dual form
(``dlrover_tpu/ops/ssd.py``).

``*``, attention: ``num_attention_heads`` query heads on
``num_key_value_heads`` key heads of ``head_dim``, NO rotary position
(``rope_theta`` and ``partial_rotary_factor`` are read by nobody), causal
softmax at ``head_dim ** -0.5``, the mask written out, no bias, no q/k norm.

``E``, the routed block, for the normed stream ``u``::

    s   = sigmoid(u @ router)                 float32, n_routed_experts wide
    T   = the num_experts_per_tok largest of s + b      b: the selection bias
    w_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-20)
    out = sum_{e in T and HELD} w_e down_e relu(up_e u)^2
          + down_s relu(up_s u)^2             the shared expert, every token

The share: the router is ``published.n_routed_experts`` (128) wide and the
picks are taken and normalised over all of it; this chip HOLDS experts ``0
.. n_routed_experts - 1`` of the file (8), computes those of a token's
picks and leaves out what the absent experts would add.  That partial result
is the layer's output and goes on to the next layer, here as in the program.

Loss = the mean next-token cross-entropy over the rows of this chip's slice
of the vocabulary + ``1e-4`` x the sum over the routed blocks of ``E *
sum_e f_e P_e`` (``f_e`` the share of all tokens' picks that took expert
``e``, ``P_e`` the mean over tokens of ``s_e / sum_e' s_e'``).  The bias
update is the optimizer step's and is not computed here.

Independent of ``dlrover_tpu``: it imports none of it and takes the same
parameter tree (that is the interface, not shared code; ``conv_w`` is stored
``[taps, channels]``, PyTorch's ``[C, 1, K]`` transposed; an expert's ``wi``
is ``up`` and ``wo`` ``down``) and HF key names for sizes.  Departures, for
memory only and with no effect on any value: one sequence at a time, the
scan in blocks of ``scan_block`` positions, attention over blocks of query
rows, the held experts one at a time and the loss over blocks of positions,
each block and each layer a ``jax.checkpoint``, so that ``jax.grad`` of this
reference at 8,192 positions fits beside the training state on one chip.

The routed half of the adapter contract (``benchmark/run.py``):
``given=None`` routes for itself; with ``given`` (the system's picks per
routed block, by name) it computes THOSE, weighted by its own float32 scores
of them.  Either way it returns ``(hidden, loss, extra)``:
``extra["choices"]`` what it would have chosen itself, ``extra["probs"]``
the ``s + b`` the choice was made from, ``extra["scalars"]`` the balance term
AS IT ENTERS THE LOSS.

``cfg["planted"]`` makes this reference compute something else ON PURPOSE
(``benchmark/harness/nemotron_h_probe.py``,
``benchmark/tests/test_nemotron_h.py``: the comparison must find each).
Faults: ``"one_group_norm"`` (the gated norm's mean square over the whole
width), ``"swiglu_expert"`` (every expert and the shared one ``down(silu(up
u) * up u)``: the gated form with the one matrix there is),
``"silu_for_relu2"`` (``down(silu(up u)^2)``), ``"rope_on"`` (rotary
position on q and k at ``rope_theta``), ``"no_routed_scaling"`` (the 2.5
left out).  The lower-precision stand-in: ``"fp8_stream"`` rounds the values
of the NORMED stream entering every branch to float8 e4m3 (gradients pass
straight through).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: assumed (the configuration file); a ``moe_aux_weight`` in the dict handed
#: to the reference replaces it (a planted fault)
AUX_WEIGHT = 1e-4
FAULTS = ("one_group_norm", "swiglu_expert", "silu_for_relu2", "rope_on",
          "no_routed_scaling")
STAND_INS = ("fp8_stream",)
#: stand-ins no limit can see: none here (``harness/lfm2_probe.py`` reads
#: the name)
UNSEEN = ()
PLANTED = FAULTS + STAND_INS
#: the kinds of layer by their character of ``hybrid_override_pattern``,
#: each with the key of the layer dict that holds its leaves (attention's
#: sit in the layer dict itself)
KINDS = {"M": "ssm", "*": "wq", "E": "moe", "-": "mlp"}


def experts_name(i) -> str:
    return f"layers.{i}.experts"


def router_width(cfg: dict) -> int:
    """The experts the router knows: the source's count, where the file's
    own ``n_routed_experts`` is what this chip holds."""
    return cfg.get("published", cfg)["n_routed_experts"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rounded(x, dtype):
    """The VALUES of ``x`` in ``dtype``, behind a barrier (XLA may keep the
    excess precision of a convert pair); the gradient passes unrounded."""
    low = jax.lax.optimization_barrier(x.astype(dtype)).astype(F32)
    return x + jax.lax.stop_gradient(low - x)


def _conv(x, w, b):
    """x [S, C], w [K, C]: out_t = sum_k w[k] x[t - (K - 1) + k], zeros
    before the sequence; K shifted adds."""
    k_taps, s = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k_taps - 1, 0), (0, 0)))
    out = sum(xp[k:k + s] * w[k] for k in range(k_taps))
    return out if b is None else out + b


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
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    zxbcdt = u @ ssm["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + inner + 2 * groups * n]
    dt = zxbcdt[:, inner + inner + 2 * groups * n:]
    xbc = jax.nn.silu(_conv(xbc, ssm["conv_w"], ssm.get("conv_b")))
    x = xbc[:, :inner].reshape(s, heads, p)
    per_head = lambda a: jnp.repeat(  # noqa: E731 - group g serves H/G heads
        a.reshape(s, groups, n), heads // groups, axis=1)
    b = per_head(xbc[:, inner:inner + groups * n])
    c = per_head(xbc[:, inner + groups * n:])
    dt = jax.nn.softplus(dt + ssm["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(ssm["A_log"]))
    y = _recurrence(x, dt, decay, b, c, min(scan_block, s))
    y = (y + ssm["D"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    eps = cfg["layer_norm_epsilon"]
    if planted == "one_group_norm":
        y = _rmsnorm(y, ssm["norm"], eps)
    else:
        # each group of H P / G by its own mean square, then the gain
        parts = y.reshape(s, groups, inner // groups)
        y = _rmsnorm(parts, 1.0, eps).reshape(s, inner) * ssm["norm"]
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


def _attention(u, layer, cfg, planted, q_block):
    """GQA on one sequence's normed stream u [S, d]: no position, scale
    ``head_dim ** -0.5``, query i attends keys 0 .. i."""
    s = u.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = (u @ layer["wq"]).reshape(s, h, hd)
    k = (u @ layer["wk"]).reshape(s, kv, hd)
    v = (u @ layer["wv"]).reshape(s, kv, hd)
    if planted == "rope_on":
        theta = float(cfg["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        ok = kpos[None, :] <= (start + jnp.arange(q_block))[:, None]
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, q_block))
    return out.reshape(s, h * hd) @ layer["wo"]


def _two_matrix(u, up, down, planted):
    """``down(relu(up u)^2)``, or what a planted fault makes of it."""
    a = u @ up
    if planted == "swiglu_expert":
        a = jax.nn.silu(a) * a
    elif planted == "silu_for_relu2":
        a = jnp.square(jax.nn.silu(a))
    else:
        a = jnp.square(jax.nn.relu(a))
    return a @ down


def _routed(u, moe, cfg, planted, given):
    """u [S, d] -> (out, own choice, selection scores, the picks' counts
    [E], the sum over positions of the scores' shares [E])."""
    n_exp, held = router_width(cfg), cfg["n_routed_experts"]
    top_k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ moe["router"])
    select = s + moe["router_bias"]
    _, own = jax.lax.top_k(select, top_k)
    chosen = own if given is None else given
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if planted != "no_routed_scaling":
        w = w * cfg["routed_scaling_factor"]
    taken = jax.nn.one_hot(chosen, n_exp, dtype=F32)  # [S, k, E]
    combine = jnp.sum(w[..., None] * taken, -2)  # [S, E]

    @jax.checkpoint
    def one_expert(out, e):
        return out + combine[:, e, None] * _two_matrix(
            u, moe["wi"][e], moe["wo"][e], planted), None

    # the held experts are the first `held` of the router's numbering; a
    # pick of an absent expert adds nothing here
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(held))
    shared = moe["shared"]
    out = out + _two_matrix(u, shared["w_up"], shared["w_down"], planted)
    return (out, own, select, jnp.sum(taken, (0, 1)),
            jnp.sum(s / jnp.sum(s, -1, keepdims=True), 0))


def _mean_nll(x, head, tgt, block):
    """Mean next-token cross-entropy of x [S, d] against tgt [S], logits
    ``x @ head``, over blocks of positions."""
    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    s = x.shape[0]
    nll = jax.lax.map(jax.checkpoint(one_block),
                      (x.reshape(s // block, block, -1),
                       tgt.reshape(s // block, block)))
    return jnp.mean(nll)


def hidden_and_loss(params, tokens, cfg: dict, given=None,
                    q_block: int = 512, scan_block: int = 128):
    """tokens [B, S+1] int -> (final-norm hidden [B, S, d] f32, loss,
    extra).  ``cfg``: the configuration file's dict (HF keys)."""
    planted = cfg.get("planted")
    if planted is not None and planted not in PLANTED:
        raise ValueError(
            f"nemotron_h_ref: unknown planted fault {planted!r}")
    pattern = cfg["hybrid_override_pattern"]
    if (cfg["tie_word_embeddings"] or cfg["mlp_hidden_act"] != "relu2"
            or len(pattern) != cfg["num_hidden_layers"]
            or set(pattern) - set(KINDS) or cfg["n_group"] != 1):
        raise ValueError(
            "nemotron_h_ref computes an untied head, relu2 MLPs, a pattern "
            f"of num_hidden_layers characters out of {tuple(KINDS)} and a "
            "router without a group limit")
    eps = cfg["layer_norm_epsilon"]
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    routed = [i for i, kind in enumerate(pattern) if kind == "E"]
    for kind, layer in zip(pattern, params["layers"]):
        if [key in layer for key in KINDS.values()] != [
                kind == k for k in KINDS]:
            raise ValueError(
                "nemotron_h_ref: the parameters' layers are not of the "
                f"kinds hybrid_override_pattern={pattern!r} names")

    def branch(u, layer, kind, pick, qb):
        """One layer's branch on the normed stream, and what a routed one
        reports."""
        if kind == "M":
            return _mamba(u, layer["ssm"], cfg, planted, scan_block), None
        if kind == "*":
            return _attention(u, layer, cfg, planted, qb), None
        if kind == "-":
            mlp = layer["mlp"]
            return _two_matrix(u, mlp["w_up"], mlp["w_down"], planted), None
        out, *report = _routed(u, layer["moe"], cfg, planted, pick)
        return out, tuple(report)

    def one_sequence(args):
        seq, picks = args
        inp, tgt = seq[:-1], seq[1:]
        qb = min(q_block, inp.shape[0])
        x = params["embed"][inp]
        reports = {}
        for i, (kind, layer) in enumerate(zip(pattern, params["layers"])):
            def block(x, layer, pick, kind=kind):
                name = "ln2" if kind in "E-" else "ln1"
                u = _rmsnorm(x, layer[name], eps)
                if planted == "fp8_stream":
                    u = _rounded(u, jnp.float8_e4m3fn)
                out, report = branch(u, layer, kind, pick, qb)
                return x + out, report

            x, report = jax.checkpoint(block)(
                x, layer, picks.get(experts_name(i)))
            if report is not None:
                reports[experts_name(i)] = report
        x = _rmsnorm(x, params["ln_f"], eps)
        return x, _mean_nll(x, params["lm_head"], tgt, qb), reports

    with jax.default_matmul_precision("highest"):
        hidden, losses, reports = jax.lax.map(
            one_sequence, (tokens, {} if given is None else dict(given)))
    n_exp, top_k = router_width(cfg), cfg["num_experts_per_tok"]
    positions = hidden.shape[0] * hidden.shape[1]
    extra = {"choices": {}, "probs": {}, "scalars": {}}
    balance = jnp.zeros((), F32)
    for i in routed:
        own, select, counts, shares = reports[experts_name(i)]
        extra["choices"][experts_name(i)] = own
        extra["probs"][experts_name(i)] = select
        f = jnp.sum(counts, 0) / (positions * top_k)
        p = jnp.sum(shares, 0) / positions
        balance = balance + n_exp * jnp.sum(f * p)
    extra["scalars"] = {
        "moe_aux": cfg.get("moe_aux_weight", AUX_WEIGHT) * balance}
    return hidden, jnp.mean(losses) + extra["scalars"]["moe_aux"], extra
