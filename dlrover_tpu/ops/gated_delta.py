"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear attention
with a matrix state, a decay of its own per head and position, and a write
that first takes out what the state already holds under the key.  The
chunked form that trains, and a sequential form beside it as the op's own
reference (``ops/ssd.py`` keeps the same pair).

The recurrence, per head with state ``S in R^{Dk x Dv}`` and ``S_0 = 0``::

    S   <- exp(g_t) S
    m_t  = k_t^T S                        what the state holds under k_t
    S   <- S + k_t (x) beta_t (v_t - m_t)
    o_t  = q_t^T S

``q``, ``k [B, S, H, Dk]`` (the caller's L2 norms and the scale of ``q``
already applied), ``v [B, S, H, Dv]``, ``g [B, S, H]`` float32 and never
positive (the log of the decay), ``beta [B, S, H]`` in (0, 1).

:func:`gated_delta_chunked` splits a sequence into chunks of ``Q = chunk``
positions.  With ``gamma = cumsum(g)`` inside a chunk, ``u_t = beta_t (v_t -
m_t)`` solves a unit lower-triangular system (the WY form)::

    A  = tril(beta_i (k_i . k_j) exp(gamma_i - gamma_j), -1)      [Q, Q]
    T  = (I + A)^-1
    W  = T (beta k exp(gamma))       U = T (beta v)
    u  = U - W S                     S the state that ENTERS the chunk
    o  = (q exp(gamma)) S + tril(q k^T exp(gamma_i - gamma_j)) u
    S <- exp(gamma_Q) S + (k exp(gamma_Q - gamma))^T u

and a ``lax.scan`` over the chunks carries the state.  Where float32 stays:
``g``, ``gamma``, every ``exp``, ``A``, ``T`` (its products at ``highest``
matmul precision: a TPU's default rounds float32 operands to bfloat16) and
the state ``S``.  Every exponent is a difference ``gamma_i - gamma_j`` with
``i >= j`` (or ``gamma`` itself), so never positive: a head may decay by
``e^-21`` a token and ``e^-1340`` a chunk, ``exp(-gamma)`` alone would
overflow, and the difference is MASKED before the ``exp`` so that no ``inf *
0`` forms in the backward pass.  The matmuls against the state (``W S``, ``(q
exp(gamma)) S``, ``k^T u``) and the masked ``q k^T`` product against ``u``
take operands rounded to ``v``'s dtype and accumulate in float32.  The
backward pass is JAX's through this form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular float32 ``a [..., Q,
    Q]``, ``Q`` a power of two: block forward substitution, doubling.  The
    inverses of the diagonal blocks of size ``b`` are known (``1`` at ``b =
    1``); a pair ``[[P, 0], [L, R]]`` of them has the inverse ``[[P^-1, 0],
    [-R^-1 L P^-1, R^-1]]``, which for all pairs at once is ``inv - inv L_b
    inv`` with ``inv`` the block-diagonal matrix of the known inverses and
    ``L_b`` the blocks of ``a`` under the pairs' diagonals: two batched
    matmuls a level, ``log2 Q`` levels, every operand a whole ``[Q, Q]``
    matrix (a TPU pads an array's last two dims to its tiles: blocks of 2 x
    2 would take 64 times their size) and every product in float32 at
    ``highest``.  The cotangent is ``-T^T g T^T``: the one residual is the
    result."""
    q = a.shape[-1]
    if q & (q - 1):
        raise ValueError(f"unit_lower_inverse: {q} is no power of two")
    row = jnp.arange(q)[:, None]
    col = jnp.arange(q)[None, :]
    inv = jnp.broadcast_to(jnp.eye(q, dtype=F32), a.shape)
    b = 1
    while b < q:
        under = ((row // (2 * b) == col // (2 * b))
                 & (row % (2 * b) >= b) & (col % (2 * b) < b))
        inv = inv - jnp.matmul(
            jnp.matmul(inv, jnp.where(under, a, 0.0), precision="highest"),
            inv, precision="highest")
        b *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, g, precision="highest"), tt,
                        precision="highest"),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64):
    """The chunked form -> ``(o [B, S, H, Dv] float32, final state [B, H,
    Dk, Dv] float32, the least decay over a chunk, a float32 scalar)``.  A
    sequence that ``chunk`` does not divide is padded with positions of ``g
    = 0`` and ``beta = 0``, which neither decay nor write."""
    bsz, s, h, dk = k.shape
    dv, dt, qn = v.shape[-1], v.dtype, chunk
    pad = -s % qn
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    c = (s + pad) // qn
    # [B, H, c, Q, ...]: a head's chunks side by side
    rows = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape((bsz, c, qn) + x.shape[2:]), 3, 1)
    qc, kc, vc = rows(q), rows(k), rows(v)
    gamma = jnp.cumsum(rows(g.astype(F32)), axis=-1)  # [B, H, c, Q]
    bc = rows(beta.astype(F32))
    lower = jnp.tril(jnp.ones((qn, qn), bool))
    # masked inside the exp too: above the diagonal the difference is
    # positive and may overflow, and inf * 0 has no gradient
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], 0.0)), 0.0)
    grown = jnp.exp(gamma)[..., None]  # from the chunk's start to i
    total = gamma[..., -1]  # [B, H, c]: the chunk's whole sum of g
    to_end = jnp.exp(total[..., None] - gamma)[..., None]  # from j to the end

    kk = jnp.einsum("bhcid,bhcjd->bhcij", kc, kc, preferred_element_type=F32)
    a = jnp.where(jnp.tril(lower, -1), bc[..., None] * kk * decay, 0.0)
    t = unit_lower_inverse(a)
    kf, vf = kc.astype(F32), vc.astype(F32)
    w = jnp.matmul(t, kf * (bc[..., None] * grown), precision="highest")
    u = jnp.matmul(t, vf * bc[..., None], precision="highest")
    qk = (jnp.einsum("bhcid,bhcjd->bhcij", qc, kc,
                     preferred_element_type=F32) * decay).astype(dt)
    q_grown = (qc.astype(F32) * grown).astype(dt)
    k_to_end = (kf * to_end).astype(dt)
    chunk_decay = jnp.exp(total)

    @jax.checkpoint
    def carry(state, inputs):
        w_c, u_c, qk_c, qg_c, ke_c, decay_c = inputs
        low = state.astype(dt)
        new = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, low,
                               preferred_element_type=F32)
        new_low = new.astype(dt)
        out = (jnp.einsum("bhik,bhkv->bhiv", qg_c, low,
                          preferred_element_type=F32)
               + jnp.einsum("bhij,bhjv->bhiv", qk_c, new_low,
                            preferred_element_type=F32))
        state = decay_c[..., None, None] * state + jnp.einsum(
            "bhjk,bhjv->bhkv", ke_c, new_low, preferred_element_type=F32)
        return state, out

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    final, out = jax.lax.scan(
        carry, jnp.zeros((bsz, h, dk, dv), F32),
        tuple(chunks_first(x) for x in (
            w.astype(dt), u, qk, q_grown, k_to_end, chunk_decay)))
    # [c, B, H, Q, Dv] -> [B, S, H, Dv]
    out = jnp.moveaxis(out, (0, 3), (1, 2)).reshape(bsz, s + pad, h, dv)
    return out[:, :s], final, jnp.min(chunk_decay)


def gated_delta_sequential(q, k, v, g, beta):
    """The recurrence as written, one position at a time in float32 at
    ``highest`` matmul precision -> ``(o [B, S, H, Dv], final state [B, H,
    Dk, Dv])``.  The chunked form's reference; nothing trains through it."""
    bsz, _, h, dk = k.shape

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs  # [B, H, D] x 3, [B, H] x 2
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision="highest")
        state = state + k_t[..., :, None] * (
            b_t[..., None] * (v_t - held))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state,
                                 precision="highest")

    seq_first = lambda x: jnp.moveaxis(x.astype(F32), 1, 0)  # noqa: E731
    final, out = jax.lax.scan(
        step, jnp.zeros((bsz, h, dk, v.shape[-1]), F32),
        tuple(seq_first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), final
