"""State-space duality (Mamba-2, arXiv:2405.21060): the selective scan in
its chunked dual form, a sequential form beside it as the op's own
reference, and the causal depthwise convolution in front of it.

The recurrence, per head with state ``h in R^{P x N}`` and ``h_0 = 0``::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t
    y_t = h_t C_t + D * x_t

``x [B, S, H, P]``; ``dt [B, S, H]`` float32, positive (the caller's
softplus); ``A [H]`` float32, negative; ``Bm``, ``Cm [B, S, G, N]``, group
``g`` shared by heads ``g * H/G .. (g + 1) * H/G - 1``; ``D [H]`` or None.

:func:`ssd_chunked` splits a sequence into chunks of ``chunk`` positions.
Inside a chunk the output is a masked matmul, ``(C B^T o L)(dt x)`` with
``L_ij = exp(sum_{j<k<=i} dt_k A)`` for ``i >= j``; each chunk leaves the
state ``sum_j exp(sum_{k>j} dt_k A) dt_j x_j (x) B_j``; a scan over the
chunks carries the state forward and each chunk reads the one that enters
it.  ``dt``, ``dt * A``, its cumulative sums and every ``exp`` stay in
float32; the matmuls take operands in ``x``'s dtype and accumulate in
float32.  Plain ``jax.numpy``: no kernel, differentiated by JAX.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the most float32 bytes of ``L`` (``[B, chunks, heads, Q, Q]``) built at
#: once: past it the intra-chunk part runs over blocks of heads, each
#: rematerialised in the backward pass.  A shape decision, not a knob: at
#: 2 x 8,192 tokens, 64 heads and Q = 256 all heads at once are 1.07 GB.
_L_BYTES_AT_ONCE = 128 * 1024 * 1024


def causal_conv1d(x: jax.Array, w: jax.Array, b=None) -> jax.Array:
    """Depthwise causal convolution along the sequence: ``x [B, S, C]``,
    ``w [K, C]`` (tap ``K - 1`` meets position t itself, tap 0 position
    ``t - K + 1``; PyTorch's ``Conv1d(C, C, K, groups=C, padding=K - 1)``
    cut to S, its weight ``[C, 1, K]`` transposed), ``b [C]`` or None ->
    float32 ``[B, S, C]``.  Positions before the sequence are zeros."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + S] * w[k].astype(jnp.float32) for k in range(K))
    return out if b is None else out + b.astype(jnp.float32)


def _head_block(B: int, chunks: int, R: int, G: int, Q: int) -> int:
    """Heads of a group whose ``L`` is built at once: the largest divisor
    of ``R`` under :data:`_L_BYTES_AT_ONCE`."""
    per_head = 4 * B * chunks * G * Q * Q
    return max(r for r in range(1, R + 1)
               if R % r == 0 and (r == 1 or r * per_head <= _L_BYTES_AT_ONCE))


def _intra_chunk(xdt, cs, cb):
    """``(C B^T o L)(dt x)`` of some heads: ``xdt [B, c, Q, G, r, P]``,
    ``cs [B, c, Q, G, r]`` (inclusive cumulative sums of ``dt A``), ``cb
    [B, c, G, Q, Q]`` -> float32 ``[B, c, Q, G, r, P]``."""
    Q = cs.shape[2]
    at = cs.transpose(0, 1, 3, 4, 2)  # [B, c, G, r, Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # masked inside the exp too: above the diagonal the sum is positive
    # and may overflow, and inf * 0 has no gradient
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, at[..., :, None] - at[..., None, :], 0.0)), 0.0)
    m = (cb[:, :, :, None] * decay).astype(xdt.dtype)
    return jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xdt,
                      preferred_element_type=jnp.float32)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, D=None):
    """The chunked dual form -> ``(y [B, S, H, P] float32, final state
    [B, H, P, N] float32, least decay over a chunk, a float32 scalar)``.
    A sequence that ``chunk`` does not divide is padded with ``dt = 0``
    positions, which neither decay nor feed the state."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R, Q = H // G, chunk
    f32 = jnp.float32
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    c = (S + pad) // Q
    xc = x.reshape(Bsz, c, Q, G, R, P)
    dtc = dt.astype(f32).reshape(Bsz, c, Q, G, R)
    bc = Bm.reshape(Bsz, c, Q, G, N)
    cc = Cm.reshape(Bsz, c, Q, G, N)
    cs = jnp.cumsum(dtc * A.astype(f32).reshape(G, R), axis=2)
    xdt32 = xc.astype(f32) * dtc[..., None]
    xdt = xdt32.astype(x.dtype)

    # -- inside each chunk ---------------------------------------------------
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                    preferred_element_type=f32)
    rb = _head_block(Bsz, c, R, G, Q)
    if rb == R:
        y = _intra_chunk(xdt, cs, cb)
    else:
        blocks = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(a.shape[:4] + (R // rb, rb) + a.shape[5:]), 4, 0)
        y = jax.lax.map(
            jax.checkpoint(lambda args: _intra_chunk(*args, cb)),
            (blocks(xdt), blocks(cs)))
        y = jnp.moveaxis(y, 0, 4).reshape(Bsz, c, Q, G, R, P)

    # -- the state each chunk leaves, and the scan over the chunks -----------
    total = cs[:, :, -1]  # [B, c, G, R]: the chunk's whole sum of dt A
    to_end = jnp.exp(total[:, :, None] - cs)  # decay from j to the end
    left = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                      (xdt32 * to_end[..., None]).astype(x.dtype), bc,
                      preferred_element_type=f32)
    chunk_decay = jnp.exp(total)

    def carry(h, inputs):
        decay, new = inputs
        return decay[..., None, None] * h + new, h

    final, entering = jax.lax.scan(
        carry, jnp.zeros((Bsz, G, R, P, N), f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(left, 1, 0)))
    y = y + jnp.einsum(
        "bcign,cbgrpn->bcigrp", cc, entering.astype(x.dtype),
        preferred_element_type=f32) * jnp.exp(cs)[..., None]
    if D is not None:
        y = y + xc.astype(f32) * D.astype(f32).reshape(G, R)[:, :, None]
    y = y.reshape(Bsz, S + pad, H, P)[:, :S]
    return y, final.reshape(Bsz, H, P, N), jnp.min(chunk_decay)


def ssd_sequential(x, dt, A, Bm, Cm, D=None):
    """The recurrence as written, one position at a time in float32 at
    ``highest`` matmul precision -> ``(y [B, S, H, P], final state [B, H,
    P, N])``.  The chunked form's reference; nothing trains through it."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    f32 = jnp.float32
    heads = lambda a: jnp.repeat(a.astype(f32), H // G, axis=2)  # noqa: E731

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, H, P], [B, H], [B, H, N] x 2
        h = (jnp.exp(dt_t * A.astype(f32))[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t, precision="highest")

    seq_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    final, y = jax.lax.scan(
        step, jnp.zeros((Bsz, H, P, N), f32),
        (seq_first(x.astype(f32)), seq_first(dt.astype(f32)),
         seq_first(heads(Bm)), seq_first(heads(Cm))))
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + x.astype(f32) * D.astype(f32)[:, None]
    return y, final
