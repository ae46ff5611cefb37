"""Fused softmax cross-entropy: Pallas TPU kernel + reference, custom VJP.

Analogue of the reference's Triton cross-entropy
(``kernels/triton_jit/cross_entropy.py`` via ``modules/transformer/
layers.py`` dispatch): never materializes log-softmax over the vocab in HBM
— each row block computes logsumexp + gathers the target logit in VMEM.
Backward is the closed form (softmax - onehot) computed blockwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.per_shard import P, per_shard, shard_axes


def _reference(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -ll


def _kernel(logits_ref, labels_ref, loss_ref):
    x = logits_ref[:].astype(jnp.float32)  # [rows, V]
    labels = labels_ref[:, 0]  # [rows] (2D block: TPU layout needs >=2D)
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        == labels[:, None]
    )
    target = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    loss_ref[:] = (lse - target)[:, None]


def _pallas_loss(logits2d, labels1d, block_rows, interpret):
    from jax.experimental import pallas as pl

    R, V = logits2d.shape
    block_rows = min(block_rows, R)
    grid = (pl.cdiv(R, block_rows),)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, V), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
        name="softmax_xent_fwd",
    )(logits2d, labels1d[:, None])
    return out[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent(logits, labels, use_pallas, interpret):
    if use_pallas:
        shape = logits.shape
        V = shape[-1]
        # Keep the fp32 logits block within ~4MB of VMEM.
        block_rows = max(8, min(256, (4 << 20) // max(1, V * 4)))
        out = _pallas_loss(
            logits.reshape(-1, V), labels.reshape(-1), block_rows, interpret
        )
        return out.reshape(shape[:-1])
    return _reference(logits, labels)


def _fwd(logits, labels, use_pallas, interpret):
    return _xent(logits, labels, use_pallas, interpret), (logits, labels)


def _bwd(use_pallas, interpret, res, g):
    logits, labels = res
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    dlogits = (p - onehot) * g[..., None]
    return dlogits.astype(logits.dtype), None


_xent.defvjp(_fwd, _bwd)


def softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    *,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """[..., V] logits x [...] int labels -> [...] per-token loss (fp32)."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend != "pallas":
        return _xent(logits, labels, False, interpret)
    # One kernel call per shard of the mesh in scope: rows split on the
    # leading (batch) dim, the whole vocab in every shard.
    free, batch_axes, _ = shard_axes(labels.shape[0])
    rows = P(batch_axes, *([None] * (labels.ndim - 1)))
    return per_shard(
        lambda lg, lb: _xent(lg, lb, True, interpret),
        free, (P(*rows, None), rows), rows,
    )(logits, labels)


# ---------------------------------------------------------------------------
# Fused lm-head + cross-entropy: loss(x @ w, labels) without ever
# materializing the [tokens, vocab] logits in HBM.  Analogue of the memory
# win the reference gets from its Triton cross-entropy dispatch
# (``atorch/atorch/modules/transformer/layers.py:54-70``), taken one step
# further: the projection itself is chunked over token rows with a
# ``lax.scan`` so peak HBM holds one [chunk, V] block instead of [B*S, V]
# (fp32 logits of a 32k-vocab 2k-seq batch are GBs; a 1k-row chunk is
# 128MB).  Backward recomputes each chunk's logits (flash-style) and
# accumulates dw in fp32.
# ---------------------------------------------------------------------------


def _chunk(x2, labels, chunk_rows):
    R = x2.shape[0]
    n = max(1, -(-R // chunk_rows))
    pad = n * chunk_rows - R
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
    return (
        x2.reshape(n, chunk_rows, x2.shape[1]),
        labels.reshape(n, chunk_rows),
        pad,
    )


def _chunk_loss(x_c, w, l_c):
    logits = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == l_c[:, None]
    )
    target = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return lse - target


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _linear_xent(x2, w, labels, chunk_rows):
    xs, ls, pad = _chunk(x2, labels, chunk_rows)

    def body(_, xl):
        return None, _chunk_loss(xl[0], w, xl[1])

    _, loss = jax.lax.scan(body, None, (xs, ls))
    loss = loss.reshape(-1)
    return loss[: x2.shape[0]] if pad else loss


def _linear_xent_fwd(x2, w, labels, chunk_rows):
    return _linear_xent(x2, w, labels, chunk_rows), (x2, w, labels)


def _linear_xent_bwd(chunk_rows, res, g):
    x2, w, labels = res
    R = x2.shape[0]
    xs, ls, pad = _chunk(x2, labels, chunk_rows)
    gs = (jnp.pad(g, (0, pad)) if pad else g).reshape(ls.shape)

    def body(dw, xlg):
        x_c, l_c, g_c = xlg
        logits = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            == l_c[:, None]
        )
        dlogits = (p - onehot.astype(jnp.float32)) * g_c[:, None]
        dx_c = jnp.dot(
            dlogits.astype(w.dtype), w.T, preferred_element_type=jnp.float32
        )
        dw = dw + jnp.dot(
            x_c.T.astype(jnp.float32), dlogits,
            preferred_element_type=jnp.float32,
        )
        return dw, dx_c.astype(x2.dtype)

    dw, dx = jax.lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), (xs, ls, gs)
    )
    dx = dx.reshape(-1, x2.shape[1])[:R]
    return dx, dw.astype(w.dtype), None


_linear_xent.defvjp(_linear_xent_fwd, _linear_xent_bwd)


def _default_chunk_rows() -> int:
    """1024 balances scan count vs the [chunk, V] fp32 logits block
    (128 MB at V=32k).  ``DLROVER_TPU_CE_CHUNK_ROWS`` overrides for
    hardware tuning sweeps (larger chunks = fewer scan trips = better
    MXU utilization, at more HBM)."""
    import os

    try:
        v = int(os.environ.get("DLROVER_TPU_CE_CHUNK_ROWS", "1024"))
    except ValueError:
        return 1024
    return v if v > 0 else 1024


_DEFAULT_CHUNK_ROWS = _default_chunk_rows()


def linear_softmax_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    chunk_rows: int = _DEFAULT_CHUNK_ROWS,
) -> jax.Array:
    """Fused ``softmax_cross_entropy(x @ w, labels)`` per-token loss.

    x: [..., D] activations (any float dtype), w: [D, V] lm head,
    labels: [...] int — returns fp32 [...] loss without materializing the
    full [..., V] logits (HBM peak is one [chunk_rows, V] fp32 block).
    """
    shape = labels.shape
    out = _linear_xent(
        x.reshape(-1, x.shape[-1]), w, labels.reshape(-1), chunk_rows
    )
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# The reduced form: sum_r weights[r] * loss[r].  A loss is the last
# operation of the graph, so its cotangent is a scalar and the row weights
# are known in the forward pass: the forward rule forms dx and dw in the
# one scan that computes the loss, the one time the chunk logits exist,
# and the backward rule only scales them.  Three [chunk, D] x [D, V]-sized
# matmuls a chunk where the per-token op above needs four.  The same scan
# has every row's loss in hand, and that is the weights' own gradient.
# ---------------------------------------------------------------------------


def _chunk_weights(weights, pad, shape):
    return (jnp.pad(weights, (0, pad)) if pad else weights).reshape(shape)


def _vary_like(value, *operands):
    """``value``, varying over the manual mesh axes the operands vary over
    (inside a ``shard_map``; elsewhere unchanged)."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    missing = tuple(vma - jax.typeof(value).vma)
    return jax.lax.pcast(value, missing, to="varying") if missing else value


def _carry_init(shape, *operands):
    """fp32 zeros typed like the operands: inside a ``shard_map`` a scan's
    carry must enter with the type it leaves with."""
    return _vary_like(jnp.zeros(shape, jnp.float32), *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _linear_xent_sum(x2, w, labels, weights, chunk_rows):
    """-> (the weighted sum, the fp32 row losses ``[rows]``)."""
    xs, ls, pad = _chunk(x2, labels, chunk_rows)

    def body(total, xlg):
        x_c, l_c, g_c = xlg
        loss_c = _chunk_loss(x_c, w, l_c)
        return total + jnp.sum(loss_c * g_c), loss_c

    total, loss = jax.lax.scan(
        body, _carry_init((), x2, w, weights),
        (xs, ls, _chunk_weights(weights, pad, ls.shape)),
    )
    return total, loss.reshape(-1)[: x2.shape[0]]


def _linear_xent_sum_fwd(x2, w, labels, weights, chunk_rows):
    R = x2.shape[0]
    xs, ls, pad = _chunk(x2, labels, chunk_rows)

    def body(carry, xlg):
        total, dw = carry
        x_c, l_c, g_c = xlg
        # _chunk_loss's arithmetic, written out because the gradients
        # take its intermediates: exp(logits - m) / sum is the softmax
        # of _linear_xent_bwd without a second max / sum pass.
        logits = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=-1)
        e = jnp.exp(logits - m[:, None])
        s = jnp.sum(e, axis=-1)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            == l_c[:, None]
        )
        target = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        loss_c = m + jnp.log(s) - target
        total = total + jnp.sum(loss_c * g_c)
        # From here down _linear_xent_bwd's body, dtype for dtype.
        dlogits = (e / s[:, None] - onehot.astype(jnp.float32)) * g_c[:, None]
        dx_c = jnp.dot(
            dlogits.astype(w.dtype), w.T, preferred_element_type=jnp.float32
        )
        dw = dw + jnp.dot(
            x_c.T.astype(jnp.float32), dlogits,
            preferred_element_type=jnp.float32,
        )
        return (total, dw), (dx_c.astype(x2.dtype), loss_c)

    operands = (x2, w, weights)
    (total, dw), (dx, loss) = jax.lax.scan(
        body,
        (_carry_init((), *operands), _carry_init(w.shape, *operands)),
        (xs, ls, _chunk_weights(weights, pad, ls.shape)),
    )
    loss = loss.reshape(-1)[:R]
    return (total, loss), (
        dx.reshape(-1, x2.shape[1])[:R], dw.astype(w.dtype), loss)


def _linear_xent_sum_bwd(chunk_rows, res, g):
    # the row losses are handed out for reading (stop_gradient in the
    # caller below): their cotangent is not propagated
    g, _ = g
    dx, dw, loss = res
    return g.astype(dx.dtype) * dx, g.astype(dw.dtype) * dw, None, g * loss


_linear_xent_sum.defvjp(_linear_xent_sum_fwd, _linear_xent_sum_bwd)


def linear_softmax_cross_entropy_sum(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    chunk_rows: int = _DEFAULT_CHUNK_ROWS,
    with_row_losses: bool = False,
):
    """Fused ``sum(weights * softmax_cross_entropy(x @ w, labels))``.

    x: [..., D], w: [D, V], labels: [...] int, weights: [...] float row
    weights or None for the mean (every row 1/N) — returns the fp32
    scalar, logits never materialized beyond one [chunk_rows, V] block.
    ``with_row_losses`` returns ``(scalar, row losses fp32 [rows])``: what
    the scan computed on the way, for a caller's metrics, carrying no
    gradient.

    Contract: the weights are known in the forward pass (they do not
    depend on this op's result).  That is what lets the forward rule
    under differentiation compute ``dx`` and ``dw`` in the same scan as
    the loss — its residuals are those two, in ``x``'s and ``w``'s dtype,
    and the fp32 row losses the scan computes anyway; the backward rule
    multiplies each by the scalar cotangent ``g``.  The weights may
    depend on parameters (a looped model's exit probabilities): their
    cotangent is ``g * loss[r]``, the row's own loss, with no further
    matmul; for weights that no parameter reaches (``None``, a validity
    mask) it is computed and dropped.  Without a gradient the scan is
    forward only (one matmul a chunk, no ``dw`` accumulator).  Same
    dtypes at the same places as the per-token op's backward, so the
    gradients are those of
    ``sum(weights * linear_softmax_cross_entropy(...))``.

    The per-token op stays for callers that need per-token losses: a
    [...] result can meet any cotangent, so its backward has to
    recompute each chunk's logits.
    """
    rows = labels.size
    if weights is None:
        weights = jnp.full((rows,), 1.0 / rows, jnp.float32)
    # the weights' cotangent is a row loss, typed like x and w
    weights = _vary_like(weights.reshape(-1).astype(jnp.float32), x, w)
    total, row_losses = _linear_xent_sum(
        x.reshape(-1, x.shape[-1]), w, labels.reshape(-1), weights,
        chunk_rows,
    )
    if with_row_losses:
        return total, jax.lax.stop_gradient(row_losses)
    return total
