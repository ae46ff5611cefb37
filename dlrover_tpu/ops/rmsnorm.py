"""Fused RMSNorm: Pallas TPU kernel + reference, custom VJP.

Analogue of the reference's Triton rmsnorm (``kernels/triton_jit/
rmsnorm_kernel.py``) and the NPU fused ``AtorchNpuRMSNorm``
(``npu/layers.py:307``): one pass over rows computing x * rsqrt(mean(x^2))
* w with fp32 accumulation, fused backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.per_shard import P, per_shard, shard_axes


def _reference(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    return (xf * inv * w.astype(jnp.float32)).astype(x.dtype)


def _kernel(x_ref, w_ref, o_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    # w block is [1, D] (TPU layout needs >=2D); broadcasts over rows.
    o_ref[:] = (xf * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _pallas_fwd(x2d, w, eps, block_rows, interpret):
    from jax.experimental import pallas as pl

    R, D = x2d.shape
    block_rows = min(block_rows, R)
    grid = (pl.cdiv(R, block_rows),)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x2d.dtype),
        interpret=interpret,
        name="rmsnorm_fwd",
    )(x2d, w[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, w, eps, use_pallas, interpret):
    if use_pallas:
        shape = x.shape
        D = shape[-1]
        # whole sublane tiles: 2,688 columns would give 390 rows, which
        # Mosaic refuses
        block_rows = max(8, min(512, (4 << 20) // max(1, D * 4)) // 8 * 8)
        out = _pallas_fwd(
            x.reshape(-1, D), w, eps, block_rows, interpret
        )
        return out.reshape(shape)
    return _reference(x, w, eps)


def _fwd(x, w, eps, use_pallas, interpret):
    out = _rmsnorm(x, w, eps, use_pallas, interpret)
    return out, (x, w)


def _bwd(eps, use_pallas, interpret, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xhat = xf * inv
    # d/dx of x*inv(x)*w: standard RMSNorm backward.
    gw = gf * wf
    d = x.shape[-1]
    # Exact gradient: dx = r*(gw - xhat*mean(gw*xhat)), r = rsqrt(ms+eps).
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(
        (gf * xhat).reshape(-1, d), axis=0
    ).astype(w.dtype)
    return dx.astype(x.dtype), dw


_rmsnorm.defvjp(_fwd, _bwd)


def rmsnorm(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """RMSNorm over the last dim; ``w`` is the [D] gain."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend != "pallas":
        return _rmsnorm(x, w, eps, False, interpret)
    # One kernel call per shard of the mesh in scope: rows split on the
    # leading (batch) dim, the normalized dim whole in every shard.
    free, batch_axes, _ = shard_axes(x.shape[0])
    rows = P(batch_axes, *([None] * (x.ndim - 1)))
    return per_shard(
        lambda x, w: _rmsnorm(x, w, eps, True, interpret),
        free, (rows, P(None)), rows,
    )(x, w)
