"""Quantization ops: int8 block quantize/dequantize + 8-bit optimizer state.

The TPU-native analogue of the reference's quantization CUDA ops (SURVEY.md
#54: ``ops/csrc/quantization/{quantize,swizzled_quantize,quant_reduce}.cu``
+ the int8-state "quantization_optimizer" Adam): per-block scales (lane-
aligned 128-wide blocks), symmetric int8, stochastic rounding for state
updates, and an optax-compatible 8-bit Adam whose first/second moments live
as (int8 values, fp32 block scales) — 4x HBM reduction on optimizer state.

Pure-jnp formulation: XLA maps the reshape+reduce+cast pipeline onto the VPU
efficiently; a Pallas fused variant slots into ``quantize_blockwise`` when
profile data justifies it.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.ops.per_shard import free_axes

BLOCK = 128


def _pad_to_block(x: jax.Array) -> Tuple[jax.Array, int]:
    n = x.size
    pad = (-n) % BLOCK
    flat = x.reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, BLOCK), n


def _unpad(vals: jax.Array, shape, dtype) -> jax.Array:
    n = int(np.prod(shape)) if shape else 1
    return vals.reshape(-1)[:n].reshape(shape).astype(dtype)


def _quant_kernel(x_ref, codes_ref, scale_ref):
    """Fused abs-max + scale + round in VMEM — one HBM read of x, int8
    write-out (the Pallas variant the reference implements as
    ``quantize.cu``/``swizzled_quantize.cu``)."""
    x = x_ref[:].astype(jnp.float32)  # [rows, 128]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / 127.0, 1e-12)
    codes = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    codes_ref[:] = codes.astype(jnp.int8)
    scale_ref[:] = scale[:, None]


def _quantize_pallas(
    blocks: jax.Array, block_rows: int = 256, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl

    R = blocks.shape[0]
    block_rows = min(block_rows, R)
    codes, scale = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(R, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_blockwise",
    )(blocks)
    return codes, scale[:, 0]


def quantize_blockwise(
    x: jax.Array,
    *,
    stochastic: bool = False,
    key: jax.Array | None = None,
    backend: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """x -> (int8 codes [ceil(n/128), 128], fp32 scales [ceil(n/128)]).

    ``backend``: "auto" uses the fused Pallas kernel on TPU (jnp
    elsewhere); "pallas"/"jnp" force a path (pallas + ``interpret=True``
    runs the kernel on CPU for tests).  Stochastic rounding stays on the
    jnp path (it needs a threaded PRNG)."""
    if backend == "pallas" and stochastic:
        raise ValueError(
            "stochastic rounding is jnp-only (needs a threaded PRNG); "
            "don't force backend='pallas' with stochastic=True"
        )
    blocks, n = _pad_to_block(x.astype(jnp.float32))
    use_pallas = backend == "pallas" or (
        backend == "auto"
        and not stochastic
        and jax.default_backend() == "tpu"
    )
    if use_pallas:
        if free_axes()[0]:
            # The flattened [blocks, 128] view has no per-shard form (a
            # shard boundary need not fall on a block boundary), and
            # GSPMD cannot partition the kernel.
            raise NotImplementedError(
                "quantize_blockwise: the Pallas kernel cannot run on "
                "operands sharded over a mesh; pass backend='jnp' there"
            )
        return _quantize_pallas(blocks, interpret=interpret)
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    scaled = blocks / scale[:, None]
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        noise = jax.random.uniform(key, scaled.shape) - 0.5
        codes = jnp.clip(jnp.round(scaled + noise), -127, 127)
    else:
        codes = jnp.clip(jnp.round(scaled), -127, 127)
    return codes.astype(jnp.int8), scale


def dequantize_blockwise(
    codes: jax.Array, scale: jax.Array, shape, dtype=jnp.float32
) -> jax.Array:
    return _unpad(codes.astype(jnp.float32) * scale[:, None], shape, dtype)


class Quantized(NamedTuple):
    codes: jax.Array  # int8 [blocks, 128]
    scale: jax.Array  # fp32 [blocks]


# -- dynamic (log-spaced) 8-bit quantization ---------------------------------
# Linear int8 cannot span Adam's second-moment dynamic range (~7 decades
# inside one block); small entries collapse to zero and the 1/sqrt(nu)
# denominator explodes.  The reference's CUDA optimizer uses dynamic 8-bit
# code maps (``quantization_optimizer.cu``); here the map is analytic:
# signed level m in [-127,127], |value| = scale * 10^((|m|-1)/(L-1)*D - D),
# m=0 encodes exact zero, D=7 decades.

_DYN_DECADES = 7.0


def quantize_dynamic(
    x: jax.Array,
    *,
    signed: bool = True,
    key: jax.Array | None = None,
):
    """x -> (int8 log-codes, fp32 per-block scale). ~6% relative error over
    7 decades instead of linear int8's hard floor at scale/127.

    ``key`` enables stochastic rounding of the log level so sub-step EMA
    increments accumulate in expectation instead of freezing at the nearest
    code (the role stochastic rounding plays in the reference's CUDA
    optimizer state updates)."""
    blocks, _ = _pad_to_block(x.astype(jnp.float32))
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1), 1e-30)
    mag = jnp.abs(blocks) / scale[:, None]
    levels = 127.0 if signed else 255.0
    # log-position in [0,1] over the D-decade range
    pos = (jnp.log10(jnp.maximum(mag, 1e-30)) + _DYN_DECADES) / _DYN_DECADES
    noise = (
        jax.random.uniform(key, pos.shape) - 0.5
        if key is not None
        else 0.0
    )
    m = jnp.round(pos * (levels - 1.0) + noise) + 1.0
    m = jnp.clip(m, 1.0, levels)
    m = jnp.where(mag < 10.0**(-_DYN_DECADES), 0.0, m)
    if signed:
        m = m * jnp.sign(blocks)
        codes = m.astype(jnp.int8)
    else:
        codes = (m - 128.0).astype(jnp.int8)  # shift to int8 range
    return codes, scale


def dequantize_dynamic(
    codes: jax.Array, scale: jax.Array, shape, *, signed: bool = True,
    dtype=jnp.float32,
) -> jax.Array:
    cf = codes.astype(jnp.float32)
    if signed:
        m = jnp.abs(cf)
        sign = jnp.sign(cf)
        levels = 127.0
    else:
        m = cf + 128.0
        sign = 1.0
        levels = 255.0
    mag = 10.0 ** ((m - 1.0) / (levels - 1.0) * _DYN_DECADES - _DYN_DECADES)
    vals = jnp.where(m == 0.0, 0.0, sign * mag) * scale[:, None]
    return _unpad(vals, shape, dtype)


class Adam8bitState(NamedTuple):
    count: jax.Array
    mu: optax.Params  # pytree of Quantized (signed dynamic codes)
    nu: optax.Params  # pytree of Quantized (unsigned dynamic codes)
    key: jax.Array  # PRNG for stochastic rounding of state updates


def adam8bit(
    learning_rate: float | optax.Schedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
) -> optax.GradientTransformation:
    """Adam with int8-quantized moments (the reference's
    ``quantization_optimizer.cu`` capability as an optax transform)."""

    lr = learning_rate if callable(learning_rate) else (lambda _: learning_rate)

    def init(params):
        def q_zero(p, signed):
            blocks = (p.size + BLOCK - 1) // BLOCK
            fill = 0 if signed else -128  # code for exact zero
            return Quantized(
                jnp.full((blocks, BLOCK), fill, jnp.int8),
                jnp.zeros((blocks,), jnp.float32),
            )

        return Adam8bitState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(lambda p: q_zero(p, True), params),
            nu=jax.tree_util.tree_map(lambda p: q_zero(p, False), params),
            key=jax.random.PRNGKey(0),
        )

    def update(grads, state, params=None):
        count = state.count + 1
        round_key = jax.random.fold_in(state.key, count)
        keys = iter(
            jax.random.split(
                round_key, 2 * len(jax.tree_util.tree_leaves(grads))
            )
        )

        def per_leaf(g, qmu, qnu, p):
            gf = g.astype(jnp.float32)
            mu = dequantize_dynamic(
                qmu.codes, qmu.scale, g.shape, signed=True
            )
            nu = dequantize_dynamic(
                qnu.codes, qnu.scale, g.shape, signed=False
            )
            mu = b1 * mu + (1 - b1) * gf
            nu = b2 * nu + (1 - b2) * jnp.square(gf)
            mu_hat = mu / (1 - b1 ** count.astype(jnp.float32))
            nu_hat = nu / (1 - b2 ** count.astype(jnp.float32))
            upd = mu_hat / (jnp.sqrt(nu_hat) + eps)
            if weight_decay and p is not None:
                upd = upd + weight_decay * p.astype(jnp.float32)
            new_qmu = Quantized(
                *quantize_dynamic(mu, signed=True, key=next(keys))
            )
            new_qnu = Quantized(
                *quantize_dynamic(nu, signed=False, key=next(keys))
            )
            return (-lr(count) * upd).astype(g.dtype), new_qmu, new_qnu

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_mu = treedef.flatten_up_to(state.mu)
        flat_nu = treedef.flatten_up_to(state.nu)
        flat_p = (
            treedef.flatten_up_to(params)
            if params is not None
            else [None] * len(flat_g)
        )
        outs = [
            per_leaf(g, m, n, p)
            for g, m, n, p in zip(flat_g, flat_mu, flat_nu, flat_p)
        ]
        updates = treedef.unflatten([o[0] for o in outs])
        new_mu = treedef.unflatten([o[1] for o in outs])
        new_nu = treedef.unflatten([o[2] for o in outs])
        return updates, Adam8bitState(count, new_mu, new_nu, state.key)

    return optax.GradientTransformation(init, update)
