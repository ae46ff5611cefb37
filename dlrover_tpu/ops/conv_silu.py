"""The causal depthwise convolution in front of a sequence mixer and the
``silu`` behind it as ONE op with its own backward: ``silu(conv(x) + b)``
of ``x [B, S, C]`` in ``x``'s dtype, whose float32 pre-activation never
exists in HBM.  The state-space mixer (Mamba-2, with bias) and the gated
delta-rule mixer (no bias) call it; LFM2's double-gated convolution has no
``silu`` and keeps ``ops.ssd.causal_conv1d``, which XLA fuses with its two
gates.

The arithmetic is ``silu(causal_conv1d(x, w, b)).astype(x.dtype)``'s, to the
rounding: ``x`` and the taps to float32, the ``K`` products summed in
float32 in tap order, the bias added in float32, ``silu`` in float32, ONE
rounding to ``x``'s dtype.  Positions before the sequence are zeros, and a
batch row never reads its neighbour's last rows.  The backward recomputes
the pre-activation from ``x`` (the residuals are ``x``, ``w`` and ``b``),
``dpre = dy * silu'(pre)`` in float32, and

    dx_t = sum_k w_k * dpre_{t + (K - 1) - k}        rounded once
    dw_k = sum_{b, t} x_t * dpre_{t + (K - 1) - k}   float32
    db   = sum_{b, t} dpre_t                         float32

with ``dpre`` past the sequence's end zero: ``dx`` and ``dw`` read the SAME
``K`` shifted views of ``dpre``.

Two forms.  In plain ``jax.numpy``, differentiated by JAX: what the CPU
runs, the kernels' reference, and the fall-back for a shape they do not
tile.  On a TPU the Pallas pair ``conv_silu_fwd`` / ``conv_silu_bwd`` under
one ``jax.custom_vjp``, grid ``(batch row, block of lanes, tile of rows)``:
positions along the sublanes, channels along the lanes, bf16 (or float32)
in and out of HBM and float32 only in VMEM.  A tile of ``T`` rows needs the
``K - 1`` rows before it (forward; backward also the ``K - 1`` rows after
it, of ``x`` and of ``dy``): a second view of the same array through its
own ``BlockSpec``, one native tile of rows high (8 of float32, 16 of
bfloat16), clamped at the sequence's ends and zeroed there in the kernel.
A grid step widens the tile with its halo to float32 in a VMEM scratch, one
``[rows, 128]`` array a lane tile (a row offset into such an array is an
address; into a wider one it is not, and Mosaic refuses it), and then walks
it :data:`_STEP_ROWS` rows by 128 lanes at a time, so that the taps'
products, their sum and the ``silu`` live in vector registers: the shifted
views are loads of the scratch at a row offset.  ``dw`` and ``db`` are
summed in registers along a tile, and across a batch row's tiles in the
kernel's second output, whose block does not move along the grid's last
axis (``"arbitrary"``); the batch rows' sums are added in ``jax.numpy``.

The rule (:func:`_tile`): ``jax.default_backend() == "tpu"``, ``C`` a
multiple of 128, ``S`` a multiple of :data:`_ROW_TILE`, ``K - 1`` at most 8,
and bfloat16 or float32.  Under a mesh the call runs once per shard
(``ops/per_shard.py``): the op is independent per batch row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.flash_attention import _vmem_params
from dlrover_tpu.ops.per_shard import P as Spec, per_shard, shard_axes
from dlrover_tpu.ops.ssd import causal_conv1d

F32 = jnp.float32

#: rows of a grid step's tile.  A shape decision, not a knob: a sequence
#: that it does not divide runs the ``jax.numpy`` form.
_ROW_TILE = 512
#: most lanes of a grid step's tile: the rows a DMA moves are this long
_BLOCK_LANES = 512
#: rows of the sums' block: the taps' ``K <= 9`` and the bias's one
_SUM_ROWS = 16
#: rows one step of a kernel's walk down its tile takes: eight float32
#: registers an array, so that as many chains of arithmetic are in flight
#: (at 16 rows a step the kernels wait on their own latencies and take 2.3
#: times as long; my chip run, PR 54)
_STEP_ROWS = 64


def _reference(x, w, b):
    return jax.nn.silu(causal_conv1d(x, w, b)).astype(x.dtype)


def _tile(shape, taps: int, dtype):
    """``(rows of a tile, lanes of a tile, rows of a halo)``, or None where
    the kernels do not tile the operands."""
    _, s, c = shape
    if (dtype not in (jnp.bfloat16, jnp.float32) or c % 128 or s % _ROW_TILE
            or not 1 <= taps <= 9):
        return None
    lanes = max(n for n in range(128, _BLOCK_LANES + 1, 128) if c % n == 0)
    return _ROW_TILE, lanes, 32 // jnp.dtype(dtype).itemsize


def _pre(xs_ref, group, at, rows, taps, bias):
    """The pre-activation of ``rows`` positions from the scratch's row
    ``at`` on (the scratch's row ``r`` is the tile's position ``r - halo``):
    the products in tap order, then the bias."""
    from jax.experimental import pallas as pl

    first = at - (len(taps) - 1)
    acc = None
    for k, tap in enumerate(taps):
        term = tap * xs_ref[group, pl.ds(first + k, rows)]
        acc = term if acc is None else acc + term
    return acc + bias


def _lane_groups(width: int):
    return [slice(at, at + 128) for at in range(0, width, 128)]


def _steps(t: int, body, carry=None):
    """``body(first row of the step, carry) -> carry`` down a tile of ``t``
    rows, :data:`_STEP_ROWS` a step: ONE traced body, which Mosaic's
    lowering writes out for every step so that their chains overlap (left
    as a loop the backward takes a fifth longer; my chip run, PR 54)."""
    from jax.experimental import pallas as pl

    return jax.lax.fori_loop(
        0, t // _STEP_ROWS,
        lambda r, c: body(pl.multiple_of(r * _STEP_ROWS, _STEP_ROWS), c),
        carry, unroll=True)


def _fwd_kernel(x_ref, prev_ref, w_ref, b_ref, y_ref, xs_ref):
    from jax.experimental import pallas as pl

    h, (t, width), n = prev_ref.shape[0], x_ref.shape, _STEP_ROWS
    first = pl.program_id(2) == 0
    for g, lanes in enumerate(_lane_groups(width)):
        xs_ref[g, :h] = jnp.where(first, 0.0, prev_ref[:, lanes].astype(F32))
        xs_ref[g, h:] = x_ref[:, lanes].astype(F32)
        taps = [w_ref[k:k + 1, lanes] for k in range(w_ref.shape[0])]
        bias = b_ref[:, lanes]

        def step(at, _, g=g, lanes=lanes, taps=taps, bias=bias):
            pre = _pre(xs_ref, g, at + h, n, taps, bias)
            y_ref[pl.ds(at, n), lanes] = (
                pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

        _steps(t, step)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dnext_ref, w_ref, b_ref,
                dx_ref, sums_ref, xs_ref, dp_ref):
    """``xs`` holds ``x`` of the tile between its two halos, ``dp`` the
    ``dpre`` of the tile and of the halo after it.  ``sums``' rows: ``dw``
    of tap ``k`` at ``k``, ``db`` at ``K``."""
    from jax.experimental import pallas as pl

    i, last = pl.program_id(2), pl.program_id(2) == pl.num_programs(2) - 1
    h, (t, width), k_taps = prev_ref.shape[0], x_ref.shape, w_ref.shape[0]
    n = _STEP_ROWS

    @pl.when(i == 0)
    def _new_row():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    for g, lanes in enumerate(_lane_groups(width)):
        xs_ref[g, :h] = jnp.where(i == 0, 0.0, prev_ref[:, lanes].astype(F32))
        xs_ref[g, h:h + t] = x_ref[:, lanes].astype(F32)
        xs_ref[g, h + t:] = jnp.where(
            last, 0.0, next_ref[:, lanes].astype(F32))
        taps = [w_ref[k:k + 1, lanes] for k in range(k_taps)]
        bias = b_ref[:, lanes]

        def dpre(at, rows, dy, g=g, taps=taps, bias=bias):
            pre = _pre(xs_ref, g, at + h, rows, taps, bias)
            s = jax.nn.sigmoid(pre)
            dp_ref[g, pl.ds(at, rows)] = dy.astype(F32) * (
                s * (1.0 + pre * (1.0 - s)))

        _steps(t, lambda at, _, lanes=lanes, dpre=dpre: dpre(
            at, n, dy_ref[pl.ds(at, n), lanes]))
        # past the sequence's end no position reads this tile
        dpre(t, h, jnp.where(last, 0.0, dnext_ref[:, lanes].astype(F32)))

        def step(at, sums, g=g, lanes=lanes, taps=taps):
            x, dx, out = xs_ref[g, pl.ds(at + h, n)], None, []
            for k in range(k_taps):
                ahead = dp_ref[g, pl.ds(at + k_taps - 1 - k, n)]
                term = taps[k] * ahead
                dx = term if dx is None else dx + term
                out.append(sums[k] + _fold(x * ahead))
            dx_ref[pl.ds(at, n), lanes] = dx.astype(dx_ref.dtype)
            # tap K - 1 looks 0 rows ahead
            return (*out, sums[k_taps] + _fold(ahead))

        sums = _steps(t, step, (jnp.zeros((8, 128), F32),) * (k_taps + 1))
        for k, total in enumerate(sums):
            sums_ref[k:k + 1, lanes] += jnp.sum(total, axis=0, keepdims=True)


def _fold(a):
    """``[rows, 128] -> [8, 128]``: the float32 row tiles added up, one
    vector register's worth of partial sums."""
    return jnp.sum(a.reshape(-1, 8, 128), axis=0)


def _specs(shape, tile, taps: int):
    """The grid and the block specs: of ``x``-shaped arrays the tile, the
    halo before it and the halo after it; the taps; the bias."""
    from jax.experimental import pallas as pl

    bsz, s, c = shape
    t, width, h = tile
    per, halos = t // h, s // h
    return (bsz, c // width, s // t), {
        "tile": pl.BlockSpec((None, t, width), lambda b, j, i: (b, i, j)),
        "before": pl.BlockSpec(
            (None, h, width),
            lambda b, j, i: (b, jnp.maximum(i * per - 1, 0), j)),
        "after": pl.BlockSpec(
            (None, h, width),
            lambda b, j, i: (b, jnp.minimum((i + 1) * per, halos - 1), j)),
        "taps": pl.BlockSpec((taps, width), lambda b, j, i: (0, j)),
        "bias": pl.BlockSpec((1, width), lambda b, j, i: (0, j)),
    }


def _call_params(resident_bytes: int) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    # a batch row's tiles run in turn: dw and db are summed across them
    raised = _vmem_params(resident_bytes).get("compiler_params")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=raised and raised.vmem_limit_bytes)}


# Jitted, so that JAX traces and lowers each kernel once a program and not
# once a call site: a step has a dozen (a layer's forward, block remat's and
# the mixer's own recomputation, the backward), and nested checkpoints trace
# the custom VJP's rules again.  Without it the delta-rule cell's warm
# ``setup_s`` read 30-32 s where the parent's reads 26, and with it 26.7 (my
# chip runs, PR 54); the sandbox's CPU shows no such difference.
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _conv_fwd(x, w, b, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, width, h = tile
    size = x.dtype.itemsize
    grid, specs = _specs(x.shape, tile, w.shape[0])
    return pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[specs[n] for n in ("tile", "before", "taps", "bias")],
        out_specs=specs["tile"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((width // 128, h + t, 128), F32)],
        interpret=interpret,
        name="conv_silu_fwd",
        # blocks twice (the pipeline's two buffers): the tile in and out
        # and the halo; the widened tile
        **_call_params(2 * (2 * t + h) * width * size
                       + (h + t) * width * 4),
    )(x, x, w.astype(F32), b.astype(F32).reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _conv_bwd(x, w, b, dy, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, width, h = tile
    k_taps, size = w.shape[0], x.dtype.itemsize
    grid, specs = _specs(x.shape, tile, k_taps)
    dx, sums = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[specs[n] for n in ("tile", "before", "after", "tile",
                                     "after", "taps", "bias")],
        out_specs=[specs["tile"], pl.BlockSpec(
            (None, _SUM_ROWS, width), lambda b, j, i: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (x.shape[0], _SUM_ROWS, x.shape[2]), F32)],
        scratch_shapes=[pltpu.VMEM((width // 128, t + 2 * h, 128), F32),
                        pltpu.VMEM((width // 128, t + h, 128), F32)],
        interpret=interpret,
        name="conv_silu_bwd",
        # blocks twice: x, dy and dx's tiles, three halos, the sums; the
        # widened tile and its dpre
        **_call_params(2 * ((3 * t + 3 * h) * width * size
                            + _SUM_ROWS * width * 4)
                       + (2 * t + 3 * h) * width * 4),
    )(x, x, x, dy, dy, w.astype(F32), b.astype(F32).reshape(1, -1))
    sums = jnp.sum(sums, axis=0)
    return dx, sums[:k_taps].astype(w.dtype), sums[k_taps].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_silu_kernels(x, w, b, tile, interpret):
    return _conv_fwd(x, w, b, tile, interpret)


def _kernels_fwd(x, w, b, tile, interpret):
    # the inputs are the only residuals
    return _conv_fwd(x, w, b, tile, interpret), (x, w, b)


def _kernels_bwd(tile, interpret, res, dy):
    return _conv_bwd(*res, dy, tile, interpret)


_conv_silu_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def causal_conv1d_silu(x: jax.Array, w: jax.Array, b=None, *,
                       backend: Optional[str] = None,
                       interpret: bool = False) -> jax.Array:
    """``silu(causal_conv1d(x, w, b))`` rounded once to ``x``'s dtype: ``x
    [B, S, C]``, ``w [K, C]`` (tap ``K - 1`` meets position t itself), ``b
    [C]`` or None -> ``[B, S, C]``.  By the kernel pair where the module's
    rule allows, one call per shard of the mesh in scope (the batch dim
    split, as ``ops/ssd.py``), else in ``jax.numpy``.  ``backend``
    (``"pallas"`` / ``"reference"``; None: by the device) and ``interpret``
    are for tests of the kernels on the CPU."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    tile = _tile(x.shape, w.shape[0], x.dtype) if backend == "pallas" else None
    if tile is None:
        return _reference(x, w, b)
    if b is None:  # a constant: its cotangent goes nowhere
        b = jnp.zeros((x.shape[-1],), F32)
    free, batch_axes, _ = shard_axes(x.shape[0])
    rows = Spec(batch_axes, None, None)
    return per_shard(
        lambda *ops: _conv_silu_kernels(*ops, tile, interpret), free,
        (rows, Spec(None, None), Spec(None)), rows,
    )(x, w, b)
