"""The gated RMS norm over SHORT groups of lanes as one op with its own
backward: each row of ``x [..., W]`` normalised by the mean square of each
of its ``W / group`` groups of ``group`` columns by itself, a gain ``[W]``,
and a gate ``act(z)`` on one side of the norm or the other, ``act`` one of
:data:`ACTIVATIONS` (``silu`` unless said):

    norm then gate   gain * x * rsqrt(mean_g(x^2) + eps) * act(z)
    gate then norm   gain * v * rsqrt(mean_g(v^2) + eps),  v = x * act(z)

The first is the gated delta-rule mixer's (a group is a value head's 128
lanes; with ``sigmoid`` the per-channel rule's, Kimi Delta Attention), the
second the state-space mixer's in more groups than one (Mamba-2's
``MambaRMSNormGated`` at ``group_size = W / n_groups``: 512 lanes a group in
Nemotron's eight).  One group over the whole width is ``ops.rmsnorm``'s case
and not this op's.  XLA's fusions of this arithmetic move 110-130 GB/s of the
chip's 819 at either group width (``tools/gated_norm_bench.py``); the kernels
pass over their bytes once each way at 620-700.

The arithmetic is float32 throughout — ``x``, ``z`` and the gain widened,
the squares of a group summed in float32, one rounding to the output's dtype
(``z``'s) — and the backward recomputes the inverse RMS from ``x`` and ``z``:
the residuals are the inputs alone.  With ``n = v * inv`` the normalised
row, ``gw`` the cotangent of ``n`` and ``c = mean_g(gw * n)``:

    dv = inv * (gw - n * c)        dgain = sum_rows dy * n [* act(z)]

and the gate's two factors by the product rule on whichever side it sits.

Two forms.  In plain ``jax.numpy``, differentiated by JAX: what the CPU
runs, the kernels' reference, and the fall-back for a shape they do not
tile.  On a TPU the Pallas pair ``gated_norm_fwd`` / ``gated_norm_bwd`` under
one ``jax.custom_vjp``, grid ``(block of lanes, tile of rows)``: a block is
whole groups, as many as 512 lanes hold, by 1,024 rows.  A grid step walks
its block a group of lanes and a step of rows at a time (a loop: see
:func:`_walk`): the group's lane tiles are squared and added elementwise, and
ONE cross-lane sum a step gives each row's mean square (the same sum through
the MXU, against a block of ones in three bfloat16 terms that keep float32's
mantissa, is no faster — the kernels wait on HBM either way — and is the
bench's ``--mxu``).  ``dgain`` is summed across a block of lanes' tiles of
rows in the kernel's third output, whose block does not move along the grid's
last axis (``"arbitrary"``); rows past the array's end (a row count the tile
does not divide) are kept out of it by their index.

The rule (:func:`_tile`): ``jax.default_backend() == "tpu"``, ``group`` a
whole number of 128-lane tiles that divides ``W``, at least 16 rows,
bfloat16 or float32.  Under a mesh the call runs once per shard
(``ops/per_shard.py``): rows split on the leading (batch) dim, the width
whole in every shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.flash_attention import _vmem_params
from dlrover_tpu.ops.per_shard import P as Spec, per_shard, shard_axes

F32, BF16 = jnp.float32, jnp.bfloat16

#: most rows and lanes of a grid step's block.  Shape decisions, not knobs.
_BLOCK_ROWS = 1024
_BLOCK_LANES = 512
#: float32 vector registers one array of a step holds: a step is this many
#: registers' rows by a group's lanes, 256 rows at a group of 128 lanes and
#: 64 at 512.  More than the register file, so a step's arrays pass through
#: VMEM — which costs nothing while the kernels wait on HBM, and a step of 8
#: registers takes a fifth (forward) to a third (backward) longer for its
#: four times as many turns of the loop (my chip runs, PR 60)
_STEP_REGISTERS = 32
#: rows of the gain's sums: one float32 register's
_SUM_ROWS = 8
#: the gate's activations: ``(act, act')``, each of float32 ``z`` and
#: ``sigmoid(z)``
ACTIVATIONS = {
    "silu": (lambda z, sig: z * sig,
             lambda z, sig: sig * (1.0 + z * (1.0 - sig))),
    "sigmoid": (lambda z, sig: sig, lambda z, sig: sig * (1.0 - sig)),
}


def _gate(z, activation):
    """``act(z)`` of float32 ``z``: all the forward needs."""
    return ACTIVATIONS[activation][0](z, jax.nn.sigmoid(z))


def _gate_terms(z, activation):
    """``(act(z), act'(z))`` of float32 ``z``."""
    sig = jax.nn.sigmoid(z)
    act, slope = ACTIVATIONS[activation]
    return act(z, sig), slope(z, sig)


def _reference(x, z, gain, group, eps, gate_first, activation="silu"):
    xf, s = x.astype(F32), _gate(z.astype(F32), activation)
    v = xf * s if gate_first else xf
    parts = v.reshape(v.shape[:-1] + (-1, group))
    inv = jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    n = (parts * inv).reshape(v.shape)
    gf = gain.astype(F32)
    return (n * gf if gate_first else (gf * n) * s).astype(z.dtype)


def _tile(rows: int, width: int, group: int, dtypes):
    """``(rows of a block, lanes of a block, rows of a step)``, or None
    where the kernels do not tile the operands.  A block is whole groups:
    as many as :data:`_BLOCK_LANES` holds (one, where a group is wider)."""
    # (a block is at least a group wide: past 1,024 lanes the backward's
    # five blocks of 1,024 rows outgrow VMEM)
    if (group % 128 or width % group or group > 1024
            or any(d not in (BF16, F32) for d in dtypes)):
        return None
    # whole native tiles of rows (16 of bfloat16), fewer than a step's where
    # the operands are that short
    step = min(_STEP_REGISTERS * 8 * 128 // group, rows // 16 * 16)
    if not step:
        return None
    block = min(_BLOCK_ROWS, rows // step * step)
    lanes = max(n for n in range(group, max(group, _BLOCK_LANES) + 1, group)
                if width % n == 0)
    return block, lanes, step


def _group_mean(a, group):
    """``[rows, group]`` float32 -> ``[rows, 1]``, each row's mean: a
    group's lane tiles added elementwise and one cross-lane sum."""
    return jnp.sum(a, axis=-1, keepdims=True) * (1.0 / group)


def _fold(a):
    """``[rows, lanes] -> [8, lanes]``: the float32 row tiles added up, one
    vector register's worth of partial sums a lane tile."""
    return jnp.sum(a.reshape(-1, _SUM_ROWS, a.shape[-1]), axis=0)


def _walk(block: int, step: int, body):
    """``body(first row of the step)`` down a block of rows: a loop, so that
    a kernel's text holds one step a group and not every step of a block —
    a program traces and lowers the kernels with it, and written out whole
    (32 groups by 4 steps) the pair added 4.5 s and 12 s to two cells' warm
    ``setup_s`` (my chip runs, PR 60)."""
    from jax.experimental import pallas as pl

    jax.lax.fori_loop(
        0, block // step,
        lambda r, _: body(pl.multiple_of(r * step, step)), None)


def _groups(width: int, group: int):
    return [slice(at, at + group) for at in range(0, width, group)]


def _fwd_kernel(x_ref, z_ref, gain_ref, y_ref, *, group, eps, gate_first,
                step, activation):
    from jax.experimental import pallas as pl

    block, width = x_ref.shape
    for lanes in _groups(width, group):
        gain = gain_ref[:, lanes]

        def body(at, lanes=lanes, gain=gain):
            rows = pl.ds(at, step)
            x = x_ref[rows, lanes].astype(F32)
            s = _gate(z_ref[rows, lanes].astype(F32), activation)
            v = x * s if gate_first else x
            n = v * jax.lax.rsqrt(_group_mean(v * v, group) + eps)
            y = n * gain if gate_first else (gain * n) * s
            y_ref[rows, lanes] = y.astype(y_ref.dtype)

        _walk(block, step, body)


def _bwd_kernel(x_ref, z_ref, gain_ref, dy_ref, dx_ref, dz_ref, dgain_ref, *,
                group, eps, gate_first, step, live_rows, activation):
    from jax.experimental import pallas as pl

    block, width = x_ref.shape
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first_tile():
        dgain_ref[...] = jnp.zeros_like(dgain_ref)

    for lanes in _groups(width, group):
        gain = gain_ref[:, lanes]

        def body(at, lanes=lanes, gain=gain):
            rows = pl.ds(at, step)
            x = x_ref[rows, lanes].astype(F32)
            dy = dy_ref[rows, lanes].astype(F32)
            s, ds = _gate_terms(z_ref[rows, lanes].astype(F32), activation)
            v = x * s if gate_first else x
            # the cotangent of the normalised row n = v * inv
            gw = dy * gain if gate_first else (dy * gain) * s
            inv = jax.lax.rsqrt(_group_mean(v * v, group) + eps)
            n = v * inv
            dv = inv * (gw - n * _group_mean(gw * n, group))
            if gate_first:
                dx, dz, dg = dv * s, (dv * x) * ds, dy * n
            else:
                dx, dz, dg = dv, ((dy * gain) * n) * ds, (dy * n) * s
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)
            dz_ref[rows, lanes] = dz.astype(dz_ref.dtype)
            if live_rows % block:  # the last tile ends past the rows
                dg = jnp.where(i * block + at + jax.lax.broadcasted_iota(
                    jnp.int32, dg.shape, 0) < live_rows, dg, 0.0)
            dgain_ref[:, lanes] += _fold(dg)

        _walk(block, step, body)


def _specs(rows: int, width: int, block: int, lanes: int):
    """The grid — blocks of lanes, and a block's tiles of rows in turn —
    and the block specs."""
    from jax.experimental import pallas as pl

    return (width // lanes, pl.cdiv(rows, block)), {
        "rows": pl.BlockSpec((block, lanes), lambda j, i: (i, j)),
        "gain": pl.BlockSpec((1, lanes), lambda j, i: (0, j)),
        "sums": pl.BlockSpec((_SUM_ROWS, lanes), lambda j, i: (0, j)),
    }


def _call_params(resident_bytes: int) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    # a block of lanes' tiles of rows run in turn: dgain is summed across
    # them
    raised = _vmem_params(resident_bytes).get("compiler_params")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=raised and raised.vmem_limit_bytes)}


# Jitted, so that JAX traces and lowers each kernel once a program and not
# once a call site (``ops/conv_silu.py``: a step has several, and an unjitted
# wrapper cost seconds of ``setup_s``).
@functools.partial(jax.jit, static_argnames=(
    "group", "eps", "gate_first", "tile", "interpret", "activation"))
def _norm_fwd(x, z, gain, group, eps, gate_first, tile, interpret,
              activation):
    from jax.experimental import pallas as pl

    (rows, width), (block, lanes, step) = x.shape, tile
    grid, specs = _specs(rows, width, block, lanes)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, group=group, eps=eps,
                          gate_first=gate_first, step=step,
                          activation=activation),
        grid=grid,
        in_specs=[specs["rows"], specs["rows"], specs["gain"]],
        out_specs=specs["rows"],
        out_shape=jax.ShapeDtypeStruct(x.shape, z.dtype),
        interpret=interpret,
        name="gated_norm_fwd",
        # blocks twice (the pipeline's two buffers): x, z and y
        **_call_params(2 * block * lanes * (
            x.dtype.itemsize + 2 * z.dtype.itemsize)),
    )(x, z, gain.astype(F32).reshape(1, -1))


@functools.partial(jax.jit, static_argnames=(
    "group", "eps", "gate_first", "tile", "interpret", "activation"))
def _norm_bwd(x, z, gain, dy, group, eps, gate_first, tile, interpret,
              activation):
    from jax.experimental import pallas as pl

    (rows, width), (block, lanes, step) = x.shape, tile
    grid, specs = _specs(rows, width, block, lanes)
    dx, dz, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, group=group, eps=eps,
                          gate_first=gate_first, step=step, live_rows=rows,
                          activation=activation),
        grid=grid,
        in_specs=[specs["rows"], specs["rows"], specs["gain"], specs["rows"]],
        out_specs=[specs["rows"], specs["rows"], specs["sums"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((_SUM_ROWS, width), F32)],
        interpret=interpret,
        name="gated_norm_bwd",
        # blocks twice: x, z, dy, dx and dz
        **_call_params(2 * block * lanes * (
            2 * x.dtype.itemsize + 3 * z.dtype.itemsize)),
    )(x, z, gain.astype(F32).reshape(1, -1), dy)
    return dx, dz, jnp.sum(sums, axis=0).astype(gain.dtype)


def _flat(a):
    return a.reshape(-1, a.shape[-1])


def _forward(x, z, gain, group, eps, gate_first, tile, interpret,
             activation):
    return _norm_fwd(_flat(x), _flat(z), gain, group, eps, gate_first, tile,
                     interpret, activation).reshape(x.shape)


_gated_norm_kernels = jax.custom_vjp(
    _forward, nondiff_argnums=(3, 4, 5, 6, 7, 8))


def _kernels_fwd(x, z, gain, *static):
    # the inputs are the only residuals
    return _forward(x, z, gain, *static), (x, z, gain)


def _kernels_bwd(group, eps, gate_first, tile, interpret, activation, res,
                 dy):
    x, z, gain = res
    dx, dz, dgain = _norm_bwd(_flat(x), _flat(z), gain, _flat(dy), group, eps,
                              gate_first, tile, interpret, activation)
    return dx.reshape(x.shape), dz.reshape(z.shape), dgain


_gated_norm_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_norm(x: jax.Array, z: jax.Array, gain: jax.Array, *, group: int,
               eps: float, gate_first: bool, activation: str = "silu",
               backend: Optional[str] = None,
               interpret: bool = False) -> jax.Array:
    """The gated norm of the module docstring: ``x [B, ..., W]`` (float32 or
    bfloat16), ``z`` of ``x``'s shape, ``gain [W]`` -> ``[B, ..., W]`` in
    ``z``'s dtype.  ``gate_first``: gate then norm (else norm then gate);
    ``activation``: the gate's, of :data:`ACTIVATIONS`.
    By the kernel pair where the module's rule allows, one call per shard of
    the mesh in scope, else in ``jax.numpy``.  ``backend`` (``"pallas"`` /
    ``"reference"``; None: by the device) and ``interpret`` are for tests of
    the kernels on the CPU."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"gated_norm: activation={activation!r} is none of "
            f"{tuple(ACTIVATIONS)}")
    if backend != "pallas":
        return _reference(x, z, gain, group, eps, gate_first, activation)

    def shard(x, z, gain):
        tile = _tile(x.size // x.shape[-1], x.shape[-1], group,
                     (x.dtype, z.dtype))
        if tile is None:
            return _reference(x, z, gain, group, eps, gate_first, activation)
        return _gated_norm_kernels(x, z, gain, group, eps, gate_first, tile,
                                   interpret, activation)

    free, batch_axes, _ = shard_axes(x.shape[0])
    rows = Spec(batch_axes, *([None] * (x.ndim - 1)))
    return per_shard(shard, free, (rows, rows, Spec(None)), rows)(x, z, gain)
