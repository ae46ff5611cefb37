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
float32.

What a chunk's positions put out, given the state that enters the chunk —
the masked matmul, the only part with a ``[Q, Q]`` array for every head,
plus ``exp(cs) C h_entering`` and ``D x`` (:func:`_chunk_outputs`) — runs on
a TPU as a Pallas kernel pair under one ``jax.custom_vjp``
(``ssd_chunk_fwd``, ``ssd_chunk_bwd``): one grid step per (batch row, chunk,
group, block of heads) forms ``C B^T`` once for the group, and each head's
``dt x``, the differences of the cumulative sums, their ``exp``, the causal
mask and the product rounded to ``x``'s dtype in VMEM, so no ``[Q, Q]``
array is written to or read from HBM in any phase; the backward recomputes
them from the same inputs, which are its only residuals.  ``x`` comes in,
and ``y`` and ``dx`` go out, channels-last — ``[B, S, G R P]``, the array as
:func:`ssd_chunked` holds it, reshaped — as the convolution's kernel in
front of the scan writes it and the gated norm behind it reads it
(``ops/conv_silu.py``, ``ops/gated_norm.py``): no copy stands between the
three, and a block arrives as the ``[Q, lanes]`` tile the kernels compute
on.  The rule (:func:`_kernel_heads`): ``jax.default_backend() == "tpu"``,
``chunk`` and ``N`` multiples of 128, and a block of a group's heads whose
``r * P`` is a multiple of 128 lanes (at most :data:`_BLOCK_LANES`; ``P`` a
divisor or a multiple of 128).  Any other shape or backend runs
:func:`_chunk_outputs`, the same arithmetic in plain ``jax.numpy``
(:func:`_intra_chunk` is its ``[Q, Q]`` part), differentiated by JAX and the
kernels' reference.  The cumulative sums, the states the chunks leave and
the scan over the chunks are ``jax.numpy`` everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.flash_attention import NEG_INF, _vmem_params
from dlrover_tpu.ops.per_shard import P as Spec, per_shard, shard_axes

#: lanes of ``x`` (heads x P) one grid step of the kernels takes: 8 heads
#: of 64.  A shape decision, not a knob: the rows a DMA moves are this long,
#: and the kernel's body is unrolled over the block's heads.
_BLOCK_LANES = 512


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


def _intra_chunk(xdt, cs, bc, cc):
    """``(C B^T o L)(dt x)`` of every head: ``xdt [B, c, Q, G, R, P]``,
    ``cs [B, c, Q, G, R]`` (inclusive cumulative sums of ``dt A``), ``bc``,
    ``cc [B, c, Q, G, N]`` -> float32 ``[B, c, Q, G, R, P]``."""
    Q = cs.shape[2]
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                    preferred_element_type=jnp.float32)
    at = cs.transpose(0, 1, 3, 4, 2)  # [B, c, G, R, Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # masked inside the exp too: above the diagonal the sum is positive
    # and may overflow, and inf * 0 has no gradient
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, at[..., :, None] - at[..., None, :], 0.0)), 0.0)
    m = (cb[:, :, :, None] * decay).astype(xdt.dtype)
    return jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xdt,
                      preferred_element_type=jnp.float32)


def _chunk_outputs(x, dt, cs, bc, cc, entering, D):
    """What a chunk's positions put out, given the state that enters the
    chunk: ``(C B^T o L)(dt x) + exp(cs) C h_entering + D x``.  ``x [B, c,
    Q, G, R, P]``, ``dt``, ``cs [B, c, Q, G, R]`` float32, ``bc``, ``cc [B,
    c, Q, G, N]``, ``entering [c, B, G, R, P, N]`` in ``x``'s dtype, ``D
    [G, R]`` float32 -> float32 ``[B, c, Q, G, R, P]``."""
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype)
    y = _intra_chunk(xdt, cs, bc, cc)
    y = y + jnp.einsum("bcign,cbgrpn->bcigrp", cc, entering,
                       preferred_element_type=f32) * jnp.exp(cs)[..., None]
    return y + x.astype(f32) * D[:, :, None]


# -- the same as a Pallas kernel pair ------------------------------------------
#
# Layouts, per grid step (b, chunk, g, block j of the group's heads; hb heads
# a block, W = hb * P lanes).  In HBM ``x``, ``y``, ``dy`` and ``dx`` are ``[B,
# c Q, G R P]``, channels last: a block is ``[Q, W]`` at ``(b, chunk, g J +
# j)``, heads side by side along the lanes, a DMA row W lanes long, and the
# kernels work on it as it comes.  What is one number a head and position
# comes in ready for its broadcast, so nothing is transposed in the kernel:
# the cumulative sums twice, ``[hb, Q]`` (a head's row broadcasts down the
# sublanes) and ``[Q, hb]`` (its column broadcasts along the lanes), ``dt``
# ``[Q, hb]``.  ``B``, ``C`` [Q, N]; the entering state ``[W, N]``; ``D``
# spread over its head's lanes ``[1, W]``.  A head narrower than 128 lanes
# shares a 128-lane unit with its neighbours: each head's matmul runs over the
# whole unit (the MXU is 128 wide either way) and a lane mask keeps its own
# part.


def _kernel_heads(Q: int, R: int, P: int, N: int) -> int:
    """Heads of a group one grid step takes, 0 where the kernels do not
    tile the shapes: the largest divisor ``r`` of ``R`` with ``r * P`` a
    multiple of 128 and at most :data:`_BLOCK_LANES` (one unit where a
    single head is wider)."""
    if Q % 128 or N % 128 or (P % 128 and 128 % P):
        return 0
    fits = [r for r in range(1, R + 1) if R % r == 0 and (r * P) % 128 == 0
            and r * P <= max(_BLOCK_LANES, P)]
    return max(fits, default=0)


def _units(hb: int, P: int):
    """``(lanes of a unit, heads in it, units a block)``."""
    per = max(1, 128 // P)
    return per * P, per, hb // per


def _lower(Q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _head_lanes(width: int, P: int, k: int):
    """``[1, width]`` mask of the lanes of a unit's ``k``-th head."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= k * P) & (lane < (k + 1) * P)


def _spread(cols, first: int, per: int, width: int, P: int):
    """``cols [Q, hb]``, one column a head -> ``[Q, width]`` (or ``[Q, 1]``,
    which broadcasts): a unit's heads' columns, each along its own lanes."""
    out = cols[:, first:first + 1]
    for k in range(1, per):
        out = jnp.where(_head_lanes(width, P, k),
                        cols[:, first + k:first + k + 1], out)
    return out


def _nt(a, b):
    """``a b^T``, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b``, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _decay(row_ref, col_ref, h: int, lower):
    """``L`` of the block's head ``h``, float32 ``[Q, Q]``: the mask goes
    inside the ``exp`` (a positive sum above the diagonal never forms) and
    ``exp`` of the filler is 0 exactly."""
    diff = col_ref[:, h:h + 1] - row_ref[h:h + 1, :]
    return jnp.exp(jnp.where(lower, diff, NEG_INF))


def _fwd_kernel(x_ref, dt_ref, row_ref, col_ref, b_ref, c_ref, ent_ref,
                d_ref, y_ref, cb_ref, *, P):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(3) == 0)
    def _new_group():
        cb_ref[...] = _nt(c_ref[...], b_ref[...])

    width, per, units = _units(row_ref.shape[0], P)
    lower = _lower(cb_ref.shape[0])
    cb, dt = cb_ref[...], dt_ref[...]
    from_state = _nt(c_ref[...], ent_ref[...])  # [Q, W]: C h_entering
    grown = jnp.exp(col_ref[...])  # [Q, hb]
    for u in range(units):
        lanes = slice(u * width, (u + 1) * width)
        x32 = x_ref[:, lanes].astype(jnp.float32)
        xu = (x32 * _spread(dt, u * per, per, width, P)).astype(x_ref.dtype)
        y = None
        for k in range(per):
            m = (cb * _decay(row_ref, col_ref, u * per + k, lower)).astype(
                xu.dtype)
            yk = jnp.dot(m, xu, preferred_element_type=jnp.float32)
            y = yk if y is None else jnp.where(
                _head_lanes(width, P, k), yk, y)
        y_ref[:, lanes] = (
            y + from_state[:, lanes] * _spread(grown, u * per, per, width, P)
            + x32 * d_ref[:, lanes])


def _bwd_kernel(x_ref, dt_ref, row_ref, col_ref, b_ref, c_ref, ent_ref, d_ref,
                dy_ref, seg_ref, dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref,
                dent_ref, dd_ref, cb_ref, dcb_ref, dcs_acc, ddt_acc, g_acc,
                dc_acc, *, P):
    """With ``M = C B^T o L`` of one head, ``m`` its rounding, ``xdt`` the
    rounded ``dt x`` and ``dm = dy xdt^T``: ``dxdt = m^T dy``; ``d(C B^T) =
    sum over heads of dm o L``, then ``dC = d(CB^T) B`` and ``dB =
    d(CB^T)^T C``; and the cotangent of the cumulative sums, row sums less
    column sums of ``dm o M``, without a ``[Q, Q]`` reduction: the row sums
    are ``sum_p dy o (m xdt)`` and the column sums ``sum_p xdt o dxdt``, so
    ``[Q, W]`` products summed over each head's lanes by one float32 matmul
    against the 0/1 matrix ``seg``.  The entering state's term ``g = dy o
    exp(cs)``: ``dC += g h``, ``dh = g^T C``, ``dcs += sum_p g o (C h)``.
    ``dx = dxdt o dt + dy o D``, ``ddt = sum_p dxdt o x``, ``dD = sum over
    positions of dy o x`` (the caller sums it over each head's lanes)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(3)

    @pl.when(j == 0)
    def _new_group():
        cb_ref[...] = _nt(c_ref[...], b_ref[...])
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    f32 = jnp.float32
    hb = row_ref.shape[0]
    width, per, units = _units(hb, P)
    lower = _lower(cb_ref.shape[0])
    cb, dt = cb_ref[...], dt_ref[...]
    from_state = _nt(c_ref[...], ent_ref[...])
    grown = jnp.exp(col_ref[...])
    for u in range(units):
        lanes = slice(u * width, (u + 1) * width)
        x32 = x_ref[:, lanes].astype(f32)
        dtu = _spread(dt, u * per, per, width, P)
        xu = (x32 * dtu).astype(x_ref.dtype)
        dy32 = dy_ref[:, lanes]
        # rounded once, for the matmuls AND the sums below: row and column
        # sums of the same products must cancel over a chunk
        dyu = dy32.astype(xu.dtype)
        y = dxdt = None
        for k in range(per):
            mine = _head_lanes(width, P, k)
            decay = _decay(row_ref, col_ref, u * per + k, lower)
            m = (cb * decay).astype(xu.dtype)
            dm = _nt(dyu if per == 1 else jnp.where(mine, dyu, 0), xu)
            dcb_ref[...] += dm * decay
            yk = jnp.dot(m, xu, preferred_element_type=f32)
            dxk = _tn(m, dyu)  # m^T dy
            y = yk if y is None else jnp.where(mine, yk, y)
            dxdt = dxk if dxdt is None else jnp.where(mine, dxk, dxdt)
        g = dy32 * _spread(grown, u * per, per, width, P)
        g_acc[:, lanes] = g.astype(g_acc.dtype)
        dcs_acc[:, lanes] = (dyu.astype(f32) * y - xu.astype(f32) * dxdt
                             + g * from_state[:, lanes])
        ddt_acc[:, lanes] = dxdt * x32
        dx_ref[:, lanes] = (dxdt * dtu + dy32 * d_ref[:, lanes]).astype(
            dx_ref.dtype)
        dd_ref[:, lanes] = jnp.sum(dy32 * x32, axis=0, keepdims=True)
    over_lanes = functools.partial(
        jnp.dot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)
    dcs_ref[...] = over_lanes(dcs_acc[...], seg_ref[...])[:, :hb]
    ddt_ref[...] = over_lanes(ddt_acc[...], seg_ref[...])[:, :hb]
    dc_acc[...] += jnp.dot(g_acc[...], ent_ref[...],
                           preferred_element_type=f32)
    dent_ref[...] = _tn(g_acc[...], c_ref[...]).astype(dent_ref.dtype)

    @pl.when(j == pl.num_programs(3) - 1)
    def _group_done():
        dcb = dcb_ref[...].astype(b_ref.dtype)
        dc_ref[...] = (jnp.dot(dcb, b_ref[...], preferred_element_type=f32)
                       + dc_acc[...]).astype(dc_ref.dtype)
        db_ref[...] = _tn(dcb, c_ref[...]).astype(db_ref.dtype)


def _kernel_operands(x, dt, cs, bc, cc, entering, D, hb):
    """The arrays as the kernels take them, and the grid's block specs."""
    from jax.experimental import pallas as pl

    B, c, Q, G, R, P = x.shape
    N, J, W = bc.shape[-1], R // hb, hb * P
    cols = lambda a: a.reshape(B, c, Q, G, J, hb).transpose(  # noqa: E731
        0, 1, 3, 4, 2, 5)
    by_group = lambda a: a.transpose(0, 1, 3, 2, 4)  # noqa: E731
    arrays = dict(
        x=x.reshape(B, c * Q, G * R * P), dt=cols(dt), col=cols(cs),
        row=cs.reshape(B, c, Q, G, J, hb).transpose(0, 1, 3, 4, 5, 2),
        b=by_group(bc), c=by_group(cc),
        ent=entering.reshape(c, B, G, J, W, N),
        d=jnp.repeat(D.astype(jnp.float32), P, axis=-1).reshape(G, J, 1, W))
    a_block = lambda b, i, g, j: (b, i, g, j, 0, 0)  # noqa: E731
    col = pl.BlockSpec((None, None, None, None, Q, hb), a_block)
    group = pl.BlockSpec((None, None, None, Q, N),
                         lambda b, i, g, j: (b, i, g, 0, 0))
    specs = dict(
        x=pl.BlockSpec((None, Q, W), lambda b, i, g, j: (b, i, g * J + j)),
        dt=col, col=col, b=group, c=group,
        row=pl.BlockSpec((None, None, None, None, hb, Q), a_block),
        ent=pl.BlockSpec((None, None, None, None, W, N),
                         lambda b, i, g, j: (i, b, g, j, 0, 0)),
        d=pl.BlockSpec((None, None, 1, W), lambda b, i, g, j: (g, j, 0, 0)))
    return arrays, specs, (B, c, G, J)


_INPUTS = ("x", "dt", "row", "col", "b", "c", "ent", "d")


def _call_params(resident_bytes: int) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    # the head blocks of a group run in turn: they share its C B^T
    raised = _vmem_params(resident_bytes).get("compiler_params")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3 + ("arbitrary",),
        vmem_limit_bytes=raised and raised.vmem_limit_bytes)}


def _chunk_fwd(x, dt, cs, bc, cc, entering, D, hb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, Q, _, _, P = x.shape
    N, W, size = bc.shape[-1], hb * P, x.dtype.itemsize
    arrays, specs, grid = _kernel_operands(x, dt, cs, bc, cc, entering, D, hb)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P),
        grid=grid,
        in_specs=[specs[n] for n in _INPUTS],
        out_specs=specs["x"],
        out_shape=jax.ShapeDtypeStruct(arrays["x"].shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((Q, Q), jnp.float32)],
        interpret=interpret,
        name="ssd_chunk_fwd",
        # blocks twice (the pipeline's two buffers), C B^T, the entering
        # state's [Q, W] term and a head's [Q, Q] temporaries: the
        # difference, L, the product and its rounding
        **_call_params(2 * (Q * W * (size + 4) + (2 * Q + W) * N * size)
                       + Q * W * 4 + 5 * Q * Q * 4),
    )(*(arrays[n] for n in _INPUTS))
    return y.reshape(x.shape)


def _chunk_bwd(x, dt, cs, bc, cc, entering, D, dy, hb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, c, Q, G, R, P = x.shape
    N, J, W, size = bc.shape[-1], R // hb, hb * P, x.dtype.itemsize
    f32 = jnp.float32
    arrays, specs, grid = _kernel_operands(x, dt, cs, bc, cc, entering, D, hb)
    arrays["dy"] = dy.astype(f32).reshape(arrays["x"].shape)
    specs["dy"] = specs["x"]
    # seg[l, h] = 1 where lane l is head h's, 128 columns for the MXU
    arrays["seg"] = (jnp.arange(W)[:, None] // P
                     == jnp.arange(128)[None, :]).astype(f32)
    specs["seg"] = pl.BlockSpec((W, 128), lambda b, i, g, j: (0, 0))
    names = _INPUTS + ("dy", "seg")
    like = lambda n, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        arrays[n].shape, dtype)
    dx, ddt, dcs, db, dc, dent, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P),
        grid=grid,
        in_specs=[specs[n] for n in names],
        out_specs=[specs[n] for n in ("x", "dt", "col", "b", "c", "ent")] + [
            pl.BlockSpec((None, None, None, None, 1, W),
                         lambda b, i, g, j: (b, i, g, j, 0, 0))],
        out_shape=[like("x", x.dtype), like("dt", f32), like("col", f32),
                   like("b", bc.dtype), like("c", cc.dtype),
                   like("ent", entering.dtype),
                   jax.ShapeDtypeStruct((B, c, G, J, 1, W), f32)],
        scratch_shapes=[pltpu.VMEM((Q, Q), f32), pltpu.VMEM((Q, Q), f32),
                        pltpu.VMEM((Q, W), f32), pltpu.VMEM((Q, W), f32),
                        pltpu.VMEM((Q, W), x.dtype), pltpu.VMEM((Q, N), f32)],
        interpret=interpret,
        name="ssd_chunk_bwd",
        **_call_params(2 * (Q * W * (2 * size + 4)
                            + (4 * Q + 2 * W) * N * size)
                       + Q * W * (12 + size) + W * 128 * 4 + 8 * Q * Q * 4),
    )(*(arrays[n] for n in names))
    rows = lambda a: a.transpose(0, 1, 4, 2, 3, 5).reshape(  # noqa: E731
        cs.shape)
    by_group = lambda a: a.transpose(0, 1, 3, 2, 4)  # noqa: E731
    return (dx.reshape(x.shape), rows(ddt), rows(dcs), by_group(db),
            by_group(dc), dent.reshape(entering.shape),
            dd.reshape(B, c, G, R, P).sum((0, 1, 4)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chunk_outputs_kernels(x, dt, cs, bc, cc, entering, D, hb, interpret):
    return _chunk_fwd(x, dt, cs, bc, cc, entering, D, hb, interpret)


def _kernels_fwd(*args):
    return _chunk_fwd(*args), args[:7]  # the inputs are the only residuals


def _kernels_bwd(hb, interpret, res, dy):
    return _chunk_bwd(*res, dy, hb, interpret)


_chunk_outputs_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _block_heads(backend: Optional[str], Q: int, R: int, P: int,
                 N: int) -> int:
    """:func:`_kernel_heads` where the kernels run (``backend`` None: by
    the device), else 0."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    return _kernel_heads(Q, R, P, N) if backend == "pallas" else 0


def chunk_outputs(x, dt, cs, bc, cc, entering, D, *,
                  backend: Optional[str] = None, interpret: bool = False):
    """:func:`_chunk_outputs` by the kernel pair where the module's rule
    allows, one call per shard of the mesh in scope (the batch dim split,
    as ``ops/rmsnorm.py``), else in ``jax.numpy``."""
    _, _, Q, _, R, P = x.shape
    hb = _block_heads(backend, Q, R, P, bc.shape[-1])
    if not hb:
        return _chunk_outputs(x, dt, cs, bc, cc, entering, D)
    free, batch_axes, _ = shard_axes(x.shape[0])
    rows = lambda a: Spec(batch_axes, *([None] * (a.ndim - 1)))  # noqa: E731
    specs = tuple(rows(a) for a in (x, dt, cs, bc, cc)) + (
        Spec(None, batch_axes, None, None, None, None), Spec(None, None))
    return per_shard(
        lambda *ops: _chunk_outputs_kernels(*ops, hb, interpret), free,
        specs, rows(x),
    )(x, dt, cs, bc, cc, entering, D)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, D=None, *,
                backend: Optional[str] = None, interpret: bool = False):
    """The chunked dual form -> ``(y [B, S, H, P] float32, final state
    [B, H, P, N] float32, least decay over a chunk, a float32 scalar)``.
    A sequence that ``chunk`` does not divide is padded with ``dt = 0``
    positions, which neither decay nor feed the state.  ``backend``
    (``"pallas"`` / ``"reference"``; None: by the device) and ``interpret``
    are for tests of the kernels on the CPU."""
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

    # -- the state each chunk leaves, and the scan over the chunks -----------
    total = cs[:, :, -1]  # [B, c, G, R]: the chunk's whole sum of dt A
    to_end = jnp.exp(total[:, :, None] - cs)  # decay from j to the end
    xs = xc
    if _block_heads(backend, Q, R, P, N):
        # The kernels pin ``x`` channels-last, and XLA wants the positions
        # minor in this einsum's operand, so it turns ``x`` for it.  Left
        # alone it turns ``x``'s float32 conversion (twice the bytes) and,
        # going back, turns the kernels' ``dx`` to add it in the einsum's
        # layout and turns the sum back.  Behind the two barriers it turns
        # the bfloat16 ``x`` once, and the two cotangents meet as ``[B, S, H
        # P]``, where the kernels' lies as it is.
        xs = jax.lax.optimization_barrier(jax.lax.optimization_barrier(
            x.reshape(Bsz, S + pad, H * P)).reshape(xc.shape))
    left = jnp.einsum(
        "bcjgrp,bcjgn->bcgrpn",
        (xs.astype(f32) * dtc[..., None] * to_end[..., None]).astype(x.dtype),
        bc,
        preferred_element_type=f32)
    chunk_decay = jnp.exp(total)

    def carry(h, inputs):
        decay, new = inputs
        return decay[..., None, None] * h + new, h

    final, entering = jax.lax.scan(
        carry, jnp.zeros((Bsz, G, R, P, N), f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(left, 1, 0)))

    # -- what each chunk puts out, given the state that enters it ------------
    y = chunk_outputs(
        xc, dtc, cs, bc, cc, entering.astype(x.dtype),
        (jnp.zeros((H,), f32) if D is None else D.astype(f32)).reshape(G, R),
        backend=backend, interpret=interpret)
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
