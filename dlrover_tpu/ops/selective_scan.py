"""The selective scan of Mamba-1 (S6, arXiv:2312.00752): a state-space
recurrence whose decay is a matrix over (channel, state), in a sequential
form (the op's own reference), a chunked ``jax.numpy`` form and, on a TPU, a
Pallas kernel pair, the last two under ONE ``jax.custom_vjp``.

The recurrence, per batch row with state ``s in R^{Dn x N}`` and ``s_0 = 0``::

    s_t = exp(dt_t (x) A) o s_{t-1} + (dt_t o x_t) (x) B_t
    y_t = s_t C_t + D o x_t

``x``, ``dt [B, S, Dn]`` (``dt`` float32, positive: the caller's softplus);
``A [Dn, N]`` float32, negative; ``Bm``, ``Cm [B, S, N]``, shared by all
channels; ``D [Dn]`` or None.  Every (channel, state) pair decays at a rate
of its own, ``exp(dt_t[d] A[d, n])``: there is no ``[Q, Q]`` dual of a chunk
as ``ops/ssd.py`` has for Mamba-2's one scalar a head, and the work is ``Dn
N`` state updates a token on the VPU and the EUP.  ``dt``, ``dt (x) A``,
every ``exp`` and the state stay float32 in every form.

:func:`selective_scan` splits a sequence into chunks of ``chunk`` positions
(a sequence it does not divide is padded with ``dt = 0`` positions, which
neither decay nor feed the state).  Going forward it keeps ``y`` and the
state that ENTERS each chunk (``[B, S / chunk, Dn, N]`` float32: 42 MB a
sequence of 16,384 at 5,120 x 16 and chunks of 128) under the names of
:data:`SAVED_NAMES`, and never an array along ``[.., S, Dn, N]``; going back
it walks the chunks from the last, rebuilds a chunk's ``chunk`` states from
the one that entered it, and walks the chunk's positions back with the
state's cotangent ``g_t = exp(dt_{t+1} (x) A) o g_{t+1} + dy_t (x) C_t``.
The residuals are the inputs and the entering states.  The final state
leaves under ``stop_gradient``: nothing differentiates through it.

The kernels (``s6_scan_fwd``, ``s6_scan_bwd``).  A block of 1,024 channels is
ONE vector register a state: ``[8, 128]``, channels on sublanes and lanes,
so the state of a block is ``N`` registers and a position's update is ``N``
multiply-adds on whole registers with no movement across lanes.  ``x``,
``dt``, ``y`` are viewed ``[B, S / Q, Q, Dn / 1024, 8, 128]`` (a free
reshape) and a grid step takes ``[Q, 8, 128]`` of each; ``B_t[n]`` and
``C_t[n]`` are scalars read from SMEM (``[1, Q N]`` a chunk) and splat;
``A`` comes ``[N, 8, 128]`` a block.  Time is walked INSIDE the kernel
(``lax.fori_loop`` over the chunk's positions, the state the loop's carry);
the chunk axis of the grid is sequential and carries the state in VMEM.  The
forward's grid is ``(batch row, channel block, chunk)``; the backward's
``(batch row, chunk from the last, channel block)``: it rebuilds the chunk's
states into VMEM (``(Q + 1) N`` registers), walks back, and forms what
needs a sum over channels — ``dC_t[n] = sum_d dy_t[d] s_t[d, n]`` and
``dB_t[n] = sum_d g_t[d, n] dt_t[d] x_t[d]`` — as unreduced products a
position and state in VMEM, sums their sublanes once a grid step by strided
reads and over a chunk's channel blocks in the output's block, and leaves the
lanes' sum to XLA (``[Q N, 128]`` a chunk).  The rule
(:func:`_kernels_tile`): ``jax.default_backend() == "tpu"``, ``Dn`` a
multiple of 1,024 and ``N`` at most 32.  Any other shape or backend runs the
``jax.numpy`` form: a ``lax.scan`` over the chunks of a
``lax.scan`` over a chunk's positions, whose backward is ``jax.vjp`` of the
chunk's forward at its entering state — derived, not written a second
time, and so the kernels' reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.per_shard import P as Spec, per_shard, shard_axes

#: positions a chunk holds: a shape decision of the op, not a setting.  The
#: backward kernel keeps ``2 chunk N`` registers of 4 KB in VMEM (16 MB at
#: ``N`` 16) and the entering states cost ``S / chunk`` states a sequence.
CHUNK = 128
#: what block remat keeps of the scan: its output and the states that enter
#: the chunks (``llama.forward_hidden``'s policy), so that the forward
#: kernel does not run again in front of the backward
SAVED_NAMES = ("s6_out", "s6_entering")
#: channels a grid step of the kernels takes: one ``[8, 128]`` register
_BLOCK = 1024
_F32 = jnp.float32


def selective_scan_sequential(x, dt, A, Bm, Cm, D=None):
    """The recurrence as written, one position at a time in float32 ->
    ``(y [B, S, Dn], final state [B, Dn, N])``.  The chunked form's
    reference; nothing trains through it."""
    A = A.astype(_F32)

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, Dn] x 2, [B, N] x 2
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    seq_first = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)  # noqa: E731
    final, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + A.shape, _F32),
        (seq_first(x), seq_first(dt), seq_first(Bm), seq_first(Cm)))
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + x.astype(_F32) * D.astype(_F32)
    return y, final


# -- the chunked form in jax.numpy ---------------------------------------------
#
# Operands, here and for the kernels: ``x``, ``dt [B, c, Q, Dn]``, ``bc``, ``cc
# [B, c, Q, N]``, all float32; ``A [Dn, N]``, ``D [Dn]``.


def _chunk(s, x, dt, b, c, A, D):
    """One chunk from the state that enters it: ``s [B, Dn, N]``, ``x``, ``dt
    [B, Q, Dn]``, ``b``, ``c [B, Q, N]`` -> ``(state it leaves, y [B, Q,
    Dn])``."""
    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * x_t

    s, y = jax.lax.scan(
        step, s, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, b, c)))
    return s, jnp.moveaxis(y, 0, 1)


def _chunks_first(*arrays):
    return tuple(jnp.moveaxis(a, 1, 0) for a in arrays)


def _fwd_jnp(x, dt, A, bc, cc, D):
    """``(y [B, c, Q, Dn], entering [B, c, Dn, N], final [B, Dn, N])``."""
    def carry(s, inputs):
        left, y = _chunk(s, *inputs, A, D)
        return left, (y, s)

    final, (y, entering) = jax.lax.scan(
        carry, jnp.zeros(x.shape[:1] + A.shape, _F32),
        _chunks_first(x, dt, bc, cc))
    return jnp.moveaxis(y, 0, 1), jnp.moveaxis(entering, 0, 1), final


def _bwd_jnp(x, dt, A, bc, cc, D, entering, dy):
    """The cotangents of ``(x, dt, A, bc, cc, D)``: the chunks from the
    last, each through ``jax.vjp`` of :func:`_chunk` at its entering state
    (which rebuilds the chunk's states and nothing else)."""
    def carry(acc, inputs):
        g, dA, dD = acc
        xc, dtc, bcc, ccc, ent, dyc = inputs
        g, dxc, ddtc, dbc, dcc, dA_c, dD_c = jax.vjp(
            _chunk, ent, xc, dtc, bcc, ccc, A, D)[1]((g, dyc))
        return (g, dA + dA_c, dD + dD_c), (dxc, ddtc, dbc, dcc)

    (_, dA, dD), per_chunk = jax.lax.scan(
        carry, (jnp.zeros(x.shape[:1] + A.shape, _F32), jnp.zeros_like(A),
                jnp.zeros_like(D)),
        _chunks_first(x, dt, bc, cc, entering, dy), reverse=True)
    dx, ddt, dbc, dcc = (jnp.moveaxis(a, 0, 1) for a in per_chunk)
    return dx, ddt, dA, dbc, dcc, dD


# -- the same as a Pallas kernel pair ------------------------------------------
#
# Layouts, per grid step: ``x``, ``dt``, ``y``, ``dy``, ``dx``, ``ddt`` ``[Q,
# 8, 128]`` (a position a register); ``A``, a state, a state's cotangent ``[N,
# 8, 128]``; ``D [8, 128]``; ``B`` and ``C`` of the chunk in SMEM, ``[1, Q N]``
# with position ``t``'s ``N`` values from ``t N``.


def _kernels_tile(Dn: int, N: int) -> bool:
    """Whether the kernels tile the shapes: whole registers of channels, and
    a state's registers few enough for a loop's carry."""
    return Dn % _BLOCK == 0 and 0 < N <= 32


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, ent_ref,
                fin_ref, s_ref):
    from jax.experimental import pallas as pl

    Q, N = x_ref.shape[0], a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _new_sequence():
        s_ref[...] = jnp.zeros_like(s_ref)

    ent_ref[...] = s_ref[...]
    a = [a_ref[n] for n in range(N)]
    d = d_ref[...]

    def step(t, s):
        dt_t, x_t = dt_ref[t], x_ref[t]
        dtx, y, out = dt_t * x_t, d * x_t, []
        for n in range(N):
            s_n = jnp.exp(dt_t * a[n]) * s[n] + dtx * b_ref[0, t * N + n]
            y = y + s_n * c_ref[0, t * N + n]
            out.append(s_n)
        y_ref[t] = y
        return tuple(out)

    s = jax.lax.fori_loop(0, Q, step, tuple(s_ref[n] for n in range(N)))
    for n in range(N):
        s_ref[n] = s[n]
    fin_ref[...] = s_ref[...]


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, ent_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, dbp_ref, dcp_ref,
                g_ref, da_acc, dd_acc, s_ref, p_ref):
    """With ``E_t = exp(dt_t (x) A)`` and ``g_t`` the cotangent of ``s_t``
    (``g_t = E_{t+1} o g_{t+1} + dy_t (x) C_t``): ``dC_t = sum_d dy_t o
    s_t``, ``dB_t = sum_d g_t o dt_t x_t``, ``w = g_t o s_{t-1} o E_t`` (the
    cotangent of ``dt_t (x) A``), ``dA = sum_t w o dt_t``, ``ddt_t = sum_n w
    o A + x_t sum_n g_t B_t``, ``dx_t = dt_t sum_n g_t B_t + D dy_t``, ``dD =
    sum_t dy_t x_t``.  ``s_ref`` holds the chunk's states, slot ``t`` the one
    that enters position ``t``; slot ``t + 1`` is overwritten by position
    ``t``'s ``dy_t o s_t`` once read, ``p_ref`` takes ``g_t o dt_t x_t``."""
    from jax.experimental import pallas as pl

    Q, N = x_ref.shape[0], a_ref.shape[0]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        g_ref[j] = jnp.zeros(g_ref.shape[1:], _F32)
        da_acc[j] = jnp.zeros(da_acc.shape[1:], _F32)
        dd_acc[j] = jnp.zeros(dd_acc.shape[1:], _F32)

    @pl.when(j == 0)
    def _first_block():
        dbp_ref[...] = jnp.zeros_like(dbp_ref)
        dcp_ref[...] = jnp.zeros_like(dcp_ref)

    a = [a_ref[n] for n in range(N)]
    d = d_ref[...]
    rows = N * 8  # sublanes of s_ref and p_ref a position

    def slot(t, n):
        return pl.ds(pl.multiple_of((t * N + n) * 8, 8), 8)

    for n in range(N):
        s_ref[slot(0, n), :] = ent_ref[n]

    def rebuild(t, s):
        dt_t = dt_ref[t]
        dtx, out = dt_t * x_ref[t], []
        for n in range(N):
            s_n = jnp.exp(dt_t * a[n]) * s[n] + dtx * b_ref[0, t * N + n]
            s_ref[slot(t + 1, n), :] = s_n
            out.append(s_n)
        return tuple(out)

    jax.lax.fori_loop(0, Q, rebuild, tuple(ent_ref[n] for n in range(N)))

    def back(k, carry):
        g, dd = carry
        t = Q - 1 - k
        dt_t, x_t, dy_t = dt_ref[t], x_ref[t], dy_ref[t]
        dtx = dt_t * x_t
        ddt = from_b = jnp.zeros_like(dt_t)
        out = []
        for n in range(N):
            g_n = g[n] + dy_t * c_ref[0, t * N + n]
            s_t = s_ref[slot(t + 1, n), :]
            s_ref[slot(t + 1, n), :] = dy_t * s_t
            grown = jnp.exp(dt_t * a[n])
            w = g_n * s_ref[slot(t, n), :] * grown
            da_acc[j, n] += w * dt_t
            ddt = ddt + w * a[n]
            from_b = from_b + g_n * b_ref[0, t * N + n]
            p_ref[slot(t, n), :] = g_n * dtx
            out.append(grown * g_n)
        ddt_ref[t] = ddt + from_b * x_t
        dx_ref[t] = from_b * dt_t + d * dy_t
        return tuple(out), dd + dy_t * x_t

    g, dd = jax.lax.fori_loop(
        0, Q, back, (tuple(g_ref[j, n] for n in range(N)), dd_acc[j]))
    for n in range(N):
        g_ref[j, n] = g[n]
    dd_acc[j] = dd
    # the running sums: the last visit of a block writes the whole sum
    da_ref[...] = da_acc[j]
    dd_ref[...] = dd
    # a position and state's eight sublanes summed by eight strided reads
    # (the blocks of a chunk run in turn and add up in the output's block)
    dcp, dbp = dcp_ref[...], dbp_ref[...]
    for k in range(8):
        dcp = dcp + s_ref[pl.ds(rows + k, Q * N, stride=8), :]
        dbp = dbp + p_ref[pl.ds(k, Q * N, stride=8), :]
    dcp_ref[...] = dcp
    dbp_ref[...] = dbp


def _kernel_operands(x, dt, A, bc, cc, D):
    """The arrays as the kernels take them."""
    B, c, Q, Dn = x.shape
    N, J = A.shape[1], Dn // _BLOCK
    tiles = lambda a: a.reshape(B, c, Q, J, 8, 128)  # noqa: E731
    flat = lambda a: a.reshape(B, c, 1, Q * N)  # noqa: E731
    return (tiles(x), tiles(dt), A.T.reshape(N, J, 8, 128).transpose(
        1, 0, 2, 3), D.reshape(J, 8, 128), flat(bc), flat(cc))


def _specs(Q: int, N: int, at):
    """Block specs of ``(a position's tile, A, D, B or C in SMEM, a state)``;
    ``at(*grid ids) -> (b, chunk, block)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def tile(*ids):
        b, i, j = at(*ids)
        return (b, i, 0, j, 0, 0)

    def state(*ids):
        b, i, j = at(*ids)
        return (b, i, j, 0, 0, 0)

    return (
        pl.BlockSpec((None, None, Q, None, 8, 128), tile),
        pl.BlockSpec((None, N, 8, 128), lambda *ids: (at(*ids)[2], 0, 0, 0)),
        pl.BlockSpec((None, 8, 128), lambda *ids: (at(*ids)[2], 0, 0)),
        pl.BlockSpec((None, None, 1, Q * N),
                     lambda *ids: at(*ids)[:2] + (0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((None, None, None, N, 8, 128), state))


def _vmem_limit(resident_bytes: int) -> int:
    """What a kernel holds plus room for its temporaries, and never under
    32 MiB (the compiler's own 16 is short of the backward's two buffers)."""
    return max(int(resident_bytes * 1.25) + 8 * 2 ** 20, 32 * 2 ** 20)


def _fwd_kernels(x, dt, A, bc, cc, D, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, c, Q, Dn = x.shape
    N, J = A.shape[1], Dn // _BLOCK
    tile, a_spec, d_spec, smem, state = _specs(
        Q, N, lambda b, j, i: (b, i, j))
    y, entering, final = pl.pallas_call(
        _fwd_kernel,
        grid=(B, J, c),
        in_specs=[tile, tile, a_spec, d_spec, smem, smem],
        out_specs=[tile, state, pl.BlockSpec(
            (None, None, N, 8, 128), lambda b, j, i: (b, j, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, c, Q, J, 8, 128), _F32),
                   jax.ShapeDtypeStruct((B, c, J, N, 8, 128), _F32),
                   jax.ShapeDtypeStruct((B, J, N, 8, 128), _F32)],
        scratch_shapes=[pltpu.VMEM((N, 8, 128), _F32)],
        interpret=interpret,
        name="s6_scan_fwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(6 * Q * 4096 + 8 * N * 4096)),
    )(*_kernel_operands(x, dt, A, bc, cc, D))
    return y.reshape(x.shape), _states(entering), _states(final)


def _states(s):
    """``[.., J, N, 8, 128] -> [.., Dn, N]``."""
    lead = s.shape[:-4]
    J, N = s.shape[-4:-2]
    return jnp.moveaxis(s.reshape(lead + (J, N, _BLOCK)), -2, -1).reshape(
        lead + (J * _BLOCK, N))


def _bwd_kernels(x, dt, A, bc, cc, D, entering, dy, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, c, Q, Dn = x.shape
    N, J = A.shape[1], Dn // _BLOCK
    tile, a_spec, d_spec, smem, state = _specs(
        Q, N, lambda b, i, j: (b, c - 1 - i, j))
    xs, dts, As, Ds, bs, cs = _kernel_operands(x, dt, A, bc, cc, D)
    ent = jnp.moveaxis(
        entering.reshape(B, c, J, _BLOCK, N), -1, -2).reshape(
            B, c, J, N, 8, 128)
    partial = pl.BlockSpec((None, None, Q * N, 128),
                           lambda b, i, j: (b, c - 1 - i, 0, 0))
    # the chunk's states and the products for ``dB``, the blocks twice,
    # the two partial outputs, the carried cotangents and sums
    resident = ((2 * Q + 1) * N * 4096 + 10 * Q * 4096 + 4 * Q * N * 512
                + (3 * J + 8) * N * 4096)
    dx, ddt, dA, dD, dbp, dcp = pl.pallas_call(
        _bwd_kernel,
        grid=(B, c, J),
        in_specs=[tile, tile, a_spec, d_spec, smem, smem, state, tile],
        out_specs=[tile, tile,
                   pl.BlockSpec((None, None, N, 8, 128),
                                lambda b, i, j: (b, j, 0, 0, 0)),
                   pl.BlockSpec((None, None, 8, 128),
                                lambda b, i, j: (b, j, 0, 0)),
                   partial, partial],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, _F32),
                   jax.ShapeDtypeStruct(xs.shape, _F32),
                   jax.ShapeDtypeStruct((B, J, N, 8, 128), _F32),
                   jax.ShapeDtypeStruct((B, J, 8, 128), _F32),
                   jax.ShapeDtypeStruct((B, c, Q * N, 128), _F32),
                   jax.ShapeDtypeStruct((B, c, Q * N, 128), _F32)],
        scratch_shapes=[pltpu.VMEM((J, N, 8, 128), _F32),
                        pltpu.VMEM((J, N, 8, 128), _F32),
                        pltpu.VMEM((J, 8, 128), _F32),
                        pltpu.VMEM(((Q + 1) * N * 8, 128), _F32),
                        pltpu.VMEM((Q * N * 8, 128), _F32)],
        interpret=interpret,
        name="s6_scan_bwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(resident)),
    )(xs, dts, As, Ds, bs, cs, ent, dy.reshape(xs.shape))
    over = lambda p: p.sum(-1).reshape(bc.shape)  # noqa: E731
    return (dx.reshape(x.shape), ddt.reshape(x.shape),
            _states(dA.sum(0)), over(dbp), over(dcp),
            dD.sum(0).reshape(Dn))


def _forward(operands, kernels, interpret):
    """``(y, entering states, final state)`` by the kernel or in
    ``jax.numpy``."""
    if kernels:
        return _fwd_kernels(*operands, interpret)
    return _fwd_jnp(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_chunks(x, dt, A, bc, cc, D, kernels, interpret):
    """``(y, final state)`` of chunked operands by the kernel pair
    (``kernels``) or in ``jax.numpy``."""
    y, _, final = _forward((x, dt, A, bc, cc, D), kernels, interpret)
    return y, final


def _scan_chunks_fwd(x, dt, A, bc, cc, D, kernels, interpret):
    operands = (x, dt, A, bc, cc, D)
    y, entering, final = _forward(operands, kernels, interpret)
    y, entering = map(checkpoint_name, (y, entering), SAVED_NAMES)
    return (y, final), (*operands, entering)


def _scan_chunks_bwd(kernels, interpret, res, cotangents):
    dy, _ = cotangents  # the final state leaves under stop_gradient
    if kernels:
        return _bwd_kernels(*res, dy, interpret)
    return _bwd_jnp(*res, dy)


_scan_chunks.defvjp(_scan_chunks_fwd, _scan_chunks_bwd)


def selective_scan(x, dt, A, Bm, Cm, D=None, *, chunk: Optional[int] = None,
                   backend: Optional[str] = None, interpret: bool = False):
    """The chunked form -> ``(y [B, S, Dn] float32, final state [B, Dn, N]
    float32 under stop_gradient, least decay over a chunk, a float32
    scalar: 0 says a (channel, state) pair's decay underflowed float32 in a
    chunk, which the recurrence allows)``.  The cotangents come back in the
    operands' dtypes.  ``chunk`` (None: :data:`CHUNK`) is for tests of the
    ``jax.numpy`` form; the kernels take :data:`CHUNK` alone.  ``backend``
    (``"pallas"`` / ``"reference"``; None: by the device) and ``interpret``
    are for tests of the kernels on the CPU.  Under a mesh the kernels run
    once per shard of the batch dim (``ops/per_shard.py``)."""
    Bsz, S, Dn = x.shape
    N = A.shape[1]
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    kernels = (backend == "pallas" and chunk in (None, CHUNK)
               and _kernels_tile(Dn, N))
    Q = chunk or CHUNK
    pad = -S % Q
    c = (S + pad) // Q

    def chunked(a):
        a = a.astype(_F32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return a.reshape(Bsz, c, Q, a.shape[-1])

    operands = (chunked(x), chunked(dt), A.astype(_F32), chunked(Bm),
                chunked(Cm),
                jnp.zeros((Dn,), _F32) if D is None else D.astype(_F32))
    run = lambda *ops: _scan_chunks(*ops, kernels, interpret)  # noqa: E731
    if kernels:
        free, batch_axes, _ = shard_axes(Bsz)
        rows = Spec(batch_axes, None, None, None)
        run = per_shard(
            run, free, (rows, rows, Spec(None, None), rows, rows, Spec(None)),
            (rows, Spec(batch_axes, None, None)))
    y, final = run(*operands)
    # the least decay a chunk applies: its sum of dt times the most
    # negative rate of the channel
    least = jnp.min(jnp.sum(operands[1], axis=2) * jnp.min(operands[2], -1))
    return (y.reshape(Bsz, S + pad, Dn)[:, :S],
            jax.lax.stop_gradient(final),
            jax.lax.stop_gradient(jnp.exp(least)))
