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
already applied; the per-channel rule can take them raw, ``unit_scales``
below), ``v [B, S, H, Dv]``, ``g [B, S, H]`` float32 and never
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

and the state passes from chunk to chunk.  Where float32 stays: ``g``,
``gamma``, every ``exp``, ``A``, ``T``, ``W`` and ``U`` (their products at
``highest`` matmul precision: a TPU's default rounds float32 operands to
bfloat16) and the state ``S``.  Every exponent is a difference ``gamma_i -
gamma_j`` with ``i >= j`` (or ``gamma`` itself), so never positive: a head
may decay by ``e^-21`` a token and ``e^-1340`` a chunk, ``exp(-gamma)`` alone
would overflow, and the difference is MASKED before the ``exp`` so that no
``inf * 0`` forms in the backward pass.  The matmuls against the state (``W
S``, ``(q exp(gamma)) S``, ``k^T u``) and the masked ``q k^T`` product against
``u`` take operands rounded to ``v``'s dtype and accumulate in float32.

Two forms of the same arithmetic.  In plain ``jax.numpy``
(:func:`_chunked_xla`): the ``[Q, Q]`` arrays of every chunk at once, a
``lax.scan`` over the chunks, JAX's backward through both.  It is what the
CPU runs, the kernels' reference and the fall-back for a shape they do not
tile.  On a TPU a Pallas kernel pair under one ``jax.custom_vjp``
(``gdn_chunk_fwd``, ``gdn_chunk_bwd``), grid ``(batch row, block of heads,
chunk)`` with the chunk axis last and ``"arbitrary"``:

- **forward**: a grid step reads the chunk's ``q``, ``k``, ``v`` blocks of
  ``[B, S, H D]`` and a ``[8, 128]`` block each of ``gamma`` and ``beta``
  (one row a tile of heads), forms every array above in VMEM, and writes
  ``o`` and — only when called under differentiation — the state that
  entered the chunk, rounded to ``v``'s dtype as every product takes it.
  The block's float32 state ``[hb Dk, Dv]`` is the kernel's second output,
  whose block index does not move along the chunk axis: it stays in VMEM
  from a sequence's first chunk to its last, and leaves as the final state.
- **backward**: one kernel walks the chunks last to first with the state's
  cotangent in a VMEM scratch, recomputes the chunk's arrays from the same
  inputs and the saved entering state (the only residual besides the
  inputs), and writes ``dq``, ``dk``, ``dv``, ``d gamma``, ``d beta``.
  Neither the scan body's ``jax.checkpoint`` nor :func:`unit_lower_inverse`'s
  ``custom_vjp`` is on this path.

The forward kernel's three outputs reach the backward rule under the names
of :data:`SAVED_NAMES` (``o``, the final state, the entering states), as
``ops/flash_attention.py``'s two do: a remat policy that keeps them
(``models/llama.py``'s block remat does) runs ``gdn_chunk_fwd`` once per
layer application, not again in front of the block's backward, and holds
those of the three that the backward reads.  The names are identities under
any other policy, and the ``jax.numpy`` form, which keeps its scan body's
own checkpoint, emits none.

No array with a ``[Q, Q]`` dimension and no float32 state is written to or
read from HBM in either.  ``gamma`` (a cumulative sum), its layout and the
least decay are ``jax.numpy`` around the kernels.  The rule
(:func:`_kernel_heads`): ``jax.default_backend() == "tpu"``, ``chunk`` 64 or
128, ``Dk`` and ``Dv`` multiples of 128 lanes, and ``H`` a multiple of the
``128 // chunk`` heads that share a tile.

``g [B, S, H, Dk]`` is a decay a key CHANNEL (Kimi Delta Attention,
arXiv:2510.26692): each row of the state decays on its own, the exponent
sits inside the sum over a head's channels, and the scalar factorisation
above does not hold.  The same three forms for it — the sequential one (this
module's, with ``g`` of ``k``'s rank), a chunked ``jax.numpy`` one and a
kernel pair of its own (``kda_chunk_fwd``, ``kda_chunk_bwd``; outputs named
by :data:`CHANNEL_SAVED_NAMES`) — are the section "a decay per key CHANNEL"
below, which says how it keeps every exponent that is evaluated at or under
0.  There the chunk's prologue is the chunk's own: the kernels read ``g``
itself and, with ``unit_scales``, q and k before their L2 norms, and form
``Gamma`` and the unit rows on the tile they hold in VMEM.
:func:`gated_delta_chunked` takes either.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.flash_attention import NEG_INF as _NEG, _vmem_params
from dlrover_tpu.ops.per_shard import P as Spec, per_shard, shard_axes

F32 = jnp.float32
#: the forward kernel's outputs as the backward rule receives them: ``o``, the
#: final state, the states that entered the chunks (the module's docstring)
SAVED_NAMES = ("gdn_out", "gdn_state", "gdn_entering")


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


def _chunked_xla(q, k, v, g, beta, qn: int):
    """The chunked form in plain ``jax.numpy`` on a sequence that ``qn``
    divides -> ``(o, final state, least decay over a chunk)``."""
    bsz, s, h, dk = k.shape
    dv, dt = v.shape[-1], v.dtype
    c = s // qn
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
    out = jnp.moveaxis(out, (0, 3), (1, 2)).reshape(bsz, s, h, dv)
    return out, final, jnp.min(chunk_decay)


# -- the same as a Pallas kernel pair -----------------------------------------
#
# Layouts, per grid step (b, block j of hb heads, chunk i).  In HBM ``q``,
# ``k``, ``v``, ``o`` and their cotangents are ``[B, S, H D]`` as the mixer
# holds them: a head's chunk is a ``[Q, D]`` block, no copy in front of the
# kernels.  A chunk shorter than the 128 rows of an MXU pass shares a TILE
# with its neighbours: ``per = 128 // Q`` heads stacked along the rows,
# ``[per Q, D]``, so that every ``[Q, Q]`` array of the rule is one ``[128,
# 128]`` array whose diagonal blocks are the heads' (the others masked) and
# the inverse by doubling runs on the tile up to blocks of ``Q``.  What is one
# number a head and position (``gamma``, ``beta``) comes as ``[8, per Q]``
# rows, one a tile of the block (the rest zeros: a float32 tile's sublanes),
# lane-dense in HBM; a row broadcasts down the sublanes as it is, and one
# whole-tile transpose a grid step (:func:`_columns`) gives the columns that
# broadcast along the lanes.  The backward's sums over a head's lanes come
# out as columns and are turned back the same way.  The state of the block's
# heads ``[hb Dk, Dv]`` float32 is the forward's second output, whose block
# does not move along the chunk axis (the last, ``"arbitrary"``), so it stays
# in VMEM from a sequence's first chunk to its last; the backward holds the
# state's cotangent in a scratch the same way and walks the chunks last to
# first.

#: lanes of ``q`` (heads x Dk) one grid step of the kernels takes: 8 heads
#: of 128, four tiles run in lockstep (:func:`_in_lockstep`).  A shape
#: decision, not a knob.
_BLOCK_LANES = 1024
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _kernel_heads(chunk: int, h: int, dk: int, dv: int) -> int:
    """Heads one grid step takes, 0 where the kernels do not tile the
    shapes: ``chunk`` 64 or 128, ``Dk`` and ``Dv`` multiples of 128 lanes,
    and the largest divisor of ``H`` that fills whole tiles within
    :data:`_BLOCK_LANES` (one tile where that is wider)."""
    if chunk not in (64, 128) or dk % 128 or dv % 128:
        return 0
    per = 128 // chunk
    fits = [r for r in range(per, min(h, per * _ROWS) + 1, per) if h % r == 0
            and r * max(dk, dv) <= max(_BLOCK_LANES, per * max(dk, dv))]
    return max(fits, default=0)


def _nt(a, b, precision=None):
    """``a b^T``, float32."""
    return jax.lax.dot_general(a, b, _NT, precision=precision,
                               preferred_element_type=F32)


def _tn(a, b, precision=None):
    """``a^T b``, float32."""
    return jax.lax.dot_general(a, b, _TN, precision=precision,
                               preferred_element_type=F32)


def _dot(a, b, precision=None):
    return jnp.dot(a, b, precision=precision, preferred_element_type=F32)


def _in_lockstep(tiles):
    """Run the tiles' generators a stage at a time -> their return values.
    The kernels' bodies are straight-line code and Mosaic schedules it much
    as written: a tile's chunk is a chain of products each waiting for the
    last (the inverse alone is ten), and a ``yield`` between two stages puts
    the other tiles' same stage between them, so that the MXU takes one
    tile's product while another's drains."""
    tiles, results = list(tiles), {}
    while len(results) < len(tiles):
        for i, tile in enumerate(tiles):
            if i not in results:
                try:
                    next(tile)
                except StopIteration as stop:
                    results[i] = stop.value
    return [results[i] for i in range(len(tiles))]


def _tile_inverse(a, qn: int):
    """``(I + a)^-1`` of a float32 tile whose ``[qn, qn]`` diagonal blocks
    are strictly lower-triangular and whose other blocks are zero:
    :func:`unit_lower_inverse`'s doubling on the whole tile, stopped at
    blocks of ``qn``.  The first level's inverses are identities, so it
    costs no product; from blocks of 8 rows on (a float32 tile's sublanes)
    only the rows under the pairs' diagonals, half the tile's, go through
    the two products: the others of ``inv L inv`` are zero.  A generator
    (:func:`_in_lockstep`) that returns the inverse."""
    n = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    inv = jnp.where(row == col, 1.0, 0.0).astype(F32)
    b = 1
    while b < qn:
        shift = (2 * b).bit_length() - 1
        under = ((jax.lax.shift_right_logical(row, shift)
                  == jax.lax.shift_right_logical(col, shift))
                 & ((row & b) != 0) & ((col & b) == 0))
        step = jnp.where(under, a, 0.0)
        if b == 1:
            inv = inv - step
        elif b < 8:
            step = _dot(inv, step, _HIGHEST)
            yield
            inv = inv - _dot(step, inv, _HIGHEST)
            yield
        else:
            starts = range(b, n, 2 * b)
            step = _dot(jnp.concatenate([inv[r:r + b] for r in starts]),
                        step, _HIGHEST)
            yield
            step = _dot(step, inv, _HIGHEST)
            yield
            inv = jnp.concatenate([x for i, r in enumerate(starts) for x in (
                inv[r - b:r], inv[r:r + b] - step[i * b:(i + 1) * b])])
        b *= 2
    return inv


def _rows_of(values, qn: int, n: int):
    """``[n, 1]`` from one ``[1, 1]`` value a head: each over its head's
    ``qn`` rows of the tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    out = jnp.broadcast_to(values[0], (n, 1))
    for p in range(1, len(values)):
        out = jnp.where(row >= p * qn, values[p], out)
    return out


def _stack(ref, heads, d: int):
    """The heads' ``[Q, d]`` blocks of ``ref [Q, hb d]`` -> ``[per Q, d]``."""
    return jnp.concatenate([ref[:, h * d:(h + 1) * d] for h in heads], axis=0)


def _per_head(fn, per: int, qn: int):
    """``fn(p, rows of head p)`` of every head of a tile, stacked."""
    return jnp.concatenate(
        [fn(p, slice(p * qn, (p + 1) * qn)) for p in range(per)], axis=0)


def _columns(rows):
    """``[8, 128]`` rows, one a tile -> ``[128, 128]`` whose column ``t``
    is row ``t``: a whole-tile transpose, the one Mosaic has for float32."""
    return jnp.concatenate(
        [rows, jnp.zeros((128 - rows.shape[0], 128), F32)], axis=0).T


def _chunk_terms(q, k, v, gc, gr, bc, lows, qn: int):
    """What a tile's chunk is made of, forward and backward alike: ``q``,
    ``k [n, Dk]``, ``v [n, Dv]`` (``n = per qn`` rows), ``gc``, ``bc [n,
    1]`` and ``gr [1, n]`` float32, ``lows`` the heads' entering states
    rounded to ``v``'s dtype.  A generator (:func:`_in_lockstep`) that
    returns the terms."""
    n, dt, per = q.shape[0], v.dtype, q.shape[0] // qn
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    head = lambda x: jax.lax.shift_right_logical(  # noqa: E731
        x, qn.bit_length() - 1)
    same = head(row) == head(col) if per > 1 else True
    t = SimpleNamespace(strict=same & (row > col))
    # masked inside the exp: above the diagonal (and between heads) the
    # difference may be positive, and exp of the filler is 0 exactly
    t.decay = jnp.exp(jnp.where(same & (row >= col), gc - gr, _NEG))
    t.kkd = _nt(k, k) * t.decay
    t.a = jnp.where(t.strict, bc * t.kkd, 0.0)
    t.qkd = _nt(q, k) * t.decay
    t.qk = t.qkd.astype(dt)
    yield
    t.inv = yield from _tile_inverse(t.a, qn)
    t.qf, t.kf, t.vf = q.astype(F32), k.astype(F32), v.astype(F32)
    t.grown = jnp.exp(gc)  # from the chunk's start to i
    t.kg = t.kf * t.grown
    t.w = _dot(t.inv, t.kg * bc, _HIGHEST)
    t.u = _dot(t.inv, t.vf * bc, _HIGHEST)
    t.w_low = t.w.astype(dt)
    t.qg = (t.qf * t.grown).astype(dt)
    t.totals = [gc[(p + 1) * qn - 1:(p + 1) * qn] for p in range(per)]
    t.to_end = jnp.exp(_rows_of(t.totals, qn, n) - gc)  # from j to the end
    t.ke = (t.kf * t.to_end).astype(dt)
    # [1, Dv] rows: Mosaic broadcasts one number along the lanes or down
    # the sublanes, not both at once
    t.chunk_decay = [jnp.exp(jnp.broadcast_to(x, (1, v.shape[1])))
                     for x in t.totals]
    yield
    t.new = t.u - _per_head(lambda p, r: _dot(t.w_low[r], lows[p]), per, qn)
    t.new_low = t.new.astype(dt)
    yield
    return t


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, state_ref,
                *entering_ref, qn, dk, dv):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _new_sequence():
        state_ref[...] = jnp.zeros_like(state_ref)

    per, dt = max(1, 128 // qn), v_ref.dtype
    g_cols, b_cols = _columns(g_ref[...]), _columns(b_ref[...])

    def a_tile(tile):
        heads = range(tile * per, (tile + 1) * per)
        states = [state_ref[h * dk:(h + 1) * dk, :] for h in heads]
        lows = [s.astype(dt) for s in states]
        t = yield from _chunk_terms(
            _stack(q_ref, heads, dk), _stack(k_ref, heads, dk),
            _stack(v_ref, heads, dv), g_cols[:, tile:tile + 1],
            g_ref[tile:tile + 1, :], b_cols[:, tile:tile + 1], lows, qn)
        out = _per_head(lambda p, r: _dot(t.qg[r], lows[p]), per, qn) + _dot(
            t.qk, t.new_low)
        for p, h in enumerate(heads):
            r = slice(p * qn, (p + 1) * qn)
            o_ref[:, h * dv:(h + 1) * dv] = out[r]
            if entering_ref:
                entering_ref[0][h * dk:(h + 1) * dk, :] = lows[p]
            state_ref[h * dk:(h + 1) * dk, :] = (
                t.chunk_decay[p] * states[p] + _tn(t.ke[r], t.new_low[r]))

    _in_lockstep(a_tile(tile) for tile in range(q_ref.shape[1] // (per * dk)))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entering_ref, do_ref,
                dstate_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *,
                qn, dk, dv):
    """One chunk of the backward pass, from the chunk's own inputs, the
    state that entered it and the cotangents of ``o`` and of the state it
    left (``ds_ref``, resident).  With ``M`` the sum of ``dA o A`` and ``d(q
    k^T o decay) o (q k^T o decay)``, ``gamma``'s cotangent through the decay
    mask is ``M``'s row sums less its column sums.  What comes out as a
    column a tile (the sums over a head's lanes) is turned into rows once, at
    the end.  ``dA = -T^T (dW Kb^T + dU Vb^T) T^T`` is ``-(dKb W^T + dVb
    U^T)`` with ``dKb = T^T dW`` and ``dVb = T^T dU``, which ``k``, ``v`` and
    ``beta`` need anyway."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _sequence_end():
        ds_ref[...] = dstate_ref[...]

    per, dt, n = max(1, 128 // qn), v_ref.dtype, 128
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    over_lanes = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    g_cols, b_cols = _columns(g_ref[...]), _columns(b_ref[...])

    def a_tile(tile):
        """-> the tile's ``(d gamma, d beta)`` as columns, ``d gamma``'s
        other part as a row."""
        heads = range(tile * per, (tile + 1) * per)
        lows = [entering_ref[h * dk:(h + 1) * dk, :] for h in heads]
        q, k = _stack(q_ref, heads, dk), _stack(k_ref, heads, dk)
        gc, bc = g_cols[:, tile:tile + 1], b_cols[:, tile:tile + 1]
        t = yield from _chunk_terms(q, k, _stack(v_ref, heads, dv), gc,
                                    g_ref[tile:tile + 1, :], bc, lows, qn)
        do_low = _stack(do_ref, heads, dv).astype(dt)
        left = [ds_ref[h * dk:(h + 1) * dk, :] for h in heads]
        left_low = [x.astype(dt) for x in left]
        # o = qg S + qk new; S' = chunk_decay S + ke^T new; new = u - w S
        d_qg = _per_head(lambda p, r: _nt(do_low[r], lows[p]), per, qn)
        d_qk = _nt(do_low, t.new_low)
        d_ke = _per_head(lambda p, r: _nt(t.new_low[r], left_low[p]), per, qn)
        d_new = _tn(t.qk, do_low) + _per_head(
            lambda p, r: _dot(t.ke[r], left_low[p]), per, qn)
        d_new_low = d_new.astype(dt)
        yield
        d_w = -_per_head(lambda p, r: _nt(d_new_low[r], lows[p]), per, qn)
        d_total = []  # of each head's whole sum of g, through chunk_decay
        for p, h in enumerate(heads):
            r = slice(p * qn, (p + 1) * qn)
            d_total.append(jnp.sum(over_lanes(
                t.chunk_decay[p] * left[p] * lows[p].astype(F32)),
                axis=0, keepdims=True))
            ds_ref[h * dk:(h + 1) * dk, :] = (
                t.chunk_decay[p] * left[p] + _tn(t.qg[r], do_low[r])
                - _tn(t.w_low[r], d_new_low[r]))
        yield
        # w = T (k beta grown), u = T (v beta), T = (I + A)^-1
        inv_t = t.inv.T
        d_kb = _dot(inv_t, d_w, _HIGHEST)
        d_vb = _dot(inv_t, d_new, _HIGHEST)
        yield
        d_a = -jnp.where(t.strict, _nt(d_kb, t.w, _HIGHEST)
                         + _nt(d_vb, t.u, _HIGHEST), 0.0)
        yield
        through_mask = d_a * t.a + d_qk * t.qkd
        d_kk = (d_a * bc * t.decay).astype(dt)
        d_qkd = (d_qk * t.decay).astype(dt)
        by_kg = over_lanes(d_kb * t.kg)
        d_to_end = over_lanes(d_ke * t.kf) * t.to_end
        d_total = [d_total[p] + jnp.sum(
            d_to_end[p * qn:(p + 1) * qn], axis=0, keepdims=True)
            for p in range(per)]
        d_gc = (over_lanes(through_mask) + bc * by_kg
                + over_lanes(d_qg * t.qf) * t.grown - d_to_end)
        for p in range(per):
            d_gc = d_gc + jnp.where(row == (p + 1) * qn - 1, d_total[p], 0.0)
        d_q = _dot(d_qkd, k) + d_qg * t.grown
        d_k = (_dot(d_kk, k) + _tn(d_kk, k) + _tn(d_qkd, q)
               + d_kb * (bc * t.grown) + d_ke * t.to_end)
        d_v = d_vb * bc
        for p, h in enumerate(heads):
            r = slice(p * qn, (p + 1) * qn)
            dq_ref[:, h * dk:(h + 1) * dk] = d_q[r].astype(dq_ref.dtype)
            dk_ref[:, h * dk:(h + 1) * dk] = d_k[r].astype(dk_ref.dtype)
            dv_ref[:, h * dv:(h + 1) * dv] = d_v[r].astype(dv_ref.dtype)
        return (d_gc, over_lanes(d_a * t.kkd) + by_kg
                + over_lanes(d_vb * t.vf),
                -jnp.sum(through_mask, axis=0, keepdims=True))

    sums = _in_lockstep(
        a_tile(tile) for tile in range(q_ref.shape[1] // (per * dk)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 0)
    dg_cols, db_cols = jnp.zeros((n, n), F32), jnp.zeros((n, n), F32)
    dg_rows = jnp.zeros(g_ref.shape, F32)
    for tile, (d_gc, d_bc, d_gr) in enumerate(sums):
        dg_cols = jnp.where(lane == tile, d_gc, dg_cols)
        db_cols = jnp.where(lane == tile, d_bc, db_cols)
        dg_rows = jnp.where(sublane == tile, d_gr, dg_rows)
    dg_ref[...] = dg_rows + dg_cols.T[:dg_ref.shape[0]]
    db_ref[...] = db_cols.T[:db_ref.shape[0]]


#: rows of the per-position block (``gamma``, ``beta``): a float32 tile's
#: sublanes, one a tile of heads, the rest zeros
_ROWS = 8
#: the kernels' first operands by the name of their block spec: q, k, v,
#: then gamma and beta, which share one (as their cotangents do)
_INPUTS = ("q", "k", "v", "g", "g")


def _block_specs(bsz, c, blocks, qn, hb, dk, dv, backward=False):
    """The grid ``(B, H / hb, chunks)`` and the block specs by name; the
    backward's index maps walk the chunks last to first."""
    from jax.experimental import pallas as pl

    at = (lambda i: c - 1 - i) if backward else (lambda i: i)
    per_chunk = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None, None, None) + shape, lambda b, j, i: (b, at(i), j, 0, 0))
    rows = lambda d: pl.BlockSpec(  # noqa: E731
        (None, qn, hb * d), lambda b, j, i: (b, at(i), j))
    specs = dict(
        q=rows(dk), k=rows(dk), v=rows(dv), g=per_chunk(_ROWS, 128),
        entering=per_chunk(hb * dk, dv),
        state=pl.BlockSpec((None, None, hb * dk, dv),
                           lambda b, j, i: (b, j, 0, 0)))
    return (bsz, blocks, c), specs


def _call_params(resident_bytes: int) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    # a block of heads' chunks run in turn: the state passes between them
    raised = _vmem_params(resident_bytes).get("compiler_params")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=raised and raised.vmem_limit_bytes)}


def _chunk_fwd(q, k, v, g, b, dims, interpret, keep_entering):
    """``q``, ``k``, ``v [B, S, H D]``; ``g`` (the cumulative sums) and ``b
    [B, c, J, 8, 128]`` float32 -> ``(o [B, S, H Dv] float32, final state
    [B, J, hb Dk, Dv] float32, the states that entered the chunks [B, c, J,
    hb Dk, Dv] in ``v``'s dtype or None)``."""
    from jax.experimental import pallas as pl

    qn, hb, dk, dv = dims
    bsz, c, blocks = g.shape[:3]
    size, n = v.dtype.itemsize, 128
    grid, specs = _block_specs(bsz, c, blocks, qn, hb, dk, dv)
    out_specs = [specs["v"], specs["state"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, F32),
                 jax.ShapeDtypeStruct((bsz, blocks, hb * dk, dv), F32)]
    if keep_entering:
        out_specs.append(specs["entering"])
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, c, blocks, hb * dk, dv), v.dtype))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, qn=qn, dk=dk, dv=dv),
        grid=grid,
        in_specs=[specs[name] for name in _INPUTS],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="gdn_chunk_fwd",
        # blocks twice (the pipeline's two buffers): q, k, v, o, the state
        # and its rounding; every tile's [n, n] and [n, D] temporaries at
        # once (the tiles run in lockstep)
        **_call_params(2 * hb * (qn * (2 * dk + dv) * size + qn * dv * 4
                                 + dk * dv * (4 + size))
                       + hb * qn // n * 4 * n * (10 * n + 16 * max(dk, dv))),
    )(q, k, v, g, b)
    return (*out, None)[:3]


def _chunk_bwd(q, k, v, g, b, entering, do, dstate, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qn, hb, dk, dv = dims
    bsz, c, blocks = g.shape[:3]
    size, n = v.dtype.itemsize, 128
    grid, specs = _block_specs(bsz, c, blocks, qn, hb, dk, dv, backward=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, qn=qn, dk=dk, dv=dv),
        grid=grid,
        in_specs=[specs[name] for name in _INPUTS + (
            "entering", "v", "state")],
        out_specs=[specs[name] for name in _INPUTS],
        out_shape=[like(a) for a in (q, k, v, g, b)],
        scratch_shapes=[pltpu.VMEM((hb * dk, dv), F32)],
        interpret=interpret,
        name="gdn_chunk_bwd",
        **_call_params(2 * hb * (2 * qn * (2 * dk + dv) * size + qn * dv * 4
                                 + dk * dv * (4 + size)) + hb * dk * dv * 4
                       + hb * qn // n * 4 * n * (16 * n + 32 * max(dk, dv))),
    )(q, k, v, g, b, entering, do, dstate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunked_kernels(q, k, v, g, b, dims, interpret):
    return _chunk_fwd(q, k, v, g, b, dims, interpret, False)[:2]


def _kernels_fwd(q, k, v, g, b, dims, interpret):
    o, final, entering = map(checkpoint_name, _chunk_fwd(
        q, k, v, g, b, dims, interpret, True), SAVED_NAMES)
    return (o, final), (q, k, v, g, b, entering)


def _kernels_bwd(dims, interpret, res, cotangents):
    return tuple(_chunk_bwd(*res, *cotangents, dims, interpret))


_chunked_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _chunked_pallas(q, k, v, g, beta, qn: int, hb: int, interpret: bool):
    """:func:`_chunked_xla` by the kernel pair, one call per shard of the
    mesh in scope (the batch dim split, as ``ops/ssd.py``).  The cumulative
    sums, their layout and the least decay are ``jax.numpy``."""
    bsz, s, h, dk = k.shape
    dv, c, per = v.shape[-1], s // qn, max(1, 128 // qn)
    tiles, blocks = hb // per, h // hb
    gamma = jnp.cumsum(g.astype(F32).reshape(bsz, c, qn, h), axis=2)

    def rows(x):
        """``[B, c, Q, H] -> [B, c, J, 8, per Q]``: a tile's heads one
        after the other along a row, a block's tiles down the rows."""
        x = x.reshape(bsz, c, qn, blocks, tiles, per).transpose(
            0, 1, 3, 4, 5, 2).reshape(bsz, c, blocks, tiles, per * qn)
        return jnp.pad(x, ((0, 0),) * 3 + ((0, _ROWS - tiles), (0, 0)))

    flat = lambda x: x.reshape(bsz, s, -1)  # noqa: E731
    free, batch_axes, _ = shard_axes(bsz)
    first = lambda nd: Spec(batch_axes, *([None] * (nd - 1)))  # noqa: E731
    o, final = per_shard(
        lambda *ops: _chunked_kernels(*ops, (qn, hb, dk, dv), interpret),
        free, (first(3),) * 3 + (first(5),) * 2, (first(3), first(4)),
    )(flat(q), flat(k), flat(v), rows(gamma),
      rows(beta.astype(F32).reshape(bsz, c, qn, h)))
    return (o.reshape(bsz, s, h, dv), final.reshape(bsz, h, dk, dv),
            jnp.min(jnp.exp(gamma[:, :, -1])))


# -- a decay per key CHANNEL (Kimi Delta Attention, arXiv:2510.26692) ---------
#
# ``g [B, S, H, Dk]``: each of the state's ``Dk`` rows decays on its own, so
# ``Gamma = cumsum(g)`` inside a chunk is ``[Q, Dk]`` and the exponent of
#
#     A_ij  = beta_i sum_d k_id k_jd exp(Gamma_id - Gamma_jd)      (i > j)
#     QK_ij =        sum_d q_id k_jd exp(Gamma_id - Gamma_jd)      (i >= j)
#
# sits INSIDE the sum over ``d``: ``A`` is no ``(k k^T) * decay``, and
# ``exp(-Gamma_j)`` alone overflows as ever.  The module's rule — no exponent
# that is evaluated is ever positive — is kept by halving: a pair ``(i, j)``
# with ``i > j`` lies, at exactly one block size ``b`` of ``1, 2, 4, ..., Q /
# 2``, in the two halves of one aligned block of ``2 b`` positions, ``i`` in
# the second and ``j`` in the first.  With ``r`` the ``Gamma`` of the first
# half's last position, ``Gamma_i - Gamma_j = (Gamma_i - r) + (r - Gamma_j)``
# and neither bracket is positive, so a level is ONE product of two bounded
# operands on the MXU::
#
#     (k . exp(Gamma - r))  (k . exp(r - Gamma))^T     masked to the level's
#                                                      off-diagonal blocks
#
# (rows of the wrong half get the filler exponent and are zero exactly);
# ``log2 Q`` levels cover every pair once, and the diagonal of ``QK`` has the
# exponent 0.  No ``[Q, Q, Dk]`` array and no pairwise work on the VPU.  ``r``
# is a row of ``Gamma`` copied down its block, and costs the MXU nothing: two
# turns of the rows and two selects a level (:func:`_halving_references`;
# sublane rotations in the kernels, exact; the cotangent is the same moves
# back).  The rest is the scalar rule's with ``exp(Gamma)`` a ``[Q, Dk]``
# array: ``W = T (beta k . exp(Gamma))``, ``o = (q . exp(Gamma)) S + QK u``,
# ``S <- diag(exp(Gamma_Q)) S + (k . exp(Gamma_Q - Gamma))^T u``.  The state
# is held TRANSPOSED, ``[Dv, Dk]``, so that a channel's decay runs along the
# lanes.  Float32 stays where it stays above; the products that feed the
# inverse (``A``) take float32 operands at ``highest``, those rounded anyway
# (``QK``, and every product against the state) operands in ``v``'s dtype.
#
# ONE function computes a chunk (:func:`_channel_chunk`), from whole-tile
# operations alone, its prologue included: ``Gamma`` is the running sum of the
# chunk's ``g`` down its rows, ``log2 Q`` turns of the rows each added where it
# did not wrap (:func:`_running_sum`; the halving's moves, no product; the
# cotangent the same sum from the chunk's end), and with ``unit_scales`` q and
# k are L2-normalised over their lanes there (:func:`_unit_rows`), in float32,
# rounded where an operand in HBM would be.  So nothing along ``[B, S, H Dk]``
# runs outside but what forms ``g`` and ``beta`` and the least decay, a sum of
# ``g`` over each chunk.  A tree of sums rounds otherwise than a run: where a
# ``g`` is under ``Gamma``'s last bit an exponent may come out positive by that
# bit, which overflows nothing.
#
# The ``jax.numpy`` form maps the function over heads and scans it over
# chunks; the forward kernel (``kda_chunk_fwd``) calls it on a head's chunk in
# VMEM; the backward kernel (``kda_chunk_bwd``) takes ``jax.vjp`` of it THERE,
# on the same inputs and the saved entering state, so that the chunk's
# backward is derived and not written a second time.  Its matmuls are
# :func:`_product`, whose own rule rounds a cotangent as the forward rounds
# an operand.  The scalar rule's kernels are as they were.

#: the kernels' outputs under a per-channel decay, as :data:`SAVED_NAMES`
CHANNEL_SAVED_NAMES = ("kda_out", "kda_state", "kda_entering")
#: positions of a chunk the per-channel kernels take: one head a tile
CHANNEL_CHUNK = 128


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _product(kind, a, b, dt):
    """``a b`` (``kind`` "nn"), ``a b^T`` ("nt") or ``a^T b`` ("tn") of
    float32 2-D operands -> float32: the operands rounded to ``dt`` on the
    way in, or, at ``dt`` float32, whole at ``highest``.  The backward rounds
    the cotangent the same way."""
    precision = _HIGHEST if dt == F32 else None
    fn = {"nn": _dot, "nt": _nt, "tn": _tn}[kind]
    return fn(a.astype(dt), b.astype(dt), precision)


def _product_fwd(kind, a, b, dt):
    return _product(kind, a, b, dt), (a, b)


def _product_bwd(kind, dt, res, g):
    a, b = res
    nn, nt, tn = (functools.partial(_product, x, dt=dt)
                  for x in ("nn", "nt", "tn"))
    return {"nn": lambda: (nt(g, b), tn(a, g)),
            "nt": lambda: (nn(g, b), tn(g, a)),
            "tn": lambda: (nt(b, g), nn(a, g))}[kind]()


_product.defvjp(_product_fwd, _product_bwd)


@jax.custom_vjp
def _whole_tile_inverse(a):
    """:func:`_tile_inverse` of one head's ``[Q, Q]`` tile, with
    :func:`unit_lower_inverse`'s cotangent."""
    steps = _tile_inverse(a, a.shape[0])
    try:
        while True:
            next(steps)
    except StopIteration as stop:
        return stop.value


def _whole_tile_inverse_bwd(t, g):
    tt = t.T
    return (-_dot(_dot(tt, g, _HIGHEST), tt, _HIGHEST),)


_whole_tile_inverse.defvjp(
    lambda a: (_whole_tile_inverse(a),) * 2, _whole_tile_inverse_bwd)


def _reads(b, x):
    """The rows of ``x`` that :func:`_half_rows` moves."""
    second = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) & abs(b)) != 0
    return second if b > 0 else ~second


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _half_rows(roll, b, x):
    """Float32 ``x [n, d]`` with one half of every aligned block of ``2 |b|``
    rows reading from the other: at ``b > 0`` row ``i`` of a second half
    takes row ``i - b``, at ``b < 0`` row ``i`` of a first half takes row ``i
    + |b|``; the other half keeps its own.  ``roll(x, shift)`` turns the rows
    as ``jnp.roll`` along axis 0; the rows that wrap are never taken.  The
    cotangent is the same move back, added to the row it came from."""
    return jnp.where(_reads(b, x), roll(x, b), x)


_half_rows.defvjp(
    lambda roll, b, x: (_half_rows(roll, b, x), None),
    lambda roll, b, _, g: (
        jnp.where(_reads(b, g), 0.0, g + roll(g, -b)),))


def _rolled_rows(x, shift):
    """``jnp.roll`` along the rows: the ``jax.numpy`` form's moves."""
    return jnp.roll(x, shift, axis=0)


def _rotated_sublanes(x, shift):
    """The same in a kernel, a rotation of the sublanes (shifts of 8 and
    more move whole vregs, 1, 2 and 4 go through the XLU)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[0], 0)


def _halving_references(gam, roll):
    """For every block size ``b = 1, 2, 4, ... n / 2`` of the halving, ``(b,
    ref)`` with ``ref[i] = gam[(i & ~(2 b - 1)) | (b - 1)]``: the ``Gamma``
    of the last position of the first half of row ``i``'s aligned block of
    ``2 b``, by moves of ``gam``'s rows alone.  ``held`` has ``gam[i | (b -
    1)]`` in row ``i``; clearing bit ``b`` of the row gives ``ref``, setting
    it the next level's ``held``."""
    held, b = gam, 1
    while True:
        yield b, _half_rows(roll, b, held)
        if 2 * b >= gam.shape[0]:
            return
        held, b = _half_rows(roll, -b, held), 2 * b


def _summed_rows(roll, x, back):
    """Row ``i`` of float32 ``x [n, d]`` plus every row before it (``back``:
    after it), by doubling: ``log2 n`` turns of the rows, each added where
    it did not wrap.  No product."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < n:
        from_inside = (row < n - s) if back else (row >= s)
        x = x + jnp.where(from_inside, roll(x, -s if back else s), 0.0)
        s *= 2
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _running_sum(roll, g):
    """``cumsum`` along the rows of ``g [n, d]`` (:func:`_summed_rows`): a
    chunk's ``Gamma`` from its ``g``, a tree of sums and not a run (the
    section's comment says what that rounds).  The cotangent is the same sum
    run from the chunk's end."""
    return _summed_rows(roll, g, False)


_running_sum.defvjp(
    lambda roll, g: (_running_sum(roll, g), None),
    lambda roll, _, cot: (_summed_rows(roll, cot, True),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rounded(x, dt, in_kernel):
    """Float32 ``x`` rounded to ``dt`` and back; the cotangent passes whole
    (it is rounded once, where the kernel writes it).  A kernel converts
    there and back, which Mosaic runs as written.  XLA may take such a pair
    for nothing (``xla_allow_excess_precision``, a TPU's default) in SOME of
    the value's uses: the chunk's exponents then cancel between a rounded k
    and an unrounded one, and ``dg`` of a fast channel came out 90 times
    its size (builder, PR 67).  ``reduce_precision`` is the same rounding
    and not XLA's to drop; Mosaic does not know it."""
    if in_kernel:
        return x.astype(dt).astype(F32)
    info = jnp.finfo(dt)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


_rounded.defvjp(lambda x, dt, in_kernel: (_rounded(x, dt, in_kernel), None),
                lambda dt, in_kernel, _, cot: (cot,))


def _unit_rows(x, scale, dt, in_kernel):
    """The rows of float32 ``x [n, d]`` L2-normalised (``x / sqrt(sum x^2 +
    1e-6)``) and scaled, rounded to ``dt`` as an operand in HBM would be
    (:func:`_rounded`): a row of zeros (a padded position) stays one."""
    return _rounded(x * (jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=1, keepdims=True) + 1e-6) * scale), dt,
        in_kernel)


def _channel_chunk(q, k, v, g, bc, state, dt, inverse, roll=_rolled_rows,
                   unit_scales=None):
    """One head's chunk of ``n`` positions under a per-channel decay:
    ``q``, ``k``, ``g [n, Dk]``, ``v [n, Dv]``, ``bc [n, 1]`` and the
    state that enters, transposed, ``[Dv, Dk]``, all float32 -> ``(o [n,
    Dv], the state that leaves [Dv, Dk])``.  The chunk's prologue is its
    own: ``Gamma`` is the running sum of ``g`` down the chunk's rows
    (:func:`_running_sum`) and, with ``unit_scales = (q's, k's)``, q and k
    are the RAW rows, normalised and scaled here (:func:`_unit_rows`);
    without, the caller's.  ``inverse`` computes ``(I + A)^-1`` and ``roll``
    turns a tile's rows (:func:`_half_rows`); the kernels' ``roll`` also
    says that a kernel is where this runs (:func:`_rounded`)."""
    if unit_scales is not None:
        q, k = (_unit_rows(x, scale, dt, roll is _rotated_sublanes)
                for x, scale in zip((q, k), unit_scales))
    gam = _running_sum(roll, g)
    n, dk = k.shape
    iota = jax.lax.broadcasted_iota
    row, col = iota(jnp.int32, (n, n), 0), iota(jnp.int32, (n, n), 1)
    at = iota(jnp.int32, (n, dk), 0)
    a = jnp.zeros((n, n), F32)
    qk = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    # ref: the Gamma of the last position of the first half of a row's block
    for b, ref in _halving_references(gam, roll):
        shift = (2 * b).bit_length() - 1
        block = lambda x: jax.lax.shift_right_logical(x, shift)  # noqa: E731
        under = ((block(row) == block(col)) & ((row & b) != 0)
                 & ((col & b) == 0))
        second = (at & b) != 0
        # masked inside the exp: in the other half the bracket is positive
        from_ref = jnp.exp(jnp.where(second, gam - ref, _NEG))
        to_ref = jnp.exp(jnp.where(second, _NEG, ref - gam))
        k_to = k * to_ref
        a = a + jnp.where(under, _product("nt", k * from_ref, k_to, F32), 0.0)
        qk = qk + jnp.where(under, _product("nt", q * from_ref, k_to, dt),
                            0.0)
    inv = inverse(bc * a)
    grown = jnp.exp(gam)  # from the chunk's start to i, a channel
    w = _product("nn", inv, k * grown * bc, F32)
    u = _product("nn", inv, v * bc, F32)
    new = u - _product("nt", w, state, dt)
    out = (_product("nt", q * grown, state, dt)
           + _product("nn", qk, new, dt))
    total = jnp.sum(jnp.where(at == n - 1, gam, 0.0), axis=0, keepdims=True)
    k_to_end = k * jnp.exp(total - gam)  # from j to the chunk's end
    return out, (jnp.exp(total) * state
                 + _product("tn", new, k_to_end, dt))


def _least_channel_decay(g, qn: int):
    """``min exp(sum of g over a chunk)`` of ``g [B, S, H, Dk]`` over
    chunks, heads and channels: the one thing of the per-channel rule that
    reads ``g`` outside :func:`_channel_chunk`."""
    bsz, s = g.shape[:2]
    return jnp.min(jnp.exp(jnp.sum(
        g.astype(F32).reshape(bsz, s // qn, qn, -1), axis=2)))


def _chunked_channel_xla(q, k, v, g, beta, qn: int, unit_scales=None):
    """:func:`_chunked_xla` under a per-channel decay: :func:`_channel_chunk`
    mapped over batch rows and heads, a ``lax.scan`` over the chunks."""
    bsz, s, h, dk = k.shape
    dv, dt, c = v.shape[-1], v.dtype, s // qn
    # [c, B, H, Q, ...]: the chunks first, a head's rows together
    rows = lambda x: jnp.moveaxis(  # noqa: E731
        x.astype(F32).reshape((bsz, c, qn) + x.shape[2:]), (1, 3), (0, 2))
    chunk = functools.partial(_channel_chunk, dt=dt,
                              inverse=unit_lower_inverse,
                              unit_scales=unit_scales)

    @jax.checkpoint
    def carry(state, inputs):
        out, state = jax.vmap(jax.vmap(chunk))(*inputs, state)
        return state, out

    final, out = jax.lax.scan(
        carry, jnp.zeros((bsz, h, dv, dk), F32),
        (rows(q), rows(k), rows(v), rows(g), rows(beta)[..., None]))
    out = jnp.moveaxis(out, (0, 3), (1, 2)).reshape(bsz, s, h, dv)
    return (out, jnp.swapaxes(final, -1, -2), _least_channel_decay(g, qn))


def _channel_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, state_ref,
                        *entering_ref, unit_scales):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _new_sequence():
        state_ref[...] = jnp.zeros_like(state_ref)

    dt, state = v_ref.dtype, state_ref[...]
    if entering_ref:
        entering_ref[0][...] = state.astype(dt)
    o_ref[...], state_ref[...] = _channel_chunk(
        q_ref[...].astype(F32), k_ref[...].astype(F32),
        v_ref[...].astype(F32), g_ref[...], _columns(b_ref[...])[:, :1],
        state, dt, _whole_tile_inverse, _rotated_sublanes, unit_scales)


def _channel_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entering_ref,
                        do_ref, dstate_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                        db_ref, ds_ref, *, unit_scales):
    """One chunk of the backward pass: ``jax.vjp`` of :func:`_channel_chunk`
    on the chunk's own inputs and the state that entered it (as saved,
    rounded: every product takes it so, and the decay's own cotangent reads
    it as the scalar rule's backward does), pulled back from the cotangents
    of ``o`` and of the state the chunk left (``ds_ref``, resident).  The
    chunk's prologue is inside the ``vjp``: ``dg`` is the cotangent of ``g``
    itself and ``dq``, ``dk`` those of the rows as they were read."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _sequence_end():
        ds_ref[...] = dstate_ref[...]

    dt = v_ref.dtype
    _, pull = jax.vjp(
        functools.partial(_channel_chunk, dt=dt, inverse=_whole_tile_inverse,
                          roll=_rotated_sublanes, unit_scales=unit_scales),
        q_ref[...].astype(F32), k_ref[...].astype(F32),
        v_ref[...].astype(F32), g_ref[...], _columns(b_ref[...])[:, :1],
        entering_ref[...].astype(F32))
    d_q, d_k, d_v, d_g, d_b, ds_ref[...] = pull((do_ref[...], ds_ref[...]))
    dq_ref[...] = d_q.astype(dq_ref.dtype)
    dk_ref[...] = d_k.astype(dk_ref.dtype)
    dv_ref[...] = d_v.astype(dv_ref.dtype)
    dg_ref[...] = d_g
    lane = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    db_ref[...] = jnp.where(lane == 0, d_b, 0.0).T[:db_ref.shape[0]]


def _channel_specs(bsz, c, h, dk, dv, backward=False):
    """The grid ``(B, H, chunks)`` — a head a grid step — and the block
    specs by name, as :func:`_block_specs`."""
    from jax.experimental import pallas as pl

    at = (lambda i: c - 1 - i) if backward else (lambda i: i)
    rows = lambda d: pl.BlockSpec(  # noqa: E731
        (None, CHANNEL_CHUNK, d), lambda b, j, i: (b, at(i), j))
    per_chunk = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None, None, None) + shape, lambda b, j, i: (b, at(i), j, 0, 0))
    return (bsz, h, c), dict(
        q=rows(dk), k=rows(dk), g=rows(dk), v=rows(dv),
        b=per_chunk(_ROWS, 128), entering=per_chunk(dv, dk),
        state=pl.BlockSpec((None, None, dv, dk),
                           lambda b, j, i: (b, j, 0, 0)))


#: what a chunk's ``[128, 128]`` float32 temporaries may hold in VMEM beside
#: the blocks: seven levels of a dozen arrays forward, and the backward keeps
#: the forward's while it walks back
_CHANNEL_VMEM = 32 * 2 ** 20
_CHANNEL_INPUTS = ("q", "k", "v", "g", "b")


def _channel_fwd(q, k, v, g, b, dims, interpret, keep_entering):
    """``q``, ``k [B, S, H Dk]``, ``v [B, S, H Dv]``, ``g [B, S, H Dk]`` and
    ``b [B, c, H, 8, 128]`` float32 -> ``(o [B, S, H Dv] float32, final
    state [B, H, Dv, Dk] float32, the entering states [B, c, H, Dv, Dk] in
    ``v``'s dtype or None)``.  ``dims``: ``(H, Dk, Dv, unit_scales)``."""
    from jax.experimental import pallas as pl

    h, dk, dv, unit_scales = dims
    bsz, c = b.shape[:2]
    grid, specs = _channel_specs(bsz, c, h, dk, dv)
    out_specs = [specs["v"], specs["state"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, F32),
                 jax.ShapeDtypeStruct((bsz, h, dv, dk), F32)]
    if keep_entering:
        out_specs.append(specs["entering"])
        out_shape.append(jax.ShapeDtypeStruct((bsz, c, h, dv, dk), v.dtype))
    out = pl.pallas_call(
        functools.partial(_channel_fwd_kernel, unit_scales=unit_scales),
        grid=grid,
        in_specs=[specs[name] for name in _CHANNEL_INPUTS],
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
        name="kda_chunk_fwd", **_call_params(_CHANNEL_VMEM),
    )(q, k, v, g, b)
    return (*out, None)[:3]


def _channel_bwd(q, k, v, g, b, entering, do, dstate, dims, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, dk, dv, unit_scales = dims
    bsz, c = b.shape[:2]
    grid, specs = _channel_specs(bsz, c, h, dk, dv, backward=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_channel_bwd_kernel, unit_scales=unit_scales),
        grid=grid,
        in_specs=[specs[name] for name in _CHANNEL_INPUTS + (
            "entering", "v", "state")],
        out_specs=[specs[name] for name in _CHANNEL_INPUTS],
        out_shape=[like(a) for a in (q, k, v, g, b)],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32)],
        interpret=interpret, name="kda_chunk_bwd",
        **_call_params(2 * _CHANNEL_VMEM),
    )(q, k, v, g, b, entering, do, dstate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _channel_kernels(q, k, v, g, b, dims, interpret):
    return _channel_fwd(q, k, v, g, b, dims, interpret, False)[:2]


def _channel_kernels_fwd(q, k, v, g, b, dims, interpret):
    o, final, entering = map(checkpoint_name, _channel_fwd(
        q, k, v, g, b, dims, interpret, True), CHANNEL_SAVED_NAMES)
    return (o, final), (q, k, v, g, b, entering)


def _channel_kernels_bwd(dims, interpret, res, cotangents):
    return tuple(_channel_bwd(*res, *cotangents, dims, interpret))


_channel_kernels.defvjp(_channel_kernels_fwd, _channel_kernels_bwd)


def _chunked_channel_pallas(q, k, v, g, beta, interpret: bool,
                            unit_scales=None):
    """:func:`_chunked_channel_xla` by the kernel pair at
    :data:`CHANNEL_CHUNK`, one call per shard of the mesh in scope.  The
    kernels read ``g`` and q and k as they are handed over: the cumulative
    sums and, with ``unit_scales``, the L2 norms are the chunk's own
    (:func:`_channel_chunk`).  What stays ``jax.numpy`` is ``beta``'s layout
    (a head's chunk a lane-dense row) and the least decay."""
    bsz, s, h, dk = k.shape
    dv, qn = v.shape[-1], CHANNEL_CHUNK
    c = s // qn
    rows = jnp.pad(
        beta.astype(F32).reshape(bsz, c, qn, h).transpose(0, 1, 3, 2)[
            :, :, :, None], ((0, 0),) * 3 + ((0, _ROWS - 1), (0, 0)))
    flat = lambda x: x.reshape(bsz, s, -1)  # noqa: E731
    free, batch_axes, _ = shard_axes(bsz)
    first = lambda nd: Spec(batch_axes, *([None] * (nd - 1)))  # noqa: E731
    o, final = per_shard(
        lambda *ops: _channel_kernels(
            *ops, (h, dk, dv, unit_scales), interpret),
        free, (first(3),) * 4 + (first(5),), (first(3), first(4)),
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), rows)
    return (o.reshape(bsz, s, h, dv), jnp.swapaxes(final, -1, -2),
            _least_channel_decay(g, qn))


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64, *,
                        unit_scales: Optional[tuple] = None,
                        backend: Optional[str] = None,
                        interpret: bool = False):
    """The chunked form -> ``(o [B, S, H, Dv] float32, final state [B, H,
    Dk, Dv] float32, the least decay over a chunk, a float32 scalar)``.  ``g
    [B, S, H]`` is a decay a head, ``g [B, S, H, Dk]`` one a key channel
    (the least decay is then over channels too).  A sequence that ``chunk``
    does not divide is padded with positions of ``g = 0`` and ``beta = 0``,
    which neither decay nor write.  ``unit_scales = (q's, k's)``, for the
    per-channel rule alone: q and k are the RAW rows and each chunk
    L2-normalises them itself in float32 (``x / sqrt(sum x^2 + 1e-6)``
    times the scale, rounded to ``v``'s dtype), so the caller runs no norm
    of its own; without it q and k arrive normalised, as the module's
    docstring has them.  ``backend`` (``"pallas"`` /
    ``"reference"``; None: by the device) and ``interpret`` are for tests of
    the kernels on the CPU; a shape the kernels do not tile
    (:func:`_kernel_heads`; per channel: ``chunk`` :data:`CHANNEL_CHUNK` and
    whole lane tiles) runs the ``jax.numpy`` form on any backend."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    s, h, dk = k.shape[1:]
    dv, channel = v.shape[-1], g.ndim == k.ndim
    if unit_scales is not None and not channel:
        raise ValueError("gated_delta_chunked: unit_scales is the "
                         "per-channel rule's (g [B, S, H, Dk])")
    # heads a grid step of the kernels takes: 0 where they do not tile
    if backend != "pallas":
        hb = 0
    elif channel:
        hb = int(chunk == CHANNEL_CHUNK and not (dk % 128 or dv % 128))
    else:
        hb = _kernel_heads(chunk, h, dk, dv)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    if channel and hb:
        out, final, decay_min = _chunked_channel_pallas(
            q, k, v, g, beta, interpret, unit_scales)
    elif channel:
        out, final, decay_min = _chunked_channel_xla(q, k, v, g, beta, chunk,
                                                     unit_scales)
    elif hb:
        out, final, decay_min = _chunked_pallas(q, k, v, g, beta, chunk, hb,
                                                interpret)
    else:
        out, final, decay_min = _chunked_xla(q, k, v, g, beta, chunk)
    return out[:, :s], final, decay_min


def gated_delta_sequential(q, k, v, g, beta):
    """The recurrence as written, one position at a time in float32 at
    ``highest`` matmul precision -> ``(o [B, S, H, Dv], final state [B, H,
    Dk, Dv])``.  ``g [B, S, H]`` or, a number a key channel, ``[B, S, H,
    Dk]``.  The chunked forms' reference; nothing trains through it."""
    bsz, _, h, dk = k.shape

    def step(state, inputs):
        # [B, H, D] x 3, g [B, H] or [B, H, Dk], beta [B, H]
        q_t, k_t, v_t, g_t, b_t = inputs
        decay = jnp.exp(g_t)[..., None]
        state = (decay if g_t.ndim == 3 else decay[..., None]) * state
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
