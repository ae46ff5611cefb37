"""Flash attention: Pallas TPU kernel + reference, with custom VJP.

The TPU-native analogue of the reference's flash-attn integration
(``kernels/extensions/flash_attention/flash_attn_func_ext.py`` wrapping the
CUDA flash-attn, and ``kernels/extensions/xla/flash_attention_xla.py``):
blocked online-softmax attention that never materializes the [S, S] score
matrix.  Forward saves per-row logsumexp; backward recomputes block scores
(FlashAttention-2 style) in two Pallas kernels (dq, then dk/dv).

Layout [B, H, S, D]; D padded to the 128-lane register width by the caller
or the dispatcher.  Causal masking skips fully-masked K blocks via the grid.
``v`` (and with it ``o`` and their cotangents) carries a width of its own,
``[B, KV, S, Dv]``: scores are ``[bq, D] x [bk, D]^T`` at ``1 / sqrt(D)`` and
the output ``p x [bk, Dv]``, so values narrower than the keys (latent
attention: 192-wide q and k over 128-wide v) cost no padded column.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.per_shard import P, per_shard, shard_axes

# Defaults tuned on v5e at [8,16,2048,64]: large blocks amortize MXU
# pipeline fill (128x128 blocks ran at ~5% of peak; 512x512 at ~17%).
# The forward and dq kernels work QUERY-major ([block_q, block_k] scores,
# one query block a grid step); the dkv kernel works KEY-major
# ([block_k, block_q], one key block a grid step): with the keys on
# sublanes its two sums over queries, dv += p^T g and dk += ds^T q, are
# plain products, and lse and delta (sequence along lanes, the only
# layout Mosaic takes for them) broadcast down sublanes as they are.
# Query-major, each of the two sums contracts its left operand over its
# first axis, a [block_q, block_k] float32 transpose before the product,
# and lse and delta change from lanes to sublanes, every turn of the loop
# (PERF.md, PR 64).
# The dkv grid's innermost axis is the group's query head r, and Q and dO
# are blocked by (b, r): their block index changes EVERY grid step, so the
# pipeline fetches the block anew every step.  A window layer's step is
# therefore handed only the rows its key block can reach (block_k + window
# - 1, in whole query blocks: _dkv_query_rows), at an element offset that
# follows the key block; the head's whole sequence, 2 x 4 MiB at S 16,384,
# took 11.1 us a step against 5.5 us of work (PERF.md, PR 66).
# Env overrides (read once at import) let a hardware tuning sweep try
# block shapes per subprocess without touching call sites:
# DLROVER_TPU_FLASH_BLOCK_{Q,K} / DLROVER_TPU_FLASH_BWD_BLOCK_{Q,K}.
import os as _os


def _env_block(name: str, default: int) -> int:
    try:
        v = int(_os.environ.get(name, default))
    except ValueError:
        return default
    # 0/negative would crash deep inside _block_sizes with no mention
    # of the env var; fall back instead.
    return v if v > 0 else default


DEFAULT_BLOCK_Q = _env_block("DLROVER_TPU_FLASH_BLOCK_Q", 512)
DEFAULT_BLOCK_K = _env_block("DLROVER_TPU_FLASH_BLOCK_K", 512)
DEFAULT_BWD_BLOCK_Q = _env_block("DLROVER_TPU_FLASH_BWD_BLOCK_Q", 256)
DEFAULT_BWD_BLOCK_K = _env_block("DLROVER_TPU_FLASH_BWD_BLOCK_K", 512)
NEG_INF = -1e30
# The forward kernel's two outputs as the backward rule receives them,
# under names a remat policy can keep (``models/llama.py``'s block remat
# does: the kernel then runs once per block application, not again in
# front of the block's backward).  Identities under any other policy.
SAVED_NAMES = ("flash_out", "flash_lse")


# ---------------------------------------------------------------------------
# Reference (jnp) implementation — ground truth + CPU fallback
# ---------------------------------------------------------------------------


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """[B,H,S,D] attention in fp32 accumulation.  ``segment_ids`` [B,S]
    restricts attention to same-segment pairs (packed sequences).  GQA:
    k/v may carry KV < H heads (H % KV == 0).  ``window > 0`` adds
    sliding-window attention: position q attends only keys with
    ``0 <= q - k < window``.  ``v [B, KV, S, Dv]`` may be of another width
    than ``q`` and ``k``: the output is ``[B, H, S, Dv]``."""
    if k.shape[1] != q.shape[1]:  # GQA: broadcast kv heads
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), Sk - Sq)
        s = jnp.where(mask, s, NEG_INF)
    if window > 0:
        # Honors the full contract 0 <= q - k < window even when
        # causal=False (the lower bound duplicates causal's mask, but
        # without it this ground-truth path would silently leave future
        # keys visible).
        Sq, Sk = s.shape[-2], s.shape[-1]
        qpos = (Sk - Sq) + np.arange(Sq)[:, None]
        kpos = np.arange(Sk)[None, :]
        diff = qpos - kpos
        s = jnp.where(jnp.asarray((diff >= 0) & (diff < window)),
                      s, NEG_INF)
    if segment_ids is not None:
        seg = (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        )  # [B, 1, Sq, Sk]
        s = jnp.where(seg, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k, causal,
                sm_scale, seq_len, segmented=False, window=0):
    from jax.experimental import pallas as pl

    # Blocks carry a leading unit (batch*head) dim:
    # q_ref: [1, block_q, D]; k_ref: [1, S, D]; v_ref: [1, S, Dv]; o_ref:
    # [1, block_q, Dv];
    # lse_ref: [1, 1, block_q]; segmented adds seg_ref: [1, 1, S_pad] int32.
    if segmented:
        seg_ref, o_ref, lse_ref = rest
    else:
        seg_ref = None
        o_ref, lse_ref = rest
    block_q = q_ref.shape[1]
    d = v_ref.shape[2]
    qi = pl.program_id(1)
    q_start = qi * block_q

    q = q_ref[0].astype(jnp.float32) * sm_scale
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    num_k_blocks = pl.cdiv(seq_len, block_k)
    if causal:
        # K blocks strictly after this Q block's last row are fully masked.
        last_q = q_start + block_q - 1
        num_k_blocks = jnp.minimum(
            num_k_blocks, (last_q // block_k) + 1
        )
    start_ki = 0
    if window > 0:
        # K blocks entirely BELOW this Q block's window are skipped:
        # the earliest visible key is q_start - window + 1.
        start_ki = jnp.maximum(0, (q_start - window + 1) // block_k)

    def body(ki, carry):
        m, l, acc = carry
        k_start = ki * block_k
        kb = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal or window > 0:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            if causal:
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            if window > 0:
                s = jnp.where(qpos - kpos < window, s, NEG_INF)
        if segmented:
            seg_q = seg_ref[0, 0, pl.ds(q_start, block_q)]
            seg_k = seg_ref[0, 0, pl.ds(k_start, block_k)]
            s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
        # Mask K padding beyond seq_len.
        kpos2 = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(kpos2 < seq_len, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(start_ki, num_k_blocks, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse block is [1, 1, block_q]: block_q rides the 128-lane dim directly,
    # no 128x broadcast materialization (round-1 review Weak #3).
    lse_ref[0, 0] = (m + jnp.log(l_safe)).astype(jnp.float32)


def _block_sizes(S: int, block_q: int, block_k: int):
    """Clamp blocks to powers of two <= pow2-ceil(S) and pad S to a multiple
    of the larger block.  Power-of-two blocks keep the padding bounded (the
    naive lcm of a block and a clamped-to-S block can blow the sequence up
    by the block size itself, e.g. lcm(256, 301) = 77056)."""
    p2_ceil = 1 << max(0, (S - 1).bit_length())
    block_q = min(1 << (block_q.bit_length() - 1), p2_ceil)
    block_k = min(1 << (block_k.bit_length() - 1), p2_ceil)
    unit = max(block_q, block_k)
    S_pad = ((S + unit - 1) // unit) * unit
    return block_q, block_k, S_pad


def _seg3(segment_ids, S, S_pad):
    """[B, S] segment ids -> [B, 1, S_pad] int32, padding = -1 (matches
    no real segment, so padded positions are always masked).  Kept one
    row per BATCH — the grid's b axis covers B*H programs, so the seg
    BlockSpec index map divides by H instead of materializing H copies."""
    seg = segment_ids.astype(jnp.int32)
    if S_pad != S:
        seg = jnp.pad(seg, [(0, 0), (0, S_pad - S)], constant_values=-1)
    return seg[:, None, :]


def _kv_row_map(H: int, KV: int):
    """Grid row b in [0, B*H) -> row of the [B*KV, ...] k/v array its
    query head attends to (GQA: H % KV == 0 query heads share a kv head;
    the kernel reads the shared head in place, never materializing the
    repeat)."""
    rep = H // KV

    def index_map(b, i):
        return (b // H) * KV + (b % H) // rep, 0, 0

    return index_map


#: the compiler's own limit on a kernel's scoped VMEM; the chip has 128 MiB
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


#: what the flash kernels' blocks and temporaries take beside the resident
#: operands: at S 16,384 with 64-wide q and k under 128-wide v the operands
#: hold 12 MiB and the forward's blocks 4.54 more (the compiler's count),
#: which a room of 4 let through at the default limit.  No other shape that
#: runs holds between 11 and 12 MiB, so none takes another limit than before.
_FLASH_ROOM = 5 * 2 ** 20


def _vmem_params(resident_bytes: int, room: int = 4 * 2 ** 20) -> dict:
    """``pallas_call`` keywords for a kernel whose resident operands (K
    and V forward and for dq, ``[1, S, D]`` each; Q and dO for dkv, the
    rows a grid step holds; double-buffered) hold ``resident_bytes`` at
    once.  Under the compiler's
    default limit nothing is passed and the kernel compiles as it always
    has (S 8,192 at D 128: 8 MiB); past it (S 8,192 at D 256: 16 MiB
    before any block) the limit is raised to what the kernel holds plus
    room for its blocks and temporaries.  ``room`` is what the caller
    expects those to take: 4 MiB, and :data:`_FLASH_ROOM` for the flash
    kernels."""
    if resident_bytes + room <= _DEFAULT_SCOPED_VMEM:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(resident_bytes * 1.25) + 12 * 2 ** 20)}


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
               segment_ids=None, window=0):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    KV, Dv = k.shape[1], v.shape[3]
    sm_scale = 1.0 / np.sqrt(D)
    # Pad the sequence to block multiples: pl.ds clamps out-of-bounds
    # starts (dynamic_slice semantics), which would silently shift the
    # ragged last K block.  Padded keys are masked by seq_len below.
    block_q, block_k, S_pad = _block_sizes(S, block_q, block_k)
    if S_pad != S:
        pad = [(0, 0), (0, 0), (0, S_pad - S), (0, 0)]
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    grid = (B * H, pl.cdiv(S_pad, block_q))

    q3 = q.reshape(B * H, S_pad, D)
    k3 = k.reshape(B * KV, S_pad, D)
    v3 = v.reshape(B * KV, S_pad, Dv)
    kv_map = _kv_row_map(H, KV)

    segmented = segment_ids is not None
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, sm_scale=sm_scale,
        seq_len=S, segmented=segmented, window=window,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, S_pad, D), kv_map),
        pl.BlockSpec((1, S_pad, Dv), kv_map),
    ]
    inputs = [q3, k3, v3]
    if segmented:
        in_specs.append(
            pl.BlockSpec((1, 1, S_pad), lambda b, i: (b // H, 0, 0))
        )
        inputs.append(_seg3(segment_ids, S, S_pad))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S_pad, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S_pad), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **_vmem_params(2 * S_pad * (D + Dv) * k.dtype.itemsize, _FLASH_ROOM),
    )(*inputs)
    return (
        out.reshape(B, H, S_pad, Dv)[:, :, :S],
        lse.reshape(B, H, S_pad)[:, :, :S],
    )


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style, recompute-based).
#
# Two kernels, neither materializing the [S, S] score matrix:
#   dq kernel : grid (B*H, q_blocks); inner loop over K blocks recomputes
#               p = exp(q k^T * scale - lse), ds = p (dp - delta) scale,
#               accumulates dq += ds @ k.
#   dkv kernel: grid (B*KV, k_blocks, group); inner loop over Q blocks
#               (starting at the first causally-unmasked Q block)
#               recomputes p^T = exp(k q^T * scale - lse) key-major and
#               accumulates dv += p^T @ g and dk += ds^T @ q.
# delta = rowsum(o * do) is precomputed outside (cheap fused elementwise).
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest,
                   block_k, causal, sm_scale, seq_len, padded_len,
                   segmented=False, window=0):
    from jax.experimental import pallas as pl

    # q_ref/dq_ref: [1, block_q, D]; g_ref: [1, block_q, Dv]; k_ref: [1,
    # S_pad, D]; v_ref: [1, S_pad, Dv];
    # lse_ref/delta_ref: [1, 1, block_q]; seg_ref: [1, 1, S_pad] int32.
    if segmented:
        seg_ref, dq_ref = rest
    else:
        seg_ref = None
        (dq_ref,) = rest
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    qi = pl.program_id(1)
    q_start = qi * block_q

    q = q_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    num_k_blocks = pl.cdiv(padded_len, block_k)
    if causal:
        last_q = q_start + block_q - 1
        num_k_blocks = jnp.minimum(num_k_blocks, (last_q // block_k) + 1)
    start_ki = 0
    if window > 0:
        start_ki = jnp.maximum(0, (q_start - window + 1) // block_k)

    def body(ki, acc):
        k_start = ki * block_k
        kb = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [block_q, block_k]
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        if causal or window > 0:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            if causal:
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            if window > 0:
                s = jnp.where(qpos - kpos < window, s, NEG_INF)
        if segmented:
            seg_q = seg_ref[0, 0, pl.ds(q_start, block_q)]
            seg_k = seg_ref[0, 0, pl.ds(k_start, block_k)]
            s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # masked entries -> exp(-inf) = 0
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * sm_scale
        return acc + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        start_ki, num_k_blocks, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = acc.astype(dq_ref.dtype)


#: query blocks a turn of the dkv kernel's loop.  One block's chain (product,
#: exp, product, products) leaves the MXU and the VPU waiting on each other,
#: and a loop with traced bounds is not pipelined across its turns; with
#: several blocks in one body the scheduler fills one's waits with another's
#: work.  A full layer's call at S 16,384, 32/4 heads of 128 on a v5e: 39.8
#: ms at 1, 33.5 at 2, 30.2 at 4 (tools/flash_bench.py; PERF.md, PR 64).
_DKV_UNROLL = 4


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    *rest, block_q, causal, sm_scale, seq_len,
                    padded_len, segmented=False, window=0):
    from jax.experimental import pallas as pl

    # Grid (B*KV, k_blocks, rep): the innermost r axis streams one GQA
    # query head at a time (VMEM holds ONE [1,1,rows,D] q/g block, not
    # the whole group), revisiting the same compact [1, block_k, D]
    # dk/dv output block — r==0 initializes it, r>0 accumulates (fp32
    # output; cast to the param dtype happens outside).
    # lse_ref/delta_ref: [1, 1, 1, S_pad] (the unit dim keeps the block's
    # last two dims (1, S_pad) whole-axis, which Mosaic requires when
    # rep > 1); seg_ref: [1, 1, S_pad] int32.
    # KEY-MAJOR (the header says why): s^T, p^T, dp^T and ds^T are
    # [block_k, block_q], keys on sublanes and queries on lanes; lse and
    # delta are read as [1, block_q] rows.
    if segmented:
        seg_ref, dk_ref, dv_ref = rest
    else:
        seg_ref = None
        dk_ref, dv_ref = rest
    block_k = k_ref.shape[1]
    ki = pl.program_id(1)
    r = pl.program_id(2)
    k_start = ki * block_k

    # Operands go to the MXU in the dtype they arrive in, p^T and ds^T
    # narrowed to it where they are made: the MXU's one pass narrows a
    # float32 operand to bfloat16 itself, so from bfloat16 operands dk and
    # dv are the same bits either way (tools/flash_bench.py --against).
    kb = k_ref[0].astype(q_ref.dtype)
    vb = v_ref[0].astype(g_ref.dtype)
    if segmented:
        seg_k = seg_ref[0, 0, pl.ds(k_start, block_k)][:, None]

    num_q_blocks = pl.cdiv(padded_len, block_q)
    # Q blocks whose last row precedes k_start are fully causally masked.
    start_qi = (k_start // block_q) if causal else 0
    # q_ref and g_ref hold the head's whole sequence, or (a window layer:
    # _dkv_query_rows) the rows this key block can reach, from query block
    # first_qi on: a block is read at its place behind that one.
    rows = q_ref.shape[2]
    first_qi = 0
    if rows < padded_len:
        first_qi = jnp.minimum(start_qi, (padded_len - rows) // block_q)
    if window > 0:
        # Q rows beyond k_start + block_k - 1 + window - 1 see none of
        # this K block.
        num_q_blocks = jnp.minimum(
            num_q_blocks,
            ((k_start + block_k + window - 2) // block_q) + 1,
        )

    def body(qi, carry):
        dk_acc, dv_acc = carry
        q_start = qi * block_q
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0
        )
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1
        )
        held = pl.ds((qi - first_qi) * block_q, block_q)
        qb = q_ref[0, 0, held, :]
        gb = g_ref[0, 0, held, :]
        lse_row = lse_ref[0, 0, :, pl.ds(q_start, block_q)]  # [1, block_q]
        delta_row = delta_ref[0, 0, :, pl.ds(q_start, block_q)]
        sT = jax.lax.dot_general(
            kb, qb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # k q^T -> [block_k, block_q]
        if padded_len != seq_len:
            sT = jnp.where(qpos < seq_len, sT, NEG_INF)
            sT = jnp.where(kpos < seq_len, sT, NEG_INF)
        if causal:
            sT = jnp.where(qpos >= kpos, sT, NEG_INF)
        if window > 0:
            sT = jnp.where(qpos - kpos < window, sT, NEG_INF)
        if segmented:
            seg_q = seg_ref[0, :, pl.ds(q_start, block_q)]  # [1, block_q]
            sT = jnp.where(seg_k == seg_q, sT, NEG_INF)
        pT = jnp.exp(sT - lse_row)
        dv_acc = dv_acc + jax.lax.dot_general(
            pT.astype(gb.dtype), gb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ g -> [block_k, Dv]
        dpT = jax.lax.dot_general(
            vb, gb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # v g^T -> [block_k, block_q]
        dsT = pT * (dpT - delta_row) * sm_scale
        dk_acc = dk_acc + jax.lax.dot_general(
            dsT.astype(qb.dtype), qb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T @ q -> [block_k, D]
        return dk_acc, dv_acc

    # _DKV_UNROLL query blocks a turn of the loop, then the blocks left
    # over one a turn: the same blocks in the same order, so the same sums.
    def several(j, carry):
        for i in range(_DKV_UNROLL):
            carry = body(start_qi + _DKV_UNROLL * j + i, carry)
        return carry

    whole = (num_q_blocks - start_qi) // _DKV_UNROLL
    carry = jax.lax.fori_loop(
        0, whole, several,
        (jnp.zeros(k_ref.shape[1:], jnp.float32),
         jnp.zeros(v_ref.shape[1:], jnp.float32)),
    )
    dk_acc, dv_acc = jax.lax.fori_loop(
        start_qi + _DKV_UNROLL * whole, num_q_blocks, body, carry
    )

    @pl.when(r == 0)
    def _init():
        dk_ref[0] = dk_acc
        dv_ref[0] = dv_acc

    @pl.when(r > 0)
    def _accum():
        dk_ref[0] = dk_ref[0] + dk_acc
        dv_ref[0] = dv_ref[0] + dv_acc


def _dkv_query_rows(window: int, block_q: int, block_k: int,
                    S_pad: int) -> int:
    """Rows of Q and dO that ``flash_bwd_dkv`` holds a grid step.  The query
    blocks a key block's loop visits under a causal window lie between the
    block's first key and the last query that sees its last key, ``window -
    1`` behind it: ``block_k + window - 1`` rows from the key block's first
    query block on, in whole query blocks (1,536 at a window of 1,024 and
    blocks of 256 and 512, 2,560 at 2,048, 4,608 at 4,096).  No window, or
    one that reaches that far: the head's whole padded sequence."""
    if window <= 0:
        return S_pad
    reach = max(block_q, block_k) + window - 2
    return min((reach // block_q + 1) * block_q, S_pad)


def _flash_bwd_pallas(q, k, v, out, lse, g, causal, block_q, block_k,
                      interpret, segment_ids=None, window=0):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    KV, Dv = k.shape[1], v.shape[3]
    rep = H // KV
    sm_scale = 1.0 / np.sqrt(D)
    block_q, block_k, S_pad = _block_sizes(S, block_q, block_k)
    delta = jnp.sum(
        out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1
    )  # [B, H, S]
    if S_pad != S:
        pad4 = [(0, 0), (0, 0), (0, S_pad - S), (0, 0)]
        pad3 = [(0, 0), (0, 0), (0, S_pad - S)]
        q, k, v, g = (jnp.pad(t, pad4) for t in (q, k, v, g))
        lse = jnp.pad(lse, pad3)
        delta = jnp.pad(delta, pad3)

    q3, g3 = q.reshape(B * H, S_pad, D), g.reshape(B * H, S_pad, Dv)
    k3 = k.reshape(B * KV, S_pad, D)
    v3 = v.reshape(B * KV, S_pad, Dv)
    kv_map = _kv_row_map(H, KV)
    lse2 = lse.reshape(B * H, 1, S_pad).astype(jnp.float32)
    delta2 = delta.reshape(B * H, 1, S_pad)

    segmented = segment_ids is not None
    common = [q3, k3, v3, g3, lse2, delta2]
    seg_spec = []
    if segmented:
        common.append(_seg3(segment_ids, S, S_pad))
        seg_spec = [
            pl.BlockSpec((1, 1, S_pad), lambda b, i: (b // H, 0, 0))
        ]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, causal=causal, window=window,
            sm_scale=sm_scale, seq_len=S, padded_len=S_pad,
            segmented=segmented,
        ),
        grid=(B * H, pl.cdiv(S_pad, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S_pad, D), kv_map),
            pl.BlockSpec((1, S_pad, Dv), kv_map),
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ] + seg_spec,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S_pad, D), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        **_vmem_params(2 * S_pad * (D + Dv) * k.dtype.itemsize, _FLASH_ROOM),
    )(*common)

    # dkv: grid (B*KV, k_blocks, rep) — the innermost axis streams the
    # GQA group's query heads one at a time into the SAME compact output
    # block (fp32 accumulation), so dk/dv never exist at query-head size
    # in HBM and per-program VMEM stays at one head's footprint.
    q4 = q3.reshape(B * KV, rep, S_pad, D)
    g4 = g3.reshape(B * KV, rep, S_pad, Dv)
    lse4 = lse2.reshape(B * KV, rep, 1, S_pad)
    delta4 = delta2.reshape(B * KV, rep, 1, S_pad)
    dkv_in = [q4, k3, v3, g4, lse4, delta4]
    # Q and dO change with r, the innermost axis: the pipeline fetches their
    # block anew EVERY grid step.  A window layer's step is handed the rows
    # its key block can reach and no more (the header says why), from the
    # key block's first query block on, or from where the array's last
    # ``rows`` rows start if that is earlier: no read leaves the array.
    rows = _dkv_query_rows(window if causal else 0, block_q, block_k, S_pad)

    def q_spec(width):
        if rows == S_pad:
            return pl.BlockSpec((1, 1, S_pad, width),
                                lambda b, i, r: (b, r, 0, 0))
        # an element offset on one axis makes every axis of the block one;
        # Mosaic takes the row's only with its multiple stated
        return pl.BlockSpec(
            tuple(pl.Element(n) for n in (1, 1, rows, width)),
            lambda b, i, r: (b, r, pl.multiple_of(jnp.minimum(
                jax.lax.div(i * block_k, block_q) * block_q,
                S_pad - rows), block_q), 0))

    dkv_seg_spec = []
    if segmented:
        dkv_in.append(common[-1])
        dkv_seg_spec = [
            pl.BlockSpec((1, 1, S_pad), lambda b, i, r: (b // KV, 0, 0))
        ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, causal=causal, window=window,
            sm_scale=sm_scale, seq_len=S, padded_len=S_pad,
            segmented=segmented,
        ),
        grid=(B * KV, pl.cdiv(S_pad, block_k), rep),
        in_specs=[
            q_spec(D),
            pl.BlockSpec((1, block_k, D), lambda b, i, r: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, r: (b, i, 0)),
            q_spec(Dv),
            pl.BlockSpec((1, 1, 1, S_pad), lambda b, i, r: (b, r, 0, 0)),
            pl.BlockSpec((1, 1, 1, S_pad), lambda b, i, r: (b, r, 0, 0)),
        ] + dkv_seg_spec,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i, r: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, r: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KV, S_pad, D), jnp.float32),
            jax.ShapeDtypeStruct((B * KV, S_pad, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **_vmem_params(2 * rows * (D + Dv) * q.dtype.itemsize, _FLASH_ROOM),
    )(*dkv_in)

    return (
        dq.reshape(B, H, S_pad, D)[:, :, :S],
        dk.reshape(B, KV, S_pad, D)[:, :, :S].astype(k.dtype),
        dv.reshape(B, KV, S_pad, Dv)[:, :, :S].astype(v.dtype),
    )


# ---------------------------------------------------------------------------
# Backward (reference math, jnp) — ground truth for the Pallas backward in
# tests.  (The CPU path, backend="reference", differentiates
# reference_attention with plain autodiff and never reaches this.)
# ---------------------------------------------------------------------------


def _flash_bwd_reference(q, k, v, out, lse, g, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), Sk - Sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[..., None])  # exact softmax via saved lse
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    delta = jnp.sum(of * gf, axis=-1)  # [B,H,Sq]
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def _flash_attention(q, k, v, causal, block_q, block_k, bwd_block_q,
                     bwd_block_k, interpret, window):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                        window=window)
    return out


def _name_saved(out, lse):
    return (checkpoint_name(out, SAVED_NAMES[0]),
            checkpoint_name(lse, SAVED_NAMES[1]))


def _fwd_rule(q, k, v, causal, block_q, block_k, bwd_block_q, bwd_block_k,
              interpret, window):
    out, lse = _name_saved(*_flash_fwd(
        q, k, v, causal, block_q, block_k, interpret, window=window))
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret,
              window, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        q, k, v, out, lse, g, causal, bwd_block_q, bwd_block_k, interpret,
        window=window,
    )
    return dq, dk, dv


_flash_attention.defvjp(_fwd_rule, _bwd_rule)


# Segmented (packed-sequence) variant: segment_ids is a traced arg whose
# cotangent is None.  Separate from the dense path so the unsegmented
# kernels stay byte-identical (no dead mask ops on the hot path).
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10)
)
def _flash_attention_seg(q, k, v, seg, causal, block_q, block_k,
                         bwd_block_q, bwd_block_k, interpret, window):
    out, _ = _flash_fwd(
        q, k, v, causal, block_q, block_k, interpret, segment_ids=seg,
        window=window,
    )
    return out


def _seg_fwd_rule(q, k, v, seg, causal, block_q, block_k, bwd_block_q,
                  bwd_block_k, interpret, window):
    out, lse = _name_saved(*_flash_fwd(
        q, k, v, causal, block_q, block_k, interpret, segment_ids=seg,
        window=window,
    ))
    return out, (q, k, v, seg, out, lse)


def _seg_bwd_rule(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                  interpret, window, res, g):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        q, k, v, out, lse, g, causal, bwd_block_q, bwd_block_k, interpret,
        segment_ids=seg, window=window,
    )
    return dq, dk, dv, None


_flash_attention_seg.defvjp(_seg_fwd_rule, _seg_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S] packed sequences
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    bwd_block_q: int = DEFAULT_BWD_BLOCK_Q,
    bwd_block_k: int = DEFAULT_BWD_BLOCK_K,
    backend: Optional[str] = None,  # None=auto | 'pallas' | 'reference'
    interpret: bool = False,
    window: int = 0,  # >0: sliding-window (needs causal)
) -> jax.Array:
    """[B, H, S, D] flash attention.

    GQA: ``k``/``v`` may carry ``KV < H`` heads (``H % KV == 0``); the
    kernels read each shared kv head in place — the repeat is never
    materialized in HBM — and ``dk``/``dv`` come back ``[B, KV, S, D]``.

    ``segment_ids`` [B, S] restricts attention to same-segment pairs —
    packed-sequence training (the reference's pack-mask flash-attn
    variants, ``flash_attn_func_ext.py`` GLM/pack masks) without
    materializing the mask.

    auto backend: Pallas on TPU, jnp reference elsewhere (XLA fuses it
    acceptably on CPU; the Pallas path is the production TPU path).
    """
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"GQA needs H % KV == 0, got H={q.shape[1]} KV={k.shape[1]}"
        )
    if window > 0 and not causal:
        raise ValueError("window > 0 requires causal attention")
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend == "reference":
        return reference_attention(q, k, v, causal, segment_ids, window)
    statics = (causal, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, window)
    # One kernel call per shard of the mesh in scope: batch over
    # dp/fsdp, heads over tp (q and kv heads split alike, so each tp
    # shard keeps whole GQA groups).
    free, batch_axes, head_axis = shard_axes(
        q.shape[0], (q.shape[1], k.shape[1])
    )
    qkv = P(batch_axes, head_axis, None, None)
    if segment_ids is not None:
        return per_shard(
            lambda q, k, v, seg: _flash_attention_seg(q, k, v, seg, *statics),
            free, (qkv, qkv, qkv, P(batch_axes, None)), qkv,
        )(q, k, v, segment_ids)
    return per_shard(
        lambda q, k, v: _flash_attention(q, k, v, *statics),
        free, (qkv, qkv, qkv), qkv,
    )(q, k, v)
