"""Grouped matmul for MoE experts.

Analogue of the reference's grouped-GEMM extension (``Grouped_GEMM_MoE``
``modules/moe/grouped_gemm_moe.py:345`` + the CANN ``gmm.cpp`` NPU op): many
[m_e, K] x [K, N] products, one per expert, where the m_e are data-dependent.

One formulation, the one ``models/llama.py::_moe_swiglu`` calls: flat
``[T, K]`` rows sorted by group plus the group sizes; nothing of size
``groups x T`` is built, forward or backward (both transposes are ragged
products again).  On the TPU it is the megablox ``gmm`` kernel that ships
with JAX (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and
for the row gradient, ``tgmm`` for the weight gradient); everywhere else,
and at shapes the kernel's tiling does not divide, ``jax.lax.ragged_dot``,
its reference.  Measured on one v5e at the OLMoE cell's shapes (262,144
rows in 64 groups, 2048 x 1024, bf16, SwiGLU forward + backward): gmm at
this tiling 65.9 ms (150 TFLOP/s), ``lax.ragged_dot`` 86.8 ms (114);
PERF.md section 6, PR 27.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.per_shard import free_axes

#: rows, contraction and columns of one tile: the fastest of six tried on
#: the v5e; (512, 2048, 1024) and (1024, 1024, 1024) overflow VMEM
TILING = (512, 1024, 1024)


def kernel_takes(dtype, m: int, k: int, n: int) -> bool:
    """Whether the kernel's tiling divides ``[m, k] x [groups, k, n]``:
    whole row tiles of bf16, and a contraction and columns of whole
    128-lane vectors.  An expert 1,856 wide (14.5 x 128) is not taken, in
    either of its matmuls: ``lax.ragged_dot`` runs it, unpadded."""
    return (dtype == jnp.bfloat16 and m % TILING[0] == 0
            and k % 128 == 0 and n % 128 == 0)


def _kernel_fits(tokens: jax.Array, weights: jax.Array) -> bool:
    """:func:`kernel_takes`, and GSPMD cannot partition a Mosaic kernel:
    under a mesh with a free axis the reference goes, which the
    partitioner splits itself."""
    return (kernel_takes(tokens.dtype, *tokens.shape, weights.shape[2])
            and not free_axes()[0])


def backend_for(dtype, k: int, n: int) -> str:
    """The backend :func:`grouped_matmul_ragged` chooses for itself on this
    device for an expert's two matmuls, ``[rows, k] x [k, n]`` and ``[rows,
    n] x [n, k]``, at a buffer of whole row tiles outside a mesh's free
    axes: what ``models.llama.program_facts`` journals as
    ``moe_expert_backend``."""
    # the rule is the same with ``k`` and ``n`` exchanged
    fits = kernel_takes(dtype, TILING[0], k, n)
    return "pallas" if jax.default_backend() == "tpu" and fits else (
        "reference")


def grouped_matmul_ragged(
    tokens: jax.Array,  # [T, K] sorted by group
    weights: jax.Array,  # [E, K, N]
    group_sizes: jax.Array,  # [E] int32, sum <= T
    *,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged grouped GEMM: rows [offset_e : offset_e + size_e] x weights[e].

    The sizes may sum to fewer rows than ``tokens`` has (a layer that
    holds a share of the experts computes the pairs routed to those): the
    kernel's grid then ends with the last group's tile, forward and in both
    transposes, and no time is spent on the rest.  The rows of the result
    past the sum are UNSPECIFIED (the kernel never writes them; the
    reference writes zeros): a caller keeps them out of every sum, as
    ``_moe_swiglu`` does with a mask on the rows going in and on the
    result coming out."""
    if backend is None:
        backend = "pallas" if (jax.default_backend() == "tpu"
                               and _kernel_fits(tokens, weights)) else (
            "reference")
    if backend != "pallas":
        return jax.lax.ragged_dot(tokens, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    (_, k), n = tokens.shape, weights.shape[2]
    tiling = (TILING[0], min(TILING[1], k), min(TILING[2], n))
    return gmm(tokens, weights, group_sizes, tokens.dtype, tiling,
               None, None, False, interpret)
