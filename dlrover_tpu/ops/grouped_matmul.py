"""Grouped matmul for MoE experts.

Analogue of the reference's grouped-GEMM extension (``Grouped_GEMM_MoE``
``modules/moe/grouped_gemm_moe.py:345`` + the CANN ``gmm.cpp`` NPU op): many
[m_e, K] x [K, N] products, one per expert, where the m_e are data-dependent.

TPU-first formulations (both MXU-friendly, no scalar loops):

- ``grouped_matmul_dense``: tokens already bucketed to [E, C, K] capacity
  buffers -> one batched einsum (the default; pairs with
  ``parallel.moe.moe_layer``).
- ``grouped_matmul_ragged``: flat [T, K] tokens + group sizes, via
  ``jax.lax.ragged_dot`` (XLA's native ragged GEMM on TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul_dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """[E, C, K] x [E, K, N] -> [E, C, N] (batched over experts)."""
    return jnp.einsum(
        "eck,ekn->ecn", x, w,
    )


def grouped_matmul_ragged(
    tokens: jax.Array,  # [T, K] sorted by group
    weights: jax.Array,  # [E, K, N]
    group_sizes: jax.Array,  # [E] int32, sum == T
) -> jax.Array:
    """Ragged grouped GEMM: rows [offset_e : offset_e + size_e] x weights[e].
    """
    return jax.lax.ragged_dot(tokens, weights, group_sizes)
