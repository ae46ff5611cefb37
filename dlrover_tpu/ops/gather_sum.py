"""Gather K rows a token, weigh them and sum: ``[R, C] -> [N, C]``.

The token side of a routed block (``models/llama.py::_routed_sum``): the
expert rows of a token's K picks, wherever the sort put them, multiplied by
the router's weights and summed — and, with weights of one, the transpose
of the dispatch gather.  ``out[n] = sum_k w[n, k] * rows[index[n, k]]``,
the products and the sum in float32 from operands in ``rows``' dtype, one
rounding.  **A pick whose weight is zero contributes a selected zero**: its
row is never multiplied (it may be unwritten memory) and, in the kernel,
never read.

In ``jax.numpy`` it is an XLA gather that writes the ``[N*K, C]`` rows and
a weighted sum over k that reads them back; XLA's gather is a fusion of its
own and joins no reader, whatever the formulation.  On a TPU it is one
Pallas kernel, ``gather_sum``, and no array of ``N*K`` rows is written: a
grid over tiles of tokens, a tile's picks and weights in SMEM, ``rows`` left
in HBM, one row DMA a LIVE pick into a VMEM buffer (the next tile's rows are
fetched while this tile's are summed), a float32 multiply-and-sum on the
VPU and one ``[tile, C]`` write.  Mosaic slices an HBM array by whole tiles
of its last two dims, so ``rows`` goes in as ``[R, C/128, 128]`` — a row is
one contiguous tile — and the result comes out so: one XLA relayout copy
each way.

The rule (:func:`_kernel_fits`): TPU backend, bfloat16, ``C`` a multiple of
1,024, whole tiles of tokens, no free mesh axis (GSPMD cannot partition a
Mosaic kernel).  The caller (``models/llama.py::_gather_k``) brings it the
blocks that hold a share of the experts, where most picks are dead; with
every pick live the kernel has no row to skip and XLA's gather moves rows
as fast.  Measured on one v5e, forward, kernel with its two copies against
gather + einsum: 5.3 against 10.3 ms at 32,768 tokens x 4 picks out of
40,960 rows (a quarter live), 2.0 against 3.4 ms at 16,384 x 4 out of
10,240 (none live), and 12.9 against 11.5 ms at 32,768 x 8 with all 262,144
live (``tools/gather_sum_bench.py``; PERF.md section 6, PR 48).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.per_shard import free_axes

#: picks (token, k) of one grid step: up to 1,024 rows of 4 KB in flight,
#: twice (this tile's and the next one's), 8 MB of VMEM at C = 2048; 512
#: picks, or two tokens a loop turn, read the same on the v5e
_TILE_PICKS = 1024

f32, i32 = jnp.float32, jnp.int32


def weighted_sum(picked: jax.Array, weights) -> jax.Array:
    """``sum_k weights[n, k] * picked[n, k]``, ``[N, K, C] -> [N, C]``:
    the op's arithmetic over rows that are gathered already and all
    finite (``weights`` None: ones)."""
    if weights is None:
        return jnp.sum(picked, axis=1, dtype=f32).astype(picked.dtype)
    return jnp.einsum("nkc,nk->nc", picked, weights,
                      preferred_element_type=f32).astype(picked.dtype)


def _reference(rows, index, weights):
    n, k = index.shape
    picked = rows[index.reshape(-1)].reshape(n, k, -1)
    if weights is not None:
        picked = jnp.where(weights[..., None] != 0, picked, 0)
    return weighted_sum(picked, weights)


def _tile(k: int) -> int:
    """Tokens of one grid step."""
    return max(8, _TILE_PICKS // k)


def _kernel_fits(rows: jax.Array, index: jax.Array) -> bool:
    n, k = index.shape
    return (rows.dtype == jnp.bfloat16 and rows.shape[1] % 1024 == 0
            and n % _tile(k) == 0 and not free_axes()[0])


def _kernel(idx_ref, nxt_ref, w_ref, rows_ref, out_ref, buf, sem, live,
            *, tile: int, k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    step, steps = pl.program_id(0), pl.num_programs(0)
    slot = step % 2

    def fetch(ref, into, t, more):
        """Start the row DMAs of token ``t``'s live picks; how many."""
        started = jnp.int32(0)
        for j in range(k):
            p = t * k + j
            row = ref[0, p]
            wanted = jnp.logical_and(row >= 0, more)

            @pl.when(wanted)
            def _():
                pltpu.make_async_copy(
                    rows_ref.at[row], buf.at[into, p], sem.at[into]).start()
            started += wanted.astype(i32)
        return started

    @pl.when(step == 0)
    def _():
        live[0] = jax.lax.fori_loop(
            0, tile, lambda t, c: c + fetch(idx_ref, 0, t, True), jnp.int32(0))

    def wait(_, carry):
        pltpu.make_async_copy(
            rows_ref.at[0], buf.at[slot, 0], sem.at[slot]).wait()
        return carry

    jax.lax.fori_loop(0, live[slot], wait, 0)
    more = step + 1 < steps

    def token(t, started):
        started += fetch(nxt_ref, 1 - slot, t, more)
        acc = None
        for j in range(k):
            p = t * k + j
            w = w_ref[0, p]
            term = jnp.where(w != 0, w * buf[slot, p].astype(f32), 0.0)
            acc = term if acc is None else acc + term
        out_ref[t] = acc.astype(out_ref.dtype)
        return started

    live[1 - slot] = jax.lax.fori_loop(0, tile, token, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_sum_kernel(rows, index, weights, interpret):
    # jitted: a step calls it four times a routed block, and one trace and
    # one lowering a shape serve them all (71 ms a call otherwise)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = index.shape
    r, c = rows.shape
    tile = _tile(k)
    steps = n // tile
    weights = (jnp.ones(index.shape, f32) if weights is None
               else weights.astype(f32))
    # a dead pick is told by its index: it starts no DMA
    index = jnp.where(weights != 0, index, -1).astype(i32)
    index = index.reshape(steps, 1, tile * k)

    def picks(of):
        return pl.BlockSpec((None, 1, tile * k), lambda i: (of(i), 0, 0),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, k=k),
        grid=(steps,),
        in_specs=[picks(lambda i: i),
                  picks(lambda i: jnp.minimum(i + 1, steps - 1)),
                  picks(lambda i: i),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, c // 128, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c // 128, 128), rows.dtype),
        scratch_shapes=[pltpu.VMEM((2, tile * k, c // 128, 128), rows.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), i32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gather_sum",
    )(index, index, weights.reshape(steps, 1, tile * k),
      rows.reshape(r, c // 128, 128))
    return out.reshape(n, c)


def gather_sum(
    rows: jax.Array,  # [R, C]
    index: jax.Array,  # [N, K] int32, every entry in [0, R)
    weights: Optional[jax.Array],  # [N, K] in rows' dtype; None: ones
    *,
    backend: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """``out[n] = sum_k weights[n, k] * rows[index[n, k]]``, ``[N, C]``:
    float32 products and sum, one rounding to ``rows``' dtype; a pick of
    weight zero contributes a selected zero, whatever its row holds.
    ``backend``: ``"pallas"`` / ``"reference"``, None for the op's own
    choice."""
    if backend is None:
        backend = "pallas" if (jax.default_backend() == "tpu"
                               and _kernel_fits(rows, index)) else (
            "reference")
    if backend != "pallas":
        return _reference(rows, index, weights)
    return _gather_sum_kernel(rows, index, weights, interpret)
