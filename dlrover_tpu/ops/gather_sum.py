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
Pallas kernel, ``gather_sum``, whose scalar work, DMAs and buffers are
sized by the LIVE picks and not by ``N*K``:

- **The live picks, listed in two steps.**  XLA moves a token's live picks
  to the front of its K, in ascending k, and counts them
  (:func:`_live_first`: one-hot sums along K, elementwise over the tokens,
  0.04 ms a call).  Every pass along ``N*K`` that would list them outright
  costs more than the kernel: a cumulative sum 0.6 ms, a scatter 2.1,
  ``jnp.nonzero`` 2.8, a sort 1.4, and a sort of the ``R`` sorted pairs
  1.2 (163,840 picks, one v5e; PERF.md section 6, PR 56).  The kernel's
  scalar core then walks a tile's tokens and writes the tile's list into
  SMEM — a dead pick costs it nothing, a token 26 ns.
- **The kernel**: a grid over tiles of tokens, one step ahead of itself.
  Step ``s`` lists tile ``s``; then it sums tile ``s - 1`` chunk by chunk:
  ``_CHUNK`` rows at most are in flight by one row DMA a live pick
  (``rows`` stays in HBM), and while a chunk is multiplied and added in
  float32 into the tile's accumulator, in ascending k, the next chunk's
  rows — this tile's, or the first of the tile just listed — are started.
  A tile takes as many chunks as it has live picks for (a skewed step,
  the fall-back buffer of every pick): nothing is dropped or capped.  One
  rounding and one ``[tile, C]`` write a tile, by DMA, while the next tile
  is summed; a token with no live pick is zeros.
- **Layouts.**  Mosaic slices an HBM array by whole tiles of its last two
  dims, so ``rows`` goes in as ``[R, C/128, 128]`` — a row is one
  contiguous tile, its lane tiles padded to a multiple of 8 (21 to 24 at
  ``C`` = 2,688; the padding is zeros and never written back) — at the
  price of one XLA relayout copy of the ``R`` rows.  The result comes out
  as ``[N, C]``: the accumulator's ``[C/128, 128]`` a token is turned into
  the token's row by strided loads when the tile is written, so no copy of
  ``N`` rows follows the kernel (until PR 56 one did: 2.2 of 5.3 ms a
  call at LFM2's shapes were the two copies).

The rule (:func:`_kernel_fits`): TPU backend, bfloat16, ``C`` a whole
number of 128-lane tiles, a power of two of at least 8 tokens that divides
``N`` (K is no part of it), no free mesh axis (GSPMD cannot partition a
Mosaic kernel).  The caller (``models/llama.py::_gather_k``) brings it the
blocks that hold a share of the experts, where most picks are dead; with
every pick live the kernel has no row to skip and XLA's gather moves rows
as fast (12.9 against 11.5 ms at 32,768 x 8 with all 262,144 live; PERF.md
section 6, PR 48).  Device time of a call on one v5e, the kernel with its
copy against the ``jax.numpy`` form: 1.05 against 5.44 ms at 16,384 tokens
x 10 picks out of 12,800 rows (10,223 live), 1.57 against 7.56 at 24,576 x
6 out of 11,776 rows of 2,688 columns (9,070 live), 3.70 against 8.18 at
32,768 x 4 out of 40,960 (32,747 live; the kernel it replaces took 4.7 with
its two copies) and 1.36 against 2.95 at 24,576 x 4 out of 15,360 (12,084
live).  What a call costs: 26 ns a token for the walk (0.43 to 0.85 ms), and
a live pick 14 ns of list, 14 ns of multiply-and-add and 20 to 50 ns of
its row DMA that nothing hides (50 where a tile has a chunk's worth of
them) — the kernel alone 92 to 128 ns a live pick (a traced call and
``tools/gather_sum_bench.py``; PERF.md section 6, PR 56).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.per_shard import free_axes

#: tokens of one result tile: the float32 accumulator and the two staged
#: copies of the result, 2 + 2 MB of VMEM at C = 2048
_TILE_TOKENS = 256
#: live picks of one chunk: as many row DMAs in flight, twice (the chunk
#: that is summed and the one behind it), 2 + 2 MB of VMEM at C = 2048
_CHUNK = 256
#: a row's lane tiles are padded to a multiple of this
_SUBLANES = 8

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


def _tile(n: int) -> int:
    """Tokens of one result tile: the largest power of two that divides
    ``n``, up to ``_TILE_TOKENS``."""
    return min(_TILE_TOKENS, n & -n)


def _kernel_fits(rows: jax.Array, index: jax.Array) -> bool:
    return (rows.dtype == jnp.bfloat16 and rows.shape[1] % 128 == 0
            and _tile(index.shape[0]) >= 8 and not free_axes()[0])


def _live_first(index, weights):
    """A token's live picks moved to the front of its K, in ascending k:
    ``(how many [N], their rows [N, K], their weights [N, K] float32)``.
    Elementwise over the tokens: no pass along ``N*K`` (a cumulative sum,
    a sort or a scatter of that length is 0.6 to 2.8 ms on the v5e)."""
    k = index.shape[1]
    live = weights != 0
    rank = jnp.cumsum(live, axis=1, dtype=i32) - live
    # [N, K, K]: pick k goes to place j
    goes = live[:, :, None] & (rank[:, :, None] == jnp.arange(k, dtype=i32))
    return (jnp.sum(live, axis=1, dtype=i32),
            jnp.sum(jnp.where(goes, index[:, :, None], 0), axis=1, dtype=i32),
            jnp.sum(jnp.where(goes, weights.astype(f32)[:, :, None], 0.0),
                    axis=1))


def _kernel(count_ref, index_ref, weight_ref, rows_ref, out_ref,
            buf, acc, stage, sem, out_sem, picks, pick_w, state,
            *, tile: int, k: int, chunk: int, lanes: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # step s lists tile s's live picks and sums tile s - 1
    step, tiles = pl.program_id(0), pl.num_programs(0) - 1
    sublanes = acc.shape[0] // tile
    ROW, AT, LIVE, PAR = 0, 1, 2, 3  # picks' and state's first index
    new, old = step % 2, (step + 1) % 2

    @pl.when(step == 0)
    def _():
        state[PAR, 0] = 0
        state[LIVE, 0] = 0
        state[LIVE, 1] = 0
        acc[...] = jnp.zeros_like(acc)

    @pl.when(step < tiles)
    def _():
        def token(n, found):
            def pick(j, found):
                picks[ROW, new, found] = index_ref[0, n * k + j]
                picks[AT, new, found] = n * sublanes
                pick_w[new, found] = weight_ref[0, n * k + j]
                return found + 1

            return jax.lax.fori_loop(0, count_ref[0, n], pick, found)

        state[LIVE, new] = jax.lax.fori_loop(0, tile, token, jnp.int32(0))

    @pl.when(step == tiles)
    def _():
        state[LIVE, new] = 0

    def fetch(of, at, into, j):
        pltpu.make_async_copy(rows_ref.at[picks[ROW, of, at + j]],
                              buf.at[into, j], sem.at[into]).start()

    def fetch_all(of, at, into, lo, hi):
        def one(j, carry):
            fetch(of, at, into, j)
            return carry

        jax.lax.fori_loop(lo, hi, one, 0)

    live = jnp.where(step > 0, state[LIVE, old], 0)
    following = jnp.minimum(state[LIVE, new], chunk)
    rounds = (live + chunk - 1) // chunk

    def one_round(r, par):
        base = r * chunk
        here = jnp.minimum(live - base, chunk)

        def wait(_, carry):
            pltpu.make_async_copy(
                rows_ref.at[0], buf.at[par, 0], sem.at[par]).wait()
            return carry

        jax.lax.fori_loop(0, here, wait, 0)
        # in flight meanwhile: this tile's next chunk, or the next tile's
        # first
        last = r + 1 == rounds
        of = jnp.where(last, new, old)
        at = jnp.where(last, 0, base + chunk)
        ahead = jnp.where(last, following,
                          jnp.minimum(live - base - chunk, chunk))

        def pick(j, carry):
            @pl.when(j < ahead)
            def _():
                fetch(of, at, 1 - par, j)

            to = pl.ds(pl.multiple_of(picks[AT, old, base + j], 8), sublanes)
            acc[to] += pick_w[old, base + j] * buf[par, j].astype(f32)
            return carry

        jax.lax.fori_loop(0, here, pick, 0)
        fetch_all(of, at, 1 - par, here, ahead)
        return 1 - par

    par = jax.lax.fori_loop(0, rounds, one_round, state[PAR, 0])
    state[PAR, 0] = par

    @pl.when(rounds == 0)
    def _():
        fetch_all(new, 0, par, 0, following)

    def written(t):
        return pltpu.make_async_copy(
            stage.at[t % 2], out_ref.at[pl.ds(t * tile, tile)],
            out_sem.at[t % 2])

    @pl.when(step > 0)
    def _():
        t = step - 1

        @pl.when(t >= 2)
        def _():
            written(t - 2).wait()

        # a token's [C/128, 128] becomes its row of [tile, C]
        for lane in range(lanes):
            stage[t % 2, :, lane * 128:(lane + 1) * 128] = acc[
                pl.ds(lane, tile, stride=sublanes)].astype(stage.dtype)
        acc[...] = jnp.zeros_like(acc)
        written(t).start()

    @pl.when(step == tiles)
    def _():
        for t in range(max(out_ref.shape[0] // tile - 2, 0),
                       out_ref.shape[0] // tile):
            written(t).wait()


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _gather_sum_kernel(rows, index, weights, tile, chunk, interpret):
    # jitted: a step calls it four times a routed block, and one trace and
    # one lowering a shape serve them all (71 ms a call otherwise)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = index.shape
    r, c = rows.shape
    lanes, tiles = c // 128, n // tile
    if weights is None:
        weights = jnp.ones(index.shape, f32)
    count, index, weights = _live_first(index, weights)
    # a row travels as [C/128, 128], whole sublane tiles of it: Mosaic
    # slices HBM by whole tiles of the last two dims
    sublanes = -(-lanes // _SUBLANES) * _SUBLANES
    rows = jnp.pad(rows.reshape(r, lanes, 128),
                   ((0, 0), (0, sublanes - lanes), (0, 0)))

    def of_tile(width):
        return pl.BlockSpec(
            (None, 1, width), lambda i: (jnp.minimum(i, tiles - 1), 0, 0),
            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, k=k, chunk=chunk, lanes=lanes),
        grid=(tiles + 1,),
        in_specs=[of_tile(tile), of_tile(tile * k), of_tile(tile * k),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, c), rows.dtype),
        scratch_shapes=[pltpu.VMEM((2, chunk, sublanes, 128), rows.dtype),
                        pltpu.VMEM((tile * sublanes, 128), f32),
                        pltpu.VMEM((2, tile, c), rows.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2, 2, tile * k), i32),
                        pltpu.SMEM((2, tile * k), f32),
                        pltpu.SMEM((4, 2), i32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gather_sum",
    )(count.reshape(tiles, 1, tile), index.reshape(tiles, 1, tile * k),
      weights.reshape(tiles, 1, tile * k), rows)


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
    return _gather_sum_kernel(rows, index, weights, _tile(index.shape[0]),
                              _CHUNK, interpret)
