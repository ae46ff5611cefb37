"""The routed block's token side alone, on the chip: ``out[n] = sum_k
w[n, k] * rows[inverse[n*K + k]]`` forward, and its gradients, at the
routed cells' shapes, for each way of computing it.

    chiprun -- python3 tools/gather_sum_bench.py [--iters 10]

Candidates, forward: ``gather_einsum`` (what the block ran before PR 48: an
XLA gather that writes ``[N*K, C]``, then ``einsum nkc,nk->nc``),
``k_gathers`` (K gathers of ``[N, C]`` and one weighted add: the one other
form XLA offers, its gather is a fusion of its own and never joins its
reader), ``reference`` and ``pallas`` (``ops.gather_sum``'s two backends; its own
rule takes the kernel where the buffer has fewer rows than picks).
``grad`` (``d rows`` and ``d weights`` of ``sum(out * cot)`` and nothing
else, so whatever of the forward the backward does not need is dropped, as
in a training step's backward rule): ``gather_einsum`` differentiated by
JAX (gathers ``[N*K, C]`` again for the weights' gradient, forms the
``[N*K, C]`` cotangent) against ``models.llama._combine_rows``' own rule —
on the sorted side, needing nothing of the forward, where the buffer ends
before every pick; the gathered rows kept where every pick has a row (the
``olmoe`` shape) — under each backend of ``gather_sum``.  One ``GATHER_SUM`` line a shape and
candidate: milliseconds a call, the median over ``--iters`` batches of ten
calls dispatched back to back (so the host's dispatch hides behind the
call before it: until PR 56 every call was waited for, and some 0.7 ms of
dispatch rode on each), the live picks, and the largest distance from
``gather_einsum``; a candidate that refuses a shape says so.  The whole table is also written to
``chiprun_out/gather_sum_bench.json``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: (tokens, k, width, experts, experts held, buffer rows, router skew)
SHAPES = {
    "lfm2": (32768, 4, 2048, 32, 8, 40960, None),
    "olmoe": (32768, 8, 2048, 64, 64, 262144, None),
    "glm_even": (16384, 4, 2048, 64, 8, 10240, None),
    # a collapsed router: nearly every pick goes to absent experts
    "glm_collapsed": (16384, 4, 2048, 64, 8, 10240, 0.002),
    # the cells' own shapes (PR 56): ten picks, 21 lane tiles, three tiles
    "qwen3_next": (16384, 10, 2048, 512, 32, 12800, None),
    "nemotron": (24576, 6, 2688, 128, 8, 11776, None),
    "glm": (24576, 4, 2048, 64, 8, 15360, None),
}


def _routing(rng, n, k, e, held, rows, held_share):
    """``(order, inverse, weights, live_rows)`` as ``_moe_swiglu`` makes
    them: the held experts sort first, the buffer keeps ``rows`` rows, a
    pick of an absent expert has weight zero."""
    if held_share is None:
        expert = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    else:
        expert = rng.randint(held, e, size=(n, k))
        hit = rng.rand(n, k) < held_share
        expert[hit] = rng.randint(0, held, size=int(hit.sum()))
    flat = expert.reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    inverse = np.argsort(order).astype(np.int32)
    live = int((flat < held).sum())
    assert live < rows or rows == n * k, (live, rows)
    weights = np.where(expert < held, rng.rand(n, k) + 0.1, 0.0)
    return (order[:rows], np.minimum(inverse, rows - 1), weights,
            None if held == e else live)


#: calls dispatched back to back before the host waits: the device then
#: runs one behind the other and a call's dispatch (some 0.7 ms of host
#: time on the chip machine) is hidden behind the call before it
BACK_TO_BACK = 10


def _median_ms(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(BACK_TO_BACK):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / BACK_TO_BACK)
    return float(np.median(times) * 1e3)


def _distance(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--toy", action="store_true",
                    help="a rehearsal off the chip: small shapes, the "
                         "kernel in interpret mode")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.ops import gather_sum as gs

    f32, bf16 = jnp.float32, jnp.bfloat16

    def gather_einsum(rows, weights, order, inverse, live_rows):
        n, k = weights.shape
        picked = rows[inverse].reshape(n, k, -1)
        return jnp.einsum("nkc,nk->nc", picked, weights,
                          preferred_element_type=f32).astype(rows.dtype)

    def k_gathers(rows, weights, order, inverse, live_rows):
        n, k = weights.shape
        index = inverse.reshape(n, k)
        acc = sum(rows[index[:, j]].astype(f32)
                  * weights[:, j, None].astype(f32) for j in range(k))
        return acc.astype(rows.dtype)

    def ours(backend):
        def fn(rows, weights, order, inverse, live_rows):
            n, k = weights.shape
            return gs.gather_sum(rows, inverse.reshape(n, k), weights,
                                 backend=backend, interpret=args.toy)
        return fn

    def sorted_side(backend):
        # _combine_rows' own rule over the named forward
        def fn(rows, weights, order, inverse, live_rows):
            steered = functools.partial(
                gs.gather_sum, backend=backend, interpret=args.toy)
            with mock.patch.object(llama, "gather_sum", steered):
                return llama._combine_rows(rows, weights, order, inverse,
                                           live_rows)
        return fn

    forwards = {"gather_einsum": gather_einsum, "k_gathers": k_gathers,
                "reference": ours("reference"), "pallas": ours("pallas")}
    backwards = {"gather_einsum": gather_einsum,
                 "combine_rows_reference": sorted_side("reference"),
                 "combine_rows_pallas": sorted_side("pallas")}

    def with_grad(fn):
        def loss(rows, weights, cot, order, inverse, live_rows):
            out = fn(rows, weights, order, inverse, live_rows)
            return jnp.sum(out.astype(f32) * cot.astype(f32))
        return jax.grad(loss, argnums=(0, 1))

    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind}",
          flush=True)
    table = []
    for name in args.shapes:
        n, k, c, e, held, rows_n, share = SHAPES[name]
        if args.toy:
            # eight lane tiles, or three where the width is no multiple
            n, c, rows_n = n // 64, 384 if c % 1024 else 1024, rows_n // 64
        rng = np.random.RandomState(48)
        order, inverse, weights, live = _routing(
            rng, n, k, e, held, rows_n, share)
        rows = jnp.asarray(rng.randn(rows_n, c), bf16)
        if live is not None:
            rows = jnp.where(llama._live_mask(rows_n, live), rows, 0)
        cot = jnp.asarray(rng.randn(n, c), bf16)
        operands = (jnp.asarray(order), jnp.asarray(inverse),
                    None if live is None else jnp.int32(live))
        weights = jnp.asarray(weights, bf16)
        live_picks = int(np.count_nonzero(np.asarray(weights, np.float32)))
        live_share = live_picks / (n * k)
        base = {}
        for phase, fns, lead in (("fwd", forwards, (rows, weights)),
                                 ("grad", backwards,
                                  (rows, weights, cot))):
            for cand, fn in fns.items():
                run = jax.jit(fn if phase == "fwd" else with_grad(fn))
                line = {"shape": name, "phase": phase, "candidate": cand,
                        "live_share": round(live_share, 4),
                        "live_picks": live_picks}
                try:
                    ms = _median_ms(run, (*lead, *operands), args.iters)
                    out = jax.tree.leaves(run(*lead, *operands))
                    base.setdefault(phase, out)
                    line.update(ms=round(ms, 3),
                                distance=[_distance(a, b)
                                          for a, b in zip(out, base[phase])])
                except Exception as err:  # noqa: BLE001 - a shape it refuses
                    line["refused"] = f"{type(err).__name__}: {err}"[:160]
                table.append(line)
                print("GATHER_SUM " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gather_sum_bench.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
