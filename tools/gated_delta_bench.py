"""The gated delta rule alone, on the chip: ``ops.gated_delta``'s kernel pair
against its ``jax.numpy`` chunked form at the Qwen3-Next cell's shapes (two
sequences of 8,192, 32 value heads of 128, chunks of 64), forward and every
gradient.

    chiprun -- python3 tools/gated_delta_bench.py [--iters 5]

``q`` and ``k`` are unit vectors in bfloat16 (``q`` scaled by ``D^-1/2``),
``v`` bfloat16, ``beta = sigmoid(N(0, 1))`` and ``g = -exp(A_log) softplus(a
+ dt_bias)`` in float32 with ``A_log = log U(0, 16)``, ``dt_bias = 1`` and
``a ~ N(0, 1)`` — as ``models.llama._gdn_mixer`` forms them from a fresh
``_init_gdn``: some heads decay by ``e^-16`` a token and underflow a chunk.
``fwd`` is the call; ``grad`` the five gradients of ``sum(o * cot) +
sum(state^2)``.  One ``GATED_DELTA`` line a phase and candidate: median
milliseconds of ``--iters`` calls and, for the kernels, each result's
distance from the ``jax.numpy`` form's (the norm of the difference over the
norm).  ``--block_lanes`` times the kernels at other widths of a grid step
than the module's.  The table is also written to
``chiprun_out/gated_delta_bench.json`` (``--root .parent``:
``gated_delta_bench.parent.json``); ``--toy`` rehearses it off the chip
(short sequences, the kernels in interpret mode).  ``--channel`` times the
rule with a decay per key CHANNEL (``kda_chunk_fwd`` / ``kda_chunk_bwd``) at
the Kimi Linear cell's shapes: one sequence of 16,384, 32 heads of 128,
chunks of 128, ``g [B, S, H, 128]`` with a rate drawn per channel; and
counts, in one ``GATED_DELTA_PASSES`` line, the MXU passes of one head's
chunk as the kernels compute it, forward and through ``jax.vjp``
(:func:`mxu_passes`).  Beside the op alone it times, as ``scope_*``, all of
``models.llama._kda_mixer``'s scope ``kda_scan`` — the decay from its
projection (``g = -exp(A_log) softplus(f + dt_bias)``, ``f`` bfloat16),
``beta``'s sigmoid, q and k as the convolutions put them out with their L2
norms wherever the tree in ``--root`` runs them, and the op — with the
gradients of everything it reads.  ``--root DIR`` takes ``dlrover_tpu`` from
another checkout (the parent's: ``--root .parent``), whose op may know no
``unit_scales`` and then gets its q and k normalised in ``jax.numpy`` as its
mixer did; two calls, one a tree, put both trees' lines side by side.
"""

from __future__ import annotations

import argparse
import functools
import inspect
import json
import os
import sys
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NAMES = ("q", "k", "v", "g", "beta")
SCOPE_NAMES = ("q", "k", "v", "f", "b", "A_log", "dt_bias")


def _median_ms(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def _distance(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def mxu_passes(fn, *args) -> dict:
    """The MXU passes of ``fn(*args)`` by its jaxpr: every ``dot_general``,
    sub-jaxprs included, as tiles of 128 (a product of ``[m, 128]`` and
    ``[128, 128]`` is ``m / 128`` passes), six times at ``highest`` ->
    ``{"highest": ..., "default": ...}`` pass-equivalents."""
    import jax

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    passes = {"highest": 0.0, "default": 0.0}
    for eqn in eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        (contract, _), _ = eqn.params["dimension_numbers"]
        lhs, out = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
        tiles = np.prod(out[:-1]) / 128 * -(-out[-1] // 128) * np.prod(
            [-(-lhs[d] // 128) for d in contract])
        highest = "HIGHEST" in str(eqn.params["precision"])
        passes["highest" if highest else "default"] += (
            6 if highest else 1) * float(tiles)
    return passes


def channel_chunk_passes(**chunk_kwargs) -> dict:
    """:func:`mxu_passes` of one head's chunk under a per-channel decay as
    ``kda_chunk_fwd`` computes it (bfloat16 operands, the whole-tile
    inverse; ``chunk_kwargs``: ``unit_scales``) and of its ``jax.vjp`` as
    ``kda_chunk_bwd`` takes it."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import gated_delta as gd

    chunk = functools.partial(gd._channel_chunk, dt=jnp.bfloat16,
                              inverse=gd._whole_tile_inverse, **chunk_kwargs)
    n = gd.CHANNEL_CHUNK
    ops = [jnp.zeros(shape, jnp.float32) for shape in (
        (n, 128),) * 4 + ((n, 1), (128, 128))]

    def pulled(*ops):
        out, pull = jax.vjp(chunk, *ops)
        return pull(out)
    return {"fwd": mxu_passes(chunk, *ops), "vjp": mxu_passes(pulled, *ops)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--block_lanes", type=int, nargs="*", default=[])
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--channel", action="store_true")
    ap.add_argument("--root", default="",
                    help="take dlrover_tpu from this checkout")
    args = ap.parse_args()
    if args.root:
        sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import gated_delta as gd

    f32, bf16 = jnp.float32, jnp.bfloat16
    bsz, s, h, d, chunk = (1, 256, 8, 128, 64) if args.toy else (
        2, 8192, 32, 128, 64)
    if args.channel:
        chunk = gd.CHANNEL_CHUNK
        bsz, s = (1, 256) if args.toy else (1, 16384)
    rates = (h, d) if args.channel else (h,)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    a_log = jnp.log(jax.random.uniform(keys[0], rates, f32, 1e-6, 16.0))
    operands = (
        (unit(jax.random.normal(keys[1], (bsz, s, h, d))) * d ** -0.5
         ).astype(bf16),
        unit(jax.random.normal(keys[2], (bsz, s, h, d))).astype(bf16),
        jax.random.normal(keys[3], (bsz, s, h, d)).astype(bf16),
        -jnp.exp(a_log) * jax.nn.softplus(
            jax.random.normal(keys[4], (bsz, s) + rates) + 1.0),
        jax.nn.sigmoid(jax.random.normal(keys[5], (bsz, s, h))))
    cot = jax.random.normal(keys[6], (bsz, s, h, d))
    raw = jax.random.split(keys[6], 4)
    # what ``kda_scan`` reads: q and k before their norms (rows of many
    # lengths), the decay's projection in bfloat16 and its two parameters
    scope_operands = None if not args.channel else (
        (jax.random.normal(raw[0], (bsz, s, h, d)) * jnp.exp(
            jax.random.normal(raw[1], (bsz, s, h, 1)))).astype(bf16),
        jax.random.normal(raw[2], (bsz, s, h, d)).astype(bf16),
        operands[2], jax.random.normal(keys[4], (bsz, s, h * d)).astype(bf16),
        jax.random.normal(keys[5], (bsz, s, h)).astype(bf16), a_log,
        jnp.ones(rates, f32))
    takes_raw = "unit_scales" in inspect.signature(
        gd.gated_delta_chunked).parameters

    def candidate(backend):
        def fwd(*ops, **kwargs):
            return gd.gated_delta_chunked(
                *ops, chunk, backend=backend, interpret=args.toy, **kwargs)

        def scope(q, k, v, f, b, a_log, dt_bias):
            """``_kda_mixer``'s ``kda_scan``, as the tree at hand runs it."""
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                f.astype(f32).reshape(bsz, s, h, d) + dt_bias)
            if takes_raw:
                return fwd(q, k, v, g, beta, unit_scales=(d ** -0.5, 1.0))
            return fwd((unit(q.astype(f32)) * d ** -0.5).astype(bf16),
                       unit(k.astype(f32)).astype(bf16), v, g, beta)

        def summed(fn):
            def loss(*ops):
                o, state, _ = fn(*ops)
                return jnp.sum(o * cot) + jnp.sum(jnp.square(state))
            return loss
        out = {"fwd": jax.jit(fwd),
               "grad": jax.jit(jax.grad(summed(fwd), argnums=range(5)))}
        if args.channel:
            out.update(scope_fwd=jax.jit(scope), scope_grad=jax.jit(
                jax.grad(summed(scope), argnums=range(7))))
        return out

    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind} "
          f"ops={os.path.relpath(os.path.dirname(gd.__file__), REPO)}",
          flush=True)
    table, base = [], {}
    if args.channel:
        table.append({"passes": channel_chunk_passes()})
        print("GATED_DELTA_PASSES " + json.dumps(table[0]), flush=True)
    runs = [("jax_numpy", "reference", None), ("kernels", "pallas", None)] + [
        (f"kernels_{lanes}_lanes", "pallas", lanes)
        for lanes in args.block_lanes]
    for label, backend, lanes in runs:
        with mock.patch.object(gd, "_BLOCK_LANES",
                               lanes or gd._BLOCK_LANES):
            for phase, fn in candidate(backend).items():
                ops = scope_operands if "scope" in phase else operands
                ms = _median_ms(fn, ops, args.iters)
                out = fn(*ops)
                base.setdefault(phase, out)
                names = ("o", "state", "decay_min") if "fwd" in phase else [
                    "d" + n for n in (
                        SCOPE_NAMES if "scope" in phase else NAMES)]
                line = {"phase": phase, "candidate": label,
                        "tree": args.root or ".",
                        "ms": round(ms, 3),
                        "distance": {n: _distance(x, y) for n, x, y in zip(
                            names, out, base[phase])}}
                table.append(line)
                print("GATED_DELTA " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    tree = os.path.basename(os.path.abspath(args.root)).strip(".")
    with open("chiprun_out/gated_delta_bench%s.json" % (
            "." + tree if tree else ""), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
