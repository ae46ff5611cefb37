"""The chunked scan's kernel pair alone, on the chip: ``ops.ssd``'s
``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` at the two state-space cells' shapes —
the Granite hybrid's ``[2, 8192, 64 x 64]`` in ONE group with chunks of 256,
the one-branch hybrid's ``[3, 8192, 64 x 64]`` in EIGHT groups with chunks of
128, a state of 128 both — forward and forward + backward, this checkout's
beside another's.

    chiprun -- python3 tools/ssd_bench.py [--other .parent] [--iters 10]

The operands are what ``ssd_chunked`` hands ``chunk_outputs``: ``x``, ``B``,
``C`` and the entering states bfloat16, ``dt`` a softplus, the cumulative
sums of ``dt A`` float32.  ``x`` and ``dy`` come in, and ``y`` and ``dx`` go
out, as ``[B, S, H P]`` — what ``conv_silu_fwd`` writes and the gated norm
reads — and are split into chunks and heads inside the timed program: a
program's own arguments have a fixed layout, and a six-dim argument with 64
lanes last is stored padded, which no array of the model is.  ``fwd`` is
the call; ``fwd_bwd`` the call and its ``jax.vjp`` on a float32 ``dy``:
``y`` and the seven cotangents.  One ``SSD`` line a cell, phase and tree:
median milliseconds a call of ``--iters`` batches of ten calls enqueued back
to back, and the bytes of the four sequence-sized arrays once each way
(forward: ``x`` in, ``y`` out; with the backward ``x`` twice and ``dy`` in,
``y`` and ``dx`` out) over that time in GB/s and as a share of the chip's
819 — the small operands and the MXU's work are not in it, so it says how
far from a copy the pair is, not what bounds it.  ``--other PATH`` loads
``dlrover_tpu/ops/ssd.py`` of the checkout at PATH as a module of its own
(what it imports of the package is this checkout's) and adds its lines and
``distance``, each result's distance from the other tree's (the norm of the
difference over the norm, ``0.0`` where the bits are the same).  The table
is also written to ``chiprun_out/ssd_bench.json``; ``--toy`` rehearses it
off the chip (two chunks, the kernels in interpret mode).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: one v5e chip's HBM, GB/s (``benchmark/harness/peaks.py``)
HBM_GBPS = 819.0

#: calls enqueued back to back under one host-clock reading
CALLS = 10

#: cell -> (sequences, groups, heads a group, chunk); heads of 64, state 128
CELLS = {"granite4_h_micro": (2, 1, 64, 256),
         "nemotron3_nano": (3, 8, 8, 128)}
RESULTS = ("y", "dx", "ddt", "dcs", "dB", "dC", "dentering", "dD")


def _load(root):
    path = os.path.join(root, "dlrover_tpu", "ops", "ssd.py")
    spec = importlib.util.spec_from_file_location(
        "ssd_of_" + os.path.basename(os.path.normpath(root)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _median_ms(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(CALLS)])
        times.append((time.perf_counter() - t0) / CALLS)
    return float(np.median(times) * 1e3)


def _distance(a, b):
    # on the device: a result is 268 MB
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


def _operands(cell, seed, toy):
    import jax
    import jax.numpy as jnp

    bsz, g, r, q = CELLS[cell]
    c, p, n = (2 if toy else 8192 // q), 64, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, c, q, g, r)) - 2.0)
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (g, r)))
    ops = (jax.random.normal(k[0], (bsz, c, q, g, r, p)).astype(bf16), dt,
           jnp.cumsum(dt * a, axis=2),
           (jax.random.normal(k[3], (bsz, c, q, g, n)) * n ** -.5).astype(
               bf16),
           jax.random.normal(k[4], (bsz, c, q, g, n)).astype(bf16),
           (jax.random.normal(k[5], (c, bsz, g, r, p, n)) * .1).astype(bf16),
           jax.random.normal(k[6], (g, r)))
    return ops, jax.random.normal(k[7], ops[0].shape, f32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=69)
    ap.add_argument("--other", default="")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    import jax

    from dlrover_tpu.ops import ssd

    trees = {"this": ssd}
    if args.other:
        trees["other"] = _load(os.path.abspath(args.other))
    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind}",
          flush=True)
    table = []
    for cell in CELLS:
        ops, dy = _operands(cell, args.seed, args.toy)
        shape = ops[0].shape
        flat = (shape[0], shape[1] * shape[2], -1)
        ops, dy = (ops[0].reshape(flat),) + ops[1:], dy.reshape(flat)
        x_bytes = ops[0].nbytes
        # x bfloat16, y and dy float32, dx bfloat16
        hbm = {"fwd": 3 * x_bytes, "fwd_bwd": 8 * x_bytes}
        outs = {}
        for tree, mod in trees.items():
            def fwd(x, *rest, mod=mod):
                return mod.chunk_outputs(
                    x.reshape(shape), *rest, backend="pallas",
                    interpret=args.toy).reshape(x.shape)

            def fwd_bwd(*o, fwd=fwd):
                y, vjp = jax.vjp(fwd, *o)
                return (y,) + vjp(dy)

            for phase, fn in (("fwd", jax.jit(fwd)),
                              ("fwd_bwd", jax.jit(fwd_bwd))):
                ms = _median_ms(fn, ops, args.iters)
                out = fn(*ops)
                outs[tree, phase] = (out,) if phase == "fwd" else out
                gbps = hbm[phase] / (ms * 1e-3) / 1e9
                line = {"cell": cell, "x": list(shape),
                        "phase": phase, "tree": tree, "ms": round(ms, 3),
                        "hbm_gbps": round(gbps, 1),
                        "hbm_share_pct": round(100 * gbps / HBM_GBPS, 1)}
                if tree == "other":
                    line["distance"] = {
                        name: _distance(a, b) for name, a, b in zip(
                            RESULTS, outs["this", phase], outs[tree, phase])}
                table.append(line)
                print("SSD " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_bench.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
