"""The causal convolution and its ``silu`` alone, on the chip:
``ops.conv_silu``'s kernel pair against its ``jax.numpy`` form at the two
cells' shapes — the delta-rule mixer's ``[2, 8192, 8192]`` with four taps and
no bias (Qwen3-Next), the state-space mixer's ``[2, 8192, 4352]`` with four
taps and a bias (Granite hybrid) — forward and every gradient.

    chiprun -- python3 tools/conv_silu_bench.py [--iters 10]

``x`` is bfloat16 ``N(0, 1)``, the taps float32 ``U(-1/2, 1/2)`` (``K^-1/2``,
as ``models.llama`` draws them), the bias float32 ``N(0, 0.1)``.  ``fwd`` is
the call; ``grad`` the gradients of ``sum(y * cot)`` by ``x``, ``w`` (and
``b``), which runs the backward kernel alone: the cotangent needs no
forward.  One ``CONV_SILU`` line a shape, phase and candidate: median
milliseconds a call of ``--iters`` batches of ten calls enqueued back to
back, the bytes that have to touch HBM (forward:
``x`` in, ``y`` out; backward: ``x`` and ``dy`` in, ``dx`` out) over that time
in GB/s and as a share of the chip's 819, and, for the kernels, each
result's distance from the ``jax.numpy`` form's (the norm of the difference
over the norm).  ``--tiles 1024x512 ...`` times the kernels at other tiles
(rows x lanes) than the module's.  The table is also written to
``chiprun_out/conv_silu_bench.json``; ``--toy`` rehearses it off the chip
(short sequences, the kernels in interpret mode).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: one v5e chip's HBM, GB/s (``benchmark/harness/peaks.py``)
HBM_GBPS = 819.0


#: calls enqueued back to back under one host-clock reading: a call lasts
#: about a millisecond, a dispatch a tenth of that
CALLS = 10


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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=54)
    ap.add_argument("--tiles", nargs="*", default=[])
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import conv_silu as cs

    f32, bf16 = jnp.float32, jnp.bfloat16
    bsz, s = (2, 2 * cs._ROW_TILE) if args.toy else (2, 8192)
    shapes = [("gdn", 512 if args.toy else 8192, False),
              ("ssm", 256 if args.toy else 4352, True)]
    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind}",
          flush=True)
    table = []
    for mixer, c, with_bias in shapes:
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        x = jax.random.normal(keys[0], (bsz, s, c)).astype(bf16)
        w = jax.random.uniform(keys[1], (4, c), f32, -0.5, 0.5)
        operands = (x, w) + (
            (0.1 * jax.random.normal(keys[2], (c,)),) if with_bias else ())
        cot = jax.random.normal(keys[3], (bsz, s, c)).astype(bf16)
        hbm = {"fwd": 2 * x.nbytes, "grad": 3 * x.nbytes}
        base = {}
        module = (cs._ROW_TILE, cs._BLOCK_LANES)
        runs = [("jax_numpy", "reference", module),
                ("kernels", "pallas", module)] + [
            (f"kernels_{tile}", "pallas", tuple(map(int, tile.split("x"))))
            for tile in args.tiles]
        for label, backend, (rows, lanes) in runs:
            def fwd(*ops, backend=backend):
                return cs.causal_conv1d_silu(
                    *ops, backend=backend, interpret=args.toy)

            def loss(*ops, fwd=fwd):
                return jnp.sum(fwd(*ops).astype(f32) * cot.astype(f32))

            phases = {"fwd": jax.jit(fwd), "grad": jax.jit(
                jax.grad(loss, argnums=tuple(range(len(operands)))))}
            for phase, fn in phases.items():
                with mock.patch.multiple(cs, _ROW_TILE=rows,
                                         _BLOCK_LANES=lanes):
                    ms = _median_ms(fn, operands, args.iters)
                out = fn(*operands)
                out = (out,) if phase == "fwd" else out
                base.setdefault(phase, out)
                names = ("y",) if phase == "fwd" else ("dx", "dw", "db")
                gbps = hbm[phase] / (ms * 1e-3) / 1e9
                line = {"mixer": mixer, "shape": [bsz, s, c], "phase": phase,
                        "candidate": label, "ms": round(ms, 3),
                        "hbm_gbps": round(gbps, 1),
                        "hbm_share_pct": round(100 * gbps / HBM_GBPS, 1),
                        "distance": {n: _distance(a, b) for n, a, b in zip(
                            names, out, base[phase])}}
                table.append(line)
                print("CONV_SILU " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/conv_silu_bench.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
