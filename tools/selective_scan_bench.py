"""The selective scan alone, on the chip: ``ops.selective_scan``'s kernel
pair (``s6_scan_fwd`` / ``s6_scan_bwd``) against its ``jax.numpy`` form at
the Phi-4-mini-flash cell's shape — one sequence of 16,384, 5,120 channels
of 16 states — forward and the six gradients.

    chiprun -- python3 tools/selective_scan_bench.py [--iters 5]

``x`` is bfloat16 ``N(0, 1)``, ``dt`` the softplus of ``N(-4, 1)`` in float32
(steps of 1e-3 to 1e-1, as the mixer draws its bias), ``A = -(1 .. N)`` a
channel, ``B`` and ``C`` bfloat16 ``N(0, 1)``, ``D`` 1.  ``fwd`` is the call;
``grad`` the gradients of ``sum(y * cot)``.  One ``S6_SCAN`` line a phase and
candidate: median milliseconds a call, the bytes that have to touch HBM
(forward: ``x`` and ``dt`` in, ``y`` out; backward: ``x``, ``dt`` and ``dy``
in, ``dx`` and ``ddt`` out; float32 as the kernels take them) over that time
as a share of the chip's 819 GB/s, and, for the kernels, each result's
distance from the ``jax.numpy`` form's (the norm of the difference over the
norm).  The table is also written to ``chiprun_out/selective_scan_bench.json``;
``--toy`` rehearses it off the chip (a short sequence, 1,024 channels, the
kernels in interpret mode); ``--skip-reference`` times the kernels alone (the
``jax.numpy`` form walks 16,384 positions one ``while`` iteration each).
"""

from __future__ import annotations

import argparse
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
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=68)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import selective_scan as ss

    f32, bf16 = jnp.float32, jnp.bfloat16
    bsz, s, dn, n = (1, 300, 1024, 16) if args.toy else (1, 16384, 5120, 16)
    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind}",
          flush=True)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    operands = (
        jax.random.normal(keys[0], (bsz, s, dn)).astype(bf16),
        jax.nn.softplus(jax.random.normal(keys[1], (bsz, s, dn)) - 4.0),
        -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=f32), (dn, n)),
        jax.random.normal(keys[2], (bsz, s, n)).astype(bf16),
        jax.random.normal(keys[3], (bsz, s, n)).astype(bf16),
        jnp.ones((dn,), f32))
    cot = jax.random.normal(keys[4], (bsz, s, dn))
    tile = 4 * bsz * s * dn
    hbm = {"fwd": 3 * tile, "grad": 5 * tile}
    base, table = {}, []
    runs = ([] if args.skip_reference else [("jax_numpy", "reference")]) + [
        ("kernels", "pallas")]
    for label, backend in runs:
        def fwd(*ops, backend=backend):
            return ss.selective_scan(*ops, backend=backend,
                                     interpret=args.toy)[0]

        def loss(*ops, fwd=fwd):
            return jnp.sum(fwd(*ops) * cot)

        phases = {"fwd": jax.jit(fwd),
                  "grad": jax.jit(jax.grad(loss, argnums=tuple(range(6))))}
        for phase, fn in phases.items():
            ms = _median_ms(fn, operands, args.iters)
            out = fn(*operands)
            out = (out,) if phase == "fwd" else out
            base.setdefault(phase, out)
            names = ("y",) if phase == "fwd" else (
                "dx", "ddt", "dA", "dB", "dC", "dD")
            gbps = hbm[phase] / (ms * 1e-3) / 1e9
            line = {"shape": [bsz, s, dn, n], "phase": phase,
                    "candidate": label, "ms": round(ms, 3),
                    "hbm_share_pct": round(100 * gbps / HBM_GBPS, 1),
                    "finite": bool(all(
                        jnp.isfinite(a.astype(f32)).all() for a in out)),
                    "distance": {k: _distance(a, b) for k, a, b in zip(
                        names, out, base[phase])}}
            table.append(line)
            print("S6_SCAN " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/selective_scan_bench.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
