"""The gated norm over short lane groups alone, on the chip:
``ops.gated_norm``'s kernel pair against its ``jax.numpy`` form (what XLA
fuses) at the two cells' shapes — the delta-rule mixer's ``[2, 8192, 4096]``
in groups of 128, norm then gate (Qwen3-Next), the state-space mixer's ``[3,
8192, 4096]`` in groups of 512, gate then norm (Nemotron) — forward and
every gradient.

    chiprun -- python3 tools/gated_norm_bench.py [--iters 10]

``x`` is float32 ``N(0, 9)`` (what the rule's and the scan's kernels put
out), ``z`` bfloat16 ``N(0, 1)``, the gain float32 ``1 + N(0, 0.01)``.
``fwd`` is the call; ``grad`` the gradients of ``sum(y * cot)`` by ``x``,
``z`` and the gain, which runs the backward kernel alone: the cotangent needs
no forward.  One ``GATED_NORM`` line a shape, phase and candidate: median
milliseconds a call of ``--iters`` batches of ten calls enqueued back to
back, the bytes that have to touch HBM (forward: ``x`` and ``z`` in, ``y``
out; backward: ``x``, ``z`` and ``dy`` in, ``dx`` and ``dz`` out) over that
time in GB/s and as a share of the chip's 819, and, for the kernels, each
result's distance from the ``jax.numpy`` form's (the norm of the difference
over the norm).  ``--tiles 512x8 2048x64 ...`` times the kernels at other
walks (rows of a block x float32 registers an array of a step) than the
module's, and ``--mxu`` with the group's sum through the MXU (against a
block of ones, the partial sums as three bfloat16 terms that keep float32's
mantissa) instead of the cross-lane unit.  The table is also written to
``chiprun_out/gated_norm_bench.json``; ``--toy`` rehearses it off the chip
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


def _mxu_group_mean(a, group):
    """``ops.gated_norm._group_mean`` with no cross-lane reduction: the
    group's lane tiles added elementwise, the ``[rows, 128]`` partial sums
    as three bfloat16 terms (8 + 8 + 8 bits of mantissa, each remainder
    exact) times a ``[128, 128]`` block of ones, the exact products added in
    the MXU's float32 accumulator — every lane then holds its row's sum."""
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    part = sum(a[:, at:at + 128] for at in range(128, group, 128)) + a[:, :128]
    hi = part.astype(bf16)
    rest = part - hi.astype(f32)
    mid = rest.astype(bf16)
    ones = jnp.ones((128, 128), bf16)
    total = sum(jnp.dot(term, ones, preferred_element_type=f32)
                for term in (hi, mid, (rest - mid.astype(f32)).astype(bf16)))
    return total[:, :1] * (1.0 / group)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=60)
    ap.add_argument("--tiles", nargs="*", default=[])
    ap.add_argument("--mxu", action="store_true")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import gated_norm as gn

    f32, bf16 = jnp.float32, jnp.bfloat16
    s, w = (256, 1024) if args.toy else (8192, 4096)
    shapes = [("gdn", 2, 128, False), ("ssm", 3, 512, True)]
    device = jax.devices()[0]
    print(f"DEVICE platform={device.platform} kind={device.device_kind}",
          flush=True)
    table = []
    module = (gn._BLOCK_ROWS, gn._STEP_REGISTERS)
    runs = [("jax_numpy", "reference", module, gn._group_mean),
            ("kernels", "pallas", module, gn._group_mean)]
    for tile in args.tiles:
        runs.append((f"kernels_{tile}", "pallas",
                     tuple(map(int, tile.split("x"))), gn._group_mean))
    if args.mxu:
        runs.append(("kernels_mxu", "pallas", module, _mxu_group_mean))
    for mixer, bsz, group, gate_first in shapes:
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        x = 3.0 * jax.random.normal(keys[0], (bsz, s, w), f32)
        z = jax.random.normal(keys[1], (bsz, s, w)).astype(bf16)
        operands = (x, z, 1.0 + 0.1 * jax.random.normal(keys[2], (w,)))
        cot = jax.random.normal(keys[3], (bsz, s, w)).astype(bf16)
        given = {"fwd": operands, "grad": operands + (cot,)}
        hbm = {"fwd": x.nbytes + 2 * z.nbytes,
               "grad": 2 * x.nbytes + 3 * z.nbytes}
        base = {}
        for label, backend, (rows, registers), group_mean in runs:
            jax.clear_caches()  # the kernels' own jit does not key on these

            def fwd(*ops, backend=backend):
                return gn.gated_norm(
                    *ops, group=group, eps=1e-6, gate_first=gate_first,
                    backend=backend, interpret=args.toy)

            def loss(x, z, gain, cot, fwd=fwd):
                return jnp.sum(fwd(x, z, gain).astype(f32) * cot.astype(f32))

            phases = {"fwd": jax.jit(fwd),
                      "grad": jax.jit(jax.grad(loss, argnums=(0, 1, 2)))}
            for phase, fn in phases.items():
                with mock.patch.multiple(
                        gn, _BLOCK_ROWS=rows, _STEP_REGISTERS=registers,
                        _group_mean=group_mean):
                    try:
                        ms = _median_ms(fn, given[phase], args.iters)
                    except Exception as e:  # noqa: BLE001 - Mosaic refuses
                        print(f"GATED_NORM_REFUSED {mixer} {phase} {label}: "
                              f"{str(e)[:400]}", flush=True)
                        continue
                out = fn(*given[phase])
                out = (out,) if phase == "fwd" else out
                base.setdefault(phase, out)
                names = ("y",) if phase == "fwd" else ("dx", "dz", "dgain")
                gbps = hbm[phase] / (ms * 1e-3) / 1e9
                line = {"mixer": mixer, "shape": [bsz, s, w], "group": group,
                        "phase": phase, "candidate": label,
                        "ms": round(ms, 3), "hbm_gbps": round(gbps, 1),
                        "hbm_share_pct": round(100 * gbps / HBM_GBPS, 1),
                        "distance": {n: _distance(a, b) for n, a, b in zip(
                            names, out, base[phase])}}
                table.append(line)
                print("GATED_NORM " + json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gated_norm_bench.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
