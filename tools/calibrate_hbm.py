"""Calibrate the static HBM estimator against XLA's compiled truth.

For each (model, strategy) point this AOT-compiles the real train step
on the 8-device virtual CPU mesh (``accelerate.aot_analyze`` — no state
is materialized, so models far bigger than host RAM are fine) and
compares ``strategy_search.estimate_step_hbm_bytes`` with the peak
bytes XLA's buffer assignment reports (``compiled.memory_analysis()``).

This keeps the BO search's memory pruning honest before it faces real
HBM (VERDICT r3 next #8; the dryrun-scoring role of the reference's
``atorch/auto/engine/sg_algo/bayes_opt_sg.py``).  The resulting
calibration table lives in CALIBRATE_HBM.json; ``tests/test_strategy_search.py``
asserts the error bound on a fast subset.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/calibrate_hbm.py [--fast]
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time


def points(fast: bool = False):
    """(label, cfg, batch, seq, strategy) calibration matrix."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy
    from dlrover_tpu.parallel.mesh import MeshSpec

    m300 = llama.LlamaConfig.small_300m()
    m300h = dataclasses.replace(m300, n_head=8, n_kv_head=8)
    m800 = llama.LlamaConfig.medium_800m()
    pts = [
        # llama_300m family: the bench sweep's shapes.
        ("300m dp8 none", m300, 8, 2048, Strategy(mesh=MeshSpec(dp=8))),
        ("300m dp8 block", dataclasses.replace(m300, remat_block=True),
         8, 2048, Strategy(mesh=MeshSpec(dp=8))),
        ("300m dp8 dots", m300, 8, 2048,
         Strategy(mesh=MeshSpec(dp=8), remat="dots")),
        ("300m dp8 full", m300, 8, 2048,
         Strategy(mesh=MeshSpec(dp=8), remat="full")),
        ("300m dp8 accum4", m300, 8, 2048,
         Strategy(mesh=MeshSpec(dp=8), grad_accum=4)),
        ("300m dp2xfsdp4 none", m300, 8, 2048,
         Strategy(mesh=MeshSpec(dp=2, fsdp=4))),
        ("300m fsdp8 block",
         dataclasses.replace(m300, remat_block=True), 8, 2048,
         Strategy(mesh=MeshSpec(fsdp=8))),
        ("300m_h128 dp8 none", m300h, 8, 2048,
         Strategy(mesh=MeshSpec(dp=8))),
        ("300m b16 dp8 block",
         dataclasses.replace(m300, remat_block=True), 16, 2048,
         Strategy(mesh=MeshSpec(dp=8))),
    ]
    if not fast:
        m800b = dataclasses.replace(m800, remat_block=True)
        pts += [
            ("800m dp8 block", m800b, 8, 2048,
             Strategy(mesh=MeshSpec(dp=8))),
            ("800m fsdp8 block", m800b, 8, 2048,
             Strategy(mesh=MeshSpec(fsdp=8))),
            ("800m fsdp8 b16 block", m800b, 16, 2048,
             Strategy(mesh=MeshSpec(fsdp=8))),
            ("800m dp2xfsdp2xtp2 block", m800b, 8, 2048,
             Strategy(mesh=MeshSpec(dp=2, fsdp=2, tp=2))),
            ("800m fsdp8 b16 accum4", m800b, 16, 2048,
             Strategy(mesh=MeshSpec(fsdp=8), grad_accum=4)),
        ]
    return pts


def measure_point(label, cfg, batch, seq, strategy):
    """Returns (predicted_bytes, actual_peak_bytes, compile_s)."""
    import numpy as np

    import jax
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import aot_analyze
    from dlrover_tpu.parallel.strategy_search import (
        estimate_step_hbm_bytes,
    )

    sample = {
        "tokens": np.zeros((batch, seq + 1), np.int32)
    }
    t0 = time.perf_counter()
    job = aot_analyze(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch=sample,
        strategy=strategy,
        devices=jax.devices()[:8],
    )
    dt = time.perf_counter() - t0
    if job.memory is None:
        raise RuntimeError(f"{label}: no memory_analysis on this backend")
    params_shape = jax.eval_shape(
        lambda r: llama.init_params(r, cfg), jax.random.PRNGKey(0)
    )
    # The estimator sees the same inputs the pruner gives it; the
    # model-level remat flag travels as strategy.remat="block" there.
    est_strategy = job.strategy
    if cfg.remat_block:
        est_strategy = dataclasses.replace(est_strategy, remat="block")
    predicted = estimate_step_hbm_bytes(
        params_shape, sample, est_strategy
    )
    return predicted, float(job.memory["peak_bytes"]), dt


def main() -> int:
    fast = "--fast" in sys.argv
    rows = []
    for label, cfg, batch, seq, strategy in points(fast):
        try:
            pred, actual, dt = measure_point(
                label, cfg, batch, seq, strategy
            )
        except Exception as e:  # noqa: BLE001
            print(f"{label:34s}  FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        ratio = pred / actual if actual else float("inf")
        rows.append({
            "point": label,
            "predicted_gb": round(pred / 2**30, 3),
            "actual_gb": round(actual / 2**30, 3),
            "ratio": round(ratio, 3),
            "compile_s": round(dt, 1),
        })
        print(
            f"{label:34s}  pred {pred / 2**30:7.3f} GB   "
            f"actual {actual / 2**30:7.3f} GB   ratio {ratio:6.3f}   "
            f"({dt:.0f}s)",
            file=sys.stderr,
        )
        # Flush partials as points complete (a wedged run still leaves
        # data).
        import os as _os

        _out = _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            "CALIBRATE_HBM.json",
        )
        with open(_out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
    if not rows:
        print(json.dumps({"error": "no points measured"}))
        return 1
    ratios = [r["ratio"] for r in rows]
    import numpy as np

    summary = {
        "n_points": len(rows),
        "ratio_geomean": round(float(np.exp(np.mean(np.log(ratios)))), 3),
        "ratio_min": min(ratios),
        "ratio_max": max(ratios),
        "max_abs_rel_err": round(
            max(abs(r - 1.0) for r in ratios), 3
        ),
        "rows": rows,
    }
    import os

    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "CALIBRATE_HBM.json",
    )
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    import os

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    sys.exit(main())
