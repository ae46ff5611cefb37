#!/usr/bin/env python3
r"""What stands behind the LFM2 cell, at PUBLISHED width on the chip, in two
parts.  Not a cell and not a measurement of speed; run when the
configuration, its adapter, its traffic or a limit is new:

    python3 benchmark/harness/lfm2_probe.py <cell> <seed>... \
        [--steps n] [--stand-in-seeds n]

THE COMPARISON.  For each seed ``check_against_reference`` against the true
reference (the most the system reads, beside each limit); on the first
``--stand-in-seeds`` seeds (default 2) against the reference's three
lower-precision stand-ins (``reference/lfm2_moe_ref.py``: fp8 e4m3 on what
enters the experts' matmuls, on the stream entering every routed block, on
the stream entering every mixer and MLP), each of which must read ``ok:
false``; on the first seed also the planted faults (the q/k norm over the
whole width, ``C`` and ``X`` exchanged in the convolution mixer, the
convolution reading one position ahead, and ``fault_probe.py``'s two of a
routed block), each ``ok: false``.

THE TRAJECTORY (``--steps n``, default 46; 0 leaves it out).  What the
routers do at the cell's own traffic, on the first seed: per step the loss,
the share of each routed block's picks that land on the held experts, the
fullest expert over the mean, and the largest selection bias — the counters
the jitted step returns, fetched every step.  The last line says whether
every block's held share stayed within 20-30 % (25 % is even at 8 of 32).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the band of ``held_pair_share_pct`` the cell's traffic promises
HELD_SHARE_BAND = (20.0, 30.0)


def _option(argv: list, name: str, default: int) -> tuple:
    if name not in argv:
        return default, argv
    at = argv.index(name)
    return int(argv[at + 1]), argv[:at] + argv[at + 2:]


def planted_configs(cfg: dict, reference) -> dict:
    """``{name: the configuration the REFERENCE computes}`` of every planted
    fault: the reference's own and the routed block's two."""
    from benchmark.harness.fault_probe import planted_faults

    faults = {name: dict(cfg, planted=name) for name in reference.FAULTS}
    faults.update({name: ref_cfg
                   for name, ref_cfg in planted_faults(cfg).items()
                   if name != "none"})
    return faults


def compare(cell: dict, seeds: list, stand_in_seeds: int) -> bool:
    import jax

    from benchmark.harness import common
    from benchmark.harness.model import build_job, check_against_reference

    cfg = cell["config_data"]
    reference = common.load_module("reference", cfg["reference"])
    job, mc = build_job(cell)
    found = True
    for n, seed in enumerate(seeds):
        params = job.create_state(jax.random.PRNGKey(seed))["params"]
        wanted = {"none": cfg}
        if n < stand_in_seeds:
            wanted.update({name: dict(cfg, planted=name)
                           for name in reference.STAND_INS})
        if n == 0:
            wanted.update(planted_configs(cfg, reference))
        for name, ref_cfg in wanted.items():
            out = check_against_reference(job, mc, cell, params, seed, ref_cfg)
            print(f"LFM2_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            found &= out["ok"] == (name == "none")
        del params
    print("LFM2_PROBE every stand-in and planted fault found, true "
          f"reference ok at every seed: {found}", flush=True)
    return found


def trajectory(cell: dict, seed: int, steps: int) -> bool:
    import jax
    import numpy as np

    from benchmark.harness.train_loop import TrainSession

    sess = TrainSession(cell, seed, 0.0)
    sess.open_device()
    sess.build()
    sess.create_state()
    sess.start_sampler()
    low, high = HELD_SHARE_BAND
    inside = True
    for step in range(steps):
        loss = sess.step(record=False)
        m = jax.device_get(sess.last_metrics)
        per_expert = np.asarray(m["moe_tokens_per_expert"], np.float64)
        share = [100.0 * h / row.sum() for h, row in zip(
            np.asarray(m["moe_held_pairs"]), per_expert)]
        inside &= all(low <= s <= high for s in share)
        print("LFM2_TRAJECTORY " + json.dumps({
            "step": step, "loss": round(loss, 4),
            "held_pair_share_pct": [round(s, 3) for s in share],
            "load_max_over_mean": [round(row.max() * row.size / row.sum(), 3)
                                   for row in per_expert],
            "router_bias_abs_max": float(m["moe_router_bias_abs_max"])}),
            flush=True)
    print(f"LFM2_TRAJECTORY seed={seed} steps={steps} every block's held "
          f"share within {low:g}-{high:g} % at every step: {inside}",
          flush=True)
    return inside


def main(argv) -> int:
    from benchmark.harness import common
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    steps, argv = _option(list(argv), "--steps", 46)
    stand_in_seeds, argv = _option(argv, "--stand-in-seeds", 2)
    cell = common.load_cell(argv[1])
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    ok = compare(cell, seeds, stand_in_seeds)
    if steps:
        ok &= trajectory(cell, seeds[0], steps)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
