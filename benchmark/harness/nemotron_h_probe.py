#!/usr/bin/env python3
r"""What stands behind the Nemotron-H cell, at PUBLISHED width on the chip,
in two parts.  Not a cell and not a measurement of speed; run when the
configuration, its adapter, its traffic or a limit is new:

    python3 benchmark/harness/nemotron_h_probe.py <cell> <seed>... \
        [--steps n] [--stand-in-seeds n] [--fault-seeds n] \
        [--trajectory-seeds n]

THE COMPARISON.  For each seed ``check_against_reference`` against the true
reference (the most the system reads, beside each limit); on the first
``--stand-in-seeds`` seeds (default 2) against the reference's
lower-precision stand-in (``reference/nemotron_h_ref.py``: fp8 e4m3 on the
normed stream entering every branch), which must read ``ok: false``; on the
first ``--fault-seeds`` seeds (default 1) the planted faults (the gated norm
over one group, a SwiGLU expert, ``silu`` for relu2, an applied rotary
embedding, the routed scaling left out, and ``fault_probe.py``'s two of a
routed block), each ``ok: false``.  Every line says what it read; the last
line names what was NOT found.

THE TRAJECTORY (``--steps n``, default 24; 0 leaves it out).  What the
routers and the scan do at the cell's own traffic, on the first
``--trajectory-seeds`` seeds (default 1): per
step the loss, the share of each routed layer's picks that land on the held
experts, the fullest expert over the mean, the selection bias's largest
magnitude and the scan's two counters (``ssm_state_rms`` per Mamba-2 layer,
``ssm_decay_min``) — what the jitted step returns, fetched every step.  The
last line says whether every layer's held share stayed within 5-7.5 % (6.25
% is even at 8 of 128).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the band of ``held_pair_share_pct`` the cell's traffic promises
HELD_SHARE_BAND = (5.0, 7.5)


# the options' parser and ``{name: the configuration the REFERENCE computes}``
# of every planted fault (the reference's own and the routed block's two)
# are the LFM2 probe's: both configurations are routed hybrids
from benchmark.harness.lfm2_probe import _option, planted_configs  # noqa: E402


def compare(cell: dict, seeds: list, stand_in_seeds: int,
            fault_seeds: int) -> bool:
    import jax

    from benchmark.harness import common
    from benchmark.harness.model import build_job, check_against_reference

    cfg = cell["config_data"]
    reference = common.load_module("reference", cfg["reference"])
    job, mc = build_job(cell)
    true_ok, missed = True, []
    for n, seed in enumerate(seeds):
        params = job.create_state(jax.random.PRNGKey(seed))["params"]
        wanted = {"none": cfg}
        if n < stand_in_seeds:
            wanted.update({name: dict(cfg, planted=name)
                           for name in reference.STAND_INS})
        if n < fault_seeds:
            wanted.update(planted_configs(cfg, reference))
        for name, ref_cfg in wanted.items():
            out = check_against_reference(job, mc, cell, params, seed, ref_cfg)
            print(f"NEMOTRON_H_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            if name == "none":
                true_ok &= out["ok"]
            elif out["ok"]:
                missed.append(f"{name}@{seed}")
        del params
    print(f"NEMOTRON_H_PROBE true reference ok at every seed: {true_ok}; "
          f"stand-ins and planted faults NOT found: {missed or 'none'}",
          flush=True)
    return true_ok and not missed


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
        print("NEMOTRON_H_TRAJECTORY " + json.dumps({
            "step": step, "loss": round(loss, 4),
            "held_pair_share_pct": [round(s, 3) for s in share],
            "load_max_over_mean": [round(row.max() * row.size / row.sum(), 3)
                                   for row in per_expert],
            "router_bias_abs_max": float(m["moe_router_bias_abs_max"]),
            "ssm_state_rms": [round(float(v), 5) for v in
                              np.asarray(m["ssm_state_rms"])],
            "ssm_decay_min": float(m["ssm_decay_min"])}), flush=True)
    print(f"NEMOTRON_H_TRAJECTORY seed={seed} steps={steps} every layer's "
          f"held share within {low:g}-{high:g} % at every step: {inside}",
          flush=True)
    return inside


def main(argv) -> int:
    from benchmark.harness import common
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    steps, argv = _option(list(argv), "--steps", 24)
    stand_in_seeds, argv = _option(argv, "--stand-in-seeds", 2)
    fault_seeds, argv = _option(argv, "--fault-seeds", 1)
    trajectory_seeds, argv = _option(argv, "--trajectory-seeds", 1)
    cell = common.load_cell(argv[1])
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    ok = compare(cell, seeds, stand_in_seeds, fault_seeds)
    for seed in seeds[:trajectory_seeds] if steps else ():
        ok &= trajectory(cell, seed, steps)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
