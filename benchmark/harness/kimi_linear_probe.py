#!/usr/bin/env python3
r"""What stands behind the Kimi Linear cell, at PUBLISHED width on the chip,
in two parts.  Not a cell and not a measurement of speed; run when the
configuration, its adapter, its traffic or a limit is new:

    python3 benchmark/harness/kimi_linear_probe.py <cell> <seed>... \
        [--steps n] [--stand-in-seeds n] [--fault-seeds n]

THE COMPARISON.  For each seed ``check_against_reference`` against the true
reference (the most the system reads, beside each limit); on the first
``--stand-in-seeds`` seeds (default 2) against the reference's
lower-precision stand-ins (``reference/kimi_linear_ref.py``: fp8 e4m3 on the
stream entering every mixer and on that entering every router, and the
decay's running sum and the rule's state in bfloat16), each ``ok: false``; on
the first ``--fault-seeds`` seeds (default 1) the planted faults (the decay a
head and not a channel, ``k_pe`` rotated, ``v`` padded and not sliced, the
output gate as ``silu``, its bias dropped, beta left out of the write, and
two of the routed block: the top-k weights normalised the other way, one
pick fewer), each ``ok: false``.  Every line says what it read; the last line
names what was NOT found.

THE TRAJECTORY (``--steps n``, default 24; 0 leaves it out).  What the
routers and the rule do at the cell's own traffic, on the first seed: per
step the loss, the share of each routed block's picks that land on the held
experts, the fullest expert over the mean, and the rule's two counters
(``kda_state_rms`` per KDA layer, ``kda_decay_min``) — what the jitted step
returns, fetched every step.  The last line says whether every block's held
share stayed within 1.5-5 % (3.125 % is even at 8 of 256; at initialisation
the four routers read 1.8 to 4.3 %).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the band of ``held_pair_share_pct`` the cell's traffic promises
HELD_SHARE_BAND = (1.5, 5.0)

from benchmark.harness.lfm2_probe import _option  # noqa: E402


def planted_configs(cfg: dict, reference) -> dict:
    """``{name: the configuration the REFERENCE computes}`` of every planted
    fault: the reference's own and two of the routed block (under this
    configuration's keys, which ``fault_probe.py`` does not read)."""
    faults = {name: dict(cfg, planted=name) for name in reference.FAULTS}
    faults["moe_renormalize flipped"] = dict(
        cfg, moe_renormalize=not cfg["moe_renormalize"])
    faults["num_experts_per_token minus one"] = dict(
        cfg, num_experts_per_token=cfg["num_experts_per_token"] - 1)
    return faults


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
            print(f"KIMI_LINEAR_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            if name == "none":
                true_ok &= out["ok"]
            elif out["ok"]:
                missed.append(f"{name}@{seed}")
        del params
    print(f"KIMI_LINEAR_PROBE true reference ok at every seed: {true_ok}; "
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
        print("KIMI_LINEAR_TRAJECTORY " + json.dumps({
            "step": step, "loss": round(loss, 4),
            "held_pair_share_pct": [round(s, 3) for s in share],
            "load_max_over_mean": [round(row.max() * row.size / row.sum(), 3)
                                   for row in per_expert],
            "kda_state_rms": [round(float(v), 5) for v in
                              np.asarray(m["kda_state_rms"])],
            "kda_decay_min": float(m["kda_decay_min"])}), flush=True)
    print(f"KIMI_LINEAR_TRAJECTORY seed={seed} steps={steps} every block's "
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
    cell = common.load_cell(argv[1])
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    ok = compare(cell, seeds, stand_in_seeds, fault_seeds)
    if steps:
        ok &= trajectory(cell, seeds[0], steps)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
