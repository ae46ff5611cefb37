#!/usr/bin/env python3
r"""What stands behind the Trinity-Mini cell, at PUBLISHED width on the chip,
in two parts.  Not a cell and not a measurement of speed; run when the
configuration, its adapter, its traffic or a limit is new:

    python3 benchmark/harness/afmoe_probe.py <cell> <seed>... \
        [--steps n] [--stand-in-seeds n] [--fault-seeds n]

THE COMPARISON.  For each seed ``check_against_reference`` against the true
reference (the most the system reads, beside each limit); on the first
``--stand-in-seeds`` seeds (default 2) against the reference's
lower-precision stand-ins (``reference/afmoe_ref.py``: fp8 e4m3 on the
stream entering every attention block and MLP, on that entering every router
alone, and bfloat16 where the file states float32), each ``ok: false``; on
the first ``--fault-seeds`` seeds (default 1) the planted faults, each ALONE
and each ``ok: false``: a full layer rotated, the window layers unrotated, the
window one key short, the gate dropped, the gate one scalar a head, either
output norm dropped, the selection bias added to the weights, ``route_scale``
dropped, the embedding's multiplier dropped.  The faults are read on a state
whose selection biases HAVE MOVED (:func:`with_moved_biases`: +-0.3 by seeded
signs, 300 steps of the rule one way): at initialisation the bias is zero and
adding it to a weight changes nothing; the true reference is read there too
(``none@moved``).  Every line says what it read; the last line names what was
NOT found.

THE TRAJECTORY (``--steps n``, default 24; 0 leaves it out).  What the
routers do at the cell's own traffic on the first seed: per step the loss,
the share of each routed layer's picks that land on the held experts, the
fullest expert over the mean and the largest selection bias — what the jitted
step returns, fetched every step.  The last line says whether every layer's
held share stayed within 10-15 % (12.5 % is even at 16 of 128).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the band of ``held_pair_share_pct`` the cell's traffic promises
HELD_SHARE_BAND = (10.0, 15.0)
#: how far :func:`with_moved_biases` moves every selection bias
MOVED_BIAS = 0.3

from benchmark.harness.lfm2_probe import _option  # noqa: E402


def planted_configs(cfg: dict, reference) -> dict:
    """``{name: the configuration the REFERENCE computes}`` of every planted
    fault."""
    return {name: dict(cfg, planted=name) for name in reference.FAULTS}


def with_moved_biases(params, seed: int, by: float = MOVED_BIAS):
    """``params`` with every routed layer's selection bias at ``+- by``, the
    signs drawn from ``seed`` and the layer: a state the rule reaches, on
    which a bias that leaks into a weight shows."""
    import jax
    import jax.numpy as jnp

    layers = []
    for i, layer in enumerate(params["layers"]):
        if "moe" in layer:
            bias = layer["moe"]["router_bias"]
            signs = jax.random.rademacher(
                jax.random.fold_in(jax.random.PRNGKey(seed), i), bias.shape,
                jnp.float32)
            layer = dict(layer, moe=dict(
                layer["moe"], router_bias=jax.device_put(
                    by * signs, bias.sharding)))
        layers.append(layer)
    return dict(params, layers=layers)


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
        wanted = {"none": (cfg, params)}
        if n < stand_in_seeds:
            wanted.update({name: (dict(cfg, planted=name), params)
                           for name in reference.STAND_INS})
        if n < fault_seeds:
            moved = with_moved_biases(params, seed)
            wanted["none@moved"] = (cfg, moved)
            wanted.update({name: (ref_cfg, moved) for name, ref_cfg in
                           planted_configs(cfg, reference).items()})
        for name, (ref_cfg, state) in wanted.items():
            out = check_against_reference(job, mc, cell, state, seed, ref_cfg)
            print(f"AFMOE_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            if name.startswith("none"):
                true_ok &= out["ok"]
            elif out["ok"]:
                missed.append(f"{name}@{seed}")
        del params, wanted
    print(f"AFMOE_PROBE true reference ok at every seed: {true_ok}; "
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
        print("AFMOE_TRAJECTORY " + json.dumps({
            "step": step, "loss": round(loss, 4),
            "held_pair_share_pct": [round(s, 3) for s in share],
            "load_max_over_mean": [round(row.max() * row.size / row.sum(), 3)
                                   for row in per_expert],
            "router_bias_abs_max": round(
                float(m["moe_router_bias_abs_max"]), 5)}), flush=True)
    print(f"AFMOE_TRAJECTORY seed={seed} steps={steps} every layer's held "
          f"share within {low:g}-{high:g} % at every step: {inside}",
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
