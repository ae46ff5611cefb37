#!/usr/bin/env python3
"""Does the comparison that decides ``correct`` find a fault at PUBLISHED
width?  Runs a cell's ``check_against_reference`` on the chip against the
true reference and against references with a fault planted, and prints each
distance beside its tolerance.  The faults follow from what the
configuration has, not from its name: with a sliding window, the window
dropped and halved; with ``num_experts_per_tok``, the top-k weights
normalised the other way and one expert fewer.  A planted fault must read
``ok: false``.  Not a cell and not a measurement of speed; run once when a
configuration, an adapter or a tolerance is new:

    python3 benchmark/harness/fault_probe.py <cell> [seed]

The toy-width version of the same proof is benchmark/tests/test_reference.py.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def planted_faults(cfg: dict) -> dict:
    """``{fault: the configuration the REFERENCE computes}``; ``none`` is
    the true one."""
    faults = {"none": cfg}
    window = cfg.get("sliding_window") or 0
    if window:
        faults["window dropped"] = dict(cfg, sliding_window=0)
        faults["window halved"] = dict(cfg, sliding_window=window // 2)
    if cfg.get("num_experts_per_tok"):
        faults["norm_topk_prob flipped"] = dict(
            cfg, norm_topk_prob=not cfg.get("norm_topk_prob", False))
        faults["num_experts_per_tok minus one"] = dict(
            cfg, num_experts_per_tok=cfg["num_experts_per_tok"] - 1)
    return faults


def main(argv) -> int:
    import jax

    from benchmark.harness import common
    from benchmark.harness.model import build_job, check_against_reference
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    cell = common.load_cell(argv[1])
    seed = int(argv[2]) if len(argv) > 2 else 0
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    job, mc = build_job(cell)
    params = job.create_state(jax.random.PRNGKey(seed))["params"]
    found = True
    for name, ref_cfg in planted_faults(cell["config_data"]).items():
        out = check_against_reference(job, mc, cell, params, seed, ref_cfg)
        print(f"FAULT_PROBE {name}: {json.dumps(out)}", flush=True)
        found &= out["ok"] == (name == "none")
    print(f"FAULT_PROBE every planted fault found, true reference ok: {found}")
    return 0 if found else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
