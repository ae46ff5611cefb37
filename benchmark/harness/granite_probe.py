#!/usr/bin/env python3
"""The two readings behind each limit of the Granite hybrid's comparison, at
PUBLISHED width on the chip.  For each seed ``check_against_reference``
against the true reference (the most the system reads); on the first
``--stand-in-seeds`` seeds (default 2) against the reference's two
lower-precision stand-ins (``reference/granite_hybrid_ref.py``:
``fp8_stream``, the stream entering every layer rounded to fp8 e4m3, and
``bf16_scan``, the scan's cumulative sums and decays in bfloat16), each of
which must read ``ok: false`` by at least one of the standing limits; on the
first seed also the planted faults (the convolution shifted by one position,
``D`` dropped, the gate after the norm, rotary left on, and
``residual_multiplier`` 1), each ``ok: false``.  Not a cell and not a
measurement of speed; run when the configuration, its adapter or a limit is
new:

    python3 benchmark/harness/granite_probe.py <cell> <seed>... [--stand-in-seeds n]
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def planted_configs(cfg: dict, reference) -> dict:
    """``{name: the configuration the REFERENCE computes}`` of every
    planted fault."""
    faults = {name: dict(cfg, planted=name) for name in reference.FAULTS}
    faults["residual_multiplier 1"] = dict(cfg, residual_multiplier=1.0)
    return faults


def main(argv) -> int:
    import jax

    from benchmark.harness import common
    from benchmark.harness.model import build_job, check_against_reference
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    stand_in_seeds = 2
    if "--stand-in-seeds" in argv:
        at = argv.index("--stand-in-seeds")
        stand_in_seeds, argv = int(argv[at + 1]), argv[:at] + argv[at + 2:]
    cell = common.load_cell(argv[1])
    cfg = cell["config_data"]
    reference = common.load_module("reference", cfg["reference"])
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
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
            print(f"GRANITE_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            found &= out["ok"] == (name == "none")
    print("GRANITE_PROBE every stand-in and planted fault found, true "
          f"reference ok at every seed: {found}")
    return 0 if found else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
