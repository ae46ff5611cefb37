#!/usr/bin/env python3
"""The two readings behind the limits ``adapters/glm4_moe_lite.py`` brings,
at PUBLISHED width on the chip.  For each seed ``check_against_reference``
against the true reference (the most the system reads), and against the
reference with the stream entering every router rounded to fp8 e4m3 (the
nearest precision below the stated one, planted through the ``planted`` key
``reference/glm4_moe_lite_ref.py`` reads), which must read ``ok: false`` by
a limit of the choices; on the first seed also the planted faults
``fault_probe.py`` derives from the configuration's keys (the top-k weights
normalised the other way, one expert fewer), each ``ok: false``.  Not a
cell and not a measurement of speed; run when the configuration, its
adapter or a limit is new:

    python3 benchmark/harness/glm_probe.py <cell> <seed>...
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv) -> int:
    import jax

    from benchmark.harness import common
    from benchmark.harness.fault_probe import planted_faults
    from benchmark.harness.model import build_job, check_against_reference
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

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
        faults = {"none": cfg, reference.FP8_ROUTER_STREAM: dict(
            cfg, planted=reference.FP8_ROUTER_STREAM)}
        if n == 0:
            faults.update(planted_faults(cfg))
        for name, ref_cfg in faults.items():
            out = check_against_reference(job, mc, cell, params, seed, ref_cfg)
            print(f"GLM_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            found &= out["ok"] == (name == "none")
    print("GLM_PROBE every planted fault found, true reference ok at every "
          f"seed: {found}")
    return 0 if found else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
