#!/usr/bin/env python3
"""How deep does the comparison that decides ``correct`` see into a looped
model, and does it find a fault at PUBLISHED width?  ``fault_probe.py``
plants its faults from the keys of a configuration; a looped model's (one
pass fewer, the branch-output norms dropped, the exit distribution without
its remainder, the stream in fp8) are planted through the ``planted`` key
its reference reads (``benchmark/reference/ouro_ref.py``).  On the chip,
for each seed: ``check_against_reference`` against the true reference, and
the relative L2 of EACH pass's normed stream (the harness compares their
stack: 32 successive blocks deep at the last pass, where its tolerance was
calibrated for 8).  Then, on the first seed, each planted fault (or those
named after ``--planted``, comma-separated), which must read ``ok: false``.
Not a cell and not a measurement of speed; run when the configuration, its
adapter or a limit is new:

    python3 benchmark/harness/ouro_probe.py <cell> <seed>... [--planted a,b]
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def per_pass_distances(cell: dict, mc):
    """``(params, tokens) -> {"z_rel_l2": [T]}``: system against
    reference, forward only, one number a pass."""
    import jax.numpy as jnp

    from benchmark.harness import common

    cfg = cell["config_data"]
    adapter = common.adapter_of(cfg)
    reference = common.load_module("reference", cfg["reference"])
    passes = cfg["total_ut_steps"]

    def fn(params, tokens):
        hidden = adapter.hidden_and_loss(params, tokens, mc)[0]
        hidden_r = reference.hidden_and_loss(params, tokens, cfg)[0]
        split = lambda h: h.reshape(passes, -1)  # noqa: E731
        diff = jnp.linalg.norm(split(hidden) - split(hidden_r), axis=1)
        return {"z_rel_l2": diff / jnp.linalg.norm(split(hidden_r), axis=1)}

    return fn


def main(argv) -> int:
    import jax

    from benchmark.harness import common
    from benchmark.harness.model import (
        build_job,
        check_against_reference,
        comparison_sequences,
        sample_tokens,
    )
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    cell = common.load_cell(argv[1])
    cfg = cell["config_data"]
    reference = common.load_module("reference", cfg["reference"])
    planted = reference.PLANTED
    if "--planted" in argv:
        at = argv.index("--planted")
        planted, argv = tuple(argv[at + 1].split(",")), argv[:at]
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    job, mc = build_job(cell)
    per_pass = jax.jit(per_pass_distances(cell, mc))
    found = True
    for n, seed in enumerate(seeds):
        params = job.create_state(jax.random.PRNGKey(seed))["params"]
        out = check_against_reference(job, mc, cell, params, seed)
        print(f"OURO_PROBE seed={seed} none: {json.dumps(out)}", flush=True)
        found &= out["ok"]
        toks = sample_tokens(
            seed + 7919, range(10**6, 10**6 + comparison_sequences(cell)),
            cell["traffic_data"]["seq_len"], cfg["vocab_size"])
        with jax.set_mesh(job.mesh):
            z = per_pass(params, jax.make_array_from_process_local_data(
                job.batch_sharding["tokens"], toks))
        per_pass_read = {k: v.tolist() for k, v in jax.device_get(z).items()}
        print(f"OURO_PROBE seed={seed} per pass: "
              f"{json.dumps(per_pass_read)}", flush=True)
        if n:
            continue
        for name in planted:
            out = check_against_reference(
                job, mc, cell, params, seed, dict(cfg, planted=name))
            print(f"OURO_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            found &= not out["ok"]
    print("OURO_PROBE every planted fault found, true reference ok at "
          f"every seed: {found}")
    return 0 if found else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
