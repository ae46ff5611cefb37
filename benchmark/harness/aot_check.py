#!/usr/bin/env python3
"""Compile a cell's real step for a DESCRIBED ``v5e:2x2`` — no chip attached
(``on-chip-measurement`` guide, section 2, rehearsal 3) — and print XLA's
memory analysis and what the program contains, then the same for the
programs of the comparison with the reference (system forward + backward;
reference forward + ``jax.grad``; for a routed block the reference's forward
choosing for itself), which must fit BESIDE the training state.
Run it here, on the CPU,
before a cell's first chip run: what the TPU compiler refuses (a Mosaic
block shape, a program that does not fit HBM) costs no chip time this way.

    JAX_PLATFORMS=cpu python3 benchmark/harness/aot_check.py <cell> [batch] [remat 0|1]

A compile that passes is not a chip run: nothing here is a time or a rate.
"""

from __future__ import annotations

import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reported it (PR 21)
V5E_BYTES_LIMIT = 16_909_336_064


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from benchmark.harness import common
    from benchmark.harness.model import (
        build_job,
        comparison_programs,
        comparison_sequences,
    )

    cell = common.load_cell(argv[1])
    if len(argv) > 2:
        cell["batch_sequences"] = int(argv[2])
    if len(argv) > 3:
        cell["remat_block"] = bool(int(argv[3]))
    # a described device cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    # the kernel dispatchers ask jax.default_backend() and would see the CPU
    jax.default_backend = lambda: "tpu"
    job, mc = build_job(cell, devices=list(topo.devices)[:cell["chips"]])
    mem = job.memory
    # arguments are donated: what is live at the peak is arguments + temps
    live = mem["argument_bytes"] + mem["temp_bytes"]
    print(json.dumps({
        "cell": cell["name"], "batch_sequences": cell["batch_sequences"],
        "remat_block": cell["remat_block"], "memory": mem,
        "arguments_plus_temps": live,
        "free_share_of_bytes_limit": 1.0 - live / V5E_BYTES_LIMIT,
        "program": job.program,
    }))
    # The comparison runs beside the training state: its programs take the
    # parameters as arguments, and the optimizer state stays resident.
    state = jax.eval_shape(job.create_state, jax.random.PRNGKey(0))

    def abstract(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    opt = abstract(state["opt_state"], job.state_sharding["opt_state"])
    opt_bytes = sum(
        math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(opt))
    params = abstract(state["params"], job.state_sharding["params"])
    toks = jax.ShapeDtypeStruct(
        (comparison_sequences(cell), cell["traffic_data"]["seq_len"] + 1),
        "int32",
        sharding=job.batch_sharding["tokens"])
    system, against_reference, independent = comparison_programs(cell, mc)
    with jax.set_mesh(job.mesh):
        compiled = {"system": jax.jit(system).lower(params, toks).compile()}
        loss, hidden, grads, extra = jax.eval_shape(system, params, toks)
        compiled["against_reference"] = jax.jit(against_reference).lower(
            params, toks, loss, hidden, grads, extra).compile()
        if extra is not None:
            compiled["independent"] = jax.jit(independent).lower(
                params, toks, loss, hidden, extra).compile()
    for name, c in compiled.items():
        m = c.memory_analysis()
        live = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes + opt_bytes)
        print(json.dumps({
            "comparison_program": name,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "optimizer_state_bytes": opt_bytes,
            "live_beside_the_state": live,
            "free_share_of_bytes_limit": 1.0 - live / V5E_BYTES_LIMIT}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
