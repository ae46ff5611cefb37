#!/usr/bin/env python3
r"""What stands behind the Phi-4-mini-flash cell, at PUBLISHED width on the
chip.  Not a cell and not a measurement of speed; run when the configuration,
its adapter, its traffic or a limit is new:

    python3 benchmark/harness/phi4flash_probe.py <cell> <seed>... \
        [--stand-in-seeds n] [--fault-seeds n] [--only name,name]

For each seed ``check_against_reference`` against the true reference (the
most the system reads, beside each limit); on the first ``--stand-in-seeds``
seeds (default 2) against the reference's lower-precision stand-in
(``reference/phi4flash_ref.py``: the scan's state and each step's decay in
bfloat16), ``ok: false``; on the first ``--fault-seeds`` seeds (default 1)
the planted faults, each ALONE and each ``ok: false``: the memory taken after
the gate; the memory without the ``D`` skip; a cross layer on its own keys and
values; ``lambda_init`` by the cut's index; ``subln`` dropped; ``1 -
lambda_init`` dropped; the window one key short; RMS in place of LayerNorm; the
attention biases dropped; ``B`` and ``C`` swapped; ``dt_bias`` dropped; the
GMU's ``silu`` a sigmoid.  The faults are read on a state whose biases HAVE
MOVED (:func:`with_moved_biases`: every bias of the attention projections and
of the LayerNorms N(0, 0.1) from the seed): at initialisation they are zero
and dropping one changes nothing; the true reference is read there too
(``none@moved``).  ``--only`` keeps the named stand-ins and faults (a
second reading of some).  Every line says what it read; the last line names
what was NOT found.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the standard deviation :func:`with_moved_biases` draws every bias at
MOVED_BIAS = 0.1

from benchmark.harness.lfm2_probe import _option  # noqa: E402


def with_moved_biases(params, seed: int, std: float = MOVED_BIAS):
    """``params`` with every bias of the attention projections (``bq``,
    ``bk``, ``bv``, ``bo``) and of the LayerNorms drawn N(0, ``std``) from
    ``seed`` and the leaf's path: a state training reaches, on which a bias
    that is dropped shows."""
    import zlib

    import jax

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if not any(name.endswith(f"['{b}']")
                   for b in ("bias", "bq", "bk", "bv", "bo")):
            return leaf
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(name.encode()) % (2 ** 31))
        return jax.device_put(
            std * jax.random.normal(key, leaf.shape, leaf.dtype),
            leaf.sharding)

    return jax.tree_util.tree_map_with_path(moved, params)


def compare(cell: dict, seeds: list, stand_in_seeds: int,
            fault_seeds: int, only: tuple = ()) -> bool:
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
        picked = lambda names: [  # noqa: E731
            name for name in names if not only or name in only]
        if n < stand_in_seeds:
            wanted.update({name: (dict(cfg, planted=name), params)
                           for name in picked(reference.STAND_INS)})
        if n < fault_seeds:
            moved = with_moved_biases(params, seed)
            wanted["none@moved"] = (cfg, moved)
            wanted.update({name: (dict(cfg, planted=name), moved)
                           for name in picked(reference.FAULTS)})
        for name, (ref_cfg, state) in wanted.items():
            out = check_against_reference(job, mc, cell, state, seed, ref_cfg)
            print(f"PHI4FLASH_PROBE seed={seed} {name}: {json.dumps(out)}",
                  flush=True)
            if name.startswith("none"):
                true_ok &= out["ok"]
            elif out["ok"]:
                missed.append(f"{name}@{seed}")
        del params, wanted
    print(f"PHI4FLASH_PROBE true reference ok at every seed: {true_ok}; "
          f"stand-ins and planted faults NOT found: {missed or 'none'}",
          flush=True)
    return true_ok and not missed


def main(argv) -> int:
    from benchmark.harness import common
    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )

    stand_in_seeds, argv = _option(list(argv), "--stand-in-seeds", 2)
    fault_seeds, argv = _option(argv, "--fault-seeds", 1)
    only = ()
    if "--only" in argv:
        at = argv.index("--only")
        only, argv = tuple(argv[at + 1].split(",")), argv[:at] + argv[at + 2:]
    cell = common.load_cell(argv[1])
    seeds = [int(s) for s in argv[2:]] or [0]
    enable_compilation_cache()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    return 0 if compare(cell, seeds, stand_in_seeds, fault_seeds,
                        only) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
