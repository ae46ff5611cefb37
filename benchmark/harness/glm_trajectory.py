#!/usr/bin/env python3
"""What the router of a cell with a SHARE of the experts does over the first
steps of training: per step the loss, the share of the router's picks that
land on the held experts, the fullest expert over the mean, and the largest
selection bias — the counters the jitted step returns, fetched every step —
and, where the block chooses its sorted buffer's size at run time, the rows
each block's buffer took.  It replays the traffic of the cell it is given
(its workload file's ``traffic``: the learning rate, the sequences), so the
same call shows ``train-steady``'s collapse within ten steps on a workload
file that names it and ``train-decayed``'s routing holding for a window
(``glm4_7_flash-l5.train-decayed``, ``lfm2_8b_a1b-l5.train-decayed``;
PERF.md section 5).  Not a cell and not a measurement of speed:

    python3 benchmark/harness/glm_trajectory.py <cell> <seed> [steps]
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
    import numpy as np

    from benchmark.harness import common
    from benchmark.harness.train_loop import TrainSession
    from dlrover_tpu.common.jax_env import device_summary

    cell = common.load_cell(argv[1])
    seed = int(argv[2]) if len(argv) > 2 else 0
    steps = int(argv[3]) if len(argv) > 3 else 30
    sess = TrainSession(cell, seed, 0.0)
    sess.open_device()
    common.check_device(device_summary(), cell["chips"], rehearse=False)
    sess.build()
    sess.create_state()
    sess.start_sampler()
    for step in range(steps):
        loss = sess.step(record=False)
        m = jax.device_get(sess.last_metrics)
        per_expert = np.asarray(m["moe_tokens_per_expert"], np.float64)
        print("GLM_TRAJECTORY " + json.dumps({
            "step": step, "loss": round(loss, 4),
            "held_pair_share_pct": [round(100.0 * h / row.sum(), 3)
                                    for h, row in zip(
                                        np.asarray(m["moe_held_pairs"]),
                                        per_expert)],
            "load_max_over_mean": [round(row.max() * row.size / row.sum(), 2)
                                   for row in per_expert],
            "buffer_rows": np.asarray(
                m.get("moe_buffer_rows", [])).tolist(),
            "router_bias_abs_max": float(m["moe_router_bias_abs_max"]),
            "main_ce": round(float(m["main_ce"]), 4),
            "mtp_ce": round(float(m["mtp_ce"]), 4),
            "moe_seq_aux": round(float(m["moe_seq_aux"]), 4)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
