#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``benchmark/workloads/<cell>.json``; it names a configuration
(``configs/``) and a traffic mix (``traffic/``); the traffic's ``kind``
names the runner (``runners/<kind>.py``); the metrics a cell reports are
the entries of ``BENCHMARK.json`` that apply to it, and each per-layer
metric is read by ``layer_metrics/<name>.py``.  Adding a cell, a
configuration, a traffic mix or a per-layer metric adds files and entries
and edits none.

What decides ``correct`` of a steady cell belongs to the configuration too
(``harness/model.py::check_against_reference``).  Its adapter's
``hidden_and_loss(params, tokens, mc)`` returns ``(hidden, loss)``, and the
system is held to its reference's ``hidden_and_loss(params, tokens, cfg)``
in hidden states, loss and the gradients of the adapter's ``grad_leaves``,
at the three standing tolerances of ``harness/model.py``.  A block that
makes DISCRETE CHOICES (a routed one: which experts) returns ``(hidden,
loss, extra)`` instead, ``extra = {"choices": {name: ints [..., k]},
"scalars": {name: scalar}}``: what the system chose, and the further scalars
of its loss (auxiliary and z-losses).  Its reference then takes
``given=None``: with ``given`` set to the system's ``choices`` it computes
THOSE in place of its own selection (weighted by its own float32
probabilities of them), and either way it returns ``extra`` with the
``choices`` it would have made itself, its ``scalars``, and ``probs``
(``{name: float32 [..., n]}``), the probabilities its choices were made
from.  The harness then judges (i) hidden states, loss and every gradient
leaf under the system's choices at the SAME standing tolerances, which no
adapter can replace; (ii) the choices: the share of tokens whose set differs
from the reference's own and how far under the reference's weakest pick the
probability of anything the system took lies, against the adapter's
``CHOICE_DIFF_SHARE_TOL_PER_SQRT_LAYER`` and
``CHOICE_PROB_GAP_TOL_PER_SQRT_LAYER``; (iii) each further scalar within the
adapter's ``SCALAR_REL_TOL``; and it reports, and judges nothing by, the
distances to the reference choosing for itself (``*_independent``): rounding
flips a near tie, and a token with another expert is far away.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``).  Without a TPU, with an unknown ``device_kind`` or with
another device count than the cell's ``chips`` the exit code is 2 and no
result is printed.

``--rehearse`` drives the same runners end to end at toy widths on the CPU
backend (four virtual devices) to find wrong paths and control flow.  It
prints counts only, never a metric, and is no measurement.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import common  # noqa: E402  (JAX-free)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the CPU backend; no measurement")
    ap.add_argument("--dump-trace", default="",
                    help="also write the traced window as plain data here")
    return ap.parse_args(argv)


def rehearse_all(spec: dict) -> int:
    """Each cell in a process of its own (the device count is fixed at
    JAX's start-up, and the elastic parent must stay off JAX)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    worst = 0
    for w in spec["workloads"]:
        rc = subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--rehearse",
             "--workload", w["name"], "--seconds", "4", "--trace", "1"],
            env=env, cwd=REPO)
        print(f"REHEARSAL {w['name']} rc={rc}", flush=True)
        worst = worst or rc
    return worst


def layer_metrics(spec: dict, cell: dict, out: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    metrics = {}
    for m in common.metrics_for(spec, "per_layer", cell["name"]):
        reader = common.load_module("layer_metrics", m["name"])
        value = reader.read(out["spans"], out["trace"], out["counters"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = common.load_spec()
    if args.rehearse and not args.workload:
        return rehearse_all(spec)
    if not args.workload or args.seconds <= 0:
        print("run.py: --workload and --seconds are required",
              file=sys.stderr)
        return 2
    cell = common.load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cell = common.rehearsal_cell(cell)
    runner = common.load_module("runners", cell["traffic_data"]["kind"])
    try:
        out = runner.run(cell, args, T_START)
    except common.Refused as e:
        print(f"run.py: refused: {e}", file=sys.stderr)
        return 2
    if args.trace:
        metrics = layer_metrics(spec, cell, out)
    else:
        wanted = common.metrics_for(spec, "end_to_end", cell["name"])
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                               "unit": m["unit"]} for m in wanted}
    for line in out.get("notes", []):
        print(line, flush=True)
    if args.rehearse:
        print("REHEARSAL (CPU, toy widths, not a measurement) " + json.dumps({
            "cell": cell["name"], "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics_found": sorted(metrics)}), flush=True)
        return 0 if out["correct"] else 1
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": out["device"]}
    if args.trace:
        from benchmark.harness import trace_reduce

        result["breakdown"] = trace_reduce.breakdown(out["trace"])
    common.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
