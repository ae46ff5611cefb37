"""Steady training in one process: set-up, then optimizer steps for the
window, each ended by the loss reaching the host.  No checkpointing.

``setup_s`` is the host clock from the process's start to the window's
opening LESS ``backend_open_s`` (the first ``jax.devices()``: the runtime's
start-up) and ``check_s`` (the comparison with the reference and the work
directory's removal): imports, ``build_job`` + ``accelerate()``, the state,
the sampler and every warm-up step.  The ``SETUP_S`` line prints the total
and both parts."""

from __future__ import annotations

import json
import math
import os
import shutil
import statistics
import time

from benchmark.harness import common, trace_reduce
from benchmark.harness.train_loop import TrainSession, start_trace, stop_trace


def run(cell: dict, args, t_start: float) -> dict:
    from benchmark.harness.model import check_against_reference

    traffic = cell["traffic_data"]
    sess = TrainSession(cell, args.seed, t_start)
    summary = sess.open_device()
    peaks = common.check_device(summary, cell["chips"], args.rehearse)
    compiles = common.CompileCounter()
    sess.build()
    sess.create_state()
    sess.start_sampler()
    sess.first_step()
    for _ in range(traffic["warmup_steps"] - 1):
        sess.step(record=False)
    # the benchmark checking itself: decides ``correct``, is printed, and
    # is no part of ``setup_s`` (its programs are the harness's, not the
    # tree's; their compile is most of a cold run)
    t_check = time.monotonic()
    check = check_against_reference(
        sess.job, sess.model_config, cell, sess.state["params"], args.seed)
    work = os.path.join(common.WORK_DIR, cell["name"])
    trace_dir = os.path.join(work, "trace")
    shutil.rmtree(work, ignore_errors=True)
    t_ready = time.monotonic()
    setup_s, setup_note = common.less_parts(
        "SETUP_S", t_ready - t_start,
        backend_open_s=sess.spans["backend_open_s"],
        check_s=t_ready - t_check)

    # -- the window ---------------------------------------------------------
    compiles.armed = True
    t_open = time.monotonic()
    trace, traced_steps, tracing = {"planes": []}, set(), False
    first_traced = traffic["trace_skip_steps"] if args.trace else -1
    n = 0
    while True:
        if n == first_traced:
            start_trace(trace_dir)
            tracing = True
        sess.step()
        if tracing:
            traced_steps.add(n)
            if len(traced_steps) == traffic["trace_steps"]:
                trace, tracing = stop_trace(trace_dir), False
        n += 1
        t_end = time.monotonic()
        if t_end - t_open >= args.seconds:
            break
    compiles.armed = False
    if tracing:
        trace = stop_trace(trace_dir)
    if args.dump_trace:
        with open(args.dump_trace, "w") as f:
            json.dump(trace, f)
        raw = trace_reduce.newest_xplane(trace_dir)
        if raw:
            shutil.copy(raw, args.dump_trace + ".xplane.pb")
    shutil.rmtree(work, ignore_errors=True)

    tokens = n * sess.tokens_per_step
    bad = sum(not math.isfinite(x) for x in sess.losses)
    untraced = [s for i, s in enumerate(sess.spans["step_s"])
                if i not in traced_steps]
    reduced = trace_reduce.reduce_trace(trace)
    device = dict(summary, memory_peak_bytes=common.memory_peak_bytes())
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    counters = {
        "cell": cell, "peaks": peaks, "chips": cell["chips"],
        "steps": n, "tokens_per_step": sess.tokens_per_step,
        "traced_steps": len(traced_steps),
        # tracing slows the host: the rate of a traced run is taken over
        # the steps outside the trace
        "tokens_per_s": sess.tokens_per_step * len(untraced) / sum(untraced),
        "memory_peak_bytes": device["memory_peak_bytes"],
        "compiles_in_window": compiles.count,
        "compiled_memory": sess.job.memory,
        # what the last step returned beside its loss, for a reader of a
        # counter the jitted step computes itself
        "step_metrics": sess.step_metrics(),
    }
    notes = [
        f"DEVICE {summary}",
        f"PROGRAM {sess.job.program} memory {sess.job.memory}",
        f"CHECK {check}",
        f"SETUP {({k: round(v, 3) for k, v in sess.spans.items() if not isinstance(v, list)})}",
        setup_note,
        f"WINDOW steps={n} tokens={tokens} seconds={t_end - t_open:.3f} "
        f"median_step_s={statistics.median(sess.spans['step_s']):.4f} "
        # a host stall shows as one long step; the rate counts it
        f"max_step_s={max(sess.spans['step_s']):.4f} "
        f"at_step={sess.spans['step_s'].index(max(sess.spans['step_s']))} "
        f"compiles_in_window={compiles.count} "
        f"loss_first={sess.losses[0]:.4f} loss_last={sess.losses[-1]:.4f}",
        # what the window's last step returned beside its loss: the routed
        # cells' loads, held pairs and buffer sizes, in untraced runs too
        f"STEP_METRICS {json.dumps(counters['step_metrics'])}",
    ]
    return {
        "correct": check["ok"] and bad == 0 and compiles.count == 0,
        "attempted": n, "failed": bad,
        "end_to_end": {
            "train_tokens_per_s": tokens / (t_end - t_open),
            "setup_s": setup_s,
        },
        "spans": sess.spans, "trace": reduced, "counters": counters,
        "device": device, "notes": notes,
    }
