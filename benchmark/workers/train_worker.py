#!/usr/bin/env python3
"""The elastic cell's worker, started by the agent as a user's training
script is: ``python -m dlrover_tpu.run --standalone ... train_worker.py``.

Incarnation 0 (``restart_count`` 0) builds the step, trains, saves to shared
memory at ``setup_save_step`` and trains on until the benchmark's parent
kills it.  Every later incarnation restores, replays the steps the first
had computed past the save (their losses must agree), then measures the
window as the steady cells do: optimizer steps until its seconds are up,
each ended by the loss reaching the host and by the step's report to the
agent.  Once the window has closed it saves to shared memory once more (the
resumed worker can; the stall is printed and is no part of any metric).  It
reports on standard output in lines ``BENCH {json}``; the parent stamps each
with its own clock as it arrives.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def emit(kind: str, **fields) -> None:
    print("BENCH " + json.dumps(dict(fields, kind=kind, pid=os.getpid())),
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--work", required=True)
    args = ap.parse_args()

    import dlrover_tpu.trainer as trainer_sdk
    from benchmark.harness import common
    from benchmark.harness.train_loop import (
        TrainSession,
        start_trace,
        stop_trace,
    )

    ctx = trainer_sdk.init()
    emit("start", restart_count=ctx.restart_count)
    cell = common.load_cell(args.cell)
    if args.rehearse:
        cell = common.rehearsal_cell(cell)
    traffic = cell["traffic_data"]
    sess = TrainSession(cell, args.seed, T_START)
    summary = sess.open_device()
    try:
        common.check_device(summary, cell["chips"], bool(args.rehearse))
    except common.Refused as e:
        emit("refused", why=str(e))
        return 3
    emit("device", summary=summary,
         device_open_s=sess.spans["device_open_s"],
         backend_open_s=sess.spans["backend_open_s"])
    compiles = common.CompileCounter()
    sess.build()
    sess.create_state()

    from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

    ckpt = FlashCheckpointer(os.path.join(args.work, "ckpt"),
                             job_name=ctx.job_name)
    t0 = time.monotonic()
    restored = ckpt.load(target=sess.state)
    sess.spans["restore_s"] = time.monotonic() - t0
    start_step = 0
    if restored is not None:
        sess.state, meta = restored
        start_step = int(meta.get("step", 0))
        emit("restored", step=start_step, restore_s=sess.spans["restore_s"])
    sess.start_sampler(start_step)
    loss = sess.first_step()
    ctx.report_step(sess.step_no)
    emit("step", n=sess.step_no, loss=loss, first=True)

    save_step = traffic["setup_save_step"]
    replay_to = save_step + traffic["kill_steps_after_save"]
    if restored is None:
        # incarnation 0: train, save once, train on until killed
        while sess.step_no < replay_to + 200:
            if sess.step_no == save_step:
                stall = sess.save(ckpt, record=False)
                emit("save", step=sess.step_no, stall_s=stall, setup=True)
            loss = sess.step(record=False)
            ctx.report_step(sess.step_no)
            emit("step", n=sess.step_no, loss=loss)
        emit("error", why="incarnation 0 was never killed")
        return 4

    while sess.step_no < replay_to:
        loss = sess.step(record=False)
        ctx.report_step(sess.step_no)
        emit("step", n=sess.step_no, loss=loss)

    # -- the window: optimizer steps, as ``runners/train_steady.py`` --------
    trace_dir = os.path.join(args.work, "trace")

    def end_trace():
        with open(os.path.join(args.work, "trace.json"), "w") as f:
            json.dump(stop_trace(trace_dir), f)

    emit("window_open")
    compiles.armed = True
    t_open = time.monotonic()
    traced_steps, tracing = [], False
    first_traced = traffic["trace_skip_steps"] if args.trace else -1
    n = 0
    while True:
        if n == first_traced:
            start_trace(trace_dir)
            tracing = True
        sess.step()
        ctx.report_step(sess.step_no)
        if tracing:
            traced_steps.append(n)
            if len(traced_steps) == traffic["trace_steps"]:
                end_trace()
                tracing = False
        n += 1
        t_end = time.monotonic()
        if t_end - t_open >= args.seconds:
            break
    compiles.armed = False
    if tracing:
        end_trace()
    # the window has closed: training went on after the resume; saving does
    stall_s = sess.save(ckpt)

    from dlrover_tpu.agent.metrics import perf_stats

    emit("result",
         spans=sess.spans, losses=sess.losses, steps=n,
         window_s=t_end - t_open, traced_steps=traced_steps,
         tokens_per_step=sess.tokens_per_step, save_stall_s=stall_s,
         compiles_in_window=compiles.count,
         memory_peak_bytes=common.memory_peak_bytes(),
         engine_stall_ms_last=ckpt.engine.last_stall_ms,
         engine_staged_mbps_last=perf_stats.get("ckpt_staged_mbps"),
         program=sess.job.program, memory=sess.job.memory)
    # Exiting would make the agent persist the staged step once more
    # (tens of seconds nobody measures): wait for the parent to end the run.
    time.sleep(3600)
    return 0


if __name__ == "__main__":
    sys.exit(main())
