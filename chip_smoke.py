#!/usr/bin/env python3
"""First contact: prove the system still starts on the chip.

    python chip_smoke.py            # one chip: train+kill+resume, restore, serve, kernels
    python chip_smoke.py --chips 4  # four chips: the sharded step, nothing else

Drives the main path once through the entry points a user would call, at the
full width of ``LlamaConfig.small_300m()`` (sequence 2,048, bf16, weights from
a seed), and checks what comes out by the repo's own means:

- ``train``: ``python -m dlrover_tpu.run --standalone --nproc_per_node=1
  examples/llama_train.py`` with ``DLROVER_TPU_FAULTS=worker.kill:step=6`` —
  the worker dies holding the chip, the agent restarts it, it restores from
  shared memory and finishes; then ``checkpoint.fsck`` on what it persisted.
- ``serve``: ``DecodeServer`` over 8 requests (prompts 64..1,024 tokens, so
  bucketed and chunked prefill both run), slotted and paged, against
  ``generate`` and the teacher-forced training forward for one request.
- ``kernels``: ``ops/smoke.py`` — every Pallas kernel compiled by Mosaic
  (``interpret=False``), executed, value- and grad-checked.
- ``restore``: the warm restore hands views of the shm arena straight to
  ``device_put``; state A is saved and restored, state B is saved over it,
  and the restored arrays must still be A bit for bit — only a chip can
  show that its ``device_put`` keeps no alias of the host buffer.
- ``mesh4`` (``--chips 4`` only): ``accelerate`` on a ``fsdp=2 x tp=2`` mesh
  against the same seed and batches on a one-device mesh.

A chip belongs to one process at a time, so this parent NEVER imports JAX:
each phase runs in a child that holds the chip alone and exits before the
next starts, and the device block of the last line is what the children
reported.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
without a TPU it is ``"ok": false`` and the exit code is non-zero — there is
no CPU mode that can print ``true``.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")  # git-ignored
JOB = "chip-smoke"
# fp32 params + two AdamW moments of the 220M-parameter preset, staged in
# POSIX shared memory and persisted to disk: ~2.6 GB each, with headroom.
TRAIN_STATE_BYTES = 3 << 30
# Logits of this preset are ~N(0, 0.64): the maximum of 32,000 sits near 2.9,
# where one bf16 step is 0.016; a wrong token (a real fault) sits ~2-3 below
# the maximum, a rounding tie within a few steps of it.
TIE_EPS = 0.1


#: The chip tool shows only the end of a run's output and brings back this
#: directory: the whole log goes there too.
LOG_PATH = os.path.join(REPO, "chiprun_out", "chip_smoke.log")


def say(msg: str) -> None:
    print(msg, flush=True)
    if "--child" not in sys.argv:  # children are echoed by the parent
        with open(LOG_PATH, "a") as f:
            f.write(msg + "\n")


# ---------------------------------------------------------------------------
# Phases that run IN a child (they import JAX and take the chip).  Each
# returns a JSON-able dict with "ok" and "device"; sizes are parameters so
# the tier-1 tests can run the same functions tiny on the virtual CPU mesh.
# ---------------------------------------------------------------------------


def _peak_bytes() -> object:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "n/a")


def device_phase() -> dict:
    from dlrover_tpu.common.jax_env import device_summary

    return {"ok": True, "device": device_summary()}


def serve_phase(cfg=None, *, slots=8, max_len=2048, new_tokens=64,
                prompt_lens=(64, 100, 200, 256, 300, 512, 777, 1024),
                seed=0) -> dict:
    """One process builds the preset from ``seed`` and serves the requests
    once slotted and once paged; greedy tokens must agree with each other
    and, for the first request, with the references (see below)."""
    import jax
    import numpy as np

    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )
    from dlrover_tpu.models import llama, llama_infer

    enable_compilation_cache()
    cfg = cfg or llama.LlamaConfig.small_300m()
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.RandomState(seed + 1)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in prompt_lens
    ]
    outs = {}
    for layout in ("slotted", "paged"):
        t0 = time.monotonic()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=slots, max_len=max_len,
            paged=layout == "paged",
        )
        outs[layout] = [
            np.asarray(o) for o in srv.serve(prompts,
                                             max_new_tokens=new_tokens)
        ]
        chunked = sum(len(p) > max(srv.buckets) for p in prompts)
        say(f"serve {layout}: {len(prompts)} requests, {chunked} past the "
            f"largest prompt bucket (chunked prefill), "
            f"{srv.last_stats.get('emitted_tokens')} tokens emitted in "
            f"{srv.last_stats.get('rounds')} rounds, "
            f"{time.monotonic() - t0:.1f}s incl. compile")
    shape_ok = all(
        len(o) == len(p) + new_tokens
        and (o[: len(p)] == p).all()
        and ((o >= 0) & (o < cfg.vocab_size)).all()
        for o, p in zip(outs["slotted"], prompts)
    )
    same_layouts = all(
        a.shape == b.shape and (a == b).all()
        for a, b in zip(outs["slotted"], outs["paged"])
    )
    # Request 0 against two references on the same device.  In bf16 with
    # random weights about one greedy step in ten is a tie or a near-tie
    # at the top of the logits, and the server (8 slots, a 2,048-long
    # cache) rounds differently from a solo pass — so "right" is: every
    # token the server chose is a maximiser of the REFERENCE logits for
    # its own prefix (the training forward, teacher-forced) within
    # TIE_EPS, and ``generate`` agrees with it token for token up to a
    # position where both their choices are such maximisers.
    out0, n0 = outs["slotted"][0], len(prompts[0])
    logits = np.asarray(jax.jit(
        lambda p, t: llama.forward(p, t, cfg)[0]
    )(params, jax.numpy.asarray(out0[None, :-1]))[0], np.float32)
    steps = logits[n0 - 1:]  # row i scores generated token i
    top = steps.max(-1)
    gaps = top - steps[np.arange(new_tokens), out0[n0:]]
    forced_ok = bool(np.isfinite(steps).all() and gaps.max() <= TIE_EPS)
    say(f"serve: request 0 vs the teacher-forced training forward: "
        f"{int((gaps == 0).sum())} of {new_tokens} tokens are the exact "
        f"argmax, worst gap to the maximum {gaps.max():.4f} "
        f"(tolerance {TIE_EPS})")
    ref = np.asarray(llama_infer.generate(
        params, cfg, jax.numpy.asarray(prompts[0])[None],
        max_new_tokens=new_tokens,
    ))[0]
    diff = np.nonzero(ref != out0)[0]
    if diff.size == 0:
        generate_ok = True
        say(f"serve: generate == server on all {new_tokens} tokens")
    else:
        i = int(diff[0]) - n0  # same prefix up to here
        gen_gap = float(top[i] - steps[i, ref[n0 + i]])
        generate_ok = i >= 0 and gen_gap <= TIE_EPS
        say(f"serve: generate == server on the first {i} of {new_tokens} "
            f"tokens; at the first difference the server's choice is "
            f"{gaps[i]:.4f} and generate's {gen_gap:.4f} below the "
            f"reference maximum (a tie within {TIE_EPS}: {generate_ok})")
    say(f"serve: shapes/vocab ok={shape_ok} slotted==paged={same_layouts} "
        f"reference ok={forced_ok} generate ok={generate_ok}")
    return {
        "ok": bool(shape_ok and same_layouts and forced_ok and generate_ok),
        "device": device_summary(),
        "peak_bytes_in_use": _peak_bytes(),
    }


def kernels_phase() -> dict:
    from dlrover_tpu.common.jax_env import device_summary
    from dlrover_tpu.ops.smoke import run_kernel_smoke

    os.makedirs(WORK, exist_ok=True)
    res = run_kernel_smoke(out_path=os.path.join(WORK, "kernel_smoke.json"))
    for name, r in res["kernels"].items():
        say(f"kernel {name}: " + json.dumps(
            {k: v for k, v in r.items() if k != "traceback"}))
    say(f"kernels: {res['n_ok']} of {res['n_total']} ok (interpret=False)")
    return {
        "ok": bool(res["all_ok"] and res["n_total"] == 14),
        "device": device_summary(),
        "peak_bytes_in_use": _peak_bytes(),
    }


def mesh4_phase(cfg=None, *, batch=8, seq=2048, steps=3, seed=0,
                rel_tol=1e-3) -> dict:
    """The sharded step: ``fsdp=2 x tp=2`` over four local devices against
    a one-device mesh, same seed, same batches, one process.  ``rel_tol``
    is the stated bf16 tolerance on the step-wise loss: tp splits each
    contraction into two partial sums, so bf16 roundings differ — 1.1e-5
    was measured on four v5e chips (PR 21), a wrong shard or a dropped
    collective moves the loss in its first digits."""
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    enable_compilation_cache()
    cfg = cfg or llama.LlamaConfig.small_300m()
    devs = jax.devices()
    if len(devs) < 4:
        say(f"mesh4: needs 4 devices, this process has {len(devs)}")
        return {"ok": False, "device": device_summary()}
    rng = np.random.RandomState(seed)
    batches = [
        rng.randint(0, cfg.vocab_size, size=(batch, seq + 1)).astype(
            np.int32)
        for _ in range(steps)
    ]

    def run(spec, devices):
        job = accelerate(
            loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
            init_fn=lambda r: llama.init_params(r, cfg),
            optimizer=optax.adamw(3e-4),
            sample_batch={"tokens": batches[0]},
            strategy=Strategy(mesh=spec), param_specs="planner",
            devices=devices,
        )
        state = job.create_state(jax.random.PRNGKey(seed))
        losses = []
        for toks in batches:
            b = {"tokens": jax.device_put(toks,
                                          job.batch_sharding["tokens"])}
            state, metrics = job.train_step(state, b)
            losses.append(float(metrics["loss"]))
        return job, state, losses

    job, state, sharded = run(MeshSpec(fsdp=2, tp=2), devs[:4])
    leaves = jax.tree_util.tree_leaves(state["params"])
    split = [x for x in leaves if not x.sharding.is_fully_replicated]
    spread_ok = bool(split) and all(
        len({s.device for s in x.addressable_shards}) == 4
        and all(s.data.size < x.size for s in x.addressable_shards)
        for x in split
    ) and all(
        len({s.device for s in x.addressable_shards}) == 4 for x in leaves
    )
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devs[:4]
    ]
    on_tpu = devs[0].platform == "tpu"
    # memory_stats() is None on the CPU backend; on the chip every device
    # must hold bytes (code that has only seen one chip may put all of the
    # state on the first).
    mem_ok = all(b for b in in_use) if on_tpu else True
    prog = job.program
    say(f"mesh4: program {json.dumps(prog)}")
    say(f"mesh4: {len(split)} of {len(leaves)} param leaves sharded, each "
        f"on 4 distinct devices with partial shards: {spread_ok}; "
        f"bytes_in_use per device {in_use}")
    coll_ok = (prog["collectives"]["all-gather"] > 0
               and prog["collectives"]["all-reduce"] > 0)
    kern_ok = bool(prog["kernels"]) if on_tpu else True
    del state
    _, _, single = run(MeshSpec(), devs[:1])
    worst = max(
        abs(a - b) / max(abs(b), 1e-9) for a, b in zip(sharded, single)
    )
    finite = all(np.isfinite(sharded)) and all(np.isfinite(single))
    for i, (a, b) in enumerate(zip(sharded, single)):
        say(f"mesh4: step {i + 1} loss fsdp2xtp2={a:.5f} "
            f"one-device={b:.5f}")
    say(f"mesh4: worst relative loss difference {worst:.2e} "
        f"(tolerance {rel_tol:.0e})")
    return {
        "ok": bool(finite and worst <= rel_tol and spread_ok and mem_ok
                   and coll_ok and kern_ok),
        "device": device_summary(),
        "peak_bytes_in_use": _peak_bytes(),
    }


def restore_phase(*, leaves=4, leaf_mib=64, seed=0) -> dict:
    """Save state A to shared memory, ``load()`` it onto the device, save
    a different state B into the same arena, and compare what was
    restored with A bit for bit.  On a TPU every piece must have gone
    from the arena's file through a reused staging buffer to
    ``device_put`` (``staged_bytes`` = the state, ``copied_bytes`` 0),
    so this is the proof that the restored arrays keep no alias of the
    arena or of a staging buffer once ``block_until_ready`` has
    returned; on the CPU backend, which may alias a numpy buffer, each
    piece is read into an array of its own."""
    import jax
    import numpy as np

    from dlrover_tpu import obs
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common.jax_env import device_summary
    from dlrover_tpu.common.shm import arena_name

    job = f"{JOB}-restore-{os.getpid()}"
    n = (leaf_mib << 20) // 4
    rng = np.random.RandomState(seed)
    host_a = {f"w{i}": rng.standard_normal(n).astype(np.float32)
              for i in range(leaves)}
    host_a["count"] = np.int32(7)
    host_b = {k: (v + 1).astype(v.dtype) for k, v in host_a.items()}
    dev = jax.local_devices()[0]
    state_a = jax.device_put(host_a, dev)
    nbytes = sum(int(v.nbytes) for v in host_a.values())
    eng = CheckpointEngine(os.path.join(WORK, "restore_ckpt"), job_name=job)
    try:
        eng.save_to_memory(1, state_a)
        t0 = time.monotonic()
        restored, meta = eng.load(target=state_a)
        load_s = time.monotonic() - t0
        eng.save_to_memory(2, jax.device_put(host_b, dev))
        # B is in the arena now: an alias would read B here
        got = {k: np.asarray(v) for k, v in restored.items()}
        same = all(
            got[k].dtype == host_a[k].dtype
            and np.array_equal(got[k], host_a[k])
            for k in host_a
        )
        again, meta_b = eng.load(target=state_a)
        staged_b = meta_b.get("step") == 2 and all(
            np.array_equal(np.asarray(again[k]), host_b[k]) for k in host_b
        )
    finally:
        eng.close()
        try:
            os.unlink(f"/dev/shm/{arena_name(job, 0)}")
        except FileNotFoundError:
            pass
    evs, _, _ = obs.get_recorder().snapshot()
    spans = {e["name"]: e.get("args", {}) for e in evs if e["k"] == "span"}
    read, put = spans["ckpt.load.shm_read"], spans["ckpt.load.device_put"]
    on_cpu = dev.platform == "cpu"
    want_copied = nbytes if on_cpu else 0
    counted = (read.get("copy") is False
               and put.get("copied_bytes") == want_copied
               and put.get("staged_bytes") == nbytes - want_copied)
    say(f"restore: {nbytes} bytes in {len(host_a)} leaves on "
        f"{dev.platform}; load {load_s:.3f}s; shm_read {json.dumps(read)}; "
        f"device_put {json.dumps(put)}")
    say(f"restore: step {meta.get('step')} restored; arena overwritten "
        f"with step 2 (restored in turn: {staged_b}); the first restore "
        f"still equals state A bit for bit: {same}; counts as expected: "
        f"{counted}")
    return {
        "ok": bool(same and staged_b and counted and meta.get("step") == 1),
        "device": device_summary(),
        "peak_bytes_in_use": _peak_bytes(),
    }


CHILD_PHASES = {
    "device": device_phase,
    "serve": serve_phase,
    "kernels": kernels_phase,
    "mesh4": mesh4_phase,
    "restore": restore_phase,
}


def child_main(name: str) -> int:
    res = CHILD_PHASES[name]()
    say("RESULT " + json.dumps(res))
    return 0 if res["ok"] else 1


# ---------------------------------------------------------------------------
# The parent: never imports JAX.
# ---------------------------------------------------------------------------


#: Every process this run starts inherits this marker, so the sweep finds
#: them whatever session or process group they moved to (the agent gives
#: each worker its own).
MARK = ("CHIP_SMOKE_RUN", f"{os.getpid()}-{int(time.time())}")
DEADLINE = time.monotonic() + 1150.0  # the contract allows 1200 s


def _kill_started() -> None:
    """Kill every live process that carries this run's marker: a phase
    leaves nothing behind that could hold the chip."""
    import psutil

    me = os.getpid()
    for p in psutil.process_iter():
        try:
            if p.pid != me and p.environ().get(MARK[0]) == MARK[1]:
                p.kill()
        except (psutil.NoSuchProcess, psutil.AccessDenied):
            continue


def _stream(cmd, env, timeout_s: float, tag: str):
    """Run ``cmd``, echo its merged output line by line under ``tag``, and
    return ``(rc, lines)``.  At the time limit (the phase's own, or what
    is left of the whole run's) everything it started is killed, and in
    any case before returning."""
    import threading

    env = dict(env, **{MARK[0]: MARK[1]})
    timeout_s = max(1.0, min(timeout_s, DEADLINE - time.monotonic()))
    proc = subprocess.Popen(
        cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, errors="replace",
    )
    timer = threading.Timer(timeout_s, _kill_started)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            say(f"[{tag}] {line}")
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_started()
    return rc, lines


def run_child(name: str, timeout_s: float) -> dict:
    rc, lines = _stream(
        [sys.executable, os.path.abspath(__file__), "--child", name],
        os.environ, timeout_s, name,
    )
    for line in reversed(lines):
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            res["ok"] = bool(res["ok"]) and rc == 0
            return res
    say(f"{name}: child exited {rc} without a result")
    return {"ok": False, "device": None}


def train_phase() -> dict:
    """The elastic job through its launcher.  The launcher, the master and
    the agent never open the device; only the worker does."""
    ckpt = os.path.join(WORK, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    os.makedirs(ckpt)
    for path, what in (("/dev/shm", "the flash checkpoint's arena"),
                       (ckpt, "the persisted checkpoint")):
        free = shutil.disk_usage(path).free
        say(f"train: {path} has {free >> 20} MiB free")
        if free < TRAIN_STATE_BYTES:
            say(f"train: NOT ENOUGH SPACE for {what}: {path} has "
                f"{free >> 20} MiB free, the run needs "
                f"{TRAIN_STATE_BYTES >> 20} MiB")
            return {"ok": False, "device": None}
    env = dict(os.environ, DLROVER_TPU_FAULTS="worker.kill:step=6")
    try:
        rc, lines = _stream(
            [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
             "--nproc_per_node=1", f"--job_name={JOB}",
             "--monitor_interval=1",
             os.path.join("examples", "llama_train.py"), "--",
             "--model", "300m", "--seq_len", "2048",
             "--batch_per_proc", "8", "--steps", "12",
             "--ckpt_dir", ckpt, "--ckpt_interval", "4",
             "--log_interval", "1",
             # a dataset of one batch at a brisk rate: the loss falls by
             # memorisation within the dozen steps a smoke can afford
             "--lr", "1e-3", "--dataset_size", "8"],
            env, 900.0, "train",
        )
    finally:
        # The launcher unlinks its own run's arenas at exit; make sure of
        # it even when it was killed at the time limit.
        for seg in glob.glob(f"/dev/shm/dlrtpu_{JOB}-*"):
            os.unlink(seg)
    text = "\n".join(lines)
    devices = [json.loads(m) for m in re.findall(r"DEVICE (\{.*\})", text)]
    programs = [json.loads(m)
                for m in re.findall(r"PROGRAM (\{.*\})", text)]
    first = re.findall(r"FIRST_STEP seconds=([\d.]+) restart_count=(\d+)",
                       text)
    losses = [float(x) for x in re.findall(r"step \d+ loss ([-\w.]+)", text)]
    restored = [int(x) for x in re.findall(r"restored step=(\d+)", text)]
    killed = re.findall(r"chaos: worker\.kill fired \(ctx=\{[^}]*'step': "
                        r"(\d+)", text)
    peak = re.findall(r"MEMORY peak_bytes_in_use=(\S+)", text)
    native = sorted(set(re.findall(r"native (lib\w+\.so: [^\n]*)", text)))
    agent_opened = re.findall(
        r"device runtime opened by the agent: (\w+)", text)

    kernels = programs[-1]["kernels"] if programs else {}
    checks = {
        "launcher rc 0": rc == 0,
        "TRAIN_DONE": "TRAIN_DONE step=12" in text,
        "worker killed at step 6": killed == ["6"],
        "two incarnations, same device": (
            len(devices) == 2 and devices[0] == devices[1]),
        "restored at a step > 0 from shared memory": (
            bool(restored) and restored[-1] > 0
            and "warm restore from shm" in text),
        "compiled step holds flash fwd/bwd and rmsnorm kernels": all(
            kernels.get(k, 0) > 0 for k in
            ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rmsnorm_fwd")),
        "losses finite and falling": (
            len(losses) >= 12
            and all(math.isfinite(x) for x in losses)
            and losses[-1] < losses[0]
            and sum(losses[-3:]) < sum(losses[:3])),
        "agent never opened the device": agent_opened == ["False"],
    }
    say(f"train: time to first step per incarnation (s, restart_count): "
        f"{first}")
    say(f"train: losses {losses}")
    say(f"train: compiled step {json.dumps(programs[-1]) if programs else None}")
    say(f"train: peak_bytes_in_use {peak}; native backends {native}")
    t0 = time.monotonic()
    fsck_rc, _ = _stream(
        [sys.executable, "-m", "dlrover_tpu.checkpoint.fsck", ckpt],
        os.environ, 300.0, "fsck",
    )
    say(f"train: fsck rc {fsck_rc} in {time.monotonic() - t0:.1f}s")
    checks["fsck rc 0"] = fsck_rc == 0
    for what, ok in checks.items():
        say(f"train: {'ok  ' if ok else 'FAIL'} {what}")
    shutil.rmtree(ckpt, ignore_errors=True)
    return {"ok": all(checks.values()),
            "device": devices[-1] if devices else None,
            "peak_bytes_in_use": peak[-1] if peak else "n/a"}


def _cache_entries() -> str:
    from dlrover_tpu.common.jax_env import compilation_cache_dir  # JAX-free

    d = compilation_cache_dir()
    n = len(os.listdir(d)) if os.path.isdir(d) else 0
    return f"{d} ({n} entries)"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--child", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args.child)

    os.makedirs(os.path.dirname(LOG_PATH), exist_ok=True)
    open(LOG_PATH, "w").close()
    t_all = time.monotonic()
    device = None
    ok = True
    # A quick child first: what does JAX find here?  Everything after it
    # costs minutes, and none of it may claim a result without a TPU.
    plan = [("device", lambda: run_child("device", 180.0))]
    if args.chips == 1:
        plan += [
            ("train", train_phase),
            ("restore", lambda: run_child("restore", 300.0)),
            ("serve", lambda: run_child("serve", 900.0)),
            ("kernels", lambda: run_child("kernels", 600.0)),
        ]
    else:
        plan += [("mesh4", lambda: run_child("mesh4", 900.0))]
    for name, phase in plan:
        t0 = time.monotonic()
        res = phase()
        say(f"PHASE {name} ok={res['ok']} wall_s="
            f"{time.monotonic() - t0:.1f} "
            f"peak_bytes_in_use={res.get('peak_bytes_in_use', 'n/a')} "
            f"compile cache {_cache_entries() if res['device'] else 'n/a'}")
        device = device or res["device"]
        on_chip = (
            res["device"] is not None
            and res["device"] == device
            and device["platform"] == "tpu"
            and device["count"] == args.chips
        )
        if not (res["ok"] and on_chip):
            if res["ok"]:
                say(f"{name}: ran on {res['device']}, not on "
                    f"{args.chips} TPU chip(s)")
            ok = False
            break
    say(f"chip_smoke: total wall_s={time.monotonic() - t_all:.1f} "
        f"(parent imported jax: {'jax' in sys.modules})")
    ok = ok and "jax" not in sys.modules
    say(json.dumps({"ok": ok, "device": device}))  # the last line
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
