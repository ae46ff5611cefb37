"""Save, kill, resume: the elastic job through its launcher.

The parent (this file) NEVER imports JAX: the chip belongs to the worker the
agent starts.  It launches ``python -m dlrover_tpu.run --standalone ...
benchmark/workers/train_worker.py``, stamps every line with its own
monotonic clock as it arrives, sends the first worker a ``SIGKILL`` two
steps after its save, reads the kill-to-step seconds off the restarted
worker's first completed step, lets it run the window (optimizer steps for
its seconds, as the steady cells', each also reported to the agent) and save
to memory once after it, then ends the whole process tree, checks the
persisted checkpoint with ``checkpoint.fsck`` and removes what the run left
in ``/dev/shm`` and on disk (the job's journal directory too; a traced run's
after its readers).

``train_tokens_per_s`` is the window's, by the resumed worker's clock: the
tokens of its completed steps over the seconds from its opening to the end
of its last step.  ``setup_s`` is the parent's clock from its own start to
the window's opening; the first save, the kill, the agent's notice, persist
and restart, the restore and the replay all lie inside it.  The kill-to-step
seconds (``resume_s`` end to end until PR 50: one kill a run swings by more
than any bound may take, PERF.md section 2) are the per-layer
``agent.kill_to_step_s``.  Both clocks of set-up leave out the runtime's own
start-up, which no tree can move: each worker stamps its first
``jax.devices()`` as ``backend_open_s`` on its ``device`` line; the
kill-to-step seconds are less the restarted worker's, ``setup_s`` less both
workers'.  The ``RESUME`` and ``SETUP_S`` lines print each total and what
was taken out.
"""

from __future__ import annotations

import glob
import json
import math
import os
import queue
import re
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

from benchmark.harness import common, obs_read, trace_reduce

#: seconds to wait for the next expected line before the run is given up
#: (the first run of a checkout compiles inside these)
WAIT_S = {"start": 120, "device": 180, "step": 900, "restored": 900,
          "window_open": 300}
#: losses of the steps replayed after the restore, against those of the
#: first incarnation: same seed, same data order, same compiled program,
#: state restored bit for bit from shared memory — measured identical on
#: the v5e (PR 22); 1e-6 relative allows nothing but a changed last digit.
REPLAY_REL_TOL = 1e-6
MARK = "DLROVER_BENCH_RUN"
#: the agent's log lines between the kill and the new worker's first line,
#: by the parent's clock as they arrive; printed on the ``RESTART`` line as
#: seconds after the kill (a reworded line prints None), read by no metric
#: but ``ckpt.persist_s`` (persisting -> stopped)
RESTART_MARKS = {
    "failure_seen": r"worker failure\(s\)",
    "persisting": r"breakpoint save \(.*persisting",
    "stopped": r"stopped workers \(",
    "started": r"started \d+ worker\(s\)",
}


class Lines:
    """The launcher's merged output, line by line, stamped on arrival."""

    def __init__(self, proc, log_path: str):
        self.events: "queue.Queue[dict]" = queue.Queue()
        self.seen = []  # every BENCH event, in order of arrival
        self.log = []  # (t, line) of everything that is no BENCH line
        self._proc = proc
        self._log_file = open(log_path, "w")
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        for line in self._proc.stdout:
            t = time.monotonic()
            line = line.rstrip("\n")
            self._log_file.write(f"{t:.3f} {line}\n")
            at = line.find("BENCH {")
            if at >= 0:
                try:
                    ev = json.loads(line[at + 6:])
                except ValueError:
                    continue
                ev["t"] = t
                self.seen.append(ev)
                self.events.put(ev)
            else:
                self.log.append((t, line))
        self._log_file.flush()
        self.events.put({"kind": "eof", "t": time.monotonic()})

    def expect(self, kind: str, timeout: float, **match) -> dict:
        """The next event of ``kind`` (others of the worker's are skipped);
        raises on end of output, a refusal, an error or the time limit."""
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            try:
                ev = self.events.get(timeout=max(0.1, left))
            except queue.Empty:
                raise RuntimeError(f"no {kind!r} line within {timeout}s")
            if ev["kind"] == "refused":
                raise common.Refused(ev["why"])
            if ev["kind"] in ("eof", "error"):
                raise RuntimeError(f"waiting for {kind!r}: got {ev}")
            if ev["kind"] == kind and all(
                    ev.get(k) == v for k, v in match.items()):
                return ev

    def close(self) -> None:
        self._thread.join(timeout=10)
        self._log_file.close()

    def first_time(self, pattern: str, after: float = 0.0):
        rx = re.compile(pattern)
        for t, line in self.log:
            if t >= after and rx.search(line):
                return t
        return None


def _marked_pids(mark: str) -> list:
    import psutil

    found = []
    for p in psutil.process_iter():
        try:
            if p.pid != os.getpid() and p.environ().get(MARK) == mark:
                found.append(p)
        except (psutil.NoSuchProcess, psutil.AccessDenied):
            continue
    return found


def _kill_tree(mark: str) -> None:
    """End every process that carries this run's marker, and wait."""
    import psutil

    procs = _marked_pids(mark)
    for p in procs:
        try:
            p.kill()
        except psutil.NoSuchProcess:
            pass
    psutil.wait_procs(procs, timeout=30)


def _holds_device(pid: int) -> bool:
    """Whether a process has the TPU's device files open or libtpu mapped
    — asked of /proc, so of the launcher and the agent too."""
    try:
        fds = [os.readlink(f) for f in glob.glob(f"/proc/{pid}/fd/*")]
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
    except OSError:
        return False
    return ("libtpu" in maps
            or any(fd.startswith(("/dev/vfio/", "/dev/accel")) for fd in fds))


def _arenas(job: str) -> list:
    """This run's shared-memory arenas: ``dlrtpu_<job>-<run id>_<purpose>_
    <rank>`` (``common/shm.py::arena_name``; the launcher scopes the job by
    its run id).  The separator after the job keeps ``bench-12`` from
    matching ``bench-123``'s."""
    return glob.glob(f"/dev/shm/dlrtpu_{job}[-_]*")


def _watch_exit(pid: int, t_kill: float, seen: dict) -> None:
    """Stamps ``worker_exit``: the parent's clock from its SIGKILL until
    /proc shows the worker dead, and how (``Z``: a zombie for the agent to
    reap; ``gone``: reaped already).  Beside the agent's ``failure_seen``
    on the ``RESTART`` line it says whether the seconds before the agent
    sees an exit code are the kernel's or the agent's; taken out of
    nothing."""
    def poll():
        while time.monotonic() - t_kill < WAIT_S["restored"]:
            try:
                with open(f"/proc/{pid}/stat") as f:
                    state = f.read().rsplit(")", 1)[1].split()[0]
            except (OSError, IndexError):
                state = "gone"
            if state in ("Z", "X", "gone"):
                seen["worker_exit"] = (
                    f"{time.monotonic() - t_kill:.3f}({state})")
                return
            time.sleep(0.02)

    threading.Thread(target=poll, daemon=True).start()


def run(cell: dict, args, t_start: float) -> dict:
    traffic = cell["traffic_data"]
    work = os.path.join(common.WORK_DIR, cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "ckpt"))
    mark = f"{os.getpid()}-{int(time.time())}"
    job = f"bench-{os.getpid()}"
    sock_dir = tempfile.mkdtemp(prefix="dlb")
    env = dict(os.environ, **{
        MARK: mark, "DLROVER_TPU_SOCK_DIR": sock_dir,
        "PYTHONPATH": os.pathsep.join(
            [common.REPO] + [p for p in [os.environ.get("PYTHONPATH")] if p]),
    })
    worker = os.path.join(common.BENCH_DIR, "workers", "train_worker.py")
    cmd = [sys.executable] + traffic["launcher"] + [
        f"--job_name={job}", worker, "--",
        "--cell", cell["name"], "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--rehearse", str(int(args.rehearse)), "--work", work,
    ]
    proc = subprocess.Popen(
        cmd, env=env, cwd=common.REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, errors="replace")
    lines = Lines(proc, os.path.join(work, "launcher.log"))
    try:
        out = _drive(cell, args, t_start, lines, mark)
    except Exception:
        tail = [ln for _, ln in lines.log[-40:]]
        print("\n".join(tail), file=sys.stderr)
        raise
    finally:
        _kill_tree(mark)
        proc.wait()
        lines.close()
        for seg in _arenas(job):
            os.unlink(seg)
        shutil.rmtree(sock_dir, ignore_errors=True)
        if not args.trace:
            # a traced run's journals are read after this returns
            # (layer_metrics/), and go when the process exits
            obs_read.remove_job_dirs()
    # -- outside the window, the chip free again ---------------------------
    t0 = time.monotonic()
    fsck = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.checkpoint.fsck",
         os.path.join(work, "ckpt")],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=common.REPO,
        capture_output=True, text=True)
    notes = out["notes"]
    notes.append(f"FSCK rc={fsck.returncode} seconds="
                 f"{time.monotonic() - t0:.1f} "
                 f"{fsck.stdout.strip().splitlines()[-1:]}")
    out["checks"]["fsck rc 0 on the persisted step"] = fsck.returncode == 0
    leftovers = _arenas(job) + [
        p.pid for p in _marked_pids(mark)] + (
        [] if args.trace else obs_read.job_dirs())
    out["checks"]["no arena, process or journal outlives the run"] = (
        not leftovers)
    for what, ok in out["checks"].items():
        notes.append(f"CHECK {'ok  ' if ok else 'FAIL'} {what}")
    out["correct"] = all(out.pop("checks").values())
    # gigabytes of checkpoint never stay behind; the log does after a fault
    for part in ("ckpt", "trace", "trace.json"):
        path = os.path.join(work, part)
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        elif os.path.exists(path):
            os.unlink(path)
    if out["correct"]:
        shutil.rmtree(work, ignore_errors=True)
    return out


def _drive(cell, args, t_start, lines, mark) -> dict:
    traffic = cell["traffic_data"]
    spans = {}
    save_step = traffic["setup_save_step"]
    kill_at = save_step + traffic["kill_steps_after_save"]

    # -- incarnation 0: build, train, save, be killed -----------------------
    first = lines.expect("start", WAIT_S["start"], restart_count=0)
    dev0 = lines.expect("device", WAIT_S["device"])
    peaks = common.check_device(
        dev0["summary"], cell["chips"], args.rehearse)
    losses0 = {}
    while True:
        ev = lines.expect("step", WAIT_S["step"])
        losses0[ev["n"]] = ev["loss"]
        if ev["n"] == kill_at:
            break
    t_kill = time.monotonic()
    os.kill(first["pid"], signal.SIGKILL)
    marks = {}
    _watch_exit(first["pid"], t_kill, marks)
    setup_save = [e for e in lines.seen if e["kind"] == "save"]

    # -- incarnation 1: restore, first step, replay, window -----------------
    second = lines.expect("start", WAIT_S["restored"], restart_count=1)
    spans["agent_restart_s"] = second["t"] - t_kill
    dev1 = lines.expect("device", WAIT_S["device"])
    restored = lines.expect("restored", WAIT_S["restored"])
    losses1 = {}
    ev = lines.expect("step", WAIT_S["step"], first=True)
    resume_s, resume_note = common.less_parts(
        "RESUME", ev["t"] - t_kill, backend_open_s=dev1["backend_open_s"])
    losses1[ev["n"]] = ev["loss"]
    while ev["n"] < kill_at:
        ev = lines.expect("step", WAIT_S["step"])
        losses1[ev["n"]] = ev["loss"]
    opened = lines.expect("window_open", WAIT_S["window_open"])
    setup_s, setup_note = common.less_parts(
        "SETUP_S", opened["t"] - t_start,
        backend_open_s_0=dev0["backend_open_s"],
        backend_open_s_1=dev1["backend_open_s"])
    # while the window runs: nobody but the worker may hold the chip
    others = [p.pid for p in _marked_pids(mark) if p.pid != second["pid"]]
    holders = [pid for pid in others if _holds_device(pid)]
    worker_holds = _holds_device(second["pid"]) or args.rehearse
    res = lines.expect("result", args.seconds + 240)

    for name, pattern in RESTART_MARKS.items():
        t = lines.first_time(pattern, after=t_kill)
        marks[name] = None if t is None else t - t_kill
    if marks["persisting"] is not None and marks["stopped"] is not None:
        spans["persist_s"] = marks["stopped"] - marks["persisting"]
    spans.update(res["spans"])
    spans["kill_to_step_s"] = resume_s
    spans["device_open_s"] = dev1["device_open_s"]
    spans["backend_open_s"] = dev1["backend_open_s"]
    if setup_save:
        spans["first_save_s"] = setup_save[0]["stall_s"]

    steps, step_s = res["steps"], res["spans"]["step_s"]
    tokens = steps * res["tokens_per_step"]
    # tracing slows the host: the rate a traced run's readers get is taken
    # over the steps outside the trace, by each step's own seconds
    untraced = [s for i, s in enumerate(step_s)
                if i not in res["traced_steps"]]
    replayed = sorted(set(losses0) & set(losses1))
    replay_rel = max(
        (abs(losses0[n] - losses1[n]) / abs(losses0[n]) for n in replayed),
        default=float("inf"))
    bad = sum(not math.isfinite(x) for x in res["losses"])
    checks = {
        "restored step equals the saved step":
            restored["step"] == save_step,
        f"replayed losses agree within {REPLAY_REL_TOL:g}":
            len(replayed) == traffic["kill_steps_after_save"]
            and replay_rel <= REPLAY_REL_TOL,
        "every loss finite": bad == 0,
        "the resumed worker saved to memory after the window":
            math.isfinite(res["save_stall_s"])
            and res["engine_stall_ms_last"] > 0,
        "no compilation inside the window": res["compiles_in_window"] == 0,
        "same device in both incarnations":
            dev0["summary"] == dev1["summary"],
        "launcher and agent never held the device": not holders,
        "the worker holds the device": bool(worker_holds),
    }
    notes = [
        f"DEVICE {dev1['summary']}",
        f"PROGRAM {res['program']} memory {res['memory']}",
        f"SETUP first save (first touch) {setup_save[:1]}",
        setup_note,
        f"{resume_note} agent_restart_s="
        f"{spans['agent_restart_s']:.3f} persist_s="
        f"{spans.get('persist_s')} device_open_s="
        f"{spans['device_open_s']:.3f} build_s={spans['build_s']:.3f} "
        f"restore_s={spans['restore_s']:.3f}",
        "RESTART seconds after the kill: " + " ".join(
            f"{k}={round(v, 3) if isinstance(v, float) else v}"
            for k, v in list(marks.items()))
        + f" first_line={spans['agent_restart_s']:.3f}",
        f"REPLAY steps {replayed} worst relative loss difference "
        f"{replay_rel:.3g}: first {[losses0[n] for n in replayed]} "
        f"second {[losses1[n] for n in replayed]}",
        f"WINDOW steps={steps} tokens={tokens} "
        f"seconds={res['window_s']:.3f} "
        f"median_step_s={statistics.median(step_s):.4f} "
        # a host stall shows as one long step; the rate counts it
        f"max_step_s={max(step_s):.4f} at_step={step_s.index(max(step_s))} "
        f"compiles_in_window={res['compiles_in_window']} "
        f"loss_first={res['losses'][0]:.4f} loss_last={res['losses'][-1]:.4f}",
        # after the window, on the resumed worker; no metric reads it (one
        # save in eight takes 7-8 s for 5.2-5.8 on a shared host)
        f"SAVE after the window: stall_s={res['save_stall_s']:.3f} "
        f"engine_stall_ms_last={res['engine_stall_ms_last']:.1f} "
        f"engine_staged_mbps_last={res.get('engine_staged_mbps_last')}",
    ]
    trace = {}
    trace_path = os.path.join(common.WORK_DIR, cell["name"], "trace.json")
    if os.path.exists(trace_path):
        with open(trace_path) as f:
            raw = json.load(f)
        if args.dump_trace:
            shutil.copy(trace_path, args.dump_trace)
            pb = trace_reduce.newest_xplane(os.path.join(
                common.WORK_DIR, cell["name"], "trace"))
            if pb:
                shutil.copy(pb, args.dump_trace + ".xplane.pb")
        trace = trace_reduce.reduce_trace(raw)
    device = dict(dev1["summary"],
                  memory_peak_bytes=res["memory_peak_bytes"])
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    counters = {
        "cell": cell, "chips": cell["chips"], "peaks": peaks,
        "steps": steps, "tokens_per_step": res["tokens_per_step"],
        "traced_steps": len(res["traced_steps"]),
        "tokens_per_s": res["tokens_per_step"] * len(untraced)
        / sum(untraced),
        "memory_peak_bytes": res["memory_peak_bytes"],
        "compiles_in_window": res["compiles_in_window"],
        "compiled_memory": res["memory"],
    }
    return {
        "checks": checks,
        "attempted": steps + 2,  # the window's steps, the kill, the save
        "failed": bad,
        "end_to_end": {"train_tokens_per_s": tokens / res["window_s"],
                       "setup_s": setup_s},
        "spans": spans, "trace": trace, "counters": counters,
        "device": device, "notes": notes,
    }
