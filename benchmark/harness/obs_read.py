"""Reads the program's own flight recorder (``dlrover_tpu/obs``): the spans
it records inside save, restore, persist, restart, bootstrap and build, and
the scope table of its compiled step.  JAX-free.

Where the records are:

- a cell that runs in this process (steady training): the process
  recorder's ring;
- the elastic cell: the journal files its launcher gave the job without
  being asked — ``<tmp>/dlrover_tpu_obs/bench-<this pid>-<run id>/
  flight-<process>-<pid>.jsonl``, one per process (``agent-n0``,
  ``worker-r0-i0``, ``worker-r0-i1``), each line written as its span
  ended, so that they are whole although every process was SIGKILLed.
  A traced run's directory is removed when this process exits (the
  readers run after the runner has returned), an untraced run's by the
  runner itself.

A program that records no such span (the parent of the PR that added them)
yields no records, and every reader built on this returns None.
"""

from __future__ import annotations

import atexit
import glob
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, Iterable, List, Optional

_removed_at_exit = set()


def _root() -> str:
    return os.path.join(tempfile.gettempdir(), "dlrover_tpu_obs")


def job_dirs() -> List[str]:
    """The journal directories of the job this process launched."""
    job = f"bench-{os.getpid()}"
    # the separator keeps bench-12 from matching bench-123's
    return [d for d in glob.glob(os.path.join(_root(), job + "*"))
            if os.path.basename(d) == job
            or os.path.basename(d).startswith(job + "-")]


def remove_job_dirs() -> None:
    """What a run that nothing will read (an untraced one) calls once its
    job has ended."""
    for d in job_dirs():
        _remove(d, _root())


def _remove(path: str, root: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    try:
        os.rmdir(root)  # only when nothing else is in it
    except OSError:
        pass


def records(spans: dict) -> List[dict]:
    """Every span and event the program recorded in this run, each with
    the recording process under ``_proc``; nothing where the runner handed
    over no stamps at all (no run happened)."""
    if not spans:
        return []
    dirs = job_dirs()
    for d in dirs:  # read now, by every reader that asks; removed at exit
        if d not in _removed_at_exit:
            _removed_at_exit.add(d)
            atexit.register(_remove, d, _root())
    if dirs:
        # the program's own reader of its files: a line cut by the kill
        # is skipped, the last meta line names the process
        from dlrover_tpu.obs.collect import load_dir

        return [dict(rec, _proc=str(dump["meta"].get("process", "")))
                for d in dirs for dump in load_dir(d)
                for rec in dump["events"]]
    recorder = sys.modules.get("dlrover_tpu.obs.recorder")
    if recorder is None:
        return []
    ring, _, _ = recorder.get_recorder().snapshot()
    return [dict(rec, _proc="") for rec in ring]


# -- picking spans ----------------------------------------------------------


def incarnation(rec: dict) -> Optional[int]:
    """The worker incarnation (the agent's restart count at its start) a
    record comes from, None for any other process."""
    m = re.fullmatch(r"worker-r\d+-i(\d+)", rec.get("_proc", ""))
    return int(m.group(1)) if m else None


def last_incarnation(recs: Iterable[dict]) -> List[dict]:
    """The records of the newest worker incarnation where the job ran
    under the launcher (the resumed worker: the one the kill-to-step clock
    waits for), else all of them (one process, no launcher)."""
    recs = list(recs)
    newest = max((i for i in map(incarnation, recs) if i is not None),
                 default=None)
    return recs if newest is None else [
        r for r in recs if incarnation(r) == newest]


def named(recs: Iterable[dict], name: str) -> List[dict]:
    return sorted((r for r in recs
                   if r.get("k") == "span" and r.get("name") == name),
                  key=lambda r: r["ts"])


def children(recs: Iterable[dict], parent: dict,
             name: str = "") -> List[dict]:
    return [r for r in recs
            if r.get("k") == "span" and r.get("psid") == parent["sid"]
            and r.get("_proc") == parent.get("_proc")
            and (not name or r.get("name") == name)]


def descendants(recs: List[dict], parent: dict, name: str) -> List[dict]:
    out, frontier = [], [parent]
    while frontier:
        kids = [k for p in frontier for k in children(recs, p)]
        out += [k for k in kids if k.get("name") == name]
        frontier = kids
    return out


def seconds(spans_: Iterable[dict]) -> Optional[float]:
    """Summed duration in seconds; None of no span."""
    spans_ = list(spans_)
    return sum(s["dur"] for s in spans_) * 1e-6 if spans_ else None


def child_seconds(recs: List[dict], parents: List[dict],
                  *names: str) -> Optional[float]:
    """Seconds of the first parent's children of these names."""
    if not parents:
        return None
    return seconds(k for n in names for k in children(recs, parents[0], n))


# -- the compiled step's scope table against a device trace -----------------


def program_tables(recs: List[dict], trace: dict,
                   nested: bool = False) -> Optional[dict]:
    """The newest ``accelerate.program`` event that journals a scope table
    (with ``nested``: and ``subscopes``), where the reduced trace has
    operations to join to it; None otherwise."""
    if not trace or not trace.get("op_self_s") or not trace.get("busy_s"):
        return None
    programs = [r for r in last_incarnation(recs)
                if r.get("kind") == "accelerate.program" and r.get("scopes")
                and (r.get("subscopes") or not nested)]
    return programs[-1] if programs else None


def placed_ops(trace: dict):
    """``(instruction, label, seconds)`` of every device operation of a
    reduced trace, by the name the program's scope table knows it by: an
    XLA instruction's own, and for a Mosaic kernel each CALLING instruction
    (``trace["kernel_call_s"]``: ``gather_sum.16`` runs under
    ``moe_combine``, ``gather_sum.24`` under ``moe_permute``), never the
    kernel's name, which is the same under every scope.  ``label`` is the
    key of ``op_self_s``: the kernel's name for a kernel."""
    calls = trace.get("kernel_call_s") or {}
    for label, secs in trace["op_self_s"].items():
        if label in calls:
            for name, call_secs in calls[label].items():
                yield name, label, call_secs
        else:
            yield label.split(" ", 1)[0], label, secs


def scope_shares(recs: List[dict], trace: dict) -> Optional[dict]:
    """Device self time by ``(phase, scope)`` as shares of busy time: the
    trace's operations (:func:`placed_ops`) joined to the ``scopes`` table
    the program journals once with its ``accelerate.program`` event.
    ``unphased`` is what the table does not name; ``unplaced_kernel_s``
    (``{kernel: seconds}``) is the part of it that is Mosaic kernels, by
    kernel name, for a reader that knows whose kernel it is."""
    program = program_tables(recs, trace)
    if program is None:
        return None
    table = program["scopes"]
    kernels = trace.get("kernel_s") or {}
    by: Dict[tuple, float] = {}
    unplaced: Dict[str, float] = {}
    for name, label, secs in placed_ops(trace):
        verdict = table.get(name)
        key = tuple(verdict) if verdict else ("", "unphased")
        by[key] = by.get(key, 0.0) + secs
        if verdict is None and label in kernels:
            unplaced[label] = unplaced.get(label, 0.0) + secs
    busy = trace["busy_s"]
    return {"by": {k: 100.0 * v / busy for k, v in by.items()},
            "unphased_pct": 100.0 * by.get(("", "unphased"), 0.0) / busy,
            "unplaced_kernel_s": unplaced}


def print_scope_shares(shares: dict) -> None:
    rows = sorted(shares["by"].items(), key=lambda kv: -kv[1])
    print("SCOPES pct_of_busy " + " ".join(
        f"{phase or '-'}/{scope}={pct:.2f}" for (phase, scope), pct in rows)
        + f" unphased_pct={shares['unphased_pct']:.3f}"
        + " unplaced_kernels=" + (",".join(
            f"{k}:{v:.4f}s" for k, v in sorted(
                shares["unplaced_kernel_s"].items())) or "none"), flush=True)
