"""Crash postmortem CLI: reconstruct a killed fleet's last seconds.

::

    python -m dlrover_tpu.obs.postmortem DUMP_DIR [--out trace.json]

Reads every per-process flight-recorder dump under ``DUMP_DIR`` and
answers the three questions an operator asks after a kill:

- **who died** — each process's dump reason (clean exit / SIGTERM /
  chaos crash, naming the injected site) and its last recorded instant;
- **what it held** — requests a dead process had in flight (spans in
  its ring with no terminal of its own) and its final journal events;
- **where work went** — traces whose spans appear in more than one
  process's dump, with the process that recorded the effective
  terminal (the failover/replay destination).

For a training job's journal directory it also answers **what each
restart cost** (:func:`restart_accounts`): from the moment the agent
could first have known of the failure to the new incarnation's first
step, part by part across both processes, and the seconds no span names.

``--out`` additionally writes the merged Perfetto-loadable chrome
trace (:func:`dlrover_tpu.obs.collect.build_chrome_trace`).
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict, Iterable, List, Optional, Tuple

from dlrover_tpu.obs.collect import (
    load_dir,
    spans_by_trace,
    validate_trace,
    write_chrome_trace,
)


def _fmt_ts(us: float) -> str:
    return f"{us / 1e6:.3f}s"


# -- what a restart cost ------------------------------------------------------

#: results of ``agent.monitor`` that the agent answers with a restart
_RESTARTING = ("failed", "membership_changed", "restart_requested")
#: the new worker's named parts, in the order they run
_WORKER_PARTS = ("bootstrap.init", "bootstrap.backend_init",
                 "accelerate.build", "accelerate.create_state",
                 "ckpt.load", "accelerate.first_call")


def _end(rec: Dict[str, Any]) -> float:
    return float(rec["ts"]) + float(rec.get("dur", 0.0))


def _gaps(intervals: Iterable[Tuple[float, float]],
          lo: float, hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers, in order."""
    gaps, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            gaps.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        gaps.append((reach, hi))
    return gaps


def _interpreter(events: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """``bootstrap.process_start`` counted as a span: the event's
    ``[ts - since_process_start_s, ts]``, the new interpreter's start and
    the imports before ``bootstrap.init``."""
    for e in events:
        if e.get("kind") == "bootstrap.process_start":
            dur = float(e.get("since_process_start_s", 0.0)) * 1e6
            return {"ts": float(e["ts"]) - dur, "dur": dur,
                    "psid": e.get("psid", "")}
    return None


def restart_accounts(dumps: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One account a restart, oldest first: for each ``agent.monitor``
    span that ended ``failed`` / ``membership_changed`` /
    ``restart_requested`` and was followed by an ``agent.restart``, the
    interval from that span's end less its ``unseen_s`` (the first moment
    the agent could have known) to the end of the new incarnation's
    ``accelerate.first_call`` (its last span, where it made no such
    call).  The new incarnation is the worker whose
    ``bootstrap.process_start`` names the restart's
    ``agent.start_workers`` as ``psid``; of several, the last to finish.

    ``parts`` holds the seconds of each named part inside the interval
    (they may nest or overlap by milliseconds: no sum is meant) and
    ``unspanned_s`` the interval less the UNION of every span the agent
    and that worker recorded inside it, the interpreter's start among
    them (and not the agent's next watch, which only waits): what the
    program still cannot name — the user script's own code between the
    spans; ``holes`` are its three longest stretches, each ``[seconds
    into the interval, seconds, the span that ended last before it]``."""
    accounts = []
    for agent in dumps:
        spans = sorted((e for e in agent["events"] if e.get("k") == "span"),
                       key=lambda e: e["ts"])
        watches = [s for s in spans if s.get("name") == "agent.monitor"]
        for restart in spans:
            if restart.get("name") != "agent.restart":
                continue
            # the restart starts where its watch ended (0.2 us: the
            # rounding of two stamps)
            before = [w for w in watches if _end(w) <= restart["ts"] + 0.2]
            if not before or (before[-1].get("args") or {}).get(
                    "result") not in _RESTARTING:
                continue
            spawns = {s["sid"] for s in spans
                      if s.get("name") == "agent.start_workers"
                      and s.get("psid") == restart["sid"]}
            account = _account(agent, spans, before[-1], restart, [
                d for d in dumps if d is not agent
                and (_interpreter(d["events"]) or {}).get("psid") in spawns])
            if account is not None:
                accounts.append(account)
    return sorted(accounts, key=lambda a: a["start_us"])


def _account(agent, agent_spans, watch, restart, workers):
    def done(dump) -> float:
        spans = [e for e in dump["events"] if e.get("k") == "span"]
        first = [s for s in spans
                 if s.get("name") == "accelerate.first_call"]
        return _end(first[0]) if first else max(
            map(_end, spans), default=0.0)

    if not workers:
        return None
    worker = max(workers, key=done)
    unseen_us = float((watch.get("args") or {}).get("unseen_s", 0.0)) * 1e6
    lo, hi = _end(watch) - unseen_us, done(worker)
    if hi <= lo:
        return None
    start = dict(_interpreter(worker["events"]), name="interpreter")
    worker_spans = [e for e in worker["events"] if e.get("k") == "span"]

    def within(spans, name=""):
        """The spans (of this name) that reach into the interval."""
        return [s for s in spans if (not name or s.get("name") == name)
                and _end(s) > lo and float(s["ts"]) < hi]

    def clipped(spans):
        return [(max(float(s["ts"]), lo), min(_end(s), hi)) for s in spans]

    def secs(spans) -> float:
        return round(sum(e - s for s, e in clipped(spans)) * 1e-6, 6)

    persist = secs(within(agent_spans, "ckpt.persist"))
    parts = {
        "unseen": round(unseen_us * 1e-6, 6),
        "ckpt.persist": persist,
        # stop + rendezvous + spawn: the restart less the persist in it
        "agent.restart": round(secs(within([restart])) - persist, 6),
        "interpreter": secs(within([start])),
    }
    for name in _WORKER_PARTS:
        parts[name] = secs(within(worker_spans, name))
    # the watch on the NEW workers spans all that follows their start and
    # names none of it: of the agent's watches only the one that saw the
    # failure counts
    named = within([s for s in agent_spans
                    if s.get("name") != "agent.monitor" or s is watch]
                   + worker_spans + [start])
    gaps = _gaps(clipped(named), lo, hi)

    def hole(gap):
        """``[seconds into the interval, seconds, the span that ended last
        before it]``."""
        before = [s for s in named if _end(s) <= gap[0] + 0.2]
        return [round((gap[0] - lo) * 1e-6, 6),
                round((gap[1] - gap[0]) * 1e-6, 6),
                max(before, key=_end)["name"] if before else ""]

    return {
        "agent": str(agent["meta"].get("process", "")),
        "worker": str(worker["meta"].get("process", "")),
        "result": (watch.get("args") or {}).get("result", ""),
        "start_us": lo, "interval_s": round((hi - lo) * 1e-6, 6),
        "parts": parts,
        "unspanned_s": round(sum(e - s for s, e in gaps) * 1e-6, 6),
        # the three longest stretches under no span
        "holes": sorted(map(hole, sorted(
            gaps, key=lambda g: g[0] - g[1])[:3])),
    }


def analyze(dump_dir: str) -> Dict[str, Any]:
    """The postmortem as data (the CLI renders it; tests assert on it)."""
    dumps = load_dir(dump_dir)
    traces = spans_by_trace(dumps)
    processes: List[Dict[str, Any]] = []
    for dump in dumps:
        meta = dump["meta"]
        evs = dump["events"]
        last_ts = max(
            (float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
             for e in evs), default=0.0,
        )
        proc = {
            "process": str(meta.get("process", "")),
            "pid": int(meta.get("pid", 0)),
            "reason": str(meta.get("reason", "")),
            "chaos_site": str(meta.get("chaos_site", "")),
            "events": len(evs),
            "dropped": int(meta.get("dropped", 0)),
            "last_ts_us": last_ts,
            "journal_tail": [
                {k: v for k, v in e.items() if k not in ("k", "seq")}
                for e in evs if e.get("k") == "ev"
            ][-5:],
        }
        # In-flight at death: traces this process touched but never
        # CLOSED from its own point of view.  Closure is role-shaped:
        # a gateway closes with the terminal span, a replica with the
        # decode-completion or a journal replay (a replica never
        # records terminals, so "no terminal" alone would damn every
        # request it ever finished).
        held = []
        closed = set()
        touched = {}
        for e in evs:
            if e.get("k") != "span" or not e.get("tid"):
                continue
            args = e.get("args") or {}
            rid = args.get("rid") or args.get("req_id") or ""
            touched.setdefault(e["tid"], rid)
            if args.get("terminal") or e.get("name") in (
                "rep.decode", "rep.journal_replay", "rep.kv_export",
            ):
                closed.add(e["tid"])
        for tid_key, rid in touched.items():
            if tid_key not in closed:
                held.append(rid or tid_key)
        proc["held_in_flight"] = sorted(held)
        processes.append(proc)
    # Where orphaned work went: traces spanning >1 process.
    rerouted = []
    for tid_key, spans in traces.items():
        procs = sorted({s.get("_proc", "") for s in spans})
        if len(procs) < 2:
            continue
        rep = validate_trace(spans)
        rid = next(
            (str((s.get("args") or {}).get("rid") or "")
             for s in spans if (s.get("args") or {}).get("rid")), "",
        )
        rerouted.append({
            "trace_id": tid_key,
            "req_id": rid,
            "processes": procs,
            "terminal_process": rep.get("terminal_process", ""),
            "state": rep.get("state", ""),
            "superseded_terminals": rep.get("superseded_terminals", 0),
        })
    rerouted.sort(key=lambda r: r["trace_id"])
    crashed = [p for p in processes if p["reason"] == "chaos"]
    return {
        "dump_dir": dump_dir,
        "processes": processes,
        "crashed": [p["process"] for p in crashed],
        "chaos_sites": sorted(
            {p["chaos_site"] for p in crashed if p["chaos_site"]}
        ),
        "traces": len(traces),
        "rerouted": rerouted,
        "restarts": restart_accounts(dumps),
    }


def render(report: Dict[str, Any]) -> str:
    lines = [f"fleet postmortem: {report['dump_dir']}"]
    lines.append(
        f"  {len(report['processes'])} process dump(s), "
        f"{report['traces']} trace(s)"
    )
    lines.append("who died:")
    for proc in report["processes"]:
        tag = proc["reason"]
        if proc["chaos_site"]:
            tag += f" [{proc['chaos_site']}]"
        lines.append(
            f"  {proc['process']:<16} pid={proc['pid']:<7} "
            f"reason={tag:<28} events={proc['events']} "
            f"dropped={proc['dropped']} "
            f"last={_fmt_ts(proc['last_ts_us'])}"
        )
        if proc["reason"] == "chaos":
            held = proc["held_in_flight"]
            lines.append(
                f"    held in flight at death: "
                f"{', '.join(held) if held else '(nothing)'}"
            )
            for ev in proc["journal_tail"]:
                lines.append(f"    last journal: {json.dumps(ev)}")
    if report.get("restarts"):
        lines.append("what each restart cost (seconds):")
        for r in report["restarts"]:
            lines.append(
                f"  {r['agent']} -> {r['worker']} ({r['result']}): "
                f"{r['interval_s']:.3f} to the first step = "
                # "unseen" is a bound: the agent's sleep may hold the death
                + " + ".join(
                    f"{'unseen <=' if name == 'unseen' else name} {s:.3f}"
                    for name, s in r["parts"].items() if s)
                + f"; unspanned {r['unspanned_s']:.3f}" + "".join(
                    f", {secs:.3f} after {after or 'the start'}"
                    for _, secs, after in r["holes"])
            )
    if report["rerouted"]:
        lines.append("requests that crossed processes:")
        for r in report["rerouted"]:
            extra = (
                f" ({r['superseded_terminals']} superseded terminal)"
                if r["superseded_terminals"] else ""
            )
            lines.append(
                f"  {r['req_id'] or r['trace_id']:<12} "
                f"{' -> '.join(r['processes'])} "
                f"finished at {r['terminal_process'] or '?'} "
                f"state={r['state']}{extra}"
            )
    else:
        lines.append("no request crossed processes")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m dlrover_tpu.obs.postmortem",
        description="reconstruct a killed fleet's last seconds from "
                    "flight-recorder dumps",
    )
    ap.add_argument("dump_dir", help="directory of flight-*.jsonl dumps")
    ap.add_argument("--out", default="",
                    help="also write the merged chrome trace here")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    args = ap.parse_args(argv)
    report = analyze(args.dump_dir)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render(report))
    if args.out:
        write_chrome_trace(args.dump_dir, args.out)
        print(f"merged chrome trace: {args.out}")
    return 0 if report["processes"] else 1


if __name__ == "__main__":  # pragma: no cover - thin CLI shell
    raise SystemExit(main())
