"""The request-exact micro rig: the global-serve bench in virtual time.

:class:`GlobalServeSim` re-runs the ``--global_bench`` scenario —
real :class:`~dlrover_tpu.serving.gateway.GatewayCore` admission, real
:class:`~dlrover_tpu.serving.spillover.CellSpillRouter` +
:class:`SpilloverPolicy` forwarding, real
``merge_global_snapshots`` accounting — with every thread, socket and
sleep of the bench replaced by scheduler events over one
:class:`VirtualClock`.  The arrival trace is an *input* (the caller
replays a recorded seeded trace, or synthesizes one
from :mod:`sim.rand`), so a comparison against a run of the real
process tree is apples to apples: identical
arrivals, identical policy code, only the transport physics modeled.

The physics model, calibrated once against such a run on a CPU:

* each cell's gateway is a serialized server with a per-message floor
  (``gw_service_us``, the bench's ``_PacedPipeline`` budget) — submits
  and completion reports occupy it, polls are treated as free;
* each replica is the bench's ``_StubDecodeServer`` loop: poll with
  full ``slots``, serve the granted batch serially at ``service_ms``
  plus ``overhead_ms`` (the calibration constant standing in for
  completion-RPC turnaround and host scheduling), poll again;
* blackout kills the hot cell exactly like the bench: its gateway
  answers nothing (casts on the wire drop), its replicas stop
  un-drained (in-core work stays in ``_by_id`` and is counted
  stranded), and in spillover mode the driver re-homes later arrivals
  and lands the dead cell's chips at the survivor ``move_delay_s``
  later.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from dlrover_tpu.common.messages import ServeSubmit
from dlrover_tpu.serving.gateway import GatewayConfig, GatewayCore
from dlrover_tpu.serving.spillover import (
    CellSpillRouter,
    SpilloverPolicy,
    merge_global_snapshots,
)
from dlrover_tpu.serving import merge_snapshots

from .clock import VirtualClock
from .events import SimScheduler


class _SimCellTransport:
    """The inter-cell hop: a direct call into the sibling cell's
    admission dispatch at the same virtual instant (the bench charges
    the hop to the origin's budget; its cost here is the origin's
    pipeline slot already consumed by the submit)."""

    def __init__(self, sim: "GlobalServeSim", cell_id: str):
        self._sim = sim
        self._cell = cell_id

    def call(self, msg, **_kw):
        if self._cell in self._sim.dead_cells:
            raise ConnectionError("cell blacked out")
        return self._sim.dispatch_submit(self._cell, msg)


class GlobalServeSim:
    """One bench row in virtual time.  ``opts`` uses the global
    bench's exact knob names; ``times``/``homes`` are the replayed
    arrival trace (seconds, home-cell indices)."""

    def __init__(self, opts: Dict[str, Any], mode: str, blackout: bool,
                 times: Sequence[float], homes: Sequence[int],
                 overhead_ms: float = 0.0):
        self.opts = dict(opts)
        self.mode = mode
        self.blackout = blackout
        self.times = list(times)
        self.homes = list(homes)
        self.overhead_s = float(overhead_ms) / 1e3
        self.clock = VirtualClock(0.0)
        self.sched = SimScheduler(self.clock)
        n_cells = int(opts["cells"])
        self.cell_ids = [f"c{i}" for i in range(n_cells)]
        self.dead_cells: Dict[str, bool] = {}
        self.cores: Dict[str, GatewayCore] = {}
        self.routers: Dict[str, CellSpillRouter] = {}
        self.in_slo = {cid: 0 for cid in self.cell_ids}
        self.blackout_lost = 0
        self.blackout_dropped = 0
        self.moved = 0
        self._pipe_free = {cid: 0.0 for cid in self.cell_ids}
        self._casts_in_flight = {cid: 0 for cid in self.cell_ids}
        self._arrived = 0
        self._last_activity = 0.0
        self._service_s = opts["service_ms"] / 1e3
        self._floor_s = opts["gw_service_us"] / 1e6
        self._stopped_replicas: Dict[str, bool] = {}
        self._batch: Dict[str, List] = {}
        self._last_poll: Dict[str, float] = {}
        self._cell_replicas: Dict[str, List[str]] = {
            cid: [] for cid in self.cell_ids
        }
        self._build_cells()

    # -- construction ------------------------------------------------------

    def _build_cells(self) -> None:
        opts = self.opts
        for cid in self.cell_ids:
            core = GatewayCore(
                GatewayConfig(
                    queue_cap=int(opts["queue_cap"]),
                    default_deadline_s=float(opts["deadline_s"]),
                ),
                clock=self.clock,
            )
            orig = core.observe_latency_ms

            def lat_obs(v, _o=orig, _c=cid):
                if _o is not None:
                    _o(v)
                if v <= opts["slo_ms"]:
                    self.in_slo[_c] += 1

            core.observe_latency_ms = lat_obs
            self.cores[cid] = core
        if self.mode == "spillover":
            for cid in self.cell_ids:
                sibs = {c: _SimCellTransport(self, c)
                        for c in self.cell_ids if c != cid}

                def view(_sibs=sibs):
                    return {
                        c: dict(self.cores[c].pressure(),
                                alive=c not in self.dead_cells)
                        for c in _sibs
                    }

                self.routers[cid] = CellSpillRouter(
                    cid, self.cores[cid], sibs,
                    policy=SpilloverPolicy(clock=self.clock),
                    view_fn=view, clock=self.clock,
                )
        for cid in self.cell_ids:
            for i in range(int(opts["replicas"])):
                self._start_replica(cid, f"{cid}-r{i}")

    def _start_replica(self, cid: str, rid: str) -> None:
        self.cores[cid].register(rid, int(self.opts["slots"]))
        self._cell_replicas[cid].append(rid)
        self.sched.push(self.clock(), "round", (cid, rid))

    # -- admission dispatch (shared with the sibling transport) ------------

    def dispatch_submit(self, cid: str, msg: ServeSubmit):
        router = self.routers.get(cid)
        if router is not None:
            return router.submit(msg)
        return self.cores[cid].submit(
            msg.req_id, msg.prompt, msg.max_new_tokens,
            msg.deadline_s, msg.prefix_len, msg.prefix_fp, msg.trace,
            spill_hops=msg.spill_hops,
        )

    # -- event handlers ----------------------------------------------------

    def _on_arrive(self, i: int) -> None:
        opts = self.opts
        at = self.times[i]
        hot = self.cell_ids[0]
        blackout_at = (opts["duration_s"] * opts["blackout_frac"]
                       if self.blackout else float("inf"))
        move_at = blackout_at + opts["move_delay_s"]
        if at >= blackout_at and hot not in self.dead_cells:
            self._kill_cell(hot)
        if (self.mode == "spillover" and self.blackout
                and self.moved == 0 and at >= move_at):
            survivor = next(c for c in self.cell_ids
                            if c not in self.dead_cells)
            for j in range(int(opts["replicas"])):
                self._start_replica(survivor, f"moved-r{j}")
                self.moved += 1
        cid = self.cell_ids[self.homes[i]]
        if cid in self.dead_cells:
            if self.mode == "static":
                self.blackout_lost += 1
                self._arrived += 1
                return
            cid = next(c for c in self.cell_ids
                       if c not in self.dead_cells)
        # The gateway pipeline: serialized, floored per message.
        t = max(self.clock(), self._pipe_free[cid]) + self._floor_s
        self._pipe_free[cid] = t
        self._casts_in_flight[cid] += 1
        self.sched.push(t, "gw_submit", (i, cid))
        self._arrived += 1

    def _on_gw_submit(self, i: int, cid: str) -> None:
        self._casts_in_flight[cid] -= 1
        if cid in self.dead_cells:
            # The cast was on the wire when the cell went dark.
            self.blackout_dropped += 1
            return
        opts = self.opts
        msg = ServeSubmit(
            req_id=f"{self.mode[0]}{int(self.blackout)}-{i}",
            prompt=list(range(1, int(opts["prompt_tokens"]) + 1)),
            max_new_tokens=int(opts["mnt"]),
            deadline_s=float(opts["deadline_s"]),
        )
        self.dispatch_submit(cid, msg)
        self._last_activity = self.clock()

    def _on_round(self, cid: str, rid: str) -> None:
        if cid in self.dead_cells or self._stopped_replicas.get(rid):
            return
        opts = self.opts
        core = self.cores[cid]
        self._last_poll[rid] = self.clock()
        # Report a paged-KV memory view (ISSUE 19) so the sim's
        # gateway exercises the real pools carry-through: the stub
        # models one token per block — outstanding batch tokens are
        # the blocks held, slot capacity the pool.  A saturated stub
        # (free_blocks == 0) hits the same admission gate a real
        # paged replica does.
        held = sum(t for _r, t in (self._batch.get(rid) or []))
        cap = int(opts["slots"]) * (
            int(opts["prompt_tokens"]) + int(opts["mnt"])
        )
        grants = core.poll(
            rid, free_slots=int(opts["slots"]), active=[],
            stats={
                "kv_occupancy": round(held / cap, 4) if cap else 0.0,
                "free_blocks": max(0, cap - held),
                "total_blocks": cap,
            },
        )
        now = self.clock()
        if not grants.requests:
            if (self._arrived >= len(self.times)
                    and self._casts_in_flight[cid] == 0
                    and core.pressure()["in_flight"] == 0):
                return  # the cell is drained; stop polling
            self.sched.push(now + float(opts["poll_interval"]),
                            "round", (cid, rid))
            return
        # The stub-decode loop: grab the whole granted batch, serve it
        # serially, poll again once it is gone.  Each item is a decode
        # charge followed by the completion report through the floored,
        # serialized gateway pipeline — the loop blocks on the report
        # before starting the next item, so pipeline pressure feeds
        # back into decode throughput exactly like the bench.
        self._batch[rid] = [
            (g.req_id, len(g.prompt) + int(g.max_new_tokens))
            for g in grants.requests
        ]
        self.sched.push(now + self._service_s + self.overhead_s,
                        "finish", (cid, rid))

    def _on_finish(self, cid: str, rid: str) -> None:
        """Decode of the batch head is done: book the completion
        report into the gateway pipeline (serialized, floored)."""
        if cid in self.dead_cells or self._stopped_replicas.get(rid):
            return
        tcomp = max(self.clock(), self._pipe_free[cid]) + self._floor_s
        self._pipe_free[cid] = tcomp
        self.sched.push(tcomp, "complete", (cid, rid))

    def _on_complete(self, cid: str, rid: str) -> None:
        if cid in self.dead_cells or self._stopped_replicas.get(rid):
            return  # in-core work dies with the cell: stranded
        batch = self._batch.get(rid)
        if not batch:
            return
        req_id, n_tok = batch.pop(0)
        self.cores[cid].complete(rid, req_id, [0] * n_tok, ok=True)
        self._last_activity = self.clock()
        now = self.clock()
        if batch:
            self.sched.push(now + self._service_s + self.overhead_s,
                            "finish", (cid, rid))
        else:
            # Batch drained: the loop ticks again, paced to the poll
            # interval like the replica runner.
            nxt = max(now, self._last_poll.get(rid, 0.0)
                      + float(self.opts["poll_interval"]))
            self.sched.push(nxt, "round", (cid, rid))

    def _kill_cell(self, cid: str) -> None:
        """The whole cell goes dark as ONE event (the bench's blackout
        semantics): gateway answers nothing, replicas stop un-drained,
        in-core work strands."""
        self.dead_cells[cid] = True
        for rid in self._cell_replicas[cid]:
            self._stopped_replicas[rid] = True

    # -- run ---------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        for i, at in enumerate(self.times):
            self.sched.push(at, "arrive", i)
        handlers = {
            "arrive": lambda p: self._on_arrive(p),
            "gw_submit": lambda p: self._on_gw_submit(*p),
            "round": lambda p: self._on_round(*p),
            "finish": lambda p: self._on_finish(*p),
            "complete": lambda p: self._on_complete(*p),
        }
        while True:
            ev = self.sched.pop()
            if ev is None:
                break
            handlers[ev[2]](ev[3])
        return self._row()

    def _row(self) -> Dict[str, Any]:
        opts = self.opts
        last_at = self.times[-1] if self.times else 0.0
        elapsed = max(last_at, self._last_activity) + 0.05
        merged = merge_global_snapshots({
            cid: merge_snapshots(
                [self.cores[cid].stats_snapshot()]
            )
            for cid in self.cell_ids
        })
        counters = merged["counters"]
        stranded = merged["in_flight"]
        slo_total = sum(self.in_slo.values())
        arrivals = len(self.times)
        row = {
            "mode": self.mode,
            "blackout": self.blackout,
            "arrivals": arrivals,
            "hot_share": round(
                self.homes.count(0) / max(arrivals, 1), 3
            ),
            "blackout_lost": self.blackout_lost,
            "blackout_dropped": self.blackout_dropped,
            "wire_dropped": 0,
            "submitted_unique": merged["submitted_unique"],
            "spill_forwarded": merged["spill_forwarded"],
            "spill_ingress": merged["spill_ingress"],
            "spill_rebuffed": merged["spill_rebuffed"],
            "spill_adopted": merged["spill_adopted"],
            "accepted": counters.get("accepted", 0),
            "rejected": counters.get("rejected", 0),
            "completed": counters.get("completed", 0),
            "timeout": counters.get("timeout", 0),
            "failed": counters.get("failed", 0),
            "stranded": stranded,
            "completed_in_slo": slo_total,
            "goodput_rps": round(slo_total / max(elapsed, 1e-9), 1),
            "moved_replicas": self.moved,
            "elapsed_s": round(elapsed, 2),
            "cells": {
                c: dict(
                    in_flight=snap["in_flight"],
                    replicas_alive=snap["replicas_alive"],
                    **{k: snap["counters"].get(k, 0)
                       for k in ("submitted", "accepted", "rejected",
                                 "completed", "timeout", "failed",
                                 "spill_forwarded", "spill_ingress",
                                 "spill_rebuffed", "spill_adopted")},
                )
                for c, snap in merged["cells"].items()
            },
            "events": self.sched.popped,
        }
        row["conservation_ok"] = (
            arrivals == row["submitted_unique"] + row["wire_dropped"]
            + row["blackout_lost"] + row["blackout_dropped"]
            and row["accepted"] == row["completed"] + row["timeout"]
            + row["failed"] + row["stranded"]
        )
        _ = opts
        return row


def run_global_rows(opts: Dict[str, Any], times: Sequence[float],
                    homes: Sequence[int], overhead_ms: float,
                    shapes: Optional[List[bool]] = None,
                    ) -> List[Dict[str, Any]]:
    """The bench's row grid (static/spillover x blackout shapes) in
    virtual time; same row order as ``--global_bench``."""
    rows = []
    for blackout in ([False, True] if shapes is None else shapes):
        for mode in ("static", "spillover"):
            sim = GlobalServeSim(opts, mode, blackout, times, homes,
                                 overhead_ms=overhead_ms)
            rows.append(sim.run())
    return rows
