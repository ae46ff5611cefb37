"""The control-plane rig: the cell bench's shard physics in virtual time.

:class:`CellPlaneSim` replays ``--cell_bench``'s open-loop row: a
uniform arrival stream at ``offered_rps``, a shared FIFO the client
workers pull in order, and each op routed to its key's owning cell by
the REAL ``cells.cell.cell_for_node`` consistent hash — so the
hot/cold split over the 256-key space is byte-for-byte the production
ring's, not a modeled approximation.

Each cell's journaled mutation path is a serialized resource (the
PR-13 append lock): an op holds its worker from pull to completion
and holds the owning cell for ``floor_ms + overhead_ms`` — the
modeled durable-log floor plus one calibrated constant for the
request path around it (gRPC hop, handler, commit bookkeeping).  The
calibration point was one 1-cell floored row of a CPU run of the
process tree; every other row is a prediction.  The convoy
effect such a run shows — workers FIFO-blocked behind the hot
cell starve the cold cells — emerges from the same structure here, it
is not programmed in.

No randomness anywhere: arrivals are uniform (the bench's arrival
loop is deterministic), routing is the consistent hash, service is
constant — a double run is byte-identical by construction, and the
determinism test pins it anyway.
"""

from __future__ import annotations

import heapq
from typing import Any, Dict, List

from dlrover_tpu.cells.cell import cell_for_node


class CellPlaneSim:
    """One cell-bench row in virtual time."""

    def __init__(self, n_cells: int, floor_ms: float,
                 offered_rps: float, clients: int,
                 duration_s: float, warmup_s: float,
                 overhead_ms: float, n_keys: int = 256):
        self.n_cells = int(n_cells)
        self.floor_ms = float(floor_ms)
        self.offered_rps = float(offered_rps)
        self.clients = int(clients)
        self.duration_s = float(duration_s)
        self.warmup_s = float(warmup_s)
        self.overhead_ms = float(overhead_ms)
        self.n_keys = int(n_keys)

    def run(self) -> Dict[str, Any]:
        cids = [f"cell{i}" for i in range(self.n_cells)]
        owner = {k: cell_for_node(k, cids)
                 for k in range(self.n_keys)}
        svc = (self.floor_ms + self.overhead_ms) / 1e3
        period = 1.0 / max(1.0, self.offered_rps)
        horizon = self.warmup_s + self.duration_s
        # Worker pool as a min-heap of (free_at, worker_id): the next
        # op goes to the earliest-free worker — the shared-FIFO pull.
        workers: List = [(0.0, w) for w in range(self.clients)]
        heapq.heapify(workers)
        cell_free = {c: 0.0 for c in cids}
        per_cell = {c: 0 for c in cids}
        completed = 0
        measured = 0
        i = 0
        at = 0.0
        while at < horizon:
            free_at, w = heapq.heappop(workers)
            cid = owner[i % self.n_keys]
            start = max(at, free_at, cell_free[cid])
            done = start + svc
            cell_free[cid] = done
            heapq.heappush(workers, (done, w))
            completed += 1
            per_cell[cid] += 1
            if self.warmup_s <= done < horizon:
                measured += 1
            i += 1
            at += period
        return {
            "cells": self.n_cells,
            "floor_ms": self.floor_ms,
            "offered_rps": round(self.offered_rps, 1),
            "ops_per_s": round(measured / self.duration_s, 1),
            "completed": completed,
            "errors": 0,
            "clients": self.clients,
            "duration_s": round(self.duration_s, 2),
            "per_cell": per_cell,
        }


def run_cell_rows(cell_counts, floor_ms: float, rate_mult: float,
                  clients: int, duration_s: float, warmup_s: float,
                  overhead_ms: float) -> List[Dict[str, Any]]:
    """The bench's row grid: for each cell count, a floored row and a
    floor_ms=0 honesty row, offered at ``rate_mult`` x the 1-cell
    floor ceiling (the bench's exact load rule)."""
    ceiling = 1000.0 / max(floor_ms, 1e-9)
    offered = ceiling * rate_mult
    rows = []
    for n in cell_counts:
        for f in (floor_ms, 0.0):
            rows.append(CellPlaneSim(
                n, f, offered, clients, duration_s, warmup_s,
                overhead_ms=overhead_ms,
            ).run())
    return rows
