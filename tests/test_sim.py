"""Wind-tunnel unit gates (ISSUE 18).

The simulator's own laws, pinned at tier-1 speed: the scheduler's
FIFO tie-break and clock advance, the trace oracle's purity (same
config => same trace, query by query), SimRole's drain countdown, and
— the point of the whole exercise — double-run byte-identity plus a
scripted small-fleet scenario whose outcome through the REAL
``GatewayCore``/``CellSpillRouter`` objects is computed by hand and
must match exactly (the fidelity smoke: if the sim can't reproduce a
scenario small enough to verify by eye, its 10,000-node numbers mean
nothing).
"""

import json
import logging

import pytest

from dlrover_tpu.fleet.role import RoleSpec
from dlrover_tpu.sim import (
    CellPlaneSim,
    FleetStormSim,
    OfflineTierSim,
    SimRole,
    SimScheduler,
    StormSpec,
    TraceConfig,
    TraceGenerator,
    VirtualClock,
    run_global_rows,
)

pytestmark = pytest.mark.sim

logging.getLogger("dlrover_tpu").setLevel(logging.WARNING)


# ---------------------------------------------------------------------------
# clock + scheduler
# ---------------------------------------------------------------------------


class TestScheduler:
    def test_ties_pop_in_insertion_order(self):
        clock = VirtualClock()
        sched = SimScheduler(clock)
        for kind in ("a", "b", "c"):
            sched.push(5.0, kind)
        sched.push(1.0, "first")
        kinds = []
        while True:
            ev = sched.pop()
            if ev is None:
                break
            kinds.append(ev[2])
        assert kinds == ["first", "a", "b", "c"]

    def test_pop_advances_the_injected_clock(self):
        clock = VirtualClock()
        sched = SimScheduler(clock)
        sched.push(3.5, "x")
        sched.pop()
        assert clock() == 3.5

    def test_push_into_the_past_clamps_to_now(self):
        """A late timer fires immediately — it never rewrites
        history (the clock stays monotonic)."""
        clock = VirtualClock()
        sched = SimScheduler(clock)
        sched.push(10.0, "later")
        sched.pop()
        sched.push(2.0, "late-timer")
        ev = sched.pop()
        assert ev[0] == 10.0 and clock() == 10.0


# ---------------------------------------------------------------------------
# the trace oracle
# ---------------------------------------------------------------------------


class TestTraceGenerator:
    CFG = TraceConfig(
        seed=7, n_cells=4, nodes=64, duration_s=300.0, step_s=30.0,
        base_rps=40.0, storms=(
            StormSpec(kind="blackout", at_s=60.0, duration_s=60.0,
                      cells=(0, 2)),
            StormSpec(kind="net_gray", at_s=90.0, duration_s=30.0,
                      cells=(1,), severity=0.5),
        ),
    )

    def test_same_config_same_trace_query_by_query(self):
        a, b = TraceGenerator(self.CFG), TraceGenerator(self.CFG)
        for step in range(self.CFG.n_steps):
            assert a.arrivals(step) == b.arrivals(step)
            assert a.churn_leaves(step, 1) == b.churn_leaves(step, 1)
            assert [a.home_of(step, n) for n in range(20)] \
                == [b.home_of(step, n) for n in range(20)]

    def test_different_seed_different_trace(self):
        import dataclasses

        other = TraceGenerator(
            dataclasses.replace(self.CFG, seed=8))
        mine = TraceGenerator(self.CFG)
        assert any(mine.arrivals(s) != other.arrivals(s)
                   for s in range(self.CFG.n_steps))

    def test_storm_windows_half_open(self):
        gen = TraceGenerator(self.CFG)
        assert gen.dead_cells(59.9) == ()
        assert gen.dead_cells(60.0) == (0, 2)
        assert gen.dead_cells(119.9) == (0, 2)
        assert gen.dead_cells(120.0) == ()
        assert [s.kind for s in gen.storms_at(95.0)] \
            == ["blackout", "net_gray"]

    def test_gray_duplicates_are_a_seeded_coin(self):
        gen = TraceGenerator(self.CFG)
        flips = [gen.gray_duplicates(3, 1, n, 0.5)
                 for n in range(64)]
        assert flips == [gen.gray_duplicates(3, 1, n, 0.5)
                         for n in range(64)]
        assert 0 < sum(flips) < 64

    def test_hot_cell_carries_the_zipf_head(self):
        gen = TraceGenerator(self.CFG)
        assert gen.share(0) > gen.share(1) > gen.share(3)


# ---------------------------------------------------------------------------
# SimRole
# ---------------------------------------------------------------------------


class TestSimRole:
    def test_drain_is_a_countdown(self):
        role = SimRole(RoleSpec("srv", desired=3, min_count=1),
                       prefix="c0/srv", drain_passes=2)
        assert role.count == 3
        victim = role.begin_drain()
        assert victim is not None and role.count == 2
        assert role.drain_pending()
        role.pump_drain()
        assert role.drain_pending()      # one pass left
        role.pump_drain()
        assert not role.drain_pending()  # gone for good
        assert role.drained == 1

    def test_fail_is_abrupt_and_bounded(self):
        role = SimRole(RoleSpec("srv", desired=2), prefix="x")
        assert role.fail(5) == 2 and role.count == 0

    def test_reconcile_respawns_failed_members(self):
        role = SimRole(RoleSpec("trn", desired=4), prefix="c1/trn")
        role.fail(2)
        role.reconcile()
        assert role.count == 4 and role.spawned == 2


# ---------------------------------------------------------------------------
# cell-plane rig
# ---------------------------------------------------------------------------


class TestCellPlaneSim:
    def test_floored_throughput_matches_the_analytic_rate(self):
        """One cell, saturating load: the serialized per-cell floor is
        the bottleneck, so ops/s must land at 1000/(floor+overhead)."""
        row = CellPlaneSim(
            n_cells=1, floor_ms=2.0, offered_rps=800.0, clients=8,
            duration_s=2.0, warmup_s=0.5, overhead_ms=0.5,
        ).run()
        assert abs(row["ops_per_s"] - 400.0) / 400.0 < 0.1, row

    def test_double_run_byte_identical(self):
        def once():
            return json.dumps(CellPlaneSim(
                n_cells=2, floor_ms=3.0, offered_rps=500.0, clients=4,
                duration_s=1.0, warmup_s=0.25, overhead_ms=1.0,
            ).run(), sort_keys=True)

        assert once() == once()


    @pytest.mark.parametrize("cells", [1, 2, 4])
    def test_every_op_is_counted_at_its_owning_cell(self, cells):
        """Offered past the one-cell ceiling: every offered op
        completes (the rig drains), none errs, and each is counted at
        exactly one cell, every cell of the ring owning some."""
        row = CellPlaneSim(
            n_cells=cells, floor_ms=3.0, offered_rps=1200.0, clients=8,
            duration_s=1.0, warmup_s=0.25, overhead_ms=1.0,
        ).run()
        assert row["errors"] == 0
        assert row["completed"] == 1200 * 1.25 + 1  # t = 0 .. 1.25 s
        assert len(row["per_cell"]) == cells
        assert sum(row["per_cell"].values()) == row["completed"]
        assert all(v > 0 for v in row["per_cell"].values())


# ---------------------------------------------------------------------------
# micro rig: the fidelity smoke
# ---------------------------------------------------------------------------

#: A scripted small fleet: 2 cells, 40 uniform arrivals over 2s
#: alternating home cells, blackout of the hot cell at t=1.0.
_OPTS = {
    "cells": 2, "replicas": 1, "slots": 4, "queue_cap": 64,
    "deadline_s": 5.0, "slo_ms": 500.0, "service_ms": 10.0,
    "gw_service_us": 200.0, "duration_s": 2.0, "blackout_frac": 0.5,
    "move_delay_s": 0.25, "prompt_tokens": 4, "mnt": 4,
    "poll_interval": 0.005,
}
_TIMES = [round(i * 0.05, 2) for i in range(40)]
_HOMES = [i % 2 for i in range(40)]


class TestGlobalServeSimFidelitySmoke:
    def test_scripted_blackout_outcome_matches_hand_count(self):
        """The REAL GatewayCore/CellSpillRouter objects, a trace small
        enough to count by hand: static partitioning must lose exactly
        the post-blackout arrivals homed at the dead cell; the global
        data plane must lose none and complete strictly more."""
        rows = run_global_rows(_OPTS, _TIMES, _HOMES,
                               overhead_ms=0.0, shapes=[True])
        by_mode = {r["mode"]: r for r in rows}
        expected_lost = sum(
            1 for t, h in zip(_TIMES, _HOMES) if t >= 1.0 and h == 0)
        assert expected_lost == 10  # the scenario IS hand-countable
        static, spill = by_mode["static"], by_mode["spillover"]
        assert static["blackout_lost"] == expected_lost
        assert spill["blackout_lost"] == 0
        assert spill["completed"] > static["completed"]
        assert spill["moved_replicas"] == _OPTS["replicas"]
        for row in rows:
            assert row["conservation_ok"] is True, row["mode"]
            assert row["arrivals"] == 40

    def test_double_run_rows_byte_identical(self):
        def once():
            rows = run_global_rows(_OPTS, _TIMES, _HOMES,
                                   overhead_ms=0.8,
                                   shapes=[False, True])
            return json.dumps(rows, sort_keys=True).encode()

        assert once() == once()


# ---------------------------------------------------------------------------
# macro rig: the storm
# ---------------------------------------------------------------------------

_STORM_CFG = TraceConfig(
    seed=3, n_cells=4, nodes=400, duration_s=600.0, step_s=30.0,
    base_rps=120.0, diurnal_amp=0.4, diurnal_period_s=600.0,
    zipf_a=0.6, storms=(
        StormSpec(kind="blackout", at_s=120.0, duration_s=180.0,
                  cells=(0, 1)),
        StormSpec(kind="net_gray", at_s=330.0, duration_s=90.0,
                  cells=(0,), severity=0.2, delay_steps=1),
        StormSpec(kind="churn", at_s=450.0, duration_s=60.0,
                  cells=(2,), severity=0.3),
    ),
)


class TestFleetStormSim:
    def test_double_run_event_log_digest_identical(self):
        a = FleetStormSim(_STORM_CFG, mode="global").run()
        b = FleetStormSim(_STORM_CFG, mode="global").run()
        assert a["event_log_sha256"] == b["event_log_sha256"]
        assert a["event_log_lines"] == b["event_log_lines"] > 0

    def test_conservation_and_global_beats_static(self):
        static = FleetStormSim(_STORM_CFG, mode="static").run()
        glob = FleetStormSim(_STORM_CFG, mode="global").run()
        for row in (static, glob):
            assert row["conservation_ok"] is True, row["mode"]
            assert row["offered"] == row["served"] + row["timeout"] \
                + row["blackout_lost"] + row["stranded"] \
                + row["backlog_final"] + row["in_transit_final"]
        # Static loses every arrival homed at a dead cell; the global
        # plane re-homes them over the surviving ring members.  (This
        # storm kills HALF the fleet, so re-homed load saturates the
        # survivors — the SLO-goodput verdict belongs to the 24-cell
        # bench; what must hold at ANY scale is survival itself.)
        assert static["blackout_lost"] > 0
        assert glob["blackout_lost"] == 0
        assert glob["rehomed"] == static["blackout_lost"]
        assert glob["served"] > static["served"]
        assert glob["storm_lost"] < static["storm_lost"]


# ---------------------------------------------------------------------------
# macro rig: the offline tier (ISSUE 20)
# ---------------------------------------------------------------------------


class TestOfflineTierSim:
    def test_double_run_event_log_digest_identical(self):
        a = OfflineTierSim(_STORM_CFG, mode="offline").run()
        b = OfflineTierSim(_STORM_CFG, mode="offline").run()
        assert a["event_log_sha256"] == b["event_log_sha256"]
        assert a["event_log_lines"] == b["event_log_lines"] > 0

    def test_tier_soaks_trough_without_slo_regression(self):
        base = OfflineTierSim(_STORM_CFG, mode="baseline").run()
        off = OfflineTierSim(_STORM_CFG, mode="offline").run()
        # The priority-class laws, end to end over the storm trace:
        # batch work soaks the trough, utilization strictly rises,
        # the online SLO plane never pays for it (the online plant is
        # trace-pure and identical in both modes), reclaims stay
        # within the one-round bound, blackout evacuation is total,
        # and no chunk is ever lost or double-counted.
        assert off["slo_goodput"] >= base["slo_goodput"]
        assert off["utilization"] > base["utilization"]
        assert off["chunks_done"] > 0
        assert off["chunks_done_trough"] > 0
        assert off["max_reclaim_rounds"] <= 1
        assert off["chunk_conservation_ok"] is True
        assert off["evacuations_ok"] is True
        assert off["overcommit_steps"] == 0
        # Request conservation (inequality: the end-of-run online
        # backlog stays inside the plant and is not exported).
        for row in (base, off):
            assert row["served"] + row["timeout"] \
                + row["blackout_lost"] <= row["offered"]
