"""Global data plane (ISSUE 17) — tier-1, sub-second, no jax.

Cross-cell spillover units (policy, router, hop accounting, terminal
adoption), the GlobalClient's whole-cell failover, the
``merge_global_snapshots`` dedupe law, the ``cell.blackout`` chaos
site on the gateway tier, and the flagship e2e: a whole-cell blackout
lands mid-stream across two in-process cells and every admitted
request still completes exactly once via spillover, with resubmits
answered byte-identical from whichever cell owns the terminal and the
traces JOINING across the hop.
"""

import os
import threading

import pytest

from dlrover_tpu import chaos, obs
from dlrover_tpu.common import messages as wire
from dlrover_tpu.obs import postmortem
from dlrover_tpu.serving import (
    CellSpillRouter,
    GatewayConfig,
    GatewayCore,
    GlobalClient,
    LocalKv,
    LoopbackTransport,
    ReplicaRunner,
    ServeRegistry,
    SpilloverConfig,
    SpilloverPolicy,
    TierClient,
    TierReplicaLink,
    merge_global_snapshots,
    merge_snapshots,
)
from dlrover_tpu.serving.tier import GatewayTierNode
from dlrover_tpu.sim import run_global_rows

from test_serving import (  # noqa: I100 - shared fleet fixtures
    FakeClock,
    FakeDecodeServer,
    core_handle,
    expected_tokens,
    wait_for,
)

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _no_ambient_chaos():
    chaos.reset()
    yield
    chaos.reset()


def _sub(rid, prompt=(1, 2), max_new=4, **kw):
    return wire.ServeSubmit(req_id=rid, prompt=list(prompt),
                            max_new_tokens=max_new, **kw)


# ---------------------------------------------------------------------------
# SpilloverPolicy: the pure forward/stay decision
# ---------------------------------------------------------------------------


class TestSpilloverPolicy:
    def make(self, **cfg):
        clock = FakeClock()
        return SpilloverPolicy(SpilloverConfig(**cfg), clock=clock), \
            clock

    def test_local_headroom_stays_local(self):
        pol, _ = self.make()
        d = pol.decide({"pressure": 0.4}, {"B": {"alive": True}})
        assert not d.forward and d.reason == "local-headroom"

    def test_saturated_forwards_to_least_loaded_sibling(self):
        pol, _ = self.make()
        d = pol.decide(
            {"pressure": 1.0},
            {"B": {"alive": True, "pressure": 0.5},
             "C": {"alive": True, "pressure": 0.2}},
        )
        assert d.forward and d.target == "C"
        assert d.reason == "saturated"

    def test_draining_cell_forwards_even_with_headroom(self):
        pol, _ = self.make()
        d = pol.decide({"pressure": 0.0, "draining": True},
                       {"B": {"alive": True}})
        assert d.forward and d.target == "B"
        assert d.reason == "draining"

    def test_hop_budget_bounds_forward_depth(self):
        pol, _ = self.make(max_hops=1)
        d = pol.decide({"pressure": 1.0}, {"B": {"alive": True}},
                       hops=1)
        assert not d.forward and d.reason == "hop-budget"

    def test_dead_and_hot_siblings_are_skipped(self):
        pol, _ = self.make(sibling_headroom=0.85)
        d = pol.decide(
            {"pressure": 1.0},
            {"B": {"alive": False, "pressure": 0.0},
             "C": {"alive": True, "pressure": 0.9}},
        )
        assert not d.forward and d.reason == "no-sibling-headroom"

    def test_failure_cooldown_expires_on_the_injected_clock(self):
        pol, clock = self.make(failure_cooldown_s=5.0)
        siblings = {"B": {"alive": True, "pressure": 0.0}}
        pol.note_failure("B")
        assert not pol.decide({"pressure": 1.0}, siblings).forward
        clock.advance(5.1)
        d = pol.decide({"pressure": 1.0}, siblings)
        assert d.forward and d.target == "B"

    def test_deterministic_tiebreak_by_cell_id(self):
        pol, _ = self.make()
        siblings = {"C": {"alive": True, "pressure": 0.3},
                    "B": {"alive": True, "pressure": 0.3}}
        assert pol.decide({"pressure": 1.0}, siblings).target == "B"

    def test_pressure_derived_from_in_flight_over_cap(self):
        pol, _ = self.make()
        hot = {"B": {"alive": True, "in_flight": 60, "queue_cap": 64}}
        cool = {"B": {"alive": True, "in_flight": 8, "queue_cap": 64}}
        assert not pol.decide({"pressure": 1.0}, hot).forward
        assert pol.decide({"pressure": 1.0}, cool).forward


# ---------------------------------------------------------------------------
# CellSpillRouter: the hop itself + the accounting law (satellite 4)
# ---------------------------------------------------------------------------


class _RouterTransport:
    """Loopback sibling transport: routes raw admission messages to
    the other cell's router — what ``TierClient.call`` does over the
    wire."""

    def __init__(self, router):
        self._router = router
        self.dead = False

    def call(self, msg, **_kw):
        if self.dead:
            raise RuntimeError("sibling cell is dead")
        if isinstance(msg, wire.ServeSubmit):
            return self._router.submit(msg)
        if isinstance(msg, wire.ServeStatusRequest):
            return self._router.status(msg.req_id)
        raise TypeError(type(msg).__name__)


def _router_pair(cap_a=1, cap_b=64):
    core_a = GatewayCore(GatewayConfig(queue_cap=cap_a))
    core_b = GatewayCore(GatewayConfig(queue_cap=cap_b))
    sib_a, sib_b = {}, {}
    ra = CellSpillRouter("A", core_a, sib_a)
    rb = CellSpillRouter("B", core_b, sib_b)
    sib_a["B"] = _RouterTransport(rb)
    sib_b["A"] = _RouterTransport(ra)
    return core_a, core_b, ra, rb


def _complete_all(core, rid_tokens, replica="r0", slots=8):
    core.register(replica, slots)
    grants = core.poll(replica, slots, []).requests
    for g in grants:
        core.complete(replica, g.req_id, rid_tokens[g.req_id])
    return grants


class TestCellSpillRouter:
    def test_forward_on_full_queue_counts_the_hop_once_each_side(self):
        core_a, core_b, ra, _rb = _router_pair(cap_a=1)
        assert ra.submit(_sub("q0")).status == "accepted"
        ack = ra.submit(_sub("q1"))
        assert ack.status == "accepted"
        a, b = core_a.counters, core_b.counters
        # Origin: the client arrived here twice; one admission was
        # forwarded, never locally queued.
        assert a["submitted"] == 2
        assert a["accepted"] == 1
        assert a["spill_forwarded"] == 1
        assert core_a.stats_snapshot()["in_flight"] == 1
        # Sibling: one submit, marked as hop ingress.
        assert b["submitted"] == 1
        assert b["spill_ingress"] == 1
        assert b["accepted"] == 1
        assert ra.spilled_count == 1

    def test_merge_global_snapshots_dedupes_the_hop(self):
        core_a, core_b, ra, _rb = _router_pair(cap_a=1)
        ra.submit(_sub("q0"))
        ra.submit(_sub("q1"))
        merged = merge_global_snapshots({
            "A": merge_snapshots([core_a.stats_snapshot()]),
            "B": merge_snapshots([core_b.stats_snapshot()]),
        })
        # Raw sum counts the forwarded request twice; unique does not.
        assert merged["counters"]["submitted"] == 3
        assert merged["spill_ingress"] == 1
        assert merged["submitted_unique"] == 2  # == client calls
        assert merged["spill_forwarded"] == 1
        assert merged["in_flight"] == 2
        assert merged["cells_alive"] == 2

    def test_origin_adopts_terminal_and_answers_byte_identical(self):
        core_a, core_b, ra, _rb = _router_pair(cap_a=1)
        ra.submit(_sub("q0"))
        ra.submit(_sub("q1"))
        _complete_all(core_b, {"q1": [7, 8, 9]})
        reply = ra.status("q1")
        assert reply.state == "done" and reply.tokens == [7, 8, 9]
        assert core_a.counters["spill_adopted"] == 1
        assert ra.spilled_count == 0
        # Resubmit at the ORIGIN: its own dedupe cache answers now,
        # byte-identical, without touching the sibling.
        ack = ra.submit(_sub("q1"))
        assert ack.status == "done" and ack.tokens == [7, 8, 9]
        assert core_a.counters["dedupe_hits"] == 1
        # Adoption is bookkeeping, not completion: the origin's own
        # completion counters (and windowed latency stats, which only
        # record at local completion) never saw the forwarded request.
        assert core_a.counters["completed"] == 0

    def test_retried_submit_stays_with_the_owning_sibling(self):
        core_a, core_b, ra, _rb = _router_pair(cap_a=1)
        ra.submit(_sub("q0"))
        ra.submit(_sub("q1"))
        ack = ra.submit(_sub("q1"))  # client retry before terminal
        assert ack.status == "accepted"
        # The retry re-forwarded to B (which absorbed it as a
        # duplicate) instead of double-admitting anywhere.
        assert core_b.counters["submitted"] == 2
        assert core_b.counters["spill_ingress"] == 2
        assert core_b.stats_snapshot()["in_flight"] == 1
        assert core_a.stats_snapshot()["in_flight"] == 1

    def test_hop_budget_rebuffs_instead_of_ping_pong(self):
        core_a, core_b, ra, rb = _router_pair(cap_a=1, cap_b=1)
        ra.submit(_sub("q0"))
        rb.submit(_sub("p0"))
        ack = ra.submit(_sub("q1"))  # both cells saturated
        assert ack.status == "rejected"
        # B rebuffed the hop (hop-marked reject) and A answered with
        # its own honest backpressure -- no infinite forward loop.
        assert core_b.counters["spill_rebuffed"] == 1
        assert core_a.counters["rejected"] == 1
        assert core_b.counters["rejected"] == 1

    def test_dead_sibling_falls_back_to_local_reject(self):
        core_a, _core_b, ra, _rb = _router_pair(cap_a=1)
        ra._siblings["B"].dead = True
        ra.submit(_sub("q0"))
        ack = ra.submit(_sub("q1"))
        assert ack.status == "rejected"
        assert core_a.counters["spill_forwarded"] == 0
        # The transport failure cooled B down in the policy.
        assert "B" in ra._policy._failed_at

    def test_draining_cell_sheds_fresh_admissions(self):
        core_a, core_b, ra, _rb = _router_pair(cap_a=64)
        ra.set_draining(True)
        ack = ra.submit(_sub("q0"))
        assert ack.status == "accepted"
        assert core_a.counters["spill_forwarded"] == 1
        assert core_b.counters["spill_ingress"] == 1
        assert core_a.stats_snapshot()["in_flight"] == 0


class TestAdoptTerminal:
    def test_adopt_rules(self):
        core = GatewayCore(GatewayConfig())
        assert core.adopt_terminal("x", "running", [1]) == "ignored"
        assert core.adopt_terminal("x", "done", [1, 2]) == "adopted"
        assert core.adopt_terminal("x", "done", [1, 2]) == "duplicate"
        assert core.counters["spill_adopted"] == 1
        reply = core.status("x")
        assert reply.state == "done" and reply.tokens == [1, 2]


# ---------------------------------------------------------------------------
# GlobalClient: home-cell routing + whole-cell failover
# ---------------------------------------------------------------------------


class _ScriptedCell:
    """TierClient-shaped fake: records submits, serves scripted
    status replies, optionally dead."""

    def __init__(self, state="done", tokens=(5,)):
        self.state = state
        self.tokens = list(tokens)
        self.dead = False
        self.submits = []

    def submit(self, req_id, prompt, max_new_tokens, deadline_s=0.0,
               submit_timeout=10.0):
        if self.dead:
            raise RuntimeError("cell is dead")
        self.submits.append(req_id)
        return wire.ServeAck(req_id=req_id, status="accepted")

    def status(self, req_id):
        if self.dead:
            raise RuntimeError("cell is dead")
        return wire.ServeStatusReply(req_id=req_id, state=self.state,
                                     tokens=self.tokens)


class TestGlobalClient:
    def test_home_cell_is_deterministic_and_spreads(self):
        gc = GlobalClient({"A": _ScriptedCell(), "B": _ScriptedCell()})
        homes = {f"r{i}": gc.home_cell(f"r{i}") for i in range(100)}
        gc2 = GlobalClient({"B": _ScriptedCell(),
                            "A": _ScriptedCell()})
        assert all(gc2.home_cell(r) == h for r, h in homes.items())
        assert set(homes.values()) == {"A", "B"}

    def test_whole_cell_failover_resubmits_same_req_id(self):
        a, b = _ScriptedCell(), _ScriptedCell()
        alive = {"A", "B"}
        gc = GlobalClient({"A": a, "B": b},
                          alive_fn=lambda: set(alive),
                          poll_interval=0.001)
        rid = next(r for r in (f"r{i}" for i in range(200))
                   if gc.home_cell(r) == "A")
        assert gc.submit(rid, [1], 4).status == "accepted"
        assert a.submits == [rid]
        a.dead = True
        alive.discard("A")
        reply = gc.result(rid, timeout=5.0)
        assert reply.state == "done"
        assert b.submits == [rid]  # SAME req_id, resubmitted
        assert gc.cell_failovers == 1


# ---------------------------------------------------------------------------
# cell.blackout chaos site on the gateway tier
# ---------------------------------------------------------------------------


class TestCellBlackoutSite:
    def test_gateway_heartbeat_fires_blackout_for_its_cell(
            self, monkeypatch, tmp_path):
        exits = []
        monkeypatch.setattr(os, "_exit",
                            lambda code: exits.append(code))
        obs.configure(out_dir=str(tmp_path), process="gw-cA-g0")
        chaos.configure("cell.blackout:method=cA")
        node = GatewayTierNode(
            "g0", ServeRegistry(LocalKv(), job="j"),
            heartbeat_s=0.005, cell_id="cA",
        )
        node.start()
        try:
            assert wait_for(lambda: exits, timeout=5.0)
        finally:
            node.stop(0.0)
        assert exits[0] == chaos.EXIT_CELL_BLACKOUT == 86
        # The pre-exit hook spilled the flight recorder: the
        # postmortem reconstructs the incident and NAMES the site.
        report = postmortem.analyze(str(tmp_path))
        assert "cell.blackout" in report["chaos_sites"]
        assert "gw-cA-g0" in report["crashed"]

    def test_gateway_without_cell_never_fires_blackout(
            self, monkeypatch):
        exits = []
        monkeypatch.setattr(os, "_exit",
                            lambda code: exits.append(code))
        chaos.configure("cell.blackout:method=cA")
        node = GatewayTierNode(
            "g0", ServeRegistry(LocalKv(), job="j"),
            heartbeat_s=0.005,
        )
        node.start()
        try:
            import time as _time

            _time.sleep(0.05)
        finally:
            node.stop(0.0)
        assert exits == []


# ---------------------------------------------------------------------------
# Flagship e2e: blackout mid-stream, exactly-once via spillover
# ---------------------------------------------------------------------------


class _Cell:
    """One in-process cell: a bare-core gateway behind the spill
    router, its own registry, an optional replica — the two-cell
    composition the real tier runs as processes."""

    def __init__(self, cell_id, queue_cap=64, lease_s=5.0):
        self.cell_id = cell_id
        self.kv = LocalKv()
        self.registry = ServeRegistry(self.kv, job=f"cell-{cell_id}",
                                      lease_s=lease_s)
        self.core = GatewayCore(GatewayConfig(queue_cap=queue_cap))
        self.siblings = {}
        self.router = CellSpillRouter(cell_id, self.core,
                                      self.siblings)
        self.addr_map = {
            f"addr-{cell_id}": LoopbackTransport(self._handle())
        }
        self.gid = f"{cell_id}-g0"
        self.registry.announce_gateway(self.gid, f"addr-{cell_id}")
        self.dead = False

    def _handle(self):
        base = core_handle(self.core)

        def handle(msg):
            if isinstance(msg, wire.ServeSubmit):
                return self.router.submit(msg)
            if isinstance(msg, wire.ServeStatusRequest):
                return self.router.status(msg.req_id)
            return base(msg)

        return handle

    def connect(self, addr):
        cell = self

        class _Proxy:
            def call(_self, msg, **kw):
                if cell.dead:
                    raise RuntimeError(
                        f"cell {cell.cell_id} is blacked out"
                    )
                return cell.addr_map[addr].call(msg, **kw)

        return _Proxy()

    def client(self, **kw):
        kw.setdefault("poll_interval", 0.002)
        kw.setdefault("refresh_s", 0.0)
        return TierClient(self.registry, connect=self.connect, **kw)

    def start_replica(self, rid, server=None):
        link = TierReplicaLink(self.registry, rid,
                               connect=self.connect, refresh_s=0.0)
        runner = ReplicaRunner(
            server or FakeDecodeServer(slots=8), link, rid,
            poll_interval=0.001, kv_p2p=False,
        )
        th = threading.Thread(target=runner.run, daemon=True)
        th.start()
        return runner, th

    def blackout(self):
        """The whole cell dies as one event: every transport errors,
        the registry entries are gone (the lease aged out)."""
        self.dead = True
        self.registry.remove_gateway(self.gid)

    def snapshot(self):
        return merge_snapshots([self.core.stats_snapshot()])


class TestCellBlackoutE2E:
    def test_blackout_mid_stream_completes_exactly_once(self):
        rec = obs.configure(process="global-e2e")
        a, b = _Cell("A", queue_cap=2), _Cell("B", queue_cap=64)
        a.siblings["B"] = b.client()
        b.siblings["A"] = a.client()
        runner_b, th_b = b.start_replica("rB")
        alive = {"A", "B"}
        gc = GlobalClient({"A": a.client(), "B": b.client()},
                          alive_fn=lambda: set(alive),
                          poll_interval=0.002)
        rids = [r for r in (f"blk{i}" for i in range(400))
                if gc.home_cell(r) == "A"][:6]
        assert len(rids) == 6
        # Cell A has NO replica yet: its 2 admissions sit queued, so
        # submits 3..6 deterministically spill A -> B mid-stream.
        for rid in rids:
            assert gc.submit(rid, [5, 6], 4).status == "accepted"
        assert a.core.counters["submitted"] == 6
        assert a.core.counters["accepted"] == 2
        assert a.core.counters["spill_forwarded"] == 4
        assert b.core.counters["spill_ingress"] == 4
        spilled = [r for r in rids if a.router._spilled.get(r)]
        stuck = [r for r in rids if r not in spilled]
        assert len(spilled) == 4 and len(stuck) == 2
        # B completes the spilled four while A is still "alive".
        assert wait_for(
            lambda: b.core.counters["completed"] == 4, timeout=10
        )
        # Origin answers one spilled request BEFORE the blackout:
        # terminal adopted A-side, resubmit byte-identical from A.
        want = expected_tokens([5, 6], 4)
        reply = gc.result(spilled[0], timeout=10)
        assert reply.state == "done" and reply.tokens == want
        assert a.core.counters["spill_adopted"] == 1
        ack = gc.submit(spilled[0], [5, 6], 4)
        assert ack.status == "done" and ack.tokens == want
        # ---- the blackout lands mid-stream: A dies whole, with two
        # admitted requests still queued inside it.
        a.blackout()
        alive.discard("A")
        for rid in rids:
            reply = gc.result(rid, timeout=15)
            assert reply.state == "done", (rid, reply)
            assert reply.tokens == want  # byte-identical everywhere
        # The two stuck in dead A were resubmitted (same req_id) to B.
        assert gc.cell_failovers >= len(stuck)
        # Exactly once: every request decoded ONCE, all at B (A's
        # replica never existed; dead A cannot answer).
        assert wait_for(lambda: runner_b.served == 6, timeout=10)
        assert b.core.counters["completed"] == 6
        # Resubmits after the blackout answer from the SURVIVOR's
        # dedupe cache, byte-identical.
        before = b.core.counters["dedupe_hits"]
        ack = gc.submit(spilled[1], [5, 6], 4, submit_timeout=0.3)
        assert ack.status == "done" and ack.tokens == want
        assert b.core.counters["dedupe_hits"] == before + 1
        # The hop accounting law holds across the blackout: every
        # client call counted exactly once globally.
        merged = merge_global_snapshots(
            {"A": a.snapshot(), "B": b.snapshot()}
        )
        assert merged["submitted_unique"] == \
            merged["counters"]["submitted"] - merged["spill_ingress"]
        assert merged["spill_forwarded"] >= 4
        # Traces JOIN across the cell hop: one trace id (derived from
        # the req_id) holds the origin's forward span AND the
        # sibling's terminal span; the failover rids carry the
        # client's cross-cell resubmit span in the same trace.
        events, _, _ = rec.snapshot()
        spans = [e for e in events if e.get("k") == "span"]

        def names_of(rid):
            tid = obs.trace_id_for(rid)
            return {e["name"] for e in spans if e.get("tid") == tid}

        joined = names_of(spilled[1])
        assert "gw.spill_forward" in joined
        assert "gw.request" in joined
        failed_over = names_of(stuck[0])
        assert "client.cell_failover" in failed_over
        assert "gw.request" in failed_over
        b.core.drain("rB")
        th_b.join(timeout=5)


# ---------------------------------------------------------------------------
# Conservation across the hop, over a whole saturating trace
# ---------------------------------------------------------------------------

#: Two cells of one 4-slot replica behind a 6-deep queue, 500 arrivals
#: in a second, three quarters of them homed at cell 0: the hot cell
#: is over its cap from the first tenth of a second, so the spillover
#: router forwards; ``blackout`` kills cell 0 halfway.  The rig is the
#: wind tunnel's micro rig: the REAL GatewayCore / CellSpillRouter /
#: merge_global_snapshots on a virtual clock, no thread and no sleep.
_HOP_OPTS = {
    "cells": 2, "replicas": 1, "slots": 4, "queue_cap": 6,
    "deadline_s": 5.0, "slo_ms": 500.0, "service_ms": 10.0,
    "gw_service_us": 200.0, "duration_s": 1.0, "blackout_frac": 0.5,
    "move_delay_s": 0.25, "prompt_tokens": 4, "mnt": 4,
    "poll_interval": 0.005,
}
_HOP_TIMES = [round(i * 0.002, 3) for i in range(500)]
_HOP_HOMES = [0 if i % 4 else 1 for i in range(500)]


class TestHopConservation:
    @pytest.fixture(scope="class")
    def rows(self):
        rows = run_global_rows(_HOP_OPTS, _HOP_TIMES, _HOP_HOMES,
                               overhead_ms=0.0, shapes=[False, True])
        return {(r["mode"], r["blackout"]): r for r in rows}

    @pytest.mark.parametrize("blackout", [False, True])
    @pytest.mark.parametrize("mode", ["static", "spillover"])
    def test_every_arrival_and_every_hop_is_accounted(
        self, rows, mode, blackout
    ):
        row = rows[(mode, blackout)]
        assert row["arrivals"] == len(_HOP_TIMES)
        # an arrival is submitted once (the hop deduped), shed on the
        # wire, or lost to the dead cell
        assert row["arrivals"] == row["submitted_unique"] \
            + row["wire_dropped"] + row["blackout_lost"] \
            + row["blackout_dropped"]
        assert row["submitted_unique"] == row["accepted"] \
            + row["rejected"]
        # an accepted request ends, or is counted stranded in the dead
        # cell's core
        assert row["accepted"] == row["completed"] + row["timeout"] \
            + row["failed"] + row["stranded"]
        # a forward lands at a sibling or is rebuffed by its hop budget
        assert row["spill_forwarded"] == row["spill_ingress"] \
            + row["spill_rebuffed"]
        assert row["rejected"] > 0  # the trace IS over the hot cell's cap
        if mode == "static":
            assert row["spill_forwarded"] == 0
            assert (row["blackout_lost"] > 0) == blackout
        else:
            assert row["spill_forwarded"] > 0
            assert row["blackout_lost"] == 0
            assert row["moved_replicas"] == (
                _HOP_OPTS["replicas"] if blackout else 0)
