"""Sharded gateway tier + P2P KV handoff units (ISSUE 9) — tier-1,
sub-second, no jax.

Everything runs in-process: gateways are bare ``GatewayCore`` state
machines behind loopback transports, the registry is a ``LocalKv``,
segment servers are stores behind ``kvseg.handle_fetch`` loopbacks.
The real-socket tier (RegistryServer + RpcKv + gateway subprocesses +
``serving.gateway_kill``) rides the ``serving+chaos+slow`` e2e lane in
``test_chaos_e2e.py``.
"""

import collections
import threading
import time

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.agent.metrics import Histogram
from dlrover_tpu.common import messages as wire
from dlrover_tpu.serving import (
    GatewayConfig,
    GatewayCore,
    HashRing,
    KvPullError,
    KvSegmentStore,
    LocalKv,
    LoopbackTransport,
    ReplicaRunner,
    ServeRegistry,
    TierClient,
    TierReplicaLink,
    TierStats,
    merge_snapshots,
    pull_kv_segment,
)
from dlrover_tpu.serving.kvseg import handle_fetch, segment_fingerprint
from dlrover_tpu.serving.tier import ring_hash

from test_serving import (  # noqa: I100 - shared fleet fixtures
    FakeClock,
    FakeDecodeServer,
    FakePrefillServer,
    core_handle,
    expected_tokens,
    wait_for,
)

pytestmark = pytest.mark.serving


def full_handle(core):
    """client + replica dispatch over a bare core — what
    ``Gateway.handle`` does, loopback."""
    base = core_handle(core)

    def handle(msg):
        if isinstance(msg, wire.ServeSubmit):
            return core.submit(msg.req_id, msg.prompt,
                               msg.max_new_tokens, msg.deadline_s,
                               msg.prefix_len, msg.prefix_fp)
        if isinstance(msg, wire.ServeStatusRequest):
            return core.status(msg.req_id)
        if isinstance(msg, wire.ServeFleetStatsRequest):
            return wire.ServeFleetStats(stats=core.stats_snapshot())
        return base(msg)

    return handle


class _Tier:
    """Two (or N) bare-core gateways on a LocalKv registry, loopback
    transports keyed by fake addresses."""

    def __init__(self, n=2, job="j", lease_s=5.0, **core_kw):
        self.kv = LocalKv()
        self.registry = ServeRegistry(self.kv, job=job,
                                      lease_s=lease_s)
        self.cores = {}
        self.addr_map = {}
        for i in range(n):
            gid = f"g{i}"
            core = GatewayCore(GatewayConfig(**core_kw))
            self.cores[gid] = core
            self.addr_map[f"addr-{gid}"] = LoopbackTransport(
                full_handle(core)
            )
            self.registry.announce_gateway(gid, f"addr-{gid}")
        self.ring = HashRing(list(self.cores))

    def connect(self, addr):
        # A proxy resolving through addr_map at CALL time: kill()
        # swaps the entry, so even transports cached before the death
        # start erroring — like a real closed socket.
        class _Proxy:
            def call(_self, msg, **kw):
                return self.addr_map[addr].call(msg, **kw)

        return _Proxy()

    def kill(self, gid):
        """The gateway process dies: registry entry gone, transport
        errors from now on."""
        self.registry.remove_gateway(gid)

        class _Dead:
            def call(self, msg, **kw):
                raise RuntimeError(f"gateway {gid} is dead")

        self.addr_map[f"addr-{gid}"] = _Dead()

    def client(self, **kw):
        kw.setdefault("poll_interval", 0.002)
        kw.setdefault("refresh_s", 0.0)
        return TierClient(self.registry, connect=self.connect, **kw)

    def link(self, rid, **kw):
        kw.setdefault("refresh_s", 0.0)
        return TierReplicaLink(self.registry, rid,
                               connect=self.connect, **kw)

    def start_replica(self, rid, server=None, journal=None, **runner_kw):
        runner_kw.setdefault("poll_interval", 0.001)
        runner_kw.setdefault("kv_p2p", False)
        runner = ReplicaRunner(
            server or FakeDecodeServer(slots=4), self.link(rid), rid,
            journal_path=journal, **runner_kw,
        )
        th = threading.Thread(target=runner.run, daemon=True)
        th.start()
        return runner, th

    def drain_all(self):
        for core in self.cores.values():
            for rid in list(core.stats_snapshot()["replicas"]):
                core.drain(rid)


# ---------------------------------------------------------------------------
# Consistent hashing
# ---------------------------------------------------------------------------


class TestHashRing:
    def test_owner_is_deterministic_and_total(self):
        ring = HashRing(["g0", "g1", "g2"])
        owners = {f"r{i}": ring.owner(f"r{i}") for i in range(200)}
        ring2 = HashRing(["g2", "g0", "g1"])  # order-insensitive
        assert all(ring2.owner(r) == o for r, o in owners.items())
        assert set(owners.values()) == {"g0", "g1", "g2"}

    def test_death_moves_only_the_dead_range(self):
        """Consistent hashing's contract IS the failover semantics:
        removing g1 re-homes exactly g1's requests (the survivors
        adopt its arcs); every other assignment is untouched."""
        before = HashRing(["g0", "g1", "g2"])
        after = HashRing(["g0", "g2"])
        moved = stayed = 0
        for i in range(500):
            rid = f"q{i}"
            b, a = before.owner(rid), after.owner(rid)
            if b == "g1":
                assert a in ("g0", "g2")
                moved += 1
            else:
                assert a == b
                stayed += 1
        assert moved > 0 and stayed > 0

    def test_balance_is_rough_but_real(self):
        ring = HashRing(["g0", "g1"], vnodes=64)
        counts = {"g0": 0, "g1": 0}
        for i in range(2000):
            counts[ring.owner(f"x{i}")] += 1
        assert 0.25 < counts["g0"] / 2000 < 0.75

    def test_empty_ring_owns_nothing(self):
        assert HashRing([]).owner("x") is None

    def test_ring_hash_is_process_stable(self):
        # Pinned value: sha1 is the cross-process contract (a
        # PYTHONHASHSEED-dependent hash would split ownership between
        # a client and a replica of the same tier).
        assert ring_hash("req-0") == int.from_bytes(
            __import__("hashlib").sha1(b"req-0").digest()[:4], "big"
        )


# ---------------------------------------------------------------------------
# Shared registry (satellite: register/re-register/lease/GC/namespacing)
# ---------------------------------------------------------------------------


class TestServeRegistry:
    def make(self, lease_s=10.0):
        clock = FakeClock()
        kv = LocalKv()
        return ServeRegistry(kv, job="jobA", lease_s=lease_s,
                             clock=clock), kv, clock

    def test_announce_visible_immediately_from_any_reader(self):
        reg, kv, clock = self.make()
        reg.announce_gateway("g0", "h:1")
        reg.announce_replica("r0", slots=4, role="prefill",
                             kv_addr="h:9")
        # A SECOND registry handle over the same kv (another gateway
        # process) sees both within one read — "within one poll".
        reader = ServeRegistry(kv, job="jobA", lease_s=10.0,
                               clock=clock)
        assert reader.gateways() == {"g0": "h:1"}
        rep = reader.replicas()["r0"]
        assert rep["slots"] == 4 and rep["role"] == "prefill"
        assert rep["kv_addr"] == "h:9"

    def test_reregister_updates_in_place(self):
        reg, kv, clock = self.make()
        reg.announce_replica("r0", slots=2)
        reg.announce_replica("r0", slots=8, role="decode")
        reps = reg.replicas()
        assert len(reps) == 1
        assert reps["r0"]["slots"] == 8
        assert reps["r0"]["role"] == "decode"

    def test_lease_expiry_hides_then_gc_deletes(self):
        reg, kv, clock = self.make(lease_s=5.0)
        reg.announce_gateway("g0", "h:1")
        reg.announce_replica("r0", slots=2)
        clock.advance(5.1)
        assert reg.gateways() == {}
        assert reg.replicas() == {}
        # Physically still there until a sweep...
        assert kv.scan("serve/jobA/") != {}
        deleted = reg.gc_stale()
        assert sorted(deleted) == [
            "serve/jobA/gw/g0", "serve/jobA/rep/r0",
        ]
        assert kv.scan("serve/jobA/") == {}

    def test_heartbeat_keeps_the_lease_alive(self):
        reg, kv, clock = self.make(lease_s=5.0)
        reg.announce_gateway("g0", "h:1")
        clock.advance(4.0)
        reg.announce_gateway("g0", "h:1")  # heartbeat
        clock.advance(4.0)
        assert reg.gateways() == {"g0": "h:1"}
        assert reg.gc_stale() == []

    def test_keys_namespaced_per_job(self):
        clock = FakeClock()
        kv = LocalKv()
        a = ServeRegistry(kv, job="jobA", clock=clock)
        b = ServeRegistry(kv, job="jobB", clock=clock)
        a.announce_gateway("g0", "h:1")
        b.announce_gateway("g9", "h:9")
        assert a.gateways() == {"g0": "h:1"}
        assert b.gateways() == {"g9": "h:9"}
        assert a.gw_key("g0").startswith("serve/jobA/")

    def test_lease_is_reader_side_and_skew_immune(self):
        """Liveness never compares writer and reader wall clocks: a
        writer 100s 'in the future' (or past) stays live as long as
        its heartbeat value keeps changing, and a skewed reader's
        gc_stale can never delete fresh peers."""
        clock = FakeClock()
        kv = LocalKv()
        writer_clock = FakeClock()
        writer_clock.t = clock.t + 100.0  # gross skew
        writer = ServeRegistry(kv, job="jobA", lease_s=5.0,
                               clock=writer_clock)
        reader = ServeRegistry(kv, job="jobA", lease_s=5.0,
                               clock=clock)
        writer.announce_gateway("g0", "h:1")
        assert reader.gateways() == {"g0": "h:1"}
        # Heartbeats keep it alive on the reader's clock...
        for _ in range(3):
            clock.advance(4.0)
            writer_clock.advance(4.0)
            writer.announce_gateway("g0", "h:1")
            assert reader.gateways() == {"g0": "h:1"}
            assert reader.gc_stale() == []
        # ... and once the heartbeats STOP, the reader expires it by
        # its own observation window.
        clock.advance(5.1)
        assert reader.gateways() == {}
        assert reader.gc_stale() == ["serve/jobA/gw/g0"]

    def test_undecodable_entry_is_dropped_not_fatal(self):
        reg, kv, clock = self.make()
        kv.set("serve/jobA/gw/bad", b"\xff{not json")
        reg.announce_gateway("g0", "h:1")
        assert reg.gateways() == {"g0": "h:1"}
        assert "serve/jobA/gw/bad" in reg.gc_stale()


def test_registry_over_real_wire_roundtrip():
    """RegistryServer + RpcKv: the subprocess path (gateway/replica/
    driver of an e2e) speaks the same KVStore* messages as the
    master's KV — one real-socket check that scan/set/delete agree."""
    from dlrover_tpu.serving import RegistryServer, RpcKv

    server = RegistryServer()
    try:
        kv = RpcKv(server.addr)
        reg = ServeRegistry(kv, job="wire", lease_s=30.0)
        reg.announce_gateway("g0", "h:1")
        reg.announce_replica("r0", slots=2)
        assert reg.gateways() == {"g0": "h:1"}
        assert list(reg.replicas()) == ["r0"]
        reg.remove_gateway("g0")
        assert reg.gateways() == {}
        kv.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Histogram.merge (satellite: window-aware, bucket-wise)
# ---------------------------------------------------------------------------


class TestHistogramMerge:
    def test_merged_percentile_equals_combined_observations(self):
        h1, h2 = Histogram(), Histogram()
        for v in (5, 5, 50):
            h1.observe(v)
        for v in (500, 5000):
            h2.observe(v)
        agg = Histogram.merged([h1, h2.state()])
        assert agg.count == 5
        assert agg.percentile(0.50) == 50.0
        assert agg.percentile(0.99) == 5000.0
        ref = Histogram()
        for v in (5, 5, 50, 500, 5000):
            ref.observe(v)
        for p in (0.5, 0.9, 0.95, 0.99):
            assert agg.percentile(p) == ref.percentile(p)

    def test_merge_is_window_aware(self):
        """Aged-out observations never reach the merged view: the
        state() of a windowed histogram covers only its live span, so
        one gateway's ancient cold-start latencies can't ratchet the
        tier-wide p95."""
        clock = FakeClock()
        h = Histogram(window_s=60.0, clock=clock)
        h.observe(30000)  # cold start
        clock.advance(130.0)  # two windows later: aged out
        h.observe(10)
        st = h.state()
        assert st["total"] == 1
        agg = Histogram.merged([st])
        assert agg.percentile(0.99) == 10.0

    def test_bounds_mismatch_raises(self):
        h1 = Histogram(buckets=(1, 2, 5))
        h2 = Histogram(buckets=(1, 2, 10))
        with pytest.raises(ValueError, match="bounds mismatch"):
            h1.merge(h2)

    def test_merge_sums_bucket_wise_and_counts(self):
        h1 = Histogram(buckets=(10, 100))
        h2 = Histogram(buckets=(10, 100))
        h1.observe(5)
        h2.observe(5)
        h2.observe(50)
        h1.merge(h2)
        st = h1.state()
        assert st["counts"] == [2, 1, 0]
        assert st["total"] == 3

    def test_merged_empty_input_is_empty_default(self):
        agg = Histogram.merged([])
        assert agg.count == 0
        assert agg.percentile(0.95) == 0.0


# ---------------------------------------------------------------------------
# merge_snapshots: the tier-wide autoscale view
# ---------------------------------------------------------------------------


class TestMergeSnapshots:
    def make_pair(self):
        """Two cores sharing one replica, split queues/assignments."""
        a, _ = GatewayCore(GatewayConfig()), None
        b = GatewayCore(GatewayConfig())
        for core in (a, b):
            core.register("r0", 4)
        a.register("r1", 4)
        for i in range(3):
            a.submit(f"a{i}", [1], 4)
        b.submit("b0", [1], 4)
        # one grant at each gateway
        a.poll("r0", 1, [])
        b.poll("r0", 1, [])
        return a, b

    def test_sums_and_union(self):
        a, b = self.make_pair()
        snap = merge_snapshots([a.stats_snapshot(),
                                b.stats_snapshot()])
        # 4 submitted, 2 granted -> 2 queued; all 4 in flight.
        assert snap["queue_depth"] == 2
        assert snap["in_flight"] == 4
        assert snap["counters"]["accepted"] == 4
        # r0 registered at BOTH gateways: union, slots not doubled.
        assert snap["replicas_alive"] == 2
        assert snap["replicas"]["r0"]["slots"] == 4
        assert snap["replicas"]["r0"]["assigned"] == 2
        pool = snap["pools"]["unified"]
        assert pool["alive"] == 2 and pool["slots"] == 8
        assert snap["gateways"] == 2

    def test_draining_anywhere_is_draining_everywhere(self):
        a, b = self.make_pair()
        a.drain("r0")
        snap = merge_snapshots([a.stats_snapshot(),
                                b.stats_snapshot()])
        assert snap["replicas"]["r0"]["draining"] is True
        assert snap["replicas_alive"] == 1

    def test_histogram_states_merge_into_p95(self):
        a, b = self.make_pair()
        h_a, h_b = Histogram(), Histogram()
        for v in (10, 10, 10, 10):
            h_a.observe(v)
        h_b.observe(5000)
        sa = a.stats_snapshot()
        sb = b.stats_snapshot()
        sa["ttft_hist"] = h_a.state()
        sb["ttft_hist"] = h_b.state()
        snap = merge_snapshots([sa, sb])
        # 4 of 5 at 10ms -> p95 reaches into the 5s observation; a
        # mean/max of per-gateway p95s could not represent this.
        assert snap["ttft_p95_ms"] == 5000.0

    def test_tier_stats_skips_dead_fetchers(self):
        a, b = self.make_pair()

        def dead():
            raise RuntimeError("gateway down")

        stats = TierStats([a.stats_snapshot, dead, b.stats_snapshot])
        snap = stats.snapshot()
        assert snap["gateways"] == 2
        assert snap["counters"]["accepted"] == 4

    def test_empty_input(self):
        snap = merge_snapshots([])
        assert snap["replicas_alive"] == 0
        assert snap["gateways"] == 0


# ---------------------------------------------------------------------------
# Replica fan-out link
# ---------------------------------------------------------------------------


class TestTierReplicaLink:
    def test_free_slots_never_double_granted(self):
        tier = _Tier(2)
        for i in range(8):
            # every id lands somewhere; both gateways hold work
            gid = tier.ring.owner(f"q{i}")
            tier.cores[gid].submit(f"q{i}", [1], 4)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=3))
        grants = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=3, active=[],
        ))
        assert isinstance(grants, wire.ServeGrants)
        # Fan-out offered 3 slots TOTAL across both gateways.
        assert len(grants.requests) == 3

    def test_drain_requires_every_gateway(self):
        tier = _Tier(2)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=2))
        tier.cores["g0"].drain("r0")
        reply = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=2, active=[],
        ))
        assert reply.drain is False  # g1 has not released it
        tier.cores["g1"].drain("r0")
        reply = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=2, active=[],
        ))
        assert reply.drain is True

    def test_known_false_reregisters_at_that_gateway_only(self):
        tier = _Tier(2)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=2))
        # Give g1 assigned work so a spurious re-register would
        # requeue it (redispatched counter).
        g1_rids = [f"w{i}" for i in range(40)
                   if tier.ring.owner(f"w{i}") == "g1"][:1]
        tier.cores["g1"].submit(g1_rids[0], [1], 4)
        link.call(wire.ServeReplicaPoll(replica_id="r0",
                                        free_slots=1, active=[]))
        # g0 "restarts": loses the replica.
        tier.cores["g0"]._replicas.clear()
        reply = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=0, active=g1_rids,
        ))
        assert isinstance(reply, wire.ServeGrants)
        assert wait_for(
            lambda: "r0" in tier.cores["g0"].stats_snapshot()[
                "replicas"
            ], timeout=2.0,
        )
        # The healthy gateway never saw a re-register requeue.
        assert tier.cores["g1"].counters["redispatched"] == 0

    def test_reports_route_to_granting_gateway(self):
        tier = _Tier(2)
        rid = next(f"q{i}" for i in range(40)
                   if tier.ring.owner(f"q{i}") == "g1")
        tier.cores["g1"].submit(rid, [1, 2], 2)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=2))
        grants = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=2, active=[],
        ))
        assert [g.req_id for g in grants.requests] == [rid]
        link.call(wire.ServeDone(replica_id="r0", req_id=rid,
                                 tokens=[7, 8], ok=True))
        assert tier.cores["g1"].counters["completed"] == 1
        assert tier.cores["g0"].counters["completed"] == 0

    def test_report_falls_back_to_ring_owner_when_granter_died(self):
        tier = _Tier(2)
        rid = next(f"q{i}" for i in range(40)
                   if tier.ring.owner(f"q{i}") == "g0")
        tier.cores["g0"].submit(rid, [1, 2], 2)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=2))
        link.call(wire.ServeReplicaPoll(replica_id="r0",
                                        free_slots=2, active=[]))
        # g0 dies; the failover owner (g1 adopted the whole ring)
        # received the client's resubmit.
        tier.kill("g0")
        tier.cores["g1"].submit(rid, [1, 2], 2)
        link.call(wire.ServeDone(replica_id="r0", req_id=rid,
                                 tokens=[7, 8], ok=True))
        assert tier.cores["g1"].counters["completed"] == 1

    def test_granted_routes_pruned_on_every_terminal_report(self):
        """ServeDone, ServeKvReject AND ServeKvReady all end this
        replica's ownership of a rid — and cancels prune too; routes
        must not leak one entry per prefilled/cancelled request on a
        long-lived replica."""
        tier = _Tier(1)
        core = tier.cores["g0"]
        core.register("p0", 4, role="prefill")
        core.register("d0", 4, role="decode")
        core.submit("k0", [1, 2], 2)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=4,
                                            role="prefill"))
        grants = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=4, active=[],
        ))
        assert [g.req_id for g in grants.requests] == ["k0"]
        assert "k0" in link._granted_by
        link.call(wire.ServeKvReady(replica_id="r0", req_id="k0",
                                    payload=b"seg"))
        assert "k0" not in link._granted_by
        # Cancel path: a deadline-expired grant produces no report.
        core.submit("k1", [1], 2, deadline_s=5.0)
        grants = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=4, active=[],
        ))
        # (k0 went kv_ready -> decode stage; this replica is prefill
        # so only k1 is granted to it.)
        assert "k1" in link._granted_by
        tier.cores["g0"]._clock = None  # unused; cancel via poll
        # Simulate the gateway cancelling k1 on a later poll reply.
        reply = wire.ServeGrants(cancel=["k1"], known=True)

        class _CancelOnce:
            def __init__(self, inner):
                self.inner = inner
                self.sent = False

            def call(self, msg, **kw):
                if isinstance(msg, wire.ServeReplicaPoll) and \
                        not self.sent:
                    self.sent = True
                    return reply
                return self.inner.call(msg, **kw)

        link._set._transports["g0"] = _CancelOnce(
            link._set._transports["g0"]
        )
        link.call(wire.ServeReplicaPoll(replica_id="r0",
                                        free_slots=0, active=[]))
        assert "k1" not in link._granted_by

    def test_no_live_gateway_poll_is_calm(self):
        tier = _Tier(1)
        link = tier.link("r0")
        link.call(wire.ServeReplicaRegister(replica_id="r0", slots=2))
        tier.kill("g0")
        reply = link.call(wire.ServeReplicaPoll(
            replica_id="r0", free_slots=2, active=[],
        ))
        assert isinstance(reply, wire.ServeGrants)
        assert reply.requests == [] and reply.known


# ---------------------------------------------------------------------------
# Tier client + failover (the tentpole's exactly-once law)
# ---------------------------------------------------------------------------


class TestTierClientFailover:
    def test_requests_route_to_owner_and_both_gateways_serve(self):
        tier = _Tier(2)
        runner, th = tier.start_replica("r0")
        cli = tier.client()
        n = 12
        for i in range(n):
            assert cli.submit(f"q{i}", [i + 1], 4).status == "accepted"
        for i in range(n):
            reply = cli.result(f"q{i}", timeout=15)
            assert reply.state == "done"
            assert reply.tokens == expected_tokens([i + 1], 4)
        done = {g: c.counters["completed"]
                for g, c in tier.cores.items()}
        assert sum(done.values()) == n
        assert all(v > 0 for v in done.values()), done
        tier.drain_all()
        th.join(timeout=5)

    def test_gateway_death_resubmit_answers_from_journal(
            self, tmp_path):
        """The flagship failover law, in-process: requests admitted at
        g0 complete at the replica (journaled), g0 dies before the
        client sees the results, the ring re-forms onto g1, the client
        resubmits — and the REPLICA'S JOURNAL answers (replayed, not
        re-decoded), so every request completes exactly once with
        byte-identical tokens."""
        tier = _Tier(2, lease_s=2.0)
        server = FakeDecodeServer(slots=4)
        runner, th = tier.start_replica(
            "r0", server=server, journal=str(tmp_path / "r0.jsonl"),
        )
        cli = tier.client()
        g0_rids = [f"f{i}" for i in range(60)
                   if tier.ring.owner(f"f{i}") == "g0"][:4]
        for rid in g0_rids:
            assert cli.submit(rid, [5, 6], 4).status == "accepted"
        # Wait until the replica decoded + journaled them all.
        assert wait_for(
            lambda: tier.cores["g0"].counters["completed"]
            == len(g0_rids)
        )
        decoded_before = runner.served
        tier.kill("g0")
        for rid in g0_rids:
            reply = cli.result(rid, timeout=15)
            assert reply.state == "done", (rid, reply)
            assert reply.tokens == expected_tokens([5, 6], 4)
        assert cli.resubmitted >= len(g0_rids)
        # Journal replay answered the failover copies: the decode ran
        # ONCE per request.
        assert wait_for(lambda: runner.replayed >= len(g0_rids))
        assert runner.served == decoded_before
        # And the adopting gateway recorded them exactly once each.
        assert tier.cores["g1"].counters["completed"] == len(g0_rids)
        tier.drain_all()
        th.join(timeout=5)

    def test_resubmit_of_terminal_request_answers_from_cache(self):
        tier = _Tier(1)
        runner, th = tier.start_replica("r0")
        cli = tier.client()
        cli.submit("t0", [2], 3)
        reply = cli.result("t0", timeout=15)
        assert reply.state == "done"
        ack = cli.submit("t0", [2], 3)
        assert ack.status == "done"
        assert ack.tokens == expected_tokens([2], 3)
        assert tier.cores["g0"].counters["dedupe_hits"] == 1
        tier.drain_all()
        th.join(timeout=5)


# ---------------------------------------------------------------------------
# P2P KV handoff: store, pulls, ticket path, fallback ladder
# ---------------------------------------------------------------------------


class TestTierAdmissionConservation:
    """Every request offered to the tier is accounted at exactly one
    gateway, under its admission cap and over it; what a tier admits
    grows with its gateways.  Driven by hand on the bare cores: no
    thread, no clock."""

    CAP = 4

    def _offer(self, tier, n):
        acks = collections.Counter()
        for i in range(n):
            rid = f"q{i}"
            gid = tier.ring.owner(rid)
            ack = tier.addr_map[f"addr-{gid}"].call(wire.ServeSubmit(
                req_id=rid, prompt=[i + 1, i + 2], max_new_tokens=3))
            acks[ack.status] += 1
        return acks

    def _serve_everything_admitted(self, tier):
        for core in tier.cores.values():
            core.register("r0", 2)
            while True:
                grants = core.poll("r0", 2, []).requests
                if not grants:
                    break
                for g in grants:
                    core.complete(
                        "r0", g.req_id,
                        expected_tokens(g.prompt, g.max_new_tokens),
                        True, "", False)

    @pytest.mark.parametrize("offered", [3, 40])
    @pytest.mark.parametrize("gateways", [1, 2])
    def test_every_offered_request_is_accounted(self, gateways, offered):
        tier = _Tier(gateways, queue_cap=self.CAP)
        acks = self._offer(tier, offered)
        self._serve_everything_admitted(tier)
        total = collections.Counter()
        for core in tier.cores.values():
            c = core.counters
            assert c["submitted"] == c["accepted"] + c["rejected"]
            assert c["accepted"] == c["completed"] + c["timeout"] \
                + c["failed"]
            assert c["accepted"] <= self.CAP
            total.update({k: c[k] for k in (
                "submitted", "accepted", "rejected", "completed")})
        assert total["submitted"] == offered
        assert total["accepted"] == acks["accepted"]
        assert total["rejected"] == acks["rejected"]
        merged = merge_snapshots(
            [c.stats_snapshot() for c in tier.cores.values()])
        assert merged["counters"]["completed"] == total["completed"]
        if offered <= self.CAP:
            assert total["rejected"] == 0
            assert total["completed"] == offered
        else:
            # over the cap something is rejected, and every gateway of
            # the tier admits a queue of its own
            assert total["rejected"] > 0
            assert total["accepted"] == gateways * self.CAP


class _FakeKvServer:
    """store + addr, no sockets — what tests inject as the runner's
    kv_server; pulls go through ``handle_fetch`` loopbacks."""

    def __init__(self, addr):
        self.addr = addr
        self.store = KvSegmentStore()
        self.stopped = False

    def stop(self):
        self.stopped = True


class TestKvSegmentStore:
    def test_put_get_roundtrip_with_ticket(self):
        store = KvSegmentStore()
        fp, crc, nb = store.put("r1", b"abcdef")
        assert nb == 6 and fp == segment_fingerprint(b"abcdef")
        payload, crc2 = store.get("r1")
        assert payload == b"abcdef" and crc2 == crc

    def test_fingerprint_pins_the_publication(self):
        store = KvSegmentStore()
        fp_old, _, _ = store.put("r1", b"old-segment")
        store.put("r1", b"new-segment")  # re-prefill under same rid
        assert store.get("r1", fp_old) is None
        assert store.get("r1")[0] == b"new-segment"

    def test_ttl_expiry(self):
        clock = FakeClock()
        store = KvSegmentStore(ttl_s=10.0, clock=clock)
        store.put("r1", b"x")
        clock.advance(11.0)
        assert store.get("r1") is None

    def test_bounded_by_count_and_bytes_oldest_first(self):
        store = KvSegmentStore(max_segments=2, max_bytes=1 << 20)
        store.put("a", b"1")
        store.put("b", b"2")
        store.put("c", b"3")
        assert store.get("a") is None
        assert store.get("b") is not None
        store2 = KvSegmentStore(max_segments=100, max_bytes=10)
        store2.put("a", b"x" * 8)
        store2.put("b", b"y" * 8)
        assert store2.get("a") is None
        assert store2.nbytes == 8

    def test_put_refuses_what_it_cannot_retain(self):
        """A ticket for bytes the server no longer holds guarantees a
        failed pull that burns a bounded attempt — put() must return
        None (caller relays) instead of a dead ticket."""
        store = KvSegmentStore(max_bytes=10)
        assert store.put("big", b"x" * 11) is None
        assert len(store) == 0
        # An insert whose sweep evicts the entry itself also refuses.
        tiny = KvSegmentStore(max_segments=0)
        assert tiny.put("r1", b"ab") is None

    def test_pull_verifies_ticket(self):
        store = KvSegmentStore()
        fp, crc, nb = store.put("r1", b"payload-bytes")
        loop = LoopbackTransport(lambda m: handle_fetch(store, m))
        got = pull_kv_segment("x", "r1", fp, crc, nb, transport=loop)
        assert got == b"payload-bytes"
        with pytest.raises(KvPullError, match="not served"):
            pull_kv_segment("x", "missing", fp, crc, nb,
                            transport=loop)
        with pytest.raises(KvPullError, match="CRC mismatch"):
            pull_kv_segment("x", "r1", fp, crc ^ 1, nb,
                            transport=loop)
        with pytest.raises(KvPullError, match="ticket promised"):
            pull_kv_segment("x", "r1", fp, crc, nb + 1,
                            transport=loop)
        # Stale publication: the stored fp differs from the ticket's.
        with pytest.raises(KvPullError, match="not served"):
            pull_kv_segment("x", "r1", "0" * 16, crc, nb,
                            transport=loop)


class TestGatewayTicketPath:
    def make_core(self):
        clock = FakeClock()
        core = GatewayCore(GatewayConfig(max_attempts=3), clock=clock)
        return core, clock

    def grant_prefill(self, core, rid="d0"):
        core.register("p0", 2, role="prefill")
        core.register("d0r", 2, role="decode")
        core.submit(rid, [1, 2, 3], 4)
        grants = core.poll("p0", 2, [])
        assert [g.req_id for g in grants.requests] == [rid]
        return grants.requests[0]

    def test_ticket_holds_no_bytes_and_rides_the_decode_grant(self):
        core, _ = self.make_core()
        grant = self.grant_prefill(core)
        assert grant.stage == "prefill" and grant.kv_relay is False
        out = core.kv_ready("p0", "d0", b"", fp32_bytes=400,
                            addr="peer:1", seg_fp="ab" * 8,
                            crc32=77, nbytes=100)
        assert out == "recorded"
        c = core.counters
        assert c["kv_handoffs"] == 1
        assert c["kv_bytes"] == 0  # nothing transited the gateway
        # p2p bytes are booked when the ticket is GRANTED for a pull,
        # not at kv_ready (bytes that never moved must not count).
        assert c["kv_p2p_bytes"] == 0
        dec = core.poll("d0r", 2, []).requests[0]
        assert dec.stage == "decode" and dec.kv == b""
        assert dec.kv_addr == "peer:1" and dec.kv_crc32 == 77
        assert dec.kv_nbytes == 100 and dec.kv_fp == "ab" * 8
        assert core.counters["kv_p2p_bytes"] == 100

    def test_relay_mode_ordered_when_p2p_disabled(self):
        clock = FakeClock()
        core = GatewayCore(GatewayConfig(kv_p2p=False), clock=clock)
        grant = self.grant_prefill(core)
        assert grant.kv_relay is True

    def test_decode_death_reships_the_same_ticket(self):
        core, clock = self.make_core()
        self.grant_prefill(core)
        core.kv_ready("p0", "d0", b"", addr="peer:1", seg_fp="f" * 16,
                      crc32=9, nbytes=10)
        core.poll("d0r", 2, [])
        core.deregister("d0r")  # decode replica died
        core.register("d2", 2, role="decode")
        dec = core.poll("d2", 2, []).requests[0]
        assert dec.stage == "decode" and dec.kv_addr == "peer:1"
        assert core.counters["redispatched"] == 1

    def test_failed_pull_falls_back_to_relay_prefill(self):
        core, _ = self.make_core()
        self.grant_prefill(core)
        core.kv_ready("p0", "d0", b"", addr="peer:1", seg_fp="f" * 16,
                      crc32=9, nbytes=10)
        core.poll("d0r", 2, [])
        out = core.kv_reject("d0r", "d0", reason="pull: peer gone")
        assert out == "recorded"
        c = core.counters
        assert c["kv_rejects"] == 1 and c["kv_relay_fallbacks"] == 1
        # Next prefill grant orders the relay path for THIS request.
        regrant = core.poll("p0", 2, []).requests[0]
        assert regrant.stage == "prefill"
        assert regrant.kv_relay is True
        # ... and a relayed kv_ready then ships bytes via the gateway.
        core.kv_ready("p0", "d0", b"relayed-segment", fp32_bytes=60)
        assert core.counters["kv_bytes"] == len(b"relayed-segment")
        dec = core.poll("d0r", 2, []).requests[0]
        assert dec.kv == b"relayed-segment" and dec.kv_addr == ""

    def test_persistently_failing_pull_is_bounded_by_max_attempts(
            self):
        core, _ = self.make_core()
        self.grant_prefill(core)
        for _n in range(3):
            core.kv_ready("p0", "d0", b"", addr="p:1",
                          seg_fp="f" * 16, crc32=9, nbytes=10)
            grants = core.poll("d0r", 2, [])
            if not grants.requests:
                break
            core.kv_reject("d0r", "d0", reason="pull: gone")
            regrants = core.poll("p0", 2, [])
            if not regrants.requests:
                break
        assert core.status("d0").state == "failed"


class TestReplicaP2P:
    def make_fleet(self, core, pull_fails=False):
        """prefill + decode runners on one core; segments move through
        an in-process fake segment server (no sockets)."""
        transport = LoopbackTransport(core_handle(core))
        servers = {}

        def connect(addr):
            if pull_fails:
                class _Gone:
                    def call(self, msg, **kw):
                        raise RuntimeError("peer unreachable")

                return _Gone()
            return LoopbackTransport(
                lambda m: handle_fetch(servers[addr].store, m)
            )

        kv_p = _FakeKvServer("peer-p0")
        servers["peer-p0"] = kv_p
        prefill = ReplicaRunner(
            FakePrefillServer(2), transport, "p0",
            poll_interval=0.001, role="prefill", kv_p2p=True,
            kv_server=kv_p,
        )
        decode = ReplicaRunner(
            FakeDecodeServer(2), transport, "d0",
            poll_interval=0.001, role="decode", kv_p2p=True,
            kv_connect=connect,
        )
        threads = [
            threading.Thread(target=r.run, daemon=True)
            for r in (prefill, decode)
        ]
        for th in threads:
            th.start()
        return prefill, decode, threads

    def drain(self, core, threads):
        for rid in list(core.stats_snapshot()["replicas"]):
            core.drain(rid)
        for th in threads:
            th.join(timeout=5)

    def test_p2p_disagg_exact_and_byteless_at_gateway(self):
        core = GatewayCore(GatewayConfig())
        prefill, decode, threads = self.make_fleet(core)
        n = 6
        for i in range(n):
            core.submit(f"q{i}", [i + 1, i + 2], 4)
        assert wait_for(lambda: core.counters["completed"] == n)
        for i in range(n):
            reply = core.status(f"q{i}")
            # unified-law exactness through the P2P handoff
            assert reply.tokens == expected_tokens([i + 1, i + 2], 4)
        c = core.counters
        assert c["kv_handoffs"] == n
        assert c["kv_bytes"] == 0
        assert c["kv_p2p_bytes"] > 0
        assert prefill.kv_published == n
        assert decode.kv_pulled == n
        self.drain(core, threads)

    def test_pull_failure_falls_back_to_relay_and_completes(self):
        core = GatewayCore(GatewayConfig())
        prefill, decode, threads = self.make_fleet(core,
                                                   pull_fails=True)
        core.submit("q0", [3, 4], 4)
        assert wait_for(lambda: core.counters["completed"] == 1)
        assert core.status("q0").tokens == expected_tokens([3, 4], 4)
        c = core.counters
        assert c["kv_rejects"] >= 1
        assert c["kv_relay_fallbacks"] >= 1
        assert c["kv_bytes"] > 0  # the fallback relayed the bytes
        assert decode.kv_pull_failed >= 1
        self.drain(core, threads)

    def test_chaos_kv_drop_pull_mode_recovers(self):
        chaos.configure("serving.kv_drop:method=pull,times=1")
        try:
            core = GatewayCore(GatewayConfig())
            prefill, decode, threads = self.make_fleet(core)
            core.submit("q0", [2, 5], 4)
            assert wait_for(lambda: core.counters["completed"] == 1)
            assert core.status("q0").tokens == \
                expected_tokens([2, 5], 4)
            assert core.counters["kv_rejects"] == 1
            assert core.counters["kv_relay_fallbacks"] == 1
            self.drain(core, threads)
        finally:
            chaos.reset()

    def test_runner_stops_its_kv_server_on_exit(self):
        core = GatewayCore(GatewayConfig())
        prefill, decode, threads = self.make_fleet(core)
        kv_server = prefill._kv_server
        self.drain(core, threads)
        assert kv_server.stopped is True


# ---------------------------------------------------------------------------
# chaos site + messages fast path
# ---------------------------------------------------------------------------


class TestGatewayKillSite:
    def test_site_registered_with_exit_code(self):
        from dlrover_tpu.chaos.plan import SITES

        site = SITES["serving.gateway_kill"]
        assert site["kind"] == "crash"
        assert site["exit"] == 81 and site["times"] == 1

    def test_method_selects_the_victim_and_step_ge_gates(self):
        plan = chaos.FaultPlan.parse(
            "serving.gateway_kill:method=g1,step_ge=2"
        )
        assert plan.fire("serving.gateway_kill", method="g0",
                         step=5) is None
        assert plan.fire("serving.gateway_kill", method="g1",
                         step=1) is None
        spec = plan.fire("serving.gateway_kill", method="g1", step=3)
        assert spec is not None and spec.exit_code == 81
        # times=1: spent
        assert plan.fire("serving.gateway_kill", method="g1",
                         step=9) is None

    def test_step_ge_requires_a_step_report(self):
        plan = chaos.FaultPlan.parse("worker.kill:step_ge=4")
        assert plan.fire("worker.kill") is None
        assert plan.fire("worker.kill", step=4) is not None


class TestMessagesFastPath:
    CASES = [
        wire.ServeSubmit(req_id="x", prompt=list(range(300)),
                         max_new_tokens=4, kv_addr="h:1",
                         kv_crc32=9, kv_nbytes=3),
        wire.ServeGrants(requests=[
            wire.ServeSubmit(req_id=f"g{i}", prompt=[1, 2])
            for i in range(5)
        ], cancel=["a", "b"], drain=True),
        wire.ServeReplicaPoll(replica_id="r", free_slots=3,
                              active=["a"], stats={"x": 1.5},
                              warm_prefixes=["ff"]),
        wire.ServeKvReady(replica_id="p", req_id="q",
                          payload=b"\x00\xff", addr="h:2",
                          seg_fp="ab", crc32=1, nbytes=2),
        wire.KVStoreScan(prefix="serve/"),
        wire.KVStoreScanResult(kvs={"k": b"v"}),
        wire.KVStoreDelete(key="k"),
        wire.ServeFleetStats(stats={"pools": {"unified": {"alive": 1}},
                                    "ids": [1, 2, 3]}),
        wire.Empty(),
    ]

    def test_fast_path_is_byte_identical_to_baseline(self):
        for msg in self.CASES:
            assert wire.serialize(msg) == wire.serialize_baseline(msg)

    def test_roundtrip(self):
        for msg in self.CASES:
            assert wire.deserialize(wire.serialize(msg)) == msg

    def test_nested_message_in_dict_and_tuple_fields(self):
        msg = wire.ServeFleetStats(stats={
            "nested": wire.ServeAck(req_id="a", tokens=[1, 2]),
            "plain": [1, 2, 3],
        })
        out = wire.deserialize(wire.serialize(msg))
        assert out.stats["nested"] == wire.ServeAck(req_id="a",
                                                    tokens=[1, 2])
        assert out.stats["plain"] == [1, 2, 3]
        assert wire.serialize(msg) == wire.serialize_baseline(msg)


def test_gateway_tier_node_heartbeats_and_gcs(tmp_path):
    """One real GatewayTierNode (socketed Gateway + heartbeat thread):
    it announces itself, keeps the lease fresh, GCs a stale peer, and
    deregisters on stop."""
    clock_now = time.time
    kv = LocalKv()
    registry = ServeRegistry(kv, job="node", lease_s=1.0,
                             clock=clock_now)
    from dlrover_tpu.serving import GatewayTierNode

    # A stale peer entry from a long-dead gateway.
    kv.set("serve/node/gw/dead", b'{"addr": "h:9", "ts": 1.0}')
    node = GatewayTierNode("g0", registry, heartbeat_s=0.05)
    node.start()
    try:
        assert wait_for(
            lambda: registry.gateways().get("g0") == node.addr,
            timeout=5.0,
        )
        assert wait_for(
            lambda: kv.get("serve/node/gw/dead") is None, timeout=5.0,
        )
        # Lease stays fresh across several windows.
        time.sleep(0.3)
        assert "g0" in registry.gateways()
        snap = node.core.stats_snapshot()
        assert snap["gateway_id"] == "g0"
    finally:
        node.stop()
    assert kv.get("serve/node/gw/g0") is None


def test_registry_server_tokened_delete_answers_first_result():
    """ISSUE 14 (graftcheck PC403): RpcKv.delete retries DEADLINE, so
    the standalone registry dedupes delete tokens exactly like the
    master KV — a retried duplicate of a landed delete answers True."""
    from dlrover_tpu.common.messages import (
        KVStoreDelete,
        KVStoreSet,
    )
    from dlrover_tpu.serving.tier import RegistryServer

    srv = RegistryServer(port=0)
    try:
        srv.handle(KVStoreSet(key="k", value=b"v"))
        rm = KVStoreDelete(key="k", token="tok")
        assert srv.handle(rm).success
        assert srv.handle(rm).success  # retried duplicate
        assert not srv.handle(
            KVStoreDelete(key="k", token="tok2")
        ).success
    finally:
        srv.stop()


def test_registry_server_delete_dedupe_is_race_safe():
    """A DEADLINE retry can race its own slow first attempt: both must
    answer the FIRST result (True), and the cache must not latch the
    loser's False (the handle() pool is 64 threads wide)."""
    import threading as _threading

    from dlrover_tpu.common.messages import KVStoreDelete, KVStoreSet
    from dlrover_tpu.serving.tier import RegistryServer

    srv = RegistryServer(port=0)
    try:
        srv.handle(KVStoreSet(key="k", value=b"v"))
        slow = _threading.Event()
        real_delete = srv.kv.delete

        def slow_delete(key):
            got = real_delete(key)
            slow.wait(0.2)  # hold the first attempt mid-sequence
            return got

        srv.kv.delete = slow_delete
        results = {}

        def attempt(tag):
            results[tag] = srv.handle(
                KVStoreDelete(key="k", token="tok")
            ).success

        t1 = _threading.Thread(target=attempt, args=("first",))
        t2 = _threading.Thread(target=attempt, args=("retry",))
        t1.start()
        t2.start()
        slow.set()
        t1.join()
        t2.join()
        assert results == {"first": True, "retry": True}
        # The cached answer stays True for any further retry.
        assert srv.handle(KVStoreDelete(key="k", token="tok")).success
    finally:
        srv.kv.delete = real_delete
        srv.stop()


def test_kv_segment_store_stats_report_block_framing():
    """ISSUE 19: store telemetry distinguishes block-list segments
    (paged prefill handoff) from monolithic ones and totals the KV
    blocks held — the handoff-side view of the fleet's memory."""
    import msgpack

    store = KvSegmentStore()
    paged = msgpack.packb(
        {"meta": {"bs": 8, "nblk": 3}, "data": b"x" * 16},
        use_bin_type=True,
    )
    store.put("p1", paged)
    store.put("d1", b"monolithic-segment-bytes")
    st = store.stats()
    assert st["segments"] == 2
    assert st["bytes"] == len(paged) + len(b"monolithic-segment-bytes")
    assert st["paged_segments"] == 1
    assert st["blocks_held"] == 3
    store.discard("p1")
    st = store.stats()
    assert st["paged_segments"] == 0 and st["blocks_held"] == 0
