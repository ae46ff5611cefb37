"""Serving-fleet control-plane units (ISSUE 5) — tier-1, sub-second.

Everything here runs WITHOUT jax or sockets: the gateway core takes an
injectable clock, the replica runner takes a fake decode server with
the real incremental-admission surface, and transports are loopback.
The real-model integration rides the ``serving+slow`` e2e lane
(``test_chaos_e2e.py``).
"""

import collections
import threading
import time

import pytest

from dlrover_tpu.common.messages import (
    ServeDone,
    ServeGrants,
    ServeKvReady,
    ServeKvReject,
    ServeReplicaDeregister,
    ServeReplicaPoll,
    ServeReplicaRegister,
    ServeSubmit,
    ServeTokens,
    deserialize,
    serialize,
)
from dlrover_tpu.serving import (
    GatewayConfig,
    GatewayCore,
    LoopbackTransport,
    PoolAutoScaler,
    ReplicaRunner,
    ScalePolicy,
    ScaleState,
    decide,
    decide_pools,
)

pytestmark = pytest.mark.serving


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_core(**kw):
    clock = FakeClock()
    cfg = GatewayConfig(**kw)
    return GatewayCore(cfg, clock=clock), clock


# ---------------------------------------------------------------------------
# Admission / backpressure / dedupe
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_accept_then_reject_past_cap_with_retry_after(self):
        core, _ = make_core(queue_cap=2, retry_after_s=1.5)
        assert core.submit("a", [1], 4).status == "accepted"
        assert core.submit("b", [2], 4).status == "accepted"
        ack = core.submit("c", [3], 4)
        assert ack.status == "rejected"
        assert ack.retry_after_s == 1.5
        assert "queue full" in ack.reason
        assert core.counters["rejected"] == 1

    def test_cap_counts_assigned_work_not_just_queued(self):
        """Backpressure is on total in-flight: granting work to a
        replica must not open admission back up."""
        core, _ = make_core(queue_cap=2)
        core.register("r0", 2)
        core.submit("a", [1], 4)
        core.submit("b", [2], 4)
        core.poll("r0", 2, [])  # both now assigned, queue empty
        assert core.submit("c", [3], 4).status == "rejected"

    def test_duplicate_submit_while_in_flight_is_single_entry(self):
        core, _ = make_core()
        core.submit("a", [1], 4)
        ack = core.submit("a", [1], 4)
        assert ack.status == "accepted"
        assert ack.reason == "duplicate-submit"
        assert core.stats_snapshot()["queue_depth"] == 1

    def test_resubmit_of_completed_request_answers_from_cache(self):
        """The req-id IS the idempotency token: a client retry after
        the answer was produced never decodes twice."""
        core, _ = make_core()
        core.register("r0", 1)
        core.submit("a", [1], 4)
        core.poll("r0", 1, [])
        core.complete("r0", "a", [7, 8, 9])
        ack = core.submit("a", [1], 4)
        assert ack.status == "done"
        assert ack.tokens == [7, 8, 9]
        assert core.counters["dedupe_hits"] == 1
        assert core.counters["completed"] == 1

    def test_status_lifecycle(self):
        core, _ = make_core()
        assert core.status("a").state == "unknown"
        core.submit("a", [1], 4)
        assert core.status("a").state == "queued"
        core.register("r0", 1)
        core.poll("r0", 1, [])
        assert core.status("a").state == "running"
        core.stream("r0", "a", [5])
        assert core.status("a").tokens == [5]
        core.complete("r0", "a", [5, 6])
        st = core.status("a")
        assert st.state == "done" and st.tokens == [5, 6]
        assert st.replica == "r0"


# ---------------------------------------------------------------------------
# Routing / grants
# ---------------------------------------------------------------------------


class TestRouting:
    def test_grants_capped_by_free_slots(self):
        core, _ = make_core()
        core.register("r0", 4)
        for i in range(5):
            core.submit(f"q{i}", [i], 4)
        g = core.poll("r0", 2, [])
        assert [r.req_id for r in g.requests] == ["q0", "q1"]
        g = core.poll("r0", 0, ["q0", "q1"])
        assert g.requests == []

    def test_work_flows_to_the_replica_with_free_slots(self):
        """Pull routing == least-loaded routing: the saturated replica
        polls with 0 free slots and gets nothing; the idle one drains
        the queue."""
        core, _ = make_core()
        core.register("busy", 2)
        core.register("idle", 2)
        for i in range(4):
            core.submit(f"q{i}", [i], 4)
        g_busy = core.poll("busy", 0, [])
        g_idle = core.poll("idle", 2, [])
        assert g_busy.requests == []
        assert [r.req_id for r in g_idle.requests] == ["q0", "q1"]

    def test_unknown_replica_is_told_to_reregister(self):
        core, _ = make_core()
        g = core.poll("ghost", 2, [])
        assert g.known is False


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


class TestDeadlines:
    def test_queued_request_times_out(self):
        core, clock = make_core()
        core.submit("a", [1], 4, deadline_s=5.0)
        clock.advance(6.0)
        core.sweep()
        st = core.status("a")
        assert st.state == "timeout"
        assert core.counters["timeout"] == 1

    def test_expired_request_never_granted(self):
        core, clock = make_core()
        core.register("r0", 1)
        core.submit("a", [1], 4, deadline_s=5.0)
        clock.advance(6.0)
        g = core.poll("r0", 1, [])
        assert g.requests == []
        assert core.status("a").state == "timeout"

    def test_in_flight_deadline_cancels_at_replica(self):
        core, clock = make_core()
        core.register("r0", 1)
        core.submit("a", [1], 4, deadline_s=5.0)
        core.poll("r0", 1, [])
        clock.advance(6.0)
        g = core.poll("r0", 0, ["a"])
        assert g.cancel == ["a"]
        assert core.status("a").state == "timeout"

    def test_resubmit_of_timed_out_request_acks_timeout_not_done(self):
        """A terminal timeout must not be masked as a zero-token
        success on resubmit — the ack carries the cached outcome."""
        core, clock = make_core()
        core.submit("a", [1], 4, deadline_s=5.0)
        clock.advance(6.0)
        core.sweep()
        ack = core.submit("a", [1], 4)
        assert ack.status == "timeout"
        assert ack.tokens == []
        assert "deadline" in ack.reason

    def test_late_completion_after_timeout_is_dropped(self):
        core, clock = make_core()
        core.register("r0", 1)
        core.submit("a", [1], 4, deadline_s=5.0)
        core.poll("r0", 1, [])
        clock.advance(6.0)
        core.poll("r0", 0, ["a"])  # timeout recorded here
        assert core.complete("r0", "a", [9]) == "duplicate"
        assert core.status("a").state == "timeout"
        # Work finished after its gateway timeout is a LATE completion,
        # not a dedupe event — the duplicate counter stays meaningful
        # as journal-replay evidence.
        assert core.counters["late_completions"] == 1
        assert core.counters["duplicate_completions"] == 0


# ---------------------------------------------------------------------------
# Replica death / re-dispatch / exactly-once
# ---------------------------------------------------------------------------


class TestRedispatch:
    def test_lease_expiry_requeues_in_flight_at_front(self):
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("r0", 2)
        core.submit("a", [1], 4)
        core.submit("b", [2], 4)
        core.poll("r0", 1, [])  # 'a' assigned
        clock.advance(11.0)
        core.sweep()
        assert core.counters["replicas_lost"] == 1
        assert core.counters["redispatched"] == 1
        core.register("r1", 2)
        g = core.poll("r1", 2, [])
        # The re-dispatched request goes FIRST (it has waited longest).
        assert [r.req_id for r in g.requests] == ["a", "b"]

    def test_duplicate_completion_from_journal_replay_is_dropped(self):
        """The exactly-once law: re-dispatch races journal replay, the
        first terminal report wins, the second is counted and dropped."""
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("r0", 1)
        core.submit("a", [1], 4)
        core.poll("r0", 1, [])
        clock.advance(11.0)
        core.sweep()  # r0 presumed dead; 'a' re-queued
        core.register("r1", 1)
        core.poll("r1", 1, [])
        assert core.complete("r1", "a", [5, 6]) == "recorded"
        # r0 restarts and replays its journal for the same request.
        assert core.complete("r0", "a", [5, 6], replayed=True) == \
            "duplicate"
        assert core.counters["completed"] == 1
        assert core.counters["duplicate_completions"] == 1
        assert core.status("a").tokens == [5, 6]

    def test_reregister_requeues_assigned_work(self):
        """A replica that crashed and re-registered cannot still be
        running its old assignment: it is re-dispatched (its journal
        replay, if any, wins the dedupe race instead)."""
        core, _ = make_core()
        core.register("r0", 1)
        core.submit("a", [1], 4)
        core.poll("r0", 1, [])
        core.register("r0", 1)  # restart, same id
        assert core.stats_snapshot()["queue_depth"] == 1
        assert core.counters["redispatched"] == 1

    def test_lost_grant_reconciled_from_owned_set(self):
        """chaos serving.drop_request's recovery path: a grant the
        replica never admits is absent from its owned set two polls
        later and goes back to the queue."""
        core, _ = make_core()
        core.register("r0", 2)
        core.submit("a", [1], 4)
        g = core.poll("r0", 2, [])
        assert [r.req_id for r in g.requests] == ["a"]
        # Poll without owning it: one poll of grace (the grant may have
        # raced this poll)...
        core.poll("r0", 2, [])
        assert core.status("a").state == "running"
        # ...then the next unowning poll proves it lost.
        g = core.poll("r0", 2, [])
        assert core.counters["redispatched"] == 1
        assert [r.req_id for r in g.requests] == ["a"]

    def test_poison_request_fails_terminally_after_max_attempts(self):
        """A request that keeps getting lost (or keeps killing its
        replica) must not head-of-line-block the fleet forever: after
        max_attempts re-dispatches it fails terminally."""
        core, clock = make_core(lease_timeout_s=5.0, max_attempts=3)
        core.submit("poison", [1], 4)
        core.submit("healthy", [2], 4)
        for round_i in range(3):
            rid = f"r{round_i}"
            core.register(rid, 1)
            g = core.poll(rid, 1, [])
            assert g.requests and g.requests[0].req_id == "poison"
            clock.advance(6.0)
            core.sweep()  # replica "died"; poison re-queued at front
        st = core.status("poison")
        assert st.state == "failed"
        assert "re-dispatched 3 times" in st.reason
        assert core.counters["failed"] == 1
        # The healthy request is now at the head for the next replica.
        core.register("r9", 1)
        g = core.poll("r9", 1, [])
        assert [r.req_id for r in g.requests] == ["healthy"]

    def test_stale_stream_from_superseded_assignment_ignored(self):
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("r0", 1)
        core.submit("a", [1], 4)
        core.poll("r0", 1, [])
        core.stream("r0", "a", [5])
        clock.advance(11.0)
        core.sweep()
        core.register("r1", 1)
        core.poll("r1", 1, [])
        core.stream("r0", "a", [6])  # zombie r0 streams on
        st = core.status("a")
        # Partial buffer reset at re-dispatch; zombie tokens dropped.
        assert st.tokens == [] and st.replica == "r1"


# ---------------------------------------------------------------------------
# Drain (scale-down)
# ---------------------------------------------------------------------------


class TestDrain:
    def test_draining_replica_gets_no_new_grants(self):
        core, _ = make_core()
        core.register("r0", 2)
        core.submit("a", [1], 4)
        core.poll("r0", 2, [])
        core.submit("b", [2], 4)
        assert core.drain("r0")
        g = core.poll("r0", 1, ["a"])
        assert g.requests == [] and g.drain is False
        # In-flight work finishes normally; only then drain=True.
        core.complete("r0", "a", [5])
        g = core.poll("r0", 2, [])
        assert g.drain is True
        # The queued request is still there for the survivors.
        core.register("r1", 2)
        g = core.poll("r1", 2, [])
        assert [r.req_id for r in g.requests] == ["b"]

    def test_pick_drain_victim_is_least_loaded(self):
        core, _ = make_core()
        core.register("r0", 2)
        core.register("r1", 2)
        for i in range(3):
            core.submit(f"q{i}", [i], 4)
        core.poll("r0", 2, [])
        core.poll("r1", 1, [])
        assert core.pick_drain_victim() == "r1"
        core.drain("r1")
        assert core.pick_drain_victim() == "r0"
        core.drain("r0")
        assert core.pick_drain_victim() is None


# ---------------------------------------------------------------------------
# Autoscale policy
# ---------------------------------------------------------------------------


class TestAutoscalePolicy:
    def _snap(self, alive, queue, occ=0.5, ttft=0.0):
        return {"replicas_alive": alive, "queue_depth": queue,
                "occupancy": occ, "ttft_p95_ms": ttft}

    def test_scale_up_needs_sustained_pressure(self):
        pol = ScalePolicy(queue_high_per_replica=4, up_patience=2,
                          max_replicas=4)
        st = ScaleState()
        assert decide(self._snap(1, 10), pol, st) == 1  # pass 1: wait
        assert decide(self._snap(1, 10), pol, st) == 2  # pass 2: grow
        assert st.up_streak == 0  # streak consumed

    def test_pressure_blip_resets_streak(self):
        pol = ScalePolicy(queue_high_per_replica=4, up_patience=2)
        st = ScaleState()
        decide(self._snap(1, 10), pol, st)
        assert decide(self._snap(1, 1), pol, st) == 1
        assert st.up_streak == 0

    def test_ttft_signal_triggers_up(self):
        pol = ScalePolicy(queue_high_per_replica=1e9,
                          ttft_p95_high_ms=500, up_patience=1)
        st = ScaleState()
        assert decide(self._snap(2, 0, ttft=900), pol, st) == 3

    def test_scale_down_needs_idle_and_patience_and_floor(self):
        pol = ScalePolicy(min_replicas=1, down_patience=3,
                          queue_low_per_replica=0.5, occupancy_low=0.3)
        st = ScaleState()
        idle = self._snap(2, 0, occ=0.1)
        assert decide(idle, pol, st) == 2
        assert decide(idle, pol, st) == 2
        assert decide(idle, pol, st) == 1  # third consecutive: shrink
        st2 = ScaleState()
        one = self._snap(1, 0, occ=0.0)
        for _ in range(10):
            assert decide(one, pol, st2) == 1  # never below min

    def test_busy_but_not_pressured_holds_steady(self):
        pol = ScalePolicy()
        st = ScaleState()
        mid = self._snap(2, 2, occ=0.7)
        for _ in range(10):
            assert decide(mid, pol, st) == 2

    def test_up_capped_at_max(self):
        pol = ScalePolicy(max_replicas=2, up_patience=1,
                          queue_high_per_replica=1)
        st = ScaleState()
        assert decide(self._snap(2, 50), pol, st) == 2


# ---------------------------------------------------------------------------
# ServingFleetAutoScaler (master hook)
# ---------------------------------------------------------------------------


class TestServingFleetAutoScaler:
    def _scaler(self, core):
        from dlrover_tpu.master.job_auto_scaler import (
            ServingFleetAutoScaler,
        )

        class Group:
            min_count = 1
            max_count = 4
            count = 1

        class JobArgs:
            workers = Group()
            node_unit = 1

        class JM:
            def __init__(self):
                self.targets = []
                self.live = 0

            def scale_workers_to(self, n):
                self.targets.append(n)
                return n - self.live

            def alive_workers(self):
                return [object()] * self.live

            def pending_workers(self):
                return []

        jm = JM()
        sc = ServingFleetAutoScaler(JobArgs(), jm, core, interval=999)
        sc._policy.up_patience = 1
        sc._policy.down_patience = 1
        return sc, jm

    def test_scale_up_on_queue_pressure(self):
        core, _ = make_core()
        core.register("r0", 2)
        for i in range(20):
            core.submit(f"q{i}", [i], 4)
        sc, jm = self._scaler(core)
        jm.live = 1
        sc.scale_once()
        assert jm.targets == [2]

    def test_scale_up_held_while_workers_warm_up(self):
        """Launched-but-unregistered workers are capacity on its way:
        pressure must not trigger an absolute scale target computed
        from the REGISTERED count (which could even kill the warming
        workers)."""
        core, _ = make_core()
        core.register("r0", 2)
        for i in range(20):
            core.submit(f"q{i}", [i], 4)
        sc, jm = self._scaler(core)
        jm.live = 3  # 2 workers still warming toward registration
        sc.scale_once()
        assert jm.targets == []

    def test_scale_down_is_two_phase_drain_first(self):
        """Scale-down must never kill a live worker: the manager's
        count drops only after the drained victim deregistered AND its
        worker exit was reaped."""
        core, _ = make_core()
        core.register("r0", 2)
        core.register("r1", 2)
        sc, jm = self._scaler(core)
        jm.live = 2
        sc.scale_once()
        # Phase A: drain only — no scale_workers_to yet.
        assert jm.targets == []
        assert core.stats_snapshot()["replicas_draining"] == 1
        victim = sc._pending_drain[0]
        # Still draining (replica present): every pass holds.
        sc.scale_once()
        assert jm.targets == []
        # Victim deregisters but its worker exit is not yet reaped:
        # still held (an absolute shrink now would kill a live one).
        core.deregister(victim)
        sc.scale_once()
        assert jm.targets == []
        # Worker exit reaped -> phase B: pure-bookkeeping target drop.
        jm.live = 1
        sc.scale_once()
        assert jm.targets == [1]
        assert sc._pending_drain is None

    def test_factory_falls_back_without_gateway_instead_of_crashing(self):
        """dist_master never wires a gateway today: a serving-strategy
        job must still boot (training scaler + loud error), not crash
        the master at startup."""
        from dlrover_tpu.master.job_auto_scaler import (
            AllreduceTrainingAutoScaler,
            ServingFleetAutoScaler,
            new_job_auto_scaler,
        )

        class JobArgs:
            distribution_strategy = "serving"
            workers = None

        sc = new_job_auto_scaler(JobArgs(), None, None)
        assert isinstance(sc, AllreduceTrainingAutoScaler)
        # With a gateway wired, the serving scaler is selected.
        class Group:
            min_count = 1
            max_count = 4

        class ServingJobArgs:
            distribution_strategy = "serving"
            workers = Group()

        core, _ = make_core()
        sc2 = new_job_auto_scaler(
            ServingJobArgs(), None, None, serving_gateway=core
        )
        assert isinstance(sc2, ServingFleetAutoScaler)


def test_gateway_wrapper_injects_ttft_p95_into_snapshot():
    """The autoscaler's ttft_p95_high_ms signal reads ttft_p95_ms off
    the production snapshot — the Gateway wrapper must inject it."""
    from dlrover_tpu.serving import Gateway

    gw = Gateway(port=0)
    try:
        gw.core.observe_ttft_ms(700.0)
        snap = gw.core.stats_snapshot()
        assert snap["ttft_p95_ms"] == 1000.0  # bucket upper bound
        assert "latency_p95_ms" in snap
        # And the signal actually drives decide().
        pol = ScalePolicy(queue_high_per_replica=1e9,
                          ttft_p95_high_ms=500, up_patience=1)
        assert decide(snap, pol, ScaleState()) == 2
    finally:
        gw.stop()


def test_replica_register_survives_dead_gateway():
    """A gateway still booting (or flapping right after a known=False
    poll) must not kill the replica: register is best-effort and the
    next poll retries it."""
    class DeadTransport:
        def call(self, msg, **_kw):
            raise ConnectionError("gateway down")

    runner = ReplicaRunner(FakeDecodeServer(1), DeadTransport(), "r0")
    runner.register()  # must not raise


# ---------------------------------------------------------------------------
# Histogram (gateway latency instrument)
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_percentiles_are_bucket_upper_bounds(self):
        from dlrover_tpu.agent.metrics import Histogram

        h = Histogram(buckets=(10, 100, 1000))
        for _ in range(98):
            h.observe(5)
        h.observe(50)
        h.observe(500)
        assert h.count == 100
        assert h.percentile(0.5) == 10
        assert h.percentile(0.99) == 100
        assert h.percentile(1.0) == 1000

    def test_empty_and_overflow(self):
        from dlrover_tpu.agent.metrics import Histogram

        h = Histogram(buckets=(10,))
        assert h.percentile(0.99) == 0.0
        h.observe(99999)  # beyond the last bound: saturates
        assert h.percentile(0.5) == 10
        assert h.snapshot()["count"] == 1.0

    def test_windowed_histogram_decays_instead_of_ratcheting(self):
        """The autoscaler's TTFT signal must forget a bad warmup
        period: with window_s set, observations older than two windows
        fall out of the percentiles."""
        from dlrover_tpu.agent.metrics import Histogram

        clk = FakeClock()
        h = Histogram(buckets=(10, 1000, 10000), window_s=60.0,
                      clock=clk)
        for _ in range(100):
            h.observe(5000.0)  # terrible cold-start TTFTs
        assert h.percentile(0.95) == 10000
        clk.advance(61.0)
        for _ in range(20):
            h.observe(5.0)  # warm steady state
        # Previous window still in view: p95 still reflects the spike.
        assert h.percentile(0.95) == 10000
        clk.advance(61.0)
        for _ in range(20):
            h.observe(5.0)
        # The spike aged out: only steady-state observations remain.
        assert h.percentile(0.95) == 10
        # Fully idle for 2+ windows: empty, not stale.
        clk.advance(200.0)
        assert h.percentile(0.95) == 0.0
        assert h.count == 0

    def test_register_gauges(self):
        from dlrover_tpu.agent.metrics import (
            Histogram,
            MetricsRegistry,
        )

        h = Histogram()
        reg = MetricsRegistry()
        h.register_gauges(reg, "serve_ttft")
        h.observe(42.0)
        text = reg.render()
        assert "serve_ttft_count 1.0" in text
        assert "serve_ttft_p99_ms 50.0" in text


# ---------------------------------------------------------------------------
# Replica runner protocol (fake decode server, loopback fleet)
# ---------------------------------------------------------------------------


class FakeKvError(ValueError):
    """The runner branches on the duck-typed marker, exactly as it
    does for the real ``llama_infer.KvSegmentError``."""

    KV_REJECT = True


def _fake_segment(prompt, first):
    """A checksummed fake KV payload: enough structure to prove the
    verify-before-decode law without the model stack."""
    import json
    import zlib

    data = json.dumps(
        {"prompt": [int(t) for t in prompt], "first": int(first)}
    ).encode()
    return zlib.crc32(data).to_bytes(4, "big") + data


def _parse_segment(payload):
    import json
    import zlib

    if len(payload) < 4 or \
            zlib.crc32(payload[4:]) != int.from_bytes(payload[:4], "big"):
        raise FakeKvError("fake KV segment CRC mismatch")
    return json.loads(payload[4:])


class FakeDecodeServer:
    """The incremental-admission surface of DecodeServer, with a
    deterministic arithmetic 'decode' (token i of prompt p is
    ``(sum(p) + i) % 97``) — the runner protocol under test, not the
    model."""

    def __init__(self, slots=2):
        self.slots = slots
        self._pending = collections.deque()
        self._active = {}
        self.last_stats = {}
        self.imported = 0

    def submit(self, rid, prompt, mnt, prefix_len=0, prefix_fp=""):
        self._pending.append((rid, [int(t) for t in prompt], int(mnt)))

    def import_kv(self, rid, payload, prompt, mnt):
        """Verify-then-admit: a torn payload raises the duck-typed
        reject error; a clean one enqueues — the fake's arithmetic
        token law makes the result identical to a unified decode, so
        disagg exactness is assertable."""
        seg = _parse_segment(payload)
        if seg["prompt"] != [int(t) for t in prompt]:
            raise FakeKvError("fake KV segment prompt mismatch")
        self.imported += 1
        self._pending.append((rid, [int(t) for t in prompt], int(mnt)))

    def cancel(self, rid):
        for i, item in enumerate(self._pending):
            if item[0] == rid:
                del self._pending[i]
                return True
        return False

    def abort(self, rid):
        if self.cancel(rid):
            return True
        return self._active.pop(rid, None) is not None

    def pending_count(self):
        return len(self._pending)

    def pending_rids(self):
        return [r for r, _, _ in self._pending]

    def active_rids(self):
        return list(self._active)

    def free_slots(self):
        return max(
            0, self.slots - len(self._active) - len(self._pending)
        )

    def serve_incremental(self, tick=None, on_finish=None,
                          on_token=None, idle_wait=0.0005):
        results = {}
        while True:
            keep = tick() is not False if tick else True
            while self._pending and len(self._active) < self.slots:
                rid, p, mnt = self._pending.popleft()
                self._active[rid] = (p, [], mnt)
            if not self._active:
                if not self._pending:
                    if tick is None or not keep:
                        break
                    time.sleep(idle_wait)
                continue
            for rid in list(self._active):
                p, out, mnt = self._active[rid]
                t = (sum(p) + len(out)) % 97
                out.append(t)
                if on_token:
                    on_token(rid, t)
                if len(out) >= mnt:
                    full = list(p) + out
                    results[rid] = full
                    del self._active[rid]
                    if on_finish:
                        on_finish(rid, full)
        return results


class FakePrefillServer(FakeDecodeServer):
    """Prefill-role fake: stages checksummed segments for export; its
    first token matches the decode law's token 0, so the handed-off
    decode reproduces the unified result exactly."""

    def __init__(self, slots=2):
        super().__init__(slots)
        self._exports = {}
        self.prefills = 0

    def prefill_request(self, rid, prompt, mnt, prefix_len=0,
                        prefix_fp=""):
        p = [int(t) for t in prompt]
        first = sum(p) % 97
        self._exports[rid] = _fake_segment(p, first)
        self.prefills += 1
        return first

    def export_kv(self, rid):
        payload = self._exports.pop(rid)
        return payload, len(payload) * 4  # fake fp32 equivalent


def core_handle(core):
    """The Gateway.handle dispatch over a bare core (loopback fleets)."""
    def handle(msg):
        if isinstance(msg, ServeReplicaRegister):
            core.register(msg.replica_id, msg.slots, msg.role)
        elif isinstance(msg, ServeReplicaDeregister):
            core.deregister(msg.replica_id)
        elif isinstance(msg, ServeReplicaPoll):
            return core.poll(msg.replica_id, msg.free_slots,
                             msg.active, msg.stats, msg.warm_prefixes)
        elif isinstance(msg, ServeTokens):
            core.stream(msg.replica_id, msg.req_id, msg.tokens)
        elif isinstance(msg, ServeDone):
            core.complete(msg.replica_id, msg.req_id, msg.tokens,
                          msg.ok, msg.reason, msg.replayed)
        elif isinstance(msg, ServeKvReady):
            core.kv_ready(msg.replica_id, msg.req_id, msg.payload,
                          msg.fp32_bytes, msg.addr, msg.seg_fp,
                          msg.crc32, msg.nbytes)
        elif isinstance(msg, ServeKvReject):
            core.kv_reject(msg.replica_id, msg.req_id, msg.reason)
        return None

    return handle


def make_loopback_fleet(core, n=1, slots=2, tmp=None, poll=0.001):
    """Wire N fake-server runners to a GatewayCore over loopback."""
    transport = LoopbackTransport(core_handle(core))
    runners = []
    for i in range(n):
        journal = f"{tmp}/r{i}.jsonl" if tmp else None
        runners.append(ReplicaRunner(
            FakeDecodeServer(slots), transport, f"r{i}",
            journal_path=journal, poll_interval=poll,
        ))
    return runners


def make_disagg_fleet(core, prefill=1, decode=1, slots=2, tmp=None,
                      poll=0.001):
    """A disaggregated loopback fleet: prefill-role + decode-role
    runners over fake servers.  kv_p2p=False keeps these units on the
    relay plane and socket-free; the P2P plane has its own loopback
    fleets in test_serving_tier.py."""
    transport = LoopbackTransport(core_handle(core))
    runners = []
    for i in range(prefill):
        runners.append(ReplicaRunner(
            FakePrefillServer(slots), transport, f"p{i}",
            poll_interval=poll, role="prefill", kv_p2p=False,
        ))
    for i in range(decode):
        journal = f"{tmp}/d{i}.jsonl" if tmp else None
        runners.append(ReplicaRunner(
            FakeDecodeServer(slots), transport, f"d{i}",
            journal_path=journal, poll_interval=poll, role="decode",
            kv_p2p=False,
        ))
    return runners


def expected_tokens(prompt, mnt):
    return [(sum(int(t) for t in prompt) + i) % 97 for i in range(mnt)]


def wait_for(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


class TestReplicaRunner:
    def test_end_to_end_loopback_fleet(self, tmp_path):
        core = GatewayCore(GatewayConfig())
        (runner,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        th = threading.Thread(target=runner.run, daemon=True)
        th.start()
        for i in range(5):
            core.submit(f"q{i}", [i + 1, i + 2], 4)
        assert wait_for(lambda: core.counters["completed"] == 5)
        for i in range(5):
            st = core.status(f"q{i}")
            assert st.state == "done"
            assert st.tokens == expected_tokens([i + 1, i + 2], 4)
        core.drain("r0")
        th.join(timeout=10)
        assert not th.is_alive()
        assert runner.served == 5
        # Drained replica deregistered itself.
        assert core.stats_snapshot()["replicas_alive"] == 0

    def test_journal_replay_reports_not_redecodes(self, tmp_path):
        core = GatewayCore(GatewayConfig())
        (r1,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        th = threading.Thread(target=r1.run, daemon=True)
        th.start()
        core.submit("a", [3, 4], 4)
        assert wait_for(lambda: core.counters["completed"] == 1)
        core.drain("r0")
        th.join(timeout=10)
        # "Restart": a fresh runner over the same journal; the gateway
        # still remembers the request (dedupe) — the replayed report is
        # dropped, and nothing decodes twice.
        (r2,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        r2.register()
        assert r2.replayed == 1
        assert core.counters["duplicate_completions"] == 1
        assert core.counters["completed"] == 1

    def test_journal_grant_hit_answers_without_decoding(self, tmp_path):
        """A re-dispatched request landing on the SAME restarted
        replica is answered from its journal at grant time."""
        core = GatewayCore(GatewayConfig())
        (r1,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        th = threading.Thread(target=r1.run, daemon=True)
        th.start()
        core.submit("a", [5, 6], 4)
        assert wait_for(lambda: core.counters["completed"] == 1)
        core.drain("r0")
        th.join(timeout=10)
        # Fresh gateway (lost all state) + restarted replica with the
        # old journal: the same request re-submitted must be served
        # from the journal, not re-decoded.
        core2 = GatewayCore(GatewayConfig())
        (r2,) = make_loopback_fleet(core2, 1, tmp=str(tmp_path))
        served_before = r2.served
        th2 = threading.Thread(target=r2.run, daemon=True)
        th2.start()
        core2.submit("a", [5, 6], 4)
        assert wait_for(lambda: core2.counters["completed"] == 1)
        assert core2.status("a").tokens == expected_tokens([5, 6], 4)
        assert r2.served == served_before  # no fresh decode
        assert r2.replayed >= 1
        core2.drain("r0")
        th2.join(timeout=10)

    def test_cancel_sheds_in_flight_slot_via_abort(self):
        """A gateway cancel for a request already decoding frees the
        slot mid-stream instead of letting it run to its budget."""
        class ScriptedTransport:
            def call(self, msg, **_kw):
                if isinstance(msg, ServeReplicaPoll):
                    return ServeGrants(cancel=["a"], known=True)
                return None

        srv = FakeDecodeServer(1)
        runner = ReplicaRunner(srv, ScriptedTransport(), "r0",
                               poll_interval=0.0)
        srv._active["a"] = ([1, 2], [5], 1000000)  # mid-decode
        runner._granted["a"] = {"prompt": [1, 2]}
        assert runner.tick() is True
        assert srv.active_rids() == []  # slot shed
        assert "a" not in runner._granted

    def test_journal_is_bounded_and_compacts(self, tmp_path):
        from dlrover_tpu.serving.replica import CompletionJournal

        path = str(tmp_path / "j.jsonl")
        j = CompletionJournal(path, max_records=8)
        for i in range(8 + 64 + 1):  # crosses the cap+slack threshold
            j.append(f"q{i}", [i], [i, i])
        # Compaction fired at the 72nd append (cap 8 + slack 64),
        # trimming to the newest 8; one more append lands after it.
        assert len(j.replayable()) == 9
        # Oldest dropped, newest kept — on disk too.
        assert j.lookup("q0", [0]) is None
        assert j.lookup("q72", [72]) == [72, 72]
        j.close()
        lines = open(path).read().strip().split("\n")
        assert len(lines) == 9
        # Reload honours the cap (constructor compacts past-cap files)
        # and still replays the survivors.
        j2 = CompletionJournal(path, max_records=8)
        assert len(j2.replayable()) == 8
        assert j2.lookup("q72", [72]) == [72, 72]

    def test_journal_replay_happens_once_per_incarnation(self, tmp_path):
        """A gateway flap (known=False poll -> re-register) must NOT
        re-send the whole journal: replay is once per process start;
        re-dispatched grants hit the journal at grant time instead."""
        core = GatewayCore(GatewayConfig())
        (r1,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        th = threading.Thread(target=r1.run, daemon=True)
        th.start()
        core.submit("a", [3, 4], 4)
        assert wait_for(lambda: core.counters["completed"] == 1)
        core.drain("r0")
        th.join(timeout=10)
        (r2,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        r2.register()
        assert r2.replayed == 1
        r2.register()  # flap: second register of the same incarnation
        assert r2.replayed == 1  # no bulk re-replay

    def test_torn_journal_tail_is_ignored(self, tmp_path):
        from dlrover_tpu.serving.replica import CompletionJournal

        j = CompletionJournal(str(tmp_path / "j.jsonl"))
        j.append("a", [1, 2], [7, 8])
        j.close()
        with open(tmp_path / "j.jsonl", "a") as f:
            f.write('{"rid": "b", "ph": "x", "tok')  # SIGKILL mid-append
        j2 = CompletionJournal(str(tmp_path / "j.jsonl"))
        assert set(j2.replayable()) == {"a"}
        assert j2.lookup("a", [1, 2]) == [7, 8]
        # Prompt-hash mismatch (journal-path reuse): no stale replay.
        assert j2.lookup("a", [9, 9]) is None

    def test_drop_request_chaos_recovers_via_reconcile(self, tmp_path):
        from dlrover_tpu import chaos

        core = GatewayCore(GatewayConfig())
        (runner,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        chaos.configure("serving.drop_request:p=1,times=1,seed=3")
        try:
            th = threading.Thread(target=runner.run, daemon=True)
            th.start()
            core.submit("a", [2, 3], 4)
            # Dropped once, re-dispatched by reconcile, then served.
            assert wait_for(lambda: core.counters["completed"] == 1)
            assert core.counters["redispatched"] >= 1
            assert runner.dropped == 1
            assert core.status("a").tokens == expected_tokens([2, 3], 4)
            core.drain("r0")
            th.join(timeout=10)
        finally:
            chaos.reset()

    def test_cancel_prunes_replica_pending(self):
        """A gateway cancel (deadline expiry) drops a granted request
        still waiting in the replica's pending queue — in-flight work
        is never interrupted, queued work is."""
        class ScriptedTransport:
            def __init__(self):
                self.sent = []

            def call(self, msg, **_kw):
                self.sent.append(msg)
                if isinstance(msg, ServeReplicaPoll):
                    return ServeGrants(cancel=["a"], known=True)
                return None

        srv = FakeDecodeServer(2)
        transport = ScriptedTransport()
        runner = ReplicaRunner(srv, transport, "r0",
                               poll_interval=0.0)
        srv.submit("a", [1, 2], 4)
        runner._granted["a"] = {"prompt": [1, 2]}
        assert runner.tick() is True
        assert srv.pending_count() == 0  # cancelled before admission
        assert "a" not in runner._granted


# ---------------------------------------------------------------------------
# Wire round-trip of the new messages
# ---------------------------------------------------------------------------


class TestFleetAccounting:
    def test_healthy_fleet_accounts_every_request_and_token(self, tmp_path):
        """5 requests x 6 tokens through a healthy replica: every
        request admitted once and finished once, every token of the
        budget streamed and returned, nothing rejected, redispatched,
        failed or timed out."""
        core = GatewayCore(GatewayConfig())
        (runner,) = make_loopback_fleet(core, 1, tmp=str(tmp_path))
        th = threading.Thread(target=runner.run, daemon=True)
        th.start()
        prompts = {f"q{i}": [i + 1, i + 2, i + 3] for i in range(5)}
        for rid, p in prompts.items():
            assert core.submit(rid, p, 6).status == "accepted"
        assert wait_for(lambda: core.counters["completed"] == 5)
        core.drain("r0")
        th.join(timeout=10)
        c = core.counters
        assert c["submitted"] == c["accepted"] == c["completed"] == 5
        for key in ("rejected", "redispatched", "failed", "timeout",
                    "duplicate_completions", "late_completions",
                    "replicas_lost"):
            assert c[key] == 0, key
        for rid, p in prompts.items():
            assert core.status(rid).tokens == expected_tokens(p, 6)
        new = sum(len(core.status(rid).tokens) for rid in prompts)
        assert new == 5 * 6
        assert c["streamed_tokens"] == new
        assert runner.served == 5

    @pytest.mark.parametrize("fingerprinted", [True, False])
    def test_every_prefixed_grant_is_a_hit_a_miss_or_a_steal(
        self, fingerprinted
    ):
        """12 requests over two prefix templates, one warm at a
        one-slot replica and one nobody holds, against a cold replica
        with room: each fingerprinted grant is counted exactly once as
        hit, miss or steal; with the fingerprints withheld the router
        has nothing to route on and counts none."""
        core, _ = make_core()
        core.register("warm", 1)
        core.register("cold", 2)
        core.poll("warm", 1, [], warm_prefixes=["fpA"])
        for i in range(12):
            fp = ("fpA", "fpB")[i % 2] if fingerprinted else ""
            core.submit(f"q{i}", [7, 8, i], 4,
                        prefix_len=2 if fingerprinted else 0,
                        prefix_fp=fp)
        held = {"warm": [], "cold": []}
        slots = {"warm": 1, "cold": 2}
        granted = 0
        for _ in range(40):
            for rid in ("cold", "warm"):
                # finish what the replica holds, then ask for more
                for req in held[rid]:
                    core.complete(rid, req.req_id, [1], True, "", False)
                held[rid] = list(core.poll(
                    rid, slots[rid], [],
                    warm_prefixes=["fpA"] if rid == "warm" else [],
                ).requests)
                granted += len(held[rid])
            if granted == 12:
                break
        assert granted == 12
        c = core.counters
        outcomes = c["prefix_hits"] + c["prefix_misses"] \
            + c["prefix_steals"]
        if fingerprinted:
            assert outcomes == 12
            assert c["prefix_hits"] > 0 and c["prefix_misses"] == 6
        else:
            assert outcomes == 0


def test_serving_messages_roundtrip():
    g = ServeGrants(
        requests=[ServeSubmit(req_id="x", prompt=[1, 2],
                              max_new_tokens=9, deadline_s=1.5)],
        cancel=["y"], drain=True, known=False,
    )
    g2 = deserialize(serialize(g))
    assert isinstance(g2, ServeGrants)
    assert g2.requests[0].prompt == [1, 2]
    assert g2.requests[0].max_new_tokens == 9
    assert g2.cancel == ["y"] and g2.drain and g2.known is False
    d = deserialize(serialize(ServeDone(
        replica_id="r", req_id="x", tokens=[3], replayed=True,
    )))
    assert d.replayed is True and d.tokens == [3]


def test_empty_req_id_is_rejected_terminally():
    """'' is BoundedTokenCache's no-token sentinel: the completion
    would be unrecordable and the client would poll 'unknown' forever."""
    core = GatewayCore(GatewayConfig())
    ack = core.submit("", [1, 2], 4)
    assert ack.status == "failed"
    assert "empty req_id" in ack.reason
    assert core.stats_snapshot()["queue_depth"] == 0


# ---------------------------------------------------------------------------
# Prefix-aware routing (ISSUE 8): the residency map and its guards
# ---------------------------------------------------------------------------


class TestPrefixRouting:
    def test_warm_replica_preferred_cold_defers(self):
        core, _ = make_core()
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        # Cold polls first: the request is reserved for the warm
        # holder (which has capacity, inside the reserve window).
        g = core.poll("cold", 2, [])
        assert g.requests == []
        g = core.poll("warm", 1, [], warm_prefixes=["fpA"])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_hits"] == 1
        assert core.counters["prefix_steals"] == 0

    def test_deferred_prefix_does_not_starve_queue_behind_it(self):
        core, _ = make_core()
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        core.submit("hot", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        core.submit("plain", [5, 6], 4)
        # The cold replica skips the reserved request and takes the
        # plain one behind it.
        g = core.poll("cold", 2, [])
        assert [r.req_id for r in g.requests] == ["plain"]

    def test_saturated_warm_holder_is_stolen_from(self):
        core, _ = make_core()
        core.register("warm", 1)
        core.register("cold", 2)
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        g = core.poll("warm", 1, [], warm_prefixes=["fpA"])
        assert [r.req_id for r in g.requests] == ["a"]  # warm busy now
        core.submit("b", [1, 2, 9], 4, prefix_len=2, prefix_fp="fpA")
        # warm has 1/1 assigned: the overload guard lets cold steal.
        g = core.poll("cold", 2, [])
        assert [r.req_id for r in g.requests] == ["b"]
        assert core.counters["prefix_steals"] == 1

    def test_reserve_window_expiry_steals(self):
        core, clock = make_core(prefix_reserve_s=2.0)
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        assert core.poll("cold", 1, []).requests == []
        clock.advance(3.0)
        g = core.poll("cold", 1, [])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_steals"] == 1

    def test_no_warm_holder_is_plain_miss(self):
        """Fingerprint nobody holds (or a stale fp after journal-path
        reuse): falls straight back to least-loaded, counted a miss."""
        core, _ = make_core()
        core.register("r0", 2)
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpX")
        g = core.poll("r0", 1, [])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_misses"] == 1

    def test_residency_evicted_on_deregister(self):
        core, _ = make_core()
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        core.deregister("warm")
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        # No defer against a dead replica: immediate miss-grant.
        g = core.poll("cold", 1, [])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_misses"] == 1

    def test_residency_evicted_on_lease_expiry(self):
        core, clock = make_core(lease_timeout_s=5.0)
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        clock.advance(3.0)
        core.poll("cold", 0, [])  # cold stays fresh
        clock.advance(3.0)
        core.sweep()  # warm's lease lapsed (6s); cold is 3s fresh
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        g = core.poll("cold", 1, [])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_misses"] == 1

    def test_poll_report_replaces_residency_wholesale(self):
        """LRU eviction on the replica must self-correct the map: the
        next poll stops reporting the fp and the reservation ends."""
        core, _ = make_core()
        core.register("warm", 2)
        core.register("cold", 2)
        core.poll("warm", 0, [], warm_prefixes=["fpA"])
        core.poll("warm", 0, [], warm_prefixes=["fpB"])  # fpA evicted
        core.submit("a", [1, 2, 3], 4, prefix_len=2, prefix_fp="fpA")
        g = core.poll("cold", 1, [])
        assert [r.req_id for r in g.requests] == ["a"]
        assert core.counters["prefix_misses"] == 1

    def test_snapshot_carries_prefix_counters_and_warm_sets(self):
        core, _ = make_core()
        core.register("r0", 2)
        core.poll("r0", 0, [], warm_prefixes=["fpZ"])
        snap = core.stats_snapshot()
        assert snap["replicas"]["r0"]["warm_prefixes"] == ["fpZ"]
        for key in ("prefix_hits", "prefix_misses", "prefix_steals"):
            assert key in snap["counters"]

    def test_runner_reports_server_warm_fps(self):
        """The runner's poll carries the decode server's warm set."""
        polls = []

        class T:
            def call(self, msg, **_kw):
                if isinstance(msg, ServeReplicaPoll):
                    polls.append(msg)
                return None

        srv = FakeDecodeServer(1)
        srv.warm_prefix_fps = lambda: ["fpQ"]
        runner = ReplicaRunner(srv, T(), "r0", poll_interval=0.0)
        runner.tick()
        assert polls and polls[-1].warm_prefixes == ["fpQ"]


# ---------------------------------------------------------------------------
# Prefill/decode disaggregation (ISSUE 8): the two-stage grant path
# ---------------------------------------------------------------------------


class TestDisaggregationCore:
    def test_two_stage_flow(self):
        core, _ = make_core()
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("x", [4, 5, 6], 5)
        assert core.poll("d0", 1, []).requests == []  # decode: no prefill
        g = core.poll("p0", 1, [])
        assert g.requests[0].stage == "prefill"
        assert core.kv_ready("p0", "x", b"SEG", fp32_bytes=40) == \
            "recorded"
        assert core.poll("p0", 1, []).requests == []  # prefill: no decode
        g = core.poll("d0", 1, [])
        assert g.requests[0].stage == "decode"
        assert g.requests[0].kv == b"SEG"
        assert core.complete("d0", "x", [1, 2]) == "recorded"
        c = core.counters
        assert c["kv_handoffs"] == 1 and c["kv_bytes"] == 3
        assert c["kv_fp32_bytes"] == 40

    def test_prefill_withheld_without_decode_capacity(self):
        """A prefill-only fleet must not burn prefills into segments
        nobody can decode."""
        core, _ = make_core()
        core.register("p0", 1, role="prefill")
        core.submit("x", [4], 5)
        assert core.poll("p0", 1, []).requests == []
        core.register("u0", 1, role="unified")
        g = core.poll("p0", 1, [])
        assert g.requests and g.requests[0].stage == "prefill"

    def test_unified_replica_serves_both_stages(self):
        core, _ = make_core()
        core.register("u0", 2, role="unified")
        core.register("p0", 1, role="prefill")
        core.submit("x", [4], 5)
        g = core.poll("p0", 1, [])
        assert g.requests[0].stage == "prefill"
        core.kv_ready("p0", "x", b"S")
        g = core.poll("u0", 1, [])
        assert g.requests[0].stage == "decode" and g.requests[0].kv

    def test_kill_between_prefill_grant_and_kv_ready_requeues(self):
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("y", [7, 8], 5)
        core.poll("p0", 1, [])
        clock.advance(6.0)
        core.poll("d0", 1, [])  # decode lease stays fresh
        clock.advance(5.0)
        core.sweep()  # p0 dead between the stages
        core.register("p1", 1, role="prefill")
        g = core.poll("p1", 1, [])
        # Re-dispatched as a FRESH prefill (no segment existed yet).
        assert g.requests[0].req_id == "y"
        assert g.requests[0].stage == "prefill"
        assert core.counters["redispatched"] == 1

    def test_kill_after_kv_ready_reships_same_segment(self):
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("y", [7, 8], 5)
        core.poll("p0", 1, [])
        core.kv_ready("p0", "y", b"SEG2")
        g = core.poll("d0", 1, [])
        assert g.requests[0].kv == b"SEG2"
        clock.advance(6.0)
        core.poll("p0", 0, [])
        clock.advance(5.0)
        core.sweep()  # d0 dead mid-decode; the segment is NOT lost
        core.register("d1", 1, role="decode")
        g = core.poll("d1", 1, [])
        assert g.requests[0].stage == "decode"
        assert g.requests[0].kv == b"SEG2"
        assert core.complete("d1", "y", [3]) == "recorded"

    def test_stale_kv_ready_from_superseded_prefill_dropped(self):
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("y", [7], 5)
        core.poll("p0", 1, [])
        clock.advance(6.0)
        core.poll("d0", 1, [])
        clock.advance(5.0)
        core.sweep()
        core.register("p1", 1, role="prefill")
        core.poll("p1", 1, [])  # y re-granted to p1
        # Zombie p0 finally reports its segment: dropped.
        assert core.kv_ready("p0", "y", b"ZOMBIE") == "stale"
        core.kv_ready("p1", "y", b"LIVE")
        g = core.poll("d0", 1, [])
        assert g.requests[0].kv == b"LIVE"

    def test_stale_kv_reject_from_superseded_decode_dropped(self):
        """A stalled decode replica rejecting AFTER the lease machinery
        re-granted the segment elsewhere must not tear down the live
        assignment (nor burn attempts on a healthy request)."""
        core, clock = make_core(lease_timeout_s=10.0)
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("y", [7], 5)
        core.poll("p0", 1, [])
        core.kv_ready("p0", "y", b"SEG")
        core.poll("d0", 1, [])  # d0 granted, then stalls
        clock.advance(6.0)
        core.poll("p0", 0, [])
        clock.advance(5.0)
        core.sweep()  # d0 presumed dead; segment kept
        core.register("d1", 1, role="decode")
        g = core.poll("d1", 1, [])
        assert g.requests and g.requests[0].kv == b"SEG"
        # Zombie d0 finally rejects: dropped, d1's decode undisturbed.
        assert core.kv_reject("d0", "y", "late") == "stale"
        assert core.counters["kv_rejects"] == 0
        assert core.status("y").state == "running"
        assert core.complete("d1", "y", [3]) == "recorded"

    def test_torn_segments_fail_terminally_after_max_attempts(self):
        """kv_reject re-prefills, bounded: never hangs, never decodes
        a torn segment."""
        core, _ = make_core(max_attempts=3)
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("z", [9], 5)
        for _ in range(3):
            g = core.poll("p0", 1, [])
            assert g.requests and g.requests[0].stage == "prefill"
            core.kv_ready("p0", "z", b"TORN")
            g = core.poll("d0", 1, [])
            assert g.requests and g.requests[0].req_id == "z"
            core.kv_reject("d0", "z", "crc mismatch")
        st = core.status("z")
        assert st.state == "failed" and "re-dispatched" in st.reason
        assert core.counters["kv_rejects"] == 3

    def test_pools_in_snapshot(self):
        core, _ = make_core()
        core.register("p0", 2, role="prefill")
        core.register("d0", 4, role="decode")
        core.submit("a", [1], 4)
        core.submit("b", [2], 4)
        g = core.poll("p0", 1, [])
        assert g.requests[0].req_id == "a"
        core.kv_ready("p0", "a", b"S")
        snap = core.stats_snapshot()
        pools = snap["pools"]
        assert pools["prefill"]["alive"] == 1
        assert pools["decode"]["alive"] == 1
        # 'b' is stage-queued (feeds the prefill pool); 'a' is a held
        # segment awaiting decode capacity (feeds the decode pool).
        assert pools["prefill"]["queue_depth"] == 1
        assert pools["decode"]["queue_depth"] == 1
        assert snap["queue_prefill"] == 1
        assert snap["queue_kv_ready"] == 1


class TestDisaggFleet:
    """Runner-level loopback fleets over the fake servers."""

    def _run(self, core, runners):
        threads = []
        for runner in runners:
            th = threading.Thread(target=runner.run, daemon=True)
            th.start()
            threads.append(th)
        return threads

    def _stop(self, core, runners, threads):
        for runner in runners:
            core.drain(runner.replica_id)
        for th in threads:
            th.join(timeout=10)
            assert not th.is_alive()

    def test_disagg_results_match_unified_law(self, tmp_path):
        core = GatewayCore(GatewayConfig())
        runners = make_disagg_fleet(core, prefill=1, decode=1,
                                    tmp=str(tmp_path))
        threads = self._run(core, runners)
        try:
            for i in range(6):
                core.submit(f"q{i}", [i + 1, i + 2], 4)
            assert wait_for(lambda: core.counters["completed"] == 6)
            for i in range(6):
                st = core.status(f"q{i}")
                assert st.state == "done"
                assert st.tokens == expected_tokens([i + 1, i + 2], 4)
            c = core.counters
            assert c["kv_handoffs"] == 6 and c["kv_rejects"] == 0
            assert c["kv_bytes"] > 0
        finally:
            self._stop(core, runners, threads)

    def test_kv_drop_at_export_recovers_via_reconcile(self, tmp_path):
        from dlrover_tpu import chaos

        core = GatewayCore(GatewayConfig(lease_timeout_s=0.5))
        runners = make_disagg_fleet(core, prefill=1, decode=1,
                                    tmp=str(tmp_path))
        chaos.configure("serving.kv_drop:method=export,times=1,seed=3")
        try:
            threads = self._run(core, runners)
            core.submit("a", [2, 3], 4)
            assert wait_for(lambda: core.counters["completed"] == 1)
            assert core.status("a").tokens == expected_tokens([2, 3], 4)
            assert runners[0].dropped == 1
            assert core.counters["redispatched"] >= 1
            self._stop(core, runners, threads)
        finally:
            chaos.reset()

    def test_kv_drop_at_import_reprefills_then_completes(self,
                                                         tmp_path):
        from dlrover_tpu import chaos

        core = GatewayCore(GatewayConfig())
        runners = make_disagg_fleet(core, prefill=1, decode=1,
                                    tmp=str(tmp_path))
        chaos.configure("serving.kv_drop:method=import,times=1,seed=3")
        try:
            threads = self._run(core, runners)
            core.submit("a", [2, 3], 4)
            assert wait_for(lambda: core.counters["completed"] == 1)
            assert core.status("a").tokens == expected_tokens([2, 3], 4)
            c = core.counters
            assert c["kv_rejects"] == 1
            assert c["kv_handoffs"] == 2  # torn once, re-prefilled
            assert runners[1].kv_rejected == 1
            self._stop(core, runners, threads)
        finally:
            chaos.reset()

    def test_always_torn_fails_terminally_never_hangs(self, tmp_path):
        from dlrover_tpu import chaos

        core = GatewayCore(GatewayConfig(max_attempts=3))
        runners = make_disagg_fleet(core, prefill=1, decode=1,
                                    tmp=str(tmp_path))
        chaos.configure(
            "serving.kv_drop:method=import,times=-1,seed=3"
        )
        try:
            threads = self._run(core, runners)
            core.submit("a", [2, 3], 4)
            assert wait_for(
                lambda: core.status("a").state == "failed"
            )
            assert "re-dispatched" in core.status("a").reason
            assert core.counters["completed"] == 0
            self._stop(core, runners, threads)
        finally:
            chaos.reset()


# ---------------------------------------------------------------------------
# Per-role pool autoscale (ISSUE 8)
# ---------------------------------------------------------------------------


class TestPoolAutoscale:
    def _pools(self, prefill, decode):
        return {"pools": {
            "prefill": prefill, "decode": decode,
        }}

    def test_independent_signals(self):
        policies = {
            "prefill": ScalePolicy(up_patience=1,
                                   queue_high_per_replica=2),
            "decode": ScalePolicy(up_patience=1, down_patience=2,
                                  queue_high_per_replica=2),
        }
        states = {}
        snap = self._pools(
            {"alive": 1, "queue_depth": 10, "occupancy": 1.0},
            {"alive": 2, "queue_depth": 0, "occupancy": 0.1},
        )
        t = decide_pools(snap, policies, states)
        assert t["prefill"] == 2  # pressure
        assert t["decode"] == 2  # down_patience not yet consumed
        t = decide_pools(snap, policies, states)
        assert t["decode"] == 1  # second idle pass shrinks decode

    def test_ttft_signal_reaches_prefill_not_decode(self):
        policies = {
            role: ScalePolicy(up_patience=1, ttft_p95_high_ms=500,
                              queue_high_per_replica=1e9)
            for role in ("prefill", "decode")
        }
        snap = self._pools(
            {"alive": 1, "queue_depth": 0, "occupancy": 0.5},
            {"alive": 1, "queue_depth": 0, "occupancy": 0.5},
        )
        snap["ttft_p95_ms"] = 900.0
        t = decide_pools(snap, policies, {})
        # Admission latency is the prefill pool's signal.
        assert t["prefill"] == 2
        assert t["decode"] == 1

    def test_pool_autoscaler_actuates_per_role(self):
        ups = []
        drains = []
        snap = self._pools(
            {"alive": 1, "queue_depth": 10, "occupancy": 1.0},
            {"alive": 3, "queue_depth": 0, "occupancy": 0.0},
        )
        sc = PoolAutoScaler(
            snapshot_fn=lambda: snap,
            scale_up_fn=lambda role, n: ups.append((role, n)),
            drain_fn=lambda role: drains.append(role),
            policies={
                "prefill": ScalePolicy(up_patience=1,
                                       queue_high_per_replica=2),
                "decode": ScalePolicy(down_patience=1),
            },
        )
        deltas = sc.scale_once()
        assert ups == [("prefill", 1)]
        assert drains == ["decode"]
        assert deltas == {"prefill": 1, "decode": -1}

    def test_gateway_pick_drain_victim_by_role(self):
        core, _ = make_core()
        core.register("p0", 2, role="prefill")
        core.register("d0", 2, role="decode")
        core.register("d1", 2, role="decode")
        assert core.pick_drain_victim(role="prefill") == "p0"
        assert core.pick_drain_victim(role="decode") == "d0"
        core.drain("d0")
        assert core.pick_drain_victim(role="decode") == "d1"


def test_journal_eager_replay_is_capped(tmp_path):
    """Restart replay must not storm the gateway with one RPC per
    journal record (a full journal would stall polls past the lease):
    only the newest replay_limit records replay eagerly."""
    from dlrover_tpu.serving.replica import CompletionJournal

    path = str(tmp_path / "j.jsonl")
    j = CompletionJournal(path)
    for i in range(40):
        j.append(f"q{i}", [i], [i])
    j.close()

    sent = []

    class T:
        def call(self, msg, **_kw):
            sent.append(msg)
            return None

    runner = ReplicaRunner(FakeDecodeServer(1), T(), "r0",
                           journal_path=path, replay_limit=10)
    runner.register()
    dones = [m for m in sent if isinstance(m, ServeDone)]
    assert len(dones) == 10
    # Newest records replay; the older ones answer via grant-time
    # lookup instead.
    assert {m.req_id for m in dones} == {f"q{i}" for i in range(30, 40)}


def test_every_core_counter_is_exported_as_a_gauge():
    """ISSUE 14 (graftcheck MT601): the admission/exactly-once
    counters (submitted/completed/failed/timeout/...) were visible
    only via the stats-snapshot RPC — /metrics showed none of them.
    Every GatewayCore counter now has a ``serve_<name>`` gauge."""
    from dlrover_tpu.agent.metrics import MetricsRegistry
    from dlrover_tpu.serving.gateway import Gateway

    gw = Gateway(port=0)
    try:
        reg = MetricsRegistry()
        gw.register_gauges(reg)
        core = gw.core
        core.register("r0", slots=2)
        core.submit("rq1", [1, 2, 3], 4, 0.0)
        body = reg.render()
        for name in core.counters:
            assert f"serve_{name} " in body, (
                f"counter {name!r} has no serve_{name} gauge"
            )
        # And the fix's headline signals carry real values.
        assert "serve_submitted 1.0" in body
        assert "serve_accepted 1.0" in body
    finally:
        gw.stop(grace=0.1)


# ---------------------------------------------------------------------------
# Paged KV at the serving layer (ISSUE 19): memory gate, snapshot
# gauges, autoscale memory-pressure signal
# ---------------------------------------------------------------------------


class TestPagedKvServing:
    def test_exhausted_block_pool_gates_grants_despite_free_slots(self):
        core, _ = make_core()
        core.register("r0", 4)
        assert core.submit("a", [1, 2], 4).status == "accepted"
        # Free SLOTS but zero free BLOCKS: granting would only queue
        # (or preempt) replica-side, so the poll comes back empty.
        g = core.poll("r0", 4, [],
                      stats={"total_blocks": 8, "free_blocks": 0})
        assert g.requests == []
        # Blocks freed (a finish or abort replica-side): the very same
        # request is granted on the next poll.
        g = core.poll("r0", 4, [],
                      stats={"total_blocks": 8, "free_blocks": 3})
        assert [r.req_id for r in g.requests] == ["a"]

    def test_dense_replica_stats_never_trip_the_gate(self):
        core, _ = make_core()
        core.register("r0", 2)
        core.submit("a", [1], 4)
        # A slotted replica reports no block gauges (total_blocks 0 /
        # absent): the gate must stay out of its way.
        g = core.poll("r0", 2, [], stats={"occupancy": 0.5})
        assert [r.req_id for r in g.requests] == ["a"]

    def test_snapshot_carries_block_gauges_and_kv_occupancy(self):
        core, _ = make_core()
        core.register("d0", 2, role="decode")
        core.register("d1", 2, role="decode")
        core.poll("d0", 2, [], stats={
            "kv_occupancy": 0.75, "total_blocks": 8, "free_blocks": 2,
        })
        core.poll("d1", 2, [], stats={
            "kv_occupancy": 0.25, "total_blocks": 8, "free_blocks": 6,
        })
        snap = core.stats_snapshot()
        pool = snap["pools"]["decode"]
        assert pool["kv_occupancy"] == pytest.approx(0.5)
        assert pool["total_blocks"] == 16
        assert pool["free_blocks"] == 8
        # Fleet roll-up: slot-weighted mean of the reported values.
        assert snap["kv_occupancy"] == pytest.approx(0.5)

    def test_kv_occupancy_falls_back_to_slot_fraction(self):
        core, _ = make_core()
        core.register("r0", 2)
        core.submit("a", [1], 4)
        g = core.poll("r0", 2, [])
        assert len(g.requests) == 1
        snap = core.stats_snapshot()
        # One of two slots assigned, nobody reporting kv_occupancy:
        # the gauge degrades to the slot fraction — continuous across
        # the paged-flag flip, so hysteresis never sees a step.
        assert snap["kv_occupancy"] == pytest.approx(0.5)
        assert snap["pools"]["unified"]["kv_occupancy"] == \
            pytest.approx(0.5)

    def test_mem_high_occupancy_scales_up_on_block_pressure(self):
        # Queue empty, slot occupancy moderate — but the block pool is
        # nearly full.  Only the memory signal sees this pressure.
        snap = {"replicas_alive": 2, "queue_depth": 0,
                "occupancy": 0.5, "kv_occupancy": 0.95}
        pol = ScalePolicy(max_replicas=4, up_patience=1,
                          mem_high_occupancy=0.8)
        assert decide(snap, pol, ScaleState()) == 3
        # Default 0.0 = signal off: identical snapshot holds steady.
        assert decide(snap, ScalePolicy(max_replicas=4, up_patience=1),
                      ScaleState()) == 2

    def test_decide_prefers_kv_occupancy_over_slot_fraction(self):
        # Slot fraction says idle; the block pool says otherwise — the
        # memory gauge wins, suppressing the scale-down.
        pol = ScalePolicy(min_replicas=1, down_patience=1,
                          queue_low_per_replica=0.5, occupancy_low=0.3)
        busy = {"replicas_alive": 2, "queue_depth": 0,
                "occupancy": 0.1, "kv_occupancy": 0.9}
        assert decide(busy, pol, ScaleState()) == 2
        idle = {"replicas_alive": 2, "queue_depth": 0,
                "occupancy": 0.1, "kv_occupancy": 0.1}
        assert decide(idle, pol, ScaleState()) == 1

    def test_decide_pools_carries_kv_occupancy_through(self):
        policies = {"decode": ScalePolicy(max_replicas=4, up_patience=1,
                                          mem_high_occupancy=0.8)}
        states = {}
        snap = {
            "ttft_p95_ms": 0.0,
            "pools": {
                "decode": {"alive": 2, "queue_depth": 0,
                           "occupancy": 0.5, "kv_occupancy": 0.95},
            },
        }
        targets = decide_pools(snap, policies, states)
        assert targets["decode"] == 3
