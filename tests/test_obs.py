"""Fleet flight recorder tests (ISSUE 12).

Covers the obs package itself (spans, bounded ring, dumps, collector,
postmortem), the wire contract (trace fields are byte-invisible until
used), the gateway's phase-tiling law (phases sum EXACTLY to measured
TTFT/latency), replica-side span propagation, trace continuity across
failover resubmit and journal replay (original trace id, replays as
spans — never duplicate traces), and the metrics-registry satellites.
All jax-free and tier-1.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import subprocess
import sys
import threading
import time

import msgpack
import pytest

from dlrover_tpu import chaos, obs
from dlrover_tpu.common import messages as wire
from dlrover_tpu.obs import collect, postmortem
from dlrover_tpu.obs.recorder import FlightRecorder
from dlrover_tpu.serving.gateway import GatewayConfig, GatewayCore
from dlrover_tpu.serving.replica import ReplicaRunner
from dlrover_tpu.serving.gateway import LoopbackTransport
from dlrover_tpu.serving.tier import ServeRegistry, TierClient, \
    TierReplicaLink

from test_serving import (  # noqa: I100 - shared fleet fixtures
    FakeDecodeServer,
    core_handle,
    expected_tokens,
)
from test_serving_tier import _Tier, full_handle  # noqa: I100

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    obs.reset()
    chaos.reset()
    yield
    obs.reset()
    chaos.reset()


def _dumps_of(events, process="p0", pid=1):
    return [{"meta": {"process": process, "pid": pid},
             "events": events}]


# ---------------------------------------------------------------------------
# Wire contract
# ---------------------------------------------------------------------------


class TestTraceWireCompat:
    def test_traceless_messages_are_byte_identical_to_legacy(self):
        """The msgpack fast path's bytes must not change for messages
        that carry no trace: the legacy encoding is every field the
        message had before it grew its ``_WIRE_OPTIONAL`` ones (``trace``,
        and since ISSUE 17 ``spill_from``/``spill_hops``), which stay off
        the wire while at their defaults — rebuilt by hand here from the
        message's own declaration and compared."""
        for msg in (
            wire.ServeSubmit(req_id="r", prompt=[1, 2, 3],
                             max_new_tokens=7, prefix_len=2,
                             prefix_fp="fp"),
            wire.ServeDone(replica_id="x", req_id="r",
                           tokens=[4, 5], tokens_per_round=2.5,
                           spec_rounds=3),
            wire.ServeKvReady(replica_id="x", req_id="r",
                              payload=b"kv", fp32_bytes=8,
                              addr="a:1", seg_fp="s", crc32=9,
                              nbytes=2),
        ):
            legacy = {
                "__msg__": type(msg).__name__,
                "f": {
                    f.name: getattr(msg, f.name)
                    for f in dataclasses.fields(msg)
                    if f.name not in type(msg)._WIRE_OPTIONAL
                },
            }
            assert "trace" in type(msg)._WIRE_OPTIONAL
            assert not any(
                getattr(msg, name) for name in type(msg)._WIRE_OPTIONAL)
            got = wire.serialize(msg)
            assert got == msgpack.packb(legacy, use_bin_type=True)
            assert b"trace" not in got
            # The slow-walk baseline stays byte-identical too.
            assert got == wire.serialize_baseline(msg)

    def test_trace_round_trips_when_present(self):
        ctx = {"tid": "t" * 16, "sid": "s" * 16}
        for msg in (
            wire.ServeSubmit(req_id="r", trace=dict(ctx)),
            wire.ServeDone(req_id="r", trace={"tid": ctx["tid"]}),
            wire.ServeKvReady(req_id="r", trace=dict(ctx)),
        ):
            back = wire.deserialize(wire.serialize(msg))
            assert back.trace == msg.trace
            assert wire.serialize(msg) == wire.serialize_baseline(msg)

    def test_missing_trace_decodes_to_default(self):
        msg = wire.ServeSubmit(req_id="r", prompt=[9])
        back = wire.deserialize(wire.serialize(msg))
        assert back.trace == {} and back.prompt == [9]

    def test_obs_scrape_messages_round_trip(self):
        reply = wire.ObsScrape(
            process="gw-g0",
            events=[{"k": "ev", "kind": "x", "ts": 1.0, "seq": 1}],
            dropped=3, next_seq=7,
        )
        back = wire.deserialize(wire.serialize(reply))
        assert back.events[0]["kind"] == "x"
        assert back.dropped == 3 and back.next_seq == 7


# ---------------------------------------------------------------------------
# Recorder / span layer
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_drops_are_counted(self):
        rec = FlightRecorder(capacity=8, process="p")
        for i in range(20):
            rec.event("noise", i=i)
        events, dropped, next_seq = rec.snapshot()
        assert len(events) == 8
        assert dropped == 12 and rec.dropped == 12
        assert next_seq == 20
        # The ring keeps the NEWEST events (the last seconds).
        assert [e["i"] for e in events] == list(range(12, 20))

    def test_snapshot_cursor_is_incremental(self):
        rec = FlightRecorder(capacity=64)
        rec.event("a")
        _, _, cursor = rec.snapshot()
        rec.event("b")
        events, _, cursor2 = rec.snapshot(since_seq=cursor)
        assert [e["kind"] for e in events] == ["b"]
        assert cursor2 == cursor + 1

    def test_dump_and_load_round_trip(self, tmp_path):
        rec = FlightRecorder(capacity=64, process="gw-g7",
                             out_dir=str(tmp_path))
        t = time.monotonic()
        rec.span("gw.request", "gateway", t, t + 0.01,
                 trace_id="abc", args={"terminal": True,
                                       "state": "done"})
        rec.event("fleet.reconcile", role="training", delta=-1)
        path = rec.dump(reason="sigterm")
        assert path is not None and os.path.exists(path)
        dump = collect.load_dump(path)
        assert dump["meta"]["process"] == "gw-g7"
        assert dump["meta"]["reason"] == "sigterm"
        assert dump["meta"]["events"] == 2
        kinds = [(e.get("k"), e.get("name") or e.get("kind"))
                 for e in dump["events"]]
        assert ("span", "gw.request") in kinds
        assert ("ev", "fleet.reconcile") in kinds

    def test_dump_without_out_dir_is_noop(self):
        rec = FlightRecorder(capacity=4)
        rec.event("x")
        assert rec.dump() is None

    def test_trace_id_is_derived_and_stable(self):
        a = obs.trace_id_for("req-1")
        assert a == obs.trace_id_for("req-1")
        assert a != obs.trace_id_for("req-2")
        assert len(a) == 16

    def test_journal_and_record_span_use_process_recorder(self):
        obs.configure(process="unit")
        obs.journal("test.kind", x=1)
        obs.record_span("s", "c", 0.0, 0.001)
        stats = obs.get_recorder().stats()
        assert stats["events"] == 1 and stats["spans"] == 1

    def test_chaos_crash_spills_dump_naming_the_site(self, tmp_path):
        """A chaos crash is SIGKILL-for-everyone except the flight
        recorder: the pre-exit hook spills the ring with the injected
        site in the header.  Run in a real subprocess so os._exit and
        the dump are the real thing."""
        code = (
            "from dlrover_tpu import chaos, obs\n"
            f"obs.configure(out_dir={str(tmp_path)!r}, "
            "process='victim')\n"
            "obs.journal('held.request', rid='req-9')\n"
            "chaos.configure('worker.kill:rank=0')\n"
            "chaos.inject('worker.kill', rank=0)\n"
            "raise SystemExit('crash site did not fire')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, timeout=60,
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == chaos.EXIT_WORKER_KILL, proc.stderr
        dumps = collect.load_dir(str(tmp_path))
        assert len(dumps) == 1
        meta = dumps[0]["meta"]
        assert meta["reason"] == "chaos"
        assert meta["chaos_site"] == "worker.kill"
        kinds = [e.get("kind") for e in dumps[0]["events"]]
        # The injection itself was journaled before the exit, and the
        # ring's prior contents survived the crash.
        assert "chaos.inject" in kinds
        assert "held.request" in kinds

    def test_live_scrape_over_gateway_handle(self):
        from dlrover_tpu.serving.gateway import Gateway

        obs.configure(process="gw-live")
        obs.journal("probe", n=1)
        gw = Gateway(port=0)
        try:
            reply = gw.handle(wire.ObsScrapeRequest())
            assert isinstance(reply, wire.ObsScrape)
            assert reply.process == "gw-live"
            assert any(e.get("kind") == "probe" for e in reply.events)
            # Incremental scrape resumes at the cursor.
            again = gw.handle(
                wire.ObsScrapeRequest(since_seq=reply.next_seq)
            )
            assert again.events == []
        finally:
            gw.stop(0.0)


# ---------------------------------------------------------------------------
# Gateway phase tiling
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


class TestGatewayTracing:
    def _core(self, **kw):
        clock = FakeClock()
        core = GatewayCore(GatewayConfig(**kw), clock=clock)
        return core, clock

    def test_phases_tile_ttft_and_latency_exactly(self):
        rec = obs.configure(process="gw-unit")
        core, clock = self._core()
        core.register("r0", 2)
        core.submit("req-1", [1, 2], 8)
        clock.advance(0.5)
        grants = core.poll("r0", 2, []).requests
        assert grants[0].trace == {
            "tid": obs.trace_id_for("req-1"),
            "sid": grants[0].trace["sid"],
        }
        clock.advance(0.3)
        core.stream("r0", "req-1", [5])
        clock.advance(0.4)
        core.complete("r0", "req-1", [5, 6])
        events, _, _ = rec.snapshot()
        rep = collect.validate_traces(_dumps_of(events))
        assert rep["total"] == 1 and rep["ok"] == 1
        tr = rep["traces"][obs.trace_id_for("req-1")]
        assert tr["terminal_spans"] == 1
        # EXACT tiling (one clock): 0.5 queue_wait + 0.3 exec = TTFT,
        # + 0.4 decode_stream = latency.
        assert tr["ttft_phase_sum_us"] == pytest.approx(8e5)
        assert tr["phase_sum_us"] == pytest.approx(1.2e6)
        assert tr["latency_us"] == pytest.approx(1.2e6)
        names = [e["name"] for e in events if e["k"] == "span"]
        assert names.count("gw.request") == 1
        assert "gw.queue_wait" in names
        assert "gw.exec_to_first_token" in names
        assert "gw.decode_stream" in names
        assert "gw.grant_scan" in names

    def test_lost_grant_phase_is_named_and_tiling_survives(self):
        rec = obs.configure(process="gw-unit")
        core, clock = self._core(lease_timeout_s=1.0)
        core.register("r0", 2)
        core.submit("req-1", [1], 4)
        clock.advance(0.2)
        core.poll("r0", 2, [])
        # Two polls without the rid in the owned set: lost in flight.
        clock.advance(0.1)
        core.poll("r0", 2, [])
        clock.advance(0.1)
        core.poll("r0", 2, [])
        # Re-granted on the SAME poll pass above; now finish it.
        clock.advance(0.3)
        core.stream("r0", "req-1", [3])
        core.complete("r0", "req-1", [3])
        events, _, _ = rec.snapshot()
        names = [e["name"] for e in events if e["k"] == "span"]
        assert "gw.exec_lost" in names
        rep = collect.validate_traces(_dumps_of(events))
        assert rep["ok"] == 1, rep

    def test_unsampled_request_emits_nothing_and_is_counted(self):
        rec = obs.configure(process="gw-unit")
        core, clock = self._core(trace_sample=0.0)
        core.register("r0", 2)
        core.submit("req-1", [1], 4)
        clock.advance(0.1)
        grants = core.poll("r0", 2, []).requests
        assert grants[0].trace == {}
        core.complete("r0", "req-1", [1])
        events, _, _ = rec.snapshot()
        assert [e for e in events if e["k"] == "span"] == []
        c = core.counters
        assert c["trace_unsampled"] == 1 and c["trace_sampled"] == 0

    def test_sampling_is_deterministic_across_gateways(self):
        core_a, _ = self._core(trace_sample=0.5)
        core_b, _ = self._core(trace_sample=0.5)
        for i in range(40):
            rid = f"req-{i}"
            core_a.submit(rid, [1], 4)
            core_b.submit(rid, [1], 4)
        ca, cb = core_a.counters, core_b.counters
        assert ca["trace_sampled"] == cb["trace_sampled"]
        assert ca["trace_unsampled"] == cb["trace_unsampled"]
        assert 0 < ca["trace_sampled"] < 40

    def test_active_chaos_plan_forces_sampling(self):
        chaos.configure("serving.drop_request:times=0")
        core, _ = self._core(trace_sample=0.0)
        core.submit("req-1", [1], 4)
        assert core.counters["trace_sampled"] == 1

    def test_client_supplied_trace_is_adopted(self):
        core, clock = self._core(trace_sample=0.0)
        core.submit("req-1", [1], 4, trace={"tid": "forced-tid"})
        clock.advance(0.1)
        grants = core.poll("r0", 2, []).requests if core.register(
            "r0", 2
        ) is None else []
        grants = grants or core.poll("r0", 2, []).requests
        assert grants[0].trace["tid"] == "forced-tid"

    def test_disagg_phases_tile_through_kv_handoff(self):
        rec = obs.configure(process="gw-unit")
        core, clock = self._core()
        core.register("p0", 1, role="prefill")
        core.register("d0", 1, role="decode")
        core.submit("req-1", [1, 2], 4)
        clock.advance(0.2)
        g = core.poll("p0", 1, []).requests
        assert g and g[0].stage == "prefill"
        clock.advance(0.3)
        core.kv_ready("p0", "req-1", b"seg", fp32_bytes=12)
        clock.advance(0.1)
        g2 = core.poll("d0", 1, []).requests
        assert g2 and g2[0].stage == "decode"
        assert g2[0].trace["tid"] == obs.trace_id_for("req-1")
        clock.advance(0.2)
        core.stream("d0", "req-1", [7])
        clock.advance(0.1)
        core.complete("d0", "req-1", [7, 8])
        events, _, _ = rec.snapshot()
        names = [e["name"] for e in events if e["k"] == "span"]
        assert "gw.prefill_exec" in names and "gw.kv_wait" in names
        rep = collect.validate_traces(_dumps_of(events))
        assert rep["ok"] == 1, rep
        tr = rep["traces"][obs.trace_id_for("req-1")]
        assert tr["latency_us"] == pytest.approx(0.9e6)
        assert tr["ttft_phase_sum_us"] == pytest.approx(0.8e6)


# ---------------------------------------------------------------------------
# Replica-side spans + journal replay continuity
# ---------------------------------------------------------------------------


def _drive_fleet(core, runner, rids):
    th = threading.Thread(target=runner.run, daemon=True)
    th.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if all(
            core.status(r).state in ("done", "failed") for r in rids
        ):
            break
        time.sleep(0.005)
    core.drain(runner.replica_id)
    th.join(timeout=20)
    assert not th.is_alive()


def _trace_handle(core):
    """core_handle + the trace-carrying routes the obs tests need."""
    base = full_handle(core)

    def handle(msg):
        if isinstance(msg, wire.ServeDone):
            core.complete(msg.replica_id, msg.req_id, msg.tokens,
                          msg.ok, msg.reason, msg.replayed,
                          msg.tokens_per_round, msg.spec_rounds,
                          msg.trace)
            return None
        return base(msg)

    return handle


class TestReplicaTracing:
    def test_replica_spans_and_journal_carry_the_trace(self, tmp_path):
        rec = obs.configure(process="rep-unit")
        core = GatewayCore(GatewayConfig())
        runner = ReplicaRunner(
            FakeDecodeServer(slots=2),
            LoopbackTransport(_trace_handle(core)),
            "r0", journal_path=str(tmp_path / "j.jsonl"),
            poll_interval=0.001,
        )
        core.submit("req-1", [3, 4], 5)
        _drive_fleet(core, runner, ["req-1"])
        assert core.status("req-1").tokens == expected_tokens(
            [3, 4], 5
        )
        tid = obs.trace_id_for("req-1")
        events, _, _ = rec.snapshot()
        spans = [e for e in events if e["k"] == "span"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["rep.prefill"]["tid"] == tid
        assert by_name["rep.decode"]["tid"] == tid
        # Decode-round spans rode the process lane while traced work
        # was in flight.
        assert any(s["name"] == "rep.decode_round" for s in spans)
        # The journal record carries the trace id for replay.
        recs = [json.loads(line) for line in
                open(tmp_path / "j.jsonl")]
        assert recs[0]["rid"] == "req-1" and recs[0]["tr"] == tid

    def test_journal_replay_joins_original_trace(self, tmp_path):
        """Replica killed after completing, gateway restarted blank:
        the re-dispatched grant answers from the journal — the replay
        must JOIN the original trace (same tid), be visible as replay
        spans, and converge on exactly one effective terminal."""
        rec1 = obs.configure(process="gw-g1")
        core1 = GatewayCore(GatewayConfig())
        runner1 = ReplicaRunner(
            FakeDecodeServer(slots=2),
            LoopbackTransport(_trace_handle(core1)),
            "r0", journal_path=str(tmp_path / "j.jsonl"),
            poll_interval=0.001,
        )
        core1.submit("req-1", [3, 4], 5)
        _drive_fleet(core1, runner1, ["req-1"])
        events1, _, _ = rec1.snapshot()

        # Fresh gateway + fresh runner INCARNATION over the same
        # journal (the replica "restarted"); the client resubmits.
        rec2 = obs.configure(process="gw-g2")
        core2 = GatewayCore(GatewayConfig())
        runner2 = ReplicaRunner(
            FakeDecodeServer(slots=2),
            LoopbackTransport(_trace_handle(core2)),
            "r0", journal_path=str(tmp_path / "j.jsonl"),
            poll_interval=0.001,
        )
        core2.submit("req-1", [3, 4], 5)
        _drive_fleet(core2, runner2, ["req-1"])
        assert runner2.replayed >= 1
        assert runner2.served == 0  # never re-decoded
        events2, _, _ = rec2.snapshot()

        tid = obs.trace_id_for("req-1")
        names2 = [e["name"] for e in events2 if e["k"] == "span"]
        assert "rep.journal_replay" in names2
        assert "gw.replay_completion" in names2
        replay = next(e for e in events2
                      if e.get("name") == "rep.journal_replay")
        assert replay["tid"] == tid  # the ORIGINAL trace id
        # Merged across both incarnations: ONE trace, two recorded
        # terminals that AGREE (exactly-once evidence), the replay's
        # the effective one — never a duplicate trace.
        dumps = [
            {"meta": {"process": "gw-g1", "pid": 1},
             "events": events1},
            {"meta": {"process": "gw-g2", "pid": 2},
             "events": events2},
        ]
        rep = collect.validate_traces(dumps)
        assert rep["total"] == 1
        tr = rep["traces"][tid]
        assert tr["ok"], tr
        assert tr["terminal_spans"] == 2
        assert tr["superseded_terminals"] == 1
        assert tr["duplicates_agree"]
        assert tr["terminal_process"] == "gw-g2"


class TestFailoverTraceContinuity:
    def test_tier_resubmit_joins_original_trace(self):
        """Gateway killed with the request queued: the client's
        failover resubmit lands at the adopting gateway under the SAME
        derived trace id, with the resubmit visible as a span."""
        rec = obs.configure(process="tier-unit")
        # _Tier gateways have no heartbeat thread: the kill() below
        # removes g0's registry entry (the aged-out-lease equivalent),
        # so the default lease keeps g1 visibly alive.
        tier = _Tier(2)
        # Pick a request id owned by g0 (the one we'll kill).
        rid = next(
            f"req-{i}" for i in range(100)
            if tier.ring.owner(f"req-{i}") == "g0"
        )
        client = TierClient(tier.registry, connect=tier.connect,
                            poll_interval=0.01, refresh_s=0.05)
        ack = client.submit(rid, [2, 3], 4, submit_timeout=5)
        assert ack.status == "accepted"
        tier.kill("g0")
        time.sleep(0.1)  # the clients' cached views refresh

        link = TierReplicaLink(tier.registry, "r0",
                               connect=tier.connect, refresh_s=0.05)
        runner = ReplicaRunner(FakeDecodeServer(slots=2), link, "r0",
                               poll_interval=0.001)
        th = threading.Thread(target=runner.run, daemon=True)
        th.start()
        try:
            reply = client.result(rid, timeout=20)
            assert reply.state == "done"
            assert reply.tokens == expected_tokens([2, 3], 4)
            assert client.resubmitted >= 1
        finally:
            tier.cores["g1"].drain("r0")
            th.join(timeout=20)
            client.close()
            link.close()
        tid = obs.trace_id_for(rid)
        events, _, _ = rec.snapshot()
        spans = [e for e in events if e["k"] == "span"]
        resub = [s for s in spans if s["name"] == "client.resubmit"]
        assert resub and resub[0]["tid"] == tid  # ORIGINAL trace id
        # One trace, one terminal (g0 died before completing), phases
        # tile at the completing gateway.
        rep = collect.validate_traces(_dumps_of(events))
        tr = rep["traces"][tid]
        assert tr["terminal_spans"] == 1 and tr["ok"], tr
        assert tr["state"] == "done"


# ---------------------------------------------------------------------------
# Collector + postmortem
# ---------------------------------------------------------------------------


def _span(name, cat, ts, dur, tid="", sid="s", psid="", args=None,
          seq=0):
    rec = {"k": "span", "name": name, "cat": cat, "ts": ts,
           "dur": dur, "tid": tid, "sid": sid, "seq": seq}
    if psid:
        rec["psid"] = psid
    if args:
        rec["args"] = args
    return rec


class TestCollector:
    def test_chrome_trace_is_perfetto_shaped_and_loadable(
            self, tmp_path):
        dumps = [{
            "meta": {"process": "gw-g0", "pid": 11},
            "events": [
                _span("gw.request", "gateway", 100.0, 50.0,
                      tid="t1", args={"terminal": True,
                                      "state": "done"}),
                {"k": "ev", "kind": "chaos.inject", "ts": 120.0,
                 "site": "serving.gateway_kill", "seq": 2},
            ],
        }]
        ct = collect.build_chrome_trace(dumps)
        phs = {e["ph"] for e in ct["traceEvents"]}
        assert {"M", "X", "i"} <= phs
        x = next(e for e in ct["traceEvents"] if e["ph"] == "X")
        assert x["pid"] == 11 and x["dur"] == 50.0
        out = tmp_path / "merged.json"
        out.write_text(json.dumps(ct))
        from dlrover_tpu.utils.trace_analysis import TraceAnalysis

        ta = TraceAnalysis.from_file(str(out))
        assert len(ta.events) == 1  # the X event survives the loader
        assert ta.events[0].name == "gw.request"

    def test_validation_rejects_disagreeing_duplicate_terminals(self):
        dumps = [
            {"meta": {"process": "a", "pid": 1}, "events": [
                _span("gw.request", "gateway", 0.0, 10.0, tid="t1",
                      sid="r1",
                      args={"terminal": True, "state": "done",
                            "tokens": 5}),
            ]},
            {"meta": {"process": "b", "pid": 2}, "events": [
                _span("gw.request", "gateway", 20.0, 10.0, tid="t1",
                      sid="r2",
                      args={"terminal": True, "state": "done",
                            "tokens": 7}),
            ]},
        ]
        rep = collect.validate_traces(dumps)
        tr = rep["traces"]["t1"]
        assert not tr["duplicates_agree"]
        assert not tr["ok"]

    def test_validation_flags_missing_terminal(self):
        dumps = _dumps_of([
            _span("gw.queue_wait", "phase", 0.0, 5.0, tid="t1"),
        ])
        rep = collect.validate_traces(dumps)
        assert rep["traces"]["t1"]["terminal_spans"] == 0
        assert not rep["traces"]["t1"]["complete"]

    def test_phase_sum_tolerance(self):
        base = _span("gw.request", "gateway", 0.0, 1_000_000.0,
                     tid="t1", sid="r1",
                     args={"terminal": True, "state": "done",
                           "latency_ms": 1000.0})
        ok_phase = _span("gw.queue_wait", "phase", 0.0, 980_000.0,
                         tid="t1")
        bad_phase = _span("gw.queue_wait", "phase", 0.0, 600_000.0,
                          tid="t1")
        rep = collect.validate_traces(_dumps_of([base, ok_phase]))
        assert rep["traces"]["t1"]["phase_sum_ok"]
        rep = collect.validate_traces(_dumps_of([base, bad_phase]))
        assert not rep["traces"]["t1"]["phase_sum_ok"]


class TestPostmortem:
    def _write_dump(self, path, meta, events):
        with open(path, "w") as f:
            f.write(json.dumps({"k": "meta", **meta}) + "\n")
            for e in events:
                f.write(json.dumps(e) + "\n")

    def test_postmortem_names_the_dead_and_the_rerouted(
            self, tmp_path):
        # g1 died by chaos holding req-9; g0 finished it after the
        # failover; r0 exited cleanly.
        self._write_dump(
            tmp_path / "flight-gw-g1-11.jsonl",
            {"process": "gw-g1", "pid": 11, "reason": "chaos",
             "chaos_site": "serving.gateway_kill", "dropped": 0},
            [
                _span("gw.queue_wait", "phase", 0.0, 5.0, tid="t9",
                      args={"rid": "req-9"}, seq=1),
                {"k": "ev", "kind": "chaos.inject", "ts": 6.0,
                 "site": "serving.gateway_kill", "seq": 2},
            ],
        )
        self._write_dump(
            tmp_path / "flight-gw-g0-10.jsonl",
            {"process": "gw-g0", "pid": 10, "reason": "exit",
             "chaos_site": "", "dropped": 0},
            [
                _span("gw.request", "gateway", 10.0, 5.0, tid="t9",
                      sid="root2",
                      args={"rid": "req-9", "terminal": True,
                            "state": "done"}, seq=1),
            ],
        )
        self._write_dump(
            tmp_path / "flight-rep-r0-12.jsonl",
            {"process": "rep-r0", "pid": 12, "reason": "sigterm",
             "chaos_site": "", "dropped": 0},
            [],
        )
        report = postmortem.analyze(str(tmp_path))
        assert report["crashed"] == ["gw-g1"]
        assert report["chaos_sites"] == ["serving.gateway_kill"]
        dead = next(p for p in report["processes"]
                    if p["process"] == "gw-g1")
        assert dead["held_in_flight"] == ["req-9"]
        assert len(report["rerouted"]) == 1
        routed = report["rerouted"][0]
        assert routed["req_id"] == "req-9"
        assert routed["terminal_process"] == "gw-g0"
        text = postmortem.render(report)
        assert "serving.gateway_kill" in text
        assert "req-9" in text
        # The CLI entry point runs end-to-end and writes the merged
        # chrome trace.
        out = tmp_path / "merged.json"
        rc = postmortem.main([str(tmp_path), "--out", str(out)])
        assert rc == 0
        assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Metrics-registry satellites
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def capture_repo_logs(level=logging.WARNING):
    """The repo logger sets ``propagate=False``, so pytest's caplog
    never sees it — attach a list handler directly."""
    from dlrover_tpu.common.log import logger as repo_logger

    records = []
    handler = logging.Handler(level=level)
    handler.emit = records.append
    repo_logger.addHandler(handler)
    try:
        yield records
    finally:
        repo_logger.removeHandler(handler)


class TestMetricsRegistrySatellite:
    def test_gauge_overwrite_warns_once_per_name(self):
        from dlrover_tpu.agent.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.gauge("q", lambda: 1.0)
        with capture_repo_logs() as records:
            reg.gauge("q", lambda: 2.0)
            reg.gauge("q", lambda: 3.0)
        warns = [r for r in records
                 if "re-registered" in r.getMessage()]
        assert len(warns) == 1
        assert "dlrover_tpu_q 3.0" in reg.render()

    def test_set_updates_without_warning(self):
        from dlrover_tpu.agent.metrics import MetricsRegistry

        reg = MetricsRegistry()
        with capture_repo_logs() as records:
            reg.set("v", 1.0)
            reg.set("v", 2.0)
        assert not [r for r in records
                    if "re-registered" in r.getMessage()]
        assert "dlrover_tpu_v 2.0" in reg.render()

    def test_persistently_failing_gauge_promotes_to_warning_once(self):
        from dlrover_tpu.agent.metrics import MetricsRegistry

        reg = MetricsRegistry()
        state = {"fail": True}

        def flaky():
            if state["fail"]:
                raise RuntimeError("boom")
            return 4.0

        reg.gauge("flaky", flaky)
        with capture_repo_logs() as records:
            for _ in range(reg.FAIL_PROMOTE_AFTER + 2):
                reg.render()
        warns = [r for r in records
                 if "consecutive" in r.getMessage()]
        assert len(warns) == 1  # promoted exactly once
        # Recovery resets; a relapse warns anew.
        state["fail"] = False
        assert "dlrover_tpu_flaky 4.0" in reg.render()
        state["fail"] = True
        with capture_repo_logs() as records:
            for _ in range(reg.FAIL_PROMOTE_AFTER):
                reg.render()
        assert [r for r in records
                if "consecutive" in r.getMessage()]


class TestTierMetricsEndpoint:
    @pytest.mark.serving
    def test_tier_node_metrics_port_serves_merged_view(self):
        """The ISSUE 12 satellite: a GatewayTierNode with a metrics
        port exports its own gauges, the merged tier view, and the
        trace/flight-recorder drop counters; without the knob, no
        server exists."""
        import urllib.request

        from dlrover_tpu.serving.tier import (
            GatewayTierNode,
            LocalKv,
            ServeRegistry,
        )

        obs.configure(process="gw-metrics")
        registry = ServeRegistry(LocalKv(), job="mx")
        node = GatewayTierNode("g0", registry, metrics_port=0,
                               heartbeat_s=5.0)
        off = GatewayTierNode("g1", registry, heartbeat_s=5.0)
        try:
            assert off.metrics_port is None
            node.start()
            node.core.submit("req-1", [1], 4)
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{node.metrics_port}/metrics",
                timeout=10,
            ).read().decode()
            for needle in (
                "dlrover_tpu_serve_queue_depth",
                "dlrover_tpu_tier_queue_depth",
                "dlrover_tpu_tier_gateways",
                "dlrover_tpu_obs_flight_dropped",
                "dlrover_tpu_serve_trace_sampled",
            ):
                assert needle in body, needle
        finally:
            node.stop(0.0)
            off.stop(0.0)
