"""Master HA units (ISSUE 13): control-state journal, warm standby,
client failover, statecheck, and the satellite regressions.

All sub-second-ish and tier-1 (marker ``ha``); the flagship process-tree
master-kill scenario lives in ``test_chaos_e2e.py`` (slow lane).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.agent.master_client import (
    MasterClient,
    build_master_client,
    invalidate_master_client,
    reset_master_client,
)
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.common.rpc import RpcClient, RpcServer
from dlrover_tpu.master.master import LocalJobMaster
from dlrover_tpu.master.standby import RpcJournalSource, StandbyMaster
from dlrover_tpu.master.state import (
    ControlStateJournal,
    JournalTail,
    MasterState,
    read_addr,
    read_lease,
    read_state_dir,
    recover_into,
    write_addr,
)
from dlrover_tpu.master.statecheck import check_state_dir
from dlrover_tpu.master.task_manager import DatasetManager, TaskManager
from dlrover_tpu.master.dataset_splitter import TableDatasetSplitter

pytestmark = pytest.mark.ha

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_state():
    from dlrover_tpu.master.statecheck import _fresh_state

    return _fresh_state()


# ---------------------------------------------------------------------------
# journal framing / recovery
# ---------------------------------------------------------------------------


class TestJournalFraming:
    def test_append_read_roundtrip(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        for i in range(5):
            j.append("kv.set", {"key": f"k{i}", "value": b"v" * i})
        j.close()
        contents = read_state_dir(str(tmp_path))
        kinds = [r["k"] for r in contents.records]
        assert kinds == ["ha.owner"] + ["kv.set"] * 5
        seqs = [r["s"] for r in contents.records]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert contents.records[-1]["d"]["value"] == b"v" * 4
        assert not contents.damage and contents.torn_tail_bytes == 0

    def test_torn_tail_truncated_on_reopen(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        j.append("kv.set", {"key": "good", "value": b"x"})
        j.close()
        wal = tmp_path / "wal.log"
        with open(wal, "ab") as f:
            f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefhalf a frame")
        contents = read_state_dir(str(tmp_path))
        assert contents.torn_tail_bytes > 0
        assert [r["k"] for r in contents.records] == ["ha.owner", "kv.set"]
        # Reopen as writer: tail truncated, next generation claimed.
        j2 = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        assert j2.generation == 2
        j2.append("kv.set", {"key": "after", "value": b"y"})
        j2.close()
        contents2 = read_state_dir(str(tmp_path))
        assert contents2.torn_tail_bytes == 0
        assert [r["k"] for r in contents2.records] == [
            "ha.owner", "kv.set", "ha.owner", "kv.set",
        ]

    def test_mid_file_corruption_is_damage(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        j.append("kv.set", {"key": "a", "value": b"1"})
        j.append("kv.set", {"key": "b", "value": b"2"})
        j.close()
        wal = tmp_path / "wal.log"
        blob = bytearray(wal.read_bytes())
        blob[20] ^= 0xFF  # flip a byte inside the first frame
        wal.write_bytes(bytes(blob))
        report = check_state_dir(str(tmp_path))
        # The scan stops at the bad frame; later good records become
        # unreachable — statecheck must NOT call that clean.
        assert report["records"] < 3

    def test_chaos_journal_torn_crash_mid_append(self, tmp_path):
        """The ``master.journal_torn`` site crashes INSIDE an append;
        the reopen must truncate the torn half-frame and lose exactly
        the unacked record, and statecheck must exit 0."""
        script = f"""
import os
from dlrover_tpu import chaos
from dlrover_tpu.master.state import ControlStateJournal
chaos.configure("master.journal_torn:method=kv.set")
j = ControlStateJournal({str(tmp_path)!r}, snapshot_every=10000)
j.append("node.status", {{"node_id": 1, "status": "RUNNING"}})
j.append("kv.set", {{"key": "doomed", "value": b"x"}})
raise SystemExit("chaos site did not fire")
"""
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            cwd=REPO, timeout=60,
        )
        assert proc.returncode == chaos.EXIT_JOURNAL_TORN, proc.stderr[-2000:]
        contents = read_state_dir(str(tmp_path))
        assert contents.torn_tail_bytes > 0
        assert [r["k"] for r in contents.records] == [
            "ha.owner", "node.status",
        ]
        report = check_state_dir(str(tmp_path))
        assert report["clean"], report["damage"]


class TestSnapshotCompaction:
    def _journal_with_state(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        state.kv_store.set("k", b"v")
        for i in range(8):
            state.kv_store.add("ctr", 1, token=f"t{i}")
        return state, j

    def test_snapshot_compacts_wal_and_recovers(self, tmp_path):
        state, j = self._journal_with_state(tmp_path)
        size_before = os.path.getsize(tmp_path / "wal.log")
        label = j.snapshot(state.capture)
        assert label == j.seq
        assert os.path.getsize(tmp_path / "wal.log") < size_before
        # Post-snapshot appends land in the (compacted) tail.
        state.kv_store.set("k2", b"v2")
        j.close()
        contents = read_state_dir(str(tmp_path))
        assert contents.snapshot is not None
        assert [r["k"] for r in contents.records] == ["kv.set"]
        fresh = _fresh_state()
        recover_into(fresh, contents)
        assert fresh.kv_store.get("k") == b"v"
        assert fresh.kv_store.get("k2") == b"v2"
        assert fresh.kv_store.get("ctr") == b"8"

    def test_overlapping_replay_is_idempotent(self, tmp_path):
        """The snapshot boundary is fuzzy by the in-flight append
        window; re-applying records the snapshot already holds must not
        double-apply (the token caches are IN the snapshot)."""
        state, j = self._journal_with_state(tmp_path)
        snap = state.capture()
        contents = read_state_dir(str(tmp_path))
        fresh = _fresh_state()
        fresh.restore(snap)
        # Replay EVERY record over the full snapshot: adds dedupe on
        # their tokens, sets overwrite.
        divergences = fresh.replay(contents.records)
        assert not divergences
        assert fresh.kv_store.get("ctr") == b"8"
        j.close()

    def test_snapshot_due_thresholds(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=5)
        state.bind(j)
        assert not j.snapshot_due()
        for i in range(5):
            state.kv_store.set(f"k{i}", b"v")
        assert j.snapshot_due()
        assert j.maybe_snapshot(state.capture)
        assert not j.snapshot_due()
        j.close()


class TestJournalTail:
    def test_gap_detected_when_compaction_outran_tail(self, tmp_path):
        """Records appended after the tail's last poll and subsumed by
        a snapshot+compaction before its next poll leave a seq hole —
        the tail must FLAG it (the standby re-bootstraps from the
        snapshot) rather than silently skipping acked mutations."""
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        tail = JournalTail(str(tmp_path))
        state.kv_store.set("a", b"1")
        tail.poll()
        assert not tail.gap
        # Appended but NEVER polled, then compacted away:
        state.kv_store.set("lost-from-wal", b"2")
        j.snapshot(state.capture)
        state.kv_store.set("c", b"3")
        recs = tail.poll()
        assert [r["d"]["key"] for r in recs if r["k"] == "kv.set"] == ["c"]
        assert tail.gap  # the hole is visible, not silent
        tail.close()
        j.close()

    def test_standby_rebootstrap_recovers_gap_records(self, tmp_path):
        """The standby's gap response: full snapshot restore + tail
        replay recovers the records the compaction dropped from the
        WAL before the tail read them."""
        master = _mk_primary(tmp_path)
        client = MasterClient(master.addr, 0)
        try:
            sb = StandbyMaster(
                str(tmp_path), port=0, primary_addr=master.addr,
                lease_s=30.0, tail_poll_s=5.0, job_name="ha-unit",
            )
            # Mutations the standby has NOT polled yet, compacted away:
            client.kv_store_set("gap/key", b"in-snapshot-only")
            master._ha_journal.snapshot(master._ha_state.capture)
            client.kv_store_set("tail/key", b"post-compaction")
            recs = sb._tail.poll()
            assert sb._tail.gap
            sb.rebootstrap()
            assert not sb._tail.gap
            assert sb.state.kv_store.get("gap/key") == b"in-snapshot-only"
            assert sb.state.kv_store.get("tail/key") == b"post-compaction"
            sb.stop()
        finally:
            client.close()
            master.stop()

    def test_incremental_poll_and_compaction_survival(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        tail = JournalTail(str(tmp_path))
        state.kv_store.set("a", b"1")
        recs = tail.poll()
        assert [r["k"] for r in recs] == ["ha.owner", "kv.set"]
        assert tail.poll() == []
        state.kv_store.set("b", b"2")
        assert [r["d"]["key"] for r in tail.poll()] == ["b"]
        # Compaction swaps the inode; the tail must reopen and dedupe.
        j.snapshot(state.capture)
        state.kv_store.set("c", b"3")
        got = [r["d"]["key"] for r in tail.poll() if r["k"] == "kv.set"]
        assert got == ["c"]
        tail.close()
        j.close()


# ---------------------------------------------------------------------------
# manager state machines: journal -> replay equivalence
# ---------------------------------------------------------------------------


class TestReplay:
    def test_rendezvous_world_replays_as_state(self, tmp_path):
        state = _fresh_state()
        mgr = state.rdzv_managers[RendezvousName.TRAINING]
        mgr.update_rdzv_params(2, 2, waiting_timeout=0.01)
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        mgr.join(0, 0, 2, host="h0", coordinator_port=9000)
        mgr.join(1, 1, 2, host="h1", coordinator_port=9001)
        round_, _, world, coord = mgr.get_comm_world(0)
        assert len(world) == 2 and coord
        j.close()
        fresh = _fresh_state()
        contents = read_state_dir(str(tmp_path))
        assert not fresh.replay(contents.records)
        fmgr = fresh.rdzv_managers[RendezvousName.TRAINING]
        # The world latch was a wall-clock decision on the primary; the
        # replayed manager holds the identical latched world WITHOUT
        # re-deciding (its own lastcall window never elapsed).
        r2, _, w2, c2 = fmgr.get_comm_world(0)
        assert (r2, w2, c2) == (round_, world, coord)
        assert fmgr.current_world_nodes() == mgr.current_world_nodes()

    def test_reshard_epoch_replays_and_rearms(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        rm = state.reshard_manager
        epoch = rm.announce(4, {"dp": 4}, expected_reports=2,
                            deadline_s=60.0)
        rm.report(m.ReshardReport(node_id=0, epoch=epoch, ok=True))
        j.close()
        fresh = _fresh_state()
        contents = read_state_dir(str(tmp_path))
        assert not fresh.replay(contents.records)
        frm = fresh.reshard_manager
        assert frm.epoch == epoch and frm.status == "preparing"
        assert set(frm.reports()) == {0}
        # Takeover re-arm: a fresh full deadline on this clock.
        frm.rearm_deadline()
        info = frm.info()
        assert info.deadline_s > 30.0
        # The second ok report resolves the epoch DONE post-failover.
        frm.report(m.ReshardReport(node_id=1, epoch=epoch, ok=True))
        assert frm.status == "done"

    def test_task_grant_divergence_is_reported(self, tmp_path):
        """A journal promising a different task id than replay produces
        must be flagged (the statecheck damage signal)."""
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        params = dict(dataset_name="d", dataset_size=30, shard_size=10)
        from dlrover_tpu.master.dataset_splitter import new_dataset_splitter

        state.task_manager.new_dataset(new_dataset_splitter(**params),
                                       params=params)
        state.task_manager.get_task("d", 0, token="tok-a")
        j.close()
        contents = read_state_dir(str(tmp_path))
        # Tamper: claim the grant handed out task 7.
        for rec in contents.records:
            if rec["k"] == "task.grant":
                rec["d"]["task_id"] = 7
        fresh = _fresh_state()
        divergences = fresh.replay(contents.records)
        assert any("journal promised 7" in d for d in divergences)

    def test_node_membership_and_speed_replay(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        state.job_manager.register_node_meta(m.NodeMeta(
            node_type="worker", node_id=3, node_rank=3, host="h3",
            agent_port=9003, local_world_size=4,
        ))
        state.speed_monitor._last_step_journal = float("-inf")
        state.speed_monitor.collect_global_step(17, 123.0)
        j.close()
        fresh = _fresh_state()
        contents = read_state_dir(str(tmp_path))
        assert not fresh.replay(contents.records)
        node = fresh.job_manager.get_node(3)
        assert node is not None and node.host == "h3"
        assert fresh.speed_monitor.completed_global_step == 17


# ---------------------------------------------------------------------------
# warm standby takeover (in-process)
# ---------------------------------------------------------------------------


def _mk_primary(tmp_path, **kw):
    master = LocalJobMaster(
        0, job_name="ha-unit", state_dir=str(tmp_path), **kw
    )
    master.prepare()
    return master


def _silence(master):
    """Simulate an unclean primary death: the server stops answering,
    the keeper stops leasing, and the journal handle dies with the
    process — crucially WITHOUT the clean ha.shutdown record a real
    stop() writes (a SIGKILL writes nothing)."""
    master._server.stop(0)
    master._ha_keeper.stop()
    master._ha_journal.close()


class TestStandbyTakeover:
    def test_state_survives_takeover_exactly_once(self, tmp_path):
        master = _mk_primary(tmp_path, min_nodes=2, max_nodes=2)
        client = MasterClient(master.addr, 0)
        try:
            client.kv_store_set("boot/k", b"v")
            assert client.kv_store_add("ctr", 3) == 3
            client.report_dataset_shard_params(
                dataset_name="ds", dataset_size=50, shard_size=10
            )
            t0 = client.get_task("ds")
            t1 = client.get_task("ds")
            client.report_task_result("ds", t0.task_id, True)
            sb = StandbyMaster(
                str(tmp_path), port=0, primary_addr=master.addr,
                lease_s=0.6, tail_poll_s=0.05, job_name="ha-unit",
                min_nodes=2, max_nodes=2,
            )
            watcher = threading.Thread(target=sb.watch, daemon=True)
            watcher.start()
            time.sleep(0.3)  # standby is tailing
            client.kv_store_set("live/k", b"tailed")
            _silence(master)
            assert sb.wait_takeover(20)
            c2 = MasterClient(sb.addr, 0)
            # Durable contract: everything acked pre-kill is there.
            assert c2.kv_store_get("boot/k") == b"v"
            assert c2.kv_store_get("live/k") == b"tailed"
            assert c2.kv_store_get("ctr") == b"3"
            # Exactly-once across the blackout: in-flight t1 is DOING on
            # the standby (not lost, not re-granted); reporting it
            # completes it once, and the next grants continue the queue.
            c2.report_task_result("ds", t1.task_id, True)
            granted = set()
            while True:
                t = c2.get_task("ds")
                if t.task_id < 0:
                    break
                granted.add(t.task_id)
                c2.report_task_result("ds", t.task_id, True)
            assert granted == {2, 3, 4}  # 0,1 done; 2-4 fresh
            assert sb.master.task_manager.dataset_completed("ds")
            report = check_state_dir(str(tmp_path))
            assert report["clean"], report["damage"]
            c2.close()
            sb.stop()
        finally:
            client.close()
            master.stop()

    @pytest.mark.parametrize("state_dir", ["same", "blank"])
    def test_cold_relaunch_recovers_what_its_state_dir_holds(
        self, tmp_path, state_dir
    ):
        """What a relaunch knows is what its state directory holds: on
        the dead primary's directory it reads the acknowledged key back
        and its queue goes on after the tasks already granted; on a
        blank one (the relaunch the journal replaced) the key is gone
        and the same dataset starts over at task 0."""
        first = tmp_path / "first"
        first.mkdir()
        master = _mk_primary(first)
        client = MasterClient(master.addr, 0)
        try:
            client.kv_store_set("boot/k", b"v")
            client.report_dataset_shard_params(
                dataset_name="ds", dataset_size=50, shard_size=10)
            done = client.get_task("ds")
            client.report_task_result("ds", done.task_id, True)
        finally:
            client.close()
            _silence(master)
        where = first
        if state_dir == "blank":
            where = tmp_path / "blank"
            where.mkdir()
        relaunched = _mk_primary(where)
        c2 = MasterClient(relaunched.addr, 0)
        try:
            if state_dir == "same":
                assert c2.kv_store_get("boot/k") == b"v"
                assert c2.get_task("ds").task_id == done.task_id + 1
            else:
                assert c2.kv_store_get("boot/k") is None
                c2.report_dataset_shard_params(
                    dataset_name="ds", dataset_size=50, shard_size=10)
                assert c2.get_task("ds").task_id == done.task_id == 0
        finally:
            c2.close()
            relaunched.stop()
        assert check_state_dir(str(where))["clean"]

    def test_standby_holds_while_primary_leases(self, tmp_path):
        master = _mk_primary(tmp_path)
        try:
            sb = StandbyMaster(
                str(tmp_path), port=0, primary_addr=master.addr,
                lease_s=0.4, tail_poll_s=0.05, job_name="ha-unit",
            )
            watcher = threading.Thread(target=sb.watch, daemon=True)
            watcher.start()
            # Well past the lease: the keeper's bumps must hold it back.
            assert not sb.wait_takeover(1.5)
            sb.stop()
        finally:
            master.stop()

    def test_split_brain_guard_probes_primary(self, tmp_path):
        """Journal silent (keeper stopped) but the primary still answers
        TCP: the standby must HOLD — a stalled shared filesystem is not
        a dead primary."""
        master = _mk_primary(tmp_path)
        try:
            master._ha_keeper.stop()  # journal goes silent; server lives
            sb = StandbyMaster(
                str(tmp_path), port=0, primary_addr=master.addr,
                lease_s=0.3, tail_poll_s=0.05, job_name="ha-unit",
            )
            watcher = threading.Thread(target=sb.watch, daemon=True)
            watcher.start()
            assert not sb.wait_takeover(1.5)
            sb.stop()
        finally:
            master.stop()

    def test_takeover_publishes_addr_and_next_generation(self, tmp_path):
        master = _mk_primary(tmp_path)
        primary_addr = master.addr
        assert read_addr(str(tmp_path)) == primary_addr
        sb = StandbyMaster(
            str(tmp_path), port=0, primary_addr=primary_addr,
            lease_s=0.4, tail_poll_s=0.05, job_name="ha-unit",
        )
        watcher = threading.Thread(target=sb.watch, daemon=True)
        watcher.start()
        _silence(master)
        assert sb.wait_takeover(20)
        assert read_addr(str(tmp_path)) == sb.addr != primary_addr
        assert sb.master._ha_journal.generation == 2
        # The new leader leases; a second standby would observe it.
        lease0 = read_lease(str(tmp_path))
        time.sleep(1.2)
        assert read_lease(str(tmp_path)) != lease0
        sb.stop()
        master.stop()

    def test_rpc_mirror_survives_primary_compaction(self, tmp_path):
        """The primary's WAL compaction shrinks the remote file below
        the mirrored offset; the mirror must detect it (wal_size),
        re-fetch the snapshot, rebuild the local WAL atomically, and
        keep streaming — a fresh bootstrap of the mirror dir stays
        complete."""
        primary_dir = tmp_path / "primary"
        mirror_dir = tmp_path / "mirror"
        master = _mk_primary(primary_dir)
        client = MasterClient(master.addr, 0)
        try:
            client.kv_store_set("a", b"1")
            source = RpcJournalSource(client._client, str(mirror_dir))
            source.sync()
            tail = JournalTail(str(mirror_dir))
            assert any(r["k"] == "kv.set" for r in tail.poll())
            # Primary snapshots + compacts, then keeps appending.
            master._ha_journal.snapshot(master._ha_state.capture)
            client.kv_store_set("b", b"2")
            assert source.sync() > 0  # shrink detected, mirror rebuilt
            got = [r["d"]["key"] for r in tail.poll()
                   if r["k"] == "kv.set"]
            assert got == ["b"]
            contents = read_state_dir(str(mirror_dir))
            assert contents.snapshot is not None  # re-fetched
            fresh = _fresh_state()
            recover_into(fresh, contents)
            assert fresh.kv_store.get("a") == b"1"
            assert fresh.kv_store.get("b") == b"2"
            tail.close()
        finally:
            client.close()
            master.stop()

    def test_clean_primary_shutdown_stands_down(self, tmp_path):
        """A master that stops ON PURPOSE (job finished) journals
        ha.shutdown; the tailing standby must stand down, not resurrect
        a completed job."""
        master = _mk_primary(tmp_path)
        sb = StandbyMaster(
            str(tmp_path), port=0, primary_addr=master.addr,
            lease_s=0.5, tail_poll_s=0.05, job_name="ha-unit",
        )
        done = {}

        def watch():
            done["takeover"] = sb.watch()

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        time.sleep(0.2)
        master.request_stop(True, "job finished")
        master.stop()
        watcher.join(timeout=10)
        assert not watcher.is_alive()
        assert done["takeover"] is False
        assert not sb.took_over()

    def test_rpc_journal_source_mirror(self, tmp_path):
        """Streaming replication: a standby in a NON-shared dir mirrors
        snapshot + WAL over JournalFetch and takes over identically."""
        primary_dir = tmp_path / "primary"
        mirror_dir = tmp_path / "mirror"
        master = _mk_primary(primary_dir)
        client = MasterClient(master.addr, 0)
        try:
            client.kv_store_set("mirrored", b"yes")
            source = RpcJournalSource(client._client, str(mirror_dir))
            assert source.sync() > 0
            sb = StandbyMaster(
                str(mirror_dir), port=0, primary_addr=master.addr,
                lease_s=0.6, tail_poll_s=0.05, job_name="ha-unit",
                rpc_source=source,
            )
            watcher = threading.Thread(target=sb.watch, daemon=True)
            watcher.start()
            time.sleep(0.2)
            client.kv_store_set("mirrored2", b"also")
            time.sleep(0.3)  # one sync cycle pulls the new frame
            _silence(master)
            assert sb.wait_takeover(20)
            c2 = MasterClient(sb.addr, 0)
            assert c2.kv_store_get("mirrored") == b"yes"
            assert c2.kv_store_get("mirrored2") == b"also"
            c2.close()
            sb.stop()
        finally:
            client.close()
            master.stop()


# ---------------------------------------------------------------------------
# client failover
# ---------------------------------------------------------------------------


class TestClientFailover:
    def test_rpc_client_rehomes_via_provider(self, tmp_path):
        served = {"a": 0, "b": 0}

        def handler_for(name):
            def handler(msg):
                served[name] += 1
                return m.BaseResponse(success=True, reason=name)
            return handler

        srv_a = RpcServer(0, handler_for("a"))
        srv_a.start()
        srv_b = RpcServer(0, handler_for("b"))
        srv_b.start()
        target = {"addr": f"127.0.0.1:{srv_a.port}"}
        cli = RpcClient(target["addr"],
                        addr_provider=lambda: target["addr"])
        try:
            assert cli.call(m.Empty()).reason == "a"
            srv_a.stop(0)
            target["addr"] = f"127.0.0.1:{srv_b.port}"
            # A grace-0 stop can surface ONE non-retriable CANCELLED
            # (GOAWAY racing the call); a real dead master yields
            # UNAVAILABLE.  The re-home itself must be automatic.
            import grpc

            reason, deadline = "", time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    reason = cli.call(m.Empty(), idempotent=True,
                                      retries=6, deadline=20.0).reason
                    break
                except grpc.RpcError:
                    time.sleep(0.2)
            assert reason == "b"
            assert cli.addr == target["addr"]
        finally:
            cli.close()
            srv_b.stop(0)

    def test_master_client_follows_state_dir_addr(self, tmp_path):
        master_a = LocalJobMaster(0, job_name="fa")
        master_a.prepare()
        master_b = LocalJobMaster(0, job_name="fb")
        master_b.prepare()
        try:
            write_addr(str(tmp_path), master_a.addr)
            cli = MasterClient(master_a.addr, 0, state_dir=str(tmp_path))
            assert cli.kv_store_get("x") is None  # served by A
            master_a._server.stop(0)
            write_addr(str(tmp_path), master_b.addr)
            master_b.kv_store.set("x", b"from-b")
            assert cli.kv_store_get("x") == b"from-b"
            assert cli.master_addr == master_b.addr
            cli.close()
        finally:
            master_a.stop()
            master_b.stop()

    def test_singleton_invalidation_on_env_change(self, monkeypatch):
        """ISSUE 13 satellite: the module-level singleton latched the
        env-resolved address at first build forever; a post-failover env
        change must be picked up."""
        reset_master_client()
        monkeypatch.setenv("DLROVER_TPU_MASTER_ADDR", "127.0.0.1:1111")
        c1 = build_master_client()
        assert c1.master_addr == "127.0.0.1:1111"
        assert build_master_client() is c1  # stable while env is stable
        monkeypatch.setenv("DLROVER_TPU_MASTER_ADDR", "127.0.0.1:2222")
        c2 = build_master_client()
        assert c2 is not c1
        assert c2.master_addr == "127.0.0.1:2222"
        # Explicit invalidation also forces a rebuild.
        invalidate_master_client()
        c3 = build_master_client()
        assert c3 is not c2 and c3.master_addr == "127.0.0.1:2222"
        reset_master_client()

    def test_explicit_addr_singleton_unchanged(self, monkeypatch):
        reset_master_client()
        monkeypatch.setenv("DLROVER_TPU_MASTER_ADDR", "127.0.0.1:1111")
        c1 = build_master_client("127.0.0.1:3333")
        monkeypatch.setenv("DLROVER_TPU_MASTER_ADDR", "127.0.0.1:2222")
        # An explicitly-addressed build keeps the cached client (the
        # env contract was never its source)...
        assert build_master_client("127.0.0.1:3333") is c1
        # ...and a later NO-ARG build must not tear it down either:
        # the env was never this singleton's source, so an env value
        # (even a differing one) is not an invalidation signal.
        assert build_master_client() is c1
        assert c1.master_addr == "127.0.0.1:3333"
        reset_master_client()


# ---------------------------------------------------------------------------
# statecheck CLI
# ---------------------------------------------------------------------------


class TestStatecheckCli:
    def _populate(self, tmp_path):
        state = _fresh_state()
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state.bind(j)
        state.kv_store.set("k", b"v")
        j.close()

    def test_clean_dir_exit_0(self, tmp_path):
        self._populate(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.master.statecheck",
             str(tmp_path), "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        report = json.loads(proc.stdout)
        assert report["clean"] and report["records"] == 2

    def test_damaged_dir_exit_1(self, tmp_path):
        self._populate(tmp_path)
        wal = tmp_path / "wal.log"
        blob = bytearray(wal.read_bytes())
        blob[14] ^= 0xFF
        wal.write_bytes(bytes(blob))
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.master.statecheck",
             str(tmp_path)],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert proc.returncode == 1, proc.stdout

    def test_usage_exit_2(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.master.statecheck",
             str(tmp_path / "missing")],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert proc.returncode == 2


# ---------------------------------------------------------------------------
# satellites: restore re-arm + chaos sites
# ---------------------------------------------------------------------------


class TestRestoreRearm:
    def test_restored_doing_task_not_instantly_reassigned(self):
        """ISSUE 13 satellite: a doing task restored from a checkpoint
        on the HA path (keep_doing=True — its worker is still alive
        across the failover) must re-arm its timeout clock (monotonic)
        — inheriting the writer's stale deadline would instantly
        re-queue work a live worker is still running."""
        ds = DatasetManager(
            TableDatasetSplitter("d", 30, 10), task_timeout=0.3
        )
        got = ds.get_task(worker_id=5)
        assert got is not None
        # Age the doing task past its timeout, then checkpoint/restore.
        ds._doing[got[0]].start_time -= 10.0
        content = ds.checkpoint()
        ds2 = DatasetManager(
            TableDatasetSplitter("d", 30, 10), task_timeout=0.3
        )
        ds2.restore(content, keep_doing=True)
        assert got[0] in ds2._doing
        assert ds2._doing[got[0]].worker_id == 5
        # Re-armed: NOT reassigned now...
        assert ds2.reassign_timeout_tasks() == []
        # ...but the timeout still protects against a dead worker.
        time.sleep(0.35)
        assert ds2.reassign_timeout_tasks() == [got[0]]

    def test_restart_restore_requeues_doing_immediately(self):
        """The worker-initiated restore (full-restart resume) folds
        doing into the todo FRONT: the grants died with the old worker
        incarnations, so holding them as doing would stall those shards
        for the whole task_timeout."""
        ds = DatasetManager(TableDatasetSplitter("d", 30, 10))
        got = ds.get_task(worker_id=5)
        content = ds.checkpoint()
        ds2 = DatasetManager(TableDatasetSplitter("d", 30, 10))
        ds2.restore(content)  # default: restart semantics
        assert not ds2._doing
        regrant = ds2.get_task(worker_id=9)
        assert regrant is not None and regrant[0] == got[0]

    def test_legacy_checkpoint_without_doing_key(self):
        ds = DatasetManager(TableDatasetSplitter("d", 20, 10))
        legacy = json.dumps({
            "dataset_name": "d",
            "todo": [[0, {"name": "d-e1-0", "start": 0, "end": 10,
                          "record_indices": None}]],
            "epoch": 1, "task_id_seq": 2,
        })
        ds.restore(legacy)
        assert len(ds._todo) == 1 and not ds._doing

    def test_rearm_doing_on_task_manager(self):
        tm = TaskManager(task_timeout=100.0)
        from dlrover_tpu.master.dataset_splitter import new_dataset_splitter

        params = dict(dataset_name="d", dataset_size=20, shard_size=10)
        tm.new_dataset(new_dataset_splitter(**params), params=params)
        got = tm.get_task("d", 1, token="t")
        granted_at = tm._datasets["d"]._doing[got[0]].start_time
        tm._datasets["d"]._doing[got[0]].start_time -= 1e6
        tm.rearm_doing()
        assert tm._datasets["d"]._doing[got[0]].start_time >= granted_at


class _FakeProc:
    def __init__(self, rc=None):
        self.rc = rc

    def poll(self):
        return self.rc


class TestSuperviseLocalMaster:
    """ISSUE 13 satellite: direct units for run.py's cold supervisor —
    until now it was only exercised through slow chaos e2e."""

    @pytest.fixture(autouse=True)
    def _clean_chaos(self):
        yield
        chaos.reset()

    def _run_supervisor(self, monkeypatch, first_rc, spawned,
                        max_restarts=3, env_faults=None, port=5123):
        import argparse

        from dlrover_tpu import run as run_mod

        def fake_popen(cmd, env=None, **kw):
            spawned.append({"cmd": list(cmd), "env": env})
            return _FakeProc(rc=None)  # replacement stays alive

        monkeypatch.setattr(run_mod.subprocess, "Popen", fake_popen)
        if env_faults is not None:
            # The supervisor consults the PROCESS plan for the exit-code
            # match and the env var for the scrub; set both the way a
            # real launcher invocation would see them.
            monkeypatch.setenv("DLROVER_TPU_FAULTS", env_faults)
            chaos.configure(env_faults)
        args = argparse.Namespace(
            nnodes="1", job_name="sup-unit", node_unit=1,
        )
        holder = [_FakeProc(rc=first_rc)]
        stop = threading.Event()
        thread = run_mod._supervise_local_master(
            args, holder, port, stop, max_restarts=max_restarts
        )
        return holder, stop, thread

    def test_crash_exit_relaunches_on_same_port(self, monkeypatch):
        spawned = []
        holder, stop, thread = self._run_supervisor(monkeypatch, 1, spawned)
        deadline = time.monotonic() + 10
        while not spawned and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=5)
        assert len(spawned) == 1
        cmd = spawned[0]["cmd"]
        assert "--port" in cmd and cmd[cmd.index("--port") + 1] == "5123"
        assert holder[0] is not None and holder[0].poll() is None

    @pytest.mark.parametrize("rc", [0, -15])
    def test_signal_and_clean_exits_stop_supervision(self, monkeypatch, rc):
        spawned = []
        holder, stop, thread = self._run_supervisor(monkeypatch, rc, spawned)
        thread.join(timeout=10)
        assert not thread.is_alive()  # supervisor ended, no respawn
        assert spawned == []
        stop.set()

    def test_restart_budget_exhausts(self, monkeypatch):
        from dlrover_tpu import run as run_mod

        spawned = []

        def fake_popen(cmd, env=None, **kw):
            spawned.append(list(cmd))
            return _FakeProc(rc=7)  # every replacement dies too

        import argparse

        monkeypatch.setattr(run_mod.subprocess, "Popen", fake_popen)
        args = argparse.Namespace(nnodes="1", job_name="sup-unit",
                                  node_unit=1)
        holder = [_FakeProc(rc=7)]
        stop = threading.Event()
        thread = run_mod._supervise_local_master(
            args, holder, 5123, stop, max_restarts=2
        )
        thread.join(timeout=20)
        assert not thread.is_alive()
        assert len(spawned) == 2  # budget, then give up
        stop.set()

    def test_one_shot_master_restart_scrubbed_from_env(self, monkeypatch):
        """A chaos master.restart (exit 42) that just fired must be
        stripped from the replacement's env — it would re-arm and kill
        the replacement identically — while other faults survive."""
        spawned = []
        holder, stop, thread = self._run_supervisor(
            monkeypatch, 42, spawned,
            env_faults="master.restart:at=1s;rpc.latency:delay=5ms,seed=3",
        )
        deadline = time.monotonic() + 10
        while not spawned and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=5)
        assert len(spawned) == 1
        faults = spawned[0]["env"]["DLROVER_TPU_FAULTS"]
        assert "master.restart" not in faults
        assert "rpc.latency" in faults and "seed=3" in faults

    def test_non_chaos_crash_keeps_fault_plan(self, monkeypatch):
        """An ordinary crash (rc not matching any master.restart exit
        code) must NOT scrub the plan — flap/latency faults are meant to
        survive relaunch."""
        spawned = []
        holder, stop, thread = self._run_supervisor(
            monkeypatch, 9, spawned,
            env_faults="master.restart:at=1s;rpc.latency:delay=5ms",
        )
        deadline = time.monotonic() + 10
        while not spawned and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=5)
        assert len(spawned) == 1
        assert "master.restart" in spawned[0]["env"]["DLROVER_TPU_FAULTS"]


class TestSuperviseHaMasters:
    """The --standby supervision mode: promote on takeover, respawn a
    fresh standby behind the new leader."""

    def test_promote_and_respawn_on_primary_crash(self, monkeypatch,
                                                  tmp_path):
        import argparse

        from dlrover_tpu import run as run_mod
        from dlrover_tpu.master.state import write_addr

        state_dir = str(tmp_path)
        write_addr(state_dir, "127.0.0.1:1000")  # the dying primary
        spawned = []
        replacement = _FakeProc(rc=None)

        def fake_launch_standby(args, sdir, primary_addr):
            spawned.append(primary_addr)
            return replacement, "127.0.0.1:3000"

        monkeypatch.setattr(run_mod, "_launch_standby_master",
                            fake_launch_standby)
        args = argparse.Namespace(nnodes="1", job_name="ha-sup",
                                  node_unit=1)
        primary_holder = [_FakeProc(rc=83)]  # unclean master.kill death
        standby = _FakeProc(rc=None)
        standby_holder = [standby]
        stop = threading.Event()
        thread = run_mod._supervise_ha_masters(
            args, state_dir, primary_holder, standby_holder, stop,
            max_restarts=3,
        )
        # The standby "takes over": the addr file changes.
        time.sleep(1.2)
        write_addr(state_dir, "127.0.0.1:2000")
        deadline = time.monotonic() + 15
        while not spawned and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=5)
        # Promoted: the old standby now fills the primary slot, and a
        # FRESH standby was spawned pointing at the NEW leader.
        assert primary_holder[0] is standby
        assert standby_holder[0] is replacement
        assert spawned == ["127.0.0.1:2000"]

    def test_dead_standby_respawned_while_primary_lives(self,
                                                        monkeypatch,
                                                        tmp_path):
        import argparse

        from dlrover_tpu import run as run_mod
        from dlrover_tpu.master.state import write_addr

        state_dir = str(tmp_path)
        write_addr(state_dir, "127.0.0.1:1000")
        spawned = []

        def fake_launch_standby(args, sdir, primary_addr):
            spawned.append(primary_addr)
            return _FakeProc(rc=None), "127.0.0.1:3000"

        monkeypatch.setattr(run_mod, "_launch_standby_master",
                            fake_launch_standby)
        args = argparse.Namespace(nnodes="1", job_name="ha-sup",
                                  node_unit=1)
        primary_holder = [_FakeProc(rc=None)]  # healthy
        standby_holder = [_FakeProc(rc=84)]  # standby died
        stop = threading.Event()
        thread = run_mod._supervise_ha_masters(
            args, state_dir, primary_holder, standby_holder, stop,
            max_restarts=3,
        )
        deadline = time.monotonic() + 15
        while not spawned and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=5)
        assert spawned == ["127.0.0.1:1000"]
        assert standby_holder[0].poll() is None


class TestChaosSites:
    def test_master_kill_site_parses_and_exits_83(self):
        spec = chaos.FaultSpec.parse("master.kill:at=10s")
        assert spec.kind == "crash"
        assert spec.exit_code == chaos.EXIT_MASTER_KILL == 83
        assert spec.times == 1
        spec2 = chaos.FaultSpec.parse("master.journal_torn:method=kv.set")
        assert spec2.exit_code == chaos.EXIT_JOURNAL_TORN == 84

    def test_site_armed_reflects_firing_budget(self):
        """The journal's split-write path gates on site_armed so a
        consumed one-shot torn-site stops costing double fsyncs."""
        plan = chaos.FaultPlan.parse("master.journal_torn:times=1")
        assert plan.site_armed("master.journal_torn")
        assert plan.fire("master.journal_torn") is not None
        assert plan.has_site("master.journal_torn")  # still present...
        assert not plan.site_armed("master.journal_torn")  # ...but spent

    def test_scrub_strips_master_kill_for_standby(self):
        env = {"DLROVER_TPU_FAULTS":
               "master.kill:at=3s;rpc.latency:delay=10ms,seed=5"}
        chaos.scrub_env(env, ("master.kill", "master.restart",
                              "master.journal_torn"))
        assert "master.kill" not in env["DLROVER_TPU_FAULTS"]
        assert "rpc.latency" in env["DLROVER_TPU_FAULTS"]
        assert "seed=5" in env["DLROVER_TPU_FAULTS"]


@pytest.mark.ha
class TestSyncServiceJournal:
    """ISSUE 14 (graftcheck PC404): sync barriers are journaled.
    Workers join a named barrier ONCE and then only poll — before this
    the joins died with the primary and every already-joined worker
    polled a barrier that could never open."""

    def _recover(self, tmp_path):
        state2 = _fresh_state()
        recover_into(state2, read_state_dir(str(tmp_path)))
        return state2.sync_service

    def test_mid_barrier_joins_survive_failover(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state = _fresh_state()
        state.bind(j)
        ss = state.sync_service
        ss.set_world([0, 1])
        ss.join_sync("ckpt-fence", 0)  # node 1 not in yet
        j.close()

        s2 = self._recover(tmp_path)
        assert not s2.sync_finished("ckpt-fence")
        # The missing node joins at the STANDBY: the barrier completes
        # from the replayed membership + world.
        s2.join_sync("ckpt-fence", 1)
        assert s2.sync_finished("ckpt-fence")

    def test_finished_latch_and_force_open_replay(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state = _fresh_state()
        state.bind(j)
        ss = state.sync_service
        ss.set_world([0, 1])
        ss.join_sync("all", 0)
        ss.join_sync("all", 1)   # completes -> sync.finished record
        ss.finish_sync("forced")  # owner override latch
        ss.join_sync("gone", 0)
        ss.remove_sync("gone")
        j.close()

        s2 = self._recover(tmp_path)
        assert s2.sync_finished("all")
        assert s2.sync_finished("forced")
        assert not s2.sync_finished("gone")

    def test_snapshot_carries_sync_state(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state = _fresh_state()
        state.bind(j)
        ss = state.sync_service
        ss.set_world([3, 4])
        ss.join_sync("warm", 3)
        j.snapshot(state.capture)  # compacts the WAL away
        j.close()

        s2 = self._recover(tmp_path)
        assert not s2.sync_finished("warm")
        s2.join_sync("warm", 4)
        assert s2.sync_finished("warm")

    def test_world_journaled_only_on_change(self, tmp_path):
        j = ControlStateJournal(str(tmp_path), snapshot_every=10_000)
        state = _fresh_state()
        state.bind(j)
        ss = state.sync_service
        seq0 = j.seq
        ss.set_world([0, 1])
        seq1 = j.seq
        assert seq1 == seq0 + 1
        for _ in range(5):  # the per-poll set_world must not spam WAL
            ss.set_world([1, 0])
        assert j.seq == seq1
        j.close()
