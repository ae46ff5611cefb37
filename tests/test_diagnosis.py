"""Diagnosis subsystem tests (reference test model: test_diagnosis_*.py —
operators fed synthetic data, agent decisions from log patterns)."""

import json
import time

import pytest

from dlrover_tpu.common import messages as m
from dlrover_tpu.common.constants import DiagnosisActionType
from dlrover_tpu.diagnosis.agent import (
    DiagnosisAgent,
    HangingDetector,
    TrainingLogCollector,
)
from dlrover_tpu.diagnosis.data import (
    DiagnosisDataManager,
    DiagnosisDataType,
)
from dlrover_tpu.diagnosis.inference import (
    Attribution,
    Inference,
    InferenceChain,
    InferenceName,
    coordinate_solutions,
)
from dlrover_tpu.diagnosis.manager import DiagnosisManager
from dlrover_tpu.diagnosis.operators import (
    CheckFailureNodeOperator,
    CheckTrainingHangOperator,
)
from dlrover_tpu.master.speed_monitor import SpeedMonitor


class TestDataManager:
    def test_store_and_expire(self):
        dm = DiagnosisDataManager(ttl_s=0.2)
        dm.store_data(0, DiagnosisDataType.STEP_METRICS, "a")
        assert len(dm.get_data(DiagnosisDataType.STEP_METRICS)) == 1
        time.sleep(0.3)
        assert dm.get_data(DiagnosisDataType.STEP_METRICS) == []

    def test_latest_per_node(self):
        dm = DiagnosisDataManager()
        now = time.time()
        dm.store_data(0, "t", "old", timestamp=now - 100)
        dm.store_data(0, "t", "new", timestamp=now)
        dm.store_data(1, "t", "x", timestamp=now - 50)
        latest = dm.latest_per_node("t")
        assert latest[0].content == "new"
        assert latest[1].content == "x"


class TestHangOperator:
    def test_global_hang_via_speed_monitor(self):
        sm = SpeedMonitor()
        sm.collect_global_step(10, timestamp=time.time() - 100)
        op = CheckTrainingHangOperator(
            DiagnosisDataManager(), sm, hang_timeout_s=50.0
        )
        out = op.infer([Inference(InferenceName.TRAINING_HANG)])
        assert out and out[0].attribution == Attribution.HANG
        assert out[0].configs["node_id"] == "-1"

    def test_compile_grace_suppresses_alarm(self):
        sm = SpeedMonitor()  # no steps at all
        op = CheckTrainingHangOperator(
            DiagnosisDataManager(), sm,
            hang_timeout_s=0.01, compile_grace_s=3600,
        )
        assert op.infer([Inference(InferenceName.TRAINING_HANG)]) == []

    def test_per_node_stall(self):
        dm = DiagnosisDataManager()
        now = time.time()
        dm.store_data(0, DiagnosisDataType.STEP_METRICS, "{}", timestamp=now)
        dm.store_data(
            1, DiagnosisDataType.STEP_METRICS, "{}", timestamp=now - 500
        )
        sm = SpeedMonitor()
        sm.collect_global_step(5, timestamp=now)
        op = CheckTrainingHangOperator(dm, sm, hang_timeout_s=100.0)
        out = op.infer([Inference(InferenceName.TRAINING_HANG)])
        assert [i.configs["node_id"] for i in out] == ["1"]


class TestFailureOperator:
    def test_node_error_classified(self):
        dm = DiagnosisDataManager()
        dm.store_data(
            2, DiagnosisDataType.FAILURE, "TPU initialization failed on host"
        )
        dm.store_data(3, DiagnosisDataType.FAILURE, "KeyError: 'foo'")
        op = CheckFailureNodeOperator(dm)
        out = op.infer([Inference(InferenceName.NODE_FAILURE)])
        by_node = {i.configs["node_id"]: i.attribution for i in out}
        assert by_node["2"] == Attribution.FAILED
        assert by_node["3"] == Attribution.HEALTHY


class TestCoordinator:
    def test_actions_from_conclusions(self):
        conclusions = [
            Inference(
                InferenceName.TRAINING_HANG, Attribution.HANG,
                {"node_id": "1", "reason": "stalled"},
            ),
            Inference(
                InferenceName.NODE_FAILURE, Attribution.FAILED,
                {"node_id": "2", "reason": "sick"},
            ),
            Inference(
                InferenceName.NODE_FAILURE, Attribution.HEALTHY,
                {"node_id": "3"},
            ),
        ]
        actions = coordinate_solutions(conclusions)
        assert actions[1][0].action_type == DiagnosisActionType.RESTART_WORKER
        assert actions[2][0].action_type == (
            DiagnosisActionType.RELAUNCH_WORKER
        )
        assert 3 not in actions


class TestDiagnosisManager:
    def test_failure_report_to_action(self):
        mgr = DiagnosisManager()
        mgr.report_failure(
            m.NodeFailure(node_id=4, error_data="ICI link down on host")
        )
        actions = mgr.diagnose_once()
        assert 4 in actions
        popped = mgr.pop_actions(4)
        assert popped and popped[0].action_type == (
            DiagnosisActionType.RELAUNCH_WORKER
        )
        # Consumed on delivery.
        assert mgr.pop_actions(4) == []

    def test_duplicate_actions_not_queued(self):
        mgr = DiagnosisManager()
        mgr.report_failure(
            m.NodeFailure(node_id=4, error_data="hardware fault")
        )
        mgr.diagnose_once()
        mgr.diagnose_once()
        assert len(mgr.pop_actions(4)) == 1


class TestDiagnosisAgent:
    def _agent_with_logs(self, tmp_path, text):
        (tmp_path / "w0.log").write_text(text)
        return DiagnosisAgent(log_dir=str(tmp_path), max_in_place_restarts=3)

    def test_transient_error_restarts_in_place(self, tmp_path):
        agent = self._agent_with_logs(
            tmp_path, "RuntimeError: coordination service unavailable"
        )
        assert agent.diagnose_training_failure([(0, 1)], 1) == (
            DiagnosisActionType.RESTART_WORKER
        )

    def test_node_error_relaunches(self, tmp_path):
        agent = self._agent_with_logs(
            tmp_path, "FATAL: TPU initialization failed"
        )
        assert agent.diagnose_training_failure([(0, 1)], 1) == (
            DiagnosisActionType.RELAUNCH_WORKER
        )

    def test_budget_exhaustion_relaunches(self, tmp_path):
        agent = self._agent_with_logs(tmp_path, "ValueError: user bug")
        assert agent.diagnose_training_failure([(0, 1)], 4) == (
            DiagnosisActionType.RELAUNCH_WORKER
        )

    def test_log_collector_tails(self, tmp_path):
        (tmp_path / "a.log").write_text("x" * 100)
        col = TrainingLogCollector(str(tmp_path), tail_bytes=10)
        assert col.collect() == "x" * 10


class TestHangingDetector:
    def test_progress_then_stall(self):
        det = HangingDetector(hang_timeout_s=0.2, compile_grace_s=0.1)
        det.record_step(1)
        assert not det.is_hanging()
        time.sleep(0.3)
        assert det.is_hanging()
        det.record_step(2)
        assert not det.is_hanging()

    def test_callback_fires_once_per_stall(self, monkeypatch):
        """Eight checks 4 s apart against a 10 s timeout, on a stand-in
        clock: an alarm at 12 s and, the clock reset by it, at 24 s —
        one a stall, not one a check (six checks lie past the
        timeout)."""
        import types

        from dlrover_tpu.diagnosis import agent

        now = [1000.0]
        monkeypatch.setattr(
            agent, "time", types.SimpleNamespace(time=lambda: now[0]))

        class _EightChecks:
            left = 8

            def wait(self, dt):
                now[0] += dt
                self.left -= 1
                return self.left < 0

        fired = []
        det = HangingDetector(
            hang_timeout_s=10.0, compile_grace_s=0.0,
            on_hang=lambda: fired.append(now[0] - 1000.0),
            check_interval_s=4.0,
        )
        det.record_step(1)
        det._stop = _EightChecks()
        det._loop()
        assert fired == [12.0, 24.0]

    def test_heartbeat_file(self, tmp_path):
        hb = tmp_path / "hb"
        hb.write_text("1")
        det = HangingDetector(
            hang_timeout_s=100.0, heartbeat_file=str(hb)
        )
        assert not det.is_hanging()


class TestConfigTuner:
    def test_poll_writes_on_new_version(self, tmp_path):
        from dlrover_tpu.agent.config_tuner import (
            ParalConfigTuner,
            read_paral_config,
        )

        class StubClient:
            def __init__(self):
                self.cfg = m.ParallelConfig(
                    dataloader={"num_workers": 4}, version=1
                )

            def get_parallel_config(self):
                return self.cfg

        client = StubClient()
        tuner = ParalConfigTuner(
            client, config_path=str(tmp_path / "cfg.json")
        )
        assert tuner.poll_once()
        cfg = read_paral_config(tuner.config_path)
        assert cfg["dataloader"]["num_workers"] == 4
        # Same version: no rewrite.
        assert not tuner.poll_once()
        client.cfg = m.ParallelConfig(
            dataloader={"num_workers": 8}, version=2
        )
        assert tuner.poll_once()
        assert read_paral_config(tuner.config_path)["dataloader"][
            "num_workers"
        ] == 8


class TestStrategyGenerator:
    def test_memory_pressure_shrinks_workers(self):
        from dlrover_tpu.common.node import Node, NodeResource
        from dlrover_tpu.master.strategy_generator import (
            SimpleStrategyGenerator,
        )

        class StubJM:
            def __init__(self):
                n = Node("worker", 0)
                n.config_resource = NodeResource(memory_mb=1000)
                n.used_resource = NodeResource(cpu=80, memory_mb=950)
                self._nodes = {0: n}

            def all_nodes(self):
                return self._nodes

        gen = SimpleStrategyGenerator(StubJM())
        cfg = gen.generate_config()
        assert cfg.dataloader["num_workers"] == 1
        assert cfg.version == 1


class TestBroadcastActions:
    def _mgr(self):
        from dlrover_tpu.diagnosis.manager import DiagnosisManager
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        return DiagnosisManager(SpeedMonitor())

    def test_fanout_scoped_to_named_nodes(self):
        """Only nodes alive at enqueue time receive the instruction — a
        later joiner must NOT inherit it."""
        mgr = self._mgr()
        mgr.enqueue_broadcast("restart_worker", "peer 2 failed", [0, 1])
        a0 = mgr.pop_actions(0)
        assert [a.action_type for a in a0] == ["restart_worker"]
        # Delivery consumed it; no repeat on the next heartbeat.
        assert mgr.pop_actions(0) == []
        # Node 5 joined after the incident: nothing for it.
        assert mgr.pop_actions(5) == []
        # Node 1 still gets its own copy.
        assert [a.action_type for a in mgr.pop_actions(1)] == [
            "restart_worker"
        ]

    def test_repeat_failure_requeues_after_delivery(self):
        mgr = self._mgr()
        mgr.enqueue_broadcast("restart_worker", "peer 2 failed", [0])
        assert len(mgr.pop_actions(0)) == 1
        # Second incident with the SAME reason after delivery: re-queued.
        mgr.enqueue_broadcast("restart_worker", "peer 2 failed", [0])
        assert len(mgr.pop_actions(0)) == 1

    def test_pending_duplicate_not_double_queued(self):
        mgr = self._mgr()
        mgr.enqueue_broadcast("restart_worker", "peer 2 failed", [0])
        mgr.enqueue_broadcast("restart_worker", "peer 2 failed", [0])
        assert len(mgr.pop_actions(0)) == 1

    def test_stale_action_expires(self, monkeypatch):
        import time as _time

        mgr = self._mgr()
        mgr.enqueue_broadcast("restart_worker", "old incident", [0])
        real = _time.time
        monkeypatch.setattr(
            "dlrover_tpu.diagnosis.manager.time.time",
            lambda: real() + mgr.BROADCAST_TTL_S + 1,
        )
        # The node was unreachable past the TTL: must not be restarted
        # by a long-resolved incident.
        assert mgr.pop_actions(0) == []

    def test_payload_is_private_per_node(self):
        mgr = self._mgr()
        mgr.enqueue_broadcast("restart_worker", "peer failed", [0, 1])
        a0 = mgr.pop_actions(0)[0]
        a1 = mgr.pop_actions(1)[0]
        assert a0 is not a1  # no shared mutable object across replies
        assert "delivered" not in a0.payload


class TestWholeJobHangFanout:
    def test_global_hang_reaches_every_alive_node(self):
        """Regression: a whole-job hang (diagnosed under node -1) must
        fan out to the alive nodes' heartbeat queues — the action was
        silently undeliverable when pop_actions only served real ids."""
        from dlrover_tpu.master.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        sm.collect_global_step(10, timestamp=time.time() - 100)
        mgr = DiagnosisManager(
            sm, hang_timeout_s=50.0, alive_nodes_fn=lambda: [0, 1]
        )
        actions = mgr.diagnose_once()
        assert -1 in actions  # the hang was diagnosed job-wide
        for nid in (0, 1):
            got = mgr.pop_actions(nid)
            assert got and got[0].action_type == (
                DiagnosisActionType.RESTART_WORKER
            ), nid
        # Later joiner inherits nothing; incident cooldown holds.
        assert mgr.pop_actions(9) == []
        mgr.diagnose_once()
        assert mgr.pop_actions(0) == []
