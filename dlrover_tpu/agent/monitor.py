"""Agent-side monitors: node resource usage and training step metrics.

Parity with reference ``elastic_agent/monitor/resource.py:86``
(``ResourceMonitor``: psutil + pynvml -> ``report_used_resource``) and
``monitor/training.py:77`` (``TorchTrainingMonitor``).  Host metrics only:
the agent never opens the device runtime (see :func:`current_usage`); the
heartbeat itself lives in the training agent.
"""

from __future__ import annotations


import threading

from typing import Optional

from dlrover_tpu.common.log import logger


def _psutil():
    try:
        import psutil  # type: ignore

        return psutil
    except ImportError:  # pragma: no cover
        return None


def current_usage() -> dict:
    """Snapshot of host CPU/memory usage.  The agent asks JAX nothing: a
    chip belongs to the worker, and a parent that opens the device runtime
    takes it from its own child — device memory is the worker's to
    report."""
    out = {"cpu_percent": 0.0, "memory_mb": 0.0}
    ps = _psutil()
    if ps is not None:
        out["cpu_percent"] = ps.cpu_percent(interval=None)
        out["memory_mb"] = ps.virtual_memory().used / (1 << 20)
    return out


class ResourceMonitor:
    """Periodic used-resource reports to the master
    (reference ``resource.py:86``)."""

    def __init__(self, master_client, interval_s: float = 15.0):
        self._client = master_client
        self._interval = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="resource-monitor", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                usage = current_usage()
                self._client.report_used_resource(
                    cpu_percent=usage["cpu_percent"],
                    memory_mb=usage["memory_mb"],
                )
            except Exception as e:  # noqa: BLE001
                logger.debug("resource report failed: %s", e)


# Worker step metrics flow to the master's diagnosis store from
# ElasticContext.report_step (bootstrap.py) — the worker already holds the
# step counter, so no agent-side relay thread is needed.
