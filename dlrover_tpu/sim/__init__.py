"""The wind tunnel (ROADMAP item 7): deterministic fleet simulation.

A discrete-event harness that drives the repo's REAL registered
policy objects — gateway admission, spillover, autoscale, chip
borrows, federation placement and cross-cell moves — over synthetic
fleets and traces in virtual time.  Three rigs, one law:

* :class:`~dlrover_tpu.sim.serve.GlobalServeSim` — the micro rig: an
  event-by-event run of the global-serve scenario (real
  ``GatewayCore`` + ``CellSpillRouter`` per cell): blackout of the
  hot cell under a Zipf-over-cells trace, static against spillover.
* :class:`~dlrover_tpu.sim.cellsim.CellPlaneSim` — the control-plane
  rig: journaled-mutation shard physics over the real consistent
  hash; its floored row is analytic (``tests/test_sim.py``).
* :class:`~dlrover_tpu.sim.storm.FleetStormSim` — the macro rig:
  10,000 nodes, 24 cells, a day-long diurnal trace and chaos storms
  (correlated blackouts, gray networks, churn waves) no real bench
  could stage.
* :class:`~dlrover_tpu.sim.offline.OfflineTierSim` — the priority-
  class rig (ISSUE 20): the preemptible offline tier soaking the
  diurnal trough, instant reclaim at the peak, total evacuation
  under blackout storms — baseline vs offline over the same trace.

The law: same seed + same trace ⇒ byte-identical event log (the
double-run digest), because the only clock is the injected
:class:`~dlrover_tpu.sim.clock.VirtualClock` and the only randomness
is :mod:`~dlrover_tpu.sim.rand`'s coordinate hashing.
"""

from dlrover_tpu.sim.cellsim import CellPlaneSim, run_cell_rows
from dlrover_tpu.sim.clock import VirtualClock
from dlrover_tpu.sim.events import SimScheduler
from dlrover_tpu.sim.fleet import SimRole
from dlrover_tpu.sim.offline import OfflineTierSim, PreemptibleSimRole
from dlrover_tpu.sim.serve import GlobalServeSim, run_global_rows
from dlrover_tpu.sim.storm import FleetStormSim
from dlrover_tpu.sim.trace import StormSpec, TraceConfig, TraceGenerator

__all__ = [
    "CellPlaneSim",
    "FleetStormSim",
    "GlobalServeSim",
    "OfflineTierSim",
    "PreemptibleSimRole",
    "SimRole",
    "SimScheduler",
    "StormSpec",
    "TraceConfig",
    "TraceGenerator",
    "VirtualClock",
    "run_cell_rows",
    "run_global_rows",
]
