"""Flight recorder for both paths: the serving fleet's distributed
request tracing and control-plane event journal (ISSUE 12), and the
training path's spans inside save, restore, persist, restart, bootstrap
and build (ISSUE 23) — one recorder, one record format, one postmortem.

Three layers, all jax-free (never importing JAX: the launcher and the
agent record here, and the chip belongs to their worker):

- :mod:`~dlrover_tpu.obs.span` — trace identity (trace_id derived from
  the request id, so a failover resubmit joins the SAME trace with no
  wire coordination) and monotonic-clock spans with per-process epoch
  anchoring (each process pins ``wall - monotonic`` once at import, so
  merged timelines align across processes to clock-sync precision
  without ever measuring durations on the wall clock).
- :mod:`~dlrover_tpu.obs.recorder` — the per-process
  :class:`FlightRecorder`: a bounded ring of structured events (spans +
  control-plane journal entries) spilled as fsync'd JSONL on exit,
  SIGTERM, and chaos crashes (``chaos.on_crash``), and scrapeable live
  over the repo RPC idiom (``ObsScrapeRequest``).  Every ring drop is
  counted, never silent.  :func:`span` is the one span primitive of the
  training path: a context manager with monotonic ends, the parent
  span's id from a per-thread stack, ``args`` for counts, a bridge to
  ``jax.profiler.TraceAnnotation`` when (and only when) JAX is already
  loaded, and — for the low-rate spans — a write-through to the
  journal file as each ends, so a ``SIGKILL`` loses nothing.
- :mod:`~dlrover_tpu.obs.collect` / :mod:`~dlrover_tpu.obs.postmortem`
  — merge per-process dumps by trace_id into one Perfetto-loadable
  chrome trace (``utils/trace_analysis.py`` consumes it for rollups),
  validate span trees, and reconstruct a killed fleet's last seconds.

Enabled by ``DLROVER_TPU_OBS_DIR`` (dump directory; unset = ring-only,
still live-scrapeable).  ``DLROVER_TPU_OBS_PROCESS`` names the process
in dumps and merged traces; ``DLROVER_TPU_OBS_PARENT`` is the agent's
to set for its workers (the ``sid`` of the span that started them), so
that a restart is one tree across both processes.  A job under ``python -m dlrover_tpu.run``
has a directory without asking: ``<tmp>/dlrover_tpu_obs/<job>-<run
id>``, removed when the job ends with rc 0 (:func:`job_dir`).
"""

from dlrover_tpu.obs.recorder import (  # noqa: F401
    ENV_DIR,
    ENV_PARENT,
    ENV_PROCESS,
    FlightRecorder,
    configure,
    current_span_id,
    gc_job_dirs,
    get_recorder,
    job_dir,
    journal,
    record_span,
    reset,
    set_process,
    span,
)
from dlrover_tpu.obs.span import (  # noqa: F401
    anchored_us,
    new_span_id,
    trace_id_for,
)
