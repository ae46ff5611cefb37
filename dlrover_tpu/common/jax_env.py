"""JAX process bootstrap shared by workers, servers, tests and bench: where
the compile cache lives, what device the process got, how many chips the
host exposes, and the multi-process runtime bring-up.

Nothing here imports JAX at module import: the agent and the launcher use
the JAX-free helpers (a chip belongs to one process — the one that runs the
model — and its parent must never open the device runtime).
"""

from __future__ import annotations

import glob
import os
import sys
import threading
import time
from typing import Optional

#: Where compiled programs are kept when ``JAX_COMPILATION_CACHE_DIR`` does
#: not place them: one fixed, git-ignored path inside the checkout.  The
#: path is part of the cache key's locality (a directory that moves never
#: hits), so it is never derived from $HOME, a temp name, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compilation_cache_dir() -> str:
    """The directory this process's compile cache uses (JAX-free)."""
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or DEFAULT_COMPILE_CACHE_DIR
    )


def enable_compilation_cache() -> bool:
    """Persistent XLA compilation cache (SURVEY §7 'warm-restart design:
    cache compiled executables keyed by topology').

    Elastic recovery is recompile-dominated: a restarted worker rebuilds
    the SAME jitted step the pre-kill worker already compiled, so a
    disk-backed cache turns most of that downtime into a cache read.
    There is one way to place the cache, JAX's own: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps it there and
    this function sets no directory; unset, it goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`.  ``DLROVER_TPU_COMPILE_CACHE=0``
    turns it off.  Returns True when enabled."""
    if os.environ.get("DLROVER_TPU_COMPILE_CACHE", "").lower() in (
        "0", "off", "false",
    ):
        return False
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    install_compile_listener()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    # Cache every executable: recovery cares about the long tail of
    # small programs too (the defaults skip fast compiles).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The cache backend LATCHES its directory (or a "no cache" decision)
    # at the first compile and ignores config updates afterwards; drop
    # the latch so the next compile binds to the settings above.
    compilation_cache.reset_cache()
    return True


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the devices this process got,
    as JAX reports them.  Opens the device runtime: only for the process
    that runs the model."""
    import jax

    from dlrover_tpu.obs import span

    install_compile_listener()
    # the first jax.devices() of a process initialises the backend and
    # takes the chip; a later call is a lookup
    with span("bootstrap.backend_init", "bootstrap",
              first=not device_runtime_opened()) as sp:
        devs = jax.devices()
        summary = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        sp.set(**summary)
    return summary


# ---------------------------------------------------------------------------
# JAX's own compile events as flight-recorder spans
# ---------------------------------------------------------------------------

#: JAX's duration events of the three stages every program passes through
#: on its way to the device, and the span each becomes (cat ``jax``)
_STAGE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
#: the persistent cache's two durations of a hit, by ``jax.compile`` arg
_CACHE_ARGS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}

#: A ``jax.trace`` shorter than this is not recorded.  JAX reports one for
#: every jitted function and primitive a trace passes through: thousands a
#: step build, nine in ten under a millisecond and nearly all inside the
#: trace of the function that called them, which is recorded — while the
#: ring they would fill holds 4,096 records.
MIN_TRACE_SPAN_S = 1e-3

_listener_mu = threading.Lock()
_listener_installed = False


class _ThreadCompiles(threading.local):
    """Per thread: what the cache has said since the thread's last
    ``jax.compile`` span (its events come before the duration of the
    compile that asked), and how many compiles it has answered either
    way."""

    def __init__(self):
        self.pending: dict = {}
        self.hits = self.misses = 0


_compiles = _ThreadCompiles()


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _compiles.pending["cache_hit"] = True
    elif event == _CACHE_MISS:
        _compiles.pending["cache_hit"] = False


def _on_stage_duration(event: str, secs: float, **kw) -> None:
    arg = _CACHE_ARGS.get(event)
    if arg is not None:
        _compiles.pending[arg] = round(secs, 6)
        return
    name = _STAGE_SPANS.get(event)
    if name is None or (name == "jax.trace" and secs < MIN_TRACE_SPAN_S):
        return
    from dlrover_tpu.obs import current_span_id, record_span

    args = {"fun_name": str(kw.get("fun_name", ""))}
    if name == "jax.compile":
        args.update({"cache_hit": None, **_compiles.pending})
        _compiles.pending = {}
        _compiles.hits += args["cache_hit"] is True
        _compiles.misses += args["cache_hit"] is False
    # JAX hands over a duration and no instants: the stage ended just
    # now, on the recorder's clock
    end = time.monotonic()
    record_span(name, "jax", end - secs, end, parent=current_span_id(),
                args=args, durable=True)


def install_compile_listener() -> None:
    """Every program this process traces, lowers and compiles (or reads
    from the persistent cache) from now on leaves ``jax.trace``,
    ``jax.lower`` and ``jax.compile`` spans (a trace only from
    :data:`MIN_TRACE_SPAN_S` up), each with ``fun_name`` and the span
    open on its thread as parent; ``jax.compile`` also says
    what the cache said (``cache_hit`` True, False, or None where it was
    not asked; on a hit ``retrieval_s`` and ``saved_s``).  One listener
    pair per process however often this is called; a warmed-up step
    fires neither."""
    global _listener_installed
    with _listener_mu:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_cache_event)
    jax.monitoring.register_event_duration_secs_listener(_on_stage_duration)


class CompileWatch:
    """The cache's verdict over the ``jax.compile`` spans this thread
    records from now on: ``cache_hit`` is True when every executable
    asked for came from the cache, False when one was compiled, None
    when the cache was not asked (disabled, or nothing compiled)."""

    def __init__(self):
        install_compile_listener()
        self._hits, self._misses = _compiles.hits, _compiles.misses

    @property
    def cache_hit(self) -> Optional[bool]:
        hits = _compiles.hits - self._hits
        misses = _compiles.misses - self._misses
        if not hits and not misses:
            return None
        return misses == 0


def host_chip_count() -> int:
    """TPU chips this process tree would drive on this host, counted from
    the device files libtpu opens — without touching JAX, so the launcher
    and the agent can ask.  0 when ``JAX_PLATFORMS`` puts the CPU first
    (tests, the virtual CPU mesh)."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if first == "cpu":
        return 0
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's account
    (``/proc/self/stat`` start time against the boot clock): what the
    interpreter's start-up and the imports took before any code of this
    package could stamp a clock.  -1 where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            # the command may hold spaces: count fields from its ')'
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        return uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return -1.0


def device_runtime_opened() -> bool:
    """Whether this process has initialised a JAX backend (the step that
    takes the chip).  JAX-free: a process that never imported JAX has
    not."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()


def initialize_distributed_from_env() -> bool:
    """Run ``jax.distributed.initialize`` from the agent-provided env
    contract (reference analogue: torchelastic's c10d store bootstrap, here
    replaced by master rendezvous -> coordinator election, SURVEY.md §5
    'Distributed communication backend').

    Returns True if a multi-process runtime was initialized.
    """
    from dlrover_tpu.common.env import (
        get_coordinator,
        get_num_processes,
        get_process_id,
    )

    coordinator = get_coordinator()
    nproc = get_num_processes()
    if not coordinator or nproc <= 1:
        return False
    import jax

    from dlrover_tpu.obs import span

    with span("bootstrap.distributed_init", "bootstrap",
              num_processes=nproc, rank=get_process_id()):
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=nproc,
            process_id=get_process_id(),
        )
    return True
