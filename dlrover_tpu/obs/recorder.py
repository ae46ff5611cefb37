"""The per-process flight recorder (ISSUE 12; training path ISSUE 23).

A bounded ring of structured events — request spans, the training
path's spans (save, restore, persist, restart, bootstrap, build) and
control-plane journal entries — that SURVIVES the process's death:

- SIGKILL (the OOM killer, a preempted host): the low-rate spans of
  :func:`span` are appended to the process's journal file as they end
  (one ``write`` + ``flush``, no ``fsync``) when a dump directory is
  set, so what a killed worker or agent did up to its death is on disk
  without any hook having run;
- normal exit: an ``atexit`` hook spills the ring as fsync'd JSONL;
- SIGTERM: a handler (installed only when the process had no handler of
  its own — embedders' handlers are never displaced) spills, restores
  the default disposition, and re-raises;
- chaos crash: :func:`dlrover_tpu.chaos.on_crash` fires the spill
  BEFORE ``os._exit``, naming the injected site in the dump header —
  a chaos kill simulates SIGKILL for every OTHER subsystem (no atexit,
  no finally), but the flight recorder is exactly the black box that
  must survive the crash, so it gets the one pre-exit callback;
- live: any process holding the repo RPC idiom can answer
  ``ObsScrapeRequest`` from :meth:`FlightRecorder.snapshot`.

The ring is bounded (``capacity`` events) because a flight recorder's
job is the LAST seconds, not an archive; every eviction is counted in
``dropped`` and exported — a drop is never silent.
"""

from __future__ import annotations

import atexit
import json
import os
import resource
import signal
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.obs.span import EPOCH_ANCHOR, anchored_us, new_span_id

ENV_DIR = "DLROVER_TPU_OBS_DIR"
ENV_PROCESS = "DLROVER_TPU_OBS_PROCESS"
#: the ``sid`` of the agent's span that started this process
#: (``agent.start_workers``): set by the agent for its workers, never by
#: a user — what joins a worker's journal to the restart that caused it
ENV_PARENT = "DLROVER_TPU_OBS_PARENT"
ENV_CAPACITY = "DLROVER_TPU_OBS_CAPACITY"


class FlightRecorder:
    """Bounded, thread-safe ring of span/journal events.

    All public methods are cheap enough for the serving data plane's
    per-request rate (a dict build + deque append under one lock); the
    decision whether a request is traced at all is the gateway's
    head-based sampling, not this class's concern."""

    def __init__(self, capacity: int = 4096, process: str = "",
                 out_dir: Optional[str] = None,
                 clock=time.monotonic):
        self.capacity = int(capacity)
        self.process = process or f"pid{os.getpid()}"
        self.out_dir = out_dir
        self._clock = clock
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        self.dropped = 0
        self.spans = 0
        self.events = 0
        self._dumped_reason: Optional[str] = None
        # Write-through journal (SIGKILL survival): records already on
        # disk, kept so that the exit dump's rewrite cannot lose the
        # ones the ring has since evicted.
        self._io_mu = threading.Lock()
        self._durable: deque = deque(maxlen=self.capacity)
        self._journal_file = None

    # -- recording --------------------------------------------------------

    def _append_locked(self, rec: Dict[str, Any]) -> None:
        self._seq += 1
        rec["seq"] = self._seq
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(rec)

    def span(self, name: str, cat: str, start_s: float, end_s: float,
             trace_id: str = "", span_id: Optional[str] = None,
             parent: str = "", args: Optional[dict] = None,
             durable: bool = False) -> str:
        """Record one completed span (monotonic instants in, anchored
        microseconds stored).  Returns the span id.  ``durable`` also
        appends it to the journal file now (low-rate spans only)."""
        sid = span_id or new_span_id()
        rec: Dict[str, Any] = {
            "k": "span", "name": name, "cat": cat,
            "ts": round(anchored_us(start_s), 1),
            "dur": round(max(0.0, end_s - start_s) * 1e6, 1),
            "tid": trace_id, "sid": sid,
        }
        if parent:
            rec["psid"] = parent
        if args:
            rec["args"] = args
        with self._mu:
            self.spans += 1
            self._append_locked(rec)
        if durable:
            self._write_through(rec)
        return sid

    def event(self, kind: str, durable: bool = False,
              **fields: Any) -> None:
        """Record one control-plane journal event (reshard transition,
        checkpoint commit verdict, reconcile decision, chaos firing,
        ...).  ``fields`` must be JSON/msgpack-safe scalars/containers."""
        rec: Dict[str, Any] = {
            "k": "ev", "kind": kind,
            "ts": round(anchored_us(self._clock()), 1),
        }
        rec.update(fields)
        with self._mu:
            self.events += 1
            self._append_locked(rec)
        if durable:
            self._write_through(rec)

    def _meta(self, reason: str, chaos_site: str = "",
              events: int = 0) -> Dict[str, Any]:
        return {
            "k": "meta", "process": self.process,
            "pid": os.getpid(), "anchor": EPOCH_ANCHOR,
            "reason": reason, "chaos_site": chaos_site,
            "dumped_at": round(anchored_us(self._clock()), 1),
            "dropped": self.dropped, "events": events,
        }

    def _write_through(self, rec: Dict[str, Any]) -> None:
        """Append one record to the journal file (no dump directory: the
        ring alone).  The file opens with a meta line whose reason,
        ``journal``, is what a reader sees when the process died with no
        hook run; any later :meth:`dump` rewrites the file whole."""
        path = self.dump_path()
        if path is None:
            return
        with self._io_mu:
            self._durable.append(rec)
            try:
                if self._journal_file is None:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    self._journal_file = open(path, "a")
                    self._journal_file.write(
                        json.dumps(self._meta("journal")) + "\n")
                self._journal_file.write(json.dumps(rec) + "\n")
                self._journal_file.flush()
            except OSError as e:
                logger.warning("flight recorder journal %s: %s", path, e)

    def close(self) -> None:
        with self._io_mu:
            if self._journal_file is not None:
                self._journal_file.close()
                self._journal_file = None

    # -- reading ----------------------------------------------------------

    def snapshot(self, since_seq: int = 0
                 ) -> Tuple[List[Dict[str, Any]], int, int]:
        """(events newer than ``since_seq``, lifetime drop count, next
        cursor) — the live-scrape read."""
        with self._mu:
            evs = [dict(r) for r in self._ring
                   if r["seq"] > since_seq]
            return evs, self.dropped, self._seq

    def stats(self) -> Dict[str, int]:
        with self._mu:
            return {"spans": self.spans, "events": self.events,
                    "dropped": self.dropped, "ring": len(self._ring)}

    # -- spilling ---------------------------------------------------------

    def dump_path(self) -> Optional[str]:
        if not self.out_dir:
            return None
        return os.path.join(
            self.out_dir,
            f"flight-{self.process}-{os.getpid()}.jsonl",
        )

    def dump(self, path: Optional[str] = None, reason: str = "exit",
             chaos_site: str = "") -> Optional[str]:
        """Spill the ring as fsync'd JSONL (atomic tmp+rename): a meta
        header line, then every retained event.  Safe to call multiple
        times (each dump rewrites with the current ring — the LAST one
        wins, which is the crash semantics a flight recorder wants).
        Returns the path, or None when no target is configured."""
        path = path or self.dump_path()
        if path is None:
            return None
        with self._mu:
            evs = list(self._ring)
            # journalled records the ring has evicted stay in the file
            oldest = evs[0]["seq"] if evs else self._seq + 1
            with self._io_mu:
                evs = [r for r in self._durable
                       if r["seq"] < oldest] + evs
            meta = self._meta(reason, chaos_site, len(evs))
            self._dumped_reason = reason
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(tmp, "w") as f:
                f.write(json.dumps(meta) + "\n")
                for rec in evs:
                    f.write(json.dumps(rec) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            logger.warning("flight recorder dump to %s failed: %s",
                           path, e)
            return None
        if path == self.dump_path():
            self.close()  # the journal's handle names the replaced file
        return path


# ---------------------------------------------------------------------------
# The process-global recorder
# ---------------------------------------------------------------------------

_mu = threading.Lock()
_RECORDER: Optional[FlightRecorder] = None
_hooks_installed = False


def _install_hooks(rec: FlightRecorder) -> None:
    """Exit/crash spill hooks, once per process.  Only when a dump
    directory exists — a ring-only recorder has nothing to spill."""
    global _hooks_installed
    if _hooks_installed or not rec.out_dir:
        return
    _hooks_installed = True

    def _atexit_dump() -> None:
        r = _RECORDER
        if r is not None and r._dumped_reason is None:
            r.dump(reason="exit")

    atexit.register(_atexit_dump)

    from dlrover_tpu import chaos

    def _chaos_dump(site: str, ctx: dict) -> None:
        r = _RECORDER
        if r is not None:
            r.event("chaos.crash", site=site,
                    ctx={k: v for k, v in ctx.items()
                         if isinstance(v, (str, int, float, bool))})
            r.dump(reason="chaos", chaos_site=site)

    chaos.on_crash(_chaos_dump)

    # SIGTERM: spill, then die with the default disposition.  Installed
    # ONLY when the process has no handler (embedders that set their
    # own — the fleet example's clean-stop path — reach the atexit
    # spill instead; displacing their handler would break their
    # shutdown).  Never from a non-main thread (signal.signal raises).
    try:
        if (threading.current_thread() is threading.main_thread()
                and signal.getsignal(signal.SIGTERM)
                == signal.SIG_DFL):
            def _term(signum, frame):
                r = _RECORDER
                if r is not None:
                    r.dump(reason="sigterm")
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, _term)
    except (ValueError, OSError) as e:
        logger.debug("obs: SIGTERM hook not installed: %s", e)


def job_dir(job_name: str, run_id: str = "") -> str:
    """Where a launched job's processes journal when the operator named
    no ``DLROVER_TPU_OBS_DIR``: one directory per launcher invocation
    under the temp dir (``tempfile.gettempdir()`` honours ``TMPDIR``)."""
    import tempfile

    safe = job_name.replace("/", "_")
    return os.path.join(
        tempfile.gettempdir(), "dlrover_tpu_obs",
        f"{safe}-{run_id}" if run_id else safe,
    )


def gc_job_dirs(max_age_s: float = 7 * 86400.0) -> None:
    """Launch-time retention for :func:`job_dir`'s directories: a job
    that did not end well keeps its journals for the postmortem, not for
    ever.  Only directories nothing has written to for ``max_age_s``."""
    import glob
    import shutil

    now = time.time()
    for path in glob.glob(os.path.join(os.path.dirname(job_dir("x")), "*")):
        try:
            newest = max(os.stat(p).st_mtime for p in
                         [path] + glob.glob(os.path.join(path, "*")))
        except OSError:
            continue
        # graftcheck: disable=OB301 -- compared against files' wall-clock
        # mtimes; wall time is the point here
        if now - newest > max_age_s:
            shutil.rmtree(path, ignore_errors=True)


def get_recorder() -> FlightRecorder:
    """The process recorder, created on first use from the environment
    (``DLROVER_TPU_OBS_DIR`` / ``_PROCESS`` / ``_CAPACITY``)."""
    global _RECORDER
    rec = _RECORDER
    if rec is not None:
        return rec
    with _mu:
        if _RECORDER is None:
            out_dir = os.environ.get(ENV_DIR) or None
            try:
                cap = int(os.environ.get(ENV_CAPACITY, "") or 4096)
            except ValueError:
                cap = 4096
            _RECORDER = FlightRecorder(
                capacity=cap,
                process=os.environ.get(ENV_PROCESS, ""),
                out_dir=out_dir,
            )
            _install_hooks(_RECORDER)
        return _RECORDER


def configure(out_dir: Optional[str] = None, process: str = "",
              capacity: int = 4096) -> FlightRecorder:
    """Install a fresh process recorder explicitly (tests, embedders).
    Replaces any existing one; the exit hooks always act on the
    CURRENT recorder, so replacement never dangles a hook."""
    global _RECORDER
    with _mu:
        if _RECORDER is not None:
            _RECORDER.close()
        _RECORDER = FlightRecorder(
            capacity=capacity, process=process, out_dir=out_dir,
        )
        _install_hooks(_RECORDER)
        return _RECORDER


def reset() -> None:
    """Drop the process recorder (tests).  The next use re-reads env."""
    global _RECORDER
    with _mu:
        if _RECORDER is not None:
            _RECORDER.close()
        _RECORDER = None


def set_process(name: str) -> None:
    """Name this process in dumps/merged traces (``gw-g0``, ``rep-r1``)
    — later configuration wins, env stays the default."""
    if name:
        get_recorder().process = name


def journal(kind: str, durable: bool = False, **fields: Any) -> None:
    """Record one control-plane event on the process recorder — the
    one-liner the fleet/reshard/checkpoint/autoscale layers call.
    ``durable`` also appends it to the journal file now (the training
    path's low-rate events: they must survive a SIGKILL)."""
    get_recorder().event(kind, durable=durable, **fields)


class Span:
    """One span of the program's own work, recorded where the work
    happens: ``with obs.span("ckpt.save", "ckpt", step=3) as sp:``.

    Monotonic start and end; the parent is the span open on this thread
    when this one starts (``psid``), so self time is duration minus
    children; ``args`` carry the counts taken at the same boundary
    (``sp.set(bytes=n)`` adds what is only known inside).  Where a span
    outlives one block (the agent's restart runs across loop turns) use
    :meth:`start` / :meth:`end`.

    ``host=True`` (a span whose seconds are bytes moving: a copy, a
    write) also reads the process's CPU clock and its page-fault counts
    at both ends — ``time.process_time()`` and ``getrusage(RUSAGE_SELF)``,
    every thread of the process, so the runtime's copy threads count —
    and adds ``cpu_s``, and where the platform counts faults at all
    ``minflt`` and ``majflt``, to ``args``: ``cpu_s`` far under the span's
    seconds says the process slept (on a DMA, on a disk); near or above
    them, that the host was copying or faulting pages in.

    When — and only when — JAX is already loaded in this process the
    span also enters ``jax.profiler.TraceAnnotation(name)``, so under
    any profiler session it lies on the trace's host plane beside the
    device ops, on the profiler's clock.  This module never imports
    JAX: the launcher and the agent use it."""

    __slots__ = ("name", "cat", "args", "sid", "psid", "ring_only",
                 "host", "_t0", "_annotation", "_host0")

    def __init__(self, name: str, cat: str, ring_only: bool = False,
                 parent: str = "", host: bool = False, **args: Any):
        self.name, self.cat, self.args = name, cat, args
        self.ring_only = ring_only
        self.host = host
        self.sid = new_span_id()
        self.psid = parent
        self._t0: Optional[float] = None
        self._annotation = None
        self._host0 = None

    def set(self, **args: Any) -> None:
        self.args.update(args)

    def start(self) -> "Span":
        stack = _open_spans()
        if not self.psid and stack:
            self.psid = stack[-1]
        stack.append(self.sid)
        if "jax" in sys.modules:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()
        if self.host:
            self._host0 = _host_cost()
        self._t0 = time.monotonic()
        return self

    def end(self, **args: Any) -> None:
        if self._t0 is None:
            return  # never started, or ended already
        t1 = time.monotonic()
        if self._host0 is not None:
            cpu, minflt, majflt = _host_cost()
            cpu0, minflt0, majflt0 = self._host0
            self._host0 = None
            self.args["cpu_s"] = round(cpu - cpu0, 6)
            if minflt or majflt:  # a platform that counts none reads 0
                self.args.update(minflt=minflt - minflt0,
                                 majflt=majflt - majflt0)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        stack = _open_spans()
        if self.sid in stack:
            del stack[stack.index(self.sid):]
        self.args.update(args)
        get_recorder().span(
            self.name, self.cat, self._t0, t1, span_id=self.sid,
            parent=self.psid, args=self.args or None,
            durable=not self.ring_only,
        )
        self._t0 = None

    __enter__ = start

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.end()


def _host_cost() -> Tuple[float, int, int]:
    """CPU seconds of every thread of this process, and its minor and
    major page faults, so far."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return time.process_time(), usage.ru_minflt, usage.ru_majflt


_tls = threading.local()


def _open_spans() -> List[str]:
    """Ids of the spans open on this thread, outermost first."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_span_id() -> str:
    """The id of the innermost span open on this thread, "" when none:
    the parent of a span recorded after the fact (:func:`record_span`)."""
    stack = _open_spans()
    return stack[-1] if stack else ""


def span(name: str, cat: str, ring_only: bool = False, parent: str = "",
         host: bool = False, **args: Any) -> Span:
    """The span primitive (see :class:`Span`).  Low-rate spans (save,
    load, persist, restart, bootstrap, build) are journalled to disk as
    they end; ``ring_only`` keeps a per-step span in the ring alone.
    ``parent`` names the causing span's ``sid`` across threads — or
    across processes (:data:`ENV_PARENT`); ``host`` adds the process's
    CPU seconds and page faults between the span's ends."""
    return Span(name, cat, ring_only=ring_only, parent=parent, host=host,
                **args)


def record_span(name: str, cat: str, start_s: float, end_s: float,
                trace_id: str = "", span_id: Optional[str] = None,
                parent: str = "", args: Optional[dict] = None,
                durable: bool = False) -> str:
    """Record one span on the process recorder (hot-path one-liner)."""
    return get_recorder().span(
        name, cat, start_s, end_s, trace_id=trace_id,
        span_id=span_id, parent=parent, args=args, durable=durable,
    )
