"""From a profiler trace to numbers.  Two steps, kept apart so that the
arithmetic can be checked on a small recorded trace without JAX:

1. :func:`load_xplane` (needs ``jax.profiler.ProfileData``) turns an
   ``.xplane.pb`` into plain data::

       {"planes": [{"name": "/device:TPU:0", "lines": [
           {"name": "XLA Ops", "events": [[name, start_ns, dur_ns, tag], ..]}
       ]}]}

   On the v5e an op event is named by its whole HLO instruction
   (``%jvp_flash_fwd_.2 = (bf16[64,8192,128]..) custom-call(..),
   custom_call_target="tpu_custom_call", ..``): ``name`` keeps the
   instruction's name (``jvp_flash_fwd_.2`` — it carries the program's
   ``pallas_call(name=...)``) and ``tag`` its result shape, with ``pallas``
   in front where the instruction is a Mosaic kernel.
2. Everything else is interval arithmetic over that data.  The interval
   union is ``utils/trace_analysis.py::_merge_busy`` and the collective
   name prefixes are ``utils/op_metrics.py``'s, copied so that a later
   change to the program cannot move the yardstick.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: What the TensorCore runs, one op at a time.  ("Async XLA Ops", the copies
#: and collectives in flight beside it, exists on device 0 only and is not
#: read: a collective that overlaps compute is by definition not exposed.)
OP_LINE = "XLA Ops"
#: the program's pallas_call names (ops/; ``gmm`` and ``tgmm`` are the
#: megablox grouped matmuls ``ops/grouped_matmul.py`` calls).  A Mosaic
#: kernel of any other name is ``pallas_other``: a later PR that brings a
#: kernel adds its name here in a ``benchmark`` PR, or reads it there.
PALLAS_KERNELS = (
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rmsnorm_fwd",
    "softmax_xent_fwd", "quantize_blockwise",
    "gmm", "tgmm", "gather_sum", "ssd_chunk_fwd", "ssd_chunk_bwd",
)
FLASH_KERNELS = PALLAS_KERNELS[:3]
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
    "async-collective",  # -start/-done wrappers XLA:TPU puts around them
)
#: spans the benchmark's own loop writes (jax.profiler.TraceAnnotation);
#: they alone set the traced window
HOST_SPANS = ("batch_build", "dispatch", "loss_sync", "ckpt_save")
#: The program's own spans (``obs.span``, which enters a TraceAnnotation of
#: the same name where JAX is loaded) are named ``<category>.<what>``; the
#: categories are those of the README's "Observability" table of training
#: spans.  They label idle gaps and take no part in any other number.
PROGRAM_SPAN_PREFIXES = ("ckpt.", "agent.", "bootstrap.", "accelerate.",
                         "trainer.")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def split_instruction(text: str) -> Tuple[str, str]:
    """``(name, tag)`` of an op event named by its HLO instruction."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    clean = re.sub(r"\{[^}]*\}", "", rest[:600])  # layouts say nothing here
    shape = (clean[: clean.find(")") + 1] if clean.startswith("(")
             else clean.split(" ", 1)[0])[:80]
    tag = f"pallas {shape}" if PALLAS_TARGET in rest else shape
    return head.lstrip("%"), tag


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPAN_PREFIXES)


def load_xplane(path: str, keep_host: Sequence[str] = HOST_SPANS) -> dict:
    """Plain data of the device planes' op lines and of the host events
    named in ``keep_host`` or by the program's own spans (everything else
    on the host is dropped: the runtime's own events are many and nothing
    reads them)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not (is_dev or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name != OP_LINE:
                continue
            events = []
            for ev in line.events:
                if is_dev:
                    name, tag = split_instruction(ev.name)
                elif ev.name in keep_host or is_program_span(ev.name):
                    name, tag = ev.name, ""
                else:
                    continue
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), tag])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- interval arithmetic ----------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint union of [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged ``a`` that no interval of merged ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- what the trace holds ---------------------------------------------------


def device_planes(trace: dict) -> List[dict]:
    """The device planes on which at least one operation ran."""
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIX) and op_events(p)]


def op_events(plane: dict) -> List[list]:
    return [ev for line in plane["lines"] if line["name"] == OP_LINE
            for ev in line["events"]]


def host_spans(trace: dict, program: bool = False) -> List[list]:
    """The loop's own spans; with ``program`` the program's beside them."""
    return [ev for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]
            if ev[0] in HOST_SPANS or (program and is_program_span(ev[0]))]


def span_of_gap(gap: Interval, spans: List[list]) -> str:
    """The innermost span that covers most of an idle gap: of the spans
    that hold more than half of it the shortest (a span nested in another
    is the shorter one); where none does, the one that holds most."""
    held = [(overlap(gap, (sp[1], sp[1] + sp[2])), sp) for sp in spans]
    held = [(ov, sp) for ov, sp in held if ov > 0]
    if not held:
        return "no_span"
    most = [sp for ov, sp in held if ov > (gap[1] - gap[0]) / 2]
    if most:
        return min(most, key=lambda sp: sp[2])[0]
    return max(held, key=lambda h: h[0])[1][0]


def is_collective(name: str) -> bool:
    n = name.lower().lstrip("%")
    return any(n.startswith(p) for p in COLLECTIVE_PREFIXES)


def kernel_of(ev: list) -> Optional[str]:
    """Which of the repo's Pallas kernels this device event is: a Mosaic
    custom call whose instruction name carries the ``pallas_call`` name
    (``jvp_flash_fwd_.2``, ``transpose_jvp_flash_bwd_dkv__.3``)."""
    if not ev[3].startswith("pallas"):
        return None
    # longest first: flash_bwd_dkv before flash_bwd_dq, tgmm before gmm
    for k in sorted(PALLAS_KERNELS, key=len, reverse=True):
        if k in ev[0]:
            return k
    return "pallas_other"


def self_times(events: List[list]) -> List[Tuple[list, float, List[Interval]]]:
    """(event, self seconds, self intervals): an event's extent minus that
    of the events nested directly inside it on the same line (a ``while``
    holds the ops of its body), so that sums over events never count a
    nanosecond twice."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    children: List[List[Interval]] = [[] for _ in order]
    stack: List[int] = []
    for i, ev in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= ev[1]:
            stack.pop()
        if stack:
            children[stack[-1]].append((ev[1], ev[1] + ev[2]))
        stack.append(i)
    out = []
    for ev, kids in zip(order, children):
        own = subtract([(ev[1], ev[1] + ev[2])], merge(kids))
        out.append((ev, total(own) * 1e-9, own))
    return out


def window_of(trace: dict) -> Interval:
    """The traced window in ns: first host span start to last host span
    end where the loop wrote spans, else the extent of the device ops."""
    spans = host_spans(trace)
    evs = spans or [ev for p in device_planes(trace) for ev in op_events(p)]
    if not evs:
        return (0.0, 0.0)
    return (min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs))


def _clip(intervals: List[Interval], win: Interval) -> List[Interval]:
    return [(max(s, win[0]), min(e, win[1])) for s, e in intervals
            if min(e, win[1]) > max(s, win[0])]


def reduce_trace(trace: dict) -> dict:
    """Every number the per-layer readers take from a trace, in seconds,
    averaged over the device planes where a device metric is per chip::

        window_s, busy_s, idle_share, kernel_s {name: s}, collective_s,
        exposed_collective_s, op_self_s {name: s}, idle_gaps {label: s},
        kernel_call_s {name: {instruction: s}}, n_devices

    ``op_self_s`` files every call of a Mosaic kernel under the kernel's
    name; ``kernel_call_s`` keeps the same seconds by the calling
    instruction (``gather_sum.16``), which is what the program's scope
    table names: a kernel called under two scopes is placed call by call
    (``obs_read.scope_shares``).
    """
    planes = device_planes(trace)
    if not planes:
        return {}
    win = window_of(trace)
    window_s = (win[1] - win[0]) * 1e-9
    if window_s <= 0:
        return {}
    spans = host_spans(trace, program=True)
    busy_s = coll_s = exposed_s = 0.0
    kernel_s: Dict[str, float] = {}
    kernel_calls: Dict[str, Dict[str, float]] = {}
    op_self: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for plane in planes:
        evs = op_events(plane)
        busy = _clip(merge((ev[1], ev[1] + ev[2]) for ev in evs), win)
        busy_s += total(busy) * 1e-9
        timed = self_times(evs)
        coll = _clip(merge((ev[1], ev[1] + ev[2]) for ev in evs
                           if is_collective(ev[0])), win)
        # compute = the self time of ops that are no collective: a `while`
        # that only holds its body must not hide the collectives inside it
        compute = _clip(merge(
            iv for ev, _, own in timed if not is_collective(ev[0])
            for iv in own), win)
        coll_s += total(coll) * 1e-9
        exposed_s += total(subtract(coll, compute)) * 1e-9
        for ev, self_s, _ in timed:
            if not overlap((ev[1], ev[1] + ev[2]), win):
                continue
            k = kernel_of(ev)
            if k is not None:
                kernel_s[k] = kernel_s.get(k, 0.0) + ev[2] * 1e-9
                calls = kernel_calls.setdefault(k, {})
                calls[ev[0]] = calls.get(ev[0], 0.0) + self_s
            label = k or f"{ev[0]} {ev[3]}".strip()
            op_self[label] = op_self.get(label, 0.0) + self_s
        for gap in subtract([win], busy):
            label = span_of_gap(gap, spans)
            gap_s[label] = gap_s.get(label, 0.0) + (gap[1] - gap[0]) * 1e-9
    n = len(planes)
    avg = lambda d: {k: v / n for k, v in d.items()}  # noqa: E731
    return {
        "n_devices": n,
        "window_s": window_s,
        "busy_s": busy_s / n,
        "idle_share": 1.0 - busy_s / n / window_s,
        "kernel_s": avg(kernel_s),
        "kernel_call_s": {k: avg(v) for k, v in kernel_calls.items()},
        "collective_s": coll_s / n,
        "exposed_collective_s": exposed_s / n,
        "op_self_s": avg(op_self),
        "idle_gaps": avg(gap_s),
    }


def breakdown(reduced: dict, k: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the ``k`` device
    operations with most self time and the idle time by host span (the
    loop's own and the program's)."""
    top = lambda d: [  # noqa: E731
        [name, sec] for name, sec in sorted(
            d.items(), key=lambda kv: -kv[1])[:k]]
    return {"device_ops": top(reduced.get("op_self_s", {})),
            "idle_gaps": top(reduced.get("idle_gaps", {}))}
