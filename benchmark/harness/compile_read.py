"""What the readers of JAX's own stages share: the ``jax.trace``,
``jax.lower`` and ``jax.compile`` spans the program records for every
function it traces, lowers and compiles or reads from the persistent cache
(``dlrover_tpu/common/jax_env.py::install_compile_listener``), each with
``fun_name``, ``jax.compile`` with the cache's verdict (``cache_hit``).

The four ``compile.*`` metrics move ``setup_s`` and so read only the stages
with an ``accelerate.*`` span among their ancestors (the program's build,
state and first step; in the elastic cell of the resumed incarnation): the
comparison's programs are the harness's and lie outside ``setup_s``.  A
correct run has no compile inside its window, so what is there is set-up.
``jax.trace`` nests (a traced function's inner
jits and primitives report their own), so seconds are the union of the
intervals, never the sum of the durations.

A program that records no such span (the parent of the PR that added them)
yields nothing, and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from benchmark.harness import obs_read

STAGES = ("jax.trace", "jax.lower", "jax.compile")


def _records(spans: dict) -> List[dict]:
    return obs_read.last_incarnation(obs_read.records(spans))


def _stages(recs: Iterable[dict], names=STAGES) -> List[dict]:
    return [r for r in recs
            if r.get("k") == "span" and r.get("name") in names]


def covered_s(spans_: Iterable[dict]) -> Optional[float]:
    """Seconds that at least one of the spans covers; None of no span."""
    spans_ = sorted(spans_, key=lambda s: s["ts"])
    if not spans_:
        return None
    total, reached = 0.0, float("-inf")
    for s in spans_:
        end = s["ts"] + s["dur"]
        if end > reached:
            total += end - max(s["ts"], reached)
            reached = end
    return total * 1e-6


def missed(spans_: Iterable[dict]) -> List[dict]:
    """The ``jax.compile`` spans the persistent cache did not have."""
    return [s for s in spans_ if s["name"] == "jax.compile"
            and (s.get("args") or {}).get("cache_hit") is False]


def by_cause(spans: dict) -> tuple:
    """The newest incarnation's stage spans as ``(inside, outside)``: those
    with an ``accelerate.*`` span among their ancestors — the program's own
    build, state and first step, which ``setup_s`` holds and the four
    ``compile.*`` metrics read — and those with none: the harness's
    comparison against the reference (taken out of ``setup_s`` as
    ``check_s``) or a user's own jits, which only the ``COMPILES`` line
    shows."""
    recs = _records(spans)
    by_sid = {r["sid"]: r for r in recs if r.get("k") == "span"}

    def caused_by_the_build(s: dict) -> bool:
        seen = set()
        while s.get("psid") in by_sid and s["psid"] not in seen:
            seen.add(s["psid"])
            s = by_sid[s["psid"]]
            if s["name"].startswith("accelerate."):
                return True
        return False

    inside, outside = [], []
    for s in _stages(recs):
        (inside if caused_by_the_build(s) else outside).append(s)
    return inside, outside


def build_stages(spans: dict, *names: str) -> Optional[List[dict]]:
    """The spans of these stages that the program's own build caused; None
    where the program recorded no span of these stages at all."""
    inside, outside = by_cause(spans)
    if not _stages(inside + outside, names):
        return None
    return _stages(inside, names)


def print_compiles(inside: List[dict], outside: List[dict]) -> None:
    """One line over EVERY stage span, whatever caused it: how many, how
    many misses, the seconds of those no ``accelerate.*`` span encloses
    (``outside_build_s``, the union of their intervals: the comparison's
    programs traced, lowered, compiled or read), then the five longest and
    every miss as ``fun_name:stage=seconds[:miss]``."""
    spans_ = inside + outside
    misses = {s["sid"] for s in missed(spans_)}
    by_length = sorted(spans_, key=lambda s: -s["dur"])
    shown = [s for i, s in enumerate(by_length)
             if i < 5 or s["sid"] in misses]
    print(f"COMPILES n={len(spans_)} misses={len(misses)} "
          f"outside_build_s={covered_s(outside) or 0.0:.3f} " + " ".join(
        "{}:{}={:.3f}{}".format(
            str((s.get("args") or {}).get("fun_name", "")).replace(" ", "_"),
            s["name"].split(".", 1)[1], s["dur"] * 1e-6,
            ":miss" if s["sid"] in misses else "")
        for s in shown), flush=True)
