"""The readers of JAX's own stages (``harness/compile_read.py`` and the five
``program_span`` metrics built on it) on a small journal in the recorder's
format (``data/compile_journal/``: two worker incarnations, the spans of a
build, a state's init that missed the cache, a first call, a comparison
outside every ``accelerate.*`` span and a user's own jit; times on a grid of
half seconds), on the same spans in this process's ring, and end to end in a
rehearsal.  The metrics move ``setup_s``, which leaves the comparison out,
so they read the stages under an ``accelerate.*`` span and no others."""

import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

from benchmark.harness import common, compile_read, obs_read

DATA = os.path.join(os.path.dirname(__file__), "data", "compile_journal")
RAN = {"device_open_s": 1.0}
#: the resumed incarnation's numbers (incarnation 0 holds a 100 s miss)
WANT = {
    # [10,14] build + [20,21] init + [25,26] first call; the nested traces
    # add nothing, the comparison's [30,33] and [40,41.5] are not the tree's
    "compile.trace_lower_s": 6.0,
    "compile.backend_s": 4.0,      # 2 + 2; not the comparison's 4 + 0.5
    "compile.cache_miss_s": 2.0,   # jit(mk); not the comparison's jit(system)
    "compile.cache_misses": 1.0,
    "state.create_s": 3.0,
}
#: what no ``accelerate.*`` span encloses, on the COMPILES line only:
#: [30,37] + [40,42] comparison, [50,51] under a span not accelerate's
OUTSIDE_S = 10.0
STAGE_METRICS = sorted(m for m in WANT if m.startswith("compile."))


def _read(metric, spans=RAN):
    reader = common.load_module("layer_metrics", metric)
    return reader.read(spans, {}, {})


def _fixture_spans(name):
    with open(os.path.join(DATA, name)) as f:
        return [r for r in map(json.loads, f) if r["k"] == "span"]


def _ring_of(spans_, tmp_path, monkeypatch):
    """These spans in this process's ring, no directory."""
    from dlrover_tpu import obs
    from dlrover_tpu.obs.span import EPOCH_ANCHOR

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    rec = obs.configure()
    for s in spans_:
        start = s["ts"] * 1e-6 - EPOCH_ANCHOR
        rec.span(s["name"], s["cat"], start, start + s["dur"] * 1e-6,
                 span_id=s["sid"], parent=s.get("psid", ""),
                 args=s.get("args"))


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """The elastic cell: the journal where its launcher would have put it
    for this process."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    job = tmp_path / "dlrover_tpu_obs" / f"bench-{os.getpid()}-ab12cd34"
    shutil.copytree(DATA, job)
    monkeypatch.setattr(obs_read, "_removed_at_exit", set())
    monkeypatch.setattr(obs_read.atexit, "register", lambda *a, **k: None)
    return job


@pytest.fixture
def ring(tmp_path, monkeypatch):
    """A steady cell: the resumed incarnation's spans in this process's
    ring, no directory."""
    from dlrover_tpu import obs

    _ring_of(_fixture_spans("flight-worker-r0-i1-202.jsonl"), tmp_path,
             monkeypatch)
    yield
    obs.reset()


@pytest.mark.parametrize("metric,want", sorted(WANT.items()))
def test_stage_metrics_on_the_journal(journal, metric, want):
    assert _read(metric) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", sorted(WANT.items()))
def test_stage_metrics_on_the_ring(ring, metric, want):
    assert _read(metric) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_nothing_recorded_reads_as_nothing(tmp_path, monkeypatch, metric):
    """The parent of the PR that added the spans: a ring with its other
    spans and none of these; and no run at all."""
    from dlrover_tpu import obs

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    obs.configure()
    try:
        obs.get_recorder().span("accelerate.compile", "accelerate",
                                30.0, 31.0, args={"cache_hit": True})
        assert _read(metric) is None
        assert _read(metric, spans={}) is None
    finally:
        obs.reset()


def test_every_hit_reads_as_zero_not_as_nothing(tmp_path, monkeypatch,
                                                capsys):
    from dlrover_tpu import obs

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    obs.configure()
    try:
        with obs.span("accelerate.build", "accelerate") as build:
            pass
        obs.get_recorder().span(
            "jax.compile", "jax", 30.0, 31.0, parent=build.sid,
            args={"fun_name": "jit(f)", "cache_hit": True})
        assert _read("compile.cache_miss_s") == 0.0
        assert _read("compile.cache_misses") == 0.0
        assert _read("compile.backend_s") == pytest.approx(1.0)
        assert _read("compile.trace_lower_s") is None
        assert ("COMPILES n=1 misses=0 outside_build_s=0.000 "
                "jit(f):compile=1.000\n") in capsys.readouterr().out
    finally:
        obs.reset()


def test_the_compiles_line(journal, capsys):
    _read("compile.cache_miss_s")
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("COMPILES ")]
    # over every stage, the comparison's too (no metric holds its seconds
    # or its miss any more): the five longest, then every miss not among them
    assert line == (
        f"COMPILES n=17 misses=2 outside_build_s={OUTSIDE_S:.3f} "
        "jit(system):compile=4.000:miss "
        "train_step:trace=3.000 jit(train_step):compile=2.000 "
        "jit(mk):compile=2.000:miss system:trace=2.000")


def test_seconds_are_the_union_of_the_intervals():
    def spans(*pairs):
        return [{"ts": a * 1e6, "dur": (b - a) * 1e6} for a, b in pairs]

    assert compile_read.covered_s([]) is None
    assert compile_read.covered_s(spans((0, 2), (5, 6))) == 3.0
    # nested, overlapping, touching, and out of order
    assert compile_read.covered_s(
        spans((5, 6), (0, 4), (1, 2), (3, 5), (6, 7))) == 7.0


def test_an_ancestor_of_the_build_is_found_through_other_spans(
        monkeypatch):
    recs = [
        {"k": "span", "name": "accelerate.build", "sid": "B"},
        {"k": "span", "name": "user.inner", "sid": "U", "psid": "B"},
        {"k": "span", "name": "jax.trace", "sid": "1", "psid": "U"},
        {"k": "span", "name": "jax.trace", "sid": "2", "psid": "gone"},
        {"k": "span", "name": "jax.trace", "sid": "3"},
    ]
    monkeypatch.setattr(obs_read, "records", lambda spans: recs)
    inside, outside = compile_read.by_cause(RAN)
    assert [s["sid"] for s in inside] == ["1"]
    assert [s["sid"] for s in outside] == ["2", "3"]
    assert [s["sid"] for s in compile_read.build_stages(
        RAN, "jax.trace")] == ["1"]
    assert compile_read.build_stages(RAN, "jax.compile") is None
    monkeypatch.setattr(obs_read, "records", lambda spans: recs[:2])
    assert compile_read.by_cause(RAN) == ([], [])


@pytest.mark.parametrize("metric", STAGE_METRICS)
def test_stages_no_build_caused_move_no_metric(tmp_path, monkeypatch,
                                               metric):
    """The recorded ring with and without the comparison's and the user's
    stages reads the same; those stages alone read as a build that traced,
    compiled and missed nothing."""
    from dlrover_tpu import obs

    spans_ = _fixture_spans("flight-worker-r0-i1-202.jsonl")
    stray = [s for s in spans_ if s["cat"] == "jax" and s["args"][
        "fun_name"] in ("_where", "system", "jit(system)",
                        "against_reference", "jit(against_reference)",
                        "jit(evalfn)")]
    assert len(stray) == 8
    try:
        _ring_of([s for s in spans_ if s not in stray], tmp_path,
                 monkeypatch)
        assert _read(metric) == pytest.approx(WANT[metric])
        obs.reset()
        _ring_of(stray, tmp_path, monkeypatch)
        assert _read(metric) == {"compile.cache_miss_s": 0.0,
                                 "compile.cache_misses": 0.0}.get(metric)
    finally:
        obs.reset()


def test_fixture_is_what_the_program_writes():
    for name in sorted(os.listdir(DATA)):
        with open(os.path.join(DATA, name)) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines[0]["k"] == "meta" and lines[0]["reason"] == "journal"
        for rec in lines[1:]:
            assert {"name", "cat", "ts", "dur", "sid"} <= set(rec)
            if rec["cat"] == "jax":
                assert "fun_name" in rec["args"]
                assert ("cache_hit" in rec["args"]) == (
                    rec["name"] == "jax.compile")


GONE = {"bootstrap.backend_init_s", "compile.outside_build_s"}


@pytest.mark.parametrize(
    "cell", [w["name"] for w in common.load_spec()["workloads"]])
def test_a_rehearsal_finds_what_moves_the_set_up(cell):
    """End to end at toy widths on the CPU, every cell: the program
    records, the readers read (the elastic cell from the resumed worker's
    journal) every per-layer metric that moves ``setup_s`` (the kill, the
    restart and the restore lie inside it; ``resume_s`` until PR 50)
    — host stamps and spans, which the CPU has too — and neither of the two
    that read what the metrics no longer hold."""
    spec = common.load_spec()
    wanted = {m["name"] for m in common.metrics_for(spec, "per_layer", cell)
              if m["moves"] == "setup_s"}
    assert set(WANT) <= wanted and not GONE & wanted
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--rehearse", "--workload", cell, "--seconds", "2", "--trace", "1"],
        env=env, cwd=common.REPO, capture_output=True, text=True,
        timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    last = res.stdout.strip().splitlines()[-1]
    found = json.loads(last[last.index("{"):])
    assert found["correct"] and wanted <= set(found["metrics_found"])
    assert not GONE & set(found["metrics_found"])
    notes = res.stdout.splitlines()
    assert any(ln.startswith("COMPILES n=") and " outside_build_s=" in ln
               for ln in notes)
    # the account of the metric is in every run's output
    (setup,) = [ln for ln in notes if ln.startswith("SETUP_S ")]
    assert " total=" in setup and " backend_open_s" in setup
