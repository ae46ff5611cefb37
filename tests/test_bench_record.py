"""bench.py's device-facing head and its CPU bench-smoke schema gates.

``main()`` measures on a TPU or fails: there is no CPU fallback, no stale
"number of record", and the peak a utilization is divided by comes from one
table keyed by ``device_kind`` — a device that is not in it is an error, not
a default.
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import bench  # noqa: E402


def test_peak_is_looked_up_by_device_kind():
    # what jax.devices()[0].device_kind says on a v5e chip
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "", "tpu v5 lite"])
def test_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(ValueError, match="no published bf16 peak"):
        bench.peak_bf16_flops(kind)


def test_main_fails_without_a_tpu(capsys):
    """On the CPU (where the tests run) ``main()`` refuses before it
    measures anything and says which device it found."""
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert "value" not in out and "TPU" in out["error"]


def test_goodput_is_its_own_command_that_imports_no_jax():
    """Its worker needs the chip, so the process that launches the tree
    must not hold it: the probe left ``main()`` for ``--goodput``."""
    import inspect

    assert bench.SUBCOMMANDS["--goodput"] is bench.goodput_main
    assert "measure_goodput" not in inspect.getsource(bench.main)
    for fn in (bench.goodput_main, bench.measure_goodput):
        assert "import jax" not in inspect.getsource(fn)
    assert bench.goodput_main(["gpu"]) == 2  # usage


def test_ckpt_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 4's checkpoint bench: the tiny config runs
    end-to-end on CPU inside the 5s budget and emits schema-valid JSON —
    before/after persist rows with the copy audit, the per-save stall
    list, byte-identity and fsck flags, and the final metric line."""
    import os
    import subprocess
    import time

    out = tmp_path / "CKPT_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--ckpt_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=60, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    # <5s is the spec on an idle host; allow CI contention headroom but
    # fail loudly if the smoke config ever becomes heavyweight.
    assert elapsed < 20.0, f"smoke bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["complete"] is True
    assert result["byte_identical"] is True
    assert result["fsck_clean_on_streamed"] is True
    rows = {r["path"]: r for r in result["rows"]}
    assert "before_pack_copy" in rows and "after_stream_w1" in rows
    # The acceptance hook: legacy copies the state 3x; the streamed path
    # does exactly one pass with zero intermediate copies.
    assert rows["before_pack_copy"]["state_copies"] == 3.0
    assert rows["after_stream_w1"]["state_copies"] == 0.0
    assert rows["after_stream_w1"]["write_passes"] == 1
    stalls = result["save_to_memory"]["stall_ms_per_save"]
    assert len(stalls) >= 2 and all(s > 0 for s in stalls)
    assert result["restore_mbps"] > 0
    # Scale-out rows (ISSUE 7): sliced rows at 1 and 2 ranks, each rank
    # writing a disjoint share, plus an incremental row whose write cost
    # tracks the dirty bytes; sliced+incremental restore byte-exact and
    # fsck-clean.  (Schema + invariants only — the ≥1.7x aggregate
    # scaling target is asserted on the committed full-size artifact,
    # not under CI contention.)
    scale = result["scaleout"]
    rows = {(r["ranks"], r["kind"]): r for r in scale["rows"]}
    r1 = rows[(1, "sliced_full")]
    r2 = rows[(2, "sliced_full")]
    assert r1["committed"] and r2["committed"]
    assert r2["per_rank_written_mb"] <= r1["per_rank_written_mb"] / 2 + 0.1
    inc = rows[(2, "incremental_10pct_dirty")]
    assert inc["committed"]
    assert inc["written_bytes_over_dirty_bytes"] <= 1.5
    assert inc["tensors_skipped"] > 0
    assert scale["restore_byte_exact"] is True
    assert scale["fsck_clean_on_sliced"] is True
    assert scale["speedup_2_ranks_vs_1"] > 1.0
    # Final stdout line is the standard bench metric record.
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "ckpt_persist_speedup"
    assert metric["artifact"] == str(out)
    assert isinstance(metric["value"], (int, float))


def test_serve_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 5's serving-fleet bench: the smoke config
    (one in-process loopback replica, tiny workload, no round floor)
    runs end-to-end on CPU inside the budget and emits schema-valid
    JSON — the workload block, a complete single-replica row with TTFT
    percentiles, and the standard metric line."""
    import os
    import subprocess
    import time

    out = tmp_path / "SERVE_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--serve_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    # ~60-80s observed on an idle host: the smoke now stands up ten
    # small fleets (plain + 4 routing planes + 2 tracing rows + 4
    # speculation rows) plus four in-process paged-KV A/B servers, and
    # each fresh DecodeServer instance pays its own XLA warmup
    # compiles; allow CI contention headroom but fail loudly if the
    # smoke config ever becomes heavyweight beyond that.
    assert elapsed < 200.0, f"smoke serve bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["complete"] is True
    assert result["workload"]["requests"] == 5
    assert result["device_round_ms"] == 0.0
    assert len(result["rows"]) == 1
    row = result["rows"][0]
    assert row["replicas"] == 1
    assert row["completed"] == 5
    assert row["new_tokens"] == 5 * 6  # full budget, greedy, no EOS
    assert row["tokens_per_sec"] > 0
    assert row["ttft_ms_p50"] > 0 and row["ttft_ms_p99"] >= \
        row["ttft_ms_p50"]
    assert row["latency_ms_p99"] >= row["latency_ms_p50"]
    assert row["rejected"] == 0 and row["redispatched"] == 0
    # Routing rows (ISSUE 8): one Zipf prefix workload under the three
    # data planes — least-loaded, prefix-aware, disaggregated.
    routing = result["routing"]
    assert routing["prefix_len"] == 28 and routing["templates"] == 2
    rows = {r["mode"]: r for r in routing["rows"]}
    assert set(rows) == {"least_loaded", "prefix", "disagg",
                         "disagg_p2p"}
    for r in rows.values():
        assert r["completed"] == routing["requests"]
    # Fingerprints withheld = the router can't route on them.
    assert rows["least_loaded"]["prefix"]["hits"] == 0
    # The prefix row actually exercised the template store.
    pf = rows["prefix"]["prefix"]
    assert pf["hits"] + pf["misses"] + pf["steals"] == \
        routing["requests"]
    assert pf["hits"] > 0
    # Disagg (relay plane): every request went through a KV handoff;
    # the int8 segment moves at under half the fp32 bytes, THROUGH
    # the gateway.
    kv = rows["disagg"]["kv"]
    assert kv["handoffs"] >= routing["requests"]
    assert kv["rejects"] == 0
    assert 0 < kv["bytes_over_fp32"] < 0.5
    assert kv["bytes_shipped"] > 0 and kv["p2p_bytes"] == 0
    assert rows["disagg"]["pools"] == {"prefill": 1, "decode": 1}
    # Disagg P2P (ISSUE 9): same handoffs, but the gateway relays
    # ZERO segment bytes — only tickets — while the bytes move
    # peer-to-peer at the same int8 ratio.
    kvp = rows["disagg_p2p"]["kv"]
    assert kvp["handoffs"] >= routing["requests"]
    assert kvp["rejects"] == 0 and kvp["relay_fallbacks"] == 0
    assert kvp["bytes_shipped"] == 0
    assert kvp["p2p_bytes"] > 0
    assert 0 < kvp["bytes_over_fp32"] < 0.5
    assert "prefix_vs_least_loaded" in routing
    # Tracing-overhead rows (ISSUE 12): the prefix plane at the
    # routing load, trace off vs full-sampling on, with the sampling
    # counters proving head-based sampling actually gated the spans
    # (every drop counted, never silent).
    tracing = result["tracing"]
    trows = {r["trace_mode"]: r for r in tracing["rows"]}
    assert set(trows) == {"off", "on"}
    for r in trows.values():
        assert r["completed"] == tracing["requests"]
    assert trows["off"]["trace"]["sampled"] == 0
    assert trows["off"]["trace"]["unsampled"] == tracing["requests"]
    assert trows["off"]["trace"]["gw_spans"] == 0
    assert trows["on"]["trace"]["sampled"] == tracing["requests"]
    assert trows["on"]["trace"]["unsampled"] == 0
    assert trows["on"]["trace"]["gw_spans"] > 0
    over = tracing["overhead"]
    assert set(over) >= {"tokens_per_sec", "tokens_per_sec_x",
                         "ttft_p99_ms", "within_3pct"}
    assert over["tokens_per_sec"]["off"] > 0
    # The <=3% bar is asserted on the COMMITTED artifact, not the
    # smoke (a 5-request run is all warmup noise); the smoke gate
    # pins the schema and the sampling accounting.
    # Speculation rows (ISSUE 11): on/off at matched chip budget with
    # goodput fields, acceptance arithmetic, and a fallback row whose
    # bad draft visibly degraded to plain decode.
    spec = result["spec"]
    srows = {r["mode"]: r for r in spec["rows"]}
    assert set(srows) == {"off", "on", "off_floor", "fallback"}
    for r in srows.values():
        assert r["completed"] == spec["requests"]
        assert r["goodput_tokens_per_sec"] >= 0
        assert r["goodput_per_chip"] >= 0
        assert r["chips"] == r["targets"] + r["drafts"]
    # Matched chip budget is the on-vs-off contract.
    assert srows["on"]["chips"] == srows["off"]["chips"]
    assert srows["on"]["drafts"] == 1 and srows["off"]["drafts"] == 0
    # Acceptance-rate arithmetic: the ceiling draft accepted real
    # tokens over real rounds, and the routing preferred spec targets.
    on = srows["on"]["spec"]
    assert on["rounds"] > 0
    assert on["accepted"] >= on["rounds"]
    assert on["grants"] == spec["requests"]
    assert on["tokens_per_round"] > 1.0
    # Plain rows never speculate; their long decodes were bypassed.
    assert srows["off"]["spec"]["rounds"] == 0
    assert srows["off"]["spec"]["bypass"] == spec["requests"]
    # The bad draft degraded: fallback rounds counted, acceptance ~1.
    fb = srows["fallback"]["spec"]
    assert fb["fallbacks"] > 0
    assert fb["tokens_per_round"] <= 2.0
    assert "verdict" in spec and "matched_chips" in spec["verdict"]
    # Paged-KV rows (ISSUE 19): slotted vs paged at MATCHED KV memory
    # over uniform and long-tail (Zipf) sequence-length workloads,
    # with the end-to-end greedy byte-parity pin in the verdict.
    paged = result["paged"]
    prows = {(r["workload"], r["mode"]): r for r in paged["rows"]}
    assert set(prows) == {
        ("uniform", "slotted"), ("uniform", "paged"),
        ("longtail", "slotted"), ("longtail", "paged"),
    }
    for r in prows.values():
        assert r["completed"] == paged["requests"]
        assert r["tokens_per_sec"] > 0
        assert 0 < r["admitted_batch_occupancy"] <= 1.0
    for w in ("uniform", "longtail"):
        sl, pg = prows[(w, "slotted")], prows[(w, "paged")]
        # Matched memory is the contract: same token budget, the
        # paged side spending it as blocks with more seats.
        assert sl["kv_pool_tokens"] == pg["kv_pool_tokens"]
        assert pg["seats"] > sl["seats"]
        assert pg["pool_blocks"] * paged["block_size"] == \
            pg["kv_pool_tokens"]
        assert "preemptions" in pg and "preemptions" not in sl
    v = paged["verdict"]
    assert v["uniform"]["outputs_match"] is True
    assert v["longtail"]["outputs_match"] is True
    assert v["paged_never_lower"] is True
    assert v["longtail_paged_higher"] is True
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "serve_fleet_speedup"
    assert metric["artifact"] == str(out)


def test_load_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 9's open-loop load harness: the smoke
    config (1-vs-2 paced in-process gateways, two sweep points
    bracketing the modeled knee, one bursty + one diurnal phase
    trace) runs end-to-end WITHOUT jax inside the budget and emits
    schema-valid JSON — conservation across every point, a knee at
    the single gateway, the >=1.5x tier verdict, per-phase TTFT, and
    the admission-profile section with the measured serialization
    fast-path delta."""
    import os
    import subprocess
    import time

    out = tmp_path / "LOAD_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--load_bench",
         "--smoke", "--calibrate", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert elapsed < 45.0, f"smoke load bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())["load"]
    assert result["complete"] is True
    assert result["bench"] == "serve_load"
    # Sweep: 2 rates x 2 tier sizes, conservation at every point.
    assert len(result["sweep"]) == 4
    for p in result["sweep"]:
        assert p["submitted"] == p["accepted"] + p["rejected"] \
            + p["wire_dropped"]
        assert p["accepted"] == p["completed"] + p["timeout"] \
            + p["failed"]
        assert p["ttft_ms_p99"] >= p["ttft_ms_p50"] > 0
    over = [p for p in result["sweep"]
            if p["gateways"] == 1
            and p["offered_rps"] > result[
                "est_single_gateway_knee_rps"]]
    assert over and any(p["rejected"] > 0 for p in over), \
        "single gateway never saturated past the knee"
    # The tier verdict: 2 gateways sustain >=1.5x the single
    # gateway's saturation admission throughput.
    assert result["tier_speedup_gateways"] == 2
    assert result["tier_speedup_x"] >= 1.5
    assert result["meets_1p5x"] is True
    assert set(result["saturation_admit_rps"]) == {"1", "2"}
    # Phase traces: bursty + diurnal with per-phase TTFT.
    traces = {t["trace"]: t for t in result["traces"]}
    assert set(traces) == {"bursty", "diurnal"}
    assert set(traces["bursty"]["phases"]) == {"burst", "idle"}
    assert set(traces["diurnal"]["phases"]) == {"peak", "trough"}
    for t in traces.values():
        for ph in t["phases"].values():
            assert ph["count"] > 0
    # Regional skew (ISSUE 17): the seeded Zipf-over-cells row routes
    # by HOME cell (gateway 0 hot) — the hot shard must carry the
    # majority the Zipf weights dictate.
    skew = result["skew"]
    assert skew["trace"] == "zipf_cells"
    assert skew["submitted"] == skew["accepted"] + skew["rejected"] \
        + skew["wire_dropped"]
    hot = skew["phases"]["hot-cell"]["count"]
    cold = skew["phases"]["cold-cell"]["count"]
    assert hot > cold > 0
    # Admission profile + the serialization fast path it justifies.
    prof = result["admission_profile"]
    assert prof["messages"] > 0
    assert 0 <= prof["serialize_frac_of_hot_loop"] <= 1
    assert prof["fast_path_us"]["submit"] > 0
    assert prof["baseline_us"]["submit"] >= \
        prof["fast_path_us"]["submit"] * 0.8
    assert result["serialize_speedup_x"] > 0
    # Calibration (ROADMAP 4c): real per-message admission CPU from a
    # subprocess gateway over real sockets, recorded BESIDE the
    # modeled floor the paced pipelines charge.
    cal = result["calibration"]
    assert "error" not in cal, cal
    assert cal["messages"] > 0
    assert cal["gw_service_us_measured"] > 0
    assert cal["gw_service_us"] == result["gw_service_us"]
    ratio = cal["gw_service_us_measured"] / cal["gw_service_us"]
    assert abs(cal["measured_over_modeled"] - ratio) < 0.05
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "serve_tier_saturation_speedup"
    assert metric["artifact"] == str(out)


def test_fleet_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 10's mixed-fleet bench: ONE FleetManager
    (training + supervised gateway tier + serving replicas) runs the
    two fleet laws end to end in the smoke config — a crashed gateway
    is RELAUNCHED under its own id with in-flight requests completing
    exactly-once, and a serving spike borrows a training chip through
    the live-reshard epoch (drain-first both directions) and hands it
    back on decay."""
    import os
    import subprocess
    import time

    out = tmp_path / "FLEET_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--fleet_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert elapsed < 60.0, f"smoke fleet bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "fleet"
    assert result["complete"] is True
    assert result["formation_ok"] is True
    gw = result["gateway_relaunch"]
    assert gw["relaunched"] is True
    assert gw["incarnations_g1"] >= 2
    assert gw["inflight_completed"] == gw["inflight_total"]
    borrow = result["borrow"]
    assert borrow["borrowed"] and borrow["handed_back"]
    assert borrow["reshard_status"] == "done"  # the live path, no abort
    assert borrow["workers_during_borrow"] == \
        borrow["workers_before"] - 1
    assert borrow["replicas_during_borrow"] == \
        borrow["replicas_before"] + 1
    assert borrow["workers_after"] == borrow["workers_before"]
    assert borrow["replicas_after"] == borrow["replicas_before"]
    assert borrow["spike_completed"] == borrow["spike_total"]
    assert borrow["transitions"] == [
        "lending", "borrowed", "reclaiming", "idle"
    ]
    req = result["requests"]
    assert req["completed"] == req["submitted"]
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "fleet_gateway_relaunch_s"
    assert metric["artifact"] == str(out)


def test_load_bench_merges_into_existing_artifact(tmp_path):
    """--load_bench owns only the `load` key: a prior serve_bench
    artifact's sections survive the merge (and serve_bench preserves
    `load` on its own rewrite — the two benches share one committed
    file).  In-process with a micro config: this checks the merge
    contract, not the measurement (the smoke gate above does that)."""
    out = tmp_path / "SERVE.json"
    out.write_text(json.dumps({"bench": "serve_fleet", "rows": [1]}))
    bench.load_bench_main([
        f"--out={out}", "--gateways=1", "--rates=80",
        "--duration_s=0.2", "--replicas=1", "--slots=8",
        "--drain_s=5.0",
    ])
    merged = json.loads(out.read_text())
    assert merged["bench"] == "serve_fleet"
    assert merged["rows"] == [1]
    assert merged["load"]["bench"] == "serve_load"


def test_reshard_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 6's live-reshard bench: the smoke config
    (4MB state, 2->4->2 over forced host devices) runs end-to-end on CPU
    inside the budget and emits schema-valid JSON — one live and one
    restart row per transition, the per-transition speedup map, and a
    rc=0 verdict that requires the live path strictly below the restart
    path (the PR's acceptance criterion, enforced on every tier-1 run)."""
    import os
    import subprocess
    import time

    out = tmp_path / "RESHARD_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--reshard_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert elapsed < 30.0, f"smoke reshard bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["complete"] is True
    assert result["live_strictly_faster"] is True
    paths = [(r["resize"], r["path"]) for r in result["rows"]]
    assert set(paths) == {
        ("2->4", "live"), ("4->2", "live"),
        ("2->4", "restart"), ("4->2", "restart"),
    }
    live = {r["resize"]: r for r in result["rows"] if r["path"] == "live"}
    assert all(r["segments"] > 0 and r["moved_mb"] > 0
               for r in live.values())
    assert set(result["speedup_restart_over_live"]) == {"2->4", "4->2"}
    assert result["speedup_total"] > 1.0
    # The metric line is the last stdout line and carries the artifact.
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "reshard_live_vs_restart_downtime"
    assert metric["artifact"] == str(out)


def test_ha_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 13's master-HA bench: the smoke config
    (one trial, 0.5s reader lease) runs the full cold-vs-warm failover
    on CPU inside the budget and emits schema-valid JSON — blackout
    fields present for both paths, warm STRICTLY below cold (the PR's
    acceptance criterion), the warm path provably stateful (marker
    readable, shard queue continues in place) while cold really is
    blank, and the surviving journal statecheck-clean."""
    import os
    import subprocess
    import time

    out = tmp_path / "HA_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    env.pop("DLROVER_TPU_MASTER_STATE_DIR", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--ha_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    assert elapsed < 60.0, f"smoke ha bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "ha"
    assert result["complete"] is True
    cold, warm = result["cold"], result["warm"]
    assert cold["blackout_s"] > 0 and warm["blackout_s"] > 0
    assert result["hot_strictly_faster"] is True
    assert warm["blackout_s"] < cold["blackout_s"]
    assert warm["state_recovered"] is True
    assert warm["queue_continues"] is True
    assert cold["state_recovered"] is False  # blank-state relaunch
    assert result["statecheck_rc"] == 0
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "ha_failover_blackout_s"
    assert metric["value"] == warm["blackout_s"]
    assert metric["vs_baseline"] == cold["blackout_s"]
    assert metric["artifact"] == str(out)


def test_cell_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 15's multi-cell bench: the smoke config
    runs real registry + cell-master subprocesses over gRPC with the
    modeled journal-append floor and emits schema-valid JSON — per-row
    ops/s present for 1 and 2 cells, 2 cells sustaining >= 1.5x the
    single master (the PR's acceptance criterion) under the open-loop
    stream, and the metric line naming the artifact."""
    import os
    import subprocess
    import time

    out = tmp_path / "CELL_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    env.pop("DLROVER_TPU_MASTER_STATE_DIR", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--cell_bench",
         "--smoke", "--floor_ms=3", "--clients=16", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    assert elapsed < 60.0, f"smoke cell bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "cell"
    assert result["complete"] is True
    assert result["smoke"] is True
    by_cells = {r["cells"]: r for r in result["rows"]}
    assert set(by_cells) == {1, 2}
    for row in result["rows"]:
        assert row["ops_per_s"] > 0
        assert row["completed"] > 0
        assert row["offered_rps"] > 0
        assert row["floor_ms"] == 3.0
    assert result["speedup"] >= 1.5
    assert by_cells[2]["ops_per_s"] > by_cells[1]["ops_per_s"]
    # Smoke skips the failover section (subprocess-heavy; the full
    # bench and the chaos e2e own it).
    assert "failover" not in result
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "cell_control_plane_ops_per_s"
    assert metric["value"] == by_cells[2]["ops_per_s"]
    assert metric["artifact"] == str(out)


def test_global_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 17's global data-plane bench: the smoke
    config (2 in-process cells, the blackout row pair on the SAME
    seeded Zipf-over-cells trace) runs end-to-end inside the budget
    and emits schema-valid JSON — conservation ACROSS the spillover
    hop (merge_global_snapshots' submitted_unique dedupe), the
    blackout row present with the hot cell's stranded work counted,
    and the spillover-vs-static verdict asserted."""
    import os
    import subprocess
    import time

    out = tmp_path / "GLOBAL_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)
    env.pop("DLROVER_TPU_MASTER_STATE_DIR", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--global_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert elapsed < 60.0, f"smoke global bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "global_serve"
    assert result["complete"] is True
    assert result["smoke"] is True
    rows = {(r["mode"], r["blackout"]) for r in result["rows"]}
    assert rows == {("static", True), ("spillover", True)}
    for row in result["rows"]:
        # Conservation across the hop: every arrival is accounted —
        # deduped gateway-level submission, wire shed, or lost to the
        # blackout — and every accepted request reached a terminal
        # state or is counted stranded in the dead cell.
        assert row["conservation_ok"] is True
        assert row["arrivals"] == row["submitted_unique"] \
            + row["wire_dropped"] + row["blackout_lost"] \
            + row["blackout_dropped"]
        assert row["accepted"] == row["completed"] + row["timeout"] \
            + row["failed"] + row["stranded"]
        assert row["spill_forwarded"] == row["spill_ingress"] \
            + row["spill_rebuffed"]
        assert row["hot_share"] > 0.5  # cell 0 IS hot under the Zipf
    by_mode = {r["mode"]: r for r in result["rows"]}
    # Static partitioning loses every post-blackout arrival homed at
    # the dead cell; the spillover row re-homes them all.
    assert by_mode["static"]["blackout_lost"] > 0
    assert by_mode["spillover"]["blackout_lost"] == 0
    assert by_mode["spillover"]["spill_forwarded"] > 0
    assert by_mode["spillover"]["moved_replicas"] > 0
    # The verdict: the cross-cell data plane strictly beats static
    # cell partitioning on SLO goodput under skew + whole-cell death.
    verdicts = result["verdicts"]
    assert verdicts["spillover_beats_static_blackout"] is True
    assert verdicts["hop_conserved"] is True
    assert verdicts["spill_forwarded_nonzero"] is True
    assert by_mode["spillover"]["goodput_rps"] > \
        by_mode["static"]["goodput_rps"]
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "global_slo_goodput_under_blackout"
    assert metric["value"] == by_mode["spillover"]["goodput_rps"]
    assert metric["speedup"] > 1.0
    assert metric["artifact"] == str(out)


def test_sim_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 18's wind tunnel: ``--sim_bench --smoke``
    runs all three rigs end to end on CPU — the fidelity replays of the
    committed GLOBAL/CELL bench artifacts, a scaled chaos-storm day
    (blackout + gray network + churn over 2,000 nodes) in static and
    global modes, and the double-run digest — inside the sub-5s spec,
    emitting schema-valid JSON and the standard metric line."""
    import os
    import subprocess
    import time

    out = tmp_path / "SIM_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--sim_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    # <5s is the spec on an idle host; allow CI contention headroom but
    # fail loudly if the smoke config ever becomes heavyweight.
    assert elapsed < 30.0, f"smoke sim bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "sim"
    assert result["smoke"] is True
    assert result["complete"] is True
    # Fidelity: every replayed row of BOTH committed artifacts within
    # its rig's stated tolerance (the constants are calibrated against
    # ONE row each; the rest are predictions).
    for rig in ("fidelity_global", "fidelity_cell"):
        sect = result[rig]
        assert sect["ok"] is True and sect["rows"], rig
        for row in sect["rows"]:
            assert row["within_tolerance"] is True, (rig, row)
            assert row["err"] <= sect["tolerance"]
    assert {(r["mode"], r["blackout"])
            for r in result["fidelity_global"]["rows"]} \
        >= {("static", True), ("spillover", True)}
    # The storm: identical trace in both modes, conservation exact,
    # the global data plane strictly better through the storm window,
    # and the double-run law on the event-log digest.
    storm = result["storm"]
    for mode in ("static", "global"):
        row = storm[mode]
        assert row["conservation_ok"] is True
        assert row["offered"] == row["served"] + row["timeout"] \
            + row["blackout_lost"] + row["stranded"] \
            + row["backlog_final"] + row["in_transit_final"]
        assert row["nodes"] == 2000 and row["event_log_lines"] > 0
    assert storm["static"]["blackout_lost"] > 0
    # The global plane re-homes every dead-cell arrival: none lost.
    assert storm["global"]["blackout_lost"] == 0
    assert storm["global"]["rehomed"] > 0
    assert storm["global"]["spilled"] > 0
    assert storm["double_run_identical"] is True
    verdicts = result["verdicts"]
    for key in ("fidelity_global_ok", "fidelity_cell_ok",
                "storm_conserved", "global_beats_static_storm",
                "double_run_identical", "spill_exercised",
                "day_under_60s_wall", "offline_no_slo_regression",
                "offline_trough_soaked", "offline_utilization_up",
                "offline_blackout_evacuated", "offline_chunks_conserved",
                "offline_reclaim_le_one_round",
                "offline_double_run_identical"):
        assert verdicts[key] is True, key
    assert storm["global"]["storm_goodput"] > \
        storm["static"]["storm_goodput"]
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "sim_storm_slo_goodput_10k_nodes"
    assert metric["value"] == storm["global"]["storm_goodput"]
    assert metric["artifact"] == str(out)


def test_offline_bench_smoke_schema(tmp_path):
    """Tier-1 gate for ISSUE 20's offline tier: ``--offline_bench
    --smoke`` runs all three rows end to end on CPU — the tier sim
    (baseline vs offline over a blackout trace), the chaos-killed
    worker's journal replay through REAL subprocesses, and the
    measured arbiter reclaim latency — inside the sub-5s spec,
    emitting schema-valid JSON and the standard metric line."""
    import os
    import subprocess
    import time

    out = tmp_path / "OFFLINE_BENCH_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(bench.__file__)), "--offline_bench",
         "--smoke", f"--out={out}"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(Path(bench.__file__).parent),
    )
    elapsed = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    # <5s is the spec on an idle host (the smoke runs in well under
    # 1s); allow CI contention headroom but fail loudly if the smoke
    # config ever becomes heavyweight.
    assert elapsed < 30.0, f"smoke offline bench took {elapsed:.1f}s"
    result = json.loads(out.read_text())
    assert result["bench"] == "offline"
    assert result["smoke"] is True
    assert result["complete"] is True
    # The tier: identical online trace in both modes — the batch tier
    # must soak the trough without the SLO plane paying for it.
    tier = result["tier"]
    base, off = tier["baseline"], tier["offline"]
    assert abs(off["slo_goodput"] - base["slo_goodput"]) \
        <= result["opts"]["goodput_noise"]
    assert off["utilization"] > base["utilization"]
    assert off["chunks_done_trough"] > 0
    assert off["max_reclaim_rounds"] <= 1
    assert off["chunk_conservation_ok"] is True
    assert off["evacuations_ok"] is True
    assert off["overcommit_steps"] == 0
    assert tier["double_run_identical"] is True
    # The replay: worker 1 really died by chaos (os._exit(78) is a
    # true process death), worker 2 finished the journal, and every
    # chunk landed exactly once with every token checked.
    replay = result["replay"]
    assert replay["victim_exit"] == 78
    assert replay["survivor_exit"] == 0
    assert replay["final_stats"]["done"] == replay["chunks_total"]
    assert replay["final_stats"]["pending"] == 0
    assert replay["final_stats"]["leased"] == 0
    assert replay["tokens_exact"] is True
    # The reclaim: a live runner mid-chunk, chunk_kill chaos armed —
    # the chip must free within ONE decode round of the arbiter's
    # preemption, and the arbiter must grant it the next pass.
    reclaim = result["reclaim"]
    assert reclaim["trials"]
    assert reclaim["max_decode_rounds"] <= 1
    for trial in reclaim["trials"]:
        assert trial["phase_after"] == "borrowed"
        assert trial["requeued_backlog"] >= 1  # the chunk survived
    for key, val in result["verdicts"].items():
        assert val is True, key
    metric = json.loads(proc.stdout.strip().splitlines()[-1])
    assert metric["metric"] == "offline_tier_fleet_utilization"
    assert metric["value"] == off["utilization"]
    assert metric["vs_baseline"] == base["utilization"]
    assert metric["artifact"] == str(out)
