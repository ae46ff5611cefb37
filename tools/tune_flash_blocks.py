"""Tune the Pallas flash-attention block sizes on the chip.

Runs one bench candidate once per block-shape point.  The block shapes are
``DLROVER_TPU_FLASH_*`` env overrides that ``ops/flash_attention.py`` reads
once at import, so each point is a child process (``--point``); a chip
belongs to one process at a time, so this parent never imports JAX and each
child is gone before the next starts.  The winner goes into
``ops/flash_attention.py``'s defaults (VERDICT r3 next #1: "tune
DEFAULT_BWD_BLOCK_* on the winner").

bwd_q=128 is out of the grid: its execution stalled the device for 900 s in
round 4 (a hand record; the v5e compiler accepts the shape, so this is a
run-time matter nobody has looked at since), and 128-wide blocks measured
~5% of peak in round 1 anyway.

Run on the chip:  python tools/tune_flash_blocks.py [--model 300m_h128]
Writes FLASH_TUNE.json next to bench.py as points complete.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (fwd_q, fwd_k, bwd_q, bwd_k, ce_chunk_rows) — first point is the
# current default.  The last entries hold flash blocks at default and
# sweep the fused lm-head CE chunking instead (the other hot kernel:
# ~20% of 300m FLOPs live in the lm-head GEMM inside a lax.scan).
GRID = [
    (512, 512, 256, 512, 1024),
    (1024, 512, 256, 512, 1024),
    (256, 512, 256, 512, 1024),
    (512, 256, 256, 512, 1024),
    (512, 512, 256, 512, 2048),
    (512, 512, 256, 512, 4096),
    (512, 512, 256, 512, 512),
    (512, 512, 512, 512, 1024),
    (512, 512, 256, 256, 1024),
    (512, 512, 512, 256, 1024),
    (1024, 1024, 512, 512, 1024),
]
POINT_TIMEOUT_S = 600.0


def measure_point(model: str) -> int:
    """Child entry: time the candidate under this process's env overrides
    and print ``POINT_RESULT {"step_time_s": ...}``."""
    import bench
    from dlrover_tpu.common.jax_env import device_summary
    from dlrover_tpu.models import llama

    device = device_summary()
    if device["platform"] != "tpu":
        print(f"tune_flash_blocks measures on a TPU, found {device}",
              file=sys.stderr)
        return 1
    if model == "300m_h128":
        cfg = dataclasses.replace(
            llama.LlamaConfig.small_300m(), n_head=8, n_kv_head=8
        )
        remat = "none"
    elif model == "800m_h128":
        cfg = dataclasses.replace(
            llama.LlamaConfig.medium_800m(), n_head=12, n_kv_head=12,
        )
        remat = "block"
    else:
        raise SystemExit(f"unknown --model {model}")
    dt, _ = bench._measure_candidate(cfg, 8, 2048, remat, 3)
    print("POINT_RESULT " + json.dumps(
        {"step_time_s": round(dt, 4), "device": device}), flush=True)
    return 0


def main() -> int:
    model = "300m_h128"
    if "--model" in sys.argv:
        model = sys.argv[sys.argv.index("--model") + 1]
    if "--point" in sys.argv:
        return measure_point(model)
    out_path = os.path.join(REPO, "FLASH_TUNE.json")
    results: list = []
    for fq, fk, bq, bk, ce in GRID:
        env = dict(
            os.environ,
            DLROVER_TPU_FLASH_BLOCK_Q=str(fq),
            DLROVER_TPU_FLASH_BLOCK_K=str(fk),
            DLROVER_TPU_FLASH_BWD_BLOCK_Q=str(bq),
            DLROVER_TPU_FLASH_BWD_BLOCK_K=str(bk),
            DLROVER_TPU_CE_CHUNK_ROWS=str(ce),
        )
        entry = {"blocks": [fq, fk, bq, bk], "ce_chunk_rows": ce}
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--point",
                 "--model", model],
                env=env, cwd=REPO, capture_output=True, text=True,
                timeout=POINT_TIMEOUT_S,
            )
            for line in proc.stdout.splitlines():
                if line.startswith("POINT_RESULT "):
                    entry.update(json.loads(line[len("POINT_RESULT "):]))
            if "step_time_s" not in entry:
                entry["error"] = (
                    f"rc={proc.returncode}: {proc.stderr[-300:]}"
                )
        except subprocess.TimeoutExpired:
            entry["error"] = f"timeout after {POINT_TIMEOUT_S:.0f}s"
        print(f"fwd{fq}x{fk}_bwd{bq}x{bk}_ce{ce}: {entry}", file=sys.stderr)
        results.append(entry)
        with open(out_path, "w") as f:
            json.dump({"model": model, "points": results,
                       "complete": len(results) == len(GRID)}, f, indent=1)
    ok = [r for r in results if "step_time_s" in r]
    if not ok:
        return 1
    print(json.dumps({"best": min(ok, key=lambda r: r["step_time_s"]),
                      "model": model}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
