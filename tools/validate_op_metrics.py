"""Validate op-metrics classification against a REAL TPU profiler trace.

The HLO-name-prefix classifier (``utils.op_metrics.classify_op``) has
only ever seen synthetic CPU traces (VERDICT r3 weak #7): if real TPU
device-track names differ, the straggler operator silently sees 0%
matmul/collective fraction and never fires.  This runs a few llama
train steps on the live backend under an OpMetricsCollector capture and
prints the observed fractions plus the top op names by self time, so
wrong prefixes are immediately visible (and fixable).

Run on the chip:  python tools/validate_op_metrics.py
Writes OP_METRICS_TPU.json at the repo root.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.utils.op_metrics import OpMetricsCollector

    backend = jax.default_backend()
    if "--require-tpu" in sys.argv and backend != "tpu":
        # Watcher mode: a shim fallback to CPU must NOT write the
        # artifact (the stage would wrongly count as done with
        # CPU-trace data — exactly the stale artifact r4 had to purge).
        print(f"FAIL: backend is {backend}, not tpu", file=sys.stderr)
        return 1
    if backend == "tpu":
        cfg = llama.LlamaConfig.small_300m()
        seq = 512
    else:  # CPU smoke of the tool itself: tiny shapes
        cfg = llama.LlamaConfig.tiny(n_layer=2)
        seq = 64
    batch_n = max(4, jax.local_device_count())
    rng = np.random.RandomState(0)
    sample = {
        "tokens": rng.randint(
            0, cfg.vocab_size, (batch_n, seq + 1)
        ).astype(np.int32)
    }
    job = accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch=sample,
        strategy=Strategy(mesh=MeshSpec(dp=jax.local_device_count())),
    )
    state = job.create_state(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(sample["tokens"])}

    col = OpMetricsCollector(capture_every=2)
    for step in range(4):
        col.step_begin(step)
        state, metrics = job.train_step(state, batch)
        _ = float(metrics["loss"])  # block
        col.step_end(step)
    diag = json.loads(col.diagnosis_data())
    m = diag["metrics"]
    captured = m.get("last_capture_step", -1.0) >= 0
    result = {
        "backend": backend,
        "matmul_frac": m.get("optime_matmul_frac"),
        "collective_frac": m.get("optime_collective_frac"),
        "other_frac": m.get("optime_other_frac"),
        "last_capture_step": m.get("last_capture_step"),
        "top_ops": diag.get("top_ops"),
    }
    out = os.path.join(REPO, "OP_METRICS_TPU.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))
    if not captured:
        print("FAIL: no capture completed", file=sys.stderr)
        return 1
    if backend == "tpu" and (m.get("optime_matmul_frac") or 0.0) <= 0.0:
        print(
            "FAIL: matmul fraction is zero on TPU — classify_op "
            "prefixes do not match real device-track names "
            "(see top_ops above for the actual names)",
            file=sys.stderr,
        )
        return 1
    print(
        f"OK: matmul={m.get('optime_matmul_frac', 0):.3f} "
        f"collective={m.get('optime_collective_frac', 0):.3f} "
        f"other={m.get('optime_other_frac', 0):.3f}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
