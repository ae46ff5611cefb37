"""Resolve the remaining hardware-gated unknowns on the chip.

Three probes, each a child process that holds the chip alone and is gone
before the next starts (this parent never imports JAX), writing into
HW_PROBES.json as it completes:

1. ``offload_combo`` — does ``Strategy(remat="offload",
   offload_opt=True)`` compile and step on the real partitioner?
   (jax-0.9 may reject the combination on TPU; the BO sweep
   self-rejects if so — but nobody has ever watched it happen.)
2. ``node_check_payload`` — wall time of the agent's pre-flight health
   payload (8 x 4096^3 matmuls) on a real chip vs its 300 s timeout
   budget (``agent/node_check.py``; a mis-sized payload would DoS the
   job it protects).
3. ``device_cache`` — per-batch cost of the device-resident embedding
   cache hit path (plan/apply + jitted gather) vs the host pull/push
   path it replaces (``embedding/device_cache.py``; the claimed
   PCIe-dominated advantage was never measured on TPU).

Run on the chip:  python tools/probe_hw_unknowns.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "HW_PROBES.json")


OFFLOAD_COMBO = r"""
import json, sys, time, traceback
sys.path.insert(0, %(repo)r)
import numpy as np
import jax, jax.numpy as jnp, optax
from dlrover_tpu.models import llama
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec

cfg = llama.LlamaConfig.small_300m()
batch, seq = 4, 1024
rng = np.random.RandomState(0)
tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq + 1)).astype("int32")
try:
    job = accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch={"tokens": tokens},
        strategy=Strategy(
            mesh=MeshSpec(dp=jax.local_device_count()),
            remat="offload", offload_opt=True,
        ),
    )
    state = job.create_state(jax.random.PRNGKey(0))
    state, m = job.train_step(state, {"tokens": jnp.asarray(tokens)})
    _ = float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(3):
        state, m = job.train_step(state, {"tokens": jnp.asarray(tokens)})
    jax.block_until_ready(state)
    out = {"ok": True, "step_time_s": round((time.perf_counter() - t0) / 3, 4),
           "loss": float(m["loss"]), "backend": jax.default_backend()}
except Exception as e:
    out = {"ok": False, "error": "%%s: %%s" %% (type(e).__name__, str(e)[:400]),
           "traceback": traceback.format_exc()[-2000:]}
print("PROBE_RESULT " + json.dumps(out))
"""


NODE_CHECK = r"""
import json, sys, time
sys.path.insert(0, %(repo)r)
from dlrover_tpu.agent.node_check import _run_check_payload
t0 = time.perf_counter()
elapsed = _run_check_payload("", 1, 0)
wall = time.perf_counter() - t0
out = {"ok": elapsed is not None,
       "payload_timed_region_s": elapsed,
       "payload_wall_s": round(wall, 1),
       "timeout_budget_s": 300.0}
print("PROBE_RESULT " + json.dumps(out))
"""


DEVICE_CACHE = r"""
import json, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax, jax.numpy as jnp
from dlrover_tpu.embedding.store import EmbeddingStore
from dlrover_tpu.embedding.device_cache import DeviceEmbeddingCache

dim, cache_rows, batch = 64, 1 << 16, 4096
store = EmbeddingStore(dim=dim)
cache = DeviceEmbeddingCache(store, capacity=cache_rows)
rng = np.random.RandomState(0)
# hot working set that fits the cache -> steady-state hit path
hot = rng.randint(0, cache_rows // 2, size=(64, batch)).astype(np.int64)

gather = jax.jit(lambda t, s: t[s])
# warm the WHOLE working set + compile: the timed loop must measure the
# steady-state hit path, not first-touch admissions
for i in range(64):
    slots = cache.map_batch(hot[i])
_ = gather(cache.table, jnp.asarray(slots)).block_until_ready()

t0 = time.perf_counter()
for i in range(32):
    slots = cache.map_batch(hot[i %% 64])
    out = gather(cache.table, jnp.asarray(slots))
out.block_until_ready()
hit_ms = (time.perf_counter() - t0) / 32 * 1e3

# host pull/push path: fetch rows from the store and device_put each batch
t0 = time.perf_counter()
for i in range(32):
    rows = store.lookup(hot[i %% 64])
    dev = jax.device_put(rows)
dev.block_until_ready()
pull_ms = (time.perf_counter() - t0) / 32 * 1e3
out = {"ok": True, "backend": jax.default_backend(),
       "cache_hit_ms_per_batch": round(hit_ms, 2),
       "host_pull_ms_per_batch": round(pull_ms, 2),
       "speedup": round(pull_ms / max(hit_ms, 1e-9), 2)}
print("PROBE_RESULT " + json.dumps(out))
"""


def run_probe(name: str, code: str, timeout_s: float) -> dict:
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code % {"repo": REPO}],
            capture_output=True, timeout=timeout_s, text=True,
            cwd=REPO, start_new_session=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"timeout after {timeout_s:.0f}s"}
    for line in proc.stdout.splitlines():
        if line.startswith("PROBE_RESULT "):
            return json.loads(line[len("PROBE_RESULT "):])
    return {
        "ok": False,
        "error": f"no result (rc={proc.returncode})",
        "stderr": proc.stderr[-1500:],
    }


def main() -> int:
    results: dict = {}
    for name, code, timeout_s in [
        ("offload_combo", OFFLOAD_COMBO, 1200.0),
        ("node_check_payload", NODE_CHECK, 600.0),
        ("device_cache", DEVICE_CACHE, 900.0),
    ]:
        t0 = time.perf_counter()
        res = run_probe(name, code, timeout_s)
        res["total_s"] = round(time.perf_counter() - t0, 1)
        results[name] = res
        print(f"{name}: {res}", file=sys.stderr)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)
    print(json.dumps({k: v.get("ok") for k, v in results.items()}))
    return 0


if __name__ == "__main__":
    main()
