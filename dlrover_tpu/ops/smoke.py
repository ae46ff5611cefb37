"""Mosaic smoke: compile + execute + grad-check every Pallas kernel on TPU.

Every Pallas kernel written since round 1 had only ever run with
``interpret=True`` (the CPU emulator) — register/VMEM pressure or an
unsupported op could invalidate the whole perf plan on first hardware
contact.  This module converts that existential risk into a checklist:
each kernel variant is compiled with ``interpret=False`` at bench-like
shapes, executed, timed, and numerically checked against the jnp
reference (values AND gradients where the kernel has a custom VJP).

Results are flushed to the artifact file after EVERY kernel, and each
case's exception is kept in its result: the run fails unless ``all_ok``.

The kernels exist to replace the role of the reference's flash-attn /
Triton dispatch (``atorch/atorch/kernels/extensions/xla/
flash_attention_xla.py``, ``kernels/triton_jit/*``); this proves ours
actually lower through Mosaic.
"""

from __future__ import annotations

import json
import time
import traceback
from typing import Callable, Dict, List, Optional

import numpy as np


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    denom = max(float(np.max(np.abs(b))), 1e-6)
    return float(np.max(np.abs(a - b))) / denom


def _time_fn(fn, *args, iters: int = 5) -> float:
    """Median wall-time (µs) of ``fn(*args)`` after warmup."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def _flash_cases() -> List[Dict]:
    """Flash-attention variants at bench-like shapes.

    Shapes mirror the bench sweep: [B,H,S,D] = [4,16,2048,64] (300m-ish)
    and the h128 layout [4,8,2048,128] the sweep prefers (head_dim=128
    fills the 128-lane width).  Smaller B than the bench keeps the smoke
    fast; block shapes and VMEM pressure are what matter, and those are
    B-independent.
    """
    cases = []
    for name, (B, H, KV, S, D), kw in [
        ("flash_causal", (4, 16, 16, 2048, 64), {}),
        ("flash_causal_h128", (4, 8, 8, 2048, 128), {}),
        ("flash_gqa", (4, 16, 4, 2048, 64), {}),
        ("flash_gqa_h128", (4, 8, 2, 2048, 128), {}),
        ("flash_window", (4, 8, 8, 2048, 128), {"window": 512}),
        ("flash_window_gqa", (4, 8, 2, 2048, 128), {"window": 512}),
        ("flash_segment", (4, 8, 8, 2048, 128), {"segmented": True}),
        ("flash_noncausal", (4, 8, 8, 2048, 128), {"causal": False}),
    ]:
        cases.append({"name": name, "shape": (B, H, KV, S, D), "kw": kw})
    return cases


def _run_flash_case(case: Dict) -> Dict:
    import jax
    import jax.numpy as jnp

    # The package re-exports the flash_attention FUNCTION, shadowing the
    # submodule for any ``import ... as`` form — import through
    # importlib to get the module itself.
    import importlib

    fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

    B, H, KV, S, D = case["shape"]
    kw = dict(case["kw"])
    causal = kw.pop("causal", True)
    segmented = kw.pop("segmented", False)
    window = kw.pop("window", 0)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, KV, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, KV, S, D), jnp.bfloat16)
    seg = None
    if segmented:
        # Two packed documents per row, ragged boundary.
        bounds = rng.randint(S // 4, 3 * S // 4, size=(B,))
        seg = jnp.asarray(
            (np.arange(S)[None, :] >= bounds[:, None]).astype(np.int32)
        )

    def loss_pallas(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=causal, segment_ids=seg, window=window,
            backend="pallas",
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        out = fa.reference_attention(
            q, k, v, causal=causal, segment_ids=seg, window=window
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    fwd = jax.jit(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, segment_ids=seg, window=window,
            backend="pallas",
        )
    )
    grad_fn = jax.jit(jax.value_and_grad(loss_pallas, argnums=(0, 1, 2)))
    ref_fwd = jax.jit(
        lambda q, k, v: fa.reference_attention(
            q, k, v, causal=causal, segment_ids=seg, window=window
        )
    )
    ref_grad = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))

    out = fwd(q, k, v)
    out_ref = ref_fwd(q, k, v)
    fwd_err = _rel_err(out, out_ref)
    (lv, grads) = grad_fn(q, k, v)
    (lr_, grads_ref) = ref_grad(q, k, v)
    grad_err = max(_rel_err(g, gr) for g, gr in zip(grads, grads_ref))
    fwd_us = _time_fn(fwd, q, k, v)
    bwd_us = _time_fn(grad_fn, q, k, v)
    # bf16 inputs, fp32 accumulation: ~1e-2 relative is the expected
    # noise floor at S=2048 reductions.
    ok = fwd_err < 3e-2 and grad_err < 6e-2
    return {
        "ok": bool(ok),
        "fwd_rel_err": round(fwd_err, 5),
        "grad_rel_err": round(grad_err, 5),
        "fwd_us": round(fwd_us, 1),
        "fwd_bwd_us": round(bwd_us, 1),
    }


def _run_rmsnorm() -> Dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.rmsnorm import rmsnorm

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4 * 2048, 2048), jnp.bfloat16)
    w = jnp.asarray(rng.randn(2048), jnp.bfloat16)

    def loss_p(x, w):
        return jnp.sum(rmsnorm(x, w, backend="pallas").astype(jnp.float32) ** 2)

    def loss_r(x, w):
        return jnp.sum(
            rmsnorm(x, w, backend="reference").astype(jnp.float32) ** 2
        )

    fwd = jax.jit(lambda x, w: rmsnorm(x, w, backend="pallas"))
    ref = jax.jit(lambda x, w: rmsnorm(x, w, backend="reference"))
    g_p = jax.jit(jax.grad(loss_p, argnums=(0, 1)))
    g_r = jax.jit(jax.grad(loss_r, argnums=(0, 1)))
    fwd_err = _rel_err(fwd(x, w), ref(x, w))
    grad_err = max(
        _rel_err(a, b) for a, b in zip(g_p(x, w), g_r(x, w))
    )
    us = _time_fn(fwd, x, w)
    return {
        "ok": bool(fwd_err < 2e-2 and grad_err < 4e-2),
        "fwd_rel_err": round(fwd_err, 5),
        "grad_rel_err": round(grad_err, 5),
        "fwd_us": round(us, 1),
    }


def _run_xent() -> Dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy

    rng = np.random.RandomState(2)
    V = 32000
    logits = jnp.asarray(rng.randn(2048, V), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, V, size=(2048,)), jnp.int32)

    fwd = jax.jit(
        lambda l, y: softmax_cross_entropy(l, y, backend="pallas")
    )
    ref = jax.jit(
        lambda l, y: softmax_cross_entropy(l, y, backend="reference")
    )
    fwd_err = _rel_err(fwd(logits, labels), ref(logits, labels))
    us = _time_fn(fwd, logits, labels)
    return {
        "ok": bool(fwd_err < 2e-2),
        "fwd_rel_err": round(fwd_err, 5),
        "fwd_us": round(us, 1),
    }


def _run_fused_lm_head() -> Dict:
    """Fused lm-head CE is lax.scan-based (no Pallas) but is on the hot
    path of every bench candidate — prove it compiles and matches at
    bench vocab."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.cross_entropy import (
        linear_softmax_cross_entropy,
        softmax_cross_entropy,
    )

    rng = np.random.RandomState(3)
    D, V = 1024, 32000
    x = jnp.asarray(rng.randn(2048, D) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.randn(D, V) * 0.02, jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, V, size=(2048,)), jnp.int32)

    def loss_f(x, w):
        return jnp.mean(linear_softmax_cross_entropy(x, w, y))

    def loss_r(x, w):
        logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return jnp.mean(softmax_cross_entropy(logits, y, backend="reference"))

    g_f = jax.jit(jax.value_and_grad(loss_f, argnums=(0, 1)))
    g_r = jax.jit(jax.value_and_grad(loss_r, argnums=(0, 1)))
    lf, gf = g_f(x, w)
    lr_, gr = g_r(x, w)
    val_err = abs(float(lf) - float(lr_)) / max(abs(float(lr_)), 1e-6)
    grad_err = max(_rel_err(a, b) for a, b in zip(gf, gr))
    us = _time_fn(g_f, x, w)
    return {
        "ok": bool(val_err < 1e-2 and grad_err < 4e-2),
        "fwd_rel_err": round(val_err, 5),
        "grad_rel_err": round(grad_err, 5),
        "fwd_bwd_us": round(us, 1),
    }


def _run_quant() -> Dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.quant import (
        dequantize_blockwise,
        quantize_blockwise,
    )

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4 << 20).astype(np.float32))

    fwd = jax.jit(lambda x: quantize_blockwise(x, backend="pallas"))
    codes, scale = fwd(x)
    back = dequantize_blockwise(codes, scale, x.shape)
    # int8 symmetric round-to-nearest: worst case is scale/2 per block
    # ≈ max/254; a kernel that truncates instead of rounds (a classic
    # lowering bug) errs up to max/127 and must FAIL this bound.
    err = float(np.max(np.abs(np.asarray(back) - np.asarray(x))))
    bound = float(np.max(np.abs(np.asarray(x)))) / 254.0
    us = _time_fn(fwd, x)
    return {
        "ok": bool(err <= bound * 1.01),
        "fwd_rel_err": round(err / max(bound, 1e-9), 5),
        "fwd_us": round(us, 1),
    }


def _run_grouped_matmul() -> Dict:
    """The MoE grouped GEMM (megablox gmm on the TPU at this shape,
    ``lax.ragged_dot`` elsewhere) — on the MoE hot path; confirm it lowers
    and matches on this backend."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.grouped_matmul import grouped_matmul_ragged

    rng = np.random.RandomState(5)
    G, M, K, N = 8, 1024, 512, 1024
    lhs = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    sizes = np.full((G,), M // G, np.int32)
    rhs = jnp.asarray(rng.randn(G, K, N) * 0.05, jnp.bfloat16)
    gs = jnp.asarray(sizes)

    fwd = jax.jit(lambda l, r, g: grouped_matmul_ragged(l, r, g))
    out = fwd(lhs, rhs, gs)
    # reference: per-group dense dot
    outs = []
    start = 0
    for g in range(G):
        seg = np.asarray(lhs, np.float32)[start:start + sizes[g]]
        outs.append(seg @ np.asarray(rhs, np.float32)[g])
        start += sizes[g]
    ref = np.concatenate(outs, axis=0)
    err = _rel_err(out, ref)
    us = _time_fn(fwd, lhs, rhs, gs)
    return {"ok": bool(err < 3e-2), "fwd_rel_err": round(err, 5),
            "fwd_us": round(us, 1)}


def _run_gather_sum() -> Dict:
    """The routed block's token side (``ops.gather_sum``: K rows a token
    gathered out of HBM by row DMA, weighed and summed) at a routed cell's
    width, a quarter of the picks live and the rows past them poisoned:
    the kernel against its ``jax.numpy`` reference (the same float32
    terms; a bf16 rounding apart at most, where the sums' orders differ)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.gather_sum import gather_sum

    rng = np.random.RandomState(6)
    N, K, C, R, live = 4096, 4, 2048, 5120, 4000
    rows = jnp.asarray(rng.randn(R, C), jnp.bfloat16).at[live:].set(jnp.nan)
    dead = rng.rand(N, K) < 0.75
    index = jnp.asarray(
        np.where(dead, R - 1, rng.randint(0, live, size=(N, K))), jnp.int32)
    weights = jnp.asarray(np.where(dead, 0.0, rng.rand(N, K)), jnp.bfloat16)
    fwd = jax.jit(lambda r, i, w: gather_sum(r, i, w, backend="pallas"))
    ref = jax.jit(lambda r, i, w: gather_sum(r, i, w, backend="reference"))
    out = fwd(rows, index, weights)
    err = _rel_err(out, ref(rows, index, weights))
    us = _time_fn(fwd, rows, index, weights)
    return {"ok": bool(err < 1e-2), "fwd_rel_err": round(err, 5),
            "fwd_us": round(us, 1)}


def run_kernel_smoke(
    out_path: Optional[str] = None,
    only: Optional[str] = None,
) -> Dict:
    """Run every kernel variant; flush partial results to ``out_path``
    after each.  Returns the full result dict."""
    import jax

    from dlrover_tpu.common.jax_env import enable_compilation_cache

    enable_compilation_cache()
    cases: List[tuple] = []
    for c in _flash_cases():
        cases.append((c["name"], lambda c=c: _run_flash_case(c)))
    cases += [
        ("rmsnorm", _run_rmsnorm),
        ("cross_entropy", _run_xent),
        ("fused_lm_head_ce", _run_fused_lm_head),
        ("quantize_blockwise", _run_quant),
        ("grouped_matmul", _run_grouped_matmul),
        ("gather_sum", _run_gather_sum),
    ]
    if only:
        cases = [c for c in cases if only in c[0]]

    results: Dict = {
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "kernels": {},
    }

    def flush():
        if out_path:
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)

    flush()
    for name, fn in cases:
        t0 = time.perf_counter()
        try:
            res = fn()
        except Exception as e:  # noqa: BLE001 — record, keep going
            res = {
                "ok": False,
                "error": f"{type(e).__name__}: {str(e)[:400]}",
                "traceback": traceback.format_exc()[-1500:],
            }
        res["total_s"] = round(time.perf_counter() - t0, 1)
        results["kernels"][name] = res
        flush()
    results["n_ok"] = sum(1 for r in results["kernels"].values() if r["ok"])
    results["n_total"] = len(results["kernels"])
    # A filter matching nothing must NOT read as green (the whole point
    # is proving kernels lower; zero kernels proves nothing).
    results["all_ok"] = (
        results["n_total"] > 0 and results["n_ok"] == results["n_total"]
    )
    flush()
    return results


#: (rows, D, V) of the lm head in the benchmark's steady cells
#: (mistral7b at 2 x 8,192 tokens, OLMoE at 8 x 4,096)
HEAD_SHAPES = ((16384, 4096, 32000), (32768, 2048, 50304))


def _head_gradient_case(rows: int, D: int, V: int) -> Dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.cross_entropy import (
        linear_softmax_cross_entropy,
        linear_softmax_cross_entropy_sum,
    )

    def rel_l2(a, b):
        a, b = (np.asarray(t, np.float32).ravel() for t in (a, b))
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    k = jax.random.split(jax.random.PRNGKey(rows), 3)
    x = jax.random.normal(k[0], (rows, D), jnp.bfloat16)
    w = (jax.random.normal(k[1], (D, V)) * 0.02).astype(jnp.bfloat16)
    y = jax.random.randint(k[2], (rows,), 0, V)
    reduced = jax.jit(jax.value_and_grad(
        lambda x, w: linear_softmax_cross_entropy_sum(x, w, y),
        argnums=(0, 1)))
    per_token = jax.jit(jax.value_and_grad(
        lambda x, w: jnp.mean(linear_softmax_cross_entropy(x, w, y)),
        argnums=(0, 1)))
    (l_s, g_s), (l_t, g_t) = reduced(x, w), per_token(x, w)
    res = {
        "shape": [rows, D, V], "backend": jax.default_backend(),
        "loss": float(l_s),
        "loss_rel": abs(float(l_s) - float(l_t)) / abs(float(l_t)),
        "dx_rel_l2": rel_l2(g_s[0], g_t[0]),
        "dw_rel_l2": rel_l2(g_s[1], g_t[1]),
    }
    res["ok"] = bool(max(
        res["loss_rel"], res["dx_rel_l2"], res["dw_rel_l2"]) < 1e-2)
    return res


def run_head_gradient_check(shapes=HEAD_SHAPES) -> List[Dict]:
    """The reduced lm-head loss (gradients formed in its forward scan: what
    a training step runs) against the per-token op's recompute backward
    (what the benchmark's gradient comparison differentiates), in bf16 at
    the cells' shapes: relative L2 of loss, dx and dw between the two.
    Not one of ``run_kernel_smoke``'s cases: ``python -m
    dlrover_tpu.ops.smoke`` runs it alone and prints one line a shape."""
    out = []
    for rows, D, V in shapes:
        res = _head_gradient_case(rows, D, V)
        print("HEAD_GRADIENT_CHECK " + json.dumps(res), flush=True)
        out.append(res)
    return out


if __name__ == "__main__":
    import sys

    sys.exit(0 if all(r["ok"] for r in run_head_gradient_check()) else 1)
