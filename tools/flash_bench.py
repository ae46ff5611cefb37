"""The flash backward's two kernels alone, on the chip: ``flash_bwd_dq`` and
``flash_bwd_dkv`` of ``ops.flash_attention`` at the cells' shapes in
bfloat16, each timed in a program that holds only it.

    chiprun -- python3 tools/flash_bench.py [--iters 10] [--shapes a,b]
        [--against DIR] [--passes] [--calls DUMP.json]

One ``FLASH_BENCH`` line a shape: median milliseconds of ``--iters`` calls of
each kernel, milliseconds a product (dq runs three a block pair, dkv four)
and their ratio; the rows and bytes of Q and dO that a ``flash_bwd_dkv`` grid
step is handed (``dkv_q_do_a_step``: a window layer's span or the head's
whole sequence, read from the call's block shapes); under ``loops`` each of
the three kernels, ``flash_fwd`` too, against the block pairs its loops visit
(microseconds a pair, the share of their scores that no mask drops, and the
MXU's least time for them over the time: a window layer against a full one
tells what a grid step costs beside its blocks); and, at the same head sizes,
group and window cut to one key head and 2,048 positions, the distance of
``dk`` and ``dv`` from the float32 autodiff of ``reference_attention`` (the
norm of the difference over the norm).  ``--against DIR`` times another
checkout's kernels (``DIR`` holds a ``dlrover_tpu/ops/flash_attention.py``,
a parent unpacked by ``git archive``) on the same operands in the same process and adds its numbers and
the distance between the two trees' ``dk`` and ``dv``: 0.0 where both feed
the MXU the same bits.  ``--passes`` times one ``[512, 256] x [256, 128]``
product a turn of a kernel's loop with float32 operands as the flash kernels
hand them over (no precision asked), with the same operands narrowed to
bfloat16, and at ``highest``: a float32 product that costs what the bfloat16
one costs is one bfloat16 pass.  ``--calls`` reads a ``benchmark/run.py
--dump-trace`` file instead and prints each flash kernel's per-call device
milliseconds in the order the calls ran (a window layer's and a full layer's
calls differ), and a ``FLASH_FETCH`` line: ``dkv_q_do_a_step`` of every shape
(traced from shapes: no chip).  The table is also written to
``chiprun_out/flash_bench.json``; ``--toy`` rehearses it off the chip (short
sequences, the kernels in interpret mode).
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.gated_delta_bench import _distance, _median_ms  # noqa: E402

#: name -> (B, H, KV, S, D, Dv, window): the flash call of one chip's step
SHAPES = {
    "mellum_window": (1, 32, 4, 16384, 128, 128, 1024),
    "mellum_full": (1, 32, 4, 16384, 128, 128, 0),
    # the Trinity-Mini cell's window layers (its full layer is mellum_full)
    "trinity_window": (1, 32, 4, 16384, 128, 128, 2048),
    "mistral": (2, 32, 8, 8192, 128, 128, 4096),
    "ouro": (2, 16, 16, 4096, 128, 128, 0),
    "olmoe": (8, 16, 16, 4096, 128, 128, 0),
    "lfm2": (4, 32, 8, 8192, 64, 64, 0),
    "nemotron": (3, 32, 2, 8192, 128, 128, 0),
    "kimi": (1, 32, 32, 16384, 192, 128, 0),
    "glm": (3, 20, 20, 8192, 256, 256, 0),
    "qwen3_next": (2, 16, 2, 8192, 256, 256, 0),
}
TOY = {"toy_window": (1, 4, 2, 1024, 128, 128, 128),
       "toy_latent": (1, 2, 2, 256, 192, 128, 0)}


def _load(root):
    """``ops/flash_attention.py`` of the checkout at ``root`` as a module of
    its own (what it imports of the package is this checkout's)."""
    path = os.path.join(root, "dlrover_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(
        "flash_attention_of_" + os.path.basename(root), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _operands(shape, seed):
    import jax
    import jax.numpy as jnp

    B, H, KV, S, D, Dv, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    dims = ((B, H, S, D), (B, KV, S, D), (B, KV, S, Dv), (B, H, S, Dv))
    return [jax.random.normal(k, d, jnp.float32).astype(jnp.bfloat16)
            for k, d in zip(keys, dims)]


def _kernels(mod, window, interpret):
    """``(dq alone, dk and dv alone)`` of ``mod``: each program returns one
    kernel's outputs, so XLA drops the other's call."""
    import jax

    def bwd(q, k, v, out, lse, g):
        return mod._flash_bwd_pallas(
            q, k, v, out, lse, g, True, mod.DEFAULT_BWD_BLOCK_Q,
            mod.DEFAULT_BWD_BLOCK_K, interpret, window=window)

    return (jax.jit(lambda *a: bwd(*a)[0]), jax.jit(lambda *a: bwd(*a)[1:]))


def dkv_call(mod, shape):
    """The ``flash_bwd_dkv`` ``pallas_call`` equation of ``mod``'s backward
    at ``shape`` in bfloat16 and the backward's default blocks, traced from
    shapes alone (nothing runs: needs no chip)."""
    import jax
    import jax.numpy as jnp

    B, H, KV, S, D, Dv, window = shape
    dims = ((B, H, S, D), (B, KV, S, D), (B, KV, S, Dv), (B, H, S, Dv),
            (B, H, S), (B, H, S, Dv))
    args = [jax.ShapeDtypeStruct(d, jnp.float32 if len(d) == 3
                                 else jnp.bfloat16) for d in dims]
    jaxpr = jax.make_jaxpr(lambda *a: mod._flash_bwd_pallas(
        *a, True, mod.DEFAULT_BWD_BLOCK_Q, mod.DEFAULT_BWD_BLOCK_K, True,
        window=window))(*args)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"
               and e.params["name"] == "flash_bwd_dkv"]
    return call


def dkv_fetch(mod, shape):
    """What ``mod``'s ``flash_bwd_dkv`` is handed of Q and dO a grid step, by
    the block shapes of its call: rows of the padded sequence, and the bytes
    of the two blocks."""
    call = dkv_call(mod, shape)
    blocks = [bm.block_aval.shape
              for bm in call.params["grid_mapping"].block_mappings]
    q_block, g_block = blocks[0], blocks[3]
    return {"rows": q_block[2], "of": call.invars[0].aval.shape[2],
            "bytes": 2 * int(np.prod(q_block) + np.prod(g_block))}


#: kernel -> products a block pair
PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def visited_pairs(kernel, S, window, block_q, block_k):
    """``(pairs, unmasked)``: the ``[block_q, block_k]`` block pairs one
    head's loops visit under the causal mask and the window, as the
    kernels' own bounds give them, and the share of their scores that is not
    masked."""
    pairs = 0
    if kernel == "dkv":  # a key block's query blocks
        for k_start in range(0, S, block_k):
            last = S // block_q
            if window > 0:
                last = min(last, (k_start + block_k + window - 2)
                           // block_q + 1)
            pairs += last - k_start // block_q
    else:  # a query block's key blocks
        for q_start in range(0, S, block_q):
            first = max(0, (q_start - window + 1) // block_k) if window else 0
            pairs += min(S // block_k,
                         (q_start + block_q - 1) // block_k + 1) - first
    seen = (S * (S + 1) // 2 if window <= 0 else
            sum(min(i + 1, window) for i in range(S)))
    return pairs, seen / (pairs * block_q * block_k)


def loop_work(mod, shape, times):
    """Each kernel's time against the MXU's least time for the block pairs
    its loops visit (masked halves included: the loop computes them):
    ``{kernel: {pairs, unmasked, us_a_pair, mxu_share}}``."""
    from benchmark.harness.peaks import PEAKS

    B, H, KV, S, D, Dv, window = shape
    peak = PEAKS["TPU v5 lite"]["bf16_flops"]  # the shapes are a v5e's
    out = {}
    for kernel, ms in times.items():
        bq, bk = ((mod.DEFAULT_BLOCK_Q, mod.DEFAULT_BLOCK_K)
                  if kernel == "fwd" else
                  (mod.DEFAULT_BWD_BLOCK_Q, mod.DEFAULT_BWD_BLOCK_K))
        bq, bk, S_pad = mod._block_sizes(S, bq, bk)
        pairs, unmasked = visited_pairs(kernel, S_pad, window, bq, bk)
        pairs *= B * H
        # products over D and over Dv alternate: half of each
        flops = pairs * PRODUCTS[kernel] * bq * bk * (D + Dv)
        out[kernel] = {"pairs": pairs, "unmasked": round(unmasked, 4),
                       "us_a_pair": ms * 1e3 / pairs,
                       "mxu_share": flops / peak / (ms / 1e3)}
    return out


def bench_shape(name, shape, trees, iters, interpret, seed=0):
    import jax
    import jax.numpy as jnp

    here = trees["change"]
    window = shape[-1]
    q, k, v, g = _operands(shape, seed)
    fwd = jax.jit(lambda q, k, v: here._flash_fwd(
        q, k, v, True, here.DEFAULT_BLOCK_Q, here.DEFAULT_BLOCK_K,
        interpret, window=window))
    out, lse = fwd(q, k, v)
    row = {"shape": name, "dims": list(shape)}
    got = {}
    for tree, mod in trees.items():
        dq_fn, dkv_fn = _kernels(mod, window, interpret)
        dq_ms = _median_ms(dq_fn, (q, k, v, out, lse, g), iters)
        dkv_ms = _median_ms(dkv_fn, (q, k, v, out, lse, g), iters)
        got[tree] = dkv_fn(q, k, v, out, lse, g)
        row[tree] = {"dq_ms": dq_ms, "dkv_ms": dkv_ms,
                     "dkv_over_dq_a_product": (dkv_ms / 4) / (dq_ms / 3),
                     "dkv_q_do_a_step": dkv_fetch(mod, shape)}
    row["loops"] = loop_work(here, shape, {
        "fwd": _median_ms(fwd, (q, k, v), iters),
        "dq": row["change"]["dq_ms"], "dkv": row["change"]["dkv_ms"]})
    if len(got) == 2:
        row["dk_dv_between_trees"] = [
            _distance(a, b) for a, b in zip(got["change"], got["against"])]

    # one key head of the same group, 2,048 positions: what float32 says
    B, H, KV, S, D, Dv, _ = shape
    small = (1, H // KV, 1, min(S, 2048), D, Dv, window)
    q, k, v, g = _operands(small, seed + 1)
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(
            lambda k, v: here.reference_attention(
                q.astype(jnp.float32), k, v, True, window=window),
            k.astype(jnp.float32), v.astype(jnp.float32))
        want = pull(g.astype(jnp.float32))
    out, lse = here._flash_fwd(q, k, v, True, here.DEFAULT_BLOCK_Q,
                               here.DEFAULT_BLOCK_K, interpret,
                               window=window)
    for tree, mod in trees.items():
        have = _kernels(mod, window, interpret)[1](q, k, v, out, lse, g)
        row[tree]["dk_dv_from_float32"] = [
            _distance(a, b) for a, b in zip(have, want)]
    return row


def product_passes(iters, interpret):
    """Milliseconds of 4,096 ``[512, 256] x [256, 128]`` products in one
    kernel (64 in interpret mode), by what the MXU is handed."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, o_ref, *, narrow, precision):
        def body(i, acc):
            a = a_ref[pl.ds(i * 512, 512), :]
            b = b_ref[...]
            if narrow:
                a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
            return acc + jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
        o_ref[...] = jax.lax.fori_loop(
            0, 8, body, jnp.zeros((512, 128), jnp.float32))

    a = jax.random.normal(jax.random.PRNGKey(0), (4096, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    steps = 8 if interpret else 512
    out, first = {}, {}
    for name, narrow, precision in (
            ("float32_as_handed", False, None), ("bfloat16", True, None),
            ("float32_highest", False, jax.lax.Precision.HIGHEST)):
        call = pl.pallas_call(
            functools.partial(kernel, narrow=narrow, precision=precision),
            grid=(steps,),
            in_specs=[pl.BlockSpec((4096, 256), lambda i: (0, 0)),
                      pl.BlockSpec((256, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((512, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((steps * 512, 128), jnp.float32),
            interpret=interpret, name="product_" + name)
        out[name + "_ms"] = _median_ms(jax.jit(call), (a, b), iters)
        first[name] = np.asarray(call(a, b)[:512])
    out["bfloat16_from_highest"] = _distance(
        first["bfloat16"], first["float32_highest"])
    out["float32_as_handed_equals_bfloat16_bitwise"] = bool(np.array_equal(
        first["float32_as_handed"], first["bfloat16"]))
    return out


def calls_of(dump):
    """Per-call device milliseconds of each flash kernel of a dumped trace,
    in the order the calls started."""
    from benchmark.harness import trace_reduce

    with open(dump) as f:
        trace = json.load(f)
    calls = {}
    for plane in trace_reduce.device_planes(trace)[:1]:
        for ev in sorted(trace_reduce.op_events(plane), key=lambda e: e[1]):
            kernel = trace_reduce.kernel_of(ev)
            if kernel in trace_reduce.FLASH_KERNELS:
                calls.setdefault(kernel, []).append(round(ev[2] / 1e6, 4))
    return calls


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--shapes", default="")
    ap.add_argument("--against", default="")
    ap.add_argument("--passes", action="store_true")
    ap.add_argument("--calls", default="")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    if args.calls:
        print("FLASH_CALLS", json.dumps(calls_of(args.calls)), flush=True)
        here = _load(REPO)
        print("FLASH_FETCH", json.dumps(
            {name: dkv_fetch(here, shape) for name, shape in SHAPES.items()}),
            flush=True)
        return 0

    import jax

    if not args.toy and jax.default_backend() != "tpu":
        print("flash_bench: no TPU (use --toy to rehearse)", file=sys.stderr)
        return 2
    trees = {"change": _load(REPO)}
    if args.against:
        trees["against"] = _load(os.path.abspath(args.against))
    shapes = TOY if args.toy else SHAPES
    if args.shapes:
        shapes = {n: shapes[n] for n in args.shapes.split(",")}
    table = []
    if args.passes:
        table.append({"passes": product_passes(args.iters, args.toy)})
        print("FLASH_PASSES", json.dumps(table[-1]["passes"]), flush=True)
    for name, shape in shapes.items():
        table.append(bench_shape(name, shape, trees, args.iters, args.toy))
        print("FLASH_BENCH", json.dumps(table[-1]), flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flash_bench.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "table": table}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
