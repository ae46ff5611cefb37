"""Ahead-of-time compiles for a DESCRIBED ``v5e:2x2`` — no chip attached.

Interpret mode cannot see what Mosaic and the TPU partitioner refuse: block
shapes not aligned to the (8, 128) tiling (the GQA flash backward was, and
every interpret-mode test passed), kernels over their VMEM budget, and
"Mosaic kernels cannot be automatically partitioned" for any ``pallas_call``
GSPMD meets on sharded operands.  The TPU compiler is installed here and
compiles for a topology that is described, not attached
(``/opt/skills/guides/on-chip-measurement`` section 2), so these cases guard
every later PR at no chip time: the 13 ``ops/smoke.py`` kernel cases at their
real widths, forward and backward; the model's kernels on operands sharded
over a 2x2 mesh; and the ``small_300m`` loss+grad (depth cut to 1 layer,
widths whole) on ``fsdp=2 x tp=2``.

A compile that passes is not a chip run: nothing here says anything about
results or times.
"""

import dataclasses
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from dlrover_tpu.ops.smoke import _flash_cases  # noqa: E402

fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
acc = importlib.import_module("dlrover_tpu.parallel.accelerate")
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip (the next run warns and
    # compiles again): keep the cache off around these.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


FLASH_BWD = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
#: the repo's pallas_call names (XLA lowers some of its own ops, such as
#: ``lax.ragged_dot``, to Mosaic kernels too: not ours to count)
OURS = set(FLASH_BWD) | {
    "rmsnorm_fwd", "softmax_xent_fwd", "quantize_blockwise",
    "gdn_chunk_fwd", "gdn_chunk_bwd", "conv_silu_fwd", "conv_silu_bwd",
    "ssd_chunk_fwd", "ssd_chunk_bwd", "gated_norm_fwd", "gated_norm_bwd"}


def _sq(x):
    return jnp.sum(x.astype(f32) ** 2)


def _our_kernels(compiled) -> dict:
    found = acc.program_summary(compiled.as_text())["kernels"]
    return {k: n for k, n in found.items() if k in OURS}


def _flash(case, grad):
    B, H, KV, S, D = case["shape"]
    kw = dict(case["kw"])
    segmented = kw.pop("segmented", False)

    def fwd(q, k, v, *seg):
        return fa.flash_attention(
            q, k, v, backend="pallas",
            segment_ids=seg[0] if seg else None, **kw,
        )

    fn = fwd
    if grad:
        def fn(q, k, v, *seg):
            return jax.grad(
                lambda q, k, v: _sq(fwd(q, k, v, *seg)), argnums=(0, 1, 2)
            )(q, k, v)
    shapes = [((B, H, S, D), bf16), ((B, KV, S, D), bf16),
              ((B, KV, S, D), bf16)]
    if segmented:
        shapes.append(((B, S), i32))
    # the repo's kernels expected in the program, by pallas_call name
    return fn, shapes, FLASH_BWD if grad else {"flash_fwd": 1}


def _rmsnorm(grad, width=2048):
    """``width`` 2,688 (21 lane tiles) is the stream whose 4 MB row block
    is 390 rows before it is cut to whole sublane tiles."""
    from dlrover_tpu.ops.rmsnorm import rmsnorm

    def fwd(x, w):
        return rmsnorm(x, w, backend="pallas")

    fn = fwd
    if grad:
        def fn(x, w):
            return jax.value_and_grad(
                lambda x, w: _sq(fwd(x, w)), argnums=(0, 1))(x, w)
    return (fn, [((4 * 2048, width), bf16), ((width,), bf16)],
            {"rmsnorm_fwd": 1})


def _xent():
    from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy

    return (
        lambda lg, y: softmax_cross_entropy(lg, y, backend="pallas"),
        [((2048, 32000), bf16), ((2048,), i32)],
        {"softmax_xent_fwd": 1},
    )


def _fused_lm_head():
    from dlrover_tpu.ops.cross_entropy import linear_softmax_cross_entropy

    def fn(x, w, y):
        return jax.value_and_grad(
            lambda x, w: jnp.mean(linear_softmax_cross_entropy(x, w, y)),
            argnums=(0, 1))(x, w)
    # lax.scan, no Pallas: on the hot path of every large-vocab loss
    return fn, [((2048, 1024), bf16), ((1024, 32000), bf16),
                ((2048,), i32)], {}


def _fused_lm_head_sum():
    """The reduced form ``llama.loss_fn`` calls (gradients formed in its
    forward scan), at the per-token case's shapes."""
    from dlrover_tpu.ops.cross_entropy import (
        linear_softmax_cross_entropy_sum,
    )

    def fn(x, w, y):
        return jax.value_and_grad(
            lambda x, w: linear_softmax_cross_entropy_sum(x, w, y),
            argnums=(0, 1))(x, w)
    return fn, [((2048, 1024), bf16), ((1024, 32000), bf16),
                ((2048,), i32)], {}


def _quant():
    from dlrover_tpu.ops.quant import quantize_blockwise

    return (lambda x: quantize_blockwise(x, backend="pallas"),
            [((4 << 20,), f32)], {"quantize_blockwise": 1})


def _grouped_matmul(backend):
    """OLMoE's expert shapes, forward and both gradients: the megablox
    kernel at the tiling ``ops/grouped_matmul.py`` fixes, and its
    reference ``lax.ragged_dot`` (XLA's own kernels over the groups)."""
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul_ragged

    def fn(x, w, sizes):
        return jax.grad(lambda x, w: _sq(grouped_matmul_ragged(
            x, w, sizes, backend=backend)), argnums=(0, 1))(x, w)
    return (fn, [((8192, 2048), bf16), ((64, 2048, 1024), bf16),
                 ((64,), i32)], {})


def _gated_delta(grad, chunk=64):
    """The delta rule's kernel pair at the Qwen3-Next cell's shapes: two
    sequences of 8,192, 32 heads of 128, four tiles of two heads a grid
    step (one of a head at a chunk of 128)."""
    from dlrover_tpu.ops.gated_delta import gated_delta_chunked

    def fwd(q, k, v, g, beta):
        return gated_delta_chunked(q, k, v, g, beta, chunk,
                                   backend="pallas")

    fn = fwd
    if grad:
        def fn(*ops):
            return jax.grad(lambda *o: sum(_sq(x) for x in fwd(*o)[:2]),
                            argnums=range(5))(*ops)
    wide, thin = ((2, 8192, 32, 128), bf16), ((2, 8192, 32), f32)
    return (fn, [wide, wide, wide, thin, thin],
            {"gdn_chunk_fwd": 1, "gdn_chunk_bwd": 1} if grad
            else {"gdn_chunk_fwd": 1})


def _conv_silu(grad, channels, bias):
    """The convolution and its ``silu`` at the two cells' shapes: two
    sequences of 8,192 by the delta-rule mixer's 8,192 channels (16 blocks
    of four lane tiles, no bias) and by the state-space mixer's 4,352 (17
    blocks of two, with its bias), four taps, tiles of 512 rows."""
    from dlrover_tpu.ops.conv_silu import causal_conv1d_silu

    def fwd(*ops):
        return causal_conv1d_silu(*ops, backend="pallas")

    fn = fwd
    if grad:
        def fn(*ops):
            return jax.grad(lambda *o: _sq(fwd(*o)),
                            argnums=range(len(ops)))(*ops)
    shapes = [((2, 8192, channels), bf16), ((4, channels), f32)] + (
        [((channels,), f32)] if bias else [])
    return (fn, shapes, {"conv_silu_fwd": 1, "conv_silu_bwd": 1} if grad
            else {"conv_silu_fwd": 1})


def _ssd(grad):
    """The scan's kernel pair at the one-branch hybrid cell's shapes: 64
    heads of 64 in EIGHT groups (a block is a group's 8 heads, 512 lanes),
    a state of 128, chunks of 128 positions."""
    from dlrover_tpu.ops.ssd import ssd_chunked

    def fwd(x, dt, a, b, c, d):
        return ssd_chunked(x, dt, a, b, c, 128, D=d, backend="pallas")

    fn = fwd
    if grad:
        def fn(*ops):
            return jax.grad(lambda *o: sum(_sq(x) for x in fwd(*o)[:2]),
                            argnums=range(6))(*ops)
    group = ((2, 8192, 8, 128), bf16)
    return (fn, [((2, 8192, 64, 64), bf16), ((2, 8192, 64), f32),
                 ((64,), f32), group, group, ((64,), f32)],
            {"ssd_chunk_fwd": 1, "ssd_chunk_bwd": 1} if grad
            else {"ssd_chunk_fwd": 1})


def _gated_norm(grad, batch, group, gate_first):
    """The gated norm at the two cells' shapes: float32 rows of 4,096
    columns under a bfloat16 gate, blocks of 1,024 rows by 512 lanes — two
    sequences of 8,192 in groups of 128, norm then gate (the delta-rule
    mixer's), three in groups of 512, gate then norm (the state-space
    mixer's eight)."""
    from dlrover_tpu.ops.gated_norm import gated_norm

    def fwd(x, z, gain):
        return gated_norm(x, z, gain, group=group, eps=1e-6,
                          gate_first=gate_first, backend="pallas")

    fn = fwd
    if grad:
        def fn(*ops):
            return jax.grad(lambda *o: _sq(fwd(*o)), argnums=(0, 1, 2))(*ops)
    rows = (batch, 8192, 4096)
    return (fn, [(rows, f32), (rows, bf16), ((4096,), f32)],
            {"gated_norm_fwd": 1, "gated_norm_bwd": 1} if grad
            else {"gated_norm_fwd": 1})


def _flash_gqa16(grad):
    """32 query heads on 2 key heads of 128 (a group of 16), 8,192
    positions, no window."""
    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, backend="pallas")

    fn = fwd
    if grad:
        def fn(q, k, v):
            return jax.grad(lambda q, k, v: _sq(fwd(q, k, v)),
                            argnums=(0, 1, 2))(q, k, v)
    kv = ((2, 2, 8192, 128), bf16)
    return (fn, [((2, 32, 8192, 128), bf16), kv, kv],
            FLASH_BWD if grad else {"flash_fwd": 1})


def _grouped_matmul_1856(backend):
    """An expert 1,856 wide (14.5 lane tiles), both of its matmuls'
    shapes, forward and both gradients, by ``lax.ragged_dot``: the backend
    ``ops.grouped_matmul`` chooses for this width."""
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul_ragged

    def fn(x, up, down, sizes):
        def both(x, up, down):
            h = grouped_matmul_ragged(x, up, sizes, backend=backend)
            return _sq(grouped_matmul_ragged(h, down, sizes,
                                             backend=backend))
        return jax.grad(both, argnums=(0, 1, 2))(x, up, down)
    return (fn, [((15360, 2688), bf16), ((8, 2688, 1856), bf16),
                 ((8, 1856, 2688), bf16), ((8,), i32)], {})


def _bwd_block_q_128():
    """Round 4's hand record has this tuning point stalling the device for
    900 s.  The compiler accepts it — so that was a run-time matter, and
    ``DLROVER_TPU_FLASH_BWD_BLOCK_Q=128`` stays reachable and unexplained
    (the smoke runs the defaults only)."""
    def fn(q, k, v):
        return jax.grad(
            lambda q, k, v: _sq(fa.flash_attention(
                q, k, v, backend="pallas", bwd_block_q=128)),
            argnums=(0, 1, 2))(q, k, v)
    s = ((8, 16, 2048, 64), bf16)
    return fn, [s, s, s], FLASH_BWD


KERNEL_CASES = {
    **{f"{c['name']}-{'bwd' if g else 'fwd'}":
       (lambda c=c, g=g: _flash(c, g))
       for c in _flash_cases() for g in (False, True)},
    "rmsnorm-fwd": lambda: _rmsnorm(False),
    "rmsnorm-grad": lambda: _rmsnorm(True),
    "cross_entropy-fwd": _xent,
    "fused_lm_head_ce-grad": _fused_lm_head,
    "fused_lm_head_ce_sum-grad": _fused_lm_head_sum,
    "quantize_blockwise-fwd": _quant,
    "grouped_matmul-grad": lambda: _grouped_matmul("pallas"),
    "grouped_matmul_reference-grad": lambda: _grouped_matmul("reference"),
    "flash_causal-bwd_block_q128": _bwd_block_q_128,
    "gated_delta-fwd": lambda: _gated_delta(False),
    "gated_delta-grad": lambda: _gated_delta(True),
    "gated_delta_chunk128-grad": lambda: _gated_delta(True, 128),
    "conv_silu_gdn-fwd": lambda: _conv_silu(False, 8192, False),
    "conv_silu_gdn-grad": lambda: _conv_silu(True, 8192, False),
    "conv_silu_ssm-fwd": lambda: _conv_silu(False, 4352, True),
    "conv_silu_ssm-grad": lambda: _conv_silu(True, 4352, True),
    "rmsnorm_2688-fwd": lambda: _rmsnorm(False, 2688),
    "rmsnorm_2688-grad": lambda: _rmsnorm(True, 2688),
    "conv_silu_ssm_6144-fwd": lambda: _conv_silu(False, 6144, True),
    "conv_silu_ssm_6144-grad": lambda: _conv_silu(True, 6144, True),
    "ssd_groups8_chunk128-fwd": lambda: _ssd(False),
    "ssd_groups8_chunk128-grad": lambda: _ssd(True),
    "flash_gqa_32_on_2-fwd": lambda: _flash_gqa16(False),
    "flash_gqa_32_on_2-bwd": lambda: _flash_gqa16(True),
    "grouped_matmul_1856-grad": lambda: _grouped_matmul_1856(None),
    "gated_norm_gdn-fwd": lambda: _gated_norm(False, 2, 128, False),
    "gated_norm_gdn-grad": lambda: _gated_norm(True, 2, 128, False),
    "gated_norm_ssm_groups8-fwd": lambda: _gated_norm(False, 3, 512, True),
    "gated_norm_ssm_groups8-grad": lambda: _gated_norm(True, 3, 512, True),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, shapes, kernels = KERNEL_CASES[case]()
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    assert _our_kernels(jax.jit(fn).lower(*args).compile()) == kernels


#: (tokens, d_model, d_ff) of the routed block below, 64 experts top 8:
#: toy widths (every honest buffer is under tokens x 8 x 64 elements; the
#: grouped matmuls go to ``lax.ragged_dot``) and OLMoE's (the kernel)
ROUTED_CASES = {"mid_size": (2048, 32, 16), "olmoe_widths": (512, 2048, 1024)}


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_routed_block_holds_no_dispatch_sized_buffer(topo, monkeypatch, case):
    """Forward and backward of ``_moe_swiglu`` as the chip's compiler leaves
    it: nothing shaped tokens*k x experts or tokens x k x experts (the
    one-hot dispatch this block replaced, 86 GB at OLMoE's widths; a dense
    fallback of a ragged product's transpose would bring it back), and at
    toy widths no buffer of tokens*k*experts elements at all."""
    from dlrover_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, f = ROUTED_CASES[case]
    e, k = 64, 8
    cfg = llama.LlamaConfig.tiny(
        d_model=d, d_ff=f, num_experts=e, top_k=k, moe_every=1,
        norm_topk_prob=False, balance_all_k=True, dtype=bf16)
    one_chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    moe = {"router": arg((d, e), f32), "wg": arg((e, d, f), f32),
           "wi": arg((e, d, f), f32), "wo": arg((e, f, d), f32)}

    def loss(x, moe):
        out, stats = llama._moe_swiglu(x, moe, cfg)
        return _sq(out) + stats["moe_aux"] + stats["moe_z"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        arg((1, n, d), bf16), moe).compile()
    text = compiled.as_text()
    kernels = acc.program_summary(text)["kernels"]
    if case == "olmoe_widths":
        assert (kernels.get("gmm"), kernels.get("tgmm")) == (6, 3), kernels
    else:
        assert "gmm" not in kernels, kernels
    bad, seen = set(), set()
    for dims in re.findall(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]", text):
        seen.add(dims)
        shape = [int(v) for v in dims.split(",")]
        tokens_by_experts = e in shape and (
            n * k in shape or (n in shape and k in shape))
        if tokens_by_experts or (
                case == "mid_size" and np.prod(shape) >= n * k * e):
            bad.add(dims)
    assert f"{n * k},{d}" in seen  # the sorted pair rows are there
    assert not bad, bad


def _mesh(topo):
    from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(fsdp=2, tp=2), topo.devices)


def _sharded_flash(grad, shape=(8, 16, 4, 2048, 64), **kw):
    # GQA with KV % tp == 0: each tp shard keeps whole query groups
    fn, shapes, n = _flash({"shape": shape, "kw": kw}, grad)
    spec = P(("dp", "fsdp"), "tp", None, None)
    return fn, shapes, [spec] * 3, n


def _sharded_rmsnorm():
    from dlrover_tpu.ops.rmsnorm import rmsnorm

    return (lambda x, w: rmsnorm(x, w, backend="pallas"),
            [((8, 2048, 1024), bf16), ((1024,), f32)],
            [P(("dp", "fsdp"), None, None), P()], {"rmsnorm_fwd": 1})


def _sharded_gated_norm():
    fn, shapes, kernels = _gated_norm(True, 4, 128, False)
    rows = P(("dp", "fsdp"), None, None)
    return fn, shapes, [rows, rows, P()], kernels


SHARDED_CASES = {
    "flash_gqa-fwd": lambda: _sharded_flash(False),
    "flash_gqa-bwd": lambda: _sharded_flash(True),
    # the four-chip window cell's call: a shard's dkv step holds 4,608 of
    # its 8,192 rows of Q and dO at an element offset
    "flash_window_4096_of_8192-bwd": lambda: _sharded_flash(
        True, (2, 32, 8, 8192, 128), window=4096),
    "rmsnorm-fwd": _sharded_rmsnorm,
    "gated_norm-grad": _sharded_gated_norm,
}


@pytest.mark.parametrize("case", sorted(SHARDED_CASES))
def test_kernel_runs_per_shard_on_2x2(topo, case):
    """Operands sharded over fsdp x tp: bare, the partitioner refuses the
    kernel; per shard (``ops/per_shard.py``, the mesh in scope) it compiles
    and nothing is gathered to feed it."""
    fn, shapes, specs, kernels = SHARDED_CASES[case]()
    mesh = _mesh(topo)
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
        for (s, d), spec in zip(shapes, specs)
    ]
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert _our_kernels(compiled) == kernels
    assert not re.search(r"\sall-gather(-start)?\(", compiled.as_text())


def test_bare_kernel_on_sharded_operands_is_refused(topo):
    """The refusal the per-shard wrapper exists for — if a later jax
    learns to partition Mosaic kernels, this says so."""
    fn, shapes, specs, _ = _sharded_rmsnorm()
    mesh = _mesh(topo)
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
        for (s, d), spec in zip(shapes, specs)
    ]
    with pytest.raises(Exception, match="Mosaic kernels cannot be"):
        jax.jit(fn).lower(*args).compile()  # no mesh in scope


def test_small_300m_loss_grad_on_fsdp2_tp2(topo, monkeypatch):
    """The model's own step, from shapes: kernels present, collectives
    present, and q, k, v reach the attention kernel without being gathered
    to full size.  Depth is cut (1 of 12 layers); widths are the preset's."""
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshSpec

    # The dispatchers ask jax.default_backend() and would see the CPU:
    # steer them here, in the test (jax's own internals do not read this
    # attribute).
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(llama.LlamaConfig.small_300m(), n_layer=1)
    B, S = 8, 2048
    captured = {}
    real_summary = acc.program_summary

    def keep_text(text):
        captured["text"] = text
        return real_summary(text)

    monkeypatch.setattr(acc, "program_summary", keep_text)
    job = acc.aot_analyze(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(fsdp=2, tp=2)),
        param_specs="planner", devices=topo.devices,
    )
    kernels, coll = job.program["kernels"], job.program["collectives"]
    assert kernels == {
        "rmsnorm_fwd": 2 * cfg.n_layer + 1,
        "flash_fwd": cfg.n_layer,
        "flash_bwd_dq": cfg.n_layer,
        "flash_bwd_dkv": cfg.n_layer,
    }
    assert coll["all-gather"] > 0 and coll["all-reduce"] > 0
    # q, k and v reach the attention kernel as shards — [b/fsdp * H/tp, S,
    # D] operands — and nothing of their full-size 4-D shape is ever
    # gathered.  ([b/fsdp, S, H*D] is gathered: it is the attention OUTPUT
    # in front of ``wo`` under the planner's (fsdp, tp) layout, the same
    # shape q has, which is why the kernel's own operands are the proof.)
    H, D = cfg.n_head, cfg.head_dim
    shard = f"bf16[{(B // 2) * (H // 2)},{S},{D}]"
    calls = [ln for ln in captured["text"].splitlines()
             if "flash_fwd/pallas_call" in ln and "tpu_custom_call" in ln]
    assert len(calls) == cfg.n_layer
    for ln in calls:
        ops = ln.split("operand_layout_constraints={")[1]
        ops = ops.split("frontend_attributes")[0]
        assert re.findall(r"\w+\[[\d,]+\]", ops) == [shard] * 3, ops
    full = {
        f"[{b},{dims}]" for b in (B, B // 2) for dims in (
            f"{S},{H},{D}", f"{H},{S},{D}")
    } | {f"[{B},{S},{H * D}]"}
    gathered = set()
    for ln in captured["text"].splitlines():
        m = re.search(r"=\s*(.*?)\sall-gather(?:-start)?\(", ln)
        if m:
            gathered |= set(re.findall(r"\[[\d,]+\]", m.group(1)))
    assert gathered and not (gathered & full), gathered & full


def test_looped_step_tells_block_applications_from_layers(topo, monkeypatch):
    """One layer run twice with block remat: ``block_applications`` says
    how often a token meets a block, the remat keeps each application's
    flash output and log-sum-exp, so the kernel runs as often and no more
    (the projections and the MLP are what is recomputed), and every scope
    of the looped loss is in the compiled step's table."""
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshSpec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        llama.LlamaConfig.small_300m(), n_layer=1, loop_passes=2,
        branch_norm=True, exit_gate_beta=0.1, remat_block=True)
    job = acc.aot_analyze(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg, metrics=True),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch={"tokens": np.zeros((4, 1025), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec()), param_specs="planner",
        devices=topo.devices[:1],
    )
    kernels = job.program["kernels"]
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (2, 2, 2)
    assert job.program["block_applications"] == cfg.block_applications == 2
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {("forward", "exit_gate"), ("backward", "exit_gate"),
            ("recompute", "attention"), ("recompute", "mlp"),
            ("forward", "lm_head_loss"), ("forward", "final_norm")} <= found


def test_latent_share_mtp_step_compiles_at_published_widths(topo, monkeypatch):
    """The GLM-4.7-Flash cell's step from shapes, depth cut to the dense
    layer, one routed layer and the prediction block (three block
    applications), widths and sequence length whole: the flash kernels at a
    head size of 256 hold K and V of 8,192 rows (4 MB each, double
    buffered: the compiler's default scoped VMEM to the byte, so they ask
    for more), the grouped matmuls run over 8 held experts' groups that end
    before the buffer does, and the compiled step's tables name the nested
    scopes."""
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshSpec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = llama.LlamaConfig(
        vocab_size=19360, n_layer=2, n_head=20, n_kv_head=20, d_model=2048,
        d_ff=10240, max_seq_len=8192, rope_theta=1e6, remat_block=True,
        num_experts=64, top_k=4, moe_every=1, first_k_dense=1,
        d_ff_expert=1536, n_shared_experts=1, router_score="sigmoid",
        routed_scaling=1.8, router_bias_rate=1e-3,
        balance_per_sequence=True, experts_held=8, mtp_layers=1,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-4,
                             metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    job = acc.aot_analyze(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(3e-4),
        sample_batch={"tokens": np.zeros((1, 8193), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec()), param_specs="planner",
        devices=topo.devices[:1],
    )
    kernels = job.program["kernels"]
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (3, 3, 3)
    assert job.program["block_applications"] == cfg.block_applications == 3
    # two routed blocks: three grouped matmuls forward, recomputed and for
    # the row gradients, three weight gradients
    assert kernels["gmm"] == 2 * 9 and kernels["tgmm"] == 2 * 3
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {("forward", "mtp"), ("backward", "mtp"), ("recompute", "mtp"),
            ("forward", "moe_shared"), ("backward", "moe_experts"),
            ("forward", "router_bias"), ("forward", "lm_head_loss"),
            ("recompute", "attention")} <= found
    inner = set(job.program["subscopes"].values())
    assert {"mla_q", "mla_kv", "mla_out", "attention", "moe_shared"} <= inner
    # and the cut fits the chip
    assert job.memory["peak_bytes"] < 16_909_336_064


@pytest.fixture(scope="module")
def hybrid_step(topo):
    """``(job, compiled text, cfg)`` of the Granite hybrid cell's step from
    shapes, depth cut to one state-space layer and the attention layer,
    widths, batch and sequence length whole."""
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshSpec

    cfg = llama.LlamaConfig(
        vocab_size=12544, n_layer=2, n_head=32, n_kv_head=8, d_model=2048,
        d_ff=8192, max_seq_len=8192, remat_block=True,
        layer_types=("mamba", "attention"), mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128, rope=False,
        attention_multiplier=1 / 64, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0,
        tie_word_embeddings=True)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, metrics=True)

    loss.program_facts = llama.program_facts(cfg, 8192)
    texts = []
    with pytest.MonkeyPatch.context() as patch:
        # the kernel dispatchers ask the backend and would see the CPU
        patch.setattr(jax, "default_backend", lambda: "tpu")
        summary = acc.program_summary
        patch.setattr(acc, "program_summary",
                      lambda text: texts.append(text) or summary(text))
        job = acc.aot_analyze(
            loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
            optimizer=optax.adamw(3e-4),
            sample_batch={"tokens": np.zeros((2, 8193), np.int32)},
            strategy=acc.Strategy(mesh=MeshSpec()), param_specs="planner",
            devices=topo.devices[:1],
        )
    return job, texts[-1], cfg


def test_hybrid_step_compiles_at_published_widths(hybrid_step):
    """The flash kernels run at 32/8 heads of 64 without rotary position,
    the head is the embedding transposed, and the compiled step's tables
    name the mixer's nested scopes in every phase."""
    job, _, cfg = hybrid_step
    kernels = job.program["kernels"]
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (1, 1, 1)
    assert job.program["block_applications"] == cfg.block_applications == 1
    assert (job.program["ssm_layers"], job.program["attention_layers"],
            job.program["ssm_chunks_per_sequence"]) == (1, 1, 32)
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {("forward", "ssm"), ("backward", "ssm"), ("recompute", "ssm"),
            ("forward", "attention"), ("forward", "lm_head_loss")} <= found
    by_inner = {}
    for name, inner in job.program["subscopes"].items():
        by_inner.setdefault(inner, set()).add(job.program["scopes"][name][0])
    for inner in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_out"):
        # since the convolution is a kernel (PR 54) XLA no longer writes
        # the recomputed ``y * silu(z)`` out: it forms it again inside the
        # two backward fusions that read it (the gate's own and the scan's
        # transpose), and what is left of the gate's recomputation at the
        # top level is the ``rmsnorm_fwd`` call, filed under its own name
        phases = {"forward", "backward"} | (
            set() if inner == "ssm_gate" else {"recompute"})
        assert phases <= by_inner[inner], inner
    assert "recompute" in by_inner["rmsnorm_fwd"]
    # 137 M parameters of state and two sequences of 8,192: the step's
    # temporaries stay under 4 GB, which all heads' masks at once would not
    assert job.memory["temp_bytes"] < 4 * 1024 ** 3


def test_hybrid_step_forms_no_decay_mask_in_hbm(hybrid_step):
    """The chunked scan's ``[Q, Q]`` part is the kernel pair: under block
    remat the step journals ``ssd_chunk_fwd`` twice a state-space layer
    (forward, recomputation) and ``ssd_chunk_bwd`` once, and no instruction
    under ``ssm_scan`` — a fusion's inner ones included — has a result or an
    operand with two chunk-length dimensions larger than one ``C B^T`` a group
    (2 x 32 chunks x 256 x 256): a mask of every head would be 64 times
    that."""
    job, text, cfg = hybrid_step
    kernels, layers = job.program["kernels"], job.program["ssm_layers"]
    assert kernels["ssd_chunk_fwd"] == 2 * layers
    assert kernels["ssd_chunk_bwd"] == layers
    q, per_group = cfg.mamba_chunk_size, 2 * 32 * cfg.mamba_n_groups
    seen = 0
    for line in text.splitlines():
        if "ssm_scan" not in line or " = " not in line:
            continue
        seen += 1
        shapes = line.split(" = ", 1)[1].split("metadata=", 1)[0]
        for dims in re.findall(r"\[([0-9,]+)\]", shapes):
            dims = [int(d) for d in dims.split(",")]
            assert not (dims.count(q) >= 2
                        and np.prod(dims) > per_group * q * q), line[:200]
    assert seen > 100  # the scope's instructions were there to be read


def _conv_scope_holds_no_float32_sequence(text, scope, channels):
    """No instruction under ``scope`` that reads or writes HBM — the top
    level's, not a fusion's inner ones, whose values stay in registers — has
    a float32 result of two sequences of 8,192 by the convolution's
    channels: the pre-activation stays in VMEM."""
    seen, fused = 0, False
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            fused = "fused_computation" in line.split()[0]
        if fused or scope not in line or " = " not in line:
            continue
        seen += 1
        shapes = line.split(" = ", 1)[1].split("metadata=", 1)[0]
        assert f"f32[2,8192,{channels}]" not in shapes, line[:200]
    assert seen > 4  # the scope's instructions were there to be read


def test_hybrid_step_keeps_the_convolutions_float32_in_vmem(hybrid_step):
    """The convolution and its ``silu`` are the kernel pair: under block
    remat the step journals ``conv_silu_fwd`` twice a state-space layer
    (forward, recomputation) and ``conv_silu_bwd`` once."""
    job, text, cfg = hybrid_step
    kernels, layers = job.program["kernels"], job.program["ssm_layers"]
    assert kernels["conv_silu_fwd"] == 2 * layers
    assert kernels["conv_silu_bwd"] == layers
    _conv_scope_holds_no_float32_sequence(text, "ssm_conv",
                                          cfg.mamba_conv_dim)


def _mixer_keeps_the_channels_minor(text, batch, seq_len, width, layers):
    """No instruction under ``ssm_scan``, ``ssm_gate`` or ``ssm_conv`` that
    reads or writes HBM — the top level's, not a fusion's inner ones — has
    a result or an operand of ``batch`` whole sequences by the mixer's width
    with the SEQUENCE minor, ``[batch, width, seq_len]`` or ``[batch,
    seq_len, width]`` in another layout than ``{2,1,0}``: the scan's kernels
    take ``x`` and hand ``y`` and ``dx`` over as the convolution's and the
    gated norm's lay them out, and nothing between the three is a
    transposing copy.  ONE turn a pass is left, and is XLA's own: the chunk
    states' einsum contracts the positions, XLA wants them minor in its
    operand, and it turns the BFLOAT16 ``x`` for that behind
    ``ssd_chunked``'s barriers — forward, recomputed, and the cotangent's
    way back (the parent's einsum read the turn the kernels needed; S13
    (ii), the chunk states as a kernel, takes it).  Never a float32 one."""
    turned = re.compile(
        rf"\[{batch},{width},{seq_len}\]"
        rf"|\[{batch},{seq_len},{width}\]\{{(?!2,1,0[:}}])")
    seen, fused, for_the_states = 0, False, 0
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            fused = "fused_computation" in line.split()[0]
        if fused or " = " not in line or not any(
                scope in line for scope in ("ssm_scan", "ssm_gate",
                                            "ssm_conv")):
            continue
        seen += 1
        shapes, meta = line.split(" = ", 1)[1].split("metadata=", 1)
        if not turned.search(shapes):
            continue
        assert shapes.startswith(
            f"bf16[{batch},{seq_len},{width}]{{1,2,0") and re.search(
                r"/ssm_scan/(optimization_barrier|reshape)\"", meta), \
            line[:300]
        for_the_states += 1
    assert seen > 100  # the scopes' instructions were there to be read
    assert for_the_states <= 3 * layers


def test_hybrid_step_keeps_the_mixers_channels_minor(hybrid_step):
    """The scan's kernels read ``x`` and write ``y``, ``dx`` as ``[B, S, G R
    P]``, channels on the lanes (PR 69): the parent's text held thirteen
    turned instructions a state-space layer, a ``copy
    f32[2,4096,8192]{1,2,0}`` behind every ``ssd_chunk_fwd`` among them.  The
    kernels' counts stand."""
    job, text, cfg = hybrid_step
    kernels, layers = job.program["kernels"], job.program["ssm_layers"]
    assert (kernels["ssd_chunk_fwd"], kernels["ssd_chunk_bwd"],
            kernels["conv_silu_fwd"], kernels["conv_silu_bwd"]) == (
                2 * layers, layers, 2 * layers, layers)
    _mixer_keeps_the_channels_minor(
        text, 2, 8192, cfg.mamba_n_heads * cfg.mamba_d_head, layers)


def _step_and_text(topo, loss, cfg, sequences, seq_len):
    """``(job, compiled text)`` of ``loss``'s training step on one described
    chip, from shapes."""
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshSpec

    texts = []
    with pytest.MonkeyPatch.context() as patch:
        # the kernel dispatchers ask the backend and would see the CPU
        patch.setattr(jax, "default_backend", lambda: "tpu")
        summary = acc.program_summary
        patch.setattr(acc, "program_summary",
                      lambda text: texts.append(text) or summary(text))
        job = acc.aot_analyze(
            loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
            optimizer=optax.adamw(1e-5),
            sample_batch={
                "tokens": np.zeros((sequences, seq_len + 1), np.int32)},
            strategy=acc.Strategy(mesh=MeshSpec()), param_specs="planner",
            devices=topo.devices[:1],
        )
    return job, texts[-1]


def _row_gathers(text, width, only=""):
    """``{(phase, scope, rows): n}`` of the compiled step's XLA row
    gathers with ``width`` columns (a gather is a fusion of its own that
    writes every row it reads), those whose ``op_name`` holds ``only``."""
    found = {}
    for line in text.splitlines():
        m = re.match(rf"\s*%?[\w.\-]+ = bf16\[(\d+),{width}\]\S* fusion\(",
                     line)
        op = re.search(r'op_name="([^"]*/gather)"', line)
        if not (m and op and "kind=kCustom" in line and only in op.group(1)):
            continue
        verdict = acc.phase_and_scope(op.group(1))
        if verdict is not None:
            key = (*verdict, int(m.group(1)))
            found[key] = found.get(key, 0) + 1
    return found


def _sized_branch_holds_no_pick_sized_array(text, n, k, c):
    """No instruction of the sized buffer's branch of a routed block has
    ``n * k`` rows by ``c`` columns in any arrangement, forward, recomputed
    or backward."""
    picks = {f"[{n * k},{c}]", f"[{n},{k},{c}]", f"[{k},{n},{c}]"}
    seen = 0
    for line in text.splitlines():
        if "branch_0_fun" not in line or " = " not in line:
            continue
        seen += 1
        shapes = line.split(" = ", 1)[1].split("metadata=", 1)[0]
        assert not picks & set(re.findall(r"\[[\d,]+\]", shapes)), line[:200]
    assert seen > 100  # the branch's instructions were there to be read


@pytest.fixture(scope="module")
def conv_step(topo):
    """``(job, compiled text, cfg)`` of the LFM2 cell's step from shapes,
    depth cut to the dense convolution layer, the routed attention layer
    and ONE routed convolution layer, widths, held experts, batch and
    sequence length whole."""
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=16384, n_layer=3, n_head=32, n_kv_head=8, d_model=2048,
        d_ff=7168, max_seq_len=8192, rope_theta=1e6, remat_block=True,
        layer_types=("conv", "attention", "conv"), qk_norm=True,
        qk_norm_per_head=True, num_experts=32, top_k=4, moe_every=1,
        first_k_dense=1, d_ff_expert=1792, router_score="sigmoid",
        router_norm_eps=1e-6, router_bias_rate=1e-3, experts_held=8,
        tie_word_embeddings=True)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=0.0,
                             metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    loss.program_facts = llama.program_facts(cfg, 8192)
    return (*_step_and_text(topo, loss, cfg, 4, 8192), cfg)


def test_conv_step_compiles_at_published_widths(conv_step):
    """The flash kernels run in the ONE attention layer (32/8 heads of 64
    behind the per-head q/k norm), a convolution layer's routed MLP runs
    the grouped matmuls over its share of the experts, and the compiled
    step's tables name the mixer's nested scopes in every phase."""
    job, _, cfg = conv_step
    kernels = job.program["kernels"]
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (1, 1, 1)
    assert job.program["block_applications"] == cfg.block_applications == 1
    assert (job.program["conv_layers"],
            job.program["attention_layers"]) == (2, 1)
    # two routed blocks: three grouped matmuls forward, again recomputed
    # (less the kept pair), and their two transposes backward
    assert kernels["gmm"] > 0 and kernels["tgmm"] == 3 * 2
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {("forward", "conv"), ("backward", "conv"), ("recompute", "conv"),
            ("forward", "attention"), ("backward", "moe_experts"),
            ("forward", "router_bias"), ("forward", "lm_head_loss")} <= found
    by_inner = {}
    for name, inner in job.program["subscopes"].items():
        by_inner.setdefault(inner, set()).add(job.program["scopes"][name][0])
    for inner in ("conv_in", "conv_gate", "conv_out"):
        assert {"forward", "backward", "recompute"} <= by_inner[inner], inner
    # 311 M parameters of state and four sequences of 8,192 fit the chip
    assert job.memory["peak_bytes"] < 16_909_336_064


def test_conv_step_sizes_its_sorted_buffer(conv_step):
    """Four sequences of 8,192 tokens take 4 of 32 experts and the chip
    holds 8: the routed blocks choose between 40,960 rows and all 131,072.
    The kernels are ONE size's (nine ``gmm`` and three ``tgmm`` a block:
    forward, and recomputed with the transposes inside the backward rule —
    the block's own recomputation drops its copy); the buffer of every
    pick multiplies with XLA's ragged dot.  A branch's instructions carry
    the program's scopes and phases, and no scope is a branch's name."""
    from dlrover_tpu.models import llama

    job, _, _ = conv_step
    assert llama._moe_buffer_bounds(4 * 8192, 4, 32, 8) == (40960, 131072)
    kernels = job.program["kernels"]
    assert (kernels["gmm"], kernels["tgmm"]) == (2 * 9, 2 * 3)
    assert kernels["unnamed"] > 0  # lax.ragged_dot's own Mosaic kernels
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {(phase, scope) for phase in ("forward", "recompute", "backward")
            for scope in ("moe_permute", "moe_experts")} <= found
    assert not [scope for _, scope in found if scope.startswith("branch_")]


def test_conv_step_token_side_builds_no_pick_sized_array(conv_step):
    """The token side of a routed block is ``gather_sum`` and its transpose:
    the kernel runs twice a block in each size's branch (the combine
    forward, the dispatch's transpose backward — the combine recomputed
    inside the backward rule has no reader and is dropped), the grouped
    matmuls are the parent's, and no instruction of the sized buffer's
    branch has 131,072 rows by 2,048 columns in any arrangement, forward,
    recomputed or backward: the three XLA row gathers left a block are the
    40,960-row dispatch (forward and recomputed) and the dispatch of the
    cotangent that the combine's backward runs on the sorted side."""
    job, text, cfg = conv_step
    kernels = job.program["kernels"]
    assert (kernels["gmm"], kernels["tgmm"]) == (2 * 9, 2 * 3)
    assert kernels["gather_sum"] == 2 * 2 * 2
    n, k, c = 4 * 8192, cfg.top_k, cfg.d_model
    _sized_branch_holds_no_pick_sized_array(text, n, k, c)
    gathers = {("forward", "moe_permute"): 2, ("recompute", "moe_permute"): 2,
               ("backward", "moe_combine"): 2}
    for branch, rows in (("branch_0_fun", 40960), ("branch_1_fun", n * k)):
        assert _row_gathers(text, c, only=branch) == {
            (*where, rows): count for where, count in gathers.items()}
    # what is left under each scope: the recomputed forward holds nothing
    # of the combine (its residuals are the experts' rows, the weights and
    # the two index vectors; the parent's second gather sat in moe_permute)
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {(phase, "moe_permute")
            for phase in ("forward", "recompute", "backward")} <= found
    assert {("forward", "moe_combine"), ("backward", "moe_combine")} <= found
    assert job.memory["peak_bytes"] < 16_909_336_064


@pytest.fixture(scope="module")
def gdn_step(topo):
    """``(job, compiled text, cfg)`` of the Qwen3-Next cell's step from
    shapes, depth cut to ONE delta-rule layer and the attention layer,
    widths, held experts, batch and sequence length whole."""
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=18992, n_layer=2, n_head=16, n_kv_head=2, d_model=2048,
        d_ff=5120, max_seq_len=8192, rope_theta=1e7, rms_eps=1e-6,
        remat_block=True, layer_types=("linear_attention", "attention"),
        gdn_k_heads=16, gdn_v_heads=32, gdn_d_head=128, gdn_d_conv=4,
        attn_head_dim=256, attn_output_gate=True, partial_rotary_factor=0.25,
        norm_plus_one=True, qk_norm=True, qk_norm_per_head=True,
        num_experts=512, top_k=10, moe_every=1, d_ff_expert=512,
        n_shared_experts=1, shared_expert_gate=True, balance_all_k=True,
        experts_held=32)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-3,
                             metrics=True)

    loss.program_facts = llama.program_facts(cfg, 8192)
    return (*_step_and_text(topo, loss, cfg, 2, 8192), cfg)


def test_gdn_step_compiles_at_published_widths(gdn_step):
    """The flash kernels run in the ONE attention layer at 16/2 heads of
    256 behind the per-head ``1 + w`` norm, the partial rotary pass and
    under the output gate; a delta-rule layer's routed MLP runs the grouped
    matmuls over its 32 of 512 experts; the compiled step's tables name the
    mixer's nested scopes in every phase; and the cut fits the chip."""
    job, _, cfg = gdn_step
    kernels = job.program["kernels"]
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (1, 1, 1)
    assert job.program["block_applications"] == cfg.block_applications == 1
    assert (job.program["gdn_layers"], job.program["attention_layers"],
            job.program["gdn_chunks_per_sequence"]) == (1, 1, 128)
    # two routed blocks: nine grouped matmuls and three transposes each in
    # the sized buffer's branch
    assert (kernels["gmm"], kernels["tgmm"]) == (2 * 9, 2 * 3)
    found = {tuple(v) for v in job.program["scopes"].values()}
    assert {("forward", "gdn"), ("backward", "gdn"), ("recompute", "gdn"),
            ("forward", "attention"), ("backward", "moe_experts"),
            ("forward", "moe_shared"), ("forward", "lm_head_loss")} <= found
    by_inner = {}
    for name, inner in job.program["subscopes"].items():
        by_inner.setdefault(inner, set()).add(job.program["scopes"][name][0])
    for inner in ("gdn_in", "gdn_conv", "gdn_scan", "gdn_gate", "gdn_out"):
        assert {"forward", "backward", "recompute"} <= by_inner[inner], inner
    assert job.memory["peak_bytes"] < 16_909_336_064


def test_gdn_step_sizes_its_sorted_buffer_and_keeps_the_rule_in_vmem(
        gdn_step):
    """Two sequences of 8,192 tokens take 10 of 512 experts and the chip
    holds 32: the routed blocks choose between 12,800 rows and all 163,840.
    The rule is the kernel pair: block remat keeps the forward kernel's
    three outputs and the mixer has no checkpoint of its own, so the step
    journals ``gdn_chunk_fwd`` once a delta-rule layer and ``gdn_chunk_bwd``
    once; no instruction under ``gdn_scan`` — a fusion's
    inner ones included — has a result or an operand with two chunk-length
    dimensions (the ``[Q, Q]`` arrays stay in VMEM) or is a loop (the scan
    over the 128 chunks is the kernels' grid), and the step's peak is under
    what the ``jax.numpy`` form's was (14.553 GB; ledger, PR 52)."""
    from dlrover_tpu.models import llama

    job, text, cfg = gdn_step
    assert llama._moe_buffer_bounds(2 * 8192, 10, 512, 32) == (12800, 163840)
    kernels, layers = job.program["kernels"], job.program["gdn_layers"]
    assert kernels["gdn_chunk_fwd"] == kernels["gdn_chunk_bwd"] == layers
    q, seen = llama.GDN_CHUNK, 0
    for line in text.splitlines():
        if "gdn_scan" not in line or " = " not in line:
            continue
        seen += 1
        assert " while(" not in line, line[:200]
        shapes = line.split(" = ", 1)[1].split("metadata=", 1)[0]
        for dims in re.findall(r"\[([0-9,]+)\]", shapes):
            assert [int(d) for d in dims.split(",")][-2:] != [q, q], line[:200]
    assert seen > 100  # the scope's instructions were there to be read
    assert job.memory["peak_bytes"] < 14_553_000_000


def test_gdn_step_token_side_builds_no_pick_sized_array(gdn_step):
    """Ten picks a token and 12,800 rows: ``gather_sum`` takes any K, so
    the kernel runs twice a routed block in each size's branch (the combine
    forward, the dispatch's transpose backward) and no instruction of the
    sized buffer's branch has 163,840 rows by 2,048 columns in any
    arrangement."""
    job, text, cfg = gdn_step
    assert job.program["kernels"]["gather_sum"] == 2 * 2 * 2
    _sized_branch_holds_no_pick_sized_array(
        text, 2 * 8192, cfg.top_k, cfg.d_model)


def test_gdn_step_keeps_the_convolutions_float32_in_vmem(gdn_step):
    """Under block remat, which keeps nothing of the convolution, the step
    journals ``conv_silu_fwd`` twice a delta-rule layer and ``conv_silu_bwd``
    once; what XLA keeps under ``gdn_conv`` is the concatenation of ``q``,
    ``k`` and ``v`` in bf16 and its transpose."""
    job, text, cfg = gdn_step
    kernels, layers = job.program["kernels"], job.program["gdn_layers"]
    assert kernels["conv_silu_fwd"] == 2 * layers
    assert kernels["conv_silu_bwd"] == layers
    _conv_scope_holds_no_float32_sequence(
        text, "gdn_conv", (2 * cfg.gdn_k_heads + cfg.gdn_v_heads)
        * cfg.gdn_d_head)


@pytest.fixture(scope="module")
def olmoe_step(topo):
    """``(job, compiled text, cfg)`` of the OLMoE cell's step from shapes:
    its one layer at published widths, eight sequences of 4,096, every
    expert held, no block remat."""
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=50304, n_layer=1, n_head=16, n_kv_head=16, d_model=2048,
        d_ff=1024, max_seq_len=4096, rms_eps=1e-5, remat_block=False,
        num_experts=64, top_k=8, moe_every=1, norm_topk_prob=False,
        balance_all_k=True, qk_norm=True)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, metrics=True)

    return (*_step_and_text(topo, loss, cfg, 8, 4096), cfg)


def test_olmoe_step_keeps_the_gathered_rows(olmoe_step):
    """Every expert is held, so every pick is live and has its row (``R =
    N*K``): the kernel would have no row to skip and the sorted side is no
    smaller than the token side, so the step stays what it was — XLA's row
    gathers, two forward (the dispatch, the combine) and two backward
    (the weighted cotangent by ``order``, the dispatch's transpose), the
    gathered rows kept for the router weights' gradient — at the same
    compiled peak (14.149 GB; the sorted-side rule read 14.245 and 1.1 %
    fewer tokens a second on the chip)."""
    job, text, cfg = olmoe_step
    kernels = job.program["kernels"]
    assert (kernels["gmm"], kernels["tgmm"]) == (6, 3)
    assert "gather_sum" not in kernels
    rows = 8 * 4096 * cfg.top_k
    assert _row_gathers(text, cfg.d_model) == {
        ("forward", "embed", 8 * 4096): 1,
        ("forward", "moe_permute", rows): 1,
        ("forward", "moe_combine", rows): 1,
        ("backward", "moe_combine", rows): 1,
        ("backward", "moe_permute", rows): 1}
    assert job.memory["peak_bytes"] < 14.16e9
