"""The Phi-4-mini-flash cell's step (``phi4_mini_flash-l6.train-16k-decayed``:
published layers 0, 1, 16, 17, 18, 19 of Phi-4-mini-flash-reasoning, an eighth
of the vocabulary) compiled ahead of time for ONE described v5e at FULL depth
and published widths, from shapes, at one sequence of 16,384: that it fits,
and what the compiled text says of the selective scan's kernel pair under
``s6/s6_scan`` — forward once a layer under block remat — and of the flash
kernels on paired heads, 64-wide q and k over 128-wide v, under the three
kinds' scopes.  A file of its own, so that the whole-depth compile runs beside
``tests/test_aot_compile.py``'s and not behind them.

A compile that passes is not a chip run: nothing here says anything about
results or times.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_aot_compile import (  # noqa: F401
    _sq,
    _step_and_text,
    acc,
    topo,
)

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reported it (PR 21)
V5E_BYTES_LIMIT = 16_909_336_064
SEQ = 16384
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def step(topo):  # noqa: F811
    from benchmark.adapters import phi4flash as adapter
    from dlrover_tpu.models import llama

    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi4_mini_flash-l6.json")) as f:
        cfg = adapter.model_config(json.load(f), remat_block=True,
                                   seq_len=SEQ)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, metrics=True)

    loss.program_facts = llama.program_facts(cfg, SEQ)
    return (*_step_and_text(topo, loss, cfg, 1, SEQ), cfg)


def test_the_cell_fits_at_one_sequence_of_16384(step):
    """697.1 M parameters: 8.37 GB of state + 2.79 GB of gradients, and XLA's
    buffer assignment peaks under 95 % of ``bytes_limit`` and over its
    quarter (the benchmark's floor)."""
    job, _, _ = step
    peak = job.memory["peak_bytes"]
    assert 0.25 * V5E_BYTES_LIMIT < peak <= 0.95 * V5E_BYTES_LIMIT, peak
    # 12,598,753,792 (PR 68): 74.5 % of ``bytes_limit``
    assert 12.0e9 < peak < 13.2e9, peak


def test_the_scan_runs_once_a_layer_under_its_scope(step):
    """Two Mamba-1 layers: ``s6_scan_fwd`` twice (block remat keeps its
    output and the entering states, so none in front of a block's backward)
    and ``s6_scan_bwd`` twice, every call under ``s6`` and, by
    ``kernel_scopes``, under ``s6_scan``; the convolution's kernels under
    ``s6_conv``; three layers through the flash kernels, one under each
    kind's scope."""
    job, _, cfg = step
    program, kernels = job.program, job.program["kernels"]
    assert (program["s6_layers"], program["gmu_layers"],
            program["cross_attention_layers"], program["attention_layers"],
            program["window_attention_layers"],
            program["s6_chunks_per_sequence"],
            program["block_applications"]) == (2, 1, 1, 3, 1, 128, 3)
    assert (program["memory_bytes_per_sequence"],
            program["shared_kv_bytes_per_sequence"]) == (
                SEQ * 5120 * 2, 2 * SEQ * 1280 * 2)
    assert (kernels["s6_scan_fwd"], kernels["s6_scan_bwd"]) == (2, 2)
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (3, 3, 3)
    # forward and recompute, then backward
    assert (kernels["conv_silu_fwd"], kernels["conv_silu_bwd"]) == (4, 2)
    by_scope = {}
    for name, scope in program["kernel_scopes"].items():
        by_scope.setdefault(scope, []).append(program["scopes"][name])
    assert sorted(by_scope["s6_scan"]) == [
        ["backward", "s6"]] * 2 + [["forward", "s6"]] * 2
    assert len(by_scope["s6_conv"]) == 6
    for scope in ("attn_window", "attn_full", "attn_cross"):
        assert sorted(by_scope[scope]) == [
            ["backward", "attention"]] * 2 + [["forward", "attention"]], scope
    inner = set(program["subscopes"].values())
    assert {"s6_in", "s6_dt", "s6_out", "attn_diff"} <= inner
    found = {tuple(v) for v in program["scopes"].values()}
    assert {("forward", "s6"), ("backward", "s6"), ("recompute", "s6"),
            ("forward", "gmu"), ("backward", "gmu"),
            ("forward", "attention")} <= found


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_the_selective_scan_compiles_for_v5e(topo, grad):  # noqa: F811
    """The kernel pair alone at the cell's shapes: one sequence of 16,384,
    5,120 channels of 16 states, ``B`` and ``C`` read as scalars from SMEM."""
    from dlrover_tpu.ops.selective_scan import selective_scan

    def fwd(x, dt, A, Bm, Cm, D):
        return selective_scan(x, dt, A, Bm, Cm, D, backend="pallas")[0]

    one_chip = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    args = (sd((1, SEQ, 5120), bf16), sd((1, SEQ, 5120), f32),
            sd((5120, 16), f32), sd((1, SEQ, 16), bf16),
            sd((1, SEQ, 16), bf16), sd((5120,), f32))
    fn = jax.grad(lambda *a: _sq(fwd(*a)), argnums=tuple(range(6))) if (
        grad) else fwd
    compiled = jax.jit(fn).lower(*args).compile()
    found = acc.program_summary(compiled.as_text())["kernels"]
    assert found.get("s6_scan_fwd") == 1
    assert found.get("s6_scan_bwd", 0) == int(grad)

