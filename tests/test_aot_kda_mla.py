"""The Kimi Linear cell's step (``kimi_linear_48b_a3b-l5.train-16k-decayed``:
published layers 1-5 of Kimi-Linear-48B-A3B, 8 of 256 experts held, an eighth
of the vocabulary) compiled ahead of time for ONE described v5e at FULL depth
and published widths, from shapes, at one sequence of 16,384: that it fits,
and what the compiled text says of the two new kernel pairs — the delta rule
with a decay per key channel under ``kda/kda_scan``, forward once a layer
under block remat, and the flash kernels at 192-wide q and k over 128-wide
v.  A file of its own, so that the whole-depth compile runs beside
``tests/test_aot_compile.py``'s and not behind them.

A compile that passes is not a chip run: nothing here says anything about
results or times.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_aot_compile import (  # noqa: F401
    _our_kernels,
    _sq,
    _step_and_text,
    acc,
    topo,
)

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reported it (PR 21)
V5E_BYTES_LIMIT = 16_909_336_064
SEQ = 16384
bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def step(topo):  # noqa: F811
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=20480, n_layer=5, n_head=32, n_kv_head=32, d_model=2304,
        d_ff=9216, max_seq_len=SEQ, rms_eps=1e-5, remat_block=True,
        layer_types=("kda", "kda", "kda", "attention", "kda"),
        kda_heads=32, kda_d_head=128, kda_d_conv=4,
        q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope=False,
        num_experts=256, top_k=8, moe_every=1, first_k_dense=1,
        d_ff_expert=1024, n_shared_experts=1, router_score="sigmoid",
        routed_scaling=2.446, router_bias_rate=1e-3,
        balance_per_sequence=True, experts_held=8)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-4,
                             moe_z_weight=0.0, metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    loss.program_facts = llama.program_facts(cfg, SEQ)
    return (*_step_and_text(topo, loss, cfg, 1, SEQ), cfg)


def test_the_cell_fits_at_one_sequence_of_16384(step):
    """602.4 M parameters: 7.23 GB of state + 2.41 GB of gradients, and XLA's
    buffer assignment peaks under 95 % of ``bytes_limit`` and over its
    quarter (the benchmark's floor)."""
    job, _, _ = step
    peak = job.memory["peak_bytes"]
    assert 0.25 * V5E_BYTES_LIMIT < peak <= 0.95 * V5E_BYTES_LIMIT, peak
    # 14,508,422,144 (PR 61): 85.8 % of ``bytes_limit``
    assert 14.0e9 < peak < 15.0e9, peak


def test_each_rule_kernel_runs_once_a_layer_under_its_scope(step):
    """Four KDA layers: ``kda_chunk_fwd`` four times (block remat keeps what
    it put out, so none in front of a block's backward) and
    ``kda_chunk_bwd`` four, every call under ``kda`` and, by
    ``kernel_scopes``, under ``kda_scan``; the three convolutions and the
    gated norm of each layer under ``kda_conv`` and ``kda_gate``; one latent
    layer through the three flash kernels."""
    job, _, cfg = step
    program, kernels = job.program, job.program["kernels"]
    assert (program["kda_layers"], program["attention_layers"],
            program["kda_chunks_per_sequence"],
            program["block_applications"]) == (4, 1, 128, 1)
    assert (kernels["kda_chunk_fwd"], kernels["kda_chunk_bwd"]) == (4, 4)
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (1, 1, 1)
    # forward and recompute: 3 convolutions x 4 layers x 2; backward 3 x 4
    assert (kernels["conv_silu_fwd"], kernels["conv_silu_bwd"]) == (24, 12)
    assert (kernels["gated_norm_fwd"], kernels["gated_norm_bwd"]) == (8, 4)
    by_scope = {}
    for name, scope in program["kernel_scopes"].items():
        outer = program["scopes"][name]
        if outer[1] == "kda":
            by_scope.setdefault(scope, []).append(outer[0])
    assert sorted(by_scope) == ["kda_conv", "kda_gate", "kda_scan"]
    assert sorted(by_scope["kda_scan"]) == ["backward"] * 4 + ["forward"] * 4
    assert len(by_scope["kda_conv"]) == 36 and len(by_scope["kda_gate"]) == 12
    inner = set(program["subscopes"].values())
    assert {"kda_in", "kda_scan", "kda_out", "mla_q", "mla_kv",
            "mla_out"} <= inner
    found = {tuple(v) for v in program["scopes"].values()}
    assert {("forward", "kda"), ("backward", "kda"), ("recompute", "kda"),
            ("forward", "attention"), ("backward", "moe_experts")} <= found


def test_the_chunks_prologue_has_left_xla(step):
    """Under ``kda_scan`` XLA runs no cumulative sum and no ``rsqrt``: the
    running sum of ``g`` and the L2 norms of q and k are each chunk's own,
    inside ``kda_chunk_fwd`` / ``kda_chunk_bwd`` (whose bodies the compiled
    text does not spell out), forward, recomputed and backward; what it
    still runs there forms ``g`` (its ``softplus``) and ``beta``."""
    _, text, _ = step
    under, left = [], []
    for line in text.splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        if not op or "/kda_scan/" not in op.group(1) or (
                "tpu_custom_call" in line):
            continue
        under.append(op.group(1))
        if re.search(r"\b(reduce-window|rsqrt)\(", line) or re.search(
                r"cumsum|reduce_window|rsqrt", op.group(1)):
            left.append(line.strip()[:160])
    assert not left, left[:4]
    assert any("softplus" in name for name in under)


@pytest.mark.parametrize("raw", [False, True],
                         ids=["unit-operands", "raw-operands"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_the_per_channel_rule_compiles_for_v5e(topo, grad, raw):  # noqa: F811
    """The kernel pair alone at the cell's shapes: one sequence of 16,384,
    32 heads of 128, a float32 decay a channel; q and k normalised by the
    caller, and raw with the norms in the chunk (``unit_scales``, what the
    mixer hands over)."""
    from dlrover_tpu.ops.gated_delta import CHANNEL_CHUNK, gated_delta_chunked

    def fwd(q, k, v, g, beta):
        return gated_delta_chunked(
            q, k, v, g, beta, CHANNEL_CHUNK, backend="pallas",
            unit_scales=(128 ** -0.5, 1.0) if raw else None)

    fn = fwd
    if grad:
        def fn(*ops):
            return jax.grad(lambda *o: sum(_sq(x) for x in fwd(*o)[:2]),
                            argnums=range(5))(*ops)
    one_chip = SingleDeviceSharding(topo.devices[0])
    wide = (1, SEQ, 32, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (wide, bf16), (wide, bf16), (wide, bf16), (wide, f32),
        ((1, SEQ, 32), f32))]
    names = {"kda_chunk_fwd", "kda_chunk_bwd"}
    found = jax.jit(fn).lower(*args).compile()
    kernels = acc.program_summary(found.as_text())["kernels"]
    assert {k: n for k, n in kernels.items() if k in names} == (
        {"kda_chunk_fwd": 1, "kda_chunk_bwd": 1} if grad
        else {"kda_chunk_fwd": 1})


def test_flash_compiles_at_192_wide_keys_over_128_wide_values(
        topo):  # noqa: F811
    """Forward and both backward kernels at the latent layer's shapes: K's
    whole-sequence block at 16,384 x 192 (two lane tiles) beside V's at
    128."""
    from dlrover_tpu.ops.flash_attention import flash_attention

    def fn(q, k, v):
        return jax.grad(lambda *o: _sq(flash_attention(
            *o, backend="pallas")), argnums=range(3))(q, k, v)

    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((1, 32, SEQ, d), bf16, sharding=one_chip)
            for d in (192, 192, 128)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _our_kernels(compiled) == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    dq, dk, dv = jax.eval_shape(fn, *args)
    assert (dq.shape[-1], dk.shape[-1], dv.shape[-1]) == (192, 192, 128)
