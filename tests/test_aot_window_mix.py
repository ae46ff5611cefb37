"""The window-and-full cell's step (``mellum2_12b_a2_5b-l8.train-16k-decayed``:
published layers 0-7 of Mellum2-12B-A2.5B, 8 of 64 experts held, an eighth of
the vocabulary) compiled ahead of time for ONE described v5e at FULL depth
and published widths, from shapes, at one and at two sequences of 16,384: the
number behind the cell's ``batch_sequences``, and what the compiled text says
of the two kinds of attention layer.  A file of its own, so that the two
whole-depth compiles run beside ``tests/test_aot_compile.py``'s and not
behind them.

A compile that passes is not a chip run: nothing here says anything about
results or times.
"""

import pytest
from test_aot_compile import _step_and_text, topo  # noqa: F401

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reported it (PR 21)
V5E_BYTES_LIMIT = 16_909_336_064
SEQ = 16384


def _cell_step(topo, sequences):  # noqa: F811
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=12288, n_layer=8, n_head=32, n_kv_head=4, d_model=2304,
        d_ff=7168, max_seq_len=SEQ, rms_eps=1e-6, remat_block=True,
        layer_types=(("window_attention",) * 3 + ("attention",)) * 2,
        sliding_window=1024,
        rotary_by_kind={
            "attention": llama.Rotary(
                theta=500000.0, factor=16.0,
                original_max_position_embeddings=8192, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.2772588722239782),
            "window_attention": llama.Rotary(theta=500000.0)},
        attn_head_dim=128, qk_norm=True, qk_norm_per_head=True,
        num_experts=64, top_k=8, moe_every=1, d_ff_expert=896,
        norm_topk_prob=True, balance_all_k=True, experts_held=8)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=1e-3,
                             moe_z_weight=0.0, metrics=True)

    loss.program_facts = llama.program_facts(cfg, SEQ)
    return (*_step_and_text(topo, loss, cfg, sequences, SEQ), cfg)


@pytest.fixture(scope="module")
def step_at_one(topo):  # noqa: F811
    return _cell_step(topo, 1)


def test_the_cell_fits_at_one_sequence_of_16384(step_at_one):
    """One sequence of 16,384: state and gradients are 9.99 GB of the
    chip's 16.9, and XLA's buffer assignment peaks under 95 % of
    ``bytes_limit`` and over its quarter (the benchmark's floor)."""
    job, _, _ = step_at_one
    peak = job.memory["peak_bytes"]
    assert 0.25 * V5E_BYTES_LIMIT < peak <= 0.95 * V5E_BYTES_LIMIT, peak
    # 12,763,367,936 (PR 59): 75.5 % of ``bytes_limit``
    assert 12.4e9 < peak < 13.1e9, peak


def test_the_cell_runs_both_attention_kinds_under_their_scopes(step_at_one):
    """Six window layers and two full ones through the same three flash
    kernels (forward once a layer under block remat, which keeps the
    kernel's outputs), every call named by its kind's scope INSIDE the
    block's ``attention`` — in the forward pass and in the backward's two
    kernels —, every layer routed with 8 of 64 experts held."""
    job, _, cfg = step_at_one
    program, kernels = job.program, job.program["kernels"]
    assert (program["attention_layers"], program["window_attention_layers"],
            program["block_applications"]) == (8, 6, 8)
    assert (program["attn_full_pairs_per_sequence"],
            program["attn_window_pairs_per_sequence"]) == (
                134_225_920, 16_253_440)
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (8, 8, 8)
    by_scope = {}
    for name, scope in program["kernel_scopes"].items():
        phase = program["scopes"][name]
        assert phase[1] == "attention", (name, phase)
        by_scope.setdefault(scope, []).append(phase[0])
    assert sorted(by_scope) == ["attn_full", "attn_window"]
    assert sorted(by_scope["attn_window"]) == (
        ["backward"] * 12 + ["forward"] * 6)
    assert sorted(by_scope["attn_full"]) == (
        ["backward"] * 4 + ["forward"] * 2)
    # the tables are built once a kind, under a scope of their own
    assert ["forward", "rotary"] in program["scopes"].values() or (
        ["other", "rotary"] in program["scopes"].values())
    found = {tuple(v) for v in program["scopes"].values()}
    assert {("forward", "attention"), ("backward", "attention"),
            ("recompute", "attention"), ("forward", "moe_experts"),
            ("backward", "moe_experts")} <= found
    inner = set(program["subscopes"].values())
    assert {"attn_window", "attn_full"} <= inner


def test_two_sequences_of_16384_do_not_fit(topo):  # noqa: F811
    """The next batch is refused by the compiler itself: "Used 15.86G of
    15.75G hbm" (PR 59) — no free share to read, so the cell runs one
    sequence."""
    with pytest.raises(Exception, match="Ran out of memory in memory space "
                                        "hbm"):
        _cell_step(topo, 2)
