"""The Trinity-Mini cell's step (``trinity_mini-l5.train-16k-decayed``: the
dense layer and one period of routed layers of Trinity-Mini, 16 of 128
experts held, an eighth of the vocabulary) compiled ahead of time for ONE
described v5e at FULL depth and published widths, from shapes, at one
sequence of 16,384: that it fits, and what the compiled text says of the
combination — the three flash kernels once a layer under each kind's scope,
the output norms' kernel under ``branch_norm``, the gate under ``attn_gate``.
A file of its own, so that the whole-depth compile runs beside
``tests/test_aot_compile.py``'s and not behind them.

A compile that passes is not a chip run: nothing here says anything about
results or times.
"""

import pytest
from test_aot_compile import _step_and_text, topo  # noqa: F401

#: ``bytes_limit`` of one v5e chip as ``memory_stats()`` reported it (PR 21)
V5E_BYTES_LIMIT = 16_909_336_064
SEQ = 16384


@pytest.fixture(scope="module")
def step(topo):  # noqa: F811
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=25024, n_layer=5, n_head=32, n_kv_head=4, d_model=2048,
        d_ff=6144, max_seq_len=SEQ, rms_eps=1e-5, remat_block=True,
        layer_types=("window_attention",) * 4 + ("attention",),
        sliding_window=2048,
        rotary_by_kind={"window_attention": llama.Rotary(theta=10000.0),
                        "attention": None},
        attn_head_dim=128, qk_norm=True, qk_norm_per_head=True,
        attn_output_gate=True, branch_norm=True,
        embedding_multiplier=2048.0 ** 0.5,
        num_experts=128, top_k=8, moe_every=1, first_k_dense=1,
        d_ff_expert=1024, n_shared_experts=1, router_score="sigmoid",
        routed_scaling=2.826, router_bias_rate=1e-3, experts_held=16)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, moe_aux_weight=0.0,
                             moe_z_weight=0.0, metrics=True)

    loss.rule_leaves = llama.rule_leaves(cfg)
    loss.program_facts = llama.program_facts(cfg, SEQ)
    return (*_step_and_text(topo, loss, cfg, 1, SEQ), cfg)


def test_the_cell_fits_at_one_sequence_of_16384(step):
    """705.5 M parameters: 8.47 GB of state + 2.82 GB of gradients, and XLA's
    buffer assignment peaks under 95 % of ``bytes_limit`` and over its
    quarter (the benchmark's floor)."""
    job, _, _ = step
    peak = job.memory["peak_bytes"]
    assert 0.25 * V5E_BYTES_LIMIT < peak <= 0.95 * V5E_BYTES_LIMIT, peak
    # 13,298,921,984 (AOT, PR 65): 78.6 % of ``bytes_limit``
    assert 13.0e9 < peak < 13.6e9, peak


def test_the_kernels_run_once_a_layer_under_the_scopes_of_their_kind(step):
    """Five attention layers through the three flash kernels once each
    (block remat keeps what the forward kernel put out), four under
    ``attn_window`` and one under ``attn_full``; ten output norms through
    ``rmsnorm_fwd`` under ``branch_norm`` going forward, the attention
    branch's five again recomputed (nothing in a block's backward reads the
    MLP branch's normed output: it is the block's last operation); the
    gate's multiply under ``attn_gate``; one layer without position in the
    journal."""
    job, _, _ = step
    program, kernels = job.program, job.program["kernels"]
    assert (program["window_attention_layers"], program["attention_layers"],
            program["unrotated_attention_layers"],
            program["block_applications"]) == (4, 5, 1, 5)
    assert (program["attn_window_pairs_per_sequence"],
            program["attn_full_pairs_per_sequence"]) == (
                31_458_304, 134_225_920)
    assert (kernels["flash_fwd"], kernels["flash_bwd_dq"],
            kernels["flash_bwd_dkv"]) == (5, 5, 5)
    by_scope = {}
    for name, scope in program["kernel_scopes"].items():
        by_scope.setdefault(scope, []).append(
            tuple(program["scopes"][name]))
    assert sorted(phase for phase, _ in by_scope["attn_window"]) == (
        ["backward"] * 8 + ["forward"] * 4)
    assert sorted(phase for phase, _ in by_scope["attn_full"]) == (
        ["backward"] * 2 + ["forward"])
    norms = by_scope["branch_norm"]
    assert {outer for _, outer in norms} == {
        "attention", "mlp", "moe_combine"}
    assert sum(phase == "forward" for phase, _ in norms) == 10
    assert [outer for phase, outer in norms if phase == "recompute"] == (
        ["attention"] * 5)
    inner = program["subscopes"]
    gate = {tuple(program["scopes"][name]) for name, scope in inner.items()
            if scope == "attn_gate"}
    assert gate and {outer for _, outer in gate} == {"attention"}
    assert {"forward", "backward"} <= {phase for phase, _ in gate}
