"""``ops.gated_norm``: the gated RMS norm over short groups of lanes.

The Pallas pair ``gated_norm_fwd`` / ``gated_norm_bwd`` in interpret mode
against float32 references of its own arithmetic — both orders of gate and
norm, groups of one and of four lane tiles, float32 and bfloat16 in, a row
count the block of rows does not divide — then through ``per_shard`` on a
two-device mesh, and inside the two mixers that call it
(``llama._gdn_mixer`` a head a group, ``llama._ssm_mixer`` in more groups
than one) against the same mixers on the op's ``jax.numpy`` form.  What a
whole model lowers to where the op is NOT on the path (one group, no such
mixer) is pinned at the end.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_remat_keeps_flash import _kernel_calls  # noqa: I100 - shared

from dlrover_tpu.models import llama
from dlrover_tpu.ops import gated_norm as gn
from dlrover_tpu.parallel.mesh import MeshSpec

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-6
#: two sequences of 100 rows: 200 rows under a tile of 192 (one step at a
#: group of 128 lanes, whose step would be 256 rows; three of 64 at 512), so
#: the second tile holds 8 rows and 184 past the array's end; two blocks of
#: 512 lanes
SHAPE = (2, 100, 1024)

kernels = functools.partial(gn.gated_norm, backend="pallas", interpret=True)


def _operands(x_dtype, z_dtype, shape=SHAPE, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ((3.0 * jax.random.normal(keys[0], shape)).astype(x_dtype),
            jax.random.normal(keys[1], shape).astype(z_dtype),
            1.0 + 0.3 * jax.random.normal(keys[2], shape[-1:]),
            jax.random.normal(keys[3], shape))


def _by_hand(x, z, gain, group, gate_first, activation="silu"):
    """The op in float64 numpy, group by group."""
    x, z, gain = (np.asarray(a.astype(F32), np.float64) for a in (x, z, gain))
    s = (z if activation == "silu" else 1.0) / (1.0 + np.exp(-z))
    v = x * s if gate_first else x
    out = np.empty_like(v)
    for at in range(0, v.shape[-1], group):
        part = v[..., at:at + group]
        out[..., at:at + group] = part / np.sqrt(
            np.mean(part * part, -1, keepdims=True) + EPS)
    return out * gain if gate_first else out * gain * s


def _rel(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _grads(fn, x, z, gain, cot, **kw):
    def scalar(x, z, gain):
        y = fn(x, z, gain, **kw)
        return jnp.sum(y.astype(F32) * cot), y
    return jax.value_and_grad(scalar, (0, 1, 2), has_aux=True)(x, z, gain)


@pytest.mark.parametrize("activation", sorted(gn.ACTIVATIONS))
@pytest.mark.parametrize("x_dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [128, 512])
@pytest.mark.parametrize("gate_first", [False, True],
                         ids=["norm_then_gate", "gate_then_norm"])
def test_the_kernels_equal_the_float32_reference(gate_first, group, x_dtype,
                                                 activation):
    """Output, ``dx``, ``dz`` and ``dgain`` at float32 tolerances, with a
    float32 gate so that no rounding to bfloat16 hides the arithmetic.  The
    output element by element against float64: a group's mean square that
    had passed through bfloat16 (2^-9) would show as 1e-3 here."""
    x, z, gain, cot = _operands(x_dtype, F32)
    assert gn._tile(200, SHAPE[-1], group, (x_dtype, F32)) == (
        192, 512, {128: 192, 512: 64}[group])
    kw = dict(group=group, eps=EPS, gate_first=gate_first,
              activation=activation)
    (_, y), (dx, dz, dgain) = _grads(kernels, x, z, gain, cot, **kw)
    (_, y_ref), want = _grads(gn.gated_norm, x, z, gain, cot,
                              backend="reference", **kw)
    assert y.dtype == F32 and dx.dtype == x_dtype and dz.dtype == F32
    assert dgain.dtype == gain.dtype and dgain.shape == gain.shape
    by_hand = _by_hand(x, z, gain, group, gate_first, activation)
    big = np.abs(by_hand) > 1e-3
    assert np.max(np.abs(np.asarray(y, np.float64) / np.where(
        big, by_hand, 1.0) - 1.0)[big]) < 1e-6
    assert _rel(y, y_ref) < 2e-7
    # dx leaves in x's dtype: a rounding to bfloat16 where x is
    assert _rel(dx, want[0]) < (2e-7 if x_dtype == F32 else 1e-2)
    assert _rel(dz, want[1]) < 1e-6
    assert _rel(dgain, want[2]) < 1e-6


@pytest.mark.parametrize("group,gate_first,activation", [
    (128, False, "silu"), (512, True, "silu"), (128, False, "sigmoid")],
    ids=["delta_rule", "state_space", "delta_rule_per_channel"])
def test_the_kernels_in_the_cells_dtypes(group, gate_first, activation):
    """float32 ``x`` (what the rule's and the scan's kernels put out) and a
    bfloat16 gate: ``y`` and ``dz`` leave in bfloat16, one rounding of the
    float32 result — which the reference's own rounding meets on all but a
    few elements that sit on a bfloat16 tie."""
    x, z, gain, cot = _operands(F32, BF16, seed=1)
    kw = dict(group=group, eps=EPS, gate_first=gate_first,
              activation=activation)
    (_, y), (dx, dz, dgain) = _grads(kernels, x, z, gain, cot, **kw)
    (_, y_ref), want = _grads(gn.gated_norm, x, z, gain, cot,
                              backend="reference", **kw)
    assert y.dtype == BF16 and dz.dtype == BF16 and dx.dtype == F32
    differ = np.asarray(y != y_ref)
    assert differ.mean() < 1e-3
    assert _rel(y.astype(F32), y_ref.astype(F32)) < 1e-4
    assert _rel(dx, want[0]) < 2e-7
    assert _rel(dz.astype(F32), want[1].astype(F32)) < 1e-4
    assert _rel(dgain, want[2]) < 1e-6


@pytest.mark.parametrize("activation", sorted(gn.ACTIVATIONS))
def test_the_reference_is_the_arithmetic_by_hand(activation):
    for gate_first, group in [(False, 128), (True, 512), (True, 1024)]:
        x, z, gain, _ = _operands(F32, F32)
        got = gn.gated_norm(x, z, gain, group=group, eps=EPS,
                            gate_first=gate_first, activation=activation)
        assert _rel(got, _by_hand(
            x, z, gain, group, gate_first, activation)) < 1e-6
    with pytest.raises(ValueError, match="activation='tanh'"):
        gn.gated_norm(x, z, gain, group=128, eps=EPS, gate_first=False,
                      activation="tanh")


@pytest.mark.parametrize("why,rows,width,group,dtypes", [
    ("a group that is no whole lane tile", 256, 1024, 64, (F32, BF16)),
    ("a width the group does not divide", 256, 1024 + 128, 512, (F32, BF16)),
    ("fewer rows than a tile of bfloat16", 12, 1024, 128, (F32, BF16)),
    ("float16", 256, 1024, 128, (jnp.float16, BF16)),
])
def test_what_the_kernels_do_not_tile_runs_the_numpy_form(
        why, rows, width, group, dtypes):
    assert gn._tile(rows, width, group, dtypes) is None, why
    if width % group == 0:
        x, z, gain, _ = _operands(*dtypes, shape=(2, rows // 2, width))
        jaxpr = jax.make_jaxpr(functools.partial(
            kernels, group=group, eps=EPS, gate_first=True))(x, z, gain)
        assert not _kernel_calls(jaxpr.jaxpr)


def test_the_residuals_are_the_inputs_alone():
    """The backward recomputes the inverse RMS: a checkpoint around the op
    keeps ``x``, ``z`` and the gain and nothing the forward made."""
    from jax._src.ad_checkpoint import saved_residuals

    x, z, gain, _ = _operands(F32, BF16)
    kept = saved_residuals(
        lambda x, z, gain: jnp.sum(kernels(
            x, z, gain, group=128, eps=EPS, gate_first=False).astype(F32)),
        x, z, gain)
    assert all("from the argument" in why for _, why in kept), kept
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(kernels(x, z, gain, group=128, eps=EPS,
                                  gate_first=False).astype(F32))))(x).jaxpr)
    assert calls == {"gated_norm_fwd": 1, "gated_norm_bwd": 1}


def test_the_kernels_run_once_per_batch_shard_of_the_mesh_in_scope():
    """Under a ``dp = 2`` mesh the pair runs in a ``shard_map`` over the
    batch dim (``ops/per_shard.py``): values and gradients those of one
    device — the gain's summed over the shards — and the output still
    sharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    x, z, gain, cot = _operands(F32, BF16, seed=2)
    kw = dict(group=128, eps=EPS, gate_first=False)
    (want, y_want), g_want = _grads(kernels, x, z, gain, cot, **kw)
    rows = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))
    with jax.set_mesh(mesh):
        (got, y_got), g_got = jax.jit(functools.partial(
            _grads, kernels, **kw))(
                jax.device_put(x, rows), jax.device_put(z, rows), gain, cot)
    assert y_got.sharding.spec[0] == ("dp", "fsdp")
    assert g_got[0].sharding.spec[0] == ("dp", "fsdp")
    # a shard is 100 rows: one tile of 64 and 36 rows of the next
    np.testing.assert_array_equal(np.asarray(y_got), np.asarray(y_want))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(g_got[:2], g_want[:2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert _rel(g_got[2], g_want[2]) < 1e-6


# -- inside the mixers --------------------------------------------------------


def _mixer_case(kind, dtype):
    """A mixer whose gated norm the kernels tile, its leaves and a stream:
    the delta rule's with two value heads of 128 under one key head, the
    state-space one's with 256 columns in two groups of 128."""
    if kind == "gdn":
        from test_llama_gdn import B, D, _gdn_leaves

        cfg, leaves, _ = _gdn_leaves(gdn_k_heads=1, gdn_v_heads=2,
                                     gdn_d_head=128, max_seq_len=64,
                                     dtype=dtype)
        mixer, d = llama._gdn_mixer, D
    else:
        from test_llama_ssm import B, _hybrid

        cfg = _hybrid(d_model=64, mamba_n_heads=4, mamba_d_head=64,
                      mamba_n_groups=2, mamba_d_state=16, mamba_chunk_size=32,
                      max_seq_len=64, dtype=dtype)
        assert cfg.mamba_d_inner // cfg.mamba_n_groups == 128
        leaves = llama._init_ssm(jax.random.PRNGKey(0), cfg)
        mixer, d = llama._ssm_mixer, 64
    leaves = dict(leaves, norm=leaves["norm"] + 0.3 * jnp.cos(
        jnp.arange(leaves["norm"].shape[0], dtype=F32)))
    u = jax.random.normal(jax.random.PRNGKey(5), (B, 64, d)).astype(dtype)
    return mixer, cfg, leaves, u


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["gdn", "ssm"])
def test_a_mixer_through_the_kernels_equals_the_numpy_form(
        kind, dtype, monkeypatch):
    """The mixer with the pair in interpret mode against the mixer on the
    op's ``jax.numpy`` form: the output, the stream's gradient and every
    leaf's — the gain's among them, which the delta rule's mixer tiles
    over its heads (``A_log``'s gradient is a sum over the positions that
    cancels: 3e-5 at float32)."""
    mixer, cfg, leaves, u = _mixer_case(kind, dtype)

    def loss(leaves, u):
        out, _ = mixer(u, leaves, cfg)
        return jnp.sum(jnp.sin(out.astype(F32))), out

    run = lambda: jax.value_and_grad(  # noqa: E731
        loss, (0, 1), has_aux=True)(leaves, u)
    (_, want), want_grads = run()
    monkeypatch.setattr(llama, "gated_norm", kernels)
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(
        lambda l_, u_: loss(l_, u_)[0]))(leaves, u).jaxpr)
    assert calls == {"gated_norm_fwd": 1, "gated_norm_bwd": 1}
    (_, got), grads = run()
    tol = 1e-4 if dtype == F32 else 2e-2
    assert got.dtype == dtype
    assert _rel(got.astype(F32), want.astype(F32)) < (
        2e-5 if dtype == F32 else tol)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    flat_w, tree_w = jax.tree_util.tree_flatten(want_grads)
    assert tree == tree_w
    for (path, g), w in zip(flat, flat_w):
        assert g.dtype == w.dtype
        assert _rel(g.astype(F32), w.astype(F32)) < tol, (
            jax.tree_util.keystr(path))


def test_one_group_keeps_the_rmsnorm_path(monkeypatch):
    """``mamba_n_groups`` 1 (Granite's nine layers) never meets the op: the
    choice is the group's width against the whole width."""
    from test_llama_ssm import B, _hybrid

    def refuse(*a, **k):
        raise AssertionError("gated_norm called at one group")

    monkeypatch.setattr(llama, "gated_norm", refuse)
    cfg = _hybrid()
    assert cfg.mamba_n_groups == 1
    ssm = llama._init_ssm(jax.random.PRNGKey(0), cfg)
    out, _ = llama._ssm_mixer(
        jnp.ones((B, cfg.max_seq_len, cfg.d_model)), ssm, cfg)
    assert bool(jnp.isfinite(out).all())


# -- where the op is not on the path, the step is the parent's ---------------

#: sha256 of the StableHLO text (no source locations) that
#: ``jit(value_and_grad(loss_fn))`` lowers to on the CPU backend, block remat
#: on — computed AT THE PARENT of the PR that brought ``ops.gated_norm``
#: (commit 21f5c5f, jax 0.9.0): a dense model (the Mistral cells' kind) and
#: a hybrid of state-space layers in ONE group and attention (Granite's
#: kind), neither of which runs the op.  A later PR that changes the model's
#: traced operations on purpose computes them anew on ITS parent and says so.
PARENT_HLO_SHA256 = {
    "dense":
        "e9dc64123c1cb38764d5eb3d52f99dc0a554916fc3b748f3c77ce88674be6aed",
    "one_group_hybrid":
        "0ee7157d631dbe365250703e93bd6df8d8777b82ba8c9d30c5dfb52b3a79b24b",
}


def _pinned_cfg(kind):
    if kind == "dense":
        return llama.LlamaConfig.tiny(max_seq_len=64, remat_block=True)
    from test_llama_ssm import _hybrid

    return _hybrid(remat_block=True)


@pytest.mark.parametrize("kind", sorted(PARENT_HLO_SHA256))
def test_a_model_without_the_op_lowers_to_the_parents_text(kind):
    cfg = _pinned_cfg(kind)
    tokens = jnp.arange(cfg.max_seq_len + 1)[None] % cfg.vocab_size
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg))).lower(
            shapes).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_HLO_SHA256[kind]
