"""``ops.gated_delta`` with a decay per key CHANNEL (``g [B, S, H, Dk]``,
Kimi Delta Attention): the chunked ``jax.numpy`` form against the sequential
form, forward and all five gradients, over decays from ``e^-1e-3`` to
``e^-20`` a token in single channels; ``g`` constant over a head's channels
against the scalar op; the kernel pair ``kda_chunk_fwd`` / ``kda_chunk_bwd``
in interpret mode against the ``jax.numpy`` form; what block remat keeps of
it; the shapes the kernels do not tile; and the chunk's own prologue — the
running sum of ``g`` down a chunk's rows and, with ``unit_scales``, the L2
norms of the raw q and k — against ``jnp.cumsum`` and the caller's norms."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_ops import _eqns  # noqa: I100 - shared
from test_remat_keeps_flash import _kernel_calls

from dlrover_tpu.ops import gated_delta as gd

F32, BF16 = jnp.float32, jnp.bfloat16
NAMES = ("q", "k", "v", "g", "beta")


def _operands(seed=0, b=1, s=160, h=2, dk=128, dv=128, dtype=F32,
              slow=1e-3, fast=20.0):
    """Unit q and k (q scaled), normal v, ``beta = sigmoid(N(0, 1))`` and a
    rate a CHANNEL and position, log-uniform between ``slow`` and ``fast``:
    in one head some channels keep nearly all of the state over a chunk and
    some underflow float32 within five positions."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
    return (
        (unit(jax.random.normal(keys[0], (b, s, h, dk))) * dk ** -0.5
         ).astype(dtype),
        unit(jax.random.normal(keys[1], (b, s, h, dk))).astype(dtype),
        jax.random.normal(keys[2], (b, s, h, dv)).astype(dtype),
        -jnp.exp(jax.random.uniform(keys[3], (b, s, h, dk), F32,
                                    np.log(slow), np.log(fast))),
        jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h))))


def _rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _scalar_loss(fn):
    def loss(*ops):
        out = fn(*ops)
        return jnp.sum(jnp.sin(out[0])) + 0.1 * jnp.sum(jnp.square(out[1]))
    return loss


def _chunked(chunk, backend="reference", **kwargs):
    return lambda *ops: gd.gated_delta_chunked(
        *ops, chunk, backend=backend, interpret=True, **kwargs)


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_the_chunked_form_equals_the_recurrence(chunk):
    """A sequence the chunk does not divide (160 = 1.25 x 128), decays
    ``e^-1e-3`` to ``e^-20`` a token: output, final state and the five
    gradients at float32's noise, and nothing infinite or NaN although a
    chunk's least decay underflows to 0."""
    ops = _operands()
    out, state, decay_min = jax.jit(_chunked(chunk))(*ops)
    want_out, want_state = gd.gated_delta_sequential(*ops)
    assert _rel(out, want_out) < 2e-5 and _rel(state, want_state) < 2e-5
    assert float(decay_min) == 0.0  # e^-20 x 16 positions and more
    got = jax.jit(jax.grad(_scalar_loss(_chunked(chunk)), range(5)))(*ops)
    want = jax.jit(jax.grad(
        _scalar_loss(gd.gated_delta_sequential), range(5)))(*ops)
    for name, g, w in zip(NAMES, got, want):
        assert bool(jnp.isfinite(g).all()), name
        assert g.shape == w.shape and _rel(g, w) < 5e-5, name


def test_no_exponent_that_is_evaluated_is_positive(monkeypatch):
    """Every ``exp`` of a chunk (``_channel_chunk``, what both forms run)
    sees an argument <= 0, the filler included, at decays whose plain
    ``exp(-Gamma)`` would overflow: two a level of the halving, and the
    three of the rule's own."""
    seen = []
    real = jnp.exp

    def watched(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    q, k, v, g, beta = _operands(s=128, h=1, slow=5.0, fast=20.0)
    gam = jnp.cumsum(g[0, :, 0], axis=0)
    assert float(jnp.min(gam)) < -1500.0  # exp(-gam) is inf in float32
    monkeypatch.setattr(gd.jnp, "exp", watched)
    out, state = gd._channel_chunk(
        q[0, :, 0], k[0, :, 0], v[0, :, 0], g[0, :, 0], beta[0, :, :1],
        jnp.ones((128, 128), F32), F32, gd.unit_lower_inverse)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    assert len(seen) == 2 * 7 + 3 and max(seen) <= 0.0


@pytest.mark.parametrize("chunk", [64, 128])
def test_a_decay_constant_over_a_heads_channels_is_the_scalar_rule(chunk):
    """``g [B, S, H]`` broadcast over the channels: the sequential form bit
    for bit, the chunked form to float32's noise against the scalar op's
    own chunked form."""
    q, k, v, g, beta = _operands(seed=1, fast=3.0)
    per_head = g[..., 0]
    wide = jnp.broadcast_to(per_head[..., None], g.shape)
    a = gd.gated_delta_sequential(q, k, v, per_head, beta)
    b = gd.gated_delta_sequential(q, k, v, wide, beta)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    got = _chunked(chunk)(q, k, v, wide, beta)
    want = _chunked(chunk)(q, k, v, per_head, beta)
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-5)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_the_kernel_pair_equals_the_numpy_form(dtype):
    """``kda_chunk_fwd`` / ``kda_chunk_bwd`` in interpret mode against the
    ``jax.numpy`` form at the same chunk: the same function computes a chunk
    in both, so float32 differs by the inverse's order of operations alone
    and bfloat16 operands by nothing more."""
    ops = _operands(seed=2, s=256, dtype=dtype)
    kernels, numpy_form = _chunked(128, "pallas"), _chunked(128)
    got, want = kernels(*ops), numpy_form(*ops)
    assert got[0].dtype == F32 and got[1].shape == (1, 2, 128, 128)
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5
    assert float(got[2]) == float(want[2])
    g_got = jax.grad(_scalar_loss(kernels), range(5))(*ops)
    g_want = jax.grad(_scalar_loss(numpy_form), range(5))(*ops)
    for name, g, w, op in zip(NAMES, g_got, g_want, ops):
        assert g.dtype == op.dtype and g.shape == op.shape, name
        assert _rel(g.astype(F32), w.astype(F32)) < (
            1e-4 if dtype == F32 else 1e-2), name


def test_the_kernels_run_once_each_and_name_what_remat_keeps():
    ops = _operands(seed=3, s=256, dtype=BF16)
    kernels = _chunked(128, "pallas")
    assert _kernel_calls(jax.make_jaxpr(kernels)(*ops).jaxpr) == {
        "kda_chunk_fwd": 1}
    grad = jax.grad(_scalar_loss(kernels), range(5))
    assert _kernel_calls(jax.make_jaxpr(grad)(*ops).jaxpr) == {
        "kda_chunk_fwd": 1, "kda_chunk_bwd": 1}
    # under a policy that keeps the three names the forward kernel does not
    # run again in front of the backward
    kept = jax.checkpoint(
        _scalar_loss(kernels),
        policy=jax.checkpoint_policies.save_only_these_names(
            *gd.CHANNEL_SAVED_NAMES))
    assert _kernel_calls(jax.make_jaxpr(jax.grad(kept, range(5)))(
        *ops).jaxpr) == {"kda_chunk_fwd": 1, "kda_chunk_bwd": 1}
    assert gd.CHANNEL_SAVED_NAMES == ("kda_out", "kda_state", "kda_entering")
    assert not set(gd.CHANNEL_SAVED_NAMES) & set(gd.SAVED_NAMES)


@pytest.mark.parametrize("why,chunk,dk", [
    ("a chunk that is not the kernels'", 64, 128),
    ("a head that is no whole lane tile", 128, 64),
])
def test_what_the_kernels_do_not_tile_runs_the_numpy_form(why, chunk, dk):
    ops = _operands(seed=4, s=128, dk=dk, dv=dk)
    jaxpr = jax.make_jaxpr(functools.partial(
        gd.gated_delta_chunked, chunk=chunk, backend="pallas",
        interpret=True))(*ops)
    assert not _kernel_calls(jaxpr.jaxpr), why


def test_the_scalar_rules_kernels_are_as_they_were():
    """A decay a head still takes ``gdn_chunk_fwd`` / ``gdn_chunk_bwd``."""
    q, k, v, g, beta = _operands(seed=5, s=128, dtype=BF16)
    grad = jax.grad(_scalar_loss(lambda *ops: gd.gated_delta_chunked(
        *ops, 64, backend="pallas", interpret=True)), range(5))
    assert _kernel_calls(jax.make_jaxpr(grad)(
        q, k, v, g[..., 0], beta).jaxpr) == {
            "gdn_chunk_fwd": 1, "gdn_chunk_bwd": 1}


# -- the halving's reference rows: moves of Gamma's rows, no product ----------


def _moved(form, fn, x, cot):
    """``(fn(roll, x), the cotangent of x from cot)`` of a function that
    moves ``x``'s rows by ``roll``: by the ``jax.numpy`` form's moves or, in
    a kernel in interpret mode, by the kernels'."""
    def rows(roll, x, cot):
        out, pull = jax.vjp(functools.partial(fn, roll), x)
        return out, pull(cot)[0]

    if form == "numpy":
        return jax.jit(functools.partial(rows, gd._rolled_rows))(x, cot)
    from jax.experimental import pallas as pl

    def kernel(x_ref, cot_ref, out_ref, d_ref):
        out_ref[...], d_ref[...] = rows(
            gd._rotated_sublanes, x_ref[...], cot_ref[...])
    return pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct(x.shape, F32)] * 2,
        interpret=True)(x, cot)


def _level_rows(form, level, gam, cot):
    """``(the reference rows of block size 2^level, the cotangent of gam
    from cot on those rows alone)``."""
    return _moved(form, lambda roll, g: [
        r for _, r in gd._halving_references(g, roll)][level], gam, cot)


@pytest.mark.parametrize("form", ["numpy", "interpret"])
@pytest.mark.parametrize("level", range(7))
def test_the_reference_rows_are_moved_not_multiplied(level, form):
    """``ref[i] = gam[(i & ~(2 b - 1)) | (b - 1)]`` bit for bit at every
    block size of a chunk of 128, and the cotangent the sum of a block's
    ``2 b`` rows, placed on its reference row."""
    b, n = 2 ** level, gd.CHANNEL_CHUNK
    keys = jax.random.split(jax.random.PRNGKey(level), 2)
    # decays that underflow beside ones that do not, as a chunk's Gamma has
    gam = -jnp.cumsum(jnp.exp(jax.random.uniform(
        keys[0], (n, 128), F32, np.log(1e-3), np.log(20.0))), axis=0)
    cot = jax.random.normal(keys[1], (n, 128), F32)
    at = (np.arange(n) & ~(2 * b - 1)) | (b - 1)
    ref, d_gam = _level_rows(form, level, gam, cot)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(gam)[at])
    want = np.zeros((n, 128), np.float64)
    np.add.at(want, at, np.asarray(cot, np.float64))
    assert not np.asarray(d_gam)[np.setdiff1d(np.arange(n), at)].any()
    assert _rel(d_gam, want) < 1e-6


@pytest.mark.parametrize("form", ["numpy", "interpret"])
@pytest.mark.parametrize("n", [16, 128])
def test_the_running_sum_of_a_chunk_is_cumsum(n, form):
    """``_running_sum`` of a chunk's ``g`` against ``cumsum`` in float64,
    channels that underflow float32 within the chunk beside ones that keep
    nearly all, and its cotangent, the running sum from the chunk's end:
    both by turns of the rows, at every chunk size the forms run."""
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    # a rate a channel, e^-1e-3 to e^-20 a token, half to one and a half of
    # it a position
    g = -jnp.exp(jax.random.uniform(
        keys[0], (1, 128), F32, np.log(1e-3), np.log(20.0))
    ) * jax.random.uniform(keys[2], (n, 128), F32, 0.5, 1.5)
    cot = jax.random.normal(keys[1], (n, 128), F32)
    gam, d_g = _moved(form, gd._running_sum, g, cot)
    want = np.cumsum(np.asarray(g, np.float64), axis=0)
    assert want[-1].min() < -100.0 and want[-1].max() > -1.0
    assert _rel(gam, want) < 1e-6
    assert float(np.max(np.abs(np.asarray(gam) - want) / np.abs(want))) < 1e-6
    assert _rel(d_g, np.cumsum(
        np.asarray(cot, np.float64)[::-1], axis=0)[::-1]) < 1e-6


# -- the L2 norms of q and k inside the chunk (``unit_scales``) ---------------

SCALES = (128 ** -0.5, 1.0)


def _raw_operands(dtype, **kwargs):
    """:func:`_operands` with q and k as a convolution puts them out: rows
    of lengths from a tenth to ten."""
    q, k, v, g, beta = _operands(dtype=F32, **kwargs)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    long = lambda key, x: (x / jnp.linalg.norm(  # noqa: E731
        x, axis=-1, keepdims=True) * jnp.exp(jax.random.uniform(
            key, x.shape[:-1] + (1,), F32, np.log(0.1), np.log(10.0)))
    ).astype(dtype)
    return long(keys[0], q), long(keys[1], k), v.astype(dtype), g, beta


def _on_unit_rows(fn):
    """``fn`` on q and k normalised as ``models.llama._kda_mixer`` did in
    front of the op (float32, rounded to the operands' dtype), a function
    of the RAW q and k."""
    def unit(a, scale):
        x = a.astype(F32)
        return (x * (jax.lax.rsqrt(jnp.sum(
            jnp.square(x), axis=-1, keepdims=True) + 1e-6) * scale)).astype(
                a.dtype)

    return lambda q, k, *rest: fn(
        unit(q, SCALES[0]), unit(k, SCALES[1]), *rest)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_raw_q_and_k_with_unit_scales_are_the_normalised_operands(
        dtype, backend):
    """The op on raw q and k with ``unit_scales`` against the op on
    operands normalised in front of it: ``o``, the final state, the least
    decay and all five gradients, those of q and k with respect to the RAW
    rows on both sides (in bfloat16 the chunk rounds their cotangent once,
    the caller's norm twice)."""
    ops = _raw_operands(dtype, seed=6, s=256)
    inside = _chunked(128, backend, unit_scales=SCALES)
    outside = _on_unit_rows(_chunked(128, backend))
    got, want = inside(*ops), outside(*ops)
    tol = 2e-5 if dtype == F32 else 1e-2
    assert _rel(got[0], want[0]) < tol and _rel(got[1], want[1]) < tol
    assert float(got[2]) == float(want[2])
    g_got = jax.grad(_scalar_loss(inside), range(5))(*ops)
    g_want = jax.grad(_scalar_loss(outside), range(5))(*ops)
    for name, g, w, op in zip(NAMES, g_got, g_want, ops):
        assert g.dtype == op.dtype and g.shape == op.shape, name
        assert bool(jnp.isfinite(g.astype(F32)).all()), name
        assert _rel(g.astype(F32), w.astype(F32)) < tol, name


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_padded_rows_of_zeros_stay_zero_under_the_chunks_norm(backend):
    """160 positions in chunks of 128: the 96 padded rows of q and k are
    zeros, ``0 * rsqrt(1e-6)`` is 0, and the answer is the recurrence's on
    the 160 — output, state and gradients, nothing NaN."""
    ops = _raw_operands(F32, seed=8, s=160)
    inside = _chunked(128, backend, unit_scales=SCALES)
    sequential = _on_unit_rows(gd.gated_delta_sequential)
    out, state, _ = inside(*ops)
    want_out, want_state = sequential(*ops)
    assert out.shape == want_out.shape
    assert _rel(out, want_out) < 2e-5 and _rel(state, want_state) < 2e-5
    got = jax.grad(_scalar_loss(inside), range(5))(*ops)
    want = jax.grad(_scalar_loss(sequential), range(5))(*ops)
    for name, g, w in zip(NAMES, got, want):
        assert bool(jnp.isfinite(g).all()), name
        assert g.shape == w.shape and _rel(g, w) < 5e-5, name


def test_the_numpy_form_rounds_its_unit_rows_by_reduce_precision():
    """Two ``reduce_precision`` in the ``jax.numpy`` form's chunk and no
    conversion to bfloat16 and back, which a TPU's XLA drops in some uses
    of the value and not in others (``dg`` of a fast channel 90 times its
    size on the chip; builder, PR 67); the same bits as the conversion."""
    chunk = functools.partial(gd._channel_chunk, dt=BF16,
                              inverse=gd.unit_lower_inverse,
                              unit_scales=SCALES)
    names = [e.primitive.name for e in _eqns(jax.make_jaxpr(chunk)(*(
        jnp.zeros(s, F32) for s in ((128, 128),) * 4 + (
            (128, 1), (128, 128)))).jaxpr)]
    assert names.count("reduce_precision") == 2
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 1e3
    np.testing.assert_array_equal(
        np.asarray(gd._rounded(x, BF16, False)),
        np.asarray(gd._rounded(x, BF16, True)))


def test_unit_scales_is_the_per_channel_rules_alone():
    q, k, v, g, beta = _operands(seed=9, s=128)
    with pytest.raises(ValueError, match="unit_scales"):
        gd.gated_delta_chunked(q, k, v, g[..., 0], beta, 64,
                               unit_scales=SCALES)


@pytest.mark.parametrize("unit_scales", [None, SCALES],
                         ids=["unit-operands", "raw-operands"])
def test_no_product_of_a_chunk_picks_rows(unit_scales):
    """Every product of ``_channel_chunk`` contracts operands that come
    from the chunk's inputs — none takes a 0/1 matrix built from iotas —
    and the MXU passes of a chunk of 128, as ``tools/gated_delta_bench.py
    --channel`` counts them, are 113 forward and 255 through ``jax.vjp``:
    the chunk's prologue, the running sum and the norms, adds no product."""
    from jax.extend.core import Var
    from tools.gated_delta_bench import channel_chunk_passes

    chunk = functools.partial(gd._channel_chunk, dt=BF16,
                              inverse=gd._whole_tile_inverse,
                              unit_scales=unit_scales)
    jaxpr = jax.make_jaxpr(chunk)(*(jnp.zeros(s, F32) for s in (
        (128, 128),) * 4 + ((128, 1), (128, 128)))).jaxpr
    fed, products = set(jaxpr.invars), 0
    for eqn in jaxpr.eqns:
        inputs = [v for v in eqn.invars if isinstance(v, Var)]
        inside = [e for sub in jax.core.jaxprs_in_params(eqn.params)
                  for e in _eqns(sub)]
        if any(e.primitive.name == "dot_general" for e in [eqn] + inside):
            products += 1
            assert all(v in fed for v in inputs), eqn
        if any(v in fed for v in inputs):
            fed.update(eqn.outvars)
    assert products == 2 * 7 + 1 + 6  # the levels', the inverse, the rule's
    assert channel_chunk_passes(unit_scales=unit_scales) == {
        "fwd": {"highest": 102.0, "default": 11.0},
        "vjp": {"highest": 222.0, "default": 33.0}}

