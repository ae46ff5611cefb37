"""``ops/conv_silu.py``: the causal depthwise convolution and its ``silu`` as
one op, the Pallas kernel pair in interpret mode (what a TPU runs) against
``silu(causal_conv1d(...))`` differentiated by JAX (what the CPU runs, and
the kernels' reference) — the value and every gradient, three or four taps,
with a bias and without, in float32 and bfloat16, over one lane tile and over
two blocks of three, on sequences of several row tiles so that the halo is
crossed, two batch rows so that a row's first positions are shown to read
zeros and not the row before."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import conv_silu as cs
from dlrover_tpu.ops.conv_silu import causal_conv1d_silu
from dlrover_tpu.ops.ssd import causal_conv1d
from dlrover_tpu.parallel.mesh import MeshSpec

F32, BF16 = jnp.float32, jnp.bfloat16
B, S = 2, 3 * cs._ROW_TILE
#: the kernels on the CPU: the dispatcher's own keywords
KERNELS = dict(backend="pallas", interpret=True)
kernels = functools.partial(causal_conv1d_silu, **KERNELS)


def _numpy_form(x, w, b=None):
    return jax.nn.silu(causal_conv1d(x, w, b)).astype(x.dtype)


def _operands(taps, channels, bias, dtype, seed=0, s=S):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ((jax.random.normal(k[0], (B, s, channels)).astype(dtype),
             jax.random.uniform(k[1], (taps, channels), F32, -.5, .5))
            + ((jax.random.normal(k[2], (channels,)),) if bias else ()),
            jax.random.normal(k[3], (B, s, channels)))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _grads(fn, ops, weights):
    """The gradients of ``sum(fn(*ops) * weights)`` by every operand."""
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32) * weights),
                    tuple(range(len(ops))))(*ops)


def _calls_a_kernel(f, *args):
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


# 4352 channels are the hybrid cell's: 17 blocks of two lane tiles; 768 are
# two blocks of three
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("channels", [128, 768])
@pytest.mark.parametrize("taps", [3, 4])
def test_the_kernel_pair_equals_the_numpy_form(taps, channels, bias, dtype):
    ops, weights = _operands(taps, channels, bias, dtype)
    assert _calls_a_kernel(kernels, *ops)
    got, want = kernels(*ops), _numpy_form(*ops)
    assert got.dtype == dtype and got.shape == want.shape
    # the same sums in the same order, one rounding: a bf16 result may
    # differ where XLA fuses a product into a sum and the kernel does not
    assert _rel(got, want) < (1e-4 if dtype == BF16 else 1e-6)

    got = _grads(kernels, ops, weights)
    want = _grads(_numpy_form, ops, weights)
    for name, g, w in zip(("dx", "dw", "db"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        # dx is rounded once to x's dtype; dw and db are float32 sums of
        # 3,072 terms a channel
        assert _rel(g, w) < (1e-4 if dtype == BF16 else 2e-6), name


@pytest.mark.parametrize("taps", [1, 9])
def test_one_tap_and_a_halo_full_of_taps(taps):
    """``K - 1`` from nothing to the eight rows a float32 halo holds."""
    ops, weights = _operands(taps, 128, True, F32, seed=taps)
    assert _calls_a_kernel(kernels, *ops)
    assert _rel(kernels(*ops), _numpy_form(*ops)) < 1e-6
    for g, w in zip(_grads(kernels, ops, weights),
                    _grads(_numpy_form, ops, weights)):
        assert _rel(g, w) < 2e-6


def test_the_op_is_causal_and_a_batch_row_starts_from_zeros():
    """``tests/test_llama_ssm.py``'s causality test on the kernels, at a
    position on either side of a row tile's edge; and the second batch row's
    first positions do not move with the first row's last."""
    (x, w, b), _ = _operands(4, 128, True, F32, seed=1)
    taps, out = w.shape[0], kernels(x, w, b)
    for t in (17, cs._ROW_TILE - 1, cs._ROW_TILE, 2 * cs._ROW_TILE + 1):
        moved = kernels(x.at[:, t].add(1.0), w, b)
        assert float(jnp.abs(moved[:, :t] - out[:, :t]).max()) == 0.0
        assert float(jnp.abs(moved[:, t] - out[:, t]).min()) > 0.0
        assert float(jnp.abs(moved[:, t + 1:t + taps]
                             - out[:, t + 1:t + taps]).max()) > 0.0
        assert float(jnp.abs(moved[:, t + taps:]
                             - out[:, t + taps:]).max()) == 0.0
    moved = kernels(x.at[0, -taps:].add(1.0), w, b)
    assert float(jnp.abs(moved[1] - out[1]).max()) == 0.0
    # and the gradient looks no further back: a cotangent at t alone
    # reaches x at t - (K - 1) .. t
    t = cs._ROW_TILE + 1
    dx = jax.grad(lambda x: jnp.sum(kernels(x, w, b)[:, t]))(x)
    reached = np.flatnonzero(np.abs(np.asarray(dx)).max(axis=(0, 2)))
    assert reached.tolist() == list(range(t - taps + 1, t + 1))


def test_a_shape_the_rule_refuses_takes_the_numpy_form():
    """Asked for the kernels by name, six channels, a sequence that the row
    tile does not divide, float16 and ten taps still run — the ``jax.numpy``
    form, bit for bit."""
    refused = {
        "channels": _operands(4, 6, True, F32)[0],
        "rows": _operands(4, 128, True, F32, s=cs._ROW_TILE + 40)[0],
        "taps": _operands(10, 128, True, F32)[0],
        "dtype": _operands(4, 128, True, jnp.float16)[0],
    }
    for why, ops in refused.items():
        assert cs._tile(ops[0].shape, ops[1].shape[0], ops[0].dtype) is None
        assert not _calls_a_kernel(kernels, *ops), why
        np.testing.assert_array_equal(
            np.asarray(kernels(*ops)), np.asarray(_numpy_form(*ops)))
    tiled = _operands(4, 128, True, F32)[0]
    assert _calls_a_kernel(kernels, *tiled)
    # and on the CPU nobody is asked: the jax.numpy form
    assert not _calls_a_kernel(causal_conv1d_silu, *tiled)


def test_the_tile_follows_from_the_shapes():
    # the delta-rule cell: 8,192 channels in 16 blocks of four lane tiles;
    # the hybrid cell: 4,352 = 34 lane tiles in 17 blocks of two
    assert cs._tile((2, 8192, 8192), 4, BF16) == (512, 512, 16)
    assert cs._tile((2, 8192, 4352), 4, BF16) == (512, 256, 16)
    assert cs._tile((2, 8192, 4352), 4, F32) == (512, 256, 8)
    assert cs._tile((1, 512, 128), 1, F32) == (512, 128, 8)


def test_the_choice_reads_the_backend_and_the_shapes_not_the_environment(
        monkeypatch):
    import os

    class Closed(dict):
        def _refuse(self, *a, **k):
            raise AssertionError("the choice read the environment")
        __getitem__ = get = __contains__ = _refuse

    tiled = _operands(4, 128, False, BF16)[0]
    monkeypatch.setattr(os, "environ", Closed())
    monkeypatch.setattr(os, "getenv", Closed()._refuse)
    # a new function each time: JAX caches a function's trace
    for backend, kernel in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert _calls_a_kernel(
            lambda *a: causal_conv1d_silu(*a), *tiled) is kernel, backend


def test_the_kernels_run_once_per_batch_shard_of_the_mesh_in_scope():
    """Under a ``dp = 2`` mesh the pair runs in a ``shard_map`` over the
    batch dim (``ops/per_shard.py``): values and gradients those of one
    device — the taps' and the bias's summed over the shards — and the
    output still sharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    ops, weights = _operands(4, 256, True, BF16, seed=2)

    def loss(fn):
        def scalar(*a):
            y = fn(*a)
            return jnp.sum(y.astype(F32) * weights), y
        return jax.value_and_grad(scalar, (0, 1, 2), has_aux=True)

    (want, y_want), g_want = loss(kernels)(*ops)
    rows = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))
    with jax.set_mesh(mesh):
        (got, y_got), g_got = jax.jit(loss(kernels))(
            jax.device_put(ops[0], rows), *ops[1:])
    assert y_got.sharding.spec[0] == ("dp", "fsdp")
    assert g_got[0].sharding.spec[0] == ("dp", "fsdp")
    np.testing.assert_array_equal(np.asarray(y_got), np.asarray(y_want))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(g_got[0]), np.asarray(g_want[0]))
    for g, w in zip(g_got[1:], g_want[1:]):
        assert _rel(g, w) < 1e-6
