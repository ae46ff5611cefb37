"""``ops/gated_delta.py``: the chunked gated delta rule against the
recurrence written one position at a time — values and every gradient, in
float32 and bfloat16, at sequence lengths that are and are not multiples of
the chunk, with a head that decays by ``e^-21`` a token, and at both ends of
``beta``; and the inverse of the unit lower-triangular matrix by doubling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_sequential,
    unit_lower_inverse,
)

NAMES = ("q", "k", "v", "g", "beta")


def _operands(seed, s=150, b=2, h=3, dk=16, dv=8, g_scale=1.0,
              dtype=jnp.float32, beta=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(  # noqa: E731
        x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -jax.random.uniform(ks[3], (b, s, h)) * g_scale
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    else:
        beta = jnp.full((b, s, h), beta, jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _scalar(fn):
    def loss(*ops):
        out, state = fn(*ops)[:2]
        return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.square(state))
    return loss


@pytest.mark.parametrize("q", [1, 2, 8, 64])
def test_the_inverse_by_doubling_is_the_inverse(q):
    a = jnp.tril(0.3 * jax.random.normal(
        jax.random.PRNGKey(q), (3, 2, q, q)), -1)
    inv = unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(q) + np.asarray(a, np.float64))
    assert np.abs(np.asarray(inv) - want).max() < 5e-5
    # unit lower-triangular, exactly
    assert np.array_equal(np.triu(np.asarray(inv), 1), np.zeros_like(inv))
    assert np.array_equal(
        np.diagonal(np.asarray(inv), axis1=-2, axis2=-1),
        np.ones(inv.shape[:-1], np.float32))


def test_the_inverse_refuses_a_size_that_does_not_halve():
    with pytest.raises(ValueError, match="power of two"):
        unit_lower_inverse(jnp.zeros((6, 6)))


@pytest.mark.parametrize("s", [64, 128, 150, 37, 1])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_equals_sequential_in_float32(s, chunk):
    ops = _operands(s + chunk, s=s)
    out, state, decay_min = gated_delta_chunked(*ops, chunk=chunk)
    want, want_state = gated_delta_sequential(*ops)
    assert out.shape == want.shape == (2, s, 3, 8)
    assert out.dtype == state.dtype == jnp.float32
    assert _rel(out, want) < 2e-5
    assert _rel(state, want_state) < 2e-5
    assert 0.0 <= float(decay_min) <= 1.0


@pytest.mark.parametrize("s", [128, 100])
def test_every_gradient_equals_the_sequential_forms(s):
    ops = _operands(7, s=s)
    got = jax.grad(_scalar(lambda *o: gated_delta_chunked(*o, chunk=32)),
                   argnums=range(5))(*ops)
    want = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(*ops)
    for name, a, b in zip(NAMES, got, want):
        assert _rel(a, b) < 5e-5, name


@pytest.mark.parametrize("s", [128, 90])
def test_bfloat16_operands_stay_near_float32(s):
    """q, k and v in bfloat16, as the mixer hands them over: what the op
    adds to the rounding of its operands is the rounding of the operands of
    its own matmuls against the state."""
    ops32 = _operands(11, s=s)
    ops16 = tuple(a.astype(jnp.bfloat16) for a in ops32[:3]) + ops32[3:]
    rounded = tuple(a.astype(jnp.float32) for a in ops16[:3]) + ops32[3:]
    out, state, _ = gated_delta_chunked(*ops16)
    want, want_state = gated_delta_sequential(*rounded)
    assert out.dtype == jnp.float32
    assert _rel(out, want) < 2e-2
    assert _rel(state, want_state) < 2e-2
    got = jax.grad(_scalar(gated_delta_chunked), argnums=range(5))(*ops16)
    ref = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(
        *rounded)
    for name, a, b in zip(NAMES, got, ref):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        assert _rel(a.astype(jnp.float32), b) < 4e-2, name


@pytest.mark.parametrize("s", [128, 70])
def test_a_head_that_forgets_everything_stays_finite(s):
    """``g = -21`` a token is ``e^-1344`` a chunk: the chunk's decay
    underflows float32 (the counter says 0), and neither a value nor a
    gradient is anything but finite, and equal to the recurrence's."""
    q, k, v, g, beta = _operands(13, s=s)
    g = g.at[:, :, 0].set(-21.0)
    ops = (q, k, v, g, beta)
    out, state, decay_min = gated_delta_chunked(*ops)
    want, _ = gated_delta_sequential(*ops)
    assert float(decay_min) == 0.0
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    assert _rel(out, want) < 2e-5
    got = jax.grad(_scalar(gated_delta_chunked), argnums=range(5))(*ops)
    ref = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(*ops)
    for name, a, b in zip(NAMES, got, ref):
        assert bool(jnp.isfinite(a).all()), name
        assert _rel(a, b) < 5e-5, name


@pytest.mark.parametrize("beta", [0.0, 1e-6, 1.0 - 1e-6, 1.0])
def test_both_ends_of_beta(beta):
    """``beta -> 0`` writes nothing (the output is zero: the state stays
    empty); ``beta -> 1`` replaces what the state holds under the key."""
    ops = _operands(17, s=96, beta=beta)
    out, state, _ = gated_delta_chunked(*ops, chunk=32)
    want, want_state = gated_delta_sequential(*ops)
    if beta == 0.0:
        assert float(jnp.abs(out).max()) == 0.0
        assert float(jnp.abs(state).max()) == 0.0
    else:
        assert _rel(out, want) < 2e-5
        assert _rel(state, want_state) < 2e-5
    got = jax.grad(_scalar(lambda *o: gated_delta_chunked(*o, chunk=32)),
                   argnums=(2, 4))(*ops)
    ref = jax.grad(_scalar(gated_delta_sequential), argnums=(2, 4))(*ops)
    for a, b in zip(got, ref):
        assert bool(jnp.isfinite(a).all())
        assert _rel(a, b) < 5e-5 or float(jnp.abs(b).max()) < 1e-12


def test_at_beta_one_without_decay_the_last_write_is_read_back():
    """The delta rule's point: a value written under a unit key with
    ``beta = 1`` is what a query along that key reads at that position,
    whatever was there before."""
    q, k, v, g, beta = _operands(19, s=40, beta=1.0)
    out, _, _ = gated_delta_chunked(k, k, v, jnp.zeros_like(g), beta,
                                    chunk=8)
    assert _rel(out, v) < 1e-5


def test_the_sequence_splits_at_any_chunk():
    """The state a prefix leaves is the state the rest starts from: two
    halves run one after the other equal the whole (the sequential form
    carries the state; the chunked form must agree at every chunk size)."""
    ops = _operands(23, s=128)
    whole, state, _ = gated_delta_chunked(*ops, chunk=64)
    for chunk in (8, 32, 128):
        out, st, _ = gated_delta_chunked(*ops, chunk=chunk)
        assert _rel(out, whole) < 2e-5 and _rel(st, state) < 2e-5
