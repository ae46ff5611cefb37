"""``ops/gated_delta.py``: the chunked gated delta rule against the
recurrence written one position at a time — values and every gradient, in
float32 and bfloat16, at sequence lengths that are and are not multiples of
the chunk, with a head that decays by ``e^-21`` a token, and at both ends of
``beta``; and the inverse of the unit lower-triangular matrix by doubling.
Each in both forms of the chunked rule: ``jax.numpy`` (what the CPU runs) and
the Pallas kernel pair in interpret mode (what a TPU runs, at head dims of
128 lanes and chunks of 64 or 128: the shapes the kernels tile)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd
from dlrover_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_sequential,
    unit_lower_inverse,
)

NAMES = ("q", "k", "v", "g", "beta")
FORMS = ("jax_numpy", "kernels")
#: operands the kernels tile: one tile of two heads, 128 lanes a head
TILED = dict(h=2, dk=128, dv=128)


def _chunked(form, chunk=64):
    """The chunked rule in ``form``; the kernels take chunks of 64 where
    the ``jax.numpy`` case of a test takes ``chunk``."""
    if form == "kernels":
        return functools.partial(gated_delta_chunked, chunk=max(chunk, 64),
                                 backend="pallas", interpret=True)
    return functools.partial(gated_delta_chunked, chunk=chunk)


def _dims(form):
    return TILED if form == "kernels" else {}


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
@pytest.mark.parametrize("chunk,form", [
    (16, "jax_numpy"), (64, "jax_numpy"), (64, "kernels")])
def test_chunked_equals_sequential_in_float32(s, chunk, form):
    ops = _operands(s + chunk, s=s, **_dims(form))
    out, state, decay_min = _chunked(form, chunk)(*ops)
    want, want_state = gated_delta_sequential(*ops)
    assert out.shape == want.shape == ops[2].shape
    assert out.dtype == state.dtype == jnp.float32
    assert _rel(out, want) < 2e-5
    assert _rel(state, want_state) < 2e-5
    assert 0.0 <= float(decay_min) <= 1.0


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("s", [128, 100])
def test_every_gradient_equals_the_sequential_forms(s, form):
    ops = _operands(7, s=s, **_dims(form))
    got = jax.grad(_scalar(_chunked(form, 32)), argnums=range(5))(*ops)
    want = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(*ops)
    for name, a, b in zip(NAMES, got, want):
        assert _rel(a, b) < 5e-5, name


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("s", [128, 90])
def test_bfloat16_operands_stay_near_float32(s, form):
    """q, k and v in bfloat16, as the mixer hands them over: what the op
    adds to the rounding of its operands is the rounding of the operands of
    its own matmuls against the state."""
    ops32 = _operands(11, s=s, **_dims(form))
    ops16 = tuple(a.astype(jnp.bfloat16) for a in ops32[:3]) + ops32[3:]
    rounded = tuple(a.astype(jnp.float32) for a in ops16[:3]) + ops32[3:]
    out, state, _ = _chunked(form)(*ops16)
    want, want_state = gated_delta_sequential(*rounded)
    assert out.dtype == jnp.float32
    assert _rel(out, want) < 2e-2
    assert _rel(state, want_state) < 2e-2
    got = jax.grad(_scalar(_chunked(form)), argnums=range(5))(*ops16)
    ref = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(
        *rounded)
    for name, a, b in zip(NAMES, got, ref):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        assert _rel(a.astype(jnp.float32), b) < 4e-2, name


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("s", [128, 70])
def test_a_head_that_forgets_everything_stays_finite(s, form):
    """``g = -21`` a token is ``e^-1344`` a chunk: the chunk's decay
    underflows float32 (the counter says 0), and neither a value nor a
    gradient is anything but finite, and equal to the recurrence's.  In the
    kernels the head shares its tile with one that forgets little."""
    q, k, v, g, beta = _operands(13, s=s, **_dims(form))
    g = g.at[:, :, 0].set(-21.0)
    ops = (q, k, v, g, beta)
    out, state, decay_min = _chunked(form)(*ops)
    want, _ = gated_delta_sequential(*ops)
    assert float(decay_min) == 0.0
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    assert _rel(out, want) < 2e-5
    got = jax.grad(_scalar(_chunked(form)), argnums=range(5))(*ops)
    ref = jax.grad(_scalar(gated_delta_sequential), argnums=range(5))(*ops)
    for name, a, b in zip(NAMES, got, ref):
        assert bool(jnp.isfinite(a).all()), name
        assert _rel(a, b) < 5e-5, name


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("beta", [0.0, 1e-6, 1.0 - 1e-6, 1.0])
def test_both_ends_of_beta(beta, form):
    """``beta -> 0`` writes nothing (the output is zero: the state stays
    empty); ``beta -> 1`` replaces what the state holds under the key."""
    ops = _operands(17, s=96, beta=beta, **_dims(form))
    out, state, _ = _chunked(form, 32)(*ops)
    want, want_state = gated_delta_sequential(*ops)
    if beta == 0.0:
        assert float(jnp.abs(out).max()) == 0.0
        assert float(jnp.abs(state).max()) == 0.0
    else:
        assert _rel(out, want) < 2e-5
        assert _rel(state, want_state) < 2e-5
    got = jax.grad(_scalar(_chunked(form, 32)), argnums=(2, 4))(*ops)
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


@pytest.mark.parametrize("case", ["two_head_blocks", "chunk_128"])
def test_the_kernel_pair_equals_the_jax_numpy_form(case, monkeypatch):
    """Forward, the state the sequence leaves and all five gradients of the
    kernel pair against the form it stands in for, and against the
    recurrence: with four heads and a grid step held to two (one tile) the
    state's block changes under the chunk axis; a chunk of 128 fills a tile
    alone, and two tiles run in lockstep.  Both with a padded tail."""
    h, chunk, lanes = (4, 64, 256) if case == "two_head_blocks" else (
        2, 128, gd._BLOCK_LANES)
    monkeypatch.setattr(gd, "_BLOCK_LANES", lanes)
    assert gd._kernel_heads(chunk, h, 128, 128) == 2
    ops = _operands(29, s=150, b=1, h=h, dk=128, dv=128)
    kernels, plain = _chunked("kernels", chunk), _chunked("jax_numpy", chunk)
    got, want, ref = kernels(*ops), plain(*ops), gated_delta_sequential(*ops)
    for a, b, c in zip(got[:2], want[:2], ref):
        assert _rel(a, b) < 2e-6 and _rel(a, c) < 2e-5
    assert float(got[2]) == float(want[2])
    grads = [jax.grad(_scalar(fn), argnums=range(5))(*ops)
             for fn in (kernels, plain, gated_delta_sequential)]
    for name, a, b, c in zip(NAMES, *grads):
        assert _rel(a, b) < 5e-6 and _rel(a, c) < 5e-5, name


@pytest.mark.parametrize("why,dims,chunk", [
    ("the_cpu", TILED, 64),
    ("heads_of_64_lanes", dict(h=2, dk=64, dv=128), 64),
    ("a_chunk_of_32", TILED, 32),
    ("an_odd_head_for_a_tile_of_two", dict(h=3, dk=128, dv=128), 64)])
def test_the_dispatcher_takes_the_jax_numpy_form(why, dims, chunk,
                                                 monkeypatch):
    """On the CPU whatever the shape; on a TPU (here: said to be one) for
    a shape the kernels do not tile.  The kernel pair would be a
    ``pallas_call`` in the traced program."""
    if why != "the_cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert gd._kernel_heads(chunk, dims["h"], dims["dk"],
                                dims["dv"]) == 0
        assert gd._kernel_heads(64, 2, 128, 128) == 2
    ops = _operands(31, s=70, b=1, **dims)
    text = str(jax.make_jaxpr(
        lambda *o: gated_delta_chunked(*o, chunk=chunk))(*ops))
    assert "pallas_call" not in text and "scan" in text
    out, _, _ = gated_delta_chunked(*ops, chunk=chunk)
    assert _rel(out, gated_delta_sequential(*ops)[0]) < 2e-5


def test_an_inverse_rounded_to_bfloat16_is_another_result(monkeypatch):
    """``T`` stays float32 inside the kernels: the same kernels with ``T``
    rounded to bfloat16 leave the recurrence by a hundred times what the
    float32 ones are held to (the benchmark cell's limits cannot tell the
    two apart, ``PERF.md`` section 7: this test must).  Keys that share a
    direction, so that ``T`` is far from the identity."""
    q, k, v, g, beta = _operands(37, s=128, b=1, **TILED)
    shared = jax.random.normal(jax.random.PRNGKey(41), (1, 1, 2, 128))
    k = k + 0.2 * shared
    ops = (q, k / jnp.linalg.norm(k, axis=-1, keepdims=True), v, 0.1 * g,
           beta)
    want, _ = gated_delta_sequential(*ops)
    assert _rel(_chunked("kernels")(*ops)[0], want) < 2e-5
    inverse = gd._tile_inverse

    def rounded(a, qn):
        inv = yield from inverse(a, qn)
        return inv.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(gd, "_tile_inverse", rounded)
    assert _rel(_chunked("kernels")(*ops)[0], want) > 1e-3


def test_the_kernel_pair_runs_once_per_batch_shard_of_a_mesh():
    """GSPMD cannot partition a Mosaic kernel: under a mesh the pair sits in
    a ``shard_map`` over the batch axes (``ops/per_shard.py``), and a
    sequence's chunks and heads stay on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=2), jax.devices()[:2])
    ops = _operands(43, s=70, b=2, **TILED)
    fn = jax.value_and_grad(_scalar(_chunked("kernels")), argnums=range(5))
    want = fn(*ops)
    rows = NamedSharding(mesh, P(("dp", "fsdp")))
    with jax.set_mesh(mesh):
        got = jax.jit(fn)(*(jax.device_put(a, rows) for a in ops))
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert a.sharding.spec[0] in ("fsdp", ("dp", "fsdp")), name  # sharded
        assert _rel(a, b) < 1e-5, name
