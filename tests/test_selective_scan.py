"""``ops.selective_scan``: the chunked form against the sequential one —
values and every gradient, chunks that do and do not divide the sequence —
the Pallas kernel pair in interpret mode against both, the least decay, what
block remat keeps by name, and the kernels' shape rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import selective_scan as ss

NAMES = ("x", "dt", "A", "B", "C", "D")


def _operands(B, S, Dn, N, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, S, Dn)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, Dn)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (Dn, N))),
            jax.random.normal(k[3], (B, S, N)).astype(dtype),
            jax.random.normal(k[4], (B, S, N)).astype(dtype),
            jax.random.normal(k[5], (Dn,)))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _loss(fn):
    return lambda *ops: jnp.sum(jnp.sin(fn(*ops)[0]))


CASES = {
    "chunk_divides": dict(shape=(2, 32, 8, 4), kw=dict(chunk=16)),
    "chunk_does_not_divide": dict(shape=(2, 37, 8, 4), kw=dict(chunk=16)),
    "one_chunk": dict(shape=(1, 24, 16, 3), kw=dict(chunk=32)),
    "chunk_of_one": dict(shape=(1, 9, 8, 2), kw=dict(chunk=1)),
    "kernels": dict(shape=(1, 256, 1024, 4),
                    kw=dict(backend="pallas", interpret=True)),
    "kernels_padded_two_blocks": dict(
        shape=(2, 200, 2048, 16), kw=dict(backend="pallas", interpret=True)),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    spec = CASES[request.param]
    ops = _operands(*spec["shape"])
    chunked = lambda *a: ss.selective_scan(*a, **spec["kw"])  # noqa: E731
    wrt = tuple(range(6))
    return dict(
        name=request.param, ops=ops, out=chunked(*ops),
        out_s=ss.selective_scan_sequential(*ops),
        grads=jax.grad(_loss(chunked), argnums=wrt)(*ops),
        grads_s=jax.grad(_loss(ss.selective_scan_sequential),
                         argnums=wrt)(*ops))


def test_the_output_and_the_final_state_are_the_sequential_forms(case):
    (y, final, _), (y_s, final_s) = case["out"], case["out_s"]
    assert y.shape == y_s.shape and y.dtype == jnp.float32
    assert _rel(y, y_s) < 1e-5 and _rel(final, final_s) < 1e-5


@pytest.mark.parametrize("which", range(6), ids=NAMES)
def test_every_gradient_is_the_sequential_forms(case, which):
    g, g_s = case["grads"][which], case["grads_s"][which]
    assert g.shape == g_s.shape and g.dtype == g_s.dtype
    assert _rel(g, g_s) < 2e-5, (case["name"], NAMES[which])


def test_the_least_decay_is_the_least_over_chunks_channels_and_states(case):
    x, dt, A = case["ops"][:3]
    chunk = CASES[case["name"]]["kw"].get("chunk") or ss.CHUNK
    S = x.shape[1]
    pad = -S % chunk
    sums = jnp.pad(dt, ((0, 0), (0, pad), (0, 0))).reshape(
        x.shape[0], -1, chunk, x.shape[2]).sum(2)
    want = jnp.exp(jnp.min(sums[..., None] * A))
    assert float(case["out"][2]) == pytest.approx(float(want), rel=1e-5)


def test_the_kernels_take_the_jax_numpy_forms_place_under_one_rule():
    ops = _operands(1, 130, 1024, 8, seed=3)
    ref = ss.selective_scan(*ops, backend="reference")
    ker = ss.selective_scan(*ops, backend="pallas", interpret=True)
    assert _rel(ker[0], ref[0]) < 1e-6 and _rel(ker[1], ref[1]) < 1e-6
    wrt = tuple(range(6))
    g_ref = jax.grad(_loss(lambda *a: ss.selective_scan(
        *a, backend="reference")), argnums=wrt)(*ops)
    g_ker = jax.grad(_loss(lambda *a: ss.selective_scan(
        *a, backend="pallas", interpret=True)), argnums=wrt)(*ops)
    for name, a, b in zip(NAMES, g_ker, g_ref):
        assert _rel(a, b) < 1e-5, name


def test_the_rule_sends_other_shapes_to_jax_numpy():
    assert ss._kernels_tile(5120, 16) and ss.CHUNK == 128
    assert ss._kernels_tile(1024, 32)
    assert not ss._kernels_tile(1000, 16)  # not whole registers
    assert not ss._kernels_tile(1024, 33)
    # a shape the kernels do not tile runs, and runs the same, under
    # ``backend="pallas"`` (no interpret flag: a kernel would fail here)
    ops = _operands(1, 20, 24, 3)
    a = ss.selective_scan(*ops, backend="pallas")
    b = ss.selective_scan(*ops, backend="reference")
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_cotangents_come_back_in_the_operands_dtypes():
    ops = _operands(1, 16, 8, 2, dtype=jnp.bfloat16)
    grads = jax.grad(_loss(lambda *a: ss.selective_scan(*a, chunk=8)),
                     argnums=tuple(range(6)))(*ops)
    assert [g.dtype for g in grads] == [a.dtype for a in ops]
    assert ss.selective_scan(*ops, chunk=8)[0].dtype == jnp.float32


def test_no_skip_without_D_and_no_gradient_through_the_final_state():
    ops = _operands(1, 16, 8, 2)
    with_zero = ss.selective_scan(*ops[:5], jnp.zeros((8,)), chunk=8)[0]
    without = ss.selective_scan(*ops[:5], chunk=8)[0]
    assert np.array_equal(np.asarray(with_zero), np.asarray(without))
    g = jax.grad(lambda x: jnp.sum(ss.selective_scan(
        x, *ops[1:], chunk=8)[1]))(ops[0])
    assert not np.asarray(g).any()


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_block_remat_keeps_the_output_and_the_entering_states(backend):
    """Under a policy that saves :data:`SAVED_NAMES` alone the forward runs
    ONCE: nothing of it is in the rematerialised part."""
    ops = _operands(1, 256, 1024, 4)

    def fn(*a):
        return jnp.sum(jnp.sin(ss.selective_scan(
            *a, backend=backend, interpret=True)[0]))

    policy = jax.checkpoint_policies.save_only_these_names(*ss.SAVED_NAMES)
    text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(fn, policy=policy)))(
        *ops))
    if backend == "pallas":
        assert text.count("s6_scan_fwd") == 1
        assert text.count("s6_scan_bwd") == 1
    assert text.count("name=s6_out") == 1
    assert text.count("name=s6_entering") == 1
    plain = jax.grad(fn)(*ops)
    kept = jax.grad(jax.checkpoint(fn, policy=policy))(*ops)
    assert np.array_equal(np.asarray(plain), np.asarray(kept))
