"""State-space layers beside attention on the normal path (the Granite-4.0-H
hybrid): the chunked state-space-duality scan of ``ops/ssd.py`` against the
sequential recurrence, forward and every gradient; the causal depthwise
convolution; a per-layer kind (``layer_types``) that ``block_apply``
dispatches on; attention without rotary position at a stated scale; the four
multipliers on the stream; a head tied to the embedding — each against a
plain formula written out here, in float32 on seeded weights.

With the defaults nothing of it may show: ``tests/test_llama_mla_moe.py``
holds a dense, a routed and a looped config to the loss and gradients an
earlier commit gave, bit for bit, and runs here unchanged.  Every path that
cannot compute the new settings refuses them by name.
"""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import refusing_calls

from dlrover_tpu.models import llama
from dlrover_tpu.ops import ssd
from dlrover_tpu.parallel.mesh import MeshSpec

acc = importlib.import_module("dlrover_tpu.parallel.accelerate")

F32 = jnp.float32
B, S = 2, 40
H, P, G, N, K = 4, 8, 2, 16, 4


def _hybrid(**over):
    base = dict(
        vocab_size=512, n_layer=3, n_head=4, n_kv_head=2, d_model=32,
        d_ff=64, max_seq_len=64, dtype=F32,
        layer_types=("mamba", "mamba", "attention"), mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16, rope=False,
        attention_multiplier=1 / 16, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0,
        tie_word_embeddings=True)
    base.update(over)
    return llama.LlamaConfig(**base)


def _tokens(seed=0, vocab=512, s=S):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (B, s + 1)).astype(np.int32))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the op -------------------------------------------------------------------


def _operands(seed=0):
    """Pre-convolution x, B and C channels, raw dt, and the mixer's own
    leaves, as eight arrays."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(k[0], (B, S, H * P)),
            jax.random.normal(k[1], (B, S, G * N)),
            jax.random.normal(k[2], (B, S, G * N)),
            jax.random.normal(k[3], (B, S, H)),  # dt before its bias
            jnp.log(jnp.arange(1, H + 1, dtype=F32)),  # A_log
            jax.random.normal(k[4], (H,)),  # D
            jax.random.normal(k[5], (H,)) - 2.0,  # dt_bias
            jax.random.uniform(k[6], (K, H * P + 2 * G * N), F32, -.5, .5))


NAMES = ("x", "B", "C", "dt", "A_log", "D", "dt_bias", "conv_w")


def _mixer_core(scan):
    """Convolution, activations and the scan as the mixer strings them,
    the scan itself left open: chunked or sequential."""
    def run(x, b, c, dt, a_log, d, dt_bias, conv_w):
        xbc = jax.nn.silu(ssd.causal_conv1d(
            jnp.concatenate([x, b, c], axis=-1), conv_w))
        step = jax.nn.softplus(dt + dt_bias)
        return scan(
            xbc[..., :H * P].reshape(B, S, H, P), step, -jnp.exp(a_log),
            xbc[..., H * P:H * P + G * N].reshape(B, S, G, N),
            xbc[..., H * P + G * N:].reshape(B, S, G, N), d)
    return run


#: chunks that divide S = 40 and that do not, one longer than the sequence
CHUNKS = [8, 16, 7, 64, 10, 5]


@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_scan_equals_the_sequential_recurrence(chunk):
    ops = _operands()
    chunked = _mixer_core(lambda x, dt, a, b, c, d: ssd.ssd_chunked(
        x, dt, a, b, c, chunk, D=d))
    sequential = _mixer_core(lambda x, dt, a, b, c, d: ssd.ssd_sequential(
        x, dt, a, b, c, D=d))
    y, state, decay_min = chunked(*ops)
    y_seq, state_seq = sequential(*ops)
    # float32 both ways: the two orders of summation differ by rounding
    assert _rel(y, y_seq) < 1e-5
    assert _rel(state, state_seq) < 1e-5
    assert 0.0 < float(decay_min) <= 1.0


@pytest.mark.parametrize("chunk", CHUNKS[1:5])
def test_chunked_scan_has_the_sequential_gradients(chunk):
    """All eight: x, B, C, dt, A_log, D, dt_bias and the convolution's
    weights, through a loss that reads the outputs AND the last state."""
    ops = _operands(1)
    weights = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P))

    def loss_of(scan):
        def loss(*ops):
            y, state = _mixer_core(scan)(*ops)[:2]
            return jnp.sum(y * weights) + jnp.sum(jnp.square(state))
        return jax.grad(loss, argnums=tuple(range(8)))(*ops)

    got = loss_of(lambda x, dt, a, b, c, d: ssd.ssd_chunked(
        x, dt, a, b, c, chunk, D=d))
    want = loss_of(lambda x, dt, a, b, c, d: ssd.ssd_sequential(
        x, dt, a, b, c, D=d))
    for name, g, w in zip(NAMES, got, want):
        # float32 rounding of two summation orders, through exp and
        # softplus: a wrong term in any gradient is of order one
        assert _rel(g, w) < 2e-5, name


#: how a test reaches the intra-chunk kernels on the CPU: the dispatcher's
#: own keywords, Pallas in interpret mode
KERNELS = dict(backend="pallas", interpret=True)


def _scan_operands(chunk, groups, head, dtype, seed=0, heads=2, chunks=2.5):
    """``x, dt, A, B, C, D`` at shapes the kernels tile (N 128, two heads a
    group), the sequence two and a half chunks long."""
    s, n, h = int(chunk * chunks), 128, groups * heads
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, s, h, head)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (B, s, h)) - 2.0),
            -jnp.exp(0.3 * jax.random.normal(k[2], (h,))),
            (jax.random.normal(k[3], (B, s, groups, n)) * n ** -.5).astype(
                dtype),
            jax.random.normal(k[4], (B, s, groups, n)).astype(dtype),
            jax.random.normal(k[5], (h,)))


SHAPES = [(chunk, groups, head) for chunk in (128, 256)
          for groups in (1, 2) for head in (64, 128)]


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("chunk,groups,head", SHAPES)
def test_the_kernel_pair_equals_the_numpy_form(chunk, groups, head, dtype):
    """``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` against ``_chunk_outputs``
    (``_intra_chunk`` plus the entering state's term and ``D x``) and JAX's
    own derivative of it: the value and the cotangents of ``x``, ``dt``,
    the cumulative sums, ``B``, ``C``, the entering states and ``D``."""
    x, dt, a, bm, cm, d = _scan_operands(chunk, groups, head, dtype, chunks=2)
    c, r, n = 2, x.shape[2] // groups, bm.shape[-1]
    assert ssd._kernel_heads(chunk, r, head, n) == r
    dtc = dt.reshape(B, c, chunk, groups, r)
    ops = (x.reshape(B, c, chunk, groups, r, head), dtc,
           jnp.cumsum(dtc * a.reshape(groups, r), axis=2),
           bm.reshape(B, c, chunk, groups, n),
           cm.reshape(B, c, chunk, groups, n),
           (jax.random.normal(jax.random.PRNGKey(5),
                              (c, B, groups, r, head, n)) * .1).astype(dtype),
           d.reshape(groups, r))
    names = ("x", "dt", "cumulative sums", "B", "C", "entering", "D")
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def value_and_grads(f):
        return jax.value_and_grad(
            lambda *o: jnp.sum(f(*o) * weights), argnums=tuple(range(7)))(
                *ops)

    y = ssd.chunk_outputs(*ops, **KERNELS)
    assert y.dtype == F32 and _rel(y, ssd._chunk_outputs(*ops)) < 1e-6
    # the [Q, Q] part alone: no state enters, D is 0
    bare = ops[:5] + (jnp.zeros_like(ops[5]), jnp.zeros_like(ops[6]))
    xdt = (ops[0].astype(F32) * dtc[..., None]).astype(dtype)
    assert _rel(ssd.chunk_outputs(*bare, **KERNELS),
                ssd._intra_chunk(xdt, *ops[2:5])) < 1e-6
    (_, got), (_, want) = (
        value_and_grads(lambda *o: ssd.chunk_outputs(*o, **KERNELS)),
        value_and_grads(ssd._chunk_outputs))
    # float32: two orders of summation.  bf16: the kernel keeps ``dy
    # xdt^T`` in float32 where JAX's transpose rounds it to bf16
    tol = 1e-5 if dtype == F32 else 1e-2
    for name, g, w in zip(names, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rel(g.astype(F32), w.astype(F32)) < tol, name


def _scan_loss(scan, weights):
    def loss(*ops):
        y, state = scan(*ops)[:2]
        return jnp.sum(y * weights) + jnp.sum(jnp.square(state))
    return loss


@pytest.mark.parametrize("chunk,groups,head", SHAPES)
def test_the_scan_through_the_kernels_equals_the_recurrence(chunk, groups,
                                                            head):
    """Values and every gradient (``x``, ``dt``, ``A``, ``B``, ``C``,
    ``D``) of ``ssd_chunked`` through the kernel pair against
    ``ssd_sequential``, float32, at the tolerances of the ``jax.numpy``
    form, on a sequence the chunk does not divide."""
    ops = _scan_operands(chunk, groups, head, F32)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    chunked = lambda x, dt, a, b, c, d: ssd.ssd_chunked(  # noqa: E731
        x, dt, a, b, c, chunk, D=d, **KERNELS)
    sequential = lambda x, dt, a, b, c, d: ssd.ssd_sequential(  # noqa: E731
        x, dt, a, b, c, D=d)
    y, state, _ = chunked(*ops)
    y_seq, state_seq = sequential(*ops)
    assert _rel(y, y_seq) < 1e-5 and _rel(state, state_seq) < 1e-5
    got, want = (jax.grad(_scan_loss(f, weights), argnums=tuple(range(6)))(
        *ops) for f in (chunked, sequential))
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        # A's gradient sums, over the whole sequence, row sums less column
        # sums of one [Q, Q] product, which cancel: the kernel forms the
        # two as separate sums over P, so its float32 rounding does not
        # cancel entry by entry as the jax.numpy form's does (6e-6 here)
        assert _rel(g, w) < (5e-5 if name == "A" else 2e-5), name


@pytest.mark.parametrize("via", ["numpy", "kernels"])
def test_the_scan_at_eight_groups_of_eight_heads_equals_the_recurrence(via):
    """64 heads of 64 in EIGHT groups, a state of 128, chunks of 128 (the
    one-branch hybrid cell's shapes; every other cell has one group): a
    grid step of the kernels takes a group's 8 heads, 512 lanes.  Values and
    every gradient against ``ssd_sequential`` at 2e-5, float32, on a
    sequence the chunk does not divide."""
    ops = _scan_operands(128, 8, 64, F32, heads=8)
    assert ssd._kernel_heads(128, 8, 64, 128) == 8
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    kw = KERNELS if via == "kernels" else {}
    chunked = lambda x, dt, a, b, c, d: ssd.ssd_chunked(  # noqa: E731
        x, dt, a, b, c, 128, D=d, **kw)
    sequential = lambda x, dt, a, b, c, d: ssd.ssd_sequential(  # noqa: E731
        x, dt, a, b, c, D=d)
    y, state, _ = chunked(*ops)
    y_seq, state_seq = sequential(*ops)
    assert _rel(y, y_seq) < 2e-5 and _rel(state, state_seq) < 2e-5
    # a head reads ITS group's B and C: with the groups rolled by one the
    # result is another
    rolled = ops[:3] + (jnp.roll(ops[3], 1, axis=2),
                        jnp.roll(ops[4], 1, axis=2)) + ops[5:]
    assert _rel(chunked(*rolled)[0], y_seq) > 1e-1
    got, want = (jax.grad(_scan_loss(f, weights), argnums=tuple(range(6)))(
        *ops) for f in (chunked, sequential))
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert _rel(g, w) < (5e-5 if name == "A" else 2e-5), name


@pytest.mark.parametrize("chunk,groups,head", SHAPES[2:6])
def test_the_kernels_take_bf16_operands_and_accumulate_in_float32(
        chunk, groups, head):
    """bf16 ``x``, ``B`` and ``C`` through the kernel pair: values and
    gradients within bf16's 8 bits of the float32 recurrence, as the
    ``jax.numpy`` form's are, and the two forms as near each other."""
    ops32 = _scan_operands(chunk, groups, head, F32, seed=3)
    ops = _scan_operands(chunk, groups, head, jnp.bfloat16, seed=3)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def grads(scan, operands):
        return jax.grad(_scan_loss(scan, weights), argnums=tuple(range(6)))(
            *operands)

    kernels = lambda x, dt, a, b, c, d: ssd.ssd_chunked(  # noqa: E731
        x, dt, a, b, c, chunk, D=d, **KERNELS)
    y, state, _ = kernels(*ops)
    assert y.dtype == F32 and state.dtype == F32
    y_seq, _ = ssd.ssd_sequential(*ops32[:5], D=ops32[5])
    assert _rel(y, y_seq) < 2e-2
    got = grads(kernels, ops)
    numpy_form = grads(lambda x, dt, a, b, c, d: ssd.ssd_chunked(
        x, dt, a, b, c, chunk, D=d), ops)
    want = grads(lambda x, dt, a, b, c, d: ssd.ssd_sequential(
        x, dt, a, b, c, D=d), ops32)
    for name, g, n, w in zip(("x", "dt", "A", "B", "C", "D"), got,
                             numpy_form, want):
        assert g.dtype == n.dtype, name
        assert _rel(g.astype(F32), w) < 3e-2, name
        assert _rel(g.astype(F32), n.astype(F32)) < 2e-2, name


@pytest.mark.parametrize("via", ["numpy", "kernels"])
def test_a_chunk_whose_decay_underflows_stays_finite(via):
    """dt A of -200 a position: ``exp`` of a chunk's sum is 0 in float32;
    the masked differences keep every entry finite, values and gradients,
    and ``ssm_decay_min`` says so."""
    if via == "kernels":
        xs, _, _, bm, cm, _ = _scan_operands(128, 1, 64, F32, seed=2)
        chunk, how = 128, KERNELS
    else:
        x, b, c = _operands(2)[:3]
        xs, bm, cm = (x.reshape(B, S, H, P), b.reshape(B, S, G, N),
                      c.reshape(B, S, G, N))
        chunk, how = 16, {}
    step = jnp.full(xs.shape[:3], 2.0)
    a = jnp.full(xs.shape[2:3], -100.0)

    def loss(xs):
        y, state, _ = ssd.ssd_chunked(xs, step, a, bm, cm, chunk, **how)
        return jnp.sum(y) + jnp.sum(state)

    y, _, decay_min = ssd.ssd_chunked(xs, step, a, bm, cm, chunk, **how)
    y_seq, _ = ssd.ssd_sequential(xs, step, a, bm, cm)
    assert float(decay_min) == 0.0
    assert bool(jnp.isfinite(y).all()) and _rel(y, y_seq) < 1e-5
    assert bool(jnp.isfinite(jax.grad(loss)(xs)).all())


def test_bf16_operands_accumulate_in_float32():
    x, b, c, dt, a_log, d, dt_bias, conv_w = _operands(3)
    bf = jnp.bfloat16
    args = (x.reshape(B, S, H, P), jax.nn.softplus(dt), -jnp.exp(a_log),
            b.reshape(B, S, G, N), c.reshape(B, S, G, N))
    y, state, _ = ssd.ssd_chunked(
        args[0].astype(bf), args[1], args[2], args[3].astype(bf),
        args[4].astype(bf), 16, D=d)
    assert y.dtype == F32 and state.dtype == F32
    y_seq, _ = ssd.ssd_sequential(*args, D=d)
    # bf16 keeps 8 bits: operands rounded once, sums in float32
    assert _rel(y, y_seq) < 2e-2


def test_the_convolution_is_causal_and_is_four_shifted_adds():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (B, S, 6))
    w, bias = jax.random.normal(k[1], (K, 6)), jax.random.normal(k[2], (6,))
    out = ssd.causal_conv1d(x, w, bias)
    want = np.zeros((B, S, 6), np.float32) + np.asarray(bias)
    for t in range(S):
        for tap in range(K):
            src = t - (K - 1) + tap
            if src >= 0:
                want[:, t] += np.asarray(w[tap]) * np.asarray(x[:, src])
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    # a change at t moves nothing before t, and t itself
    t = 17
    moved = ssd.causal_conv1d(x.at[:, t].add(1.0), w, bias)
    assert float(jnp.abs(moved[:, :t] - out[:, :t]).max()) == 0.0
    assert float(jnp.abs(moved[:, t] - out[:, t]).min()) > 0.0
    assert float(jnp.abs(moved[:, t + K:] - out[:, t + K:]).max()) == 0.0


def test_the_kernels_run_once_per_batch_shard_of_the_mesh_in_scope():
    """Under a ``dp x fsdp`` mesh GSPMD cannot partition a Mosaic call: the
    pair runs in a ``shard_map`` over the batch dim (``ops/per_shard.py``),
    values and gradients those of the ``jax.numpy`` form, outputs still
    sharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    ops = _scan_operands(128, 1, 64, F32, chunks=2)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def loss(how):
        return _scan_loss(lambda x, dt, a, b, c, d: ssd.ssd_chunked(
            x, dt, a, b, c, 128, D=d, **how), weights)

    want = jax.value_and_grad(loss({}), argnums=(0, 1, 3))(*ops)
    rows = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))
    with jax.set_mesh(mesh):
        got = jax.jit(jax.value_and_grad(loss(KERNELS), argnums=(0, 1, 3)))(
            *(jax.device_put(o, rows) if o.ndim > 1 else o for o in ops))
    assert got[1][0].sharding.spec[0] == ("dp", "fsdp")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert _rel(g, w) < 1e-5


def test_kernel_heads_follow_from_the_shapes():
    # the cell: 64 heads of 64 in one group, N 128, chunks of 256 -> eight
    # heads a grid step, 512 lanes
    assert ssd._kernel_heads(256, 64, 64, 128) == 8
    assert ssd._kernel_heads(128, 32, 128, 128) == 4
    assert ssd._kernel_heads(256, 6, 64, 128) == 6  # a divisor of R
    assert ssd._kernel_heads(256, 2, 1024, 128) == 1  # one head is wider
    # refused: a chunk or a state that is no multiple of 128 lanes, one
    # head of 64 alone, a head size that neither divides nor is divided
    for shape in ((64, 64, 64, 128), (256, 64, 64, 16), (256, 1, 64, 128),
                  (256, 3, 64, 128), (256, 64, 96, 128)):
        assert ssd._kernel_heads(*shape) == 0, shape


def _calls_a_kernel(f, *args):
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


def test_a_shape_the_rule_refuses_takes_the_numpy_form():
    """Asked for the kernels by name, a chunk of 16 still runs (and equals)
    ``_intra_chunk``; the shapes the rule admits do call a kernel."""
    x, b, c, dt = _operands(4)[:4]
    args = (x.reshape(B, S, H, P), jax.nn.softplus(dt),
            -jnp.arange(1.0, H + 1), b.reshape(B, S, G, N),
            c.reshape(B, S, G, N))
    asked = lambda *a: ssd.ssd_chunked(*a, 16, **KERNELS)  # noqa: E731
    assert not _calls_a_kernel(asked, *args)
    np.testing.assert_array_equal(
        np.asarray(asked(*args)[0]),
        np.asarray(ssd.ssd_chunked(*args, 16)[0]))
    tiled = _scan_operands(128, 1, 64, F32)[:5]
    assert _calls_a_kernel(lambda *a: ssd.ssd_chunked(*a, 128, **KERNELS),
                           *tiled)
    # and on the CPU nobody is asked: the jax.numpy form
    assert not _calls_a_kernel(lambda *a: ssd.ssd_chunked(*a, 128), *tiled)


def test_the_choice_reads_the_backend_and_the_shapes_not_the_environment(
        monkeypatch):
    import os

    class Closed(dict):
        def _refuse(self, *a, **k):
            raise AssertionError("the choice read the environment")
        __getitem__ = get = __contains__ = _refuse

    tiled = _scan_operands(128, 1, 64, F32)[:5]
    monkeypatch.setattr(os, "environ", Closed())
    monkeypatch.setattr(os, "getenv", Closed()._refuse)
    # a new function each time: JAX caches a function's trace
    for backend, kernel in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert _calls_a_kernel(
            lambda *a: ssd.ssd_chunked(*a, 128), *tiled) is kernel, backend


# -- the mixer and the block against the equations ---------------------------


def _rms(x, w, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mixer_plain(u, p, cfg):
    """The issue's equations, one position at a time."""
    heads, hp, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    inner, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
                  zxbcdt[..., inner + conv:])
    padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(
        padded[:, k:k + u.shape[1]] * p["conv_w"][k] for k in range(4))
        + p["conv_b"])
    x = xbc[..., :inner].reshape(u.shape[:2] + (heads, hp))
    bm, cm = xbc[..., inner:inner + n], xbc[..., inner + n:]  # one group
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    h = jnp.zeros((u.shape[0], heads, hp, n))
    ys = []
    for t in range(u.shape[1]):
        h = (jnp.exp(dt[:, t] * a)[..., None, None] * h
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * bm[:, t, None, None, :])
        ys.append(jnp.einsum("bhpn,bn->bhp", h, cm[:, t])
                  + p["D"][:, None] * x[:, t])
    y = jnp.stack(ys, axis=1).reshape(u.shape[:2] + (inner,))
    return _rms(y * jax.nn.silu(z), p["norm"]) @ p["out_proj"], h


def _attention_plain(y, layer, cfg):
    """GQA with the mask written out: no rotary, scores x multiplier."""
    b, s, _ = y.shape
    h, kv, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (y @ layer["wq"]).reshape(b, s, h, d)
    k = jnp.repeat((y @ layer["wk"]).reshape(b, s, kv, d), h // kv, axis=2)
    v = jnp.repeat((y @ layer["wv"]).reshape(b, s, kv, d), h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg.attention_multiplier
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(b, s, h * d) @ layer["wo"]


def _loss_plain(params, toks, cfg):
    inp, tgt = toks[:, :-1], toks[:, 1:]
    m = cfg.residual_multiplier
    x = params["embed"][inp] * cfg.embedding_multiplier
    for layer in params["layers"]:
        y = _rms(x, layer["ln1"])
        if "ssm" in layer:
            x = x + m * _mixer_plain(y, layer["ssm"], cfg)[0]
        else:
            x = x + m * _attention_plain(y, layer, cfg)
        y = _rms(x, layer["ln2"])
        mlp = layer["mlp"]
        x = x + m * ((jax.nn.silu(y @ mlp["w_gate"]) * (y @ mlp["w_up"]))
                     @ mlp["w_down"])
    logits = _rms(x, params["ln_f"]) @ params["embed"].T / cfg.logits_scaling
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))


def _params(cfg, seed=0):
    """Seeded weights with every gain and scalar leaf off its initial
    value, so that a dropped one shows."""
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 200))

    def off_one(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 1 and "A_log" not in name and "dt_bias" not in name:
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_one, params)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "logits"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_hybrid_loss_and_gradients_match_the_equations(remat, fused):
    cfg = _hybrid(remat_block=remat)
    params, toks = _params(cfg), _tokens()
    got, grads = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": toks}, cfg, fused_lm_head=fused))(params)
    want, want_grads = jax.value_and_grad(_loss_plain)(params, toks, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    flat_w, tree_w = jax.tree_util.tree_flatten(want_grads)
    assert tree == tree_w
    for (path, g), w in zip(flat, flat_w):
        # float32 on both sides; the chunked and the sequential sums round
        # differently, through ten branches
        assert _rel(g, w) < 2e-4, jax.tree_util.keystr(path)


def test_the_mixer_reports_the_state_the_sequence_leaves():
    cfg = _hybrid()
    params = _params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    out, stats = llama._ssm_mixer(u, params["layers"][0]["ssm"], cfg)
    want, state = _mixer_plain(u, params["layers"][0]["ssm"], cfg)
    assert _rel(out, want) < 1e-5
    assert float(stats["ssm_state_rms"]) == pytest.approx(
        float(jnp.sqrt(jnp.mean(jnp.square(state)))), rel=1e-5)
    assert 0.0 < float(stats["ssm_decay_min"]) < 1.0


@pytest.mark.parametrize("via", ["numpy", "kernels"])
def test_block_remat_of_a_mixed_stack_equals_no_remat(via, monkeypatch):
    cfg, toks = _hybrid(), _tokens(1)
    if via == "kernels":
        # two heads of 64, N 128 and chunks of 128: shapes the kernels
        # tile, two and a half chunks a sequence
        cfg = _hybrid(d_model=64, mamba_n_heads=2, mamba_d_head=64,
                      mamba_d_state=128, mamba_chunk_size=128,
                      max_seq_len=320)
        toks = _tokens(1, s=320)
        monkeypatch.setattr(llama, "ssd_chunked", functools.partial(
            ssd.ssd_chunked, **KERNELS))
    params = _params(cfg)
    plain = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": toks}, cfg))(params)
    remat = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": toks}, dataclasses.replace(cfg, remat_block=True)))(
            params)
    if via == "kernels":
        text = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(
            p, {"tokens": toks}, cfg)))(params))
        assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_mixer_through_the_convolutions_kernels_equals_the_numpy_form(
        dtype, monkeypatch):
    """``_ssm_mixer`` with ``ops.conv_silu``'s kernel pair (interpret mode)
    against the mixer with the ``jax.numpy`` form, the bias among its
    leaves: the output, the stream's gradient and every leaf's, at the
    tolerances the scan's kernels are held to, on two row tiles of 384
    channels (two heads of 64 and one group's B and C of 128)."""
    from dlrover_tpu.ops import conv_silu

    s = 2 * conv_silu._ROW_TILE
    cfg = _hybrid(d_model=64, mamba_n_heads=2, mamba_d_head=64,
                  mamba_d_state=128, mamba_chunk_size=128, max_seq_len=s,
                  dtype=dtype)
    assert cfg.mamba_conv_dim == 384
    ssm = llama._init_ssm(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (B, s, 64)).astype(dtype)

    def loss(ssm, u):
        out, stats = llama._ssm_mixer(u, ssm, cfg)
        return jnp.sum(jnp.sin(out.astype(F32))), (out, stats)

    run = lambda: jax.value_and_grad(  # noqa: E731
        loss, (0, 1), has_aux=True)(ssm, u)
    (_, (want, want_stats)), want_grads = run()
    monkeypatch.setattr(llama, "causal_conv1d_silu", functools.partial(
        conv_silu.causal_conv1d_silu, backend="pallas", interpret=True))
    text = str(jax.make_jaxpr(jax.grad(lambda s_, u_: loss(s_, u_)[0]))(
        ssm, u))
    assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
    (_, (got, stats)), grads = run()
    tol = 1e-5 if dtype == F32 else 1e-2
    assert got.dtype == dtype and _rel(
        got.astype(F32), want.astype(F32)) < tol
    assert float(stats["ssm_state_rms"]) == pytest.approx(
        float(want_stats["ssm_state_rms"]), rel=tol)
    flat, tree = jax.tree_util.tree_flatten_with_path(grads)
    flat_w, tree_w = jax.tree_util.tree_flatten(want_grads)
    assert tree == tree_w and "conv_b" in grads[0]
    for (path, g), w in zip(flat, flat_w):
        assert g.dtype == w.dtype
        assert _rel(g.astype(F32), w.astype(F32)) < tol, (
            jax.tree_util.keystr(path))


# -- the tied head and the multipliers ---------------------------------------


def test_a_tied_head_is_one_leaf_whose_gradient_sums_both_uses():
    cfg = _hybrid()
    params, toks = _params(cfg), _tokens(2)
    assert "lm_head" not in params
    assert "lm_head" not in llama.param_logical_axes(cfg)
    tied = jax.grad(lambda p: llama.loss_fn(p, {"tokens": toks}, cfg))(
        params)["embed"]
    # the same model with a head of its own that holds the same numbers
    untied_cfg = dataclasses.replace(cfg, tie_word_embeddings=False)
    untied = dict(params, lm_head=params["embed"].T)
    grads = jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": toks}, untied_cfg))(untied)
    assert float(jnp.abs(grads["embed"]).max()) > 0
    assert float(jnp.abs(grads["lm_head"]).max()) > 0
    np.testing.assert_allclose(
        np.asarray(tied), np.asarray(grads["embed"] + grads["lm_head"].T),
        rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("name,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", None),
    ("rope", True)])
def test_each_multiplier_and_the_missing_rotary_change_the_loss(
        name, neutral):
    cfg = _hybrid()
    params, toks = _params(cfg), _tokens(3)
    # N(0, 0.02) projections give scores near 0 and a flat softmax whatever
    # the scale or the position: decisive ones, as a trained layer's
    last = params["layers"][-1]
    params["layers"][-1] = dict(last, wq=30 * last["wq"], wk=30 * last["wk"])
    other_cfg = dataclasses.replace(cfg, **{name: neutral})
    loss = llama.loss_fn(params, {"tokens": toks}, cfg)
    other = llama.loss_fn(params, {"tokens": toks}, other_cfg)
    # one attention layer behind 0.22 moves the stream more than the loss
    stream = llama.forward_hidden(params, toks[:, :-1], cfg)[0]
    other_stream = llama.forward_hidden(params, toks[:, :-1], other_cfg)[0]
    assert max(abs(float(loss) - float(other)) / abs(float(loss)),
               _rel(other_stream, stream)) > 1e-4


def test_the_scale_goes_onto_q_exactly():
    """``attention_multiplier * sqrt(D)``: 1/64 at D = 64 is 1/8, a power
    of two, so the bf16 queries carry it without rounding."""
    cfg = _hybrid(n_head=1, n_kv_head=1, d_model=64, mamba_n_heads=16,
                  attention_multiplier=1 / 64)
    assert cfg.head_dim == 64
    assert cfg.attention_multiplier * cfg.head_dim ** 0.5 == 0.125


# -- the configuration --------------------------------------------------------


def test_defaults_are_todays_and_name_no_state_space_layer():
    cfg = llama.LlamaConfig()
    assert (cfg.layer_types, cfg.ssm_layers, cfg.attention_layers,
            cfg.block_applications) == ((), 0, 32, 32)
    assert (cfg.rope, cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling,
            cfg.tie_word_embeddings) == (True, None, 1.0, 1.0, 1.0, False)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_expand,
            cfg.mamba_chunk_size, cfg.mamba_conv_bias,
            cfg.mamba_proj_bias) == (0, 0, 0, 1, 4, 2, 256, True, False)
    assert llama.program_facts(cfg, 4096) == {}
    assert not any(cfg.mixer_kind(i) == "mamba"
                   for i in range(cfg.n_layer))
    tiny = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), tiny)
    assert "lm_head" in params and "ssm" not in params["layers"][0]
    # a list in a configuration file becomes the hashable tuple a static
    # argument needs
    assert _hybrid(layer_types=["mamba", "mamba", "attention"]) == _hybrid()


def test_published_keys_count_the_parameters_of_the_cut():
    """granite-4.0-h-micro's widths, one period and 1/8 of the vocabulary,
    from shapes alone: the table of the configuration file."""
    cfg = llama.LlamaConfig(
        vocab_size=12544, n_layer=10, n_head=32, n_kv_head=8, d_model=2048,
        d_ff=8192, layer_types=("mamba",) * 9 + ("attention",),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        tie_word_embeddings=True, rope=False, attention_multiplier=1 / 64)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert llama.num_params(shapes) == 772_160_448
    mamba, attention = shapes["layers"][0], shapes["layers"][9]
    assert llama.num_params(mamba["ssm"]) == 25_847_232
    assert llama.num_params(mamba) == 76_182_976
    assert llama.num_params(attention) == 60_821_504
    assert mamba["ssm"]["in_proj"].shape == (2048, 8512)
    assert mamba["ssm"]["conv_w"].shape == (4, 4352)
    assert (cfg.ssm_layers, cfg.attention_layers, cfg.block_applications,
            cfg.head_dim) == (9, 1, 1, 64)
    assert llama.program_facts(cfg, 8192) == {
        "ssm_layers": 9, "attention_layers": 1,
        "ssm_chunks_per_sequence": 32}
    # the axes name every leaf, and nothing else
    axes = llama.param_logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, shapes)) == (
            jax.tree_util.tree_structure(jax.tree_util.tree_map(
                lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))))
    for leaf, names in zip(
            jax.tree_util.tree_leaves(shapes),
            jax.tree_util.tree_leaves(
                axes, is_leaf=lambda a: isinstance(a, tuple))):
        assert len(names) == leaf.ndim
    # 6 x the matmul parameters, the one layer's attention, the scan
    matmul = (9 * (2048 * 8512 + 4096 * 2048) + 10 * 3 * 2048 * 8192
              + 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 12544 * 2048)
    want = (6.0 * matmul + 6.0 * 2 * 4096 * 2048
            + 3.0 * 9 * (4 * 4096 * 128 + 2 * 4 * 4352))
    assert llama.flops_per_token(cfg) == pytest.approx(want, rel=1e-12)


def test_initialisation_is_the_mixers_own():
    cfg = _hybrid()
    ssm = llama.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["ssm"]
    np.testing.assert_allclose(np.asarray(jnp.exp(ssm["A_log"])),
                               np.arange(1, 9), rtol=1e-6)
    assert float(jnp.abs(ssm["D"] - 1).max()) == 0
    step = jax.nn.softplus(ssm["dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert float(jnp.abs(ssm["conv_w"]).max()) <= 0.5
    no_bias = llama.init_params(
        jax.random.PRNGKey(0), _hybrid(mamba_conv_bias=False))
    assert "conv_b" not in no_bias["layers"][0]["ssm"]
    assert "conv_b" not in llama.param_logical_axes(
        _hybrid(mamba_conv_bias=False))["layers"][0]["ssm"]


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("mamba", "attention")), "n_layer=3"),
    (dict(layer_types=("mamba", "mamba", "linear")), "layer_types"),
    (dict(mamba_n_heads=0), "mamba_n_heads"),
    (dict(mamba_d_state=0), "mamba_d_state"),
    (dict(mamba_n_groups=3), "mamba_n_groups=3"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mamba_d_head=0), "mamba_d_head"),
    (dict(loop_passes=2, exit_gate_beta=0.1), "loop_passes=2"),
    (dict(mtp_layers=1), "mtp_layers=1"),
])
def test_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        _hybrid(**over)


# -- the step: scopes, counters -----------------------------------------------


def _job(cfg):
    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, metrics=True)

    loss.program_facts = llama.program_facts(cfg, S)
    return acc.accelerate(
        loss_fn=loss, init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-2),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(dp=1)), param_specs="planner",
        devices=jax.devices()[:1])


def test_the_step_journals_the_scopes_and_hands_out_the_counters():
    cfg = _hybrid(remat_block=True)
    job = _job(cfg)
    assert {"ssm", "attention", "mlp", "lm_head_loss"} <= {
        v[1] for v in job.program["scopes"].values()}
    assert {"ssm_in", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_out"} <= set(
        job.program["subscopes"].values())
    assert (job.program["ssm_layers"], job.program["attention_layers"],
            job.program["ssm_chunks_per_sequence"]) == (2, 1, 3)
    state = job.create_state(jax.random.PRNGKey(0))
    assert "lm_head" not in state["params"]
    losses = []
    for _ in range(3):  # the same batch: its loss must fall
        state, metrics = job.train_step(state, {"tokens": _tokens()})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert np.asarray(metrics["ssm_state_rms"]).shape == (2,)
    assert float(np.min(metrics["ssm_state_rms"])) > 0
    assert 0.0 < float(metrics["ssm_decay_min"]) < 1.0


def test_a_loss_function_without_facts_journals_none():
    cfg = llama.LlamaConfig.tiny(n_layer=1, vocab_size=512, dtype=F32)
    job = acc.accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch={"tokens": np.zeros((B, S + 1), np.int32)},
        strategy=acc.Strategy(mesh=MeshSpec(dp=1)), param_specs="planner",
        devices=jax.devices()[:1])
    assert sorted(job.program) == [
        "block_applications", "collectives", "kernels", "scopes"]


# -- what cannot compute it says so -------------------------------------------

SETTINGS = {
    "layer_types": _hybrid(),
    "rope": llama.LlamaConfig.tiny(rope=False),
    "attention_multiplier": llama.LlamaConfig.tiny(
        attention_multiplier=1 / 16),
    "embedding_multiplier": llama.LlamaConfig.tiny(embedding_multiplier=12.),
    "residual_multiplier": llama.LlamaConfig.tiny(residual_multiplier=0.22),
    "logits_scaling": llama.LlamaConfig.tiny(logits_scaling=8.0),
    "tie_word_embeddings": llama.LlamaConfig.tiny(tie_word_embeddings=True),
}


@pytest.mark.parametrize("where", sorted(refusing_calls(None)))
@pytest.mark.parametrize("setting", sorted(SETTINGS))
def test_paths_without_the_state_space_layer_refuse_by_name(setting, where):
    with pytest.raises(ValueError, match=setting):
        refusing_calls(SETTINGS[setting])[where]()


@pytest.mark.parametrize("where,path", [
    ("pipeline stage", "the pipeline split"), ("kv cache", "the KV cache"),
    ("hf layout", "the HF Llama layout table")])
def test_the_refusal_names_the_mamba_layers_and_the_path(where, path):
    with pytest.raises(ValueError) as e:
        refusing_calls(_hybrid())[where]()
    assert "'mamba' entry (2 of 3 layers)" in str(e.value)
    assert path in str(e.value) and "training path only" in str(e.value)


@pytest.mark.parametrize("kw", [
    dict(segment_ids=np.zeros((B, S), np.int32)),
    dict(attn_fn=lambda *a: None)], ids=["segment_ids", "attn_fn"])
def test_a_mamba_layer_refuses_what_its_scan_does_not_know(kw):
    cfg = _hybrid()
    params = _params(cfg)
    x = jnp.zeros((B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with pytest.raises(NotImplementedError, match="'mamba' layer"):
        llama.block_apply(params["layers"][0], x, cfg, positions, **kw)
