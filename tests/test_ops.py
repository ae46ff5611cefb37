"""Kernel tests: Pallas kernels validated in interpret mode against the jnp
references, plus VJP checks and quantized-optimizer behaviour."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy
from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from dlrover_tpu.ops.grouped_matmul import (
    grouped_matmul_ragged,
)
from dlrover_tpu.ops.quant import (
    adam8bit,
    dequantize_blockwise,
    quantize_blockwise,
)
from dlrover_tpu.ops.rmsnorm import rmsnorm


def _qkv(B=1, H=2, S=64, D=16, seed=0):
    rng = jax.random.PRNGKey(seed)
    return tuple(
        jax.random.normal(jax.random.fold_in(rng, i), (B, H, S, D),
                          jnp.float32)
        for i in range(3)
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_pallas_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = reference_attention(q, k, v, causal)
        out = flash_attention(
            q, k, v, causal=causal, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_uneven_blocks(self):
        q, k, v = _qkv(S=48)
        ref = reference_attention(q, k, v, True)
        out = flash_attention(
            q, k, v, causal=True, backend="pallas",
            block_q=32, block_k=32, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_vjp_matches_reference(self):
        q, k, v = _qkv(S=32)

        def f_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, True) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, backend="pallas",
                                block_q=16, block_k=16, interpret=True) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S,bq,bk", [(64, 16, 16), (48, 32, 16),
                                         (40, 16, 32)])
    def test_pallas_bwd_matches_reference_bwd(self, causal, S, bq, bk):
        from dlrover_tpu.ops.flash_attention import (
            _flash_bwd_pallas,
            _flash_bwd_reference,
            _flash_fwd,
        )

        q, k, v = _qkv(B=2, H=2, S=S, D=16, seed=3)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
        out, lse = _flash_fwd(q, k, v, causal, bq, bk, True)
        want = _flash_bwd_reference(q, k, v, out, lse, g, causal)
        got = _flash_bwd_pallas(q, k, v, out, lse, g, causal, bq, bk, True)
        for a, b, name in zip(got, want, "dq dk dv".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, err_msg=name
            )

    @pytest.mark.parametrize("causal", [True, False])
    def test_segment_ids_match_reference(self, causal):
        """Packed sequences: two segments per row, ragged boundaries not
        on block edges."""
        q, k, v = _qkv(S=48)
        B, S = q.shape[0], q.shape[2]
        seg = np.zeros((B, S), np.int32)
        for b in range(B):
            seg[b, 17 + 3 * b:] = 1  # per-row ragged boundary
        seg = jnp.asarray(seg)
        ref = reference_attention(q, k, v, causal, seg)
        out = flash_attention(
            q, k, v, causal=causal, segment_ids=seg, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_segment_ids_grads_match(self):
        q, k, v = _qkv(S=32)
        B, S = q.shape[0], q.shape[2]
        seg = jnp.asarray(
            np.repeat(np.arange(2), S // 2)[None].repeat(B, 0)
        )

        def f_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, True, seg) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, segment_ids=seg,
                    backend="pallas", block_q=16, block_k=16,
                    interpret=True,
                ) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_segment_isolation(self):
        """Changing segment-1 keys must not change segment-0 outputs."""
        q, k, v = _qkv(S=32)
        B, S = q.shape[0], q.shape[2]
        half = S // 2
        seg = jnp.asarray(
            np.repeat(np.arange(2), half)[None].repeat(B, 0)
        )
        out1 = flash_attention(
            q, k, v, causal=True, segment_ids=seg, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        k2 = k.at[:, :, half:].set(
            jax.random.normal(jax.random.PRNGKey(99),
                              k[:, :, half:].shape, k.dtype)
        )
        out2 = flash_attention(
            q, k2, v, causal=True, segment_ids=seg, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out1[:, :, :half]), np.asarray(out2[:, :, :half]),
            atol=1e-6,
        )

    def test_bwd_no_full_score_matrix(self):
        # The custom-VJP backward must be the blocked Pallas path: peak
        # live memory in its jaxpr should never include a [B,H,S,S] array.
        q, k, v = _qkv(B=1, H=1, S=64, D=16)

        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, backend="pallas",
                                block_q=16, block_k=16, interpret=True)
            )

        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        for eqn in jaxpr.jaxpr.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                assert not (len(shape) >= 2 and shape[-1] == 64
                            and shape[-2] == 64), (
                    f"full score matrix materialized: {eqn.primitive}"
                )


class TestRMSNorm:
    def test_pallas_matches_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
        ref = rmsnorm(x, w, backend="reference")
        out = rmsnorm(x, w, backend="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_grad_matches_autodiff(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        w = jnp.ones((64,)) * 1.3

        def explicit(x, w):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
            return jnp.sum((xf * jax.lax.rsqrt(ms + 1e-6) * w) ** 2)

        def fused(x, w):
            return jnp.sum(rmsnorm(x, w, backend="reference") ** 2)

        gx_ref, gw_ref = jax.grad(explicit, (0, 1))(x, w)
        gx, gw = jax.grad(fused, (0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                                   atol=1e-4)


class TestCrossEntropy:
    def test_pallas_matches_reference(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (6, 32, 128))
        labels = jax.random.randint(jax.random.PRNGKey(1), (6, 32), 0, 128)
        ref = softmax_cross_entropy(logits, labels, backend="reference")
        out = softmax_cross_entropy(
            logits, labels, backend="pallas", interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_grad(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        labels = jax.random.randint(jax.random.PRNGKey(1), (4,), 0, 16)

        def f(l):
            return jnp.mean(softmax_cross_entropy(l, labels,
                                                  backend="reference"))

        g = jax.grad(f)(logits)
        # Gradient rows sum to ~0 (softmax - onehot property).
        np.testing.assert_allclose(np.asarray(jnp.sum(g, -1)),
                                   np.zeros(4), atol=1e-6)

    def test_fused_linear_xent_matches_unfused(self):
        from dlrover_tpu.ops.cross_entropy import (
            linear_softmax_cross_entropy,
        )

        D, V = 16, 64
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 10, D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.1
        labels = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0, V)
        # chunk_rows=8 forces multiple chunks + row padding (30 rows).
        fused = linear_softmax_cross_entropy(x, w, labels, chunk_rows=8)
        ref = softmax_cross_entropy(x @ w, labels, backend="reference")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   atol=1e-5)

    def test_fused_linear_xent_grads_match(self):
        from dlrover_tpu.ops.cross_entropy import (
            linear_softmax_cross_entropy,
        )

        D, V = 12, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (26, D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.2
        labels = jax.random.randint(jax.random.PRNGKey(2), (26,), 0, V)

        def fused(x, w):
            return jnp.mean(
                linear_softmax_cross_entropy(x, w, labels, chunk_rows=8)
            )

        def unfused(x, w):
            return jnp.mean(
                softmax_cross_entropy(x @ w, labels, backend="reference")
            )

        gx, gw = jax.grad(fused, argnums=(0, 1))(x, w)
        gx_ref, gw_ref = jax.grad(unfused, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                                   atol=1e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, jit, custom_vjp)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def head_matmuls_and_scans(fn, *args, vocab):
    """(dot_generals with a vocab-sized operand or result dim inside a
    scan, scans holding one, whether such a scan carries a [D, V] array) —
    the structure the reduced lm-head loss is pinned to."""
    closed = jax.make_jaxpr(fn)(*args)
    dots, scans, carries = 0, 0, False
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params["jaxpr"].jaxpr
        n = sum(
            1 for e in _eqns(body)
            if e.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*e.invars, *e.outvars))
        )
        if not n:
            continue
        dots, scans = dots + n, scans + 1
        nc, k = eqn.params["num_consts"], eqn.params["num_carry"]
        carries |= any(
            v.aval.ndim == 2 and v.aval.shape[-1] == vocab
            for v in eqn.invars[nc:nc + k])
    return dots, scans, carries


class TestLinearXentSum:
    """The reduced form of the fused lm-head loss: its forward rule forms
    dx and dw in the scan that computes the loss."""

    R, D, V, CHUNK = 26, 12, 32, 8  # 4 chunks, 6 rows of padding

    def _data(self, kind, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (self.R, self.D)).astype(dtype)
        w = (jax.random.normal(ks[1], (self.D, self.V)) * 0.2).astype(dtype)
        labels = jax.random.randint(ks[2], (self.R,), 0, self.V)
        if kind == "none":
            weights = None
        elif kind == "mask":  # 0/1, the whole second chunk masked
            weights = jnp.ones((self.R,)).at[8:16].set(0.0).at[3].set(0.0)
        else:
            weights = jax.random.uniform(ks[3], (self.R,)) / self.R
        return x, w, labels, weights

    @staticmethod
    def _reduce(per_tok, weights):
        if weights is None:
            return jnp.mean(per_tok)
        return jnp.sum(per_tok * weights)

    def _losses(self, labels, weights):
        from dlrover_tpu.ops.cross_entropy import (
            linear_softmax_cross_entropy,
            linear_softmax_cross_entropy_sum,
        )

        def reduced(x, w, labels=labels, weights=weights):
            return linear_softmax_cross_entropy_sum(
                x, w, labels, weights, chunk_rows=self.CHUNK)

        def per_token(x, w, labels=labels, weights=weights):
            return self._reduce(linear_softmax_cross_entropy(
                x, w, labels, chunk_rows=self.CHUNK), weights)

        def unfused(x, w, labels=labels, weights=weights):
            logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
            return self._reduce(softmax_cross_entropy(
                logits, labels, backend="reference"), weights)

        return reduced, per_token, unfused

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("kind", ["none", "mask", "fractional"])
    def test_value_is_the_weighted_sum_of_the_per_token_op(
            self, kind, dtype):
        x, w, labels, weights = self._data(kind, dtype)
        reduced, per_token, unfused = self._losses(labels, weights)
        got = reduced(x, w)
        assert got.shape == () and got.dtype == jnp.float32
        np.testing.assert_allclose(got, per_token(x, w), rtol=1e-5)
        np.testing.assert_allclose(
            got, unfused(x, w),
            rtol=1e-5 if dtype == jnp.float32 else 2e-2)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("kind", ["none", "mask", "fractional"])
    @pytest.mark.parametrize(
        "wrap", ["plain", "checkpoint", "microbatch_scan", "mesh_2x2"])
    def test_grads_match_the_per_token_path(self, wrap, kind, dtype):
        """(dx, dw) under an upstream scalar that is not 1 (3 * loss +
        another term), alone, under jax.checkpoint, inside a grad-accum
        style scan over microbatches, and on a 2 x 2 (fsdp, tp) mesh."""
        x, w, labels, weights = self._data(kind, dtype)
        reduced, per_token, unfused = self._losses(labels, weights)

        def total(head):
            def f(x, w, *lw):
                return 3.0 * head(x, w, *lw) + 0.1 * jnp.sum(
                    x.astype(jnp.float32) ** 2) + jnp.sum(
                    w.astype(jnp.float32))
            return f

        def grads(head):
            f = total(head)
            if wrap == "plain":
                return jax.grad(f, (0, 1))(x, w)
            if wrap == "checkpoint":
                return jax.grad(jax.checkpoint(f), (0, 1))(x, w)
            if wrap == "mesh_2x2":
                from jax.sharding import Mesh, NamedSharding
                from jax.sharding import PartitionSpec as P
                mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                            ("fsdp", "tp"))
                xs = jax.device_put(
                    jnp.pad(x, ((0, 2), (0, 0))),  # 28 rows over fsdp
                    NamedSharding(mesh, P("fsdp", None)))
                ws = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))
                gx, gw = jax.jit(jax.grad(
                    lambda xp, w: f(xp[:self.R], w), (0, 1)))(xs, ws)
                return gx[:self.R], gw
            # two microbatches of 13 rows, gradients summed by the scan
            half = self.R // 2
            lw = (labels.reshape(2, half),
                  (jnp.full((self.R,), 1.0 / self.R) if weights is None
                   else weights).reshape(2, half))

            def body(acc, mb):
                x_mb, l_mb, w_mb = mb
                gx, gw = jax.grad(f, (0, 1))(x_mb, w, l_mb, w_mb)
                return acc + gw.astype(jnp.float32), gx
            gw, gx = jax.lax.scan(
                body, jnp.zeros(w.shape, jnp.float32),
                (x.reshape(2, half, self.D), *lw))
            return gx.reshape(self.R, self.D), gw

        got, same_dtypes, ref = grads(reduced), grads(per_token), grads(
            unfused)
        tol = dict(atol=1e-5) if dtype == jnp.float32 else dict(
            atol=2e-2, rtol=2e-2)
        for g, a, b in zip(got, same_dtypes, ref):
            assert g.dtype == a.dtype and g.shape == a.shape
            g, a, b = (np.asarray(t, np.float32) for t in (g, a, b))
            np.testing.assert_allclose(g, a, **tol)
            np.testing.assert_allclose(g, b, **tol)

    def test_the_weights_gradient_is_each_rows_own_loss(self):
        """Until PR 33 the weights were constants of the loss; a looped
        model's are its exit probabilities, and their cotangent is the
        row loss the forward scan has in hand."""
        x, w, labels, weights = self._data("fractional", jnp.float32)
        reduced, per_token, _ = self._losses(labels, weights)
        g = jax.grad(lambda wt: reduced(x, w, weights=wt))(weights)
        want = jax.grad(lambda wt: per_token(x, w, weights=wt))(weights)
        np.testing.assert_allclose(g, want, rtol=1e-6)
        assert float(jnp.min(g)) > 0.0  # a cross-entropy each

    def test_the_smoke_check_compares_both_ops(self, capsys):
        """``python -m dlrover_tpu.ops.smoke`` at a toy shape: several
        chunks, the two ops a rounding apart."""
        from dlrover_tpu.ops.smoke import run_head_gradient_check

        (res,) = run_head_gradient_check(((2500, 64, 512),))
        assert res["ok"] and res["shape"] == [2500, 64, 512]
        assert max(res["loss_rel"], res["dx_rel_l2"],
                   res["dw_rel_l2"]) < 1e-3
        assert "HEAD_GRADIENT_CHECK" in capsys.readouterr().out

    @pytest.mark.parametrize("grad", [True, False])
    def test_three_matmuls_and_one_scan_with_a_gradient_one_without(
            self, grad):
        x, w, labels, weights = self._data("mask", jnp.bfloat16)
        reduced, per_token, _ = self._losses(labels, weights)
        wrap = (lambda f: jax.value_and_grad(f, (0, 1))) if grad else (
            lambda f: f)
        assert head_matmuls_and_scans(
            wrap(reduced), x, w, vocab=self.V
        ) == ((3, 1, True) if grad else (1, 1, False))
        # the per-token op: recompute backward, a scan each way
        assert head_matmuls_and_scans(
            wrap(per_token), x, w, vocab=self.V
        ) == ((4, 2, True) if grad else (1, 1, False))


class TestQuant:
    def test_quant_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
        codes, scale = quantize_blockwise(x)
        back = dequantize_blockwise(codes, scale, x.shape)
        err = np.abs(np.asarray(back) - np.asarray(x))
        per_block_max = 3.0 * 4 / 127  # conservative bound
        assert err.max() < per_block_max

    def test_adam8bit_learns(self):
        params = {"w": jnp.array([2.0, -3.0, 1.0])}
        tx = adam8bit(0.1)
        state = tx.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        import optax

        for _ in range(50):
            g = jax.grad(loss)(params)
            updates, state = tx.update(g, state, params)
            params = optax.apply_updates(params, updates)
        assert float(loss(params)) < 0.05

    def test_adam8bit_state_is_int8(self):
        params = {"w": jnp.zeros((300,))}
        tx = adam8bit(0.01)
        state = tx.init(params)
        assert state.mu["w"].codes.dtype == jnp.int8
        assert state.mu["w"].codes.shape == (3, 128)  # ceil(300/128) blocks

    def test_pallas_matches_jnp_path(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(1000) * 10, jnp.float32)
        cj, sj = quantize_blockwise(x, backend="jnp")
        cp, sp = quantize_blockwise(x, backend="pallas", interpret=True)
        np.testing.assert_array_equal(np.asarray(cj), np.asarray(cp))
        np.testing.assert_allclose(
            np.asarray(sj), np.asarray(sp), rtol=1e-6
        )
        back = dequantize_blockwise(cp, sp, x.shape)
        assert float(jnp.max(jnp.abs(back - x))) <= float(
            jnp.max(sp)
        )  # within one quantization step


class TestGroupedMatmul:
    def test_kernel_matches_reference(self):
        """The TPU path (megablox gmm, interpreted here) against
        ``lax.ragged_dot``, forward and both gradients, with an empty
        group and groups that end inside a row tile."""
        tokens = jax.random.normal(
            jax.random.PRNGKey(0), (1024, 256), jnp.bfloat16)
        w = (0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (4, 256, 128))).astype(jnp.bfloat16)
        sizes = jnp.array([300, 0, 217, 507], jnp.int32)

        def loss(backend):
            return lambda t, w_: jnp.sum(jnp.square(grouped_matmul_ragged(
                t, w_, sizes, backend=backend, interpret=True
            ).astype(jnp.float32)))

        got = jax.value_and_grad(loss("pallas"), argnums=(0, 1))(tokens, w)
        ref = jax.value_and_grad(loss("reference"), argnums=(0, 1))(
            tokens, w)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            a, b = (np.asarray(v, np.float32) for v in (a, b))
            assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b)

    def test_ragged_matches_loop(self):
        tokens = jax.random.normal(jax.random.PRNGKey(0), (10, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 4))
        sizes = jnp.array([3, 0, 7], jnp.int32)
        out = grouped_matmul_ragged(tokens, w, sizes)
        ref = jnp.concatenate([tokens[:3] @ w[0], tokens[3:] @ w[2]])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestGQAFlashAttention:
    def _gqa(self, B=2, H=4, KV=2, S=32, D=8, seed=5):
        rng = jax.random.PRNGKey(seed)
        q = jax.random.normal(jax.random.fold_in(rng, 0), (B, H, S, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, S, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, S, D))
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_matches_repeated_reference(self, causal):
        q, k, v = self._gqa()
        ref = reference_attention(q, k, v, causal)  # repeats internally
        out = flash_attention(
            q, k, v, causal=causal, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_gqa_grads_match_reference(self):
        q, k, v = self._gqa()

        def f_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, True) ** 2)

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, backend="pallas",
                                block_q=16, block_k=16,
                                interpret=True) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        # dk/dv keep the compact [B, KV, S, D] shape.
        assert g_out[1].shape == k.shape and g_out[2].shape == v.shape
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_gqa_with_segments(self):
        q, k, v = self._gqa(S=32)
        B, S = q.shape[0], q.shape[2]
        seg = jnp.asarray(
            np.repeat(np.arange(2), S // 2)[None].repeat(B, 0)
        )
        ref = reference_attention(q, k, v, True, seg)
        out = flash_attention(
            q, k, v, causal=True, segment_ids=seg, backend="pallas",
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_bad_head_ratio_rejected(self):
        q, k, v = self._gqa(H=4, KV=3)
        with pytest.raises(ValueError, match="GQA"):
            flash_attention(q, k, v, backend="pallas", interpret=True)


class TestSlidingWindow:
    """Sliding-window attention (the reference flash wrappers' window
    support): q attends keys with 0 <= q-k < window; kernels skip blocks
    entirely outside the window."""

    @pytest.mark.parametrize("window", [1, 7, 16, 33])
    def test_fwd_matches_reference(self, window):
        q, k, v = _qkv(S=48)
        ref = reference_attention(q, k, v, True, window=window)
        out = flash_attention(
            q, k, v, causal=True, backend="pallas",
            block_q=16, block_k=16, interpret=True, window=window,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("window", [5, 16])
    def test_vjp_matches_reference(self, window):
        q, k, v = _qkv(S=32)

        def f_ref(q, k, v):
            return jnp.sum(
                reference_attention(q, k, v, True, window=window) ** 2
            )

        def f_flash(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, backend="pallas",
                    block_q=16, block_k=16, bwd_block_q=16,
                    bwd_block_k=16, interpret=True, window=window,
                ) ** 2
            )

        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5)

    def test_window_with_segments_and_gqa(self):
        """window composes with packed-segment masks and GQA heads."""
        rng = jax.random.PRNGKey(3)
        B, H, KV, S, D = 2, 4, 2, 32, 8
        q = jax.random.normal(rng, (B, H, S, D), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, S, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, S, D))
        seg = jnp.asarray(
            np.repeat(np.arange(4), 8)[None, :].repeat(2, 0)
        )
        ref = reference_attention(q, k, v, True, segment_ids=seg,
                                  window=6)
        out = flash_attention(
            q, k, v, causal=True, segment_ids=seg, backend="pallas",
            block_q=16, block_k=16, interpret=True, window=6,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_window_requires_causal(self):
        q, k, v = _qkv(S=16)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=4)


#: name -> (H, KV, S, D, Dv, window, segmented, backward blocks q and k):
#: the head sizes, groups and windows the cells run, two or more blocks each
#: way (the defaults, 256 and 512, at 2,048 positions), B = 1
DKV_CASES = {
    "head64_group4": (4, 1, 256, 64, 64, 0, False, 64, 128),
    "head128_group1": (2, 2, 256, 128, 128, 0, False, 64, 128),
    "head128_group16": (16, 1, 256, 128, 128, 0, False, 64, 128),
    "head192_value128": (2, 2, 256, 192, 128, 0, False, 64, 128),
    "head256_group1": (1, 1, 256, 256, 256, 0, False, 64, 128),
    "window1024_of_2048_group4": (4, 1, 2048, 128, 128, 1024, False,
                                  256, 512),
    "window_past_the_sequence": (2, 1, 256, 128, 128, 1000, False, 64, 128),
    "ragged_300_pads_to_512": (4, 2, 300, 64, 64, 0, False, 64, 128),
    "ragged_300_window_100": (2, 1, 300, 128, 128, 100, False, 64, 128),
    "segments_group4": (4, 1, 256, 128, 128, 0, True, 64, 128),
    "segments_ragged_window": (2, 2, 300, 64, 64, 100, True, 64, 128),
    # a window layer's dkv call holds a SPAN of Q and dO (the rows a key
    # block can reach, ``_dkv_query_rows``) shorter than the padded
    # sequence: 256 of 512 rows up to a window of 129, the window's edge
    # on the key block's boundary at 128, and 320 rows from 130 on; the
    # last key blocks read a span whose start is held at S_pad - rows
    "window127_of_512": (2, 1, 512, 64, 64, 127, False, 64, 128),
    "window128_of_512_on_the_key_block": (2, 1, 512, 64, 64, 128, False,
                                          64, 128),
    "window129_of_512_fills_its_span": (2, 1, 512, 64, 64, 129, False,
                                        64, 128),
    "window130_of_512_one_block_more": (2, 1, 512, 64, 64, 130, False,
                                        64, 128),
    "window200_of_1024_group8": (8, 1, 1024, 128, 128, 200, False, 64, 128),
    "window64_of_512_three_clamped_blocks": (4, 2, 512, 128, 128, 64, False,
                                             64, 128),
    "ragged_300_window_65_group4": (4, 1, 300, 64, 64, 65, False, 64, 128),
    "segments_window128_group4": (4, 1, 512, 128, 128, 128, True, 64, 128),
    "window100_query_block_wider": (2, 1, 512, 64, 64, 100, False, 128, 64),
    "window100_head192_value128": (2, 2, 512, 192, 128, 100, False, 64, 128),
}

#: name -> ((B, H, KV, S, D, Dv, window), rows of Q and dO a grid step of
#: ``flash_bwd_dkv`` holds at the backward's default blocks): the window
#: cells' flash calls, and the calls that keep the whole sequence
DKV_FETCHED = {
    "mellum_window": ((1, 32, 4, 16384, 128, 128, 1024), 1536),
    "trinity_window": ((1, 32, 4, 16384, 128, 128, 2048), 2560),
    "mistral_window": ((2, 32, 8, 8192, 128, 128, 4096), 4608),
    "mellum_full": ((1, 32, 4, 16384, 128, 128, 0), 16384),
    "kimi_latent": ((1, 32, 32, 16384, 192, 128, 0), 16384),
    "window_past_the_sequence": ((1, 32, 4, 8192, 128, 128, 8192), 8192),
    "window_that_reaches_every_row": ((1, 8, 2, 2048, 128, 128, 1537),
                                      2048),
    "ragged_3000_window_1024": ((1, 8, 2, 3000, 128, 128, 1024), 1536),
}


def _dkv_operands(case, dtype):
    H, KV, S, D, Dv, window, segmented, bq, bk = DKV_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(64), 4)
    dims = ((1, H, S, D), (1, KV, S, D), (1, KV, S, Dv), (1, H, S, Dv))
    q, k, v, cot = (jax.random.normal(key, d, jnp.float32)
                    for key, d in zip(keys, dims))
    # dk and dv sum a group's heads: of order 1 at every group size
    q, k, v, cot = (t.astype(dtype)
                    for t in (q, k, v, cot * (H // KV) ** -0.5))
    seg = None
    if segmented:  # three documents, no boundary on a block's edge
        seg = jnp.asarray(np.searchsorted([S // 3 + 5, 2 * S // 3 - 7],
                                          np.arange(S), "right")[None])
    return (q, k, v, cot), dict(segment_ids=seg, window=window), (bq, bk)


class TestFlashDkvKeyMajor:
    """``flash_bwd_dkv`` works on ``[block_k, block_q]`` arrays: ``dk`` and
    ``dv`` (and ``dq`` beside them) against float32 autodiff of the
    reference, and the two properties of the kernel's body that the
    orientation buys."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("case", list(DKV_CASES))
    def test_gradients_match_float32_autodiff(self, case, dtype):
        (q, k, v, cot), kw, (bq, bk) = _dkv_operands(case, dtype)
        f32 = [t.astype(jnp.float32) for t in (q, k, v)]
        _, pull = jax.vjp(
            lambda q, k, v: reference_attention(
                q, k, v, True, kw["segment_ids"], kw["window"]), *f32)
        want = pull(cot.astype(jnp.float32))
        _, pull = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, backend="pallas", interpret=True,
                block_q=bk, block_k=bk, bwd_block_q=bq, bwd_block_k=bk,
                **kw), q, k, v)
        got = pull(cot)
        # the file's tolerances: float32 gradients, bfloat16 operands
        tol = dict(atol=5e-4) if dtype == jnp.float32 else dict(
            atol=2e-2, rtol=2e-2)
        for a, b, name in zip(got, want, ("dq", "dk", "dv")):
            assert a.dtype == dtype and a.shape == b.shape
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b), err_msg=name,
                **tol)

    @pytest.mark.parametrize("case", ["head128_group16",
                                      "head192_value128",
                                      "segments_ragged_window"])
    def test_no_transposed_product_and_no_float32_into_the_mxu(self, case):
        """Every product of the kernel's body contracts its LEFT operand
        over its LAST axis (one contracted over its first is transposed on
        the way in, a whole ``[block_q, block_k]`` float32 array a
        product), nothing is transposed by name, and with bfloat16 operands
        no product is handed a float32 array."""
        from dlrover_tpu.ops.flash_attention import (
            _DKV_UNROLL,
            _flash_bwd_pallas,
            _flash_fwd,
        )

        (q, k, v, cot), kw, (bq, bk) = _dkv_operands(case, jnp.bfloat16)
        out, lse = _flash_fwd(q, k, v, True, bk, bk, True, **kw)
        jaxpr = jax.make_jaxpr(lambda *a: _flash_bwd_pallas(
            *a, True, bq, bk, True, **kw))(q, k, v, out, lse, cot)
        (body,) = [e.params["jaxpr"] for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "pallas_call"
                   and e.params["name"] == "flash_bwd_dkv"]
        products = [e for e in _eqns(body)
                    if e.primitive.name == "dot_general"]
        # s^T, dv, dp^T and dk of a query block: the blocks of one turn of
        # the loop, and the loop of the blocks left over
        assert len(products) == 4 * (_DKV_UNROLL + 1)
        for e in products:
            (lhs, _), batch = e.params["dimension_numbers"]
            assert tuple(lhs) == (1,) and batch == ((), ())
            assert [str(x.aval.dtype) for x in e.invars] == ["bfloat16"] * 2
            assert str(e.outvars[0].aval.dtype) == "float32"
            assert e.params["precision"] is None
        assert not [e for e in _eqns(body)
                    if e.primitive.name == "transpose"]


    @pytest.mark.parametrize("case", list(DKV_FETCHED))
    def test_rows_of_q_and_do_a_grid_step_holds(self, case):
        """A window layer's call is handed, a grid step, the rows of Q and dO
        its key block can reach, from the key block's first row on and never
        past the array's end; every other call the head's whole padded
        sequence under the block specs it always had (the group's ``r`` axis
        is innermost, so either block is fetched anew every step)."""
        import importlib

        from jax.experimental import pallas as pl

        from tools.flash_bench import dkv_call

        # (the package exports the function under the module's name)
        fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
        shape, rows = DKV_FETCHED[case]
        B, H, KV, S, D, Dv, _ = shape
        call = dkv_call(fa, shape)
        block_q, block_k, S_pad = fa._block_sizes(
            S, fa.DEFAULT_BWD_BLOCK_Q, fa.DEFAULT_BWD_BLOCK_K)
        n_k = S_pad // block_k
        maps = call.params["grid_mapping"].block_mappings
        for bm, width in ((maps[0], D), (maps[3], Dv)):
            assert bm.array_aval.shape == (B * KV, H // KV, S_pad, width)
            assert bm.block_aval.shape == (1, 1, rows, width)
            spanned = rows < S_pad
            kind = pl.Element if spanned else pl.Blocked
            assert all(isinstance(d, kind) for d in bm.block_shape)
            index = bm.index_map_jaxpr
            for i in (0, 1, n_k // 2, n_k - 2, n_k - 1):
                b, r, row, col = (int(x) for x in jax.core.eval_jaxpr(
                    index.jaxpr, index.consts, B * KV - 1, i, 3))
                assert (b, r, col) == (B * KV - 1, 3, 0)
                assert row == (min(i * block_k, S_pad - rows)
                               if spanned else 0)
                assert row % block_q == 0 and row + rows <= S_pad
        # lse and delta: a head's whole row, as ever
        for bm in maps[4:6]:
            assert bm.block_aval.shape == (1, 1, 1, S_pad)
        # the span fits the compiler's own VMEM limit; a whole sequence of
        # 16,384 asks for more, as it did
        raised = "vmem_limit_bytes" in str(call.params["compiler_params"])
        assert raised == (2 * rows * (D + Dv) * 2 + 5 * 2 ** 20
                          > 16 * 2 ** 20)

    @pytest.mark.parametrize("query", [256, 319, 511],
                             ids=["last_block_of_the_span", "inside_a_span",
                                  "clamped_span"])
    def test_the_windows_edge_in_dk_and_dv(self, query):
        """One query row's cotangent reaches exactly the keys it sees: the
        key ``window - 1`` behind it (in another key block, whose span of
        query rows ENDS with this query's block) gets a ``dk`` and a ``dv``,
        the key ``window`` behind it and every key after the query none."""
        window, S = 130, 512
        keys = jax.random.split(jax.random.PRNGKey(66), 4)
        q, k, v, cot = (jax.random.normal(key, (1, 2, S, 64), jnp.float32)
                        for key in keys)
        k, v = k[:, :1], v[:, :1]
        cot = cot * (jnp.arange(S) == query)[None, None, :, None]
        _, pull = jax.vjp(
            lambda k, v: flash_attention(
                q, k, v, causal=True, backend="pallas", interpret=True,
                block_q=128, block_k=128, bwd_block_q=64, bwd_block_k=128,
                window=window), k, v)
        _, want = jax.vjp(
            lambda k, v: reference_attention(q, k, v, True, None, window),
            k, v)
        first = query - window + 1
        for got, ref, name in zip(pull(cot), want(cot), ("dk", "dv")):
            got = np.asarray(got[0, 0])
            np.testing.assert_allclose(got, np.asarray(ref[0, 0]),
                                       atol=5e-4, err_msg=name)
            seen = np.abs(got).max(axis=-1) > 0
            assert seen[first] and seen[query], name
            assert not seen[:first].any() and not seen[query + 1:].any(), \
                name
            assert seen[first:query + 1].all(), name


class TestSlidingWindowLlama:
    def test_llama_windowed_loss_and_decode_parity(self):
        """LlamaConfig.sliding_window flows through training (flash path)
        and the KV-cache decoder: both must agree with the windowed
        reference attention."""
        from dlrover_tpu.models import llama, llama_infer

        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=4, dtype=jnp.float32,
            sliding_window=8, max_seq_len=64,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size
        )
        # Training loss: the flash path (interpret not needed — CPU auto
        # routes to the reference backend, which honors the window).
        loss_w = float(llama.loss_fn(
            params, {"tokens": tokens}, cfg, moe_aux_weight=0.0
        ))
        import dataclasses as dc

        cfg_full = dc.replace(cfg, sliding_window=0)
        loss_full = float(llama.loss_fn(
            params, {"tokens": tokens}, cfg_full, moe_aux_weight=0.0
        ))
        assert np.isfinite(loss_w) and abs(loss_w - loss_full) > 1e-4

        # Decode: cached greedy generation under the window must match
        # token-by-token argmax over the windowed full forward.
        prompts = tokens[:, :9]
        got = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=5, temperature=0.0
        )
        seq = prompts
        for _ in range(5):
            logits, _ = llama.forward(params, seq, cfg)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)
            seq = jnp.concatenate(
                [seq, nxt[:, None].astype(seq.dtype)], axis=1
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))

    def test_sliding_window_rejected_on_sp_paths(self, ):
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(n_layer=1, sliding_window=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 17), jnp.int32)
        with pytest.raises(NotImplementedError, match="sliding_window"):
            llama.loss_fn(params, {"tokens": tokens}, cfg,
                          attn_impl="ring", mesh=object())
