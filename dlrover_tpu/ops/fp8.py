"""FP8 training path: delayed-scaling quantized matmul with custom VJP.

The TPU-native counterpart of the reference's ``Fp8Optimization``
(``atorch/auto/opt_lib/amp_optimization.py`` fp8 region, which rewrites
eligible ``nn.Linear``s through TransformerEngine): here the primitive is
a functional ``fp8_dot`` following the standard recipe — activations and
weights cast to **e4m3** on the forward, incoming gradients to **e5m2**
on the backward (wider exponent for grad dynamic range), each tensor
descaled by a per-tensor scale derived from a rolling amax history
(delayed scaling).  XLA lowers fp8 dots to native hardware where the
generation supports it and to upcast-matmul elsewhere, so the same
program is portable across TPU generations.

Scale state is explicit and functional (an :class:`Fp8State` pytree the
caller threads through steps) — no module wrapping, no global amax
registry; it rides checkpoints like any other state.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2
E4M3_MAX = 448.0
E5M2_MAX = 57344.0

AMAX_HISTORY = 16


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Fp8State:
    """Delayed-scaling state for ONE fp8_dot site: amax history + current
    scale per operand (x, w, grad)."""

    x_hist: jax.Array
    w_hist: jax.Array
    g_hist: jax.Array

    @classmethod
    def init(cls) -> "Fp8State":
        z = jnp.zeros((AMAX_HISTORY,), jnp.float32)
        return cls(z, z, z)

    def tree_flatten(self):
        return (self.x_hist, self.w_hist, self.g_hist), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


def _scale_from_hist(hist: jax.Array, fmax: float) -> jax.Array:
    """Delayed scaling: scale = max(amax history) / fmax (with margin)."""
    amax = jnp.max(hist)
    return jnp.where(amax > 0, amax / (0.9 * fmax), 1.0)


def _push(hist: jax.Array, amax: jax.Array) -> jax.Array:
    return jnp.concatenate([hist[1:], amax[None]])


def _cast_fp8(x: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    fmax = E4M3_MAX if dtype == E4M3 else E5M2_MAX
    return jnp.clip(
        x.astype(jnp.float32) / scale, -fmax, fmax
    ).astype(dtype)


def _build_fp8_dot(fwd_dn, dx_dn, dw_dn):
    """One delayed-scaling fp8 dot with custom VJP, parameterized by
    ``dot_general`` dimension numbers: forward ``x @ w`` (e4m3 x e4m3),
    backward ``dX = g ·dx_dn w`` and ``dW = x ·dw_dn g`` with the
    incoming grad in e5m2.  The plain-linear and batched-expert variants
    below differ ONLY in these dimension numbers — everything else
    (cast recipe, descaling, VJP scaffolding) is this one definition."""

    @jax.custom_vjp
    def dot(x, w, x_scale, w_scale, g_scale):
        xq = _cast_fp8(x, x_scale, E4M3)
        wq = _cast_fp8(w, w_scale, E4M3)
        out = jax.lax.dot_general(
            xq, wq, fwd_dn, preferred_element_type=jnp.float32
        )
        return (out * (x_scale * w_scale)).astype(x.dtype)

    def fwd(x, w, x_scale, w_scale, g_scale):
        return dot(x, w, x_scale, w_scale, g_scale), (
            x, w, x_scale, w_scale, g_scale,
        )

    def bwd(res, g):
        x, w, x_scale, w_scale, g_scale = res
        gq = _cast_fp8(g, g_scale, E5M2)
        wq = _cast_fp8(w, w_scale, E4M3)
        xq = _cast_fp8(x, x_scale, E4M3)
        dx = jax.lax.dot_general(
            gq, wq, dx_dn, preferred_element_type=jnp.float32
        )
        dx = (dx * (g_scale * w_scale)).astype(x.dtype)
        dw = jax.lax.dot_general(
            xq, gq, dw_dn, preferred_element_type=jnp.float32
        )
        dw = (dw * (x_scale * g_scale)).astype(w.dtype)
        return dx, dw, None, None, None

    dot.defvjp(fwd, bwd)
    return dot


# x [M, K] @ w [K, N]: dX = g @ W^T, dW = X^T @ g.
_fp8_dot = _build_fp8_dot(
    (((1,), (0,)), ((), ())),
    (((1,), (1,)), ((), ())),
    (((0,), (0,)), ((), ())),
)

# x [E, C, D] @ w [E, D, F], batched over the expert dim: dX contracts
# F, dW contracts C, both carrying E as the batch dim.
_fp8_bdot = _build_fp8_dot(
    (((2,), (1,)), ((0,), (0,))),
    (((2,), (2,)), ((0,), (0,))),
    (((1,), (1,)), ((0,), (0,))),
)


def _delayed_scaling_dot(dot, x, w, state: Fp8State):
    """The ONE delayed-scaling recipe both public entry points share:
    scales applied come from the PREVIOUS amax history while the CURRENT
    tensors' amax are pushed in — keeping the cast free of a same-step
    data dependency.  The grad amax is approximated by the forward
    output's amax (a standard proxy; the true grad amax would need a
    round trip through the backward)."""
    x_scale = _scale_from_hist(state.x_hist, E4M3_MAX)
    w_scale = _scale_from_hist(state.w_hist, E4M3_MAX)
    g_scale = _scale_from_hist(state.g_hist, E5M2_MAX)
    out = dot(x, w, x_scale, w_scale, g_scale)
    new_state = Fp8State(
        x_hist=_push(
            state.x_hist, jnp.max(jnp.abs(x)).astype(jnp.float32)
        ),
        w_hist=_push(
            state.w_hist, jnp.max(jnp.abs(w)).astype(jnp.float32)
        ),
        g_hist=_push(
            state.g_hist, jnp.max(jnp.abs(out)).astype(jnp.float32)
        ),
    )
    return out, new_state


def fp8_dot(
    x: jax.Array, w: jax.Array, state: Fp8State
) -> Tuple[jax.Array, Fp8State]:
    """``x [M, K] @ w [K, N]`` with both operands in e4m3 and the
    backward in e5m2 (delayed scaling).  Returns (output, new_state)."""
    return _delayed_scaling_dot(_fp8_dot, x, w, state)


def fp8_batched_dot(
    x: jax.Array, w: jax.Array, state: Fp8State
) -> Tuple[jax.Array, Fp8State]:
    """Per-expert batched ``x[e] @ w[e]`` — the MoE grouped-matmul
    analogue of :func:`fp8_dot`.

    Scales are per-STACKED-tensor (one amax over all experts), the
    "shared" variant: a per-expert scale would need a gather per token
    block and buys little when experts share an init distribution.
    Shapes: x [E, C, D], w [E, D, F] -> [E, C, F]."""
    return _delayed_scaling_dot(_fp8_bdot, x, w, state)


def fp8_supported() -> bool:
    """True when the backend lowers e4m3 dots natively (newer TPU gens);
    the ops still RUN elsewhere via upcast, just without the speedup."""
    kind = jax.devices()[0].device_kind.lower()
    return "v5p" in kind or "v6" in kind
