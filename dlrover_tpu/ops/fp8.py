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


def _build_fp8_dot(fwd_dot, dx_dot, dw_dot):
    """One delayed-scaling fp8 dot with custom VJP, parameterized by its
    three products, each ``(a, b, *extra) -> float32``: forward ``x @ w``
    (e4m3 x e4m3), backward ``dX = dx_dot(g, w)`` and ``dW = dw_dot(x, g)``
    with the incoming grad in e5m2.  The plain-linear and ragged-expert
    variants below differ ONLY in these products (``extra`` carries the
    ragged one's group sizes) — everything else (cast recipe, descaling,
    VJP scaffolding) is this one definition."""

    @jax.custom_vjp
    def dot(x, w, x_scale, w_scale, g_scale, *extra):
        xq = _cast_fp8(x, x_scale, E4M3)
        wq = _cast_fp8(w, w_scale, E4M3)
        out = fwd_dot(xq, wq, *extra)
        return (out * (x_scale * w_scale)).astype(x.dtype)

    def fwd(x, w, x_scale, w_scale, g_scale, *extra):
        return dot(x, w, x_scale, w_scale, g_scale, *extra), (
            x, w, x_scale, w_scale, g_scale, extra,
        )

    def bwd(res, g):
        x, w, x_scale, w_scale, g_scale, extra = res
        gq = _cast_fp8(g, g_scale, E5M2)
        wq = _cast_fp8(w, w_scale, E4M3)
        xq = _cast_fp8(x, x_scale, E4M3)
        dx = (dx_dot(gq, wq, *extra) * (g_scale * w_scale)).astype(x.dtype)
        dw = (dw_dot(xq, gq, *extra) * (x_scale * g_scale)).astype(w.dtype)
        return (dx, dw, None, None, None) + (None,) * len(extra)

    dot.defvjp(fwd, bwd)
    return dot


def _dot(dimension_numbers):
    return lambda a, b: jax.lax.dot_general(
        a, b, dimension_numbers, preferred_element_type=jnp.float32)


# x [M, K] @ w [K, N]: dX = g @ W^T, dW = X^T @ g.
_fp8_dot = _build_fp8_dot(
    _dot((((1,), (0,)), ((), ()))),
    _dot((((1,), (1,)), ((), ()))),
    _dot((((0,), (0,)), ((), ()))),
)

# x [T, D] rows sorted by expert @ w [E, D, F] over ragged groups: dX is
# the same ragged product with w transposed, dW contracts the ragged row
# dim group by group (what ``lax.ragged_dot``'s own transposes compute).
_DW_RAGGED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
_fp8_rdot = _build_fp8_dot(
    lambda xq, wq, gs: jax.lax.ragged_dot(
        xq, wq, gs, preferred_element_type=jnp.float32),
    lambda gq, wq, gs: jax.lax.ragged_dot(
        gq, wq.swapaxes(1, 2), gs, preferred_element_type=jnp.float32),
    lambda xq, gq, gs: jax.lax.ragged_dot_general(
        xq, gq, gs, _DW_RAGGED, preferred_element_type=jnp.float32),
)


def _delayed_scaling_dot(dot, x, w, state: Fp8State, *extra):
    """The ONE delayed-scaling recipe both public entry points share:
    scales applied come from the PREVIOUS amax history while the CURRENT
    tensors' amax are pushed in — keeping the cast free of a same-step
    data dependency.  The grad amax is approximated by the forward
    output's amax (a standard proxy; the true grad amax would need a
    round trip through the backward)."""
    x_scale = _scale_from_hist(state.x_hist, E4M3_MAX)
    w_scale = _scale_from_hist(state.w_hist, E4M3_MAX)
    g_scale = _scale_from_hist(state.g_hist, E5M2_MAX)
    out = dot(x, w, x_scale, w_scale, g_scale, *extra)
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


def fp8_ragged_dot(
    x: jax.Array, w: jax.Array, group_sizes: jax.Array, state: Fp8State
) -> Tuple[jax.Array, Fp8State]:
    """Grouped ``x[rows of e] @ w[e]`` over ragged groups — the MoE
    grouped-matmul analogue of :func:`fp8_dot` (the fp8 counterpart of
    ``ops.grouped_matmul.grouped_matmul_ragged``).

    Scales are per-STACKED-tensor (one amax over all experts), the
    "shared" variant: a per-expert scale would need a gather per token
    block and buys little when experts share an init distribution.
    Shapes: x [T, D] sorted by expert, w [E, D, F], group_sizes [E]
    (sum T) -> [T, F]."""
    return _delayed_scaling_dot(_fp8_rdot, x, w, state, group_sizes)


def fp8_supported() -> bool:
    """True when the backend lowers e4m3 dots natively (newer TPU gens);
    the ops still RUN elsewhere via upcast, just without the speedup."""
    kind = jax.devices()[0].device_kind.lower()
    return "v5p" in kind or "v6" in kind
