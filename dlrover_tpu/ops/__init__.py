"""Native device kernels (Pallas TPU) with jnp reference fallbacks.

The TPU-native replacement for the reference's kernel zoo (SURVEY.md §2b
#53-54: flash-attention CUDA wrappers, Triton rmsnorm/cross-entropy,
quantization CUDA ops): each op ships

- a Pallas TPU kernel (MXU/VPU-tiled, VMEM-resident accumulators),
- a pure-jnp reference with identical numerics for CPU tests and as the
  XLA-fusion fallback,
- a dispatcher choosing by backend (``interpret=True`` runs the Pallas
  kernel on CPU for kernel-logic tests).
"""

from dlrover_tpu.ops.flash_attention import flash_attention  # noqa: F401
from dlrover_tpu.ops.rmsnorm import rmsnorm  # noqa: F401
from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: F401
