"""What the readers of the two attention kinds share: device seconds of the
traced window under each kind's scope — ``attn_window`` and ``attn_full``,
around the flash call INSIDE the block's ``attention``
(``dlrover_tpu/models/llama.py::_attention``, entered where a model has
layers of both kinds) —, every phase: forward, backward and block remat's
recomputation alike.

An XLA instruction is joined through the ``subscopes`` table of the
``accelerate.program`` event (its innermost scope), as
``harness/mla_read.py`` does for latent attention.  A Mosaic kernel's call
is joined through ``kernel_scopes`` (``{calling instruction: the innermost
scope ABOVE the kernel's own name}``): ``subscopes`` says of a kernel's call
only the kernel's name, the same under both kinds.

A program that journals no ``kernel_scopes`` naming either scope (every
configuration with one kind of attention layer, and the parent of the PR that
brought the second) yields None, and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read, trace_reduce

SCOPES = ("attn_window", "attn_full")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"attn_window", "attn_full", "flash_window", "flash_full",
    "busy_s"}``: seconds of everything under each kind's scope, and of the
    three flash kernels' calls alone under each."""
    program = obs_read.program_tables(obs_read.records(spans), trace)
    if program is None:
        return None
    of_kernel = program.get("kernel_scopes") or {}
    if not set(of_kernel.values()) & set(SCOPES):
        return None
    inner = program.get("subscopes") or {}
    kernels = trace.get("kernel_s") or {}
    out = dict.fromkeys(SCOPES + ("flash_window", "flash_full"), 0.0)
    for name, label, secs in obs_read.placed_ops(trace):
        scope = (of_kernel if label in kernels else inner).get(name)
        if scope not in SCOPES:
            continue
        out[scope] += secs
        if label in trace_reduce.FLASH_KERNELS:
            out["flash" + scope[len("attn"):]] += secs
    return dict(out, busy_s=trace["busy_s"])
