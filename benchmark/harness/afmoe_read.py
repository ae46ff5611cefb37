"""What the readers of the gate on the attention output and of the sandwich
norms share: device seconds of the traced window under ``attn_gate`` (the
sigmoid gate's multiply, ``dlrover_tpu/models/llama.py::_attention``) and
under ``branch_norm`` (each branch's output norm, ``block_apply``), every
phase: forward, backward and block remat's recomputation alike.  Both scopes
sit INSIDE a block's outermost scope (``attention``; ``attention``, ``mlp``
or ``moe_combine``), so no reader of an outermost scope sees them.

An XLA instruction is joined through the ``subscopes`` table of the
``accelerate.program`` event (its innermost scope; a fusion is where its
root is, or where most of what was fused into it is), a Mosaic kernel's
call (the output norm's ``rmsnorm_fwd``) through ``kernel_scopes`` (the
innermost scope ABOVE the kernel's own name), as ``harness/window_read.py``
does for the attention kinds.  ``attention`` is the seconds under the
outermost scope of that name (``scopes``), which holds ``attn_gate`` and
the attention branch's ``branch_norm``.

A program that journals neither scope (a model without the gate and without
sandwich norms, and the parent of the PR that brought the scopes) yields
None, and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

SCOPES = ("attn_gate", "branch_norm")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"attn_gate", "branch_norm", "attention", "busy_s"}``; a scope the
    program's tables do not name at all is absent from the dict."""
    program = obs_read.program_tables(obs_read.records(spans), trace)
    if program is None:
        return None
    inner = program.get("subscopes") or {}
    of_kernel = program.get("kernel_scopes") or {}
    named = (set(inner.values()) | set(of_kernel.values())) & set(SCOPES)
    if not named:
        return None
    outer = program["scopes"]
    kernels = trace.get("kernel_s") or {}
    out = dict.fromkeys(sorted(named) + ["attention"], 0.0)
    for name, label, secs in obs_read.placed_ops(trace):
        scope = (of_kernel if label in kernels else inner).get(name)
        if scope in named:
            out[scope] += secs
        if name in outer and outer[name][1] == "attention":
            out["attention"] += secs
    return dict(out, busy_s=trace["busy_s"])
