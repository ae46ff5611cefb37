"""What the readers of the Mamba-1 layers, of the Gated Memory Units and of
differential attention share: device seconds of the traced window under the
block's ``s6`` scope and under each of the mixer's six nested scopes
(``s6_in``, ``s6_conv``, ``s6_dt``, ``s6_scan``, ``s6_gate``, ``s6_out``;
``dlrover_tpu/models/llama.py::_s6_mixer``), under the block's ``gmu`` scope,
and under ``attn_diff`` (the subtraction, ``subln`` and ``1 - lambda_init``
behind the flash call, INSIDE the block's ``attention``) beside all of
``attention``, every phase — forward, backward and block remat's
recomputation alike.

EVERY device operation is placed by the instruction that ran it
(``obs_read.placed_ops``: an XLA instruction's own name, a Mosaic kernel's
CALLING instruction), and that instruction is looked up in the three tables
of the ``accelerate.program`` event, as ``harness/kda_read.py`` does:
``scopes`` (outermost scope), ``kernel_scopes`` (of a Mosaic call, the
innermost scope above the kernel's own name: ``s6_scan`` for ``s6_scan_fwd``
/ ``s6_scan_bwd``, ``s6_conv`` for the convolution's) and ``subscopes`` (of
an XLA instruction, its innermost scope).  The label the trace gives a kernel
takes no part: ``trace_reduce.PALLAS_KERNELS`` does not know the new pair
and files its calls under ``pallas_other``.

A program that journals none of the scopes (every configuration without such
layers, and the parent of the PR that brought them) yields None, and every
reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("s6_in", "s6_conv", "s6_dt", "s6_scan", "s6_gate", "s6_out")
OUTER = ("s6", "gmu", "attention")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"s6", "s6_in", .., "s6_out", "gmu", "attention", "attn_diff",
    "busy_s", "s6_layers"}``: seconds of the operations whose outermost scope
    is each of :data:`OUTER`, of those under each nested scope, the device's
    busy seconds, and the program's own count of its Mamba-1 layers."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    of_kernel = program.get("kernel_scopes") or {}
    out = dict.fromkeys(OUTER + INNER + ("attn_diff",), 0.0)
    for name, _, secs in obs_read.placed_ops(trace):
        if name not in scopes or scopes[name][1] not in OUTER:
            continue  # another scope's, or nobody's
        out[scopes[name][1]] += secs
        within = of_kernel.get(name) or inner.get(name)
        if within in INNER or within == "attn_diff":
            out[within] += secs
    if not (out["s6"] or out["gmu"] or out["attn_diff"]):
        return None
    return dict(out, busy_s=trace["busy_s"],
                s6_layers=program.get("s6_layers"))
