"""What the readers of the KDA layers share: device seconds of the traced
window under the block's ``kda`` scope and under each of the mixer's five
nested scopes (``kda_in``, ``kda_conv``, ``kda_scan``, ``kda_gate``,
``kda_out``; ``dlrover_tpu/models/llama.py::_kda_mixer``), every phase —
forward, backward and block remat's recomputation alike.

EVERY device operation is placed by the instruction that ran it
(``obs_read.placed_ops``: an XLA instruction's own name, a Mosaic kernel's
CALLING instruction), and that instruction is looked up in the three tables
of the ``accelerate.program`` event: ``scopes`` (outermost scope: is it
``kda``?), ``kernel_scopes`` (of a Mosaic call, the innermost scope above the
kernel's own name: ``kda_scan`` for ``kda_chunk_fwd`` / ``kda_chunk_bwd``,
``kda_conv`` for the convolutions', ``kda_gate`` for the gated norm's) and
``subscopes`` (of an XLA instruction, its innermost scope).  The label the
trace gives a kernel takes no part: ``trace_reduce.PALLAS_KERNELS`` does not
know the new pair and files its calls under ``pallas_other``, with the
convolution's and the gated norm's, and a reader that went by the label
would leave the rule's kernels out of the rule's share (ROADMAP R0k).  The
same call counts the same whether the trace names it or calls it
``pallas_other``.  A kernel's call that ``kernel_scopes`` does not list sits
directly under ``kda`` (the block's input norm) and counts there alone.

A program that journals no ``kda`` scope (every configuration without KDA
layers, and the parent of the PR that brought them) yields None, and every
reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("kda_in", "kda_conv", "kda_scan", "kda_gate", "kda_out")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"kda", "kda_in", .., "kda_out", "busy_s", "kda_layers"}``: seconds
    of the operations whose outermost scope is ``kda``, of those under each
    nested scope, the device's busy seconds, and the program's own count of
    its KDA layers."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    of_kernel = program.get("kernel_scopes") or {}
    out = dict.fromkeys(("kda",) + INNER, 0.0)
    for name, _, secs in obs_read.placed_ops(trace):
        if name not in scopes or scopes[name][1] != "kda":
            continue  # another scope's, or nobody's
        out["kda"] += secs
        within = of_kernel.get(name) or inner.get(name)
        if within in INNER:
            out[within] += secs
    if not out["kda"]:
        return None
    return dict(out, busy_s=trace["busy_s"],
                kda_layers=program.get("kda_layers"))
