"""What the readers of the gated short-convolution layers share: device
seconds of the traced window under the block's ``conv`` scope and under each
of the mixer's three nested scopes (``conv_in``, ``conv_gate``,
``conv_out``; ``dlrover_tpu/models/llama.py::_conv_mixer``), every phase —
forward, backward and block remat's recomputation alike.  The trace's
instruction names are joined to the two tables of the ``accelerate.program``
event, ``scopes`` (outermost scope) and ``subscopes`` (innermost), as
``harness/ssm_read.py`` does for the state-space layers.

The RMSNorm kernel (the block's ``ln1``) is a Mosaic call whose label in the
trace is the kernel's name, the same under every scope: the join cannot
place it and it is left out.

A program that journals no ``conv`` scope (every configuration without such
layers, and the parent of the PR that brought them) yields None, and every
reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("conv_in", "conv_gate", "conv_out")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"conv", "conv_in", "conv_gate", "conv_out", "busy_s",
    "conv_layers"}``: seconds of the instructions whose outermost scope is
    ``conv``, of those under each nested scope, the device's busy seconds,
    and the program's own count of its convolution layers."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    out = dict.fromkeys(("conv",) + INNER, 0.0)
    kernels = trace.get("kernel_s") or {}
    for label, secs in trace["op_self_s"].items():
        name = label.split(" ", 1)[0]
        if label in kernels or name not in scopes or (
                scopes[name][1] != "conv"):
            continue  # a kernel's label, another scope's, or nobody's
        out["conv"] += secs
        if inner.get(name) in INNER:
            out[inner[name]] += secs
    if not out["conv"]:
        return None
    return dict(out, busy_s=trace["busy_s"],
                conv_layers=program.get("conv_layers"))
