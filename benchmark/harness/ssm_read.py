"""What the readers of the state-space layers share: device seconds of the
traced window under the block's ``ssm`` scope and under each of the mixer's
five nested scopes (``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate``,
``ssm_out``; ``dlrover_tpu/models/llama.py::_ssm_mixer``), every phase —
forward, backward and block remat's recomputation alike.  The trace's
instruction names are joined to the two tables of the ``accelerate.program``
event, ``scopes`` (outermost scope) and ``subscopes`` (innermost), as
``harness/mla_read.py`` does for latent attention.

The scan's own Mosaic kernels (:data:`SCAN_KERNELS`, ``ops/ssd.py``: what a
chunk puts out, forward — recomputed too under block remat — and backward)
are joined call by call (``obs_read.placed_ops``): a call under the ``ssm``
scope counts there and, the innermost scope on a kernel's path being the
kernel's own name, under ``ssm_scan`` by that name, so that the four
readers see the same work whether XLA fusions or kernels do it.  The
RMSNorm kernels (the block's ``ln1``, the gated norm) are left out as
before (0.8 % of busy in all their uses in the other cells).

A program that journals no ``ssm`` scope (every configuration without
state-space layers, and the parent of the PR that brought them) yields None,
and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_out")
SCAN_KERNELS = ("ssd_chunk_fwd", "ssd_chunk_bwd")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"ssm", "ssm_in", .., "ssm_out", "busy_s", "ssm_layers"}``: seconds
    of the instructions whose outermost scope is ``ssm``, of those under
    each nested scope, the device's busy seconds, and the program's own
    count of its state-space layers."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    out = dict.fromkeys(("ssm",) + INNER, 0.0)
    kernels = trace.get("kernel_s") or {}
    for name, label, secs in obs_read.placed_ops(trace):
        if label in kernels and label not in SCAN_KERNELS:
            continue  # another kernel's call (the norms)
        if name not in scopes or scopes[name][1] != "ssm":
            continue  # another scope's, or nobody's
        out["ssm"] += secs
        if label in SCAN_KERNELS:
            out["ssm_scan"] += secs
        elif inner.get(name) in INNER:
            out[inner[name]] += secs
    if not out["ssm"]:
        return None
    return dict(out, busy_s=trace["busy_s"],
                ssm_layers=program.get("ssm_layers"))
