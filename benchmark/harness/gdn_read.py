"""What the readers of the delta-rule layers share: device seconds of the
traced window under the block's ``gdn`` scope and under each of the mixer's
five nested scopes (``gdn_in``, ``gdn_conv``, ``gdn_scan``, ``gdn_gate``,
``gdn_out``; ``dlrover_tpu/models/llama.py::_gdn_mixer``), every phase —
forward, backward, block remat's recomputation and the mixer's own alike.
The trace's instruction names are joined to the two tables of the
``accelerate.program`` event, ``scopes`` (outermost scope) and ``subscopes``
(innermost), as ``harness/ssm_read.py`` does for the state-space layers.

The rule runs as XLA's own instructions today.  A later kernel for its ``[Q,
Q]`` part (:data:`SCAN_KERNELS` names the calls it would journal) is joined
call by call (``obs_read.placed_ops``): a call under the ``gdn`` scope counts
there and, the innermost scope on a kernel's path being the kernel's own
name, under ``gdn_scan`` by that name, so that the three readers see the
same work whether XLA fusions or kernels do it.  The RMSNorm kernel (the
block's ``ln1``) is left out, as the other hybrids' readers leave it.

A program that journals no ``gdn`` scope (every configuration without
delta-rule layers, and the parent of the PR that brought them) yields None,
and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("gdn_in", "gdn_conv", "gdn_scan", "gdn_gate", "gdn_out")
SCAN_KERNELS = ("gdn_chunk_fwd", "gdn_chunk_bwd")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"gdn", "gdn_in", .., "gdn_out", "busy_s", "gdn_layers"}``: seconds
    of the instructions whose outermost scope is ``gdn``, of those under
    each nested scope, the device's busy seconds, and the program's own
    count of its delta-rule layers."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    out = dict.fromkeys(("gdn",) + INNER, 0.0)
    kernels = trace.get("kernel_s") or {}
    for name, label, secs in obs_read.placed_ops(trace):
        if label in kernels and label not in SCAN_KERNELS:
            continue  # another kernel's call (the norm)
        if name not in scopes or scopes[name][1] != "gdn":
            continue  # another scope's, or nobody's
        out["gdn"] += secs
        if label in SCAN_KERNELS:
            out["gdn_scan"] += secs
        elif inner.get(name) in INNER:
            out[inner[name]] += secs
    if not out["gdn"]:
        return None
    return dict(out, busy_s=trace["busy_s"],
                gdn_layers=program.get("gdn_layers"))
