"""What the readers of the state-space layers share: device seconds of the
traced window under the block's ``ssm`` scope and under each of the mixer's
five nested scopes (``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate``,
``ssm_out``; ``dlrover_tpu/models/llama.py::_ssm_mixer``), every phase —
forward, backward and block remat's recomputation alike.  The trace's
instruction names are joined to the two tables of the ``accelerate.program``
event, ``scopes`` (outermost scope) and ``subscopes`` (innermost), as
``harness/mla_read.py`` does for latent attention.

The RMSNorm kernels (the block's ``ln1``, the gated norm) are Mosaic calls
whose label in the trace is the kernel's name, the same under every scope:
the join cannot place them and they are left out (0.8 % of busy in all their
uses in the other cells).

A program that journals no ``ssm`` scope (every configuration without
state-space layers, and the parent of the PR that brought them) yields None,
and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read

INNER = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_out")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"ssm", "ssm_in", .., "ssm_out", "busy_s", "ssm_layers"}``: seconds
    of the instructions whose outermost scope is ``ssm``, of those under
    each nested scope, the device's busy seconds, and the program's own
    count of its state-space layers."""
    programs = [r for r in obs_read.last_incarnation(obs_read.records(spans))
                if r.get("kind") == "accelerate.program"
                and r.get("scopes") and r.get("subscopes")]
    ops = trace.get("op_self_s") if trace else None
    if not programs or not ops or not trace.get("busy_s"):
        return None
    scopes, inner = programs[-1]["scopes"], programs[-1]["subscopes"]
    out = dict.fromkeys(("ssm",) + INNER, 0.0)
    for label, secs in ops.items():
        name = label.split(" ", 1)[0]
        if name not in scopes or scopes[name][1] != "ssm":
            continue  # a kernel's label, another scope's, or nobody's
        out["ssm"] += secs
        if inner.get(name) in INNER:
            out[inner[name]] += secs
    if not out["ssm"]:
        return None
    return dict(out, busy_s=trace["busy_s"],
                ssm_layers=programs[-1].get("ssm_layers"))
