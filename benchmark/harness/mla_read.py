"""What the readers of latent attention and of the prediction block share:
device seconds of the traced window by the program's scopes, NESTED ones
included.  ``obs_read.scope_shares`` joins the trace's instruction names to
the ``scopes`` table of the ``accelerate.program`` event (outermost scope
only); a program that nests scopes of its own journals a second table,
``subscopes`` (``{instruction: innermost scope}``), and this joins both:
``mla_q`` / ``mla_kv`` / ``mla_out`` sit inside ``attention``, and the
prediction block's whole application (its ``attention`` and ``moe_*``) sits
inside ``mtp``.

The flash kernels are Mosaic calls whose label in the trace is the kernel's
name, the same in every application: their seconds come from
``trace["kernel_s"]`` and belong to attention as a whole.

A program that journals no ``subscopes`` (every dense, routed or looped
configuration before latent attention, and the parent of the PR that
brought it) yields None, and every reader built on this returns None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import obs_read, trace_reduce

#: innermost scopes that are part of a block's attention branch
ATTENTION_INNER = ("attention", "mla_q", "mla_kv", "mla_out")


def seconds(spans: dict, trace: dict) -> Optional[dict]:
    """``{"attention_ops", "mla_q", "mla_kv", "mla_out", "flash", "mtp_ops",
    "busy_s", "block_applications"}``: seconds of the XLA instructions under
    the ``attention`` scope of every block application (the prediction
    block's too), of those under each latent sub-scope, of the three flash
    kernels, and of everything under ``mtp`` that is no Mosaic kernel."""
    program = obs_read.program_tables(obs_read.records(spans), trace,
                                      nested=True)
    if program is None:
        return None
    scopes, inner = program["scopes"], program["subscopes"]
    out = {"attention_ops": 0.0, "mla_q": 0.0, "mla_kv": 0.0, "mla_out": 0.0,
           "mtp_ops": 0.0}
    kernels = trace.get("kernel_s") or {}
    for label, secs in trace["op_self_s"].items():
        name = label.split(" ", 1)[0]
        if label in kernels or name not in scopes:
            # a kernel's label (its first call's instruction may bear the
            # same name: ``gmm``), or nothing the program names
            continue
        scope, within = scopes[name][1], inner.get(name, "")
        if scope == "mtp":
            out["mtp_ops"] += secs
        if scope == "attention" or (
                scope == "mtp" and within in ATTENTION_INNER):
            out["attention_ops"] += secs
            if within in out:
                out[within] += secs
    out["flash"] = sum(trace.get("kernel_s", {}).get(k, 0.0)
                       for k in trace_reduce.FLASH_KERNELS)
    return dict(out, busy_s=trace["busy_s"],
                block_applications=program.get("block_applications"))
