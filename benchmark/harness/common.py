"""What every runner shares: the files of a cell, the device it may run on,
the compile cache, the count of compilations, and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
#: run-time products of the benchmark, inside the checkout and git-ignored
WORK_DIR = os.path.join(REPO, ".bench_work")


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_spec() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """``workloads/<name>.json`` with its configuration and traffic files
    read in: everything a runner needs, and nothing chosen at run time."""
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["config_data"] = load_json("configs", f"{cell['config']}.json")
    cell["traffic_data"] = load_json("traffic", f"{cell['traffic']}.json")
    return cell


#: keys of a configuration file that are the benchmark's own and no part of
#: the architecture: ``adapter`` and ``reference`` name the files under
#: ``adapters/`` and ``reference/`` that compute it, ``rehearsal`` the toy
#: sibling under ``configs/`` that ``--rehearse`` runs in its place,
#: ``published`` holds the source's own values, and the rest is prose.
#: Every OTHER key is the architecture's, and the adapter must know it.
CONFIG_META_KEYS = (
    "adapter", "reference", "rehearsal", "rehearsal_seq_len", "published",
    "source", "assumed", "reduced", "deployment", "parameters", "notes")


def adapter_of(cfg: dict):
    """The adapter a configuration file names (``adapters/<name>.py``)."""
    return load_module("adapters", cfg["adapter"])


def rehearsal_cell(cell: dict) -> dict:
    """The cell with the toy widths its configuration names (same adapter,
    same reference) and short sequences, same files otherwise (``run.py
    --rehearse``: the CPU backend, never a measurement)."""
    tiny = load_json("configs", f"{cell['config_data']['rehearsal']}.json")
    return dict(cell, config_data=tiny,
                traffic_data=dict(cell["traffic_data"],
                                  seq_len=tiny["rehearsal_seq_len"],
                                  dataset_size=512))


def load_module(folder: str, name: str):
    """A file of the benchmark found by name (names may hold dots, so this
    is not an import statement)."""
    path = os.path.join(BENCH_DIR, folder, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Refused(Exception):
    """The run may not produce a result (wrong device, wrong count)."""


def check_device(summary: dict, chips: int, rehearse: bool) -> dict:
    """The device the cell asks for, or :class:`Refused`.  A rehearsal runs
    on whatever is there and is never printed as a result."""
    from benchmark.harness.peaks import peaks_for

    if rehearse:
        return {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan"),
                "ici_bytes_per_s": float("nan")}
    if summary["platform"] != "tpu":
        raise Refused(f"no TPU: JAX found {summary}")
    if summary["count"] != chips:
        raise Refused(
            f"the cell asks for {chips} chip(s), JAX found {summary}")
    return peaks_for(summary["kind"])


class CompileCounter:
    """Counts the executables JAX had to get (compiled or read from the
    persistent cache) while ``armed``: inside the window it must stay 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices())


def less_parts(name: str, total: float, **parts: float):
    """``setup_s`` and the kill-to-step seconds judge the tree's own: the host
    clock's total less the parts no tree can move (the backend's start-up)
    or that are the benchmark's own (its comparison with the reference).
    Returns the metric and the note line that shows the whole account —
    ``SETUP_S 9.930412 total=22.970113 backend_open_s=7.860001
    check_s=5.179700`` — so that the old series (the total) stays readable
    in every run's output and the metric is the printed total less the
    printed parts."""
    value = total - sum(parts.values())
    return value, " ".join(
        [f"{name} {value:.6f}", f"total={total:.6f}"]
        + [f"{k}={v:.6f}" for k, v in parts.items()])


def print_result(result: dict) -> None:
    """The last line of standard output: one JSON object."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
