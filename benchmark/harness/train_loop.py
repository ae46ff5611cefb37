"""The training loop both runners drive: ElasticSampler -> host batch ->
``make_array_from_process_local_data`` -> ``job.train_step`` -> the loss on
the host.  The spans are the benchmark's own stamps around calls into each
layer (host clock; the same names go into the profiler's trace as
``TraceAnnotation`` so that device idle gaps can be labelled)."""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark.harness import trace_reduce
from benchmark.harness.model import build_job, sample_tokens


class TrainSession:
    def __init__(self, cell: dict, seed: int, t_proc_start: float):
        self.cell = cell
        self.seed = seed
        self.traffic = cell["traffic_data"]
        self.cfg = cell["config_data"]
        self.t_proc_start = t_proc_start
        #: host-clock stamps, seconds; lists are one entry per step or save
        self.spans = {"input_wait_s": [], "step_s": [], "save_stall_s": []}
        self.losses = []
        #: what the newest step returned beside the loss, still on the
        #: device: a runner fetches it after its window (``step_metrics``)
        self.last_metrics = {}
        self.step_no = 0
        self.job = self.model_config = self.state = self._it = None

    # -- set-up -------------------------------------------------------------
    def open_device(self) -> dict:
        """First touch of JAX's backend: the process takes the chip.
        ``backend_open_s`` is that call alone, the runtime's own start-up,
        which no tree can move: the runners take it out of ``setup_s`` and
        the kill-to-step seconds and print it beside them.  What precedes it in
        ``device_open_s`` (imports, the compile cache's set-up) stays in."""
        from dlrover_tpu.common.jax_env import (
            device_summary,
            enable_compilation_cache,
        )

        enable_compilation_cache()
        t0 = time.monotonic()
        summary = device_summary()
        t1 = time.monotonic()
        self.spans["backend_open_s"] = t1 - t0
        self.spans["device_open_s"] = t1 - self.t_proc_start
        return summary

    def build(self) -> None:
        t0 = time.monotonic()
        self.job, self.model_config = build_job(self.cell)
        self.spans["accelerate_s"] = time.monotonic() - t0

    def create_state(self) -> None:
        t0 = time.monotonic()
        self.state = self.job.create_state(jax.random.PRNGKey(self.seed))
        jax.block_until_ready(self.state)
        self.spans["create_state_s"] = time.monotonic() - t0

    def start_sampler(self, start_step: int = 0) -> None:
        from dlrover_tpu.trainer.sampler import ElasticSampler

        sampler = ElasticSampler(
            self.traffic["dataset_size"],
            batch_size_per_process=self.cell["batch_sequences"],
            num_processes=1, process_id=0,
            seed=self.seed + self.traffic["sampler_seed_offset"],
        )
        sampler.completed_steps = start_step
        self.step_no = start_step
        self._sampler = sampler
        self._it = iter(sampler)

    def first_step(self) -> float:
        """The first call of the jitted step compiles it or reads it from
        the cache: with ``accelerate()`` it is the step builder's time."""
        t0 = time.monotonic()
        loss = self.step(record=False)
        self.spans["first_step_s"] = time.monotonic() - t0
        self.spans["build_s"] = (
            self.spans["accelerate_s"] + self.spans["first_step_s"])
        return loss

    # -- the loop -----------------------------------------------------------
    def _next_indices(self):
        try:
            return next(self._it)
        except StopIteration:
            self._it = iter(self._sampler)
            return next(self._it)

    def step(self, record: bool = True) -> float:
        t0 = time.monotonic()
        with TraceAnnotation("batch_build"):
            toks = sample_tokens(
                self.seed, self._next_indices(), self.traffic["seq_len"],
                self.cfg["vocab_size"])
            batch = {"tokens": jax.make_array_from_process_local_data(
                self.job.batch_sharding["tokens"], toks)}
        t1 = time.monotonic()
        with TraceAnnotation("dispatch"):
            self.state, metrics = self.job.train_step(self.state, batch)
        with TraceAnnotation("loss_sync"):
            loss = float(metrics["loss"])
        t2 = time.monotonic()
        self.last_metrics = metrics
        self.step_no += 1
        if record:
            self.spans["input_wait_s"].append(t1 - t0)
            self.spans["step_s"].append(t2 - t0)
            self.losses.append(loss)
        return loss

    def save(self, ckpt, record: bool = True) -> float:
        """``FlashCheckpointer.save`` timed from outside: the seconds the
        loop is blocked in it."""
        t0 = time.monotonic()
        with TraceAnnotation("ckpt_save"):
            ckpt.save(self.state, meta={"step": self.step_no})
        stall = time.monotonic() - t0
        if record:
            self.spans["save_stall_s"].append(stall)
        return stall

    def step_metrics(self) -> dict:
        """The counters the jitted step itself computed in its newest call
        (``grad_norm`` today; tokens per expert, dropped tokens where a
        later step returns them), as plain numbers and lists.  Fetches from
        the device: call it outside every timed span."""
        return {k: np.asarray(v).tolist()
                for k, v in jax.device_get(self.last_metrics).items()
                if k != "loss"}

    @property
    def tokens_per_step(self) -> int:
        return self.cell["batch_sequences"] * self.traffic["seq_len"]


def start_trace(trace_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the loop's own spans are enough
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_trace(trace_dir: str) -> dict:
    """Stops the profiler and returns the trace as plain data."""
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(trace_dir)
    return trace_reduce.load_xplane(path) if path else {"planes": []}
