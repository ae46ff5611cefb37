"""Flagship elastic Llama pretraining through the full product stack.

The Llama-2 analogue of the reference's headline example
(``atorch/examples/llama2``): model + ``accelerate()`` strategy (mesh x
remat x dtype, layout planner), fused lm-head loss, elastic sampler fed
by the master's task manager, and flash checkpointing — all launched
under the elastic agent::

    python -m dlrover_tpu.run --standalone --nproc_per_node=1 \
        examples/llama_train.py -- --steps 20

On a chip host a node runs ONE worker that drives all local chips;
``--nproc_per_node=2`` is for the virtual CPU mesh (``JAX_PLATFORMS=cpu``).
The worker prints what it runs on (``DEVICE``), what its compiled step
contains (``PROGRAM``: Pallas kernels and collectives counted from the
program text), its time to the first step and its peak device memory —
``chip_smoke.py`` reads these lines.

Scale knobs: ``--model {tiny,300m,800m}`` picks the config;
``--strategy auto`` searches mesh factorizations instead of pure DP.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import dlrover_tpu.trainer as trainer_sdk


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--model", default="tiny",
                   choices=["tiny", "300m", "800m"])
    p.add_argument("--batch_per_proc", type=int, default=4)
    p.add_argument("--seq_len", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--strategy", default="dp",
                   choices=["dp", "auto"])
    p.add_argument("--remat_block", action="store_true")
    p.add_argument("--lora_rank", type=int, default=0,
                   help=">0: LoRA fine-tuning — train rank-r (A,B) "
                        "factors on the targeted projections, base "
                        "model frozen (reference fsdp_llama2.py "
                        "--use_lora/peft path)")
    p.add_argument("--lora_alpha", type=float, default=16.0)
    p.add_argument("--lora_targets", default="wq,wk,wv,wo",
                   help="comma-separated projection names; mlp adds "
                        "w_gate,w_up,w_down")
    p.add_argument("--init_from", default="",
                   help="HuggingFace Llama checkpoint dir to import as "
                        "the (frozen, for LoRA) base model")
    p.add_argument("--dataset_size", type=int, default=4096)
    p.add_argument("--ckpt_dir", default="")
    p.add_argument("--ckpt_interval", type=int, default=5)
    p.add_argument("--log_interval", type=int, default=10)
    return p.parse_args()


def build_config(args):
    from dlrover_tpu.models import llama

    if args.model == "300m":
        cfg = llama.LlamaConfig.small_300m()
    elif args.model == "800m":
        cfg = llama.LlamaConfig.medium_800m()
    else:
        cfg = llama.LlamaConfig.tiny(max_seq_len=args.seq_len)
    return dataclasses.replace(cfg, remat_block=args.remat_block)


def synth_tokens(indices, seq_len, vocab):
    import numpy as np

    base = np.random.RandomState(0).randint(0, vocab, size=(seq_len + 1,))
    return np.stack(
        [(base + i) % vocab for i in indices], axis=0
    ).astype("int32")


def main() -> int:
    t_start = time.monotonic()
    args = parse_args()
    ctx = trainer_sdk.init()

    import jax
    import numpy as np
    import optax

    from dlrover_tpu.common.jax_env import device_summary
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.sampler import ElasticSampler

    tag = f"[worker {ctx.process_id}]"
    print(f"{tag} DEVICE {json.dumps(device_summary())}", flush=True)
    cfg = build_config(args)
    local_dev = jax.local_device_count()
    if args.batch_per_proc % local_dev:
        args.batch_per_proc = -(-args.batch_per_proc // local_dev) * local_dev
    global_batch = args.batch_per_proc * ctx.num_processes

    sample = synth_tokens(
        range(global_batch), args.seq_len, cfg.vocab_size
    )
    strategy = (
        "auto" if args.strategy == "auto"
        else Strategy(mesh=MeshSpec(dp=len(jax.devices())))
    )

    if args.init_from and args.lora_rank == 0:
        # Full fine-tune from an import: compile against shapes first;
        # the weights stream onto the params sharding after create_state
        # (never an unsharded full copy — same discipline as the LoRA
        # branch below).
        from dlrover_tpu.models import hf_convert

        cfg = hf_convert.config_from_hf_dir(args.init_from)
        cfg = dataclasses.replace(cfg, remat_block=args.remat_block)
    if args.lora_rank > 0:
        # LoRA: base model frozen (rides the state as 'frozen'), only
        # the (A, B) factors train — reference fsdp_llama2.py peft path.
        from dlrover_tpu.models import lora

        if args.init_from:
            # 7B-scale flow: accelerate() sees SHAPES only; the real
            # weights stream from the checkpoint straight onto the
            # frozen sharding after compile (never an unsharded copy).
            from dlrover_tpu.models import hf_convert

            cfg = hf_convert.config_from_hf_dir(args.init_from)
            cfg = dataclasses.replace(cfg, remat_block=args.remat_block)
            frozen = jax.eval_shape(
                lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
            )
        else:
            frozen = llama.init_params(jax.random.PRNGKey(0), cfg)
        targets = tuple(
            t.strip() for t in args.lora_targets.split(",") if t.strip()
        )

        def loss_fn(factors, b, frozen):
            return llama.loss_fn(lora.merge(frozen, factors), b, cfg)

        base_for_shapes = frozen

        init_fn = lambda r: lora.init_lora(  # noqa: E731
            r, base_for_shapes, rank=args.lora_rank,
            alpha=args.lora_alpha, targets=targets,
        )
        optimizer = optax.masked(
            optax.adamw(args.lr), lora.trainable_mask
        )
    else:
        loss_fn = lambda p, b: llama.loss_fn(p, b, cfg)  # noqa: E731
        init_fn = lambda r: llama.init_params(r, cfg)  # noqa: E731
        optimizer = optax.adamw(args.lr)
        frozen = None

    job = accelerate(
        loss_fn=loss_fn,
        init_fn=init_fn,
        optimizer=optimizer,
        sample_batch={"tokens": sample},
        strategy=strategy,
        param_specs="planner",
        frozen=frozen,
    )
    print(f"{tag} PROGRAM {json.dumps(job.program)}", flush=True)
    if args.lora_rank > 0 and args.init_from:
        # Stream the checkpoint leaf-by-leaf onto the compiled frozen
        # sharding: peak host memory ~ one tensor, device memory only
        # ever holds the sharded copy.
        from dlrover_tpu.models import hf_convert

        sharded_base, _ = hf_convert.from_hf_llama_dir(
            args.init_from, cfg, dtype=cfg.dtype,
            shardings=job.state_sharding["frozen"],
        )
        state = job.create_state(
            jax.random.PRNGKey(0), frozen_values=sharded_base
        )
    else:
        state = job.create_state(jax.random.PRNGKey(0))
        if args.init_from:
            from dlrover_tpu.models import hf_convert

            sharded, _ = hf_convert.from_hf_llama_dir(
                args.init_from, cfg, dtype=cfg.dtype,
                shardings=job.state_sharding["params"],
            )
            state["params"] = sharded

    def split_ckpt(st):
        """Checkpoints exclude the frozen base under LoRA: a factor
        save costs KBs, the base is re-attached from the live copy."""
        return {k: v for k, v in st.items() if k != "frozen"}

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.checkpointer import FlashCheckpointer

        ckpt = FlashCheckpointer(args.ckpt_dir, job_name=ctx.job_name)
        restored = ckpt.load(target=split_ckpt(state))
        if restored is not None:
            got, meta = restored
            if "frozen" in state:
                got = dict(got, frozen=state["frozen"])
            state = got
            start_step = int(meta.get("step", 0))
            print(f"{tag} restored step={start_step}", flush=True)

    sampler = ElasticSampler(
        args.dataset_size,
        batch_size_per_process=args.batch_per_proc,
        num_processes=ctx.num_processes,
        process_id=ctx.process_id,
        seed=17,
    )
    sampler.completed_steps = start_step

    step, loss = start_step, float("nan")
    it = iter(sampler)
    while step < args.steps:
        try:
            indices = next(it)
        except StopIteration:
            it = iter(sampler)
            continue
        toks = synth_tokens(indices, args.seq_len, cfg.vocab_size)
        batch = {
            "tokens": jax.make_array_from_process_local_data(
                job.batch_sharding["tokens"], toks
            )
        }
        state, metrics = job.train_step(state, batch)
        loss = float(metrics["loss"])
        if step == start_step:
            print(
                f"{tag} FIRST_STEP seconds="
                f"{time.monotonic() - t_start:.1f} "
                f"restart_count={ctx.restart_count}", flush=True,
            )
        step += 1
        ctx.report_step(step)
        if ckpt is not None and step % args.ckpt_interval == 0:
            ckpt.save(split_ckpt(state), meta={"step": step})
        if step % args.log_interval == 0 or step == args.steps:
            print(f"{tag} step {step} loss {loss:.4f}", flush=True)
    if ckpt is not None:
        ckpt.save(split_ckpt(state), meta={"step": step}, storage=True)
        ckpt.wait()
    mem = jax.local_devices()[0].memory_stats() or {}
    print(f"{tag} MEMORY peak_bytes_in_use="
          f"{mem.get('peak_bytes_in_use', 'n/a')}", flush=True)
    print(f"TRAIN_DONE step={step} loss={loss:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
