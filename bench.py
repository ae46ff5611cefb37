"""Benchmark entry: flagship-model training throughput on the local chip(s).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Metric: model FLOPs utilization (MFU %) of a bf16 Llama training step on the
available TPU (single chip under the driver).  ``vs_baseline`` compares
against the reference's published Llama2-7B HFU of 62.5% on A100s
(BASELINE.md, `atorch/examples/llama2/README.md:398-407`) — an imperfect but
honest cross-hardware anchor until multi-chip goodput runs exist.

The step is built by the framework's own ``accelerate()`` (strategy -> mesh +
shardings + remat + donation + compiled SPMD step), so this number measures
the product path, not a hand-rolled ``jax.jit`` (round-1 review Weak #2).
"""

from __future__ import annotations

import json
import sys
import time

REFERENCE_HFU_PCT = 62.5  # reference Llama2-7B FSDP HFU (BASELINE.md)

#: Per-chip dense bf16 peak FLOP/s, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
#: A device that is not in the table is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published bf16 peak for device_kind {device_kind!r}: add "
            "it to PEAK_BF16_FLOPS with its source"
        ) from None


def model_flops_per_step(cfg, batch, seq) -> float:
    """6*params_matmul*tokens + 12*L*S^2*H*D (fwd+bwd attention)."""
    p_layer = (
        cfg.d_model * cfg.n_head * cfg.head_dim
        + 2 * cfg.d_model * cfg.n_kv_head * cfg.head_dim
        + cfg.n_head * cfg.head_dim * cfg.d_model
        + 3 * cfg.d_model * cfg.d_ff
    )
    dense = cfg.n_layer * p_layer + 2 * cfg.vocab_size * cfg.d_model
    tokens = batch * seq
    attn = 12.0 * cfg.n_layer * seq * seq * cfg.n_head * cfg.head_dim * batch
    return 6.0 * dense * tokens + attn


def _measure_candidate(cfg, batch, seq, remat, iters, opt="adamw",
                       fp8=False, accum=1, fused=None):
    """Compile + time one (model, batch, remat, optimizer, fp8, accum)
    point through accelerate(); returns (sec/step, final loss) or
    raises (e.g. OOM).  ``accum`` microbatches inside the jitted step:
    batch B with accum A runs A microbatches of B/A — the activation
    memory of B/A with B tokens of work per dispatch (amortizes
    dispatch + optimizer overhead per token).  ``fused`` overrides the
    fused-lm-head auto policy: False materializes the [tokens, V]
    logits as ONE big MXU-friendly GEMM — ~24% of the 300m FLOPs live
    in the lm head, and at b<=16 the logits fit HBM, so the scanned
    chunked CE may be leaving MXU efficiency on the table."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    if opt == "adam8bit":
        # The framework's blockwise-quantized Adam (ops/quant.py): int8
        # m/v state, ~4x less optimizer HBM than fp32 adamw.
        from dlrover_tpu.ops.quant import adam8bit

        tx = adam8bit(3e-4)
    else:
        tx = optax.adamw(3e-4)

    if remat == "block":
        # Per-block remat lives in the model (save only the residual
        # stream between layers); accelerate sees remat="none".
        import dataclasses as _dc

        cfg = _dc.replace(cfg, remat_block=True)
        remat = "none"

    rng = np.random.RandomState(0)
    sample_tokens = rng.randint(
        0, cfg.vocab_size, size=(batch, seq + 1)
    ).astype(np.int32)
    if fp8:
        loss_fn = lambda p, b, fp8_states: llama.loss_fn(  # noqa: E731
            p, b, cfg, fp8_states=fp8_states, fused_lm_head=fused
        )
    else:
        loss_fn = lambda p, b: llama.loss_fn(  # noqa: E731
            p, b, cfg, fused_lm_head=fused
        )
    job = accelerate(
        loss_fn=loss_fn,
        init_fn=lambda r: llama.init_params(r, cfg),
        optimizer=tx,
        sample_batch={"tokens": sample_tokens},
        strategy=Strategy(
            mesh=MeshSpec(dp=jax.local_device_count()), remat=remat,
            fp8=fp8, grad_accum=accum,
        ),
        fp8_init=(lambda: llama.init_fp8_states(cfg)) if fp8 else None,
    )
    state = job.create_state(jax.random.PRNGKey(0))
    batch_pt = {"tokens": jnp.asarray(sample_tokens)}
    # Warmup/compile; the float() host transfer forces completion.
    state, metrics = job.train_step(state, batch_pt)
    _ = float(metrics["loss"])
    t0 = time.perf_counter()
    for i in range(iters):
        state, metrics = job.train_step(state, batch_pt)
    loss = float(metrics["loss"])
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / iters
    # Free this candidate's state before the next one compiles.
    del state, job, batch_pt
    return dt, loss


def _measure_decode(cfg, batch, prompt_len, new_tokens,
                    quant_kv=False):
    """Decode tokens/s through the KV-cache generate path (the serving
    half; reference delegates this to vllm).  ``quant_kv`` stores the
    cache as int8 (half the HBM traffic per decoded token).  Returns
    tokens/sec."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama, llama_infer

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, prompt_len)
        ).astype("int32")
    )
    gen = jax.jit(
        lambda p, pr: llama_infer.generate(
            p, cfg, pr, max_new_tokens=new_tokens, temperature=0.0,
            quant_kv=quant_kv,
        )
    )
    out = gen(params, prompts)
    jax.block_until_ready(out)
    iters = 3
    t0 = time.perf_counter()
    for i in range(iters):
        out = gen(params, prompts)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return batch * new_tokens / dt


def _measure_server_decode(cfg, slots, prompt_len, new_tokens,
                           decode_chunk=1, quant_kv=False,
                           n_requests=None):
    """Continuous-batching DecodeServer tokens/s — the SERVING number
    (admission churn + host emit loop included), vs _measure_decode's
    pure fixed-batch scan.  ``decode_chunk`` is the K-tokens-per-
    dispatch lever: each dispatch costs host latency, so K divides the
    per-token share of it."""
    import numpy as np

    import jax

    from dlrover_tpu.models import llama, llama_infer

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    n_requests = n_requests or slots * 3
    prompts = [
        rng.randint(1, cfg.vocab_size, size=(prompt_len,)).astype(
            "int32"
        )
        for _ in range(n_requests)
    ]
    srv = llama_infer.DecodeServer(
        params, cfg, slots=slots,
        max_len=prompt_len + new_tokens + max(0, decode_chunk - 1),
        decode_chunk=decode_chunk, quant_kv=quant_kv,
    )
    srv.serve(prompts[:slots], max_new_tokens=8)  # warmup/compile
    t0 = time.perf_counter()
    outs = srv.serve(prompts, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    new = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    return new / dt


def _measure_spec_decode(cfg, draft_cfg, batch, prompt_len, new_tokens,
                         k, share_params=False):
    """Speculative decode tokens/s + acceptance through the batched
    draft/verify path.  ``share_params=True`` uses the TARGET itself as
    the draft (acceptance ~k+1: the mechanics' upper bound); otherwise
    the draft is a random init of ``draft_cfg`` (acceptance ~1: the
    floor — random models agree by chance).  Trained draft/target pairs
    land between the two; the break-even row from
    :func:`_measure_spec_components` says how much acceptance a pair
    must earn for speculation to beat plain decode (the speculative-
    decoding role of the serving engine the reference delegates to
    vllm, atorch/rl/model_engine/model_engine.py:35)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama, llama_infer

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    dparams = (
        params if share_params
        else llama.init_params(jax.random.PRNGKey(9), draft_cfg)
    )
    dcfg = cfg if share_params else draft_cfg
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, prompt_len)
        ).astype("int32")
    )
    lens = jnp.full((batch,), prompt_len, jnp.int32)

    def run(stats=None):
        out, olens = llama_infer.generate_speculative_batched(
            params, cfg, dparams, dcfg, prompts, lens,
            max_new_tokens=new_tokens, k=k, stats=stats,
        )
        jax.block_until_ready(out)
        return int(np.asarray(olens).sum()) - batch * prompt_len

    run()  # warmup/compile
    iters = 3
    stats: dict = {}
    t0 = time.perf_counter()
    emitted = 0
    for i in range(iters):
        emitted += run(stats)
    dt = time.perf_counter() - t0
    return {
        "tokens_per_sec": emitted / dt,
        "tokens_per_round": round(stats.get("tokens_per_round", 0.0), 3),
        "rounds_last_iter": stats.get("rounds", 0),
    }


def _measure_spec_adaptive(cfg, draft_cfg, batch, prompt_len,
                           new_tokens, k):
    """Adaptive-k speculation against a BAD draft (ISSUE 11): the
    per-request policy must walk every stream below break-even down to
    plain decode, so the measured tokens/s recovers toward the plain
    row instead of pinning at the speculation floor — the committed
    evidence that a bad draft can never make serving slower than a
    spec-less server."""
    import numpy as np

    import jax

    from dlrover_tpu.models import llama, llama_infer

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    dparams = llama.init_params(jax.random.PRNGKey(9), draft_cfg)
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(0, cfg.vocab_size, (prompt_len,)).astype("int32")
        for _ in range(batch)
    ]
    max_len = prompt_len + new_tokens + k + 8
    buckets = (prompt_len,) if prompt_len >= 16 else (16,)

    # ONE server: per-REQUEST adaptive state resets at every
    # admission (seat()), so iterations measure steady-state decode,
    # not per-instance XLA recompiles.
    srv = llama_infer.DecodeServer(
        params, cfg, slots=batch, max_len=max_len,
        prompt_buckets=buckets, draft=(dparams, draft_cfg),
        draft_k=k, adapt_k_per_request=True, spec_ewma_alpha=0.5,
    )
    srv.serve(prompts, max_new_tokens=new_tokens)  # warmup/compile
    iters = 3
    emitted = 0
    t0 = time.perf_counter()
    for i in range(iters):
        outs = srv.serve(prompts, max_new_tokens=new_tokens)
        emitted += sum(len(o) for o in outs) - batch * prompt_len
    dt = time.perf_counter() - t0
    st = srv.last_stats
    return {
        "tokens_per_sec": emitted / dt,
        "tokens_per_round": round(st.get("tokens_per_round", 0.0), 3),
        "spec_rounds_last_iter": st.get("rounds", 0),
        "fallback_rounds_last_iter": st.get("spec_fallback_rounds", 0),
        "adaptive_k_per_request": True,
        "note": (
            "same bad draft as spec_floor: adaptive k must beat that "
            "row by walking streams back to plain server rounds "
            "(the `plain` row's lax.scan batch decode is a different "
            "program and not the fallback's ceiling)"
        ),
    }


def _measure_spec_components(cfg, draft_cfg, batch, prompt_len, k,
                             ):
    """Time the three building blocks of a speculative round on warm
    caches — k-proposal draft roll, (k+1)-token chunked verify, plain
    1-token target step — and derive the break-even acceptance:
    speculation wins iff tokens-per-round > (t_draft_roll + t_verify) /
    t_plain_step.  Backend-agnostic measurement; on TPU it prices the
    real MXU/HBM cost of each block."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama, llama_infer

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    dparams = llama.init_params(jax.random.PRNGKey(9), draft_cfg)
    progs = llama_infer._spec_programs(cfg, draft_cfg, k, 0.0, 0, 0)
    max_len = prompt_len + k + 8
    cache_t = llama_infer.init_cache(cfg, batch, max_len)
    cache_d = llama_infer.init_cache(draft_cfg, batch, max_len)
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, prompt_len)
        ).astype("int32")
    )
    _, cache_t = progs["prefill_t"](params, prompts, cache_t)
    _, cache_d = progs["prefill_d"](dparams, prompts, cache_d)
    cur = prompts[:, -1]
    key = jax.random.PRNGKey(0)

    @jax.jit
    def plain_step(p, c, tok):
        lg, c2 = llama_infer.forward_step(p, tok[:, None], cfg, c)
        return jnp.argmax(lg[:, -1, :], axis=-1).astype(tok.dtype), c2

    def timeit(fn, iters=10):
        jax.block_until_ready(fn())  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    t_droll = timeit(
        lambda: progs["draft_roll"](dparams, cache_d, cur, key)[0]
    )
    d, _, _ = progs["draft_roll"](dparams, cache_d, cur, key)
    chunk = jnp.concatenate([cur[:, None], d], axis=1)
    t_verify = timeit(
        lambda: progs["target_verify"](params, cache_t, chunk)[0]
    )
    t_plain = timeit(lambda: plain_step(params, cache_t, cur)[0])
    return {
        "t_draft_roll_ms": round(t_droll * 1e3, 3),
        "t_verify_ms": round(t_verify * 1e3, 3),
        "t_plain_step_ms": round(t_plain * 1e3, 3),
        "k": k,
        # tokens-per-round a draft must earn for spec to win
        "break_even_tokens_per_round": round(
            (t_droll + t_verify) / max(t_plain, 1e-9), 3
        ),
    }


def measure_goodput(total_steps=80, timeout_s=900, backend="cpu"):
    """North-star probe (BASELINE.md): goodput under an injected worker
    failure.  Runs the real launcher->master->agent->worker tree,
    SIGKILLs one worker mid-run, and lets the stack breakpoint-save ->
    re-rendezvous -> warm-restore from shm and finish the job.

    ``backend="cpu"`` (default): 2 workers on forced-CPU devices — the
    hardware-free elasticity probe.  ``backend="tpu"``: ONE worker (one
    process per chip host) on the ambient backend, so the measured
    downtime includes real device-state transfer + XLA recompilation —
    the "restore in seconds" north star measured with a device in the
    loop (reference ``docs/blogs/flash_checkpoint.md:402-409``); the
    probe fails unless the worker's ``DEVICE`` line says ``tpu``.

    The worker needs the chip, so the CALLER must not have opened the
    device (``bench.py --goodput``, a process that imports no JAX).

    Returns {downtime_s, restore_from, probe_goodput, goodput_1h_pct} —
    ``goodput_1h_pct`` extrapolates the measured downtime to a 1-hour job
    with one failure (how the reference quotes goodput for long jobs;
    the raw probe number is dominated by the probe's short duration).
    """
    import os
    import re
    import signal
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench_goodput_")
    log_path = os.path.join(tmp, "run.log")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if backend == "tpu":
        nproc = 1  # one worker drives every chip of the host
    else:
        nproc = 2
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        })
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.run",
                "--standalone", f"--nproc_per_node={nproc}",
                "--job_name=bench-goodput", "--monitor_interval=1",
                os.path.join(repo, "examples", "nanogpt_train.py"),
                "--", f"--steps={total_steps}",
                f"--ckpt_dir={os.path.join(tmp, 'ckpt')}",
                "--ckpt_interval=3",
            ],
            cwd=repo, env=env, stdout=log, stderr=subprocess.STDOUT,
        )

    def read():
        try:
            with open(log_path) as f:
                return f.read()
        except OSError:
            return ""

    t_kill = None
    t_restored = None
    steps_before = 0
    deadline = time.time() + timeout_s
    try:
        while time.time() < deadline:
            content = read()
            if t_kill is None:
                # Last match: a pre-probe restart makes earlier pid
                # lines stale (killing a stale pid could hit an
                # unrelated process).
                pids = re.findall(
                    r"started %d worker\(s\): pids=\[([0-9, ]+)\]"
                    % nproc,
                    content,
                )
                if pids and re.search(r"step (1[0-9]|[2-9][0-9]) loss",
                                      content):
                    victim = int(pids[-1].split(",")[-1].strip())
                    os.kill(victim, signal.SIGKILL)
                    t_kill = time.time()
                    steps_before = len(re.findall(r"step \d+ loss",
                                                  content))
            elif t_restored is None:
                # Recovery ends when training actually RESUMES (a new
                # step logged after the kill), not at the restore
                # message — which prints before XLA re-compilation.
                if re.search(r"restored step=\d+", content) and len(
                    re.findall(r"step \d+ loss", content)
                ) > steps_before:
                    t_restored = time.time()
            if proc.poll() is not None:
                break
            time.sleep(0.5)
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
    except Exception:  # noqa: BLE001
        proc.kill()
        proc.wait()
    content = read()
    if t_kill is None or t_restored is None:
        raise RuntimeError(
            "goodput probe incomplete: " + content[-500:]
        )
    platforms = set(re.findall(r'DEVICE \{"platform": "(\w+)"', content))
    if platforms != {backend}:
        raise RuntimeError(
            f"goodput probe asked for {backend} workers, they ran on "
            f"{sorted(platforms)}"
        )
    downtime = t_restored - t_kill
    gp = re.findall(r"goodput=([0-9.]+)", content)
    restore_from = (
        "shm" if "warm restore from shm" in content else "storage"
    )
    return {
        "downtime_s": round(downtime, 1),
        "restore_from": restore_from,
        "probe_goodput": float(gp[-1]) if gp else None,
        "goodput_1h_pct": round(100.0 * (3600.0 - downtime) / 3600.0, 2),
    }


def main() -> int:
    """Sweep the Llama training candidates IN THIS PROCESS (one process
    holds the chip) and report the best as MFU.  Fails without a TPU: a
    CPU number is never written under a device metric's name."""
    import dataclasses as _dc

    import jax

    from dlrover_tpu.common.jax_env import (
        device_summary,
        enable_compilation_cache,
    )
    from dlrover_tpu.models import llama

    device = device_summary()
    if device["platform"] != "tpu":
        print(json.dumps({
            "metric": "llama_train_mfu", "device": device,
            "error": "bench.py measures on a TPU and found none",
        }))
        return 1
    peak_all = peak_bf16_flops(device["kind"]) * device["count"]
    enable_compilation_cache()

    # Batch and remat trade HBM for efficiency, and the 800M config's
    # wider GEMMs use the MXU better IF its optimizer state fits.  OOM
    # (or any failure) just eliminates a candidate.
    # _h128 variants trade head count for head_dim=128: the Pallas
    # attention kernel pads head_dim to the 128-lane width, so head_dim
    # 64/96 wastes 50%/25% of the attention FLOPs.
    m300 = llama.LlamaConfig.small_300m()
    m300h = _dc.replace(m300, n_head=8, n_kv_head=8)
    m800 = llama.LlamaConfig.medium_800m()
    m800h = _dc.replace(m800, n_head=12, n_kv_head=12)
    # (name, cfg, batch, remat, opt, probe_iters, fp8, accum)
    candidates = [
        ("llama_300m_h128", m300h, 8, "none", "adamw", 3, False, 1),
        ("llama_300m_h128", m300h, 16, "none", "adamw", 3, False, 1),
        ("llama_300m_h128", m300h, 32, "none", "adamw", 3, False, 1),
        # accum=2: b16-sized activations with b32 tokens/dispatch —
        # the fallback if b32 flat OOMs.
        ("llama_300m_h128", m300h, 32, "none", "adamw", 3, False, 2),
        # Unfused lm head: ~24% of the 300m FLOPs are the vocab GEMM;
        # at b8 the [16k, 32k] bf16 logits fit HBM, and one big MXU
        # GEMM may beat the scanned chunked CE.
        ("llama_300m_h128_nofuse", m300h, 8, "none", "adamw", 3,
         False, 1),
        # The 800m's wider GEMMs (d=1536, ff=4096) feed the MXU better;
        # fused lm-head loss + per-block remat make it fit in 16G HBM.
        ("llama_800m", m800, 8, "block", "adamw", 3, False, 1),
        ("llama_800m", m800, 16, "block", "adamw", 3, False, 1),
        ("llama_300m_h128", m300h, 16, "block", "adamw", 3, False, 1),
        # fp8 linears (delayed scaling): only wins where the chip lowers
        # e4m3 dots natively (v5p/v6); elsewhere XLA upcasts and the
        # candidate loses cleanly.
        ("llama_300m_h128_fp8", m300h, 8, "none", "adamw", 3, True, 1),
        ("llama_300m", m300, 8, "none", "adamw", 3, False, 1),
        ("llama_800m_h128", m800h, 8, "block", "adamw", 3, False, 1),
        ("llama_800m_h128", m800h, 16, "block", "adam8bit", 3, False, 1),
        ("llama_800m_h128_fp8", m800h, 8, "block", "adamw", 3, True, 1),
        # Activation-offload remat: block residuals parked in host
        # DRAM — the lever for b=16 if block-remat alone still OOMs
        # (VERDICT r2 next #9).
        ("llama_800m_h128", m800h, 16, "offload", "adamw", 3, False, 1),
    ]
    seq, iters = 2048, 10

    best = None  # (rate, name, cfg, batch, remat, opt, dt, loss, fp8, ...)
    for (name, cfg, batch, remat, opt, probe_iters, fp8,
         accum) in candidates:
        # "_nofuse" candidates override the fused-lm-head auto policy
        # (materialized-logits CE vs the scanned chunked CE).
        fused = False if name.endswith("_nofuse") else None
        try:
            dt, loss = _measure_candidate(cfg, batch, seq, remat,
                                          probe_iters, opt, fp8, accum,
                                          fused)
        except Exception as e:  # noqa: BLE001 - OOM/compile failure
            print(
                f"bench: candidate {name} b={batch} remat={remat} "
                f"opt={opt} failed: {type(e).__name__}: {str(e)[:600]}",
                file=sys.stderr,
            )
            continue
        rate = model_flops_per_step(cfg, batch, seq) / dt
        print(
            f"bench: candidate {name} b={batch} remat={remat} opt={opt}: "
            f"{dt*1e3:.1f} ms/step, {rate/1e12:.1f} model TFLOP/s",
            file=sys.stderr,
        )
        if best is None or rate > best[0]:
            best = (rate, name, cfg, batch, remat, opt, dt, loss, fp8,
                    accum, fused)
    if best is None:
        print(json.dumps({"metric": "llama_train_mfu", "device": device,
                          "error": "all candidates failed"}))
        return 1

    _, name, cfg, batch, remat, opt, dt, loss, fp8, accum, fused = best
    # Re-measure the winner at full iteration count for a stable number.
    dt, loss = _measure_candidate(cfg, batch, seq, remat, iters, opt, fp8,
                                  accum, fused)
    flops = model_flops_per_step(cfg, batch, seq)
    mfu_pct = 100.0 * flops / dt / peak_all
    tokens_per_sec = batch * seq / dt

    # Decode (serving) throughput through the KV-cache generate path;
    # the int8 kv variant halves the cache reads of an HBM-bound step.
    dcfg = llama.LlamaConfig.small_300m()
    decode = {
        "decode_tokens_per_sec": round(
            _measure_decode(dcfg, 8, 128, 128), 1),
        "decode_tokens_per_sec_int8": round(
            _measure_decode(dcfg, 8, 128, 128, quant_kv=True), 1),
    }
    n_dev = device["count"]
    print(
        json.dumps(
            {
                "metric": "llama_train_mfu",
                "value": round(mfu_pct, 2),
                "unit": "%",
                "vs_baseline": round(mfu_pct / REFERENCE_HFU_PCT, 4),
                "model": name,
                "device": device,
                "strategy": (
                    f"dp{n_dev} remat={remat} batch={batch} opt={opt}"
                    + (f" accum={accum}" if accum > 1 else "")
                    + (" fp8" if fp8 else "")
                    + (" fused_lm_head"
                       if (llama.uses_fused_lm_head(cfg)
                           if fused is None else fused) else "")
                ),
                "step_time_s": round(dt, 4),
                "tokens_per_sec": round(tokens_per_sec, 1),
                "final_loss": round(loss, 4),
                **decode,
            }
        )
    )
    return 0


def goodput_main(argv: list) -> int:
    """``bench.py --goodput [cpu|tpu]``: the elasticity probe as its own
    command.  Its worker needs the chip, so the process that launches it
    must never open the device — this one imports no JAX."""
    backend = argv[0] if argv else "tpu"
    if backend not in ("cpu", "tpu"):
        print("usage: bench.py --goodput [cpu|tpu]", file=sys.stderr)
        return 2
    res = measure_goodput(backend=backend)
    print(json.dumps({"metric": "goodput_probe", **res}))
    return 0


def spec_bench_main(argv: list) -> int:
    """Where does speculative decoding win?  Measures, in this process:

    - plain greedy decode tokens/s (the baseline),
    - speculative with the target AS draft (acceptance ceiling ~k+1),
    - speculative with a small random-init draft (acceptance floor ~1),
    - the round's component times -> break-even tokens-per-round.

    Untrained models can't show a realistic mid-curve acceptance, so
    the artifact reports the measured floor/ceiling plus the break-even
    threshold a trained draft must clear — the honest version of the
    table (VERDICT r4 weak #5 asked for speculation's win condition).
    Writes SPEC_DECODE_{TPU|CPU}.json; on TPU uses the 300m config, on
    CPU a tiny one."""
    import os

    import jax

    from dlrover_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = llama.LlamaConfig.small_300m()
        import dataclasses as _dc

        draft_cfg = _dc.replace(cfg, n_layer=2)
        batch, plen, ntok, k = 8, 128, 128, 4
    else:
        cfg = llama.LlamaConfig.tiny(vocab_size=512)
        draft_cfg = llama.LlamaConfig.tiny(vocab_size=512, n_layer=1)
        batch, plen, ntok, k = 4, 16, 32, 4
    out: dict = {"backend": jax.default_backend(),
                 "model": {"target_layers": cfg.n_layer,
                           "draft_layers": draft_cfg.n_layer,
                           "batch": batch, "k": k}}
    if not on_tpu:
        out["note"] = (
            "tiny-model CPU regime: the host-driven round loop "
            "(per-round sync + numpy acceptance) dominates, so "
            "spec tokens/s under-states the TPU picture where model "
            "compute dwarfs the loop; break_even is still the right "
            "threshold shape"
        )
    rows = [
        ("plain", lambda: {"tokens_per_sec": _measure_decode(
            cfg, batch, plen, ntok)}),
        ("spec_ceiling_draft_eq_target", lambda: _measure_spec_decode(
            cfg, cfg, batch, plen, ntok, k, share_params=True)),
        ("spec_floor_random_small_draft", lambda: _measure_spec_decode(
            cfg, draft_cfg, batch, plen, ntok, k)),
        ("spec_adaptive_k_bad_draft", lambda: _measure_spec_adaptive(
            cfg, draft_cfg, batch, plen, ntok, k)),
        ("components_small_draft", lambda: _measure_spec_components(
            cfg, draft_cfg, batch, plen, k)),
    ]
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"SPEC_DECODE_{'TPU' if on_tpu else 'CPU'}.json",
    )
    for name, measure in rows:
        try:
            out[name] = measure()
        except Exception as e:  # noqa: BLE001
            out[name] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        print(name, out[name], file=sys.stderr)
    out["complete"] = all(
        isinstance(out.get(n), dict) and "error" not in out[n]
        for n, _ in rows
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    comp = out.get("components_small_draft", {})
    plain = out.get("plain", {})
    if "break_even_tokens_per_round" in comp:
        out["verdict"] = {
            "break_even_tokens_per_round":
                comp["break_even_tokens_per_round"],
            "note": (
                "speculation beats plain decode iff a trained draft "
                "earns more accepted tokens/round than break_even; "
                "ceiling/floor rows bound the measurable range"
            ),
        }
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({
        "metric": "spec_decode_break_even_tokens_per_round",
        "value": comp.get("break_even_tokens_per_round", -1),
        "unit": "tokens/round",
        "vs_baseline": (
            round(
                out.get("spec_ceiling_draft_eq_target", {})
                .get("tokens_per_sec", 0.0)
                / plain["tokens_per_sec"], 3,
            ) if plain.get("tokens_per_sec") else 0.0
        ),
        "backend": out["backend"],
        "artifact": path,
    }))
    return 0


def _ckpt_scaleout_rows(
    tmp: str,
    state_mb: int,
    tensors_n: int,
    link_mbps: int,
    ranks_rows: list,
    flush,
    result: dict,
) -> dict:
    """Scale-out checkpoint rows (ISSUE 7): N simulated ranks, each with
    its own PACED storage link, persist disjoint slices of one replicated
    state concurrently; commit includes the slice-coverage tiling proof.
    Then an incremental save with ~10% dirty tensors, a byte-exact
    restore of the sliced+incremental step, and an fsck pass over it.

    The per-rank link pacing is the measurement model (see
    ``ckpt_bench_main``'s docstring): link bandwidth is per-rank in a
    real fleet, so aggregate persist MB/s is the quantity that must
    scale with rank count; CPU work stays real and is charged against
    each rank's pacing budget."""
    import contextlib
    import os
    import threading

    import numpy as np

    from dlrover_tpu.checkpoint import fsck as fsck_mod
    from dlrover_tpu.checkpoint import shard_file, slicer
    from dlrover_tpu.checkpoint.tree_utils import ShardSource
    from dlrover_tpu.common.storage import PosixDiskStorage

    mb = 1 << 20

    class PacedStorage(PosixDiskStorage):
        """One rank's modeled storage link: streamed bytes are paced to
        ``link_mbps``, with real CPU work (CRC, pwrite) spending the
        same budget — a rank never goes faster than its link, and only
        goes slower when compute genuinely exceeds it."""

        def __init__(self, mbps: float):
            self._budget = float(mbps) * mb

        @contextlib.contextmanager
        def stream_writer(self, path):
            with PosixDiskStorage.stream_writer(self, path) as sink:
                t0 = time.perf_counter()
                sent = [0]
                budget = self._budget

                class Paced:
                    parallel_safe = False

                    @staticmethod
                    def write_at(data, offset):
                        n = sink.write_at(data, offset)
                        sent[0] += n
                        lag = (
                            sent[0] / budget
                            - (time.perf_counter() - t0)
                        )
                        if lag > 0:
                            time.sleep(lag)
                        return n

                    read_at = staticmethod(sink.read_at)
                    truncate = staticmethod(sink.truncate)

                yield Paced()

    per = max(1, state_mb * mb // tensors_n // 4)
    state = {
        f"w{i}|0": (np.arange(per, dtype=np.float32) * float(i + 1))
        for i in range(tensors_n)
    }
    logical = sum(a.nbytes for a in state.values())
    paths = sorted(k.rsplit("|", 1)[0] for k in state)

    def mkinfo(world: int) -> dict:
        return {
            k: {
                "path": k.rsplit("|", 1)[0],
                "global_shape": list(v.shape),
                "index": [[0, d] for d in v.shape],
                "owners": list(range(world)),
            }
            for k, v in state.items()
        }

    def run_step(ckpt_dir, step, world, trackers, storages):
        """One fleet save: plan+stream per rank concurrently (each on
        its own link), then the coverage-gated commit.  Returns
        (wall_seconds, written_bytes, skipped, committed)."""
        info = mkinfo(world)
        plans = [None] * world
        barrier = threading.Barrier(world + 1)

        def rank_body(pid: int) -> None:
            st = storages[pid]
            extra = {
                "step": step, "meta": {}, "tensors_info": info,
                "process_id": pid, "num_processes": world,
                "tree_paths": paths,
            }
            barrier.wait()
            plan = slicer.plan_persist(
                state, extra, process_id=pid, num_processes=world,
                sliced=True, tracker=trackers[pid],
                holder_exists=lambda s: st.exists(
                    shard_file.shard_path(ckpt_dir, s, pid)
                ),
            )
            stats = shard_file.write_shard_from_views(
                st, ckpt_dir, step, pid, plan.tensors, plan.extra,
                workers=1, meta_extra=plan.meta_extra,
            )
            trackers[pid].note_plan(plan, step, stats.get("crcs", {}))
            plans[pid] = plan

        threads = [
            threading.Thread(target=rank_body, args=(pid,), daemon=True)
            for pid in range(world)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        committed = slicer.commit_gate(storages[0], ckpt_dir, step)
        if committed:
            # keep_last=0: keep every step (the incremental row's refs
            # target step 1; rotation's ref protection is unit-tested).
            shard_file.commit(storages[0], ckpt_dir, step, keep_last=0)
        wall = max(time.perf_counter() - t0, 1e-9)
        written = sum(p.written_bytes for p in plans)
        skipped = sum(p.skipped for p in plans)
        return wall, written, skipped, committed

    scale = {
        "link_mbps": link_mbps,
        "state_mb": round(logical / mb, 1),
        "model": "per-rank paced storage links; aggregate_mbps = "
                 "logical state bytes / wall (slowest rank + coverage-"
                 "gated commit)",
        "rows": [],
    }
    result["scaleout"] = scale
    agg_by_world = {}
    for world in ranks_rows:
        ckpt_dir = os.path.join(tmp, f"scale_{world}r")
        trackers = [slicer.DirtyTracker() for _ in range(world)]
        storages = [PacedStorage(link_mbps) for _ in range(world)]
        wall, written, skipped, committed = run_step(
            ckpt_dir, 1, world, trackers, storages
        )
        agg = logical / mb / wall
        agg_by_world[world] = agg
        scale["rows"].append({
            "ranks": world,
            "kind": "sliced_full",
            "seconds": round(wall, 4),
            "aggregate_mbps": round(agg, 1),
            "written_mb": round(written / mb, 1),
            "per_rank_written_mb": round(written / world / mb, 1),
            "committed": committed,
        })
        flush()
        if world != max(ranks_rows):
            continue
        # Incremental row on the biggest world: ~10% of tensors dirtied
        # between saves; cost must track the dirty bytes, not the state.
        dirty_keys = list(state)[: max(1, tensors_n // 10)]
        for k in dirty_keys:
            state[k] = state[k] + 1.0
        dirty_bytes = sum(state[k].nbytes for k in dirty_keys)
        wall2, written2, skipped2, committed2 = run_step(
            ckpt_dir, 2, world, trackers, storages
        )
        scale["rows"].append({
            "ranks": world,
            "kind": "incremental_10pct_dirty",
            "seconds": round(wall2, 4),
            "effective_aggregate_mbps": round(logical / mb / wall2, 1),
            "written_mb": round(written2 / mb, 1),
            "dirty_mb": round(dirty_bytes / mb, 1),
            "written_bytes_over_dirty_bytes": round(
                written2 / max(dirty_bytes, 1), 3
            ),
            "tensors_skipped": skipped2,
            "committed": committed2,
        })
        flush()
        # Byte-exact restore of the sliced+incremental step (slices
        # reassembled across ranks, refs resolved into step 1).
        src = ShardSource()
        plain = PosixDiskStorage()
        for pid in range(world):
            tensors_r, slices_r, extra_r = shard_file.read_shard_pieces(
                plain, ckpt_dir, 2, pid
            )
            src.add(tensors_r, extra_r["tensors_info"], slices_r)
        exact = True
        for k, v in state.items():
            got = src.assemble(
                k.rsplit("|", 1)[0],
                tuple((0, d) for d in v.shape),
                dtype=v.dtype,
            )
            exact = exact and got is not None and bool(
                np.array_equal(got, v)
            )
        scale["restore_byte_exact"] = exact
        scale["fsck_clean_on_sliced"] = not fsck_mod.fsck(
            ckpt_dir, plain
        ).damaged
    if 1 in agg_by_world and 2 in agg_by_world:
        scale["speedup_2_ranks_vs_1"] = round(
            agg_by_world[2] / max(agg_by_world[1], 1e-9), 2
        )
    if 1 in agg_by_world and 4 in agg_by_world:
        scale["speedup_4_ranks_vs_1"] = round(
            agg_by_world[4] / max(agg_by_world[1], 1e-9), 2
        )
    flush()
    return scale


def ckpt_bench_main(argv: list) -> int:
    """Flash-checkpoint fast-path bench (ISSUE 4 acceptance artifact).

    Measures, for a parameterized synthetic state, the numbers the paper
    quotes: ``save_to_memory`` blocking ms (the train stall) and staged
    MB/s, then the shm->storage persist MB/s for the **before** path
    (``read_state(copy=True)`` -> ``pack_shard`` -> monolithic write —
    three full state copies) against the **after** path
    (``write_shard_from_views`` streaming, zero copies, optional parallel
    range workers), plus restore MB/s — with the byte-audit counting
    copies/passes per row so "exactly one pass over state bytes" is a
    measured fact, not a claim.  Flushes the JSON artifact after every
    row (record machinery; a killed run keeps its measured rows).

    **Scale-out rows** (ISSUE 7): the ``scaleout`` section measures the
    cross-replica SLICED persist at ranks=1/2/4 plus an incremental save
    with ~10% dirty tensors.  Each simulated rank streams its disjoint
    slice through its own *modeled storage link* (``--link_mbps``, a
    paced sink — the serve bench's device-round-floor precedent): in a
    real fleet every rank owns an independent storage link and per-rank
    link bandwidth is the binding constraint the sliced persist exists
    to scale past, while on this 1-core CI host unthrottled ranks would
    timeshare one CPU and measure nothing.  CPU work (CRC, pwrite,
    slicing, the commit-time coverage proof) stays real and counts
    against each rank's pacing budget.  ``aggregate_mbps`` = logical
    state bytes / wall-clock for the whole step (slowest rank + commit
    with its tiling proof).

    Flags: ``--state_mb=N`` (default 256) ``--tensors=N`` (16)
    ``--workers=N`` (4) ``--saves=N`` (3) ``--link_mbps=N`` (80)
    ``--scaleout_ranks=1,2,4`` ``--dir=PATH`` (defaults to
    /dev/shm so storage bandwidth does not mask the host-side path cost;
    point it at a real checkpoint filesystem to measure end-to-end)
    ``--out=PATH`` ``--smoke`` (tiny config for the tier-1 gate).

    Host I/O only — no device in the loop.
    """
    import os
    import shutil
    import tempfile

    t_start = time.perf_counter()
    opts = {
        "state_mb": 256, "tensors": 16, "workers": 4, "saves": 3,
        "link_mbps": 80,
    }
    scaleout_ranks = [1, 2, 4]
    out_path = None
    work_dir = None
    for a in argv:
        if a == "--smoke":
            opts.update(
                state_mb=8, tensors=8, workers=2, saves=2, link_mbps=40
            )
            scaleout_ranks = [1, 2]
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif a.startswith("--dir="):
            work_dir = a.split("=", 1)[1]
        elif a.startswith("--scaleout_ranks="):
            scaleout_ranks = [
                int(x) for x in a.split("=", 1)[1].split(",") if x
            ]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = int(v)
    import numpy as np

    import jax

    from dlrover_tpu.checkpoint import fsck as fsck_mod
    from dlrover_tpu.checkpoint import shard_file
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common.byte_audit import audit
    from dlrover_tpu.common.storage import PosixDiskStorage

    backend = jax.default_backend()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            f"CKPT_BENCH_{'TPU' if backend == 'tpu' else 'CPU'}.json",
        )
    if work_dir is None:
        work_dir = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="ckpt_bench_", dir=work_dir)
    mb = 1 << 20
    per = max(1, opts["state_mb"] * mb // opts["tensors"] // 4)
    state = {
        f"w{i}": (np.arange(per, dtype=np.float32) * float(i + 1))
        for i in range(opts["tensors"])
    }
    state_bytes = sum(a.nbytes for a in state.values())
    result = {
        "bench": "ckpt_fast_path",
        "backend": backend,
        "state_mb": round(state_bytes / mb, 1),
        "tensors": opts["tensors"],
        "workers": opts["workers"],
        "work_dir": tmp,
        "rows": [],
    }

    def flush():
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    job = f"ckptbench{os.getpid()}"
    eng = CheckpointEngine(os.path.join(tmp, "ckpt"), job_name=job)
    storage = PosixDiskStorage()
    try:
        # 1. The train stall: save_to_memory blocking time, per save.
        stalls = []
        for s in range(opts["saves"]):
            t0 = time.perf_counter()
            eng.save_to_memory(s + 1, state)
            stalls.append(round((time.perf_counter() - t0) * 1e3, 1))
        result["save_to_memory"] = {
            "stall_ms_per_save": stalls,
            "staged_mbps": round(
                state_bytes / mb / max(stalls[-1] / 1e3, 1e-9), 1
            ),
            "note": "first save includes shm arena creation+growth",
        }
        flush()

        # 2. Persist rows, all consuming the SAME staged arena state.
        views, extra = eng._arena.read_state(copy=False)

        def timed_row(name, fn):
            audit.enable()
            t0 = time.perf_counter()
            fn()
            dt = max(time.perf_counter() - t0, 1e-9)
            snap = audit.snapshot()
            audit.disable()
            row = {
                "path": name,
                "seconds": round(dt, 4),
                "persist_mbps": round(state_bytes / mb / dt, 1),
                "state_copies": round(snap["copied_bytes"] / state_bytes, 2),
                "write_passes": snap["passes"].get("stream_data", 0)
                + snap["passes"].get("stream_relayout", 0)
                + (1 if snap["copied_by_site"].get("pack_join") else 0),
                "copied_by_site": {
                    k: round(v / mb, 1)
                    for k, v in snap["copied_by_site"].items()
                },
            }
            result["rows"].append(row)
            flush()
            return row

        legacy_path = os.path.join(tmp, "legacy.ckpt")
        stream_path = os.path.join(tmp, "stream.ckpt")

        def legacy():
            tensors, ex = eng._arena.read_state(copy=True)
            storage.write(shard_file.pack_shard(tensors, ex), legacy_path)

        def stream(workers, path):
            shard_file.ShardStreamWriter(
                storage, path, views, extra, workers=workers
            ).write()

        row_legacy = timed_row("before_pack_copy", legacy)
        row_s1 = timed_row("after_stream_w1", lambda: stream(1, stream_path))
        row_sn = timed_row(
            f"after_stream_w{opts['workers']}",
            lambda: stream(opts["workers"], os.path.join(tmp, "streamN.ckpt")),
        )
        with open(legacy_path, "rb") as fa, open(stream_path, "rb") as fb:
            result["byte_identical"] = fa.read() == fb.read()

        # 3. Restore MB/s (read + verify + materialize arrays).
        t0 = time.perf_counter()
        shard_file.unpack_shard(storage.read(stream_path))
        dt = max(time.perf_counter() - t0, 1e-9)
        result["restore_mbps"] = round(state_bytes / mb / dt, 1)

        # 4. A real committed checkpoint written entirely via the
        # streaming path must be fsck-clean.
        fsck_dir = os.path.join(tmp, "fsck_ckpt")
        storage.safe_makedirs(fsck_dir)
        shard_file.write_shard_from_views(
            storage, fsck_dir, int(extra.get("step", 1)), 0, views, extra,
            workers=opts["workers"],
        )
        shard_file.commit(storage, fsck_dir, int(extra.get("step", 1)))
        result["fsck_clean_on_streamed"] = not fsck_mod.fsck(
            fsck_dir, storage
        ).damaged

        # 5. Scale-out rows: sliced multi-rank persist over modeled
        # per-rank links + dirty-fence incremental save + restore/fsck.
        _ckpt_scaleout_rows(
            tmp, opts["state_mb"], opts["tensors"], opts["link_mbps"],
            scaleout_ranks, flush, result,
        )

        best = max(row_s1["persist_mbps"], row_sn["persist_mbps"])
        result["speedup_stream_vs_legacy"] = round(
            best / max(row_legacy["persist_mbps"], 1e-9), 2
        )
        result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
        result["complete"] = True
        flush()
    finally:
        eng._arena.close(unlink=True)
        eng.close()
        shutil.rmtree(tmp, ignore_errors=True)
    result["work_dir"] = "(removed)"
    flush()
    print(json.dumps({
        "metric": "ckpt_persist_speedup",
        "value": result.get("speedup_stream_vs_legacy", 0.0),
        "unit": "x_vs_pack_copy_path",
        "vs_baseline": result.get("speedup_stream_vs_legacy", 0.0),
        "backend": backend,
        "stall_ms_last": stalls[-1],
        "agg_speedup_2_ranks": result.get("scaleout", {}).get(
            "speedup_2_ranks_vs_1", 0.0
        ),
        "artifact": out_path,
    }))
    return 0 if result.get("complete") else 1


def serve_bench_main(argv: list) -> int:
    """Serving-fleet bench (ISSUE 5 acceptance artifact).

    Drives ``dlrover_tpu.serving`` end to end on the CPU host: one
    gateway, N tiny-llama replicas, a seeded Poisson request stream —
    and records p50/p99 TTFT, request-latency percentiles, and
    aggregate tokens/s at 1 vs 2+ replicas into
    ``SERVE_BENCH_CPU.json``.

    Replica rows run as SUBPROCESSES (each with its own jax runtime)
    against the gateway's real gRPC port, so the measured path is the
    wire path.  ``--device_round_ms`` (default 20) puts a latency floor
    under every decode round, modelling the accelerator-bound regime:
    on TPU the round's model time is off-host and N replicas' rounds
    overlap; on this 1-core CI host pure-CPU decode compute cannot
    overlap across processes, so the floor — a blocking sleep exactly
    where the device future would block — is what makes the fleet-
    scaling measurement about the CONTROL PLANE (admission, routing,
    streaming, journal fsync) rather than about timesharing XLA-CPU.
    ``--device_round_ms=0`` measures the raw timeshared regime.

    Flags: ``--requests=N`` (24) ``--mnt=N`` (24 new tokens)
    ``--slots=N`` (2 per replica) ``--rps=F`` (50 Poisson arrivals/s)
    ``--replicas=1,2`` (rows) ``--device_round_ms=F`` (20)
    ``--seed=N`` ``--out=PATH`` ``--smoke`` (tiny single-replica
    in-process row for the tier-1 gate: loopback transport, no
    subprocesses, no round floor).  ``--tracing_only`` /
    ``--paged_only`` re-measure just that section and merge it into
    the existing artifact.
    """
    import argparse
    import os
    import shutil
    import subprocess
    import tempfile
    import threading

    t_start = time.perf_counter()
    opts = {
        "requests": 24, "mnt": 24, "slots": 2, "rps": 50.0,
        "seed": 0, "device_round_ms": 20.0, "timeout": 300.0,
        # Routing rows (ISSUE 8): a Zipf-skewed shared-prefix workload
        # at `routing_replicas`, measured under three data planes —
        # least-loaded (fingerprints withheld), prefix-aware routing,
        # and prefill/decode disaggregation with int8 KV handoff.
        # The routing rows run near fleet capacity on a model sized so
        # admission prefill is a real cost (256-wide, 4 layers, long
        # shared prefix) — the regime prefix caching exists for.
        "routing_replicas": 4, "routing_requests": 40,
        "routing_mnt": 16, "routing_rps": 20.0,
        "routing_layers": 4, "routing_d_model": 256,
        "routing_d_ff": 512,
        "prefix_len": 192, "prefix_templates": 6, "zipf_a": 1.3,
        "prefix_cache_cap": 2,
        # Speculation rows (ISSUE 11): long-decode workload at MATCHED
        # chip budget — `off` = spec_chips plain replicas, `on` =
        # spec_chips-1 spec targets + 1 draft replica (ceiling draft:
        # target weights, standing in for a trained one; the committed
        # SPEC_DECODE artifact bounds the realistic range), `off_floor`
        # = spec_chips-1 plain (the fallback baseline), `fallback` =
        # spec_chips-1 targets + a BAD draft with per-request adaptive
        # k.  Arrivals run at the speculation-OFF fleet's analytic
        # knee; the win condition is SLO goodput per chip.
        "spec_chips": 4, "spec_requests": 32, "spec_mnt": 48,
        "spec_rps": 0.0, "spec_slo_ms": 0.0, "spec_k": 4,
        "spec_draft_ratio": 0.25,
        # Paged-KV rows (ISSUE 19): direct in-process DecodeServer A/B
        # at MATCHED KV memory — `slotted` reserves paged_slots x
        # max_len tokens per layer; `paged` gets a block pool of the
        # SAME token count (paged_slots x max_len / block_size blocks)
        # but paged_seat_factor x more seats, so admission is bounded
        # by memory actually needed, not by reservations.  Two
        # workloads: `uniform` (moderate length spread) and `longtail`
        # (Zipf sequence lengths — where slotted strands the most
        # capacity behind max_len reservations).
        "paged_requests": 24, "paged_mnt": 16, "paged_slots": 4,
        "paged_block_size": 8, "paged_max_len": 64,
        "paged_seat_factor": 3,
    }
    replicas_rows = [1, 2]
    out_path = None
    smoke = False
    #: Re-measure ONLY the tracing-overhead pair (ISSUE 12) and merge
    #: it into the existing artifact — the committed overhead row does
    #: not require re-running the whole serve bench.
    tracing_only = False
    #: Same contract for the paged-KV section (ISSUE 19): re-measure
    #: ONLY the slotted-vs-paged A/B and merge it into the existing
    #: artifact.
    paged_only = False
    for a in argv:
        if a == "--tracing_only":
            tracing_only = True
        elif a == "--paged_only":
            paged_only = True
        elif a == "--smoke":
            smoke = True
            opts.update(requests=5, mnt=6, device_round_ms=0.0,
                        timeout=60.0, routing_replicas=1,
                        routing_requests=5, routing_mnt=6,
                        routing_rps=50.0, routing_layers=2,
                        routing_d_model=64, routing_d_ff=128,
                        prefix_len=28, prefix_templates=2,
                        spec_chips=2, spec_requests=4, spec_mnt=12,
                        spec_rps=50.0, spec_k=3,
                        paged_requests=6, paged_mnt=6, paged_slots=2,
                        paged_max_len=32)
            replicas_rows = [1]
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif a.startswith("--replicas="):
            replicas_rows = [
                int(x) for x in a.split("=", 1)[1].split(",") if x
            ]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    import numpy as np

    import jax

    from dlrover_tpu.models import llama
    from dlrover_tpu.serving import (
        Gateway,
        GatewayConfig,
        LoopbackTransport,
        ServeClient,
    )

    backend = jax.default_backend()
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            f"SERVE_BENCH_{'TPU' if backend == 'tpu' else 'CPU'}.json",
        )
    repo = os.path.dirname(os.path.abspath(__file__))
    cfg = llama.LlamaConfig.tiny(n_layer=2)
    sys.path.insert(0, os.path.join(repo, "examples"))
    import serve_common  # noqa: E402

    prompts, _ = serve_common.seeded_requests(
        cfg, opts["requests"], opts["seed"] + 1
    )
    result = {
        "bench": "serve_fleet",
        "backend": backend,
        "model": {"layers": cfg.n_layer, "vocab": cfg.vocab_size,
                  "dtype": "float32"},
        "workload": {
            "requests": opts["requests"],
            "max_new_tokens": opts["mnt"],
            "slots_per_replica": opts["slots"],
            "poisson_rps": opts["rps"],
            "seed": opts["seed"],
        },
        "device_round_ms": opts["device_round_ms"],
        "note": (
            "device_round_ms models the accelerator-bound regime: a "
            "blocking per-round floor standing in for off-host device "
            "time (on the 1-core CI host pure-CPU decode compute "
            "timeshares instead of overlapping, which would measure "
            "XLA-CPU scheduling, not the serving control plane); "
            "device_round_ms=0 rows measure that raw regime"
        ),
        "rows": [],
    }
    # --load_bench owns the `load` section of this artifact; a
    # serve_bench rewrite must not silently erase it.  --tracing_only
    # goes further: the WHOLE prior artifact is the base and only the
    # tracing section is re-measured.
    try:
        with open(out_path) as f:
            prior = json.load(f)
        if isinstance(prior, dict):
            if tracing_only or paged_only:
                prior.setdefault("rows", [])
                result = prior
            elif "load" in prior:
                result["load"] = prior["load"]
    except (OSError, ValueError):
        if tracing_only or paged_only:
            print("--tracing_only/--paged_only need an existing "
                  f"artifact at {out_path}", file=sys.stderr)
            return 2

    def flush():
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    def zipf_workload(n_requests: int):
        """Shared-prefix workload: K templates, Zipf(a) popularity,
        4-12 own tokens per request.  Returns [(full_prompt,
        prefix_len)] — the fingerprint is derived at submit."""
        rng = np.random.RandomState(opts["seed"] + 11)
        K = opts["prefix_templates"]
        p0 = opts["prefix_len"]
        templates = [
            rng.randint(1, cfg.vocab_size, size=(p0,)).astype(np.int32)
            for _ in range(K)
        ]
        w = 1.0 / np.arange(1, K + 1) ** opts["zipf_a"]
        w /= w.sum()
        reqs = []
        for _ in range(n_requests):
            k = int(rng.choice(K, p=w))
            own = rng.randint(
                1, cfg.vocab_size, size=(int(rng.randint(4, 12)),)
            ).astype(np.int32)
            reqs.append((np.concatenate([templates[k], own]), p0))
        return reqs

    def run_row(n_replicas: int, mode: str = "plain",
                trace_sample=None) -> dict:
        """One fleet measurement.  ``plain`` = the uniform workload at
        least-loaded routing (the PR-5 rows); the routing modes share
        one Zipf prefix workload: ``least_loaded`` withholds the
        fingerprints, ``prefix`` routes on them, ``disagg`` splits the
        fleet into prefill/decode pools with int8 KV handoff.
        ``trace_sample`` overrides the gateway's head-based trace
        sampling (ISSUE 12): the tracing-overhead pair runs the prefix
        plane at 0.0 vs 1.0."""
        tmp = tempfile.mkdtemp(prefix="serve_bench_")
        cfg_kw = {}
        if trace_sample is not None:
            cfg_kw["trace_sample"] = float(trace_sample)
        gw = Gateway(
            port=0,
            # disagg = the PR-8 relay plane (kv_p2p off); disagg_p2p =
            # ticket-only handoff, the segment bytes never transit the
            # gateway (ISSUE 9).
            config=GatewayConfig(queue_cap=512, prefix_reserve_s=3.0,
                                 kv_p2p=(mode == "disagg_p2p"),
                                 **cfg_kw),
            # Finer than the 1-2-5 default: routing-mode TTFT deltas
            # land inside one default bucket and would read as ties.
            histogram_buckets=(
                10, 25, 50, 100, 200, 350, 500, 700, 900, 1100,
                1350, 1600, 2000, 2400, 2900, 3500, 4200, 5000,
                6000, 7500, 10000, 15000, 30000,
            ),
        )
        gw.start()
        procs = []
        threads = []
        runners = []
        roles = ["unified"] * n_replicas
        quant = False
        if mode in ("disagg", "disagg_p2p"):
            half = max(1, n_replicas // 2)
            roles = ["prefill"] * (n_replicas - half) + \
                ["decode"] * half
            quant = True
        if mode == "plain":
            max_len = 16 + opts["mnt"] + 16
            warm_p0 = 0
            row_mnt = opts["mnt"]
            row_rps = opts["rps"]
            model_kw = {"n_layer": 2, "d_model": 64, "d_ff": 128}
            workload = [(p, 0) for p in prompts]
        else:
            row_mnt = opts["routing_mnt"]
            row_rps = opts["routing_rps"]
            max_len = opts["prefix_len"] + 16 + row_mnt + 8
            warm_p0 = opts["prefix_len"]
            model_kw = {
                "n_layer": opts["routing_layers"],
                "d_model": opts["routing_d_model"],
                "d_ff": opts["routing_d_ff"],
            }
            workload = zipf_workload(opts["routing_requests"])
        arr_rng = np.random.RandomState(opts["seed"] + 7)
        row_gaps = arr_rng.exponential(
            1.0 / max(row_rps, 1e-6), size=len(workload)
        )
        try:
            if smoke:
                # In-process loopback replicas: the tier-1 gate must
                # not pay subprocess jax imports.
                sys.path.insert(0, os.path.join(repo, "examples"))
                import llama_serve_fleet as fleet_mod
                for i in range(n_replicas):
                    fleet_args = argparse.Namespace(
                        slots=opts["slots"], max_len=max_len,
                        journal_dir=os.path.join(tmp, "j"),
                        replica_id=f"r{i}", seed=opts["seed"],
                        poll_interval=0.005, round_floor_ms=0.0,
                        replica_role=roles[i], quant_kv=quant,
                        prefix_cache_cap=opts["prefix_cache_cap"],
                        warm_prefix_len=warm_p0, **model_kw,
                    )
                    runner = fleet_mod.build_replica(
                        fleet_args, LoopbackTransport(gw.handle)
                    )
                    runners.append(runner)
                    th = threading.Thread(target=runner.run,
                                          daemon=True)
                    th.start()
                    threads.append(th)
            else:
                env = dict(os.environ, JAX_PLATFORMS="cpu",
                           PYTHONPATH=repo)
                env.pop("DLROVER_TPU_FAULTS", None)
                for i in range(n_replicas):
                    log = open(os.path.join(tmp, f"r{i}.log"), "w")
                    cmd = [
                        sys.executable,
                        os.path.join(repo, "examples",
                                     "llama_serve_fleet.py"),
                        "--role", "replica",
                        "--gateway", f"127.0.0.1:{gw.port}",
                        "--replica_id", f"r{i}",
                        "--replica_role", roles[i],
                        "--slots", str(opts["slots"]),
                        "--max_len", str(max_len),
                        "--journal_dir", os.path.join(tmp, "j"),
                        "--seed", str(opts["seed"]),
                        "--poll_interval", "0.01",
                        "--prefix_cache_cap",
                        str(opts["prefix_cache_cap"]),
                        "--warm_prefix_len", str(warm_p0),
                        "--n_layer", str(model_kw["n_layer"]),
                        "--d_model", str(model_kw["d_model"]),
                        "--d_ff", str(model_kw["d_ff"]),
                        "--round_floor_ms",
                        str(opts["device_round_ms"]),
                    ]
                    if quant:
                        cmd.append("--quant_kv")
                    procs.append((subprocess.Popen(
                        cmd, cwd=repo, env=env, stdout=log,
                        stderr=subprocess.STDOUT,
                    ), log))
            deadline = time.time() + opts["timeout"]
            while time.time() < deadline:
                snap = gw.core.stats_snapshot()
                if snap["replicas_alive"] >= n_replicas:
                    break
                time.sleep(0.2)
            else:
                raise TimeoutError(
                    f"{n_replicas} replicas never registered"
                )
            client = ServeClient(LoopbackTransport(gw.handle),
                                 poll_interval=0.01)
            tag = f"{mode[0]}{n_replicas}"
            t0 = time.perf_counter()
            for i, (prompt, p0) in enumerate(workload):
                time.sleep(float(row_gaps[i]))
                client.submit(
                    f"{tag}-{i}", prompt, row_mnt,
                    prefix_len=p0
                    if mode in ("prefix", "disagg", "disagg_p2p")
                    else 0,
                )
            completed = 0
            total_new = 0
            for i in range(len(workload)):
                reply = client.result(
                    f"{tag}-{i}",
                    timeout=max(5.0, deadline - time.time()),
                )
                if reply.state == "done":
                    completed += 1
                    total_new += len(reply.tokens)
            dt = max(time.perf_counter() - t0, 1e-9)
            snap = gw.core.stats_snapshot()
            counters = snap["counters"]
            row = {
                "replicas": n_replicas,
                "completed": completed,
                "new_tokens": total_new,
                "tokens_per_sec": round(total_new / dt, 2),
                "ttft_ms_p50": gw.ttft_ms.percentile(0.50),
                "ttft_ms_p99": gw.ttft_ms.percentile(0.99),
                "latency_ms_p50": gw.latency_ms.percentile(0.50),
                "latency_ms_p99": gw.latency_ms.percentile(0.99),
                "elapsed_s": round(dt, 2),
                "rejected": counters["rejected"],
                "redispatched": counters["redispatched"],
                "duplicate_completions":
                    counters["duplicate_completions"],
            }
            if trace_sample is not None:
                row["trace"] = {
                    "sample": float(trace_sample),
                    "sampled": counters["trace_sampled"],
                    "unsampled": counters["trace_unsampled"],
                }
            if mode != "plain":
                row["mode"] = mode
                routed = (counters["prefix_hits"]
                          + counters["prefix_misses"]
                          + counters["prefix_steals"])
                row["prefix"] = {
                    "hits": counters["prefix_hits"],
                    "misses": counters["prefix_misses"],
                    "steals": counters["prefix_steals"],
                    "hit_rate": round(
                        counters["prefix_hits"] / routed, 3
                    ) if routed else 0.0,
                }
            if mode in ("disagg", "disagg_p2p"):
                fp32 = counters["kv_fp32_bytes"]
                # kv_bytes = relayed through the gateway; kv_p2p_bytes
                # = ticketed bytes granted for peer pulls.  A request
                # that failed its pull and fell back to relay appears
                # in BOTH (the bytes really moved twice); the clean
                # rows here have relay_fallbacks == 0.
                moved = (counters["kv_bytes"]
                         + counters["kv_p2p_bytes"])
                row["kv"] = {
                    "handoffs": counters["kv_handoffs"],
                    "rejects": counters["kv_rejects"],
                    # Bytes that transited the GATEWAY (the relay
                    # plane); the P2P row's acceptance criterion is
                    # this staying ~0 while p2p_bytes carries the
                    # segments peer-to-peer.
                    "bytes_shipped": counters["kv_bytes"],
                    "p2p_bytes": counters["kv_p2p_bytes"],
                    "relay_fallbacks":
                        counters["kv_relay_fallbacks"],
                    "fp32_segment_bytes": fp32,
                    "bytes_over_fp32": round(
                        moved / fp32, 3
                    ) if fp32 else 0.0,
                }
                row["pools"] = {
                    r: snap["pools"][r]["alive"]
                    for r in ("prefill", "decode")
                }
            return row
        finally:
            for runner in runners:
                gw.core.drain(runner.replica_id)
            for rid in list(
                gw.core.stats_snapshot()["replicas"]
            ):
                gw.core.drain(rid)
            for th in threads:
                th.join(timeout=30)
            for proc, log in procs:
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                log.close()
            gw.stop()
            shutil.rmtree(tmp, ignore_errors=True)

    def run_rows(dest: list, label: str = "") -> None:
        for n in replicas_rows:
            try:
                row = run_row(n, mode="plain")
            except Exception as e:  # noqa: BLE001 - record the row
                row = {"replicas": n,
                       "error": f"{type(e).__name__}: {str(e)[:200]}"}
            dest.append(row)
            flush()
            print(f"{label}replicas={n}: {row}", file=sys.stderr)

    if not tracing_only and not paged_only:
        run_rows(result["rows"])

    def _speedup(rows):
        ok = [r for r in rows if "error" not in r]
        by_n = {r["replicas"]: r for r in ok}
        if 1 not in by_n or len(by_n) < 2:
            return None, None
        best_n = max(n for n in by_n if n > 1)
        base = by_n[1]["tokens_per_sec"]
        if base <= 0:
            return None, None
        return round(by_n[best_n]["tokens_per_sec"] / base, 2), best_n

    if not smoke and not tracing_only and not paged_only \
            and opts["device_round_ms"] > 0:
        # Honesty rows: the same fleet with NO round floor — the raw
        # 1-core timeshared regime, where replica scaling measures
        # XLA-CPU contention rather than the control plane.
        result["raw_cpu_rows"] = []
        saved_floor = opts["device_round_ms"]
        opts["device_round_ms"] = 0.0
        run_rows(result["raw_cpu_rows"], label="raw ")
        opts["device_round_ms"] = saved_floor
        raw_speedup, _ = _speedup(result["raw_cpu_rows"])
        if raw_speedup is not None:
            result["raw_speedup_multi_vs_single"] = raw_speedup

    # Routing + disaggregation rows (ISSUE 8): one Zipf prefix
    # workload, three data planes, same arrival process.
    routing = {
        "replicas": opts["routing_replicas"],
        "requests": opts["routing_requests"],
        "max_new_tokens": opts["routing_mnt"],
        "poisson_rps": opts["routing_rps"],
        "model": {"layers": opts["routing_layers"],
                  "d_model": opts["routing_d_model"],
                  "d_ff": opts["routing_d_ff"],
                  "dtype": "float32"},
        "prefix_len": opts["prefix_len"],
        "templates": opts["prefix_templates"],
        "zipf_a": opts["zipf_a"],
        "prefix_cache_cap": opts["prefix_cache_cap"],
        "note": (
            "least_loaded withholds the prefix fingerprints (the "
            "PR-5 router); prefix routes them to warm replicas "
            "(residency map from poll reports, overload-steal guard); "
            "disagg splits the fleet into prefill/decode pools with "
            "the int8 KV segment shipped through the gateway; "
            "disagg_p2p ships only a ticket through the gateway and "
            "the decode replica pulls the segment directly from the "
            "prefill replica's segment server (ISSUE 9)"
        ),
        "rows": [],
    }
    if tracing_only or paged_only:
        routing = result.get("routing", routing)
    else:
        result["routing"] = routing
        for mode in ("least_loaded", "prefix", "disagg",
                     "disagg_p2p"):
            n = opts["routing_replicas"]
            if mode in ("disagg", "disagg_p2p"):
                n = max(2, n)  # at least one prefill + one decode
            try:
                row = run_row(n, mode=mode)
            except Exception as e:  # noqa: BLE001 - record the row
                row = {"mode": mode,
                       "error": f"{type(e).__name__}: {str(e)[:200]}"}
            routing["rows"].append(row)
            flush()
            print(f"routing mode={mode}: {row}", file=sys.stderr)
        by_mode = {
            r.get("mode"): r
            for r in routing["rows"] if "error" not in r
        }
        if "least_loaded" in by_mode and "prefix" in by_mode:
            ll, pf = by_mode["least_loaded"], by_mode["prefix"]
            routing["prefix_vs_least_loaded"] = {
                "tokens_per_sec_x": round(
                    pf["tokens_per_sec"] / ll["tokens_per_sec"], 2
                ) if ll["tokens_per_sec"] else 0.0,
                "ttft_p99_ms": {
                    "least_loaded": ll["ttft_ms_p99"],
                    "prefix": pf["ttft_ms_p99"],
                },
                "wins_tokens_per_sec":
                    pf["tokens_per_sec"] > ll["tokens_per_sec"],
                "wins_ttft_p99":
                    pf["ttft_ms_p99"] <= ll["ttft_ms_p99"],
            }

    # Tracing-overhead rows (ISSUE 12): the SAME prefix data plane and
    # load as the routing bench, measured with tracing off (sample 0)
    # vs FULL-SAMPLING on (sample 1.0, every request carrying spans
    # through gateway + replicas) — the committed evidence that the
    # flight recorder is cheap enough to leave on.
    tracing = {
        "replicas": opts["routing_replicas"],
        "requests": opts["routing_requests"],
        "max_new_tokens": opts["routing_mnt"],
        "poisson_rps": opts["routing_rps"],
        "note": (
            "prefix routing plane at the routing bench's load; off = "
            "trace_sample 0.0 (every request counted unsampled, no "
            "spans), on = trace_sample 1.0 (gateway phase spans + "
            "grant trace contexts + replica-side spans into the "
            "bounded ring; no dump directory, so the measured cost is "
            "the hot-path recording itself)"
        ),
        "rows": [],
    }
    if paged_only:
        tracing = result.get("tracing", tracing)
    else:
        result["tracing"] = tracing
        from dlrover_tpu.obs import get_recorder

        for sample in (0.0, 1.0):
            label = "on" if sample else "off"
            before = get_recorder().stats()
            try:
                row = run_row(opts["routing_replicas"], mode="prefix",
                              trace_sample=sample)
                after = get_recorder().stats()
                # Spans recorded in THIS (gateway-hosting) process;
                # the subprocess replicas' rings die with them by
                # design.
                row["trace"]["gw_spans"] = (
                    after["spans"] - before["spans"]
                )
                row["trace"]["ring_dropped"] = (
                    after["dropped"] - before["dropped"]
                )
            except Exception as e:  # noqa: BLE001 - record the row
                row = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            row["trace_mode"] = label
            tracing["rows"].append(row)
            flush()
            print(f"tracing {label}: {row}", file=sys.stderr)
    t_by = {
        r.get("trace_mode"): r
        for r in tracing["rows"] if "error" not in r
    }
    if {"off", "on"} <= set(t_by):
        off_r, on_r = t_by["off"], t_by["on"]
        tracing["overhead"] = {
            "tokens_per_sec": {
                "off": off_r["tokens_per_sec"],
                "on": on_r["tokens_per_sec"],
            },
            "tokens_per_sec_x": round(
                on_r["tokens_per_sec"] / off_r["tokens_per_sec"], 4
            ) if off_r["tokens_per_sec"] else 0.0,
            "ttft_p99_ms": {
                "off": off_r["ttft_ms_p99"],
                "on": on_r["ttft_ms_p99"],
            },
            # The acceptance bar: full-sampling tracing costs <= 3%
            # tokens/s at the routing bench's load.
            "within_3pct": (
                on_r["tokens_per_sec"]
                >= 0.97 * off_r["tokens_per_sec"]
            ),
        }
        flush()

    # Speculation rows (ISSUE 11): on/off at MATCHED chip budget, a
    # long-decode workload arriving at the speculation-off fleet's
    # analytic knee, SLO goodput per chip as the win condition, and a
    # fallback row proving a BAD draft (per-request adaptive k) never
    # degrades goodput below its matched-target plain baseline.
    spec_floor = opts["device_round_ms"]

    def _knee_rps(chips: int) -> float:
        """0.8 x a plain fleet's analytic service capacity at the
        device floor: chips x slots decode streams, each emitting one
        token per floor — each comparison pair runs at ITS baseline's
        knee (a supercritical baseline would amplify any service delta
        into unbounded queue growth and measure queueing theory, not
        the policy)."""
        if spec_floor <= 0:
            return 50.0
        return 0.8 * (chips * opts["slots"]) / (
            opts["spec_mnt"] * spec_floor / 1000.0
        )

    spec_slo_ms = opts["spec_slo_ms"] or (
        4.0 * opts["spec_mnt"] * max(spec_floor, 5.0)
    )

    def run_spec_row(mode: str) -> dict:
        """One speculation measurement.  ``off`` = spec_chips plain
        unified replicas; ``on`` = spec_chips-1 spec targets + 1
        ceiling-draft replica (same chip total); ``off_floor`` =
        spec_chips-1 plain replicas (what the fallback row must not
        undercut); ``fallback`` = spec_chips-1 spec targets + 1 BAD
        draft, adaptive k walking every stream back to plain."""
        import jax.numpy as jnp  # noqa: F401 (model dtype below)

        n_chips = opts["spec_chips"]
        targets = n_chips if mode == "off" else n_chips - 1
        has_draft = mode in ("on", "fallback")
        chips = targets + (1 if has_draft else 0)
        # Each comparison pair arrives at ITS baseline's knee: on/off
        # at the spec_chips plain fleet's, fallback/off_floor at the
        # (spec_chips-1)-target plain fleet's.
        rps = opts["spec_rps"] or _knee_rps(
            n_chips if mode in ("off", "on") else n_chips - 1
        )
        k = opts["spec_k"]
        mnt = opts["spec_mnt"]
        max_len = 16 + mnt + k + 8
        draft_floor_ms = spec_floor * k * opts["spec_draft_ratio"]
        tmp = tempfile.mkdtemp(prefix="serve_bench_spec_")
        gw = Gateway(
            port=0,
            config=GatewayConfig(queue_cap=512,
                                 spec_decode_min_tokens=8),
            histogram_buckets=(
                10, 25, 50, 100, 200, 350, 500, 700, 900, 1100,
                1350, 1600, 2000, 2400, 2900, 3500, 4200, 5000,
                6000, 7500, 10000, 15000, 30000,
            ),
        )
        gw.start()
        procs = []
        threads = []
        runners = []
        draft_runner = None
        dseed = opts["seed"] if mode == "on" else 9
        dlayers = 2 if mode == "on" else 1
        try:
            if smoke:
                sys.path.insert(0, os.path.join(repo, "examples"))
                import llama_serve_fleet as fleet_mod

                from dlrover_tpu.serving import (
                    DraftReplicaRunner,
                    DraftWorker,
                    RemoteDraftClient,
                )
                from dlrover_tpu.serving.draft import handle_draft

                draft_connect = None
                if has_draft:
                    import jax.numpy as jnp

                    dparams, dcfg = serve_common.tiny_llama(
                        seed=dseed, dtype=jnp.float32,
                        n_layer=dlayers, d_model=64, d_ff=128,
                    )
                    worker = DraftWorker(
                        dparams, dcfg, max_len=max_len, draft_k=k,
                        worker_id="d0",
                    )

                    class _LoopDraftServer:
                        def __init__(self, w):
                            self.worker = w
                            self.addr = "loop:d0"

                        def stop(self):
                            pass

                    draft_runner = DraftReplicaRunner(
                        _LoopDraftServer(worker),
                        LoopbackTransport(gw.handle), "d0",
                        poll_interval=0.02,
                    )
                    th = threading.Thread(target=draft_runner.run,
                                          daemon=True)
                    th.start()
                    threads.append(th)

                    def draft_connect(_addr, _w=worker):
                        return RemoteDraftClient(LoopbackTransport(
                            lambda m: handle_draft(_w, m)
                        ))
                for i in range(targets):
                    fleet_args = argparse.Namespace(
                        slots=opts["slots"], max_len=max_len,
                        journal_dir=os.path.join(tmp, "j"),
                        replica_id=f"r{i}", seed=opts["seed"],
                        poll_interval=0.005, round_floor_ms=0.0,
                        replica_role="unified", quant_kv=False,
                        prefix_cache_cap=4, warm_prefix_len=0,
                        n_layer=2, d_model=64, d_ff=128,
                        spec=has_draft, draft_k=k,
                        spec_break_even=0.0,
                    )
                    runner = fleet_mod.build_replica(
                        fleet_args, LoopbackTransport(gw.handle),
                        draft_connect=draft_connect,
                    )
                    runners.append(runner)
                    th = threading.Thread(target=runner.run,
                                          daemon=True)
                    th.start()
                    threads.append(th)
            else:
                env = dict(os.environ, JAX_PLATFORMS="cpu",
                           PYTHONPATH=repo)
                env.pop("DLROVER_TPU_FAULTS", None)
                for i in range(targets):
                    log = open(os.path.join(tmp, f"r{i}.log"), "w")
                    cmd = [
                        sys.executable,
                        os.path.join(repo, "examples",
                                     "llama_serve_fleet.py"),
                        "--role", "replica",
                        "--gateway", f"127.0.0.1:{gw.port}",
                        "--replica_id", f"r{i}",
                        "--slots", str(opts["slots"]),
                        "--max_len", str(max_len),
                        "--journal_dir", os.path.join(tmp, "j"),
                        "--seed", str(opts["seed"]),
                        "--poll_interval", "0.01",
                        "--n_layer", "2", "--d_model", "64",
                        "--d_ff", "128",
                        "--round_floor_ms", str(spec_floor),
                        "--draft_k", str(k),
                    ]
                    if has_draft:
                        cmd.append("--spec")
                    procs.append((subprocess.Popen(
                        cmd, cwd=repo, env=env, stdout=log,
                        stderr=subprocess.STDOUT,
                    ), log))
                if has_draft:
                    log = open(os.path.join(tmp, "d0.log"), "w")
                    cmd = [
                        sys.executable,
                        os.path.join(repo, "examples",
                                     "llama_serve_fleet.py"),
                        "--role", "draft",
                        "--gateway", f"127.0.0.1:{gw.port}",
                        "--replica_id", "d0",
                        "--max_len", str(max_len),
                        "--seed", str(opts["seed"]),
                        "--draft_k", str(k),
                        "--draft_seed",
                        "-1" if mode == "on" else str(dseed),
                        "--draft_layers", str(dlayers),
                        "--n_layer", "2", "--d_model", "64",
                        "--d_ff", "128",
                        "--draft_floor_ms", str(draft_floor_ms),
                    ]
                    procs.append((subprocess.Popen(
                        cmd, cwd=repo, env=env, stdout=log,
                        stderr=subprocess.STDOUT,
                    ), log))
            want = targets + (1 if has_draft else 0)
            deadline = time.time() + opts["timeout"]
            while time.time() < deadline:
                if gw.core.stats_snapshot()["replicas_alive"] >= want:
                    break
                time.sleep(0.2)
            else:
                raise TimeoutError(
                    f"{want} replicas never registered ({mode})"
                )
            client = ServeClient(LoopbackTransport(gw.handle),
                                 poll_interval=0.01)
            prompts_spec, _ = serve_common.seeded_requests(
                cfg, opts["spec_requests"], opts["seed"] + 31
            )
            gaps = np.random.RandomState(
                opts["seed"] + 13
            ).exponential(1.0 / max(rps, 1e-6),
                          size=len(prompts_spec))
            tag = f"sp-{mode}"
            t_submit: dict = {}
            t0 = time.perf_counter()
            for i, p in enumerate(prompts_spec):
                time.sleep(float(gaps[i]))
                rid = f"{tag}-{i}"
                client.submit(rid, p, mnt)
                t_submit[rid] = time.perf_counter()
            # Rotation poll: per-request completion timestamps (the
            # SLO conformity check is per request, not a percentile).
            lat: dict = {}
            toks: dict = {}
            outstanding = set(t_submit)
            poll_deadline = time.time() + opts["timeout"]
            while outstanding and time.time() < poll_deadline:
                for rid in list(outstanding):
                    rep = client.status(rid)
                    if rep.state in ("done", "failed", "timeout"):
                        lat[rid] = (
                            time.perf_counter() - t_submit[rid]
                        ) * 1000.0
                        toks[rid] = (
                            len(rep.tokens)
                            if rep.state == "done" else 0
                        )
                        outstanding.discard(rid)
                time.sleep(0.02)
            wall = max(time.perf_counter() - t0, 1e-9)
            snap = gw.core.stats_snapshot()
            counters = snap["counters"]
            good = sum(
                toks[r] for r in toks if lat[r] <= spec_slo_ms
            )
            total = sum(toks.values())
            return {
                "mode": mode,
                "chips": chips,
                "targets": targets,
                "drafts": 1 if has_draft else 0,
                "poisson_rps": round(rps, 2),
                "requests": len(prompts_spec),
                "completed": sum(1 for r in toks if toks[r] > 0),
                "new_tokens": total,
                "tokens_per_sec": round(total / wall, 2),
                "slo_ms": spec_slo_ms,
                "slo_completed": sum(
                    1 for r in toks
                    if toks[r] > 0 and lat[r] <= spec_slo_ms
                ),
                "goodput_tokens_per_sec": round(good / wall, 2),
                "goodput_per_chip": round(good / wall / chips, 2),
                "ttft_ms_p50": gw.ttft_ms.percentile(0.50),
                "ttft_ms_p99": gw.ttft_ms.percentile(0.99),
                "latency_ms_p50": gw.latency_ms.percentile(0.50),
                "latency_ms_p99": gw.latency_ms.percentile(0.99),
                "elapsed_s": round(wall, 2),
                "spec": {
                    "rounds": counters["spec_rounds"],
                    "accepted": counters["spec_accepted"],
                    "fallbacks": counters["spec_fallbacks"],
                    "grants": counters["spec_grants"],
                    "bypass": counters["spec_bypass"],
                    # Mean accepted-tokens-per-round the spec targets
                    # reported (0 for the plain rows) — the adaptive-k
                    # convergence evidence.
                    "tokens_per_round":
                        snap["pools"]["draft"]["tokens_per_round"],
                },
            }
        finally:
            if draft_runner is not None:
                draft_runner.stop()
            for runner in runners:
                gw.core.drain(runner.replica_id)
            for rid in list(gw.core.stats_snapshot()["replicas"]):
                gw.core.drain(rid)
            for th in threads:
                th.join(timeout=30)
            for proc, log in procs:
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                log.close()
            gw.stop()
            shutil.rmtree(tmp, ignore_errors=True)

    spec_sec = {
        "chips": opts["spec_chips"],
        "requests": opts["spec_requests"],
        "max_new_tokens": opts["spec_mnt"],
        "draft_k": opts["spec_k"],
        "poisson_rps": {
            "on_off": round(
                opts["spec_rps"] or _knee_rps(opts["spec_chips"]), 2
            ),
            "fallback_pair": round(
                opts["spec_rps"]
                or _knee_rps(opts["spec_chips"] - 1), 2
            ),
        },
        "slo_ms": spec_slo_ms,
        "draft_floor_ratio": opts["spec_draft_ratio"],
        "note": (
            "matched chip budget: `on` trades one target chip for a "
            "draft replica (spec targets verify k proposals per round "
            "over the draft's RPC proposals, per-request adaptive k); "
            "the ceiling draft shares the target weights (stands in "
            "for a trained draft — SPEC_DECODE_CPU.json bounds the "
            "realistic acceptance range, break-even ~3.35 tok/round); "
            "`fallback` pairs the same targets with a BAD draft and "
            "must hold the `off_floor` (matched-target plain) "
            "goodput — adaptive k walks every stream back to plain "
            "decode.  Each comparison pair arrives at ITS baseline's "
            "analytic knee (0.8 x chips x slots/(mnt x round_floor): "
            "a supercritical baseline would amplify any service delta "
            "into queue growth and measure queueing theory, not the "
            "policy); the device_round_ms floor models the "
            "accelerator-bound regime (the PR-5 note), with the "
            "draft chip charged k x draft_floor_ratio of a target "
            "round per batched roll (width-scaled: a k=1 probe costs "
            "one draft step)"
        ),
        "rows": [],
    }
    if tracing_only or paged_only:
        spec_sec = result.get("spec", spec_sec)
    else:
        result["spec"] = spec_sec
        for mode in ("off", "on", "off_floor", "fallback"):
            try:
                row = run_spec_row(mode)
            except Exception as e:  # noqa: BLE001 - record the row
                row = {"mode": mode,
                       "error": f"{type(e).__name__}: {str(e)[:200]}"}
            spec_sec["rows"].append(row)
            flush()
            print(f"spec mode={mode}: {row}", file=sys.stderr)
    spec_by = {
        r.get("mode"): r for r in spec_sec["rows"] if "error" not in r
    }
    if not tracing_only and             {"off", "on", "off_floor", "fallback"} <= set(spec_by):
        on, off = spec_by["on"], spec_by["off"]
        fb, off_f = spec_by["fallback"], spec_by["off_floor"]
        spec_sec["verdict"] = {
            "matched_chips": on["chips"] == off["chips"],
            "goodput_per_chip_x": round(
                on["goodput_per_chip"] / off["goodput_per_chip"], 2
            ) if off["goodput_per_chip"] else 0.0,
            "on_beats_off": (
                on["goodput_per_chip"] > off["goodput_per_chip"]
            ),
            "tokens_per_round_on": on["spec"]["tokens_per_round"],
            "fallback_vs_off_floor_x": round(
                fb["goodput_tokens_per_sec"]
                / off_f["goodput_tokens_per_sec"], 2
            ) if off_f["goodput_tokens_per_sec"] else 0.0,
            # The adaptive-k guarantee: a bad draft never degrades
            # goodput below the matched-target speculation-off
            # baseline (small tolerance for measurement noise).
            "fallback_holds_baseline": (
                fb["goodput_tokens_per_sec"]
                >= 0.9 * off_f["goodput_tokens_per_sec"]
            ),
            "fallback_fallbacks": fb["spec"]["fallbacks"],
        }

    # ------------------------------------------------------------------
    # Paged-KV rows (ISSUE 19): block-table memory vs slotted
    # reservations at MATCHED KV memory.
    # ------------------------------------------------------------------
    def paged_workload(workload: str):
        """Prompt set shared by both modes of one comparison (same
        seed -> same prompts -> greedy outputs must match byte-for-
        byte across modes)."""
        rng = np.random.RandomState(opts["seed"] + 23)
        n = opts["paged_requests"]
        p_max = opts["paged_max_len"] - opts["paged_mnt"]
        if workload == "uniform":
            lens = rng.randint(
                max(1, int(p_max * 0.55)), int(p_max * 0.9) + 1,
                size=n,
            )
        else:  # longtail: Zipf sequence lengths, most short, few long
            step = max(1, p_max // 8)
            lens = np.minimum(step + step * rng.zipf(1.6, size=n),
                              p_max)
        return [
            rng.randint(1, cfg.vocab_size, size=(int(L),)).astype(
                np.int32
            )
            for L in lens
        ]

    paged_params = None

    def run_paged_row(workload: str, mode: str, prompts_w) -> dict:
        """One in-process DecodeServer measurement.  Occupancy is
        sampled once per decode round from the serve loop's tick:
        tokens RESIDENT for admitted requests (prompt + emitted so
        far) over the matched memory budget — the fraction of the KV
        budget holding live work rather than stranded reservation
        padding."""
        nonlocal paged_params
        from dlrover_tpu.models import llama, llama_infer

        if paged_params is None:
            paged_params = llama.init_params(
                jax.random.PRNGKey(opts["seed"]), cfg
            )
        mnt = opts["paged_mnt"]
        S = opts["paged_slots"]
        BS = opts["paged_block_size"]
        ML = opts["paged_max_len"]
        pool_blocks = S * (ML // BS)
        pool_tokens = S * ML
        paged = mode == "paged"
        seats = S * opts["paged_seat_factor"] if paged else S
        kw = dict(paged=True, block_size=BS,
                  pool_blocks=pool_blocks) if paged else {}
        srv = llama_infer.DecodeServer(
            paged_params, cfg, slots=seats, max_len=ML, **kw
        )
        # Warm every prefill bucket this workload touches (plus the
        # decode-step jit) so the timed run measures serving, not XLA.
        reps: dict = {}
        for p in prompts_w:
            b = next(b for b in srv.buckets if len(p) <= b)
            if b not in reps or len(p) > len(reps[b]):
                reps[b] = p
        srv.serve(list(reps.values()), max_new_tokens=2)
        plen = {i: len(p) for i, p in enumerate(prompts_w)}
        emitted: dict = {}
        outs: dict = {}

        def on_token(rid, _t):
            emitted[rid] = emitted.get(rid, 0) + 1

        def on_finish(rid, tokens):
            outs[rid] = [int(t) for t in tokens]

        samples: list = []
        deadline = time.time() + opts["timeout"]

        def tick():
            if time.time() > deadline:
                raise TimeoutError(
                    f"paged row {workload}/{mode} overran "
                    f"{opts['timeout']}s"
                )
            act = srv._live_active
            sreq = srv._live_slot_req
            resident = adm = 0
            for s in range(len(sreq)):
                if act[s] and sreq[s] is not None:
                    adm += 1
                    resident += (plen[sreq[s]]
                                 + emitted.get(sreq[s], 0))
            if adm:
                samples.append((
                    resident / pool_tokens, adm,
                    float(srv.last_stats.get("occupancy", 0.0)),
                ))
            return False  # drain mode: finish everything, then return

        for i, p in enumerate(prompts_w):
            srv.submit(i, p, mnt)
        t0 = time.perf_counter()
        srv.serve_incremental(tick=tick, on_finish=on_finish,
                              on_token=on_token)
        wall = max(time.perf_counter() - t0, 1e-9)
        new = sum(len(outs[r]) - plen[r] for r in outs)
        occ = [s[0] for s in samples] or [0.0]
        adm = [s[1] for s in samples] or [0]
        rep = [s[2] for s in samples] or [0.0]
        row = {
            "workload": workload,
            "mode": mode,
            "requests": len(prompts_w),
            "completed": len(outs),
            "seats": seats,
            "kv_pool_tokens": pool_tokens,
            "new_tokens": new,
            "tokens_per_sec": round(new / wall, 2),
            "decode_rounds": len(samples),
            "admitted_batch_mean": round(float(np.mean(adm)), 2),
            "admitted_batch_occupancy": round(float(np.mean(occ)), 4),
            "reported_occupancy_mean": round(float(np.mean(rep)), 4),
            "elapsed_s": round(wall, 2),
            "outputs": outs,
        }
        if paged:
            row["block_size"] = BS
            row["pool_blocks"] = pool_blocks
            row["preemptions"] = srv.preemptions
        return row

    paged_sec = {
        "requests": opts["paged_requests"],
        "max_new_tokens": opts["paged_mnt"],
        "block_size": opts["paged_block_size"],
        "max_len": opts["paged_max_len"],
        "kv_pool_tokens": opts["paged_slots"] * opts["paged_max_len"],
        "note": (
            "matched KV memory: `slotted` reserves paged_slots full "
            "max_len rows; `paged` gets a block pool of the same "
            "token count (+1 scratch block) with paged_seat_factor x "
            "more seats, admission priced by blocks actually needed "
            "and grown on demand (preempt-youngest when dry).  "
            "admitted_batch_occupancy = mean fraction of the memory "
            "budget holding live request tokens per decode round; "
            "greedy outputs must be byte-identical across modes "
            "(outputs_match).  tokens_per_sec on this CPU host "
            "timeshares seat-width decode compute, so the committed "
            "claim is the occupancy/admission gap, not CPU tok/s"
        ),
        "rows": [],
    }
    if tracing_only:
        paged_sec = result.get("paged", paged_sec)
    else:
        result["paged"] = paged_sec
        for workload in ("uniform", "longtail"):
            prompts_w = paged_workload(workload)
            for mode in ("slotted", "paged"):
                try:
                    row = run_paged_row(workload, mode, prompts_w)
                except Exception as e:  # noqa: BLE001 - record the row
                    row = {"workload": workload, "mode": mode,
                           "error":
                           f"{type(e).__name__}: {str(e)[:200]}"}
                paged_sec["rows"].append(row)
                print(
                    f"paged {workload}/{mode}: "
                    + json.dumps({k: v for k, v in row.items()
                                  if k != "outputs"}),
                    file=sys.stderr,
                )
        pg_by = {
            (r.get("workload"), r.get("mode")): r
            for r in paged_sec["rows"] if "error" not in r
        }
        if len(pg_by) == 4:
            verdict = {}
            for workload in ("uniform", "longtail"):
                sl = pg_by[(workload, "slotted")]
                pg = pg_by[(workload, "paged")]
                verdict[workload] = {
                    "occupancy_x": round(
                        pg["admitted_batch_occupancy"]
                        / sl["admitted_batch_occupancy"], 2
                    ) if sl["admitted_batch_occupancy"] else 0.0,
                    "admitted_x": round(
                        pg["admitted_batch_mean"]
                        / sl["admitted_batch_mean"], 2
                    ) if sl["admitted_batch_mean"] else 0.0,
                    # The parity pin, measured end to end: greedy
                    # outputs byte-identical across the memory layouts.
                    "outputs_match": sl["outputs"] == pg["outputs"],
                }
            # Paged may tie slotted when every request fills its
            # reservation anyway (the uniform smoke config); it must
            # never be LOWER, and the long-tail row — where slotted
            # strands max_len reservations behind short requests — is
            # where the strict win is required.
            verdict["paged_never_lower"] = all(
                pg_by[(w, "paged")]["admitted_batch_occupancy"]
                >= pg_by[(w, "slotted")]["admitted_batch_occupancy"]
                - 1e-9
                for w in ("uniform", "longtail")
            )
            verdict["longtail_paged_higher"] = (
                pg_by[("longtail", "paged")]
                ["admitted_batch_occupancy"]
                > pg_by[("longtail", "slotted")]
                ["admitted_batch_occupancy"]
            )
            verdict["longtail_gap_largest"] = (
                verdict["longtail"]["occupancy_x"]
                >= verdict["uniform"]["occupancy_x"]
            )
            paged_sec["verdict"] = verdict
        # The raw token streams verified outputs_match; they have no
        # further value in the committed artifact.
        for r in paged_sec["rows"]:
            r.pop("outputs", None)
        flush()

    speedup, best_n = _speedup(result["rows"])
    if speedup is not None:
        result["speedup_multi_vs_single"] = speedup
        result["speedup_replicas"] = best_n
    else:
        speedup = 0.0
    main_ok = [r for r in result["rows"] if "error" not in r]
    routing_ok = [r for r in routing["rows"] if "error" not in r]
    spec_ok = [r for r in spec_sec["rows"] if "error" not in r]
    paged_ok = [r for r in paged_sec["rows"] if "error" not in r]
    tracing_ok = [r for r in tracing["rows"] if "error" not in r]
    result["complete"] = (
        (tracing_only or (
            len(main_ok) == len(replicas_rows)
            and all(r["completed"] == opts["requests"]
                    for r in main_ok)
        ))
        and len(routing_ok) == 4
        and all(r["completed"] == opts["routing_requests"]
                for r in routing_ok)
        and len(spec_ok) == 4
        and all(r["completed"] == opts["spec_requests"]
                for r in spec_ok)
        and len(paged_ok) == 4
        and all(r["completed"] == opts["paged_requests"]
                for r in paged_ok)
        and all(v["outputs_match"]
                for v in (paged_sec.get("verdict") or {}).values()
                if isinstance(v, dict))
        and bool((paged_sec.get("verdict") or {})
                 .get("paged_never_lower"))
        and bool((paged_sec.get("verdict") or {})
                 .get("longtail_paged_higher"))
        and len(tracing_ok) == 2
        and all(r["completed"] == opts["routing_requests"]
                for r in tracing_ok)
    )
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "serve_fleet_speedup",
        "value": speedup,
        "unit": "x_tokens_per_sec_vs_single_replica",
        "vs_baseline": speedup,
        "backend": backend,
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


def reshard_bench_main(argv: list) -> int:
    """Live-reshard bench (ISSUE 6 acceptance artifact): downtime to the
    first RESUMED step across a 2->4->2 device resize, live mesh-to-mesh
    resharding vs the checkpoint-restart path, one process over forced
    host CPU devices.

    Per transition the two paths measure:

    - **live**: quiesce -> plan -> move host bytes -> rebuild on the new
      mesh -> first train step done (``reshard.coordinator``);
    - **restart**: synchronous ``save_to_storage`` + commit (the scale
      event must not lose steps) -> ``engine.load(target_mesh=new)``
      restore -> first step done.  Process teardown + relaunch + XLA
      init are NOT charged to the restart path (they'd add seconds more)
      — the comparison is conservative in its favor.

    Both paths run with warm jit caches (each mesh's step is compiled
    before timing starts; the one-off compile cost, identical for both
    paths, is reported as ``jit_compile_s`` context) so the delta is the
    data plane, not XLA.  Flushes the artifact after every row.

    Flags: ``--state_mb=N`` (64) ``--tensors=N`` (8) ``--out=PATH``
    ``--smoke`` (tiny config for the tier-1 gate).
    """
    import os
    import shutil
    import subprocess
    import tempfile

    t_start = time.perf_counter()
    opts = {"state_mb": 64, "tensors": 8}
    out_path = None
    for a in argv:
        if a == "--smoke":
            opts.update(state_mb=4, tensors=4)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = int(v)

    # This bench needs >=4 virtual host devices and the cpu platform (it
    # measures the control/data plane, not a device).  Force the flag
    # before jax loads — REPLACING any ambient lower count (an inherited
    # `...device_count=2` must not starve the 4-way mesh); if jax is
    # already up without enough devices, re-exec in a clean subprocess
    # (whose env now carries the corrected flag).
    import re as _re

    flags = os.environ.get("XLA_FLAGS", "")
    flag_re = r"--xla_force_host_platform_device_count=\d+"
    m = _re.search(flag_re, flags)
    if m is None:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    elif int(m.group().rsplit("=", 1)[1]) < 4:
        flags = _re.sub(
            flag_re, "--xla_force_host_platform_device_count=8", flags
        )
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax as _jax

        if len(_jax.devices()) < 4:
            return subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--reshard_bench", *argv],
                env=dict(os.environ),
            ).returncode

    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh
    from dlrover_tpu.reshard.coordinator import reshard_state

    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "RESHARD_BENCH_CPU.json",
        )
    devs = jax.devices()
    mb = 1 << 20
    per = max(1, opts["state_mb"] * mb // opts["tensors"] // 4)
    # fsdp-shardable leading dim on every mesh size used below.
    per = -(-per // 32) * 32

    def make_mesh(n):
        return build_mesh(MeshSpec(fsdp=n), devs[:n])

    def put_state(mesh):
        return {
            f"w{i}": jax.device_put(
                (np.arange(per, dtype=np.float32) * 0.001 + i).reshape(
                    -1, 4
                ),
                NamedSharding(mesh, P("fsdp")),
            )
            for i in range(opts["tensors"])
        }

    @jax.jit
    def step_fn(state):
        return {k: v * 1.0001 for k, v in state.items()}

    result = {
        "bench": "reshard_live_resize",
        "backend": jax.default_backend(),
        "devices": len(devs),
        "state_mb": round(
            per * 4 * opts["tensors"] / mb, 1
        ),
        "tensors": opts["tensors"],
        "transitions": ["2->4", "4->2"],
        "note": (
            "downtime = resize start -> first resumed train step done, "
            "warm jit caches both paths; restart path charged save+"
            "commit+restore+step only (teardown/relaunch/XLA-init "
            "excluded, and its restore rides the flash-ckpt shm warm "
            "path — the restart ladder's best case) — conservative in "
            "its favor"
        ),
        "rows": [],
    }

    def flush():
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    tmp = tempfile.mkdtemp(prefix="reshard_bench_")
    flush()
    try:
        meshes = {2: make_mesh(2), 4: make_mesh(4)}
        # Warm both meshes' compiled steps (identical one-off cost for
        # both paths; excluded from the downtime rows below).
        t0 = time.perf_counter()
        for n, mesh in meshes.items():
            jax.block_until_ready(step_fn(put_state(mesh)))
        result["jit_compile_s"] = round(time.perf_counter() - t0, 3)
        flush()

        transitions = [(2, 4), (4, 2)]

        # -- live path -----------------------------------------------------
        state = put_state(meshes[2])
        jax.block_until_ready(state)
        for n_from, n_to in transitions:
            t0 = time.perf_counter()
            state, outcome = reshard_state(state, meshes[n_to])
            state = step_fn(state)
            jax.block_until_ready(state)
            downtime = time.perf_counter() - t0
            result["rows"].append(
                {
                    "resize": f"{n_from}->{n_to}",
                    "path": "live",
                    "downtime_s": round(downtime, 4),
                    "moved_mb": round(outcome.moved_mb, 2),
                    "segments": outcome.segments,
                }
            )
            flush()

        # -- restart path --------------------------------------------------
        state = put_state(meshes[2])
        jax.block_until_ready(state)
        step_counter = 10
        for n_from, n_to in transitions:
            eng = CheckpointEngine(
                os.path.join(tmp, f"ckpt_{n_from}to{n_to}"),
                job_name=f"rsbench{os.getpid()}_{n_from}{n_to}",
            )
            t0 = time.perf_counter()
            eng.save_to_storage(step_counter, state)
            if not eng.wait(timeout=300):
                raise RuntimeError("restart-path save never committed")
            save_s = time.perf_counter() - t0
            target = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=v.sharding
                )
                for k, v in state.items()
            }
            del state  # the old world is gone; restore must re-read
            t1 = time.perf_counter()
            got = eng.load(target, target_mesh=meshes[n_to])
            if got is None:
                raise RuntimeError("restart-path restore found nothing")
            state, _meta = got
            state = step_fn(state)
            jax.block_until_ready(state)
            downtime = time.perf_counter() - t0
            result["rows"].append(
                {
                    "resize": f"{n_from}->{n_to}",
                    "path": "restart",
                    "downtime_s": round(downtime, 4),
                    "save_commit_s": round(save_s, 4),
                    "restore_step_s": round(
                        time.perf_counter() - t1, 4
                    ),
                }
            )
            flush()
            eng.close()
            step_counter += 10

        # -- verdict -------------------------------------------------------
        live = {
            r["resize"]: r["downtime_s"]
            for r in result["rows"] if r["path"] == "live"
        }
        restart = {
            r["resize"]: r["downtime_s"]
            for r in result["rows"] if r["path"] == "restart"
        }
        per_transition = {
            k: round(restart[k] / max(live[k], 1e-9), 2)
            for k in live if k in restart
        }
        result["speedup_restart_over_live"] = per_transition
        total_live = sum(live.values())
        total_restart = sum(restart.values())
        speedup = total_restart / max(total_live, 1e-9)
        result["speedup_total"] = round(speedup, 2)
        result["live_strictly_faster"] = all(
            live[k] < restart[k] for k in live if k in restart
        )
        result["complete"] = (
            len(live) == len(transitions)
            and len(restart) == len(transitions)
        )
        result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
        flush()
        print(json.dumps({
            "metric": "reshard_live_vs_restart_downtime",
            "value": round(speedup, 2),
            "unit": "x_restart_downtime_over_live",
            "vs_baseline": round(speedup, 2),
            "backend": result["backend"],
            "artifact": out_path,
        }))
        return 0 if result["complete"] and result[
            "live_strictly_faster"
        ] else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def zipf_cell_trace(rate: float, duration: float, n_cells: int,
                    zipf_a: float, seed: int):
    """Zipf-over-CELLS hot-cell traffic (ISSUE 17): one global Poisson
    arrival stream where each request's HOME CELL is drawn from a
    Zipf(``zipf_a``) distribution over cells — cell 0 is the hot
    region, the tail cells sit on headroom.  Seeded and fully
    deterministic (`np.random.RandomState`), so the spillover and
    static-partitioning rows of the global bench replay the IDENTICAL
    trace.  Returns ``(arrival_times, home_cells)`` parallel lists."""
    import numpy as np

    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9),
                           size=int(rate * duration * 3) + 16)
    times = np.cumsum(gaps)
    times = times[times < duration]
    w = 1.0 / np.arange(1, max(n_cells, 1) + 1) ** float(zipf_a)
    homes = rng.choice(max(n_cells, 1), size=len(times), p=w / w.sum())
    return times.tolist(), [int(c) for c in homes]


class _StubDecodeServer:
    """Decode stand-in with the incremental admission surface.  The
    load bench measures the FRONT DOOR, so its decode is instant
    (``service_s=0``: slots are wide, tokens are free); the global
    bench models a finite decode capacity instead — ``service_s`` is
    charged per finished request, so a cell's replicas saturate at
    ``replicas / service_s`` rps and admission pressure (the spillover
    trigger) is real."""

    def __init__(self, slots, mnt, service_s=0.0):
        import threading

        self.slots = slots
        self.mnt = mnt
        self.service_s = service_s
        self._pending = []
        self._mu = threading.Lock()

    def submit(self, rid, prompt, mnt, **_kw):
        with self._mu:
            self._pending.append((rid, list(prompt), int(mnt)))

    def cancel(self, rid):
        with self._mu:
            for i, item in enumerate(self._pending):
                if item[0] == rid:
                    del self._pending[i]
                    return True
        return False

    def pending_count(self):
        with self._mu:
            return len(self._pending)

    def pending_rids(self):
        with self._mu:
            return [r for r, _, _ in self._pending]

    def active_rids(self):
        return []

    def free_slots(self):
        with self._mu:
            return max(0, self.slots - len(self._pending))

    def serve_incremental(self, tick=None, on_finish=None,
                          on_token=None):
        while True:
            keep = tick() is not False if tick else True
            with self._mu:
                batch, self._pending = self._pending, []
            for rid, prompt, mnt in batch:
                if self.service_s:
                    time.sleep(self.service_s)
                out = list(prompt)
                for i in range(mnt):
                    tok = (len(prompt) + i) % 97
                    out.append(tok)
                    if on_token:
                        on_token(rid, tok)
                if on_finish:
                    on_finish(rid, out)
            if not keep and not batch:
                return {}
            if not batch:
                time.sleep(0.0005)


class _PacedPipeline:
    """One gateway's modeled event loop: serialized handling with a
    per-message service-time floor; real handler CPU is charged
    against the budget.  ``cast`` is the open-loop client edge (a
    full queue DROPS, like a saturated listen backlog); ``call`` is
    the blocking replica/ops edge."""

    _DONE = object()

    def __init__(self, handle, floor, cap):
        import queue
        import threading

        self._handle = handle
        self._floor = floor
        self.q = queue.Queue(maxsize=cap)
        self.wire_dropped = 0
        self.handled = 0
        self.errors = 0
        self.busy_s = 0.0
        self._thread = threading.Thread(
            target=self._run, daemon=True
        )
        self._thread.start()

    def cast(self, data: bytes) -> None:
        import queue

        try:
            self.q.put_nowait((data, None))
        except queue.Full:
            self.wire_dropped += 1

    def call(self, msg, **_kw):
        import threading

        from dlrover_tpu.common import messages as wire

        slot = [None, threading.Event()]
        self.q.put((wire.serialize(msg), slot))
        slot[1].wait(timeout=30.0)
        data = slot[0]
        return wire.deserialize(data) if data is not None else None

    def _run(self):
        from dlrover_tpu.common import messages as wire

        while True:
            item = self.q.get()
            if item is self._DONE:
                return
            data, slot = item
            t0 = time.perf_counter()
            out = None
            try:
                reply = self._handle(wire.deserialize(data))
                if reply is not None:
                    out = wire.serialize(reply)
            except Exception as e:  # noqa: BLE001 - pipe survives
                self.errors += 1
                print(f"pipeline handler error: {e!r}",
                      file=sys.stderr)
            dt = time.perf_counter() - t0
            self.busy_s += dt
            self.handled += 1
            if slot is not None:
                slot[0] = out
                slot[1].set()
            if dt < self._floor:
                time.sleep(self._floor - dt)

    def stop(self):
        self.q.put(self._DONE)
        self._thread.join(timeout=10.0)


def load_bench_main(argv: list) -> int:
    """Open-loop load harness for the serving front door (ISSUE 9
    acceptance artifact): Poisson / bursty / diurnal / Zipf-over-cells
    arrival traces at
    thousands of requests per second against a SHARDED GATEWAY TIER,
    with SLO-attainment reporting and a profile of the admission hot
    loop.

    Everything is jax-free and in-process; what makes the measurement
    honest on a 1-core CI host is the PACED PIPELINE (the
    ``--link_mbps`` pattern from the scale-out checkpoint bench): each
    gateway's message handling — deserialize + GatewayCore dispatch +
    serialize, the real admission loop — flows through one worker
    thread that charges every message ``max(real_cpu,
    gw_service_us)``.  The floor models the per-gateway core + wire
    budget a real deployment gives each gateway process; the REAL
    python cost is charged against it, so if the admission loop (or
    msgpack) is slower than the floor, that is what saturates.  N
    gateways = N independent pipelines, so the tier's capacity scales
    the way N processes on N cores would, while the driver, ring
    routing, replicas, and every message still run the real code.

    Requests are consistent-hashed by id to their owning gateway
    (``HashRing``); replicas poll every gateway through the real
    ``TierReplicaLink`` fan-out; arrivals are OPEN-LOOP — the driver
    submits on the trace's schedule whether or not earlier requests
    completed, and a full pipeline queue drops (counted) like a
    saturated listen backlog.  ``goodput`` counts completions within
    ``--slo_ms``.

    Flags: ``--gateways=1,2`` (rows) ``--rates=csv`` (arrivals/s;
    default sweeps around the modeled knee) ``--gw_service_us=F``
    (400) ``--replicas=N`` (4) ``--slots=N`` (64) ``--duration_s=F``
    (3) ``--slo_ms=F`` (1000) ``--deadline_s=F`` (2) ``--seed=N``
    ``--out=PATH`` (default: merge into SERVE_BENCH_CPU.json under
    the ``load`` key) ``--smoke`` (sub-5s tier-1 gate).
    """
    import os
    import queue
    import threading

    import numpy as np

    from dlrover_tpu.agent.metrics import Histogram
    from dlrover_tpu.common import messages as wire
    from dlrover_tpu.serving import (
        Gateway,
        GatewayConfig,
        HashRing,
        LocalKv,
        ReplicaRunner,
        ServeRegistry,
        TierReplicaLink,
        merge_snapshots,
    )

    t_start = time.perf_counter()
    opts = {
        "gw_service_us": 400.0, "replicas": 4, "slots": 64,
        "duration_s": 3.0, "drain_s": 10.0, "slo_ms": 1000.0,
        "deadline_s": 2.0, "prompt_tokens": 8, "mnt": 1, "seed": 0,
        "poll_interval": 0.01, "queue_cap": 512,
        "burst_period_s": 1.0, "burst_duty": 0.35, "burst_high_x": 2.5,
        "diurnal_period_s": 3.0, "diurnal_amp": 0.8,
        "zipf_cells_a": 1.4,
    }
    gateways_rows = [1, 2]
    rates_override = None
    out_path = None
    smoke = False
    calibrate = False
    for a in argv:
        if a == "--calibrate":
            calibrate = True
        elif a == "--smoke":
            smoke = True
            opts.update(replicas=2, slots=32, duration_s=0.5,
                        drain_s=5.0, burst_period_s=0.4,
                        diurnal_period_s=0.6)
            gateways_rows = [1, 2]
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif a.startswith("--gateways="):
            gateways_rows = [
                int(x) for x in a.split("=", 1)[1].split(",") if x
            ]
        elif a.startswith("--rates="):
            rates_override = [
                float(x) for x in a.split("=", 1)[1].split(",") if x
            ]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "SERVE_BENCH_CPU.json",
        )

    floor_s = opts["gw_service_us"] / 1e6
    # ~3 pipeline messages per completed request (submit + streamed
    # tokens + done, polls amortized): the modeled single-gateway knee.
    est_knee = (1.0 / floor_s) / 3.0
    if rates_override is not None:
        rates = rates_override
    elif smoke:
        rates = [round(est_knee * 0.5), round(est_knee * 2.0)]
    else:
        rates = [round(est_knee * f) for f in
                 (0.4, 0.7, 1.0, 1.3, 1.7, 2.2)]

    ttft_buckets = (
        1, 2, 5, 10, 20, 35, 50, 75, 100, 150, 200, 350, 500, 750,
        1000, 1500, 2000, 3000, 5000, 10000, 30000,
    )

    def make_trace(kind: str, rate: float, duration: float, seed: int):
        """-> (arrival_times, [(t_start, phase_name), ...]).  Arrivals
        by exponential gaps (poisson), a square-wave rate (bursty), or
        sinusoidal thinning (diurnal)."""
        rng = np.random.RandomState(seed)
        if kind == "poisson":
            gaps = rng.exponential(1.0 / max(rate, 1e-9),
                                   size=int(rate * duration * 3) + 16)
            times = np.cumsum(gaps)
            return times[times < duration].tolist(), [(0.0, "steady")]
        if kind == "bursty":
            period, duty = opts["burst_period_s"], opts["burst_duty"]
            high = rate * opts["burst_high_x"]
            low = max(
                rate * (1 - opts["burst_high_x"] * duty) / (1 - duty),
                rate * 0.05,
            )
            times, phases, t = [], [], 0.0
            while t < duration:
                phases.append((t, "burst"))
                t_end = min(t + period * duty, duration)
                tt = t
                while True:
                    tt += rng.exponential(1.0 / high)
                    if tt >= t_end:
                        break
                    times.append(tt)
                phases.append((t_end, "idle"))
                t2 = min(t + period, duration)
                while True:
                    tt += rng.exponential(1.0 / low)
                    if tt >= t2:
                        break
                    times.append(tt)
                t = t2
            return times, phases
        if kind == "diurnal":
            period, amp = opts["diurnal_period_s"], opts["diurnal_amp"]
            peak = rate * (1 + amp)
            gaps = rng.exponential(1.0 / peak,
                                   size=int(peak * duration * 3) + 16)
            cand = np.cumsum(gaps)
            cand = cand[cand < duration]
            lam = rate * (1 + amp * np.sin(
                2 * np.pi * cand / period
            ))
            keep = rng.uniform(size=len(cand)) < lam / peak
            times = cand[keep].tolist()
            phases = []
            t = 0.0
            while t < duration:
                phases.append((t, "peak"))
                phases.append((t + period / 2, "trough"))
                t += period
            return times, [p for p in phases if p[0] < duration]
        raise ValueError(f"unknown trace kind {kind!r}")

    def run_point(n_gateways: int, kind: str, rate: float) -> dict:
        gids = [f"gw{i}" for i in range(n_gateways)]
        registry = ServeRegistry(LocalKv(), job="loadbench",
                                 lease_s=3600.0)
        pipes = {}
        gws = {}
        phase_hists = {}
        current_phase = [None]
        for gid in gids:
            gw = Gateway(
                port=0,
                config=GatewayConfig(
                    # Bounded per-gateway admission: past the knee,
                    # submissions REJECT (explicit backpressure) —
                    # that is what makes admission throughput a
                    # saturating, measurable quantity.
                    queue_cap=opts["queue_cap"],
                    default_deadline_s=opts["deadline_s"],
                ),
                histogram_buckets=ttft_buckets,
            )
            # NOT started: the wire cost is modeled by the pipeline's
            # serialize/deserialize pass — no sockets needed.
            row_stats = {"done_in_slo": 0}
            orig_lat = gw.core.observe_latency_ms
            orig_ttft = gw.core.observe_ttft_ms

            def lat_obs(v, _o=orig_lat, _r=row_stats):
                _o(v)
                if v <= opts["slo_ms"]:
                    _r["done_in_slo"] += 1

            def ttft_obs(v, _o=orig_ttft):
                _o(v)
                ph = current_phase[0]
                if ph is not None:
                    ph.observe(v)

            gw.core.observe_latency_ms = lat_obs
            gw.core.observe_ttft_ms = ttft_obs
            gw._loadbench_slo = row_stats  # noqa: SLF001 - bench hook
            cap = max(64, int(1.0 / floor_s))
            pipes[gid] = _PacedPipeline(gw.handle, floor_s, cap)
            gws[gid] = gw
            registry.announce_gateway(gid, f"pipe://{gid}")

        def connect(addr):
            return pipes[addr.split("//", 1)[1]]

        runners = []
        threads = []
        for i in range(opts["replicas"]):
            rid = f"r{i}"
            link = TierReplicaLink(registry, rid, connect=connect,
                                   refresh_s=1.0)
            runner = ReplicaRunner(
                _StubDecodeServer(opts["slots"], opts["mnt"]), link,
                rid, poll_interval=opts["poll_interval"],
                kv_p2p=False,
            )
            runners.append(runner)
            th = threading.Thread(target=runner.run, daemon=True)
            th.start()
            threads.append(th)

        ring = HashRing(gids)
        homes = None
        if kind == "zipf_cells":
            # ISSUE 17 regional-skew model: gateways stand in for
            # cells, cell 0 is hot — arrivals route by HOME, not by
            # the uniform request-id hash, so the hot shard's TTFT
            # inflation under skew is measured (the spillover
            # motivation; the global bench replays the same trace
            # across real cells).
            times, homes = zipf_cell_trace(
                rate, opts["duration_s"], n_gateways,
                opts["zipf_cells_a"], opts["seed"] + int(rate),
            )
            phases = [
                (at, "hot-cell" if c == 0 else "cold-cell")
                for at, c in zip(times, homes)
            ] or [(0.0, "hot-cell")]
        else:
            times, phases = make_trace(kind, rate, opts["duration_s"],
                                       opts["seed"] + int(rate))
        for name in {p[1] for p in phases}:
            phase_hists[name] = Histogram(buckets=ttft_buckets)
        prompt = list(range(1, opts["prompt_tokens"] + 1))
        behind_s = 0.0
        tag = f"{kind[0]}{n_gateways}x{int(rate)}"
        t0 = time.perf_counter()
        phase_idx = 0
        try:
            for i, at in enumerate(times):
                while phase_idx < len(phases) and \
                        at >= phases[phase_idx][0]:
                    current_phase[0] = phase_hists[
                        phases[phase_idx][1]
                    ]
                    phase_idx += 1
                rid = f"{tag}-{i}"
                msg = wire.ServeSubmit(
                    req_id=rid, prompt=prompt,
                    max_new_tokens=opts["mnt"],
                    deadline_s=opts["deadline_s"],
                )
                data = wire.serialize(msg)
                now = time.perf_counter() - t0
                if now < at:
                    time.sleep(at - now)
                else:
                    behind_s = max(behind_s, now - at)
                owner = (gids[homes[i]] if homes is not None
                         else ring.owner(rid))
                pipes[owner].cast(data)
            # Drain: every submitted request reaches a terminal state
            # (done / timeout / shed at the wire).
            drain_end = time.monotonic() + opts["drain_s"]
            while time.monotonic() < drain_end:
                # Both edges must be empty: the paced queues (casts
                # not yet handled are not in_flight anywhere yet) and
                # the gateways' books.
                if all(p.q.empty() for p in pipes.values()) and all(
                    gw.core.stats_snapshot()["in_flight"] == 0
                    for gw in gws.values()
                ):
                    break
                time.sleep(0.05)
            elapsed = time.perf_counter() - t0
            merged = merge_snapshots(
                [gw.core.stats_snapshot() for gw in gws.values()]
            )
            counters = merged["counters"]
            in_slo = sum(
                gw._loadbench_slo["done_in_slo"]  # noqa: SLF001
                for gw in gws.values()
            )
            ttft_all = Histogram.merged(
                [gw.ttft_ms for gw in gws.values()],
                buckets=ttft_buckets,
            )
            # Rates over the WHOLE window to terminal (trace + drain
            # tail): an overloaded row that accepts everything into a
            # deep queue must not book drain-time work against the
            # trace duration.
            span = max(elapsed, 1e-9)
            point = {
                "gateways": n_gateways,
                "trace": kind,
                "offered_rps": round(rate, 1),
                "submitted": len(times),
                "accepted": counters.get("accepted", 0),
                "rejected": counters.get("rejected", 0),
                "wire_dropped": sum(
                    p.wire_dropped for p in pipes.values()
                ),
                "completed": counters.get("completed", 0),
                "timeout": counters.get("timeout", 0),
                "failed": counters.get("failed", 0),
                "completed_in_slo": in_slo,
                "admit_rps": round(
                    counters.get("accepted", 0) / span, 1
                ),
                "sustained_rps": round(
                    counters.get("completed", 0) / span, 1
                ),
                "goodput_rps": round(in_slo / span, 1),
                "ttft_ms_p50": ttft_all.percentile(0.50),
                "ttft_ms_p99": ttft_all.percentile(0.99),
                "driver_behind_ms": round(behind_s * 1000.0, 1),
                "elapsed_s": round(elapsed, 2),
                "pipe_busy_frac": round(
                    sum(p.busy_s for p in pipes.values())
                    / (len(pipes) * max(elapsed, 1e-9)), 3,
                ),
            }
            if len(phase_hists) > 1:
                point["phases"] = {
                    name: {
                        "count": h.count,
                        "ttft_ms_p50": h.percentile(0.50),
                        "ttft_ms_p99": h.percentile(0.99),
                    }
                    for name, h in sorted(phase_hists.items())
                }
            return point
        finally:
            for gw in gws.values():
                for rid in list(
                    gw.core.stats_snapshot()["replicas"]
                ):
                    gw.core.drain(rid)
            for th in threads:
                th.join(timeout=15)
            for pipe in pipes.values():
                pipe.stop()

    def profile_admission() -> dict:
        """Deterministic profile of the admission hot loop (one
        serialize -> deserialize -> GatewayCore dispatch -> reply
        serialize pass per message, exactly what the pipeline worker
        runs), plus the measured fast-path-vs-baseline serialization
        delta that ISSUE 9 asked the profile to justify."""
        import cProfile
        import pstats

        gw = Gateway(port=0, config=GatewayConfig(queue_cap=100000))
        gw.core.register("rp", 64)
        n = 400 if smoke else 4000
        subs = [
            wire.serialize(wire.ServeSubmit(
                req_id=f"prof-{i}", prompt=list(range(16)),
                max_new_tokens=1,
            ))
            for i in range(n)
        ]
        poll = wire.serialize(wire.ServeReplicaPoll(
            replica_id="rp", free_slots=8,
            active=[f"prof-{i}" for i in range(8)],
            stats={"slot_occupancy": 0.5, "queue_depth": 3},
        ))

        def hot_loop():
            for data in subs:
                reply = gw.handle(wire.deserialize(data))
                wire.serialize(reply)
                reply = gw.handle(wire.deserialize(poll))
                wire.serialize(reply)

        pr = cProfile.Profile()
        pr.enable()
        hot_loop()
        pr.disable()
        stats = pstats.Stats(pr)
        total_tt = sum(row[2] for row in stats.stats.values())
        top = sorted(
            (
                (f"{fn[2]} ({os.path.basename(fn[0])}:{fn[1]})",
                 row[2], row[3])
                for fn, row in stats.stats.items()
            ),
            key=lambda r: -r[1],
        )[:10]
        ser_tt = sum(
            row[2] for fn, row in stats.stats.items()
            if fn[2] in ("serialize", "deserialize", "_encode",
                         "_decode", "packb", "unpackb")
            or fn[2].startswith(("_encode", "_decode"))
        )
        sub_msg = wire.ServeSubmit(
            req_id="x", prompt=list(range(64)), max_new_tokens=8,
        )
        grants = wire.ServeGrants(requests=[sub_msg] * 4)
        reps = 300 if smoke else 3000

        def time_of(fn, msg):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn(msg)
            return (time.perf_counter() - t0) / reps * 1e6

        return {
            "messages": 2 * n,
            "serialize_frac_of_hot_loop": round(
                ser_tt / total_tt, 3
            ) if total_tt else 0.0,
            "top_by_tottime": [
                {"fn": name, "tottime_s": round(tt, 4)}
                for name, tt, _ct in top[:6]
            ],
            "fast_path_us": {
                "submit": round(time_of(wire.serialize, sub_msg), 2),
                "grants": round(time_of(wire.serialize, grants), 2),
            },
            "baseline_us": {
                "submit": round(
                    time_of(wire.serialize_baseline, sub_msg), 2
                ),
                "grants": round(
                    time_of(wire.serialize_baseline, grants), 2
                ),
            },
        }

    result = {
        "bench": "serve_load",
        "gw_service_us": opts["gw_service_us"],
        "replicas": opts["replicas"],
        "slots_per_replica": opts["slots"],
        "duration_s": opts["duration_s"],
        "slo_ms": opts["slo_ms"],
        "deadline_s": opts["deadline_s"],
        "est_single_gateway_knee_rps": round(est_knee),
        "note": (
            "open-loop tier harness: per-gateway PACED PIPELINES "
            "(max(real_cpu, gw_service_us) per message) model the "
            "one-core-per-gateway regime on a 1-core CI host — the "
            "same modeled-budget-with-real-cpu-charged pattern as the "
            "ckpt bench's --link_mbps; ring routing, fan-out replica "
            "polls, admission, dedupe and instruments are the real "
            "code.  TTFT phases are attributed at first-token time."
        ),
        "sweep": [],
        "traces": [],
    }

    def flush():
        # Merge into the serving artifact: --serve_bench owns the
        # other sections and preserves `load` when it rewrites.
        try:
            with open(out_path) as f:
                full = json.load(f)
            if not isinstance(full, dict):
                full = {}
        except (OSError, ValueError):
            full = {}
        full["load"] = result
        with open(out_path, "w") as f:
            json.dump(full, f, indent=1)

    def calibrate_gw_service() -> dict:
        """ROADMAP 4c satellite: measure the REAL per-message admission
        CPU of a gateway — a SUBPROCESS gateway over real sockets, fed
        by the real TierClient/TierReplicaLink wire path — and record
        it beside the modeled ``gw_service_us`` floor the paced
        pipelines charge.  CPU is read from /proc/<pid>/stat
        (utime+stime, whole process: deserialize + GatewayCore dispatch
        + serialize + gRPC/socket work); the denominator is the
        gateway's served-request counter, shipped in its stats snapshot
        (``rpc_calls``).  Registry heartbeats (~1/s) ride inside the
        measurement and are noted, not subtracted."""
        import subprocess
        import threading

        from dlrover_tpu.serving import (
            RegistryServer,
            RpcKv,
            ServeRegistry,
            TierActuator,
            TierClient,
        )

        repo = os.path.dirname(os.path.abspath(__file__))
        n_req = 60 if smoke else 400
        reg_server = RegistryServer()
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": repo})
        env.pop("DLROVER_TPU_FAULTS", None)
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "examples", "llama_serve_fleet.py"),
             "--role", "gateway", "--registry", reg_server.addr,
             "--job", "calib", "--gateway_id", "cal0",
             "--lease_timeout", "10"],
            cwd=repo, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        registry = ServeRegistry(
            RpcKv(reg_server.addr), job="calib", lease_s=10.0
        )
        link = TierReplicaLink(registry, "calrep")
        runner = ReplicaRunner(
            _StubDecodeServer(64, opts["mnt"]), link, "calrep",
            poll_interval=0.005, kv_p2p=False,
        )
        cli = TierClient(registry, poll_interval=0.005, refresh_s=0.5)
        clk = os.sysconf("SC_CLK_TCK")

        def cpu_s():
            with open(f"/proc/{proc.pid}/stat") as f:
                parts = f.read().rsplit(") ", 1)[1].split()
            return (int(parts[11]) + int(parts[12])) / clk

        def gw_stats():
            snaps = cli.stats()
            return snaps[0] if snaps else {}

        th = threading.Thread(target=runner.run, daemon=True)
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if registry.gateways():
                    break
                if proc.poll() is not None:
                    return {"error":
                            f"gateway exited rc={proc.returncode}"}
                time.sleep(0.2)
            else:
                return {"error": "gateway never announced within 60s"}
            th.start()
            while time.monotonic() < deadline:
                if gw_stats().get("replicas_alive", 0) >= 1:
                    break
                time.sleep(0.1)
            else:
                return {"error":
                        "replica never registered at the gateway"}
            # Warm the wire (channel setup, first-call paths), then
            # measure a steady window.
            for i in range(10):
                cli.submit(f"warm-{i}", list(range(8)), opts["mnt"],
                           submit_timeout=10)
            for i in range(10):
                cli.result(f"warm-{i}", timeout=30)
            calls0 = int(gw_stats().get("rpc_calls", 0))
            cpu0 = cpu_s()
            t0 = time.perf_counter()
            for i in range(n_req):
                cli.submit(f"cal-{i}", list(range(8)), opts["mnt"],
                           submit_timeout=10)
            for i in range(n_req):
                cli.result(f"cal-{i}", timeout=60)
            wall = time.perf_counter() - t0
            cpu1 = cpu_s()
            calls1 = int(gw_stats().get("rpc_calls", 0))
            msgs = calls1 - calls0
            if msgs <= 0 or proc.poll() is not None:
                return {"error": f"no messages measured ({msgs})"}
            measured = (cpu1 - cpu0) * 1e6 / msgs
            out = {
                "requests": n_req,
                "messages": msgs,
                "gateway_cpu_s": round(cpu1 - cpu0, 3),
                "wall_s": round(wall, 2),
                "gw_service_us_measured": round(measured, 1),
                "gw_service_us": opts["gw_service_us"],
                "measured_over_modeled": round(
                    measured / opts["gw_service_us"], 2
                ),
                "note": (
                    "subprocess gateway over real sockets; CPU from "
                    "/proc utime+stime across the window divided by "
                    "the gateway's served-request count (submits, "
                    "status polls, replica fan-out polls, reports); "
                    "includes gRPC/socket CPU and ~1/s registry "
                    "heartbeats"
                ),
            }
            return out
        finally:
            try:
                TierActuator(registry=registry).drain("calrep")
            except Exception as e:  # noqa: BLE001 - teardown
                print(f"calibrate teardown drain failed: {e}",
                      file=sys.stderr)
            runner._stopped = True  # noqa: SLF001 - bench teardown
            th.join(timeout=15) if th.is_alive() else None
            cli.close()
            link.close()
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
            reg_server.stop()

    flush()
    prof = profile_admission()
    result["admission_profile"] = prof
    fast = prof["fast_path_us"]["submit"]
    base = prof["baseline_us"]["submit"]
    result["serialize_speedup_x"] = round(base / fast, 2) if fast else 0
    flush()

    if calibrate:
        result["calibration"] = calibrate_gw_service()
        print(f"calibration: {result['calibration']}", file=sys.stderr)
        flush()

    for n in gateways_rows:
        for rate in rates:
            point = run_point(n, "poisson", float(rate))
            result["sweep"].append(point)
            flush()
            print(f"load sweep: {point}", file=sys.stderr)

    # Saturation verdict: the best rate each tier size SUSTAINED
    # across the sweep — admission (accepted/s under bounded-queue
    # backpressure, the acceptance criterion) and SLO goodput.
    best_admit = {}
    best_goodput = {}
    for point in result["sweep"]:
        n = point["gateways"]
        best_admit[n] = max(best_admit.get(n, 0.0),
                            point["admit_rps"])
        best_goodput[n] = max(best_goodput.get(n, 0.0),
                              point["goodput_rps"])
    result["saturation_admit_rps"] = {
        str(n): v for n, v in best_admit.items()
    }
    result["saturation_goodput_rps"] = {
        str(n): v for n, v in best_goodput.items()
    }
    speedup = None
    if 1 in best_admit and max(best_admit) > 1 and best_admit[1] > 0:
        speedup = round(best_admit[max(best_admit)] / best_admit[1], 2)
        result["tier_speedup_x"] = speedup
        result["tier_speedup_gateways"] = max(best_admit)
        result["goodput_speedup_x"] = round(
            best_goodput[max(best_goodput)] / best_goodput[1], 2
        ) if best_goodput.get(1) else 0.0
        result["meets_1p5x"] = speedup >= 1.5
    flush()

    # Phase traces at the largest tier, around the single-gateway knee
    # (burst peaks push past it; the tier must hold the SLO).
    n_trace = max(gateways_rows)
    for kind in ("bursty", "diurnal"):
        point = run_point(n_trace, kind, float(rates[-2 if len(rates)
                                                    > 1 else 0]))
        result["traces"].append(point)
        flush()
        print(f"load trace: {point}", file=sys.stderr)

    # Regional skew (ISSUE 17): the same offered rate, but arrivals
    # routed by a Zipf-over-cells HOME assignment (gateway 0 hot)
    # instead of the uniform id hash — the hot shard saturates while
    # the cold shards idle, the collapse cross-cell spillover exists
    # to fix.  The global bench replays this trace across real cells.
    point = run_point(n_trace, "zipf_cells",
                      float(rates[-2 if len(rates) > 1 else 0]))
    result["skew"] = point
    flush()
    print(f"load skew: {point}", file=sys.stderr)

    # Conservation: every submission was shed at the wire, rejected by
    # backpressure, or accepted — and every accepted request reached a
    # terminal state within the drain budget.
    result["complete"] = (
        len(result["sweep"]) == len(gateways_rows) * len(rates)
        and len(result["traces"]) == 2
        and all(
            p["submitted"] == p["accepted"] + p["rejected"]
            + p["wire_dropped"]
            and p["accepted"] == p["completed"] + p["timeout"]
            + p["failed"]
            for p in result["sweep"] + result["traces"]
            + [result["skew"]]
        )
    )
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "serve_tier_saturation_speedup",
        "value": speedup if speedup is not None else 0.0,
        "unit": "x_admit_rps_vs_single_gateway",
        "vs_baseline": speedup if speedup is not None else 0.0,
        "backend": "cpu",
        "artifact": out_path,
    }))
    ok = result["complete"] and (
        speedup is None or result.get("meets_1p5x", False)
    )
    return 0 if ok else 1


def fleet_bench_main(argv: list) -> int:
    """Mixed-fleet control-plane bench (ISSUE 10): ONE FleetManager
    supervising a training role (real job manager + autoscaler over the
    in-memory platform — control-plane stub workers, the container
    cannot run multi-process XLA) AND a serving role (real-socket
    gateway tier + drain-aware replicas) in one process, measuring the
    two fleet laws end to end:

    - SUPERVISED GATEWAY RELAUNCH: a crashed tier gateway (heartbeats
      stop, registry entry ages out) is observed and respawned under
      its own id; time from crash to the registry showing the full
      desired set again, with in-flight requests completing
      exactly-once through the churn.
    - CROSS-ROLE BORROW: a sustained serving-queue spike borrows a
      training chip (drain-first: the live-reshard epoch completes
      BEFORE the worker is released, serving grows only after), and
      the chip is handed back on decay (serving drains first).

    Flags: ``--requests=N`` ``--interval=F`` (reconcile pass pacing)
    ``--out=PATH`` (default FLEET_BENCH_CPU.json) ``--smoke``.
    """
    import os
    import threading

    from dlrover_tpu.common import messages as wire
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.fleet import (
        BorrowPolicy,
        ChipBorrowArbiter,
        FleetManager,
        GatewayRole,
        RoleSpec,
        ServingReplicaRole,
        TrainingRole,
    )
    from dlrover_tpu.master import reshard as rs
    from dlrover_tpu.master.dist_job_manager import DistributedJobManager
    from dlrover_tpu.master.job_auto_scaler import (
        AllreduceTrainingAutoScaler,
    )
    from dlrover_tpu.master.reshard import ReshardManager
    from dlrover_tpu.master.scaler import PlatformScaler
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.scheduler.job import JobArgs, NodeGroupArgs
    from dlrover_tpu.scheduler.platform import InMemoryPlatform
    from dlrover_tpu.serving import (
        GatewayTierNode,
        RegistryServer,
        ReplicaRunner,
        RpcKv,
        ServeRegistry,
        TierActuator,
        TierClient,
        TierReplicaLink,
    )
    from dlrover_tpu.serving.autoscale import ScalePolicy
    from dlrover_tpu.serving.gateway import GatewayConfig

    t_start = time.perf_counter()
    opts = {"requests": 24, "spike_requests": 40, "interval": 0.1,
            "decode_ms": 200.0, "lease_s": 1.5, "seed": 0}
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
            opts.update(requests=8, spike_requests=30, decode_ms=150.0)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "FLEET_BENCH_CPU.json",
        )

    class _SlowDecode:
        """Deterministic stub decode server with a real service time
        per request, so queue pressure (the borrow signal) is real
        while the measurement stays about the CONTROL PLANE."""

        def __init__(self, slots, decode_s):
            self.slots = slots
            self.decode_s = decode_s
            self._pending = []
            self._mu = threading.Lock()

        def submit(self, rid, prompt, mnt, **_kw):
            with self._mu:
                self._pending.append((rid, list(prompt), int(mnt)))

        def cancel(self, rid):
            with self._mu:
                for i, item in enumerate(self._pending):
                    if item[0] == rid:
                        del self._pending[i]
                        return True
            return False

        def pending_count(self):
            with self._mu:
                return len(self._pending)

        def pending_rids(self):
            with self._mu:
                return [r for r, _, _ in self._pending]

        def active_rids(self):
            return []

        def free_slots(self):
            with self._mu:
                return max(0, self.slots - len(self._pending))

        def serve_incremental(self, tick=None, on_finish=None,
                              on_token=None):
            while True:
                keep = tick() is not False if tick else True
                with self._mu:
                    batch = self._pending[: self.slots]
                    self._pending = self._pending[self.slots:]
                for rid, prompt, mnt in batch:
                    time.sleep(self.decode_s)
                    out = list(prompt)
                    for i in range(mnt):
                        tok = (len(prompt) + i) % 97
                        out.append(tok)
                        if on_token:
                            on_token(rid, tok)
                    if on_finish:
                        on_finish(rid, out)
                if not keep and not batch:
                    return {}
                if not batch:
                    time.sleep(0.001)

    reg_server = RegistryServer()
    job = "fleetbench"

    def new_registry():
        return ServeRegistry(RpcKv(reg_server.addr), job=job,
                             lease_s=opts["lease_s"])

    # -- serving side: supervised gateway tier + replica role.
    nodes = {}  # gid -> [GatewayTierNode incarnations]
    node_mu = threading.Lock()

    def spawn_gateway(gid):
        node = GatewayTierNode(
            gid, new_registry(), port=0,
            # Replica lease well above the worst-case fan-out stall a
            # dying peer gateway can inject into the SERIAL poll loop
            # (the replica is not dead, its poll is late).
            config=GatewayConfig(lease_timeout_s=15.0),
            heartbeat_s=0.3,
        )
        node.start()
        with node_mu:
            nodes.setdefault(gid, []).append(node)

    runners = []  # (runner, thread)

    def spawn_replica(n=1, role=None):
        for _ in range(n):
            rid = f"r{len(runners)}"
            runner = ReplicaRunner(
                _SlowDecode(1, opts["decode_ms"] / 1000.0),
                TierReplicaLink(new_registry(), rid), rid,
                poll_interval=0.01, kv_p2p=False,
            )
            th = threading.Thread(target=runner.run, daemon=True)
            th.start()
            runners.append((runner, th))

    actuator = TierActuator(registry=new_registry())

    # -- training side: real manager/scaler/reshard epoch.
    job_args = JobArgs(job_name=job)
    job_args.node_groups[NodeType.WORKER] = NodeGroupArgs(
        count=3, min_count=2, max_count=4
    )
    platform = InMemoryPlatform()
    jm = DistributedJobManager(
        job_args, platform, PlatformScaler(job, platform)
    )
    jm.start()
    rm = ReshardManager()
    scaler = AllreduceTrainingAutoScaler(
        job_args, jm, SpeedMonitor(), None, reshard_manager=rm
    )

    # -- ONE fleet.
    fleet = FleetManager(interval=999)
    t_role = fleet.add_role(TrainingRole(
        RoleSpec("training", desired=3, min_count=2, max_count=4),
        scaler, jm,
    ))
    fleet.add_role(GatewayRole(
        RoleSpec("gateway", desired=2, min_count=1, max_count=3),
        new_registry(), spawn_gateway, id_prefix="g",
    ))
    s_role = fleet.add_role(ServingReplicaRole(
        RoleSpec("serving", desired=2, min_count=1, max_count=4,
                 # The merged membership view can flicker for a pass
                 # while a crashed gateway's lease ages out — a blip
                 # must not add real capacity.
                 spawn_confirm_passes=3),
        actuator, spawn_replica,
        policy=ScalePolicy(up_patience=10**9, down_patience=10**9),
    ))
    arbiter = fleet.add_cross_policy(ChipBorrowArbiter(
        t_role, s_role,
        BorrowPolicy(queue_high_per_member=4.0, spike_patience=2,
                     queue_low_per_member=1.0, decay_patience=3,
                     cooldown_passes=2),
    ))

    def drive(cond, timeout, report_done=False):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            rm.info()  # stub workers poll the resize epoch
            if report_done and rm.status == rs.PREPARING:
                epoch = rm.epoch
                for node_id in range(3):
                    rm.report(wire.ReshardReport(
                        node_id=node_id, epoch=epoch, ok=True,
                        downtime_ms=5.0, moved_mb=1.0,
                    ))
            fleet.reconcile_once()
            time.sleep(opts["interval"])
        return cond()

    result = {
        "bench": "fleet",
        "smoke": smoke,
        "note": (
            "one FleetManager, three roles: training (real job "
            "manager + allreduce scaler + live-reshard epoch over the "
            "in-memory platform — control-plane stub workers, this "
            "container cannot run multi-process XLA), a supervised "
            "gateway tier (real sockets, registry-leased health) and "
            "drain-aware serving replicas (stub decode with a real "
            "per-request service time).  Exactly-once is judged from "
            "the CLIENT: every submitted id reaches done with "
            "deterministic tokens across gateway churn."
        ),
        "params": dict(opts),
        "complete": False,
    }

    def flush():
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    cli = TierClient(new_registry(), poll_interval=0.02, refresh_s=0.3)
    rc = 1
    try:
        # -- formation: every role reaches its desired shape.
        t0 = time.perf_counter()
        ok = drive(
            lambda: len(cli.stats()) == 2
            and actuator.stats_snapshot()["replicas_alive"] >= 2
            and len(jm.alive_workers()) == 3,
            timeout=60,
        )
        result["formation_s"] = round(time.perf_counter() - t0, 2)
        result["formation_ok"] = ok
        flush()
        if not ok:
            print("fleet bench: formation failed", file=sys.stderr)
            return 1

        # -- steady traffic, then CRASH g1 with work in flight.
        submitted = {}
        for i in range(opts["requests"]):
            rid = f"req-{i}"
            prompt = [(7 * i + j) % 50 + 1 for j in range(6)]
            submitted[rid] = prompt
            cli.submit(rid, prompt, 4, submit_timeout=30)
        with node_mu:
            victim = nodes["g1"][0]
        crash_t = time.perf_counter()
        victim.crash()

        def tier_restored():
            if len(nodes.get("g1", [])) < 2:
                return False
            gids = {s.get("gateway_id") for s in cli.stats()}
            return gids == {"g0", "g1"}

        ok = drive(tier_restored, timeout=60)
        relaunch_s = time.perf_counter() - crash_t
        done = 0
        for rid in submitted:
            reply = cli.result(rid, timeout=60)
            done += reply.state == "done"
        result["gateway_relaunch"] = {
            "relaunched": ok,
            "crash_to_restored_s": round(relaunch_s, 2),
            "incarnations_g1": len(nodes.get("g1", [])),
            "inflight_total": len(submitted),
            "inflight_completed": done,
            "client_resubmitted": cli.resubmitted,
        }
        flush()

        # -- borrow cycle: spike -> drain-first lend -> grow; decay ->
        # drain-first shrink -> reclaim.
        workers_before = len(jm.alive_workers())
        replicas_before = actuator.stats_snapshot()["replicas_alive"]
        spike_ids = []
        spike_t = time.perf_counter()
        for i in range(opts["spike_requests"]):
            rid = f"spike-{i}"
            spike_ids.append(rid)
            cli.submit(rid, [1, 2, 3, 4], 2, submit_timeout=30)
        ok_borrow = drive(
            lambda: arbiter.phase == "borrowed", timeout=90,
            report_done=True,
        )
        borrow_s = time.perf_counter() - spike_t
        workers_during = len(jm.alive_workers())
        replicas_during = actuator.stats_snapshot()["replicas_alive"]
        # Decay: the (now larger) pool drains the spike queue.
        handback_t = time.perf_counter()
        ok_back = drive(
            lambda: arbiter.phase == "idle"
            and len(jm.alive_workers()) == workers_before,
            timeout=120,
        )
        handback_s = time.perf_counter() - handback_t
        spike_done = 0
        for rid in spike_ids:
            reply = cli.result(rid, timeout=60)
            spike_done += reply.state == "done"
        result["borrow"] = {
            "borrowed": ok_borrow,
            "handed_back": ok_back,
            "time_to_borrow_s": round(borrow_s, 2),
            "time_to_handback_s": round(handback_s, 2),
            "reshard_status": rm.status,
            "workers_before": workers_before,
            "workers_during_borrow": workers_during,
            "workers_after": len(jm.alive_workers()),
            "replicas_before": replicas_before,
            "replicas_during_borrow": replicas_during,
            "replicas_after":
                actuator.stats_snapshot()["replicas_alive"],
            "spike_completed": spike_done,
            "spike_total": len(spike_ids),
            "transitions": [t for _f, t, _r in arbiter.events],
        }
        result["requests"] = {
            "submitted": len(submitted) + len(spike_ids),
            "completed": done + spike_done,
        }
        result["complete"] = bool(
            result["formation_ok"]
            and result["gateway_relaunch"]["relaunched"]
            and done == len(submitted)
            and ok_borrow and ok_back
            and spike_done == len(spike_ids)
            and rm.status == rs.DONE
        )
        result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
        flush()
        print(json.dumps({
            "metric": "fleet_gateway_relaunch_s",
            "value": result["gateway_relaunch"]["crash_to_restored_s"],
            "unit": "s_crash_to_desired_restored",
            "vs_baseline": 0.0,
            "backend": "cpu",
            "artifact": out_path,
        }))
        rc = 0 if result["complete"] else 1
        return rc
    finally:
        # Each teardown step stands alone: a failure (e.g. draining
        # against an already-dead registry) must not skip the stops
        # below it — a leaked gRPC server would hang the process past
        # the smoke gate's subprocess timeout.
        def _teardown(step):
            try:
                step()
            except Exception:  # noqa: BLE001 - teardown must not mask rc
                print("fleet bench teardown step failed",
                      file=sys.stderr)

        def _drain_all():
            for rid in list(
                actuator.stats_snapshot().get("replicas", {})
            ):
                actuator.drain(rid)

        def _stop_runners():
            for runner, _th in runners:
                runner._stopped = True  # noqa: SLF001 - bench teardown
            for _runner, th in runners:
                th.join(timeout=10)

        def _stop_nodes():
            with node_mu:
                for incs in nodes.values():
                    for node in incs:
                        _teardown(lambda n=node: n.stop(0.0))

        _teardown(_drain_all)
        _teardown(_stop_runners)
        _teardown(cli.close)
        _teardown(actuator.close)
        _teardown(_stop_nodes)
        _teardown(jm.stop)
        _teardown(reg_server.stop)


def ha_bench_main(argv: list) -> int:
    """Master HA failover bench (ISSUE 13; ROADMAP item 5's metric):
    failover-blackout seconds, COLD vs WARM.

    - COLD: today's supervised blank-state relaunch — the launcher's
      supervisor notices the dead master on its poll tick and respawns
      ``master.main`` on the same port (process start + import +
      bind); every piece of control-plane state is gone.
    - WARM: a standby that has been tailing the control-state journal
      declares the primary dead after the reader-side lease, replays
      to head, binds and serves — with the state INTACT (proven by
      reading back a pre-kill KV marker and continuing the data-shard
      queue).

    Blackout is measured from the SIGKILL to the first successful RPC
    answered by the recovered master, probed with short-budget calls
    (0.5s per attempt) so the measurement is about recovery, not about
    a client's retry backoff.  The probe follows the state-dir ``addr``
    file exactly like a failover-aware client.

    Flags: ``--lease_s=F`` (warm reader lease, default 1.0)
    ``--supervisor_poll_s=F`` (cold supervisor tick, default 1.0 — the
    value run.py uses) ``--out=PATH`` (default HA_BENCH_CPU.json)
    ``--smoke`` (short lease, same assertions).
    """
    import os
    import signal as _signal
    import subprocess
    import tempfile

    from dlrover_tpu.common import messages as wire
    from dlrover_tpu.common.rpc import RpcClient, find_free_port
    from dlrover_tpu.master.state import read_addr

    t_start = time.perf_counter()
    opts = {"lease_s": 0.5, "supervisor_poll_s": 1.0, "tasks": 12,
            "trials": 3}
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
            opts.update(trials=1)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "HA_BENCH_CPU.json",
        )
    result = {
        "bench": "ha",
        "smoke": smoke,
        "opts": dict(opts),
        "note": (
            "blackout_s = SIGKILL -> first successful RPC at the "
            "recovered master (0.5s-budget probes; warm probe follows "
            "the state-dir addr file); medians over `trials`.  cold = "
            "supervised blank-state relaunch on run.py's 1.0s poll "
            "tick; warm = standby reader-lease expiry (lease_s — the "
            "fast-failover configuration a dedicated standby runs; it "
            "tails the journal continuously, so its detection is "
            "legitimately tighter than the supervisor's coarse poll) + "
            "journal replay + bind.  Honesty: on THIS container a "
            "blank master respawns in ~0.2s (tiny jax-free import, hot "
            "page cache), so at MATCHED 1.0s detection budgets the two "
            "liveness numbers are within ~60ms — the structural wins "
            "are the tighter detection and the STATE: cold's number is "
            "a lower bound that excludes the rebuild a blank master "
            "still needs (agent re-join intervals, dataset "
            "re-registration, doing-task leases), recorded as "
            "state_recovered=false, while warm continues the shard "
            "queue in place (queue_continues)."
        ),
    }

    def flush():
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        os.replace(tmp, out_path)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)

    def spawn_master(port, state_dir="", standby_of="", log_name="m",
                     workdir=None, lease_s=None):
        port_file = os.path.join(workdir, f"{log_name}.port")
        cmd = [sys.executable, "-m", "dlrover_tpu.master.main",
               f"--port={port}", f"--port_file={port_file}",
               "--job_name=ha-bench", "--min_nodes=1", "--max_nodes=1"]
        if state_dir:
            cmd += [f"--state_dir={state_dir}"]
        if standby_of:
            cmd += ["--standby", f"--primary_addr={standby_of}"]
        senv = dict(env)
        if lease_s is not None:
            senv["DLROVER_TPU_HA_LEASE_S"] = str(lease_s)
            senv["DLROVER_TPU_HA_TAIL_POLL_S"] = "0.05"
        log = open(os.path.join(workdir, f"{log_name}.log"), "w")
        proc = subprocess.Popen(cmd, env=senv, stdout=log,
                                stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                with open(port_file) as f:
                    content = f.read().strip()
                if content:
                    return proc, f"127.0.0.1:{content}"
            except OSError:
                pass
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{log_name} exited early rc={proc.returncode}"
                )
            time.sleep(0.1)
        raise TimeoutError(f"{log_name} never reported a port")

    def seed_state(addr):
        """A marker key + a partly-consumed data-shard queue, so warm
        recovery has real state to prove."""
        from dlrover_tpu.agent.master_client import MasterClient

        cli = MasterClient(addr, 0)
        cli.kv_store_set("ha/marker", b"pre-kill")
        cli.report_dataset_shard_params(
            dataset_name="hb", dataset_size=opts["tasks"] * 10,
            shard_size=10,
        )
        granted = []
        for _ in range(4):
            t = cli.get_task("hb")
            granted.append(t.task_id)
        cli.report_task_result("hb", granted[0], True)
        cli.close()
        return granted

    def probe_blackout(t_kill, addr_fn, timeout=90.0):
        """Seconds from the kill to the first successful RPC, probing
        whatever address addr_fn() currently names."""
        while time.monotonic() - t_kill < timeout:
            addr = addr_fn()
            if addr:
                cli = RpcClient(addr, timeout=0.5)
                try:
                    resp = cli.call(
                        wire.KVStoreGet(key="ha/marker"),
                        timeout=0.5, retries=1, deadline=0.5,
                        idempotent=True,
                    )
                    blackout = time.monotonic() - t_kill
                    found = bool(getattr(resp, "found", False))
                    return blackout, found
                except Exception:  # noqa: BLE001 - still black
                    pass
                finally:
                    cli.close()
            time.sleep(0.05)
        raise TimeoutError("master never came back")

    def run_cold(workdir, tag):
        port = find_free_port()
        proc, addr = spawn_master(port, log_name=f"{tag}_1",
                                  workdir=workdir)
        procs = [proc]
        try:
            seed_state(addr)
            os.kill(proc.pid, _signal.SIGKILL)
            t_kill = time.monotonic()
            # Emulate run.py's supervisor: notice the death on the next
            # poll tick, then respawn on the SAME port.
            while proc.poll() is None:
                time.sleep(0.01)
            time.sleep(opts["supervisor_poll_s"])
            proc2, _ = spawn_master(port, log_name=f"{tag}_2",
                                    workdir=workdir)
            procs.append(proc2)
            return probe_blackout(t_kill, lambda: addr)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()

    def run_warm(workdir, tag):
        state_dir = os.path.join(workdir, f"state_{tag}")
        primary, paddr = spawn_master(
            0, state_dir=state_dir, log_name=f"{tag}_primary",
            workdir=workdir,
        )
        standby, saddr = spawn_master(
            0, state_dir=state_dir, standby_of=paddr,
            log_name=f"{tag}_standby", workdir=workdir,
            lease_s=opts["lease_s"],
        )
        procs = [primary, standby]
        try:
            granted = seed_state(paddr)
            time.sleep(0.3)  # the tail is at head
            os.kill(primary.pid, _signal.SIGKILL)
            t_kill = time.monotonic()

            def current_addr():
                cur = read_addr(state_dir)
                return cur if cur and cur != paddr else ""

            warm_s, warm_found = probe_blackout(t_kill, current_addr)
            # The queue continues exactly where it stopped: next grant
            # is the first never-granted task id.
            from dlrover_tpu.agent.master_client import MasterClient

            cli = MasterClient(saddr, 0)
            nxt = cli.get_task("hb")
            queue_continues = nxt.task_id == max(granted) + 1
            cli.close()
            return warm_s, warm_found, queue_continues, state_dir
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(_signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    with tempfile.TemporaryDirectory(prefix="ha_bench_") as workdir:
        cold_runs, warm_runs = [], []
        cold_found_any = False
        warm_found_all, queue_all = True, True
        state_dir = ""
        for i in range(opts["trials"]):
            cold_s, cold_found = run_cold(workdir, f"cold{i}")
            cold_runs.append(round(cold_s, 3))
            cold_found_any = cold_found_any or cold_found
            warm_s, warm_found, queue_ok, state_dir = run_warm(
                workdir, f"warm{i}"
            )
            warm_runs.append(round(warm_s, 3))
            warm_found_all = warm_found_all and warm_found
            queue_all = queue_all and queue_ok
            result["cold"] = {
                "blackout_s": median(cold_runs),
                "runs": list(cold_runs),
                "state_recovered": cold_found_any,
            }
            result["warm"] = {
                "blackout_s": median(warm_runs),
                "runs": list(warm_runs),
                "state_recovered": warm_found_all,
                "queue_continues": queue_all,
                "lease_s": opts["lease_s"],
            }
            flush()

        # The last surviving journal passes fsck.
        check = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.master.statecheck",
             state_dir],
            capture_output=True, text=True, timeout=120, env=env,
        )
        result["statecheck_rc"] = check.returncode

    result["hot_strictly_faster"] = (
        result["warm"]["blackout_s"] < result["cold"]["blackout_s"]
    )
    result["complete"] = bool(
        result["hot_strictly_faster"]
        and result["warm"]["state_recovered"]
        and result["warm"]["queue_continues"]
        and not result["cold"]["state_recovered"]  # cold really is blank
        and result["statecheck_rc"] == 0
    )
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "ha_failover_blackout_s",
        "value": result["warm"]["blackout_s"],
        "unit": "s_kill_to_first_served_rpc",
        "vs_baseline": result["cold"]["blackout_s"],
        "backend": "cpu",
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


def cell_bench_main(argv: list) -> int:
    """Multi-cell control-plane bench (ISSUE 15 acceptance artifact).

    Measures CONTROL-PLANE ops/s at 1 vs N cells under an open-loop
    arrival stream (the PR-9 harness shape: arrivals never slow down
    for a struggling server — the queue just grows): real
    ``master.main --cell_id`` subprocesses over real gRPC, each with a
    PR-13 state journal, a shared registry subprocess, and ops routed
    to their node id's OWNING cell by the ``common.hashring`` ring.

    Each op is a journaled mutating RPC (``KVStoreSet``) — the class
    every rendezvous join, task grant and registry write belongs to.
    ``--floor_ms`` (default 2.0) sets
    ``DLROVER_TPU_JOURNAL_APPEND_FLOOR_MS`` in the masters: the
    modeled durable-log write latency (networked disk, the regime at
    fleet scale), serialized under the append lock — the control-plane
    analogue of the serve bench's device_round_ms.  The 1-cell row's
    ceiling is then structural (one serialized log), and the N-cell
    speedup measures real shard parallelism, not tmpfs noise; a
    ``floor_ms=0`` honesty row records the raw 1-core regime.

    A ``failover`` section (full runs only) composes with PR 13: two
    cells with warm standbys, SIGKILL one primary mid-stream, and the
    PER-CELL blackout extends HA_BENCH_CPU.json's fleet-wide metric —
    the killed cell recovers within lease+replay while the OTHER cell
    must never black out.

    Flags: ``--cells=1,2`` ``--duration_s=F`` ``--clients=N``
    ``--floor_ms=F`` ``--rate_mult=F`` (offered load as a multiple of
    the 1-cell floor ceiling) ``--lease_s=F`` ``--out=PATH`` (default
    CELL_BENCH_CPU.json) ``--smoke`` (tiny durations, no failover
    section; the tier-1 schema gate).
    """
    import os
    import queue as _queue
    import signal as _signal
    import subprocess
    import tempfile
    import threading

    from dlrover_tpu.cells.cell import cell_for_node
    from dlrover_tpu.common import messages as wire
    from dlrover_tpu.common.rpc import RpcClient
    from dlrover_tpu.master.state import read_addr

    t_start = time.perf_counter()
    opts = {"cells": "1,2", "duration_s": 6.0, "clients": 12,
            "floor_ms": 2.0, "rate_mult": 2.2, "lease_s": 0.5,
            "warmup_s": 1.0}
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
            opts.update(duration_s=1.2, warmup_s=0.4)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "CELL_BENCH_CPU.json",
        )
    cell_counts = [int(c) for c in str(opts["cells"]).split(",") if c]
    result = {
        "bench": "cell",
        "smoke": smoke,
        "opts": dict(opts),
        "rows": [],
        "note": (
            "ops/s = completed journaled mutating RPCs (KVStoreSet) "
            "under an OPEN-LOOP arrival stream offered at rate_mult x "
            "the 1-cell floor ceiling, routed to each key's owning "
            "cell by consistent hash; real master.main subprocesses "
            "over gRPC, each with its own PR-13 state journal.  "
            "floor_ms models the durable-log write latency a "
            "production control plane pays per mutation (networked "
            "disk), serialized under the append lock — the 1-cell "
            "ceiling is structural, so the N-cell speedup measures "
            "shard parallelism (the serve bench's device_round_ms "
            "precedent).  floor_ms=0 rows record the raw 1-core "
            "container regime.  failover: per-cell blackout (SIGKILL "
            "-> first successful 0.5s-budget RPC per cell) extending "
            "HA_BENCH_CPU.json's fleet-wide metric."
        ),
    }

    def flush():
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        os.replace(tmp, out_path)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DLROVER_TPU_FAULTS", None)

    def wait_port(port_file, proc, name):
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                with open(port_file) as f:
                    content = f.read().strip()
                if content:
                    return f"127.0.0.1:{content}"
            except OSError:
                pass
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{name} exited early rc={proc.returncode}"
                )
            time.sleep(0.05)
        raise TimeoutError(f"{name} never reported a port")

    def spawn_registry(workdir):
        port_file = os.path.join(workdir, "registry.port")
        log = open(os.path.join(workdir, "registry.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.cells.main",
             "--registry", "--port", "0", "--port_file", port_file],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )
        return proc, wait_port(port_file, proc, "registry")

    def spawn_cell(workdir, cid, reg_addr, floor_ms, standby_of="",
                   state_dir="", tag=""):
        tag = tag or cid
        port_file = os.path.join(workdir, f"{tag}.port")
        cmd = [sys.executable, "-m", "dlrover_tpu.master.main",
               "--port=0", f"--port_file={port_file}",
               "--job_name=cell-bench",
               f"--cell_id={cid}", f"--cell_registry={reg_addr}",
               "--min_nodes=1", "--max_nodes=8"]
        state_dir = state_dir or os.path.join(workdir, f"state_{cid}")
        cmd += [f"--state_dir={state_dir}"]
        if standby_of:
            cmd += ["--standby", f"--primary_addr={standby_of}"]
        senv = dict(env,
                    DLROVER_TPU_JOURNAL_APPEND_FLOOR_MS=str(floor_ms),
                    DLROVER_TPU_CELL_LEASE_S=str(opts["lease_s"]))
        if standby_of:
            senv["DLROVER_TPU_HA_LEASE_S"] = str(opts["lease_s"])
            senv["DLROVER_TPU_HA_TAIL_POLL_S"] = "0.05"
        log = open(os.path.join(workdir, f"{tag}.log"), "w")
        proc = subprocess.Popen(cmd, env=senv, stdout=log,
                                stderr=subprocess.STDOUT)
        return proc, wait_port(port_file, proc, tag), state_dir

    def run_row(workdir, n_cells, floor_ms, offered_rps):
        """Open-loop: an arrival thread enqueues op tokens at
        ``offered_rps`` (never waiting on completions); ``clients``
        workers drain the queue against the owning cells."""
        procs = []
        os.makedirs(workdir, exist_ok=True)
        try:
            reg_proc, reg_addr = spawn_registry(workdir)
            procs.append(reg_proc)
            cids = [f"cell{i}" for i in range(n_cells)]
            addrs = {}
            for cid in cids:
                p, addr, _sd = spawn_cell(
                    workdir, cid, reg_addr, floor_ms,
                    tag=f"{cid}_f{floor_ms}",
                )
                procs.append(p)
                addrs[cid] = addr
            owner_of = {}
            clients = {}

            def client_for(tid, key):
                cid = owner_of.get(key)
                if cid is None:
                    cid = cell_for_node(key, cids)
                    owner_of[key] = cid
                cli = clients.get((tid, cid))
                if cli is None:
                    cli = RpcClient(addrs[cid], timeout=5.0)
                    clients[(tid, cid)] = cli
                return cli

            arrivals: "_queue.Queue" = _queue.Queue()
            stop = threading.Event()
            measuring = threading.Event()
            counts = {"completed": 0, "measured": 0, "errors": 0}
            cmu = threading.Lock()

            def arrival_loop():
                # Deterministic uniform arrivals at offered_rps; the
                # stream NEVER waits on the servers (open loop).
                period = 1.0 / max(1.0, offered_rps)
                i = 0
                next_t = time.monotonic()
                while not stop.is_set():
                    now = time.monotonic()
                    if now < next_t:
                        time.sleep(min(period, next_t - now))
                        continue
                    arrivals.put(i)
                    i += 1
                    next_t += period

            def worker(tid):
                while not stop.is_set():
                    try:
                        i = arrivals.get(timeout=0.1)
                    except _queue.Empty:
                        continue
                    key = i % 256
                    cli = client_for(tid, key)
                    try:
                        cli.call(
                            wire.KVStoreSet(
                                key=f"bench/n{key}",
                                value=b"x" * 64,
                            ),
                            deadline=5.0, idempotent=True,
                        )
                    except Exception:  # noqa: BLE001 - overload path
                        with cmu:
                            counts["errors"] += 1
                        continue
                    with cmu:
                        counts["completed"] += 1
                        if measuring.is_set():
                            counts["measured"] += 1

            threads = [threading.Thread(target=arrival_loop,
                                        daemon=True)]
            threads += [
                threading.Thread(target=worker, args=(t,), daemon=True)
                for t in range(int(opts["clients"]))
            ]
            for t in threads:
                t.start()
            time.sleep(opts["warmup_s"])
            measuring.set()
            t0 = time.monotonic()
            time.sleep(opts["duration_s"])
            elapsed = time.monotonic() - t0
            measuring.clear()
            stop.set()
            for t in threads:
                t.join(timeout=2.0)
            for cli in clients.values():
                cli.close()
            return {
                "cells": n_cells,
                "floor_ms": floor_ms,
                "offered_rps": round(offered_rps, 1),
                "ops_per_s": round(counts["measured"] / elapsed, 1),
                "completed": counts["completed"],
                "errors": counts["errors"],
                "clients": int(opts["clients"]),
                "duration_s": round(elapsed, 2),
            }
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def run_failover(workdir):
        """Two cells + warm standbys; SIGKILL cell0's primary
        mid-stream; per-cell blackout via 0.5s-budget probes."""
        procs = []
        try:
            reg_proc, reg_addr = spawn_registry(workdir)
            procs.append(reg_proc)
            info = {}
            for cid in ("cell0", "cell1"):
                p, addr, sd = spawn_cell(
                    workdir, cid, reg_addr, 0.0, tag=f"fo_{cid}",
                )
                procs.append(p)
                sb, sb_addr, _ = spawn_cell(
                    workdir, cid, reg_addr, 0.0, standby_of=addr,
                    state_dir=sd, tag=f"fo_{cid}_sb",
                )
                procs.append(sb)
                info[cid] = {"proc": p, "addr": addr, "state": sd}
            # Seed a marker through each cell so recovery has state to
            # prove, then kill cell0's primary.
            for cid, ent in info.items():
                cli = RpcClient(ent["addr"], timeout=5.0)
                cli.call(wire.KVStoreSet(key=f"marker/{cid}",
                                         value=b"pre-kill"),
                         deadline=5.0, idempotent=True)
                cli.close()
            time.sleep(0.3)  # standby tails reach head
            os.kill(info["cell0"]["proc"].pid, _signal.SIGKILL)
            t_kill = time.monotonic()

            def probe(cid, follow_state):
                """Seconds from the kill to the first successful RPC,
                and whether the marker survived."""
                ent = info[cid]
                while time.monotonic() - t_kill < 60:
                    addr = ent["addr"]
                    if follow_state:
                        cur = read_addr(ent["state"])
                        if cur:
                            addr = cur
                    cli = RpcClient(addr, timeout=0.5)
                    try:
                        resp = cli.call(
                            wire.KVStoreGet(key=f"marker/{cid}"),
                            timeout=0.5, retries=1, deadline=0.5,
                            idempotent=True,
                        )
                        return (time.monotonic() - t_kill,
                                bool(getattr(resp, "found", False)))
                    except Exception:  # noqa: BLE001 - still black
                        pass
                    finally:
                        cli.close()
                    time.sleep(0.02)
                raise TimeoutError(f"{cid} never answered")

            # cell1 FIRST: its gap is the headline "never blacks out"
            # number and must not include time spent waiting on cell0.
            c1_s, c1_found = probe("cell1", follow_state=False)
            c0_s, c0_found = probe("cell0", follow_state=True)
            return {
                "killed_cell_blackout_s": round(c0_s, 3),
                "killed_cell_state_recovered": c0_found,
                "surviving_cell_gap_s": round(c1_s, 3),
                "surviving_cell_state_intact": c1_found,
                "surviving_never_blacked_out": c1_s < 0.5 and c1_found,
                "lease_s": opts["lease_s"],
            }
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                p.wait()

    with tempfile.TemporaryDirectory(prefix="cell_bench_") as workdir:
        floor = float(opts["floor_ms"])
        ceiling_1cell = 1000.0 / floor if floor > 0 else 2000.0
        offered = ceiling_1cell * float(opts["rate_mult"])
        for n in cell_counts:
            row = run_row(
                os.path.join(workdir, f"r{n}"), n, floor, offered
            )
            result["rows"].append(row)
            flush()
        if not smoke:
            for n in cell_counts:
                row = run_row(
                    os.path.join(workdir, f"r{n}f0"), n, 0.0, offered
                )
                result["rows"].append(row)
                flush()
            os.makedirs(os.path.join(workdir, "fo"), exist_ok=True)
            result["failover"] = run_failover(
                os.path.join(workdir, "fo")
            )
            flush()

    floored = {
        r["cells"]: r["ops_per_s"] for r in result["rows"]
        if r["floor_ms"] == float(opts["floor_ms"])
    }
    base = floored.get(min(floored)) or 1.0
    peak_cells = max(floored)
    result["speedup"] = round(floored[peak_cells] / base, 2)
    result["complete"] = bool(
        len(floored) >= 2 and result["speedup"] >= 1.5
        and (smoke or result.get("failover", {}).get(
            "surviving_never_blacked_out"))
        and (smoke or result.get("failover", {}).get(
            "killed_cell_state_recovered"))
    )
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "cell_control_plane_ops_per_s",
        "value": floored[peak_cells],
        "unit": f"journaled_ops_per_s_at_{peak_cells}_cells",
        "vs_baseline": base,
        "speedup": result["speedup"],
        "backend": "cpu",
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


def global_bench_main(argv: list) -> int:
    """Global data-plane bench (ISSUE 17 acceptance artifact): SLO
    goodput across TWO CELLS under hot-cell Zipf skew, with the hot
    cell blacked out mid-trace.

    Rows compare STATIC cell partitioning (a request lives and dies in
    its home cell — no cross-cell anything) against the cross-cell
    data plane (``CellSpillRouter`` spillover + post-blackout chip
    moves) on the IDENTICAL seeded ``zipf_cell_trace``.  Each cell is
    one paced-pipeline gateway (the load bench's
    max(real_cpu, gw_service_us) budget) plus replicas whose stub
    decode charges ``service_ms`` per request, so a cell SATURATES at
    ``replicas / service_ms`` rps and admission pressure — the
    spillover trigger — is real.  The cross-cell hop runs the real
    router/policy/dedupe code (``gateway.handle`` → router → sibling
    ``gateway.handle``), charged against the origin pipeline's budget.

    Blackout semantics: at ``blackout_frac`` of the trace the hot
    cell answers NOTHING more (its gateway drops every message, its
    replicas stop un-drained) — in-core work is STRANDED and counted.
    In spillover mode the driver re-homes later arrivals to the
    survivor (the ``GlobalClient`` failover contract, proven
    exactly-once in the chaos e2e); ``move_delay_s`` later the dead
    cell's chips arrive at the survivor as fresh replicas — the
    capacity outcome of the drain-first ``CrossCellMover`` ladder,
    whose actuation mechanics the fleet units own.  In static mode
    those arrivals have no cell and are counted ``blackout_lost``.

    Conservation ACROSS THE HOP per row, via
    ``merge_global_snapshots`` (a forwarded request is ``submitted``
    at both ends, deduped by the sibling's ``spill_ingress`` mark):
    arrivals == submitted_unique + wire_dropped + blackout_lost +
    blackout_dropped, and accepted == completed + timeout + failed +
    stranded.

    Flags: ``--replicas=N`` (per cell) ``--service_ms=F``
    ``--gw_service_us=F`` ``--rate_mult=F`` (of total decode
    capacity) ``--zipf_a=F`` ``--duration_s=F`` ``--blackout_frac=F``
    ``--move_delay_s=F`` ``--slo_ms=F`` ``--out=PATH`` (default
    GLOBAL_BENCH_CPU.json) ``--smoke`` (blackout pair only; the
    tier-1 schema gate)."""
    import os
    import threading

    from dlrover_tpu.common import messages as wire
    from dlrover_tpu.serving import (
        Gateway,
        GatewayConfig,
        LocalKv,
        ReplicaRunner,
        ServeRegistry,
        TierReplicaLink,
        merge_snapshots,
    )
    from dlrover_tpu.serving.spillover import (
        CellSpillRouter,
        SpilloverPolicy,
        merge_global_snapshots,
    )

    t_start = time.perf_counter()
    opts = {
        "cells": 2, "replicas": 2, "service_ms": 6.0,
        "gw_service_us": 250.0, "rate_mult": 0.9, "zipf_a": 1.4,
        "duration_s": 4.0, "drain_s": 10.0, "blackout_frac": 0.5,
        "move_delay_s": 0.4, "slo_ms": 1000.0, "deadline_s": 2.0,
        "queue_cap": 48, "slots": 32, "prompt_tokens": 8, "mnt": 1,
        "poll_interval": 0.01, "seed": 0,
    }
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
            opts.update(replicas=1, service_ms=4.0, duration_s=1.2,
                        drain_s=6.0)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "GLOBAL_BENCH_CPU.json",
        )
    n_cells = int(opts["cells"])
    service_s = opts["service_ms"] / 1e3
    floor_s = opts["gw_service_us"] / 1e6
    cell_capacity = opts["replicas"] / service_s
    rate = opts["rate_mult"] * n_cells * cell_capacity
    ttft_buckets = (
        1, 2, 5, 10, 20, 35, 50, 75, 100, 150, 200, 350, 500, 750,
        1000, 1500, 2000, 3000, 5000, 10000, 30000,
    )

    result = {
        "bench": "global_serve",
        "smoke": smoke,
        "opts": dict(opts),
        "offered_rps": round(rate, 1),
        "cell_capacity_rps": round(cell_capacity, 1),
        "rows": [],
        "note": (
            "SLO goodput across 2 cells under the SAME seeded "
            "Zipf-over-cells trace (cell 0 hot): static partitioning "
            "(requests live and die in their home cell) vs the "
            "cross-cell data plane (CellSpillRouter spillover through "
            "the real gateway dispatch + post-blackout capacity moves "
            "after the drain-first ladder's move_delay_s).  Blackout "
            "rows kill the HOT cell mid-trace: its gateway answers "
            "nothing, its replicas stop un-drained, in-core work is "
            "counted stranded.  Conservation holds ACROSS the hop via "
            "merge_global_snapshots' submitted_unique dedupe."
        ),
    }

    def flush():
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        os.replace(tmp, out_path)

    class _CellTransport:
        """The inter-cell hop: serialize -> sibling gateway dispatch
        -> deserialize, on the CALLER's pipeline thread (the hop's
        cost charges the origin's budget).  ``dead`` models the
        sibling cell blacking out mid-hop."""

        def __init__(self, gw):
            self._gw = gw
            self.dead = False

        def call(self, msg, **_kw):
            if self.dead:
                raise ConnectionError("cell blacked out")
            reply = self._gw.handle(wire.deserialize(
                wire.serialize(msg)
            ))
            if reply is None:
                raise ConnectionError("cell blacked out")
            return wire.deserialize(wire.serialize(reply))

    def run_row(mode: str, blackout: bool) -> dict:
        cell_ids = [f"c{i}" for i in range(n_cells)]
        dead_cells = set()
        gws, pipes, registries = {}, {}, {}
        in_slo = {cid: 0 for cid in cell_ids}
        blackout_dropped = [0]
        runners, threads = [], []

        def connect_for(cid):
            return lambda addr: pipes[addr.split("//", 1)[1]]

        def make_handle(cid, gw):
            def handle(msg):
                if cid in dead_cells:
                    # A dead cell answers NOTHING — casts already on
                    # the wire at blackout are dropped, not admitted.
                    if isinstance(msg, wire.ServeSubmit):
                        blackout_dropped[0] += 1
                    return None
                return gw.handle(msg)
            return handle

        def start_replica(cid, rid):
            link = TierReplicaLink(registries[cid], rid,
                                   connect=connect_for(cid),
                                   refresh_s=1.0)
            runner = ReplicaRunner(
                _StubDecodeServer(opts["slots"], opts["mnt"],
                                  service_s=service_s),
                link, rid, poll_interval=opts["poll_interval"],
                kv_p2p=False,
            )
            th = threading.Thread(target=runner.run, daemon=True)
            th.start()
            runners.append((cid, runner))
            threads.append(th)

        for cid in cell_ids:
            registries[cid] = ServeRegistry(LocalKv(),
                                            job=f"gbl-{cid}",
                                            lease_s=3600.0)
            gw = Gateway(
                port=0,
                config=GatewayConfig(
                    queue_cap=opts["queue_cap"],
                    default_deadline_s=opts["deadline_s"],
                ),
                histogram_buckets=ttft_buckets,
            )
            orig_lat = gw.core.observe_latency_ms

            def lat_obs(v, _o=orig_lat, _c=cid):
                _o(v)
                if v <= opts["slo_ms"]:
                    in_slo[_c] += 1

            gw.core.observe_latency_ms = lat_obs
            gws[cid] = gw
            cap = max(64, int(1.0 / floor_s))
            pipes[cid] = _PacedPipeline(make_handle(cid, gw),
                                        floor_s, cap)
            registries[cid].announce_gateway(f"{cid}-g0",
                                             f"pipe://{cid}")
            for i in range(opts["replicas"]):
                start_replica(cid, f"{cid}-r{i}")

        transports = {cid: _CellTransport(gws[cid])
                      for cid in cell_ids}
        if mode == "spillover":
            for cid in cell_ids:
                sibs = {c: transports[c] for c in cell_ids
                        if c != cid}

                def view(_sibs=sibs):
                    return {
                        c: dict(gws[c].core.pressure(),
                                alive=c not in dead_cells)
                        for c in _sibs
                    }

                gws[cid].spill_router = CellSpillRouter(
                    cid, gws[cid].core, sibs,
                    policy=SpilloverPolicy(), view_fn=view,
                )

        times, homes = zipf_cell_trace(
            rate, opts["duration_s"], n_cells, opts["zipf_a"],
            opts["seed"],
        )
        hot = cell_ids[0]
        blackout_at = (opts["duration_s"] * opts["blackout_frac"]
                       if blackout else float("inf"))
        move_at = blackout_at + opts["move_delay_s"]
        moved = 0
        blackout_lost = 0
        prompt = list(range(1, opts["prompt_tokens"] + 1))
        t0 = time.perf_counter()
        try:
            for i, at in enumerate(times):
                now = time.perf_counter() - t0
                if now < at:
                    time.sleep(at - now)
                if at >= blackout_at and hot not in dead_cells:
                    # The whole hot cell goes dark as ONE event: the
                    # gateway answers nothing, the sibling's transport
                    # to it fails, replicas stop with work abandoned.
                    dead_cells.add(hot)
                    transports[hot].dead = True
                    for cid_r, runner in runners:
                        if cid_r == hot:
                            runner._stopped = True  # noqa: SLF001
                            runner.server._pending.clear()  # noqa: SLF001
                if (mode == "spillover" and blackout and moved == 0
                        and at >= move_at):
                    # The dead cell's chips land at the survivor — the
                    # capacity outcome of the drain-first cross-cell
                    # move ladder (fleet units own its mechanics).
                    survivor = next(c for c in cell_ids
                                    if c not in dead_cells)
                    for j in range(opts["replicas"]):
                        start_replica(survivor, f"moved-r{j}")
                        moved += 1
                cid = cell_ids[homes[i]]
                if cid in dead_cells:
                    if mode == "static":
                        blackout_lost += 1
                        continue
                    cid = next(c for c in cell_ids
                               if c not in dead_cells)
                msg = wire.ServeSubmit(
                    req_id=f"{mode[0]}{int(blackout)}-{i}",
                    prompt=prompt, max_new_tokens=opts["mnt"],
                    deadline_s=opts["deadline_s"],
                )
                pipes[cid].cast(wire.serialize(msg))
            drain_end = time.monotonic() + opts["drain_s"]
            while time.monotonic() < drain_end:
                live = [c for c in cell_ids if c not in dead_cells]
                if all(pipes[c].q.empty() for c in live) and all(
                    gws[c].core.stats_snapshot()["in_flight"] == 0
                    for c in live
                ):
                    break
                time.sleep(0.05)
            elapsed = time.perf_counter() - t0
            merged = merge_global_snapshots({
                cid: merge_snapshots([gws[cid].core.stats_snapshot()])
                for cid in cell_ids
            })
            counters = merged["counters"]
            stranded = merged["in_flight"]
            slo_total = sum(in_slo.values())
            arrivals = len(times)
            row = {
                "mode": mode,
                "blackout": blackout,
                "offered_rps": round(rate, 1),
                "arrivals": arrivals,
                "hot_share": round(
                    homes.count(0) / max(arrivals, 1), 3
                ),
                "blackout_lost": blackout_lost,
                "blackout_dropped": blackout_dropped[0],
                "wire_dropped": sum(p.wire_dropped
                                    for p in pipes.values()),
                "submitted_unique": merged["submitted_unique"],
                "spill_forwarded": merged["spill_forwarded"],
                "spill_ingress": merged["spill_ingress"],
                "spill_rebuffed": merged["spill_rebuffed"],
                "spill_adopted": merged["spill_adopted"],
                "accepted": counters.get("accepted", 0),
                "rejected": counters.get("rejected", 0),
                "completed": counters.get("completed", 0),
                "timeout": counters.get("timeout", 0),
                "failed": counters.get("failed", 0),
                "stranded": stranded,
                "completed_in_slo": slo_total,
                "goodput_rps": round(slo_total / max(elapsed, 1e-9),
                                     1),
                "moved_replicas": moved,
                "elapsed_s": round(elapsed, 2),
                "cells": {
                    c: dict(
                        in_flight=snap["in_flight"],
                        replicas_alive=snap["replicas_alive"],
                        **{k: snap["counters"].get(k, 0)
                           for k in ("submitted", "accepted",
                                     "rejected", "completed",
                                     "timeout", "failed",
                                     "spill_forwarded",
                                     "spill_ingress",
                                     "spill_rebuffed",
                                     "spill_adopted")},
                    )
                    for c, snap in merged["cells"].items()
                },
            }
            row["conservation_ok"] = (
                arrivals == row["submitted_unique"]
                + row["wire_dropped"] + row["blackout_lost"]
                + row["blackout_dropped"]
                and row["accepted"] == row["completed"]
                + row["timeout"] + row["failed"] + row["stranded"]
            )
            return row
        finally:
            dead_cells.update(cell_ids)  # handles answer nothing more
            for _cid, runner in runners:
                runner._stopped = True  # noqa: SLF001
            for th in threads:
                th.join(timeout=15)
            for pipe in pipes.values():
                pipe.stop()

    modes = ["static", "spillover"]
    shapes = [True] if smoke else [False, True]
    rows = {}
    for blackout in shapes:
        for mode in modes:
            row = run_row(mode, blackout)
            rows[(mode, blackout)] = row
            result["rows"].append(row)
            flush()
            print(f"global row: {row}", file=sys.stderr)

    spill_bo = rows[("spillover", True)]
    static_bo = rows[("static", True)]
    result["verdicts"] = {
        "spillover_beats_static_blackout":
            spill_bo["goodput_rps"] > static_bo["goodput_rps"],
        "hop_conserved": all(r["conservation_ok"]
                             for r in result["rows"]),
        "spill_forwarded_nonzero": spill_bo["spill_forwarded"] > 0,
    }
    if not smoke:
        result["verdicts"]["spillover_beats_static_skew"] = (
            rows[("spillover", False)]["goodput_rps"]
            > rows[("static", False)]["goodput_rps"]
        )
    result["blackout_goodput_speedup_x"] = round(
        spill_bo["goodput_rps"] / max(static_bo["goodput_rps"], 1e-9),
        2,
    )
    result["complete"] = all(result["verdicts"].values())
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "global_slo_goodput_under_blackout",
        "value": spill_bo["goodput_rps"],
        "unit": "slo_goodput_rps_hot_zipf_one_cell_killed",
        "vs_baseline": static_bo["goodput_rps"],
        "speedup": result["blackout_goodput_speedup_x"],
        "backend": "cpu",
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


def sim_bench_main(argv: list) -> int:
    """Wind-tunnel bench (ROADMAP item 7 acceptance artifact): the
    deterministic fleet simulator, in two halves.

    **Fidelity** — the sim must EARN the right to extrapolate: the
    micro rig replays the committed ``GLOBAL_BENCH_CPU.json`` rows
    (identical seeded ``zipf_cell_trace``, identical opts, real
    ``GatewayCore``/``CellSpillRouter`` objects, virtual time) and the
    control-plane rig replays ``CELL_BENCH_CPU.json``'s row grid (real
    ``cell_for_node`` routing).  Each rig carries ONE calibrated
    overhead constant fitted to ONE committed row; every other row is
    a prediction and must land within the stated tolerance
    (``tolerance_global``/``tolerance_cell``).

    **Storm** — the run no real bench could stage: 10,000 nodes, 24
    cells, a day-long diurnal Zipf trace (~86M requests) with a
    correlated two-cell blackout at the diurnal peak, a gray-network
    window and a churn wave — static partitioning vs the global data
    plane (ring re-home + spillover + chip borrows + federation
    moves), all REAL policy objects.  Both modes run the IDENTICAL
    trace; the global mode runs TWICE and the double-run law (same
    seed + trace => byte-identical event log) is asserted on the
    sha256 of the per-step event log.

    Flags: ``--seed=N`` ``--overhead_ms=F`` (micro-rig calibration)
    ``--cell_overhead_ms=F`` (cell-rig calibration) ``--out=PATH``
    (default SIM_BENCH.json) ``--smoke`` (scaled storm, sub-5s; the
    tier-1 schema gate)."""
    import logging
    import os

    from dlrover_tpu.sim import (
        FleetStormSim,
        OfflineTierSim,
        StormSpec,
        TraceConfig,
        run_cell_rows,
        run_global_rows,
    )

    logging.getLogger("dlrover_tpu").setLevel(logging.WARNING)
    t_start = time.perf_counter()
    opts = {
        "seed": 0,
        #: Micro-rig calibration: completion-RPC turnaround + host
        #: scheduling per decode round, fitted to the committed
        #: static/no-blackout row.
        "overhead_ms": 0.8,
        #: Cell-rig calibration: per-op request-path cost around the
        #: durable-log floor, fitted to the committed 1-cell floored
        #: row (1000/218.6 - floor_ms).
        "cell_overhead_ms": 1.575,
        "tolerance_global": 0.05,
        "tolerance_cell": 0.15,
        "fed_every": 10,
        #: Offline-tier chunk submissions per step, in units of the
        #: fleet's block count: deep enough that the tier's sizing is
        #: SUPPLY-bound all day (a drained batch queue would shrink
        #: the lendable pool below the baseline's and turn an idle
        #: queue into an online regression).
        "offline_submit_factor": 3.0,
    }
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    here = os.path.dirname(os.path.abspath(__file__))
    if out_path is None:
        out_path = os.path.join(here, "SIM_BENCH.json")

    result = {
        "bench": "sim",
        "smoke": smoke,
        "opts": dict(opts),
        "fidelity_global": {"rows": []},
        "fidelity_cell": {"rows": []},
        "storm": {},
        "note": (
            "Wind tunnel (ROADMAP 7).  Fidelity: the micro rig "
            "replays the committed GLOBAL_BENCH_CPU.json rows (real "
            "GatewayCore/CellSpillRouter over the identical seeded "
            "zipf_cell_trace, virtual time) and the cell rig replays "
            "CELL_BENCH_CPU.json's grid (real cell_for_node "
            "routing); one calibrated overhead constant per rig, "
            "fitted to one committed row each, every other row a "
            "prediction gated by the stated tolerance.  Storm: 10k "
            "nodes / 24 cells / a diurnal day (~86M requests) with a "
            "correlated 2-hot-cell blackout at peak, a gray-network "
            "window (delay+duplicate, receiver dedupes) and a churn "
            "wave — static partitioning vs the global data plane "
            "(ring re-home + SpilloverPolicy + ChipBorrowArbiter + "
            "place_roles/plan_moves/CrossCellMover), identical "
            "trace; the global mode runs twice and the event-log "
            "sha256 must be byte-identical (the double-run law)."
        ),
    }

    def flush():
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        os.replace(tmp, out_path)

    # -- fidelity vs the committed global bench -----------------------------
    with open(os.path.join(here, "GLOBAL_BENCH_CPU.json")) as f:
        gref = json.load(f)
    gopts = dict(gref["opts"])
    rate = (gopts["rate_mult"] * gopts["cells"]
            * gopts["replicas"] / (gopts["service_ms"] / 1e3))
    times, homes = zipf_cell_trace(
        rate, gopts["duration_s"], int(gopts["cells"]),
        gopts["zipf_a"], int(gopts["seed"]),
    )
    shapes = [True] if gref.get("smoke") else [False, True]
    sim_rows = run_global_rows(gopts, times, homes,
                               overhead_ms=opts["overhead_ms"],
                               shapes=shapes)
    ref_by = {(r["mode"], r["blackout"]): r for r in gref["rows"]}
    g_ok = True
    for srow in sim_rows:
        ref = ref_by[(srow["mode"], srow["blackout"])]
        err = abs(srow["goodput_rps"] - ref["goodput_rps"]) / max(
            ref["goodput_rps"], 1e-9)
        within = err <= opts["tolerance_global"]
        g_ok = g_ok and within and srow["conservation_ok"]
        result["fidelity_global"]["rows"].append({
            "mode": srow["mode"], "blackout": srow["blackout"],
            "goodput_ref": ref["goodput_rps"],
            "goodput_sim": srow["goodput_rps"],
            "err": round(err, 4), "within_tolerance": within,
            "blackout_lost_ref": ref["blackout_lost"],
            "blackout_lost_sim": srow["blackout_lost"],
            "stranded_ref": ref["stranded"],
            "stranded_sim": srow["stranded"],
            "spill_forwarded_ref": ref["spill_forwarded"],
            "spill_forwarded_sim": srow["spill_forwarded"],
            "conservation_ok": srow["conservation_ok"],
        })
    result["fidelity_global"]["tolerance"] = opts["tolerance_global"]
    result["fidelity_global"]["ok"] = g_ok
    flush()

    # -- fidelity vs the committed cell bench -------------------------------
    with open(os.path.join(here, "CELL_BENCH_CPU.json")) as f:
        cref = json.load(f)
    copts = dict(cref["opts"])
    cell_counts = [int(c) for c in str(copts["cells"]).split(",")]
    crows = run_cell_rows(
        cell_counts, float(copts["floor_ms"]),
        float(copts["rate_mult"]), int(copts["clients"]),
        float(copts["duration_s"]), float(copts["warmup_s"]),
        overhead_ms=opts["cell_overhead_ms"],
    )
    cref_by = {(r["cells"], r["floor_ms"]): r for r in cref["rows"]}
    c_ok = True
    for srow in crows:
        ref = cref_by[(srow["cells"], srow["floor_ms"])]
        err = abs(srow["ops_per_s"] - ref["ops_per_s"]) / max(
            ref["ops_per_s"], 1e-9)
        within = err <= opts["tolerance_cell"]
        c_ok = c_ok and within
        result["fidelity_cell"]["rows"].append({
            "cells": srow["cells"], "floor_ms": srow["floor_ms"],
            "ops_ref": ref["ops_per_s"], "ops_sim": srow["ops_per_s"],
            "err": round(err, 4), "within_tolerance": within,
        })
    result["fidelity_cell"]["tolerance"] = opts["tolerance_cell"]
    result["fidelity_cell"]["ok"] = c_ok
    flush()

    # -- the storm ----------------------------------------------------------
    if smoke:
        trace_cfg = TraceConfig(
            seed=int(opts["seed"]), n_cells=8, nodes=2000,
            duration_s=3600.0, step_s=30.0, base_rps=300.0,
            diurnal_amp=0.6, diurnal_period_s=3600.0, zipf_a=0.6,
            storms=(
                StormSpec(kind="blackout", at_s=1500.0,
                          duration_s=600.0, cells=(0, 1)),
                StormSpec(kind="net_gray", at_s=2250.0,
                          duration_s=300.0, cells=(0,),
                          severity=0.05, delay_steps=2),
                StormSpec(kind="churn", at_s=2700.0,
                          duration_s=300.0, cells=(2, 3),
                          severity=0.3),
            ),
        )
    else:
        # The full day: blackout the TWO hottest cells for two hours
        # at the diurnal peak, a gray-network hour on the hot cell
        # during recovery, a churn wave in the evening.
        trace_cfg = TraceConfig(
            seed=int(opts["seed"]), n_cells=24, nodes=10000,
            duration_s=86400.0, step_s=30.0, base_rps=1000.0,
            diurnal_amp=0.6, diurnal_period_s=86400.0, zipf_a=0.6,
            storms=(
                StormSpec(kind="blackout", at_s=36000.0,
                          duration_s=7200.0, cells=(0, 1)),
                StormSpec(kind="net_gray", at_s=50400.0,
                          duration_s=3600.0, cells=(0,),
                          severity=0.05, delay_steps=2),
                StormSpec(kind="churn", at_s=64800.0,
                          duration_s=1800.0, cells=(2, 3),
                          severity=0.3),
            ),
        )

    storm_rows = {}
    walls = {}
    for mode in ("static", "global"):
        t0 = time.perf_counter()
        storm_rows[mode] = FleetStormSim(
            trace_cfg, mode=mode, fed_every=int(opts["fed_every"]),
        ).run()
        walls[mode] = round(time.perf_counter() - t0, 1)
        result["storm"][mode] = storm_rows[mode]
        result["storm"][mode]["wall_s"] = walls[mode]
        flush()
        print(f"sim storm [{mode}]: wall {walls[mode]}s "
              f"slo_goodput {storm_rows[mode]['slo_goodput']} "
              f"storm_goodput {storm_rows[mode]['storm_goodput']}",
              file=sys.stderr)
    t0 = time.perf_counter()
    rerun = FleetStormSim(
        trace_cfg, mode="global", fed_every=int(opts["fed_every"]),
    ).run()
    walls["global_rerun"] = round(time.perf_counter() - t0, 1)
    result["storm"]["double_run_identical"] = (
        rerun["event_log_sha256"]
        == storm_rows["global"]["event_log_sha256"]
    )
    result["storm"]["wall_s"] = walls

    # -- the offline tier over the same storm trace (ISSUE 20) --------------
    # Baseline (trough chips idle) vs the preemptible tier (trough
    # chips run batch chunks), identical online plant: the acceptance
    # row for priority classes at 10k-node scale.
    result["offline_tier"] = {}
    off_rows = {}
    off_walls = {}
    for mode in ("baseline", "offline"):
        t0 = time.perf_counter()
        off_rows[mode] = OfflineTierSim(
            trace_cfg, mode=mode,
            submit_factor=float(opts["offline_submit_factor"]),
        ).run()
        off_walls[mode] = round(time.perf_counter() - t0, 1)
        result["offline_tier"][mode] = off_rows[mode]
        result["offline_tier"][mode]["wall_s"] = off_walls[mode]
        flush()
        print(f"sim offline [{mode}]: wall {off_walls[mode]}s "
              f"slo_goodput {off_rows[mode]['slo_goodput']} "
              f"utilization {off_rows[mode]['utilization']}",
              file=sys.stderr)
    t0 = time.perf_counter()
    off_rerun = OfflineTierSim(
        trace_cfg, mode="offline",
        submit_factor=float(opts["offline_submit_factor"]),
    ).run()
    off_walls["offline_rerun"] = round(time.perf_counter() - t0, 1)
    result["offline_tier"]["double_run_identical"] = (
        off_rerun["event_log_sha256"]
        == off_rows["offline"]["event_log_sha256"]
    )
    result["offline_tier"]["wall_s"] = off_walls

    g, s = storm_rows["global"], storm_rows["static"]
    result["verdicts"] = {
        "fidelity_global_ok": bool(result["fidelity_global"]["ok"]),
        "fidelity_cell_ok": bool(result["fidelity_cell"]["ok"]),
        "storm_conserved": bool(
            g["conservation_ok"] and s["conservation_ok"]),
        "global_beats_static_storm":
            g["storm_goodput"] > s["storm_goodput"],
        "double_run_identical":
            bool(result["storm"]["double_run_identical"]),
        "spill_exercised": g["spilled"] > 0,
        "day_under_60s_wall": max(walls.values()) < 60.0,
    }
    ob, oo = off_rows["baseline"], off_rows["offline"]
    result["verdicts"].update({
        # The offline-tier laws (ISSUE 20): batch work soaks the
        # trough and a blackout evacuates the tier completely, with
        # ZERO online SLO regression (the only coupling — the
        # arbiter's cooldown exemption — can only help online).
        "offline_no_slo_regression":
            oo["slo_goodput"] >= ob["slo_goodput"],
        "offline_trough_soaked": oo["chunks_done_trough"] > 0,
        "offline_utilization_up":
            oo["utilization"] > ob["utilization"],
        "offline_blackout_evacuated": bool(oo["evacuations_ok"]),
        "offline_chunks_conserved":
            bool(oo["chunk_conservation_ok"]),
        "offline_reclaim_le_one_round":
            oo["max_reclaim_rounds"] <= 1,
        "offline_double_run_identical":
            bool(result["offline_tier"]["double_run_identical"]),
    })
    if not smoke:
        # Full-run-only verdicts: the smoke window is too short for a
        # federation move cycle, and its offered load is tiny.
        result["verdicts"]["moves_exercised"] = g["moved_blocks"] > 0
        result["verdicts"]["offered_ge_1m"] = g["offered"] >= 1_000_000
    result["storm_goodput_speedup_x"] = round(
        g["storm_goodput"] / max(s["storm_goodput"], 1e-9), 2)
    result["complete"] = all(result["verdicts"].values())
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "sim_storm_slo_goodput_10k_nodes",
        "value": g["storm_goodput"],
        "unit": "slo_goodput_frac_two_cell_blackout_at_peak",
        "vs_baseline": s["storm_goodput"],
        "speedup": result["storm_goodput_speedup_x"],
        "backend": "cpu",
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


class _ArithDecodeServer:
    """The ``DecodeServer`` incremental surface with the arithmetic
    token law (token *i* of prompt *p* is ``(sum(p) + i) % 97``) — the
    same fake the offline unit tests drive, so the bench's replay row
    can verify every journaled token EXACTLY instead of trusting
    counters."""

    def __init__(self, slots: int = 4):
        import collections

        self.slots = slots
        self._pending = collections.deque()
        self._active = {}

    def submit(self, rid, prompt, mnt, prefix_len=0, prefix_fp=""):
        self._pending.append((rid, [int(t) for t in prompt], int(mnt)))

    def abort(self, rid):
        for i, item in enumerate(self._pending):
            if item[0] == rid:
                del self._pending[i]
                return True
        return self._active.pop(rid, None) is not None

    def serve_incremental(self, tick=None, on_finish=None,
                          on_token=None, idle_wait=0.0005):
        while True:
            keep = tick() is not False if tick else True
            while self._pending and len(self._active) < self.slots:
                rid, p, mnt = self._pending.popleft()
                self._active[rid] = (p, [], mnt)
            if not self._active:
                if not self._pending and (tick is None or not keep):
                    break
                continue
            for rid in list(self._active):
                p, out, mnt = self._active[rid]
                t = (sum(p) + len(out)) % 97
                out.append(t)
                if on_token:
                    on_token(rid, t)
                if len(out) >= mnt:
                    del self._active[rid]
                    if on_finish:
                        on_finish(rid, list(p) + out)


def _offline_worker_cmd(argv: list) -> int:
    """Hidden helper behind ``--offline_worker`` (argv: ``queue_path
    worker_id``): ONE offline replay worker in its OWN process, so the
    ``serving.replica_kill`` chaos crash (``os._exit(78)``, armed via
    the ``DLROVER_TPU_FAULTS`` env) is a true process death and the
    relaunched worker's journal replay is what the bench measures."""
    from dlrover_tpu.offline import OfflineRunner, OfflineWorkQueue

    queue = OfflineWorkQueue(argv[0])
    row = OfflineRunner(_ArithDecodeServer(), queue, argv[1]).run()
    queue.close()
    print("WORKER_ROW " + json.dumps(row))
    return 0


def offline_bench_main(argv: list) -> int:
    """Offline-tier bench (ISSUE 20 acceptance artifact), three rows:

    **Tier** — :class:`OfflineTierSim` baseline (trough chips idle)
    vs offline (trough chips run batch chunks) over an identical
    diurnal storm trace: online SLO goodput must stay within
    ``goodput_noise`` of the baseline while offline throughput rides
    the trough and fleet utilization strictly rises.

    **Replay** — a REAL journaled queue + chunk runner; worker 1 is
    killed by ``serving.replica_kill`` chaos (``os._exit(78)`` mid
    chunk, a true process death), worker 2 relaunches over the same
    journal; every chunk must complete EXACTLY once and every token
    must match the arithmetic law.

    **Reclaim** — the loopback fleet plant: a real
    :class:`ChipBorrowArbiter` (lender = ``OfflineRole`` over a live
    runner mid-chunk, ``offline.chunk_kill`` chaos armed) reclaims
    the chip; the measured latency must be <= ONE decode round, with
    the wall-clock microseconds reported beside it.

    Flags: ``--out=PATH`` (default OFFLINE_BENCH_CPU.json)
    ``--smoke`` (scaled trace + replay, sub-5s; the tier-1 schema
    gate) plus ``--key=val`` for any opt below."""
    import logging
    import os
    import shutil
    import subprocess
    import tempfile
    import threading

    from dlrover_tpu import chaos
    from dlrover_tpu.fleet.policy import (
        BORROWED,
        LENDING,
        BorrowPolicy,
        ChipBorrowArbiter,
    )
    from dlrover_tpu.fleet.role import RoleAdapter, RoleSpec, RoleStatus
    from dlrover_tpu.fleet.roles import OfflineRole
    from dlrover_tpu.offline import (
        OfflinePolicy,
        OfflineRunner,
        OfflineWorkQueue,
    )
    from dlrover_tpu.sim import OfflineTierSim, StormSpec, TraceConfig

    logging.getLogger("dlrover_tpu").setLevel(logging.WARNING)
    t_start = time.perf_counter()
    opts = {
        "seed": 0,
        #: Two-sided tolerance on the baseline-vs-offline online SLO
        #: goodput delta ("unchanged within noise").
        "goodput_noise": 0.02,
        #: See sim_bench_main: keep the tier supply-bound all day.
        "submit_factor": 3.0,
        "reclaim_trials": 3,
        "replay_jobs": 3,
        "replay_prompts": 16,
        "replay_chunk": 4,
        "replay_mnt": 8,
        #: Runner tick at which chaos kills worker 1 (~3 chunks in).
        "replay_kill_step": 30,
    }
    out_path = None
    smoke = False
    for a in argv:
        if a == "--smoke":
            smoke = True
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        elif "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            if k in opts:
                opts[k] = type(opts[k])(v)
    here = os.path.dirname(os.path.abspath(__file__))
    if out_path is None:
        out_path = os.path.join(here, "OFFLINE_BENCH_CPU.json")
    if smoke:
        opts.update(replay_jobs=1, replay_prompts=8, replay_chunk=2,
                    replay_mnt=6, replay_kill_step=6,
                    reclaim_trials=1)

    result = {
        "bench": "offline",
        "smoke": smoke,
        "opts": dict(opts),
        "tier": {},
        "replay": {},
        "reclaim": {},
        "note": (
            "Priority classes (ISSUE 20).  Tier: OfflineTierSim "
            "baseline (trough chips idle) vs offline (the "
            "preemptible tier soaks them) over an identical diurnal "
            "storm trace — real OfflinePolicy + ChipBorrowArbiter "
            "decisions, integer plant, double-run byte-identical.  "
            "Replay: a real journaled OfflineWorkQueue + "
            "OfflineRunner; worker 1 dies by serving.replica_kill "
            "chaos (os._exit(78) mid-chunk), worker 2 replays the "
            "journal; every chunk exactly-once, every token checked "
            "against the arithmetic law.  Reclaim: a real arbiter "
            "with OfflineRole as lender preempts a live runner "
            "mid-chunk (offline.chunk_kill armed); decode rounds "
            "from reclaim request to chip grant must be <= 1."
        ),
    }

    def flush():
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        os.replace(tmp, out_path)

    # -- tier: baseline vs offline over the storm ---------------------------
    if smoke:
        trace_cfg = TraceConfig(
            seed=int(opts["seed"]), n_cells=4, nodes=400,
            duration_s=600.0, step_s=30.0, base_rps=120.0,
            diurnal_amp=0.4, diurnal_period_s=600.0, zipf_a=0.6,
            storms=(
                StormSpec(kind="blackout", at_s=120.0,
                          duration_s=180.0, cells=(0, 1)),
            ),
        )
    else:
        trace_cfg = TraceConfig(
            seed=int(opts["seed"]), n_cells=8, nodes=2000,
            duration_s=7200.0, step_s=30.0, base_rps=300.0,
            diurnal_amp=0.6, diurnal_period_s=7200.0, zipf_a=0.6,
            storms=(
                StormSpec(kind="blackout", at_s=1800.0,
                          duration_s=600.0, cells=(0, 1)),
                StormSpec(kind="churn", at_s=5400.0,
                          duration_s=600.0, cells=(2, 3),
                          severity=0.3),
            ),
        )
    tier_rows = {}
    for mode in ("baseline", "offline"):
        t0 = time.perf_counter()
        tier_rows[mode] = OfflineTierSim(
            trace_cfg, mode=mode,
            submit_factor=float(opts["submit_factor"]),
        ).run()
        tier_rows[mode]["wall_s"] = round(time.perf_counter() - t0, 2)
        result["tier"][mode] = tier_rows[mode]
        flush()
    rerun = OfflineTierSim(
        trace_cfg, mode="offline",
        submit_factor=float(opts["submit_factor"]),
    ).run()
    base, off = tier_rows["baseline"], tier_rows["offline"]
    result["tier"]["double_run_identical"] = (
        rerun["event_log_sha256"] == off["event_log_sha256"])
    result["tier"]["goodput_delta"] = round(
        off["slo_goodput"] - base["slo_goodput"], 4)
    result["tier"]["utilization_gain"] = round(
        off["utilization"] - base["utilization"], 4)
    flush()

    # -- replay: a chaos-killed worker loses zero work ----------------------
    tmpdir = tempfile.mkdtemp(prefix="offline_bench_")
    qpath = os.path.join(tmpdir, "queue.jsonl")
    chunk_sz = int(opts["replay_chunk"])
    mnt = int(opts["replay_mnt"])
    jobs = {}
    queue = OfflineWorkQueue(qpath, chunk_size=chunk_sz)
    total_chunks = 0
    for j in range(int(opts["replay_jobs"])):
        prompts = [
            [(j * 31 + i * 7 + k) % 97 for k in range(4)]
            for i in range(int(opts["replay_prompts"]))
        ]
        jobs[f"batch-{j}"] = prompts
        total_chunks += queue.submit(f"batch-{j}", prompts, mnt)
    queue.close()

    def run_worker(wid, fault):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        if fault:
            env[chaos.ENV_VAR] = fault
        else:
            env.pop(chaos.ENV_VAR, None)
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--offline_worker", qpath, wid],
            capture_output=True, text=True, timeout=120, cwd=here,
            env=env,
        )
        row = None
        for ln in (proc.stdout or "").splitlines():
            if ln.startswith("WORKER_ROW "):
                row = json.loads(ln[len("WORKER_ROW "):])
        return proc.returncode, row, round(
            time.perf_counter() - t0, 2)

    kill = (f"serving.replica_kill:step={int(opts['replay_kill_step'])}"
            f",seed={int(opts['seed'])}")
    rc1, row1, wall1 = run_worker("ow-victim", kill)
    rc2, row2, wall2 = run_worker("ow-survivor", None)

    verify = OfflineWorkQueue(qpath)
    final_stats = verify.stats()
    tokens_exact = True
    for job_id, prompts in sorted(jobs.items()):
        n_chunks = -(-len(prompts) // chunk_sz)
        for idx in range(n_chunks):
            got = verify.result(f"{job_id}/{idx}")
            if got is None:
                tokens_exact = False
                continue
            lo = idx * chunk_sz
            for i, p in enumerate(prompts[lo:lo + chunk_sz]):
                want = list(p) + [(sum(p) + t) % 97 for t in range(mnt)]
                if got.get(f"{job_id}/{idx}#{i}") != want:
                    tokens_exact = False
    verify.close()
    result["replay"] = {
        "chunks_total": total_chunks,
        "fault": kill,
        "victim_exit": rc1,
        "victim_row": row1,
        "victim_wall_s": wall1,
        "survivor_exit": rc2,
        "survivor_row": row2,
        "survivor_wall_s": wall2,
        "final_stats": final_stats,
        "tokens_exact": tokens_exact,
    }
    flush()

    # -- reclaim: measured latency under chaos ------------------------------
    class _OnlineStub(RoleAdapter):
        def __init__(self):
            super().__init__(RoleSpec(name="online", desired=2,
                                      min_count=1, max_count=8))
            self.count = 2

        def observe(self):
            return RoleStatus(
                members=tuple(f"on{i}" for i in range(self.count)))

        def spawn(self, n):
            self.count += n
            return n

    trials = []
    for t_i in range(int(opts["reclaim_trials"])):
        q2 = OfflineWorkQueue(
            os.path.join(tmpdir, f"reclaim{t_i}.jsonl"), chunk_size=2)
        q2.submit("hold", [[1, 2], [3]], 10 ** 6)  # never finishes
        runner = OfflineRunner(_ArithDecodeServer(), q2, f"ow{t_i}",
                               stop_when_drained=False)
        workers = {runner.worker_id: runner}
        role = OfflineRole(
            RoleSpec(name="offline", desired=1, min_count=0,
                     max_count=4),
            workers_fn=lambda w=workers: w,
            spawn_fn=lambda n: n,
            queue=q2, policy=OfflinePolicy(),
        )
        online = _OnlineStub()
        arb = ChipBorrowArbiter(
            lender=role, borrower=online,
            policy=BorrowPolicy(queue_high_per_member=8.0,
                                spike_patience=1, max_borrow=1),
            signal_fn=lambda c=online: {"queue_depth": 1000,
                                        "members_alive": c.count},
        )
        chaos.configure(
            f"offline.chunk_kill:p=1,times=1,"
            f"seed={int(opts['seed']) + t_i}")
        th = threading.Thread(target=runner.run)
        th.start()
        try:
            deadline = time.monotonic() + 10.0
            while not runner.busy and time.monotonic() < deadline:
                time.sleep(0.0005)
            t0 = time.perf_counter()
            arb.step()  # spike -> begin_drain -> request_reclaim
            th.join(timeout=10.0)
            wall_us = (time.perf_counter() - t0) * 1e6
            passes = 0
            while arb.phase == LENDING and passes < 100:
                passes += 1
                arb.step()
            trials.append({
                "trial": t_i,
                "phase_after": arb.phase,
                "decode_rounds": runner.reclaim_rounds,
                "arbiter_passes": passes,
                "chunk_kills": runner.chunk_kills,
                "requeued_backlog": q2.backlog(),
                "reclaim_wall_us": round(wall_us, 1),
            })
        finally:
            chaos.reset()
            runner.request_reclaim()
            th.join(timeout=5.0)
            q2.close()
    result["reclaim"] = {
        "trials": trials,
        "max_decode_rounds": max(
            (t["decode_rounds"] or 0) for t in trials),
        "max_arbiter_passes": max(
            t["arbiter_passes"] for t in trials),
    }
    shutil.rmtree(tmpdir, ignore_errors=True)

    result["verdicts"] = {
        "slo_goodput_within_noise":
            abs(off["slo_goodput"] - base["slo_goodput"])
            <= float(opts["goodput_noise"]),
        "offline_throughput_through_trough":
            off["chunks_done_trough"] > 0,
        "utilization_strictly_higher":
            off["utilization"] > base["utilization"],
        "chunks_conserved": bool(off["chunk_conservation_ok"]),
        "blackout_evacuation_total": bool(off["evacuations_ok"]),
        "no_overcommit": off["overcommit_steps"] == 0,
        "sim_reclaims_exercised": off["reclaims"] > 0,
        "sim_reclaim_le_one_round": off["max_reclaim_rounds"] <= 1,
        "tier_double_run_identical":
            bool(result["tier"]["double_run_identical"]),
        "replay_victim_died_by_chaos": rc1 == 78,
        "replay_survivor_clean_exit": rc2 == 0,
        "replay_survivor_did_work": bool(
            row2 and row2["chunks_done"] > 0),
        "replay_exactly_once": (
            final_stats["done"] == total_chunks
            and final_stats["pending"] == 0
            and final_stats["leased"] == 0
            and tokens_exact),
        "reclaim_le_one_decode_round": all(
            t["decode_rounds"] is not None
            and t["decode_rounds"] <= 1
            and t["phase_after"] == BORROWED
            for t in trials),
    }
    result["complete"] = all(result["verdicts"].values())
    result["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    flush()
    print(json.dumps({
        "metric": "offline_tier_fleet_utilization",
        "value": off["utilization"],
        "unit": "mean_chip_utilization_frac_diurnal_storm",
        "vs_baseline": base["utilization"],
        "speedup": round(
            off["utilization"] / max(base["utilization"], 1e-9), 2),
        "backend": "cpu",
        "artifact": out_path,
    }))
    return 0 if result["complete"] else 1


#: Subcommand table: every bench registers here (satellite of ISSUE 5 —
#: the tail-of-file if-chain made each new bench a copy-paste edit).
SUBCOMMANDS = {
    "--goodput": goodput_main,
    "--spec_bench": spec_bench_main,
    "--ckpt_bench": ckpt_bench_main,
    "--serve_bench": serve_bench_main,
    "--load_bench": load_bench_main,
    "--reshard_bench": reshard_bench_main,
    "--fleet_bench": fleet_bench_main,
    "--ha_bench": ha_bench_main,
    "--cell_bench": cell_bench_main,
    "--global_bench": global_bench_main,
    "--sim_bench": sim_bench_main,
    "--offline_bench": offline_bench_main,
    "--offline_worker": _offline_worker_cmd,
}


def dispatch(argv: list) -> int:
    if argv and argv[0] in SUBCOMMANDS:
        return SUBCOMMANDS[argv[0]](argv[1:])
    return main()


if __name__ == "__main__":
    sys.exit(dispatch(sys.argv[1:]))
