"""Serving example: continuous-batching decode over a Llama model.

The serving half the reference delegates to vllm
(``atorch/rl/model_engine/model_engine.py:35``), as a runnable surface:

    python examples/llama_serve.py --requests 6 --max_new_tokens 24
    python examples/llama_serve.py --quant_kv          # int8 kv cache
    python examples/llama_serve.py --speculative       # draft + verify
    python examples/llama_serve.py --tp 4              # TP over a mesh

With ``--hf_dir`` the model comes from a HuggingFace checkpoint via the
streaming importer (``models/hf_convert.py``); otherwise a small random
model demonstrates the machinery.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf_dir", default="",
                    help="HF checkpoint dir (streaming import)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new_tokens", type=int, default=24)
    ap.add_argument("--quant_kv", action="store_true",
                    help="int8 kv cache (half the decode HBM traffic)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-model speculative decode (one batched "
                         "call over all requests)")
    ap.add_argument("--spec_server", action="store_true",
                    help="speculative rounds INSIDE the continuous-"
                         "batching server (slot admission + per-slot "
                         "acceptance)")
    ap.add_argument("--draft_layers", type=int, default=1)
    ap.add_argument("--adapt_k", action="store_true",
                    help="(--spec_server) shrink/regrow the draft "
                         "window from measured acceptance")
    ap.add_argument("--decode_chunk", type=int, default=1,
                    help="tokens per dispatch in plain serving (K x "
                         "fewer device round-trips; ~9x tokens/s at "
                         "K=16 on the CPU host-loop bound)")
    ap.add_argument("--stream", action="store_true",
                    help="print request 0's tokens as they decode "
                         "(the vllm-streaming role of serve's "
                         "on_token hook)")
    ap.add_argument("--prefix_len", type=int, default=0,
                    help="share a random system prefix of N tokens "
                         "across all requests via prefix caching "
                         "(prefills once; admissions copy kv rows — "
                         "vllm's automatic-prefix-caching role)")
    ap.add_argument("--tp", type=int, default=0,
                    help="shard params over an N-way 'tp' mesh")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from dlrover_tpu.common.jax_env import enable_compilation_cache

    enable_compilation_cache()
    import numpy as np

    import jax

    from dlrover_tpu.models import llama, llama_infer

    try:
        from examples import serve_common
    except ImportError:  # run as a script: examples/ is sys.path[0]
        import serve_common

    if args.hf_dir:
        from dlrover_tpu.models import hf_convert

        params, cfg = hf_convert.from_hf_llama_dir(args.hf_dir)
    else:
        params, cfg = serve_common.tiny_llama(seed=args.seed)

    if args.stream and args.speculative:
        raise SystemExit(
            "--stream requires a server mode (it rides "
            "DecodeServer.serve's on_token hook); the one-shot "
            "--speculative batched call has no streaming surface"
        )
    if args.tp > 0:
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < args.tp:
            raise SystemExit(
                f"--tp {args.tp} needs {args.tp} devices, "
                f"have {len(devs)}"
            )
        mesh = Mesh(np.array(devs[: args.tp]), ("tp",))
        params, _ = llama_infer.shard_params_for_decode(
            params, cfg, mesh
        )
    prompts, rng = serve_common.seeded_requests(
        cfg, args.requests, args.seed
    )

    t0 = time.perf_counter()
    if args.speculative:
        import jax.numpy as jnp

        dcfg = llama.LlamaConfig.tiny(n_layer=args.draft_layers)
        if args.hf_dir:
            # A real deployment would load a small checkpoint here; the
            # example drafts with a random model (acceptance suffers —
            # but the output law is still exactly the target model's
            # greedy/sampled decode; a bad draft only costs speed).
            dcfg = llama.LlamaConfig(**{
                **cfg.__dict__, "n_layer": args.draft_layers
            })
        draft = llama.init_params(jax.random.PRNGKey(7), dcfg)
        # ONE batched call decodes the whole ragged request set: every
        # row drafts k proposals, a single chunked ragged verify scores
        # them all, acceptance is per-row.
        lens = np.asarray([len(p) for p in prompts], np.int32)
        P = int(lens.max())
        padded = np.zeros((len(prompts), P), np.int32)
        for b, p in enumerate(prompts):
            padded[b, : len(p)] = p
        stats: dict = {}
        out, out_lens = llama_infer.generate_speculative_batched(
            params, cfg, draft, dcfg, jnp.asarray(padded),
            jnp.asarray(lens),
            max_new_tokens=args.max_new_tokens,
            quant_kv=args.quant_kv, stats=stats,
            temperature=args.temperature,
            rng=jax.random.PRNGKey(args.seed),
        )
        outs = [
            np.asarray(out[b, : int(out_lens[b])])
            for b in range(len(prompts))
        ]
        mode = (f"speculative(batched) k=4 tokens/round="
                f"{stats.get('tokens_per_round', 0):.2f}")
    else:
        draft_kw = {}
        mode = (f"continuous-batching slots={args.slots}"
                + (f" decode_chunk={args.decode_chunk}"
                   if args.decode_chunk > 1 else ""))
        if args.spec_server:
            dcfg = llama.LlamaConfig.tiny(n_layer=args.draft_layers)
            draft_kw = {
                "draft": (
                    llama.init_params(jax.random.PRNGKey(7), dcfg),
                    dcfg,
                ),
                "draft_k": 4,
                "adapt_k": args.adapt_k,
            }
            mode = (f"continuous-batching+speculative "
                    f"slots={args.slots} k=4"
                    + (" adapt_k" if args.adapt_k else ""))
        srv = llama_infer.DecodeServer(
            params, cfg, slots=args.slots,
            # + chunk headroom (serve()'s capacity check counts the up
            # to K-1 writes a mid-chunk finish leaves behind) + the
            # shared prefix every request's cache rows now hold.
            max_len=max(64, args.max_new_tokens + 24)
            + max(0, args.decode_chunk - 1) + args.prefix_len,
            temperature=args.temperature, seed=args.seed,
            quant_kv=args.quant_kv, decode_chunk=args.decode_chunk,
            **draft_kw,
        )
        on_token = None
        if args.stream:
            def on_token(rid, tok):
                if rid == 0:
                    print(f"STREAM r0 +{tok}", flush=True)
        shared_prefix = None
        if args.prefix_len > 0:
            shared_prefix = rng.randint(
                1, cfg.vocab_size, size=(args.prefix_len,)
            ).astype(np.int32)
            mode += f" prefix_cached={args.prefix_len}"
        outs = srv.serve(prompts, max_new_tokens=args.max_new_tokens,
                         on_token=on_token,
                         shared_prefix=shared_prefix)
        if srv.last_stats:
            # Every path reports tokens_per_round; k_final is
            # speculative-only (plain/chunk report path+emitted).
            st = srv.last_stats
            mode += f" tokens/round={st['tokens_per_round']:.2f}"
            if "k_final" in st:
                mode += f" k_final={st['k_final']}"
    dt = time.perf_counter() - t0
    total_new = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    for i, o in enumerate(outs[:3]):
        print(f"request {i}: {len(o)} tokens -> {o[:12].tolist()}...")
    print(
        f"SERVE_DONE requests={len(outs)} mode='{mode}' "
        f"quant_kv={args.quant_kv} new_tokens={total_new} "
        f"tokens_per_sec={total_new / dt:.1f}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
