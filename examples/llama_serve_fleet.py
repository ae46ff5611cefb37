"""Elastic serving fleet example: N drain-aware replicas behind one
gateway (ISSUE 5) — the multi-replica generalization of
``llama_serve_elastic.py``.

Single-process demo (gateway + replicas as threads, loopback driver)::

    python examples/llama_serve_fleet.py --replicas 2 --requests 12

Process-per-role (what the chaos e2e composes; each role is also how a supervised deployment runs under the
elastic agent)::

    python examples/llama_serve_fleet.py --role gateway --port 8710
    python examples/llama_serve_fleet.py --role replica \
        --gateway 127.0.0.1:8710 --replica_id r0 --journal_dir /tmp/j
    python examples/llama_serve_fleet.py --role driver \
        --gateway 127.0.0.1:8710 --requests 12 --rps 20

Sharded tier (ISSUE 9): point every role at a shared registry instead
of one gateway — gateways announce themselves and own a hash range,
replicas poll every live gateway, the driver consistent-hashes request
ids to their owner and rides out gateway deaths by resubmitting::

    python examples/llama_serve_fleet.py --role gateway \
        --registry 127.0.0.1:8700 --gateway_id g0     # and g1, ...
    python examples/llama_serve_fleet.py --role replica \
        --registry 127.0.0.1:8700 --replica_id r0 --journal_dir /tmp/j
    python examples/llama_serve_fleet.py --role driver \
        --registry 127.0.0.1:8700 --requests 12 --rps 20

Every replica rebuilds the SAME seeded float32 tiny-llama
(``serve_common``), so greedy decode is byte-identical across replicas
— a re-dispatched request completes with exactly the tokens its first
assignment would have produced, and journal replay after a kill agrees
with a fresh decode.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--role", default="all",
                   choices=("all", "gateway", "replica", "driver",
                            "draft"))
    p.add_argument("--port", type=int, default=0,
                   help="(gateway) listen port; 0 = ephemeral")
    p.add_argument("--gateway", default="",
                   help="(replica/driver) gateway host:port "
                        "(single-gateway mode)")
    p.add_argument("--registry", default="",
                   help="shared registry host:port (a "
                        "serving.RegistryServer or a master's KV): "
                        "switches every role to the SHARDED TIER — "
                        "gateways announce themselves, replicas poll "
                        "every live gateway, drivers consistent-hash "
                        "requests to their owner (ISSUE 9)")
    p.add_argument("--job", default="fleet",
                   help="(tier) registry namespace")
    p.add_argument("--gateway_id", default="g0",
                   help="(tier gateway) this gateway's id on the ring")
    p.add_argument("--metrics_port", type=int, default=-1,
                   help="(tier gateway) /metrics port (-1 = off, "
                        "0 = ephemeral): own gauges + merged tier "
                        "view + trace/flight-recorder drop counters")
    p.add_argument("--kv_relay", action="store_true",
                   help="(gateway) force the prefill->decode KV "
                        "segment through the gateway (the PR-8 relay "
                        "plane) instead of peer-to-peer tickets")
    p.add_argument("--no_kv_p2p", action="store_true",
                   help="(replica) never publish KV segments "
                        "peer-to-peer (always relay the payload)")
    p.add_argument("--replica_id", default="r0")
    p.add_argument("--replica_role", default="unified",
                   choices=("unified", "prefill", "decode"),
                   help="(replica) disaggregated role: prefill scores "
                        "prompts and exports KV segments; decode "
                        "continues from imported segments")
    p.add_argument("--quant_kv", action="store_true",
                   help="(replica) int8 KV cache — halves the "
                        "prefill->decode segment transfer")
    p.add_argument("--paged", action="store_true",
                   help="(replica) paged KV (ISSUE 19): block-pool "
                        "arena + per-request block tables; admission "
                        "by blocks actually needed, the poll reports "
                        "real memory headroom")
    p.add_argument("--block_size", type=int, default=16,
                   help="(replica) tokens per KV block under --paged")
    p.add_argument("--pool_blocks", type=int, default=0,
                   help="(replica) KV pool size in blocks under "
                        "--paged (0 = slots * max_len / block_size)")
    p.add_argument("--prefix_cache_cap", type=int, default=4,
                   help="(replica) warm prefix templates retained")
    p.add_argument("--warm_prefix_len", type=int, default=0,
                   help="(replica) pre-compile the prefix-template "
                        "path for this prefix length (the bench warms "
                        "XLA before registration so TTFT measures "
                        "admission, not compiles)")
    p.add_argument("--spec", action="store_true",
                   help="(replica) speculative serving (ISSUE 11): "
                        "advertise spec capability, attach the "
                        "gateway-announced remote draft, run draft/"
                        "verify/accept rounds with per-request "
                        "adaptive k (below break-even a stream "
                        "decodes plain)")
    p.add_argument("--draft_k", type=int, default=4,
                   help="(replica/draft) speculation width ceiling")
    p.add_argument("--spec_break_even", type=float, default=0.0,
                   help="(replica) accepted-tokens/round below which "
                        "a stream rides plain (0 = 1 + 0.6*draft_k, "
                        "the default shape)")
    p.add_argument("--spec_min_tokens", type=int, default=0,
                   help="(gateway) max_new_tokens at which the grant "
                        "scan prefers spec-capable replicas (0 = off)")
    p.add_argument("--draft_layers", type=int, default=1,
                   help="(draft) draft model depth")
    p.add_argument("--draft_seed", type=int, default=-1,
                   help="(draft) draft init seed; -1 = share the "
                        "target seed AND shape (the ceiling draft "
                        "standing in for a trained one)")
    p.add_argument("--draft_streams", type=int, default=32,
                   help="(draft) concurrent stream caches retained")
    p.add_argument("--draft_floor_ms", type=float, default=0.0,
                   help="(draft) per-roll latency floor — the draft "
                        "chip's device time in the bench's "
                        "device-bound model")
    p.add_argument("--replicas", type=int, default=2,
                   help="(all) replica threads to run")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--max_len", type=int, default=96)
    p.add_argument("--n_layer", type=int, default=2)
    p.add_argument("--d_model", type=int, default=64)
    p.add_argument("--d_ff", type=int, default=128)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--max_new_tokens", type=int, default=16)
    p.add_argument("--rps", type=float, default=50.0,
                   help="(driver) Poisson arrival rate")
    p.add_argument("--deadline_s", type=float, default=0.0)
    p.add_argument("--journal_dir", default="")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--poll_interval", type=float, default=0.02)
    p.add_argument("--round_floor_ms", type=float, default=0.0,
                   help="(replica) per-round latency floor — models "
                        "the device-bound regime on a shared-CPU host")
    p.add_argument("--queue_cap", type=int, default=256)
    p.add_argument("--lease_timeout", type=float, default=10.0,
                   help="(gateway) seconds without a poll before a "
                        "replica is presumed dead and its work "
                        "re-dispatched")
    p.add_argument("--timeout", type=float, default=120.0)
    return p.parse_args(argv)


def build_replica(args, transport, draft_connect=None):
    """One seeded replica: tiny float32 llama + DecodeServer +
    ReplicaRunner (all replicas identical by construction).
    ``draft_connect`` overrides the remote-draft handle factory
    (in-process fleets: the bench smoke wires a loopback draft)."""
    import os

    import jax.numpy as jnp

    from dlrover_tpu.models import llama_infer
    from dlrover_tpu.serving import ReplicaRunner

    try:
        from examples import serve_common
    except ImportError:  # run as a script
        import serve_common

    params, cfg = serve_common.tiny_llama(
        seed=args.seed, dtype=jnp.float32,
        n_layer=getattr(args, "n_layer", 2),
        d_model=getattr(args, "d_model", 64),
        d_ff=getattr(args, "d_ff", 128),
    )
    role = getattr(args, "replica_role", "unified")
    spec = bool(getattr(args, "spec", False))
    srv = llama_infer.DecodeServer(
        params, cfg, slots=args.slots, max_len=args.max_len,
        prompt_buckets=(16, 32), seed=args.seed,
        quant_kv=getattr(args, "quant_kv", False),
        prefix_cache_cap=getattr(args, "prefix_cache_cap", 4),
        # Speculative serving (ISSUE 11): remote-draft intent sizes the
        # cache headroom; per-request adaptive k guarantees a bad
        # draft can never make a stream slower than plain decode.
        spec_remote=spec,
        draft_k=getattr(args, "draft_k", 4),
        adapt_k_per_request=spec,
        spec_break_even=getattr(args, "spec_break_even", 0.0),
        # Paged KV (ISSUE 19): block-pool arena; pool_blocks 0 keeps
        # the matched-memory default (slots * max_len / block_size).
        paged=getattr(args, "paged", False),
        block_size=getattr(args, "block_size", 16),
        pool_blocks=(getattr(args, "pool_blocks", 0) or None),
    )
    import numpy as np

    # Warm the compile caches BEFORE registering with the gateway: the
    # fleet's TTFT percentiles must measure admission+decode latency,
    # not the first request's XLA compile (~1.5s for even the tiny
    # model on CPU).  Each role warms ITS admission path; with
    # --warm_prefix_len the prefix-template jits (keyed by prefix
    # length) are compiled too.  The dummy template is dropped so it
    # never occupies the LRU or reports warm.
    warm_p0 = getattr(args, "warm_prefix_len", 0)
    dummy = np.arange(1, 5, dtype=np.int32)
    if role != "prefill":
        srv.serve([dummy], max_new_tokens=2)
    if role in ("prefill", "decode"):
        srv.prefill_request("__warm", dummy, 2)
        payload, _ = srv.export_kv("__warm")
        if role == "decode":
            srv.import_kv("__warm", payload, dummy, 2)
            srv.serve_incremental(tick=lambda: bool(
                srv.pending_count() or srv.active_rids()
            ))
    if warm_p0 > 0 and role != "decode":
        # The template path only engages when the COMBINED prompt
        # exceeds the largest bucket — a short warm prefix with a
        # short dummy tail would silently warm nothing.
        n_warm = max(warm_p0, srv.buckets[-1]) + 9
        wp = np.arange(1, n_warm + 1, dtype=np.int32)
        if role == "prefill":
            srv.prefill_request("__warmp", wp, 2, prefix_len=warm_p0)
            srv.export_kv("__warmp")
        else:
            srv.submit("__warmp", wp, 2, prefix_len=warm_p0)
            srv.serve_incremental(tick=lambda: bool(
                srv.pending_count() or srv.active_rids()
            ))
        srv.clear_prefix_templates()
    if spec and role != "prefill":
        # Warm the speculative verify programs for the widths the
        # adaptive policy actually visits (full width + the k=1
        # probe); intermediate widths compile on demand.
        cache_w = llama_infer.init_cache(
            cfg, args.slots, args.max_len, ring=False
        )
        cache_w = dict(
            cache_w, offset=jnp.zeros((args.slots,), jnp.int32)
        )
        for kw_ in {1, getattr(args, "draft_k", 4)}:
            progs = llama_infer._spec_programs(cfg, cfg, kw_, 0.0, 0, 0)
            progs["target_verify"](
                params, cache_w,
                jnp.zeros((args.slots, kw_ + 1), jnp.int32),
            )
    journal = None
    if args.journal_dir:
        os.makedirs(args.journal_dir, exist_ok=True)
        journal = os.path.join(
            args.journal_dir, f"{args.replica_id}.jsonl"
        )
    return ReplicaRunner(
        srv, transport, args.replica_id, journal_path=journal,
        poll_interval=args.poll_interval,
        round_floor_s=args.round_floor_ms / 1000.0,
        role=role,
        kv_p2p=not getattr(args, "no_kv_p2p", False),
        draft_connect=draft_connect,
    )


def drive(args, transport, core=None, client=None):
    """Submit the seeded request stream at Poisson arrivals, poll every
    result, print the summary line the tests and bench key on.
    ``client`` overrides the transport-bound ServeClient (the tier
    driver passes a consistent-hash-routing TierClient)."""
    import numpy as np

    from dlrover_tpu.models import llama
    from dlrover_tpu.serving import ServeClient

    try:
        from examples import serve_common
    except ImportError:
        import serve_common

    cfg = llama.LlamaConfig.tiny(n_layer=2)
    prompts, _ = serve_common.seeded_requests(
        cfg, args.requests, args.seed + 1
    )
    arr_rng = np.random.RandomState(args.seed + 7)
    gaps = arr_rng.exponential(1.0 / max(args.rps, 1e-6),
                               size=args.requests)
    if client is None:
        client = ServeClient(transport)
    t0 = time.perf_counter()
    for i, prompt in enumerate(prompts):
        time.sleep(float(gaps[i]))
        ack = client.submit(
            f"req-{i}", prompt, args.max_new_tokens,
            deadline_s=args.deadline_s,
        )
        print(f"SUBMIT req-{i} status={ack.status}", flush=True)
    done = 0
    total_new = 0
    for i in range(args.requests):
        reply = client.result(f"req-{i}", timeout=args.timeout)
        n = len(reply.tokens)
        print(
            f"RESULT req-{i} state={reply.state} new_tokens={n} "
            f"replica={reply.replica}", flush=True,
        )
        if reply.state == "done":
            done += 1
            total_new += n
    dt = time.perf_counter() - t0
    extra = ""
    if core is not None:
        c = core.stats_snapshot()["counters"]
        extra = (f" redispatched={c['redispatched']} "
                 f"duplicates={c['duplicate_completions']}")
    print(
        f"FLEET_DONE requests={args.requests} completed={done} "
        f"new_tokens={total_new} tokens_per_sec={total_new / dt:.1f}"
        f"{extra}", flush=True,
    )
    return 0 if done == args.requests else 1


def main() -> int:
    args = parse_args()

    from dlrover_tpu.common.jax_env import enable_compilation_cache

    enable_compilation_cache()

    # Name this process's flight recorder after its role (ISSUE 12):
    # merged traces and postmortems read "gw-g1"/"rep-r0", not pids.
    # No-op beyond the label unless DLROVER_TPU_OBS_DIR is set.
    from dlrover_tpu import obs

    obs.set_process({
        "gateway": f"gw-{args.gateway_id}",
        "replica": f"rep-{args.replica_id}",
        "draft": f"draft-{args.replica_id}",
        "driver": "driver",
    }.get(args.role, "fleet"))

    def tier_registry():
        from dlrover_tpu.serving import RpcKv, ServeRegistry

        return ServeRegistry(
            RpcKv(args.registry), job=args.job,
            lease_s=args.lease_timeout,
        )

    if args.role == "gateway":
        from dlrover_tpu.serving import (
            Gateway,
            GatewayConfig,
            GatewayTierNode,
        )

        cfg = GatewayConfig(
            queue_cap=args.queue_cap,
            lease_timeout_s=args.lease_timeout,
            kv_p2p=not args.kv_relay,
            spec_decode_min_tokens=args.spec_min_tokens,
        )
        if args.registry:
            node = GatewayTierNode(
                args.gateway_id, tier_registry(), port=args.port,
                config=cfg,
                metrics_port=(
                    args.metrics_port if args.metrics_port >= 0
                    else None
                ),
            )
            node.start()
            gw = node.gateway
            print(
                f"GATEWAY_READY port={gw.port} id={args.gateway_id}"
                + (f" metrics={node.metrics_port}"
                   if node.metrics_port is not None else ""),
                flush=True,
            )
        else:
            node = None
            gw = Gateway(port=args.port, config=cfg)
            gw.start()
            print(f"GATEWAY_READY port={gw.port}", flush=True)
        stop = threading.Event()
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        while not stop.wait(2.0):
            snap = gw.core.stats_snapshot()
            print(
                "FLEET_STATS "
                + json.dumps({
                    "queue": snap["queue_depth"],
                    "alive": snap["replicas_alive"],
                    "occupancy": round(snap["occupancy"], 3),
                    "completed": snap["counters"]["completed"],
                    "ttft_p95_ms": gw.ttft_ms.percentile(0.95),
                }), flush=True,
            )
        if node is not None:
            node.stop()
        else:
            gw.stop()
        return 0

    class _T:
        """RpcClient with the runner's best-effort budget."""

        def __init__(self, addr):
            from dlrover_tpu.common.rpc import RpcClient

            self._c = RpcClient(addr, timeout=5.0)

        def call(self, msg, **kw):
            return self._c.call(msg, deadline=10.0,
                                idempotent=True, **kw)

    if args.role == "draft":
        # Draft replica (ISSUE 11): a small proposal server registered
        # as the fifth role family; spec targets learn its address
        # from the gateway's poll replies and pull per-round
        # proposals directly (the P2P segment-path shape).
        import jax.numpy as jnp

        from dlrover_tpu.serving import (
            DraftReplicaRunner,
            DraftServer,
            DraftWorker,
        )

        try:
            from examples import serve_common
        except ImportError:
            import serve_common

        if args.draft_seed < 0:
            # Ceiling draft: the target itself (stands in for a
            # trained draft — acceptance ~k+1, the ceiling of what a
            # real draft can reach).
            dparams, dcfg = serve_common.tiny_llama(
                seed=args.seed, dtype=jnp.float32,
                n_layer=args.n_layer, d_model=args.d_model,
                d_ff=args.d_ff,
            )
        else:
            dparams, dcfg = serve_common.tiny_llama(
                seed=args.draft_seed, dtype=jnp.float32,
                n_layer=args.draft_layers, d_model=args.d_model,
                d_ff=args.d_ff,
            )
        worker = DraftWorker(
            dparams, dcfg, max_len=args.max_len,
            draft_k=args.draft_k, max_streams=args.draft_streams,
            seed=args.seed, worker_id=args.replica_id,
            round_floor_s=args.draft_floor_ms / 1000.0,
        )
        # Warm every roll/score program BEFORE registering, so target
        # TTFT never pays a draft-side XLA compile.  warm() bypasses
        # the proposal loop: the chaos site's step gate (completed
        # rolls) must only count real serving traffic.
        worker.warm()
        server = DraftServer(worker)
        runner = DraftReplicaRunner(
            server, _T(args.gateway), args.replica_id,
            poll_interval=max(args.poll_interval, 0.05),
        )
        signal.signal(signal.SIGTERM, lambda *_: runner.stop())
        print(
            f"DRAFT_READY id={args.replica_id} addr={server.addr}",
            flush=True,
        )
        runner.run()
        print(
            f"DRAFT_DONE id={args.replica_id} rolls={worker.rolls} "
            f"proposed={worker.proposed_tokens}", flush=True,
        )
        return 0

    if args.role == "replica":
        if args.registry:
            from dlrover_tpu.serving import TierReplicaLink

            transport = TierReplicaLink(
                tier_registry(), args.replica_id,
            )
        else:
            transport = _T(args.gateway)
        runner = build_replica(args, transport)
        print(f"REPLICA_READY id={args.replica_id}", flush=True)
        runner.run()
        print(
            f"REPLICA_DONE id={args.replica_id} served="
            f"{runner.served} replayed={runner.replayed}", flush=True,
        )
        return 0

    if args.role == "driver":
        if args.registry:
            from dlrover_tpu.serving import TierClient

            client = TierClient(tier_registry())
            rc = drive(args, None, client=client)
            print(
                f"DRIVER_RESUBMITTED {client.resubmitted}", flush=True,
            )
            return rc
        from dlrover_tpu.common.rpc import RpcClient

        return drive(args, RpcClient(args.gateway, timeout=10.0))

    # --role all: one-process fleet (demo): loopback gateway, replica
    # threads, inline driver.
    from dlrover_tpu.serving import (
        Gateway,
        GatewayConfig,
        LoopbackTransport,
    )

    gw = Gateway(port=0, config=GatewayConfig(queue_cap=args.queue_cap))
    gw.start()
    transport = LoopbackTransport(gw.handle)
    threads = []
    runners = []
    for i in range(args.replicas):
        rargs = argparse.Namespace(**vars(args))
        rargs.replica_id = f"r{i}"
        runner = build_replica(rargs, transport)
        runners.append(runner)
        th = threading.Thread(target=runner.run, daemon=True,
                              name=f"replica-{i}")
        th.start()
        threads.append(th)
    try:
        rc = drive(args, transport, core=gw.core)
    finally:
        for runner in runners:
            gw.core.drain(runner.replica_id)
        for th in threads:
            th.join(timeout=30)
        gw.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())
