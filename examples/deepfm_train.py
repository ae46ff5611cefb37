"""DeepFM sparse-recommendation training example.

The framework's criteo-style system-test analogue (reference
``examples/tensorflow/criteo_deeprec`` + ``dlrover-system-test-criteo``):
synthetic CTR data, unbounded-vocabulary embeddings in the native KV store
(local, or PS-style over ``--num_servers`` store servers), dense half jitted.

    python examples/deepfm_train.py --steps 200
    python examples/deepfm_train.py --steps 200 --num_servers 2
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch_size", type=int, default=256)
    p.add_argument("--vocab", type=int, default=100000)
    p.add_argument("--num_fields", type=int, default=8)
    p.add_argument("--embed_dim", type=int, default=16)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--num_servers", type=int, default=0,
                   help="0 = in-process store; N = PS-style servers")
    p.add_argument("--device_cache", type=int, default=0,
                   help="hot-row cache capacity: keeps embeddings "
                        "device-resident and trains them INSIDE the "
                        "jitted step (SparseCore shape)")
    p.add_argument("--ckpt_dir", default="")
    return p.parse_args()


def main() -> int:
    args = parse_args()
    import jax
    import optax

    from dlrover_tpu.embedding.layer import EmbeddingLayer
    from dlrover_tpu.embedding.optim import SparseAdagrad
    from dlrover_tpu.models import deepfm

    cfg = deepfm.DeepFMConfig(
        num_fields=args.num_fields, embed_dim=args.embed_dim
    )
    params = deepfm.init_dense_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adam(args.lr)
    opt_state = tx.init(params)

    if args.device_cache > 0 and args.num_servers == 0:
        return run_device_cached(args, cfg, params, opt_state, tx)
    step = deepfm.make_train_step(cfg, tx)

    servers = []
    if args.num_servers > 0:
        from dlrover_tpu.embedding.service import (
            DistributedEmbedding,
            EmbeddingServer,
        )

        servers = [
            EmbeddingServer(r, dim_by_table={
                "feat": cfg.embed_dim, "feat1": 1,
            })
            for r in range(args.num_servers)
        ]
        addrs = [s.addr for s in servers]

        class RemoteLayer:
            def __init__(self, table, dim):
                self.de = DistributedEmbedding(
                    table, dim, addrs=addrs,
                    optimizer={"kind": "adagrad", "lr": 0.1},
                )
                self.dim = dim

            def pull(self, keys, train=True):
                keys = np.asarray(keys, np.int64)
                uniq, inv = np.unique(
                    keys.reshape(-1), return_inverse=True
                )
                rows = self.de.lookup(uniq, train=train)
                return rows, {
                    "uniq": uniq, "inv": inv.astype(np.int32),
                    "shape": keys.shape,
                }

            def push(self, ctx, grad_rows):
                self.de.apply_gradients(ctx["uniq"], grad_rows)

        emb = RemoteLayer("feat", cfg.embed_dim)
        emb1 = RemoteLayer("feat1", 1)
    else:
        emb = EmbeddingLayer(cfg.embed_dim, SparseAdagrad(lr=0.1), seed=1)
        emb1 = EmbeddingLayer(1, SparseAdagrad(lr=0.1), seed=2)

    rng = np.random.default_rng(0)
    loss = None
    for i in range(1, args.steps + 1):
        keys = rng.integers(
            0, args.vocab, size=(args.batch_size, cfg.num_fields)
        )
        labels = (
            (keys[:, 0] % 3 == 0) ^ (keys[:, 1] % 2 == 0)
        ).astype(np.float32)
        rows, ctx = emb.pull(keys)
        rows1, ctx1 = emb1.pull(keys)
        params, opt_state, loss, g_rows, g_rows1 = step(
            params, opt_state, rows, ctx["inv"], rows1, ctx1["inv"], labels
        )
        emb.push(ctx, np.asarray(g_rows))
        emb1.push(ctx1, np.asarray(g_rows1))
        if i % 20 == 0:
            print(f"step {i} loss {float(loss):.4f}", flush=True)

    if args.ckpt_dir and args.num_servers == 0:
        from dlrover_tpu.embedding.checkpoint import save_table

        save_table(emb.store, args.ckpt_dir, "feat")
        save_table(emb1.store, args.ckpt_dir, "feat1")
    for s in servers:
        s.stop()
    print(f"TRAIN_DONE step={args.steps} loss={float(loss):.4f}", flush=True)
    return 0


def run_device_cached(args, cfg, params, opt_state, tx) -> int:
    """Device-resident embedding path: gather + sparse adagrad inside
    the compiled step; host store synced on a cadence + at the end."""
    import jax

    from dlrover_tpu.embedding.device_cache import DeviceEmbeddingCache
    from dlrover_tpu.embedding.store import EmbeddingStore
    from dlrover_tpu.models import deepfm

    store = EmbeddingStore(cfg.embed_dim, seed=1)
    store1 = EmbeddingStore(1, seed=2)
    cache = DeviceEmbeddingCache(
        store, args.device_cache, flush_every=50
    )
    cache1 = DeviceEmbeddingCache(
        store1, args.device_cache, flush_every=50
    )
    step = deepfm.make_cached_train_step(cfg, tx, emb_lr=0.1)

    rng = np.random.default_rng(0)

    def make_batch():
        keys = rng.integers(
            0, args.vocab, size=(args.batch_size, cfg.num_fields)
        )
        labels = (
            (keys[:, 0] % 3 == 0) ^ (keys[:, 1] % 2 == 0)
        ).astype(np.float32)
        return keys, labels

    # Admission double-buffering: the NEXT batch's store pulls + id
    # mapping (the host half) run on a worker thread while the device
    # executes the CURRENT step; apply_plan after update() is one cheap
    # scatter.  One plan in flight per cache (plan_batch contract).
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=2)
    loss = None
    keys, labels = make_batch()
    plan, plan1 = cache.plan_batch(keys), cache1.plan_batch(keys)
    for i in range(1, args.steps + 1):
        slots = cache.apply_plan(plan)
        slots1 = cache1.apply_plan(plan1)
        if i < args.steps:
            nxt_keys, nxt_labels = make_batch()
            fut = pool.submit(cache.plan_batch, nxt_keys)
            fut1 = pool.submit(cache1.plan_batch, nxt_keys)
        (params, opt_state, table, accum, table1, accum1, loss) = step(
            params, opt_state, cache.table, cache.accum, slots,
            cache1.table, cache1.accum, slots1, labels,
        )
        cache.update(table, accum)
        cache1.update(table1, accum1)
        cache.maybe_flush()
        cache1.maybe_flush()
        if i % 20 == 0:
            print(f"step {i} loss {float(loss):.4f}", flush=True)
        if i < args.steps:
            plan, plan1 = fut.result(), fut1.result()
            keys, labels = nxt_keys, nxt_labels
    pool.shutdown()

    cache.flush()
    cache1.flush()
    if args.ckpt_dir:
        from dlrover_tpu.embedding.checkpoint import save_table

        save_table(store, args.ckpt_dir, "feat")
        save_table(store1, args.ckpt_dir, "feat1")
    print(
        f"TRAIN_DONE step={args.steps} loss={float(loss):.4f} "
        f"device_cache={args.device_cache}", flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
