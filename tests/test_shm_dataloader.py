"""Coworker shm-ring dataloader tests: ordering, crash-respawn with
exactly-once delivery, prefetch overlap, sampler integration (test model:
the reference's shm_dataloader/coworker unit tests)."""

import time

import numpy as np
import pytest

from dlrover_tpu.data.shm_dataloader import (
    ShmDataLoader,
    ShmRing,
    _READY,
    _pack_batch,
    _unpack_batch,
)
from dlrover_tpu.trainer.sampler import ElasticSampler


def fetch_squares(indices: np.ndarray):
    """Module-level so the spawn-context producer can pickle it."""
    idx = np.asarray(indices, np.int64)
    return {
        "x": (idx[:, None] * np.ones((1, 4))).astype(np.float32),
        "y": (idx**2).astype(np.int64),
    }


def fetch_slow(indices: np.ndarray):
    time.sleep(0.05)
    return fetch_squares(indices)


class TestPacking:
    def test_round_trip(self):
        batch = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.array([7], dtype=np.int64),
        }
        buf = _pack_batch(batch)
        out = _unpack_batch(memoryview(buf))
        np.testing.assert_array_equal(out["a"], batch["a"])
        np.testing.assert_array_equal(out["b"], batch["b"])


class TestRing:
    def test_put_get_wraparound(self):
        ring = ShmRing("dlrtpu_test_ring_a", 4096, 2, create=True)
        try:
            for seq in range(5):
                payload = _pack_batch(
                    {"v": np.array([seq], dtype=np.int64)}
                )
                assert ring.put(seq, payload, timeout=5.0)
                got = ring.get(seq, timeout=5.0)
                assert int(got["v"][0]) == seq
        finally:
            ring.close(unlink=True)

    def test_oversized_payload_rejected(self):
        ring = ShmRing("dlrtpu_test_ring_b", 64, 2, create=True)
        try:
            with pytest.raises(ValueError, match="exceeds slot"):
                ring.put(0, b"x" * 100)
        finally:
            ring.close(unlink=True)


class TestLoader:
    def test_yields_all_batches_in_order(self):
        batches = [np.arange(i * 4, (i + 1) * 4) for i in range(8)]
        with ShmDataLoader(fetch_squares, batches, n_slots=3) as loader:
            got = list(loader)
        assert len(got) == 8
        for i, b in enumerate(got):
            np.testing.assert_array_equal(
                b["y"], (np.arange(i * 4, (i + 1) * 4) ** 2)
            )

    def test_producer_crash_respawns_exactly_once_delivery(self):
        batches = [np.array([i]) for i in range(10)]
        loader = ShmDataLoader(
            fetch_squares, batches, n_slots=2, _crash_after=4
        )
        try:
            got = [int(b["y"][0]) for b in loader]
            # Every batch delivered exactly once despite the crash at 4.
            assert got == [i * i for i in range(10)]
            assert loader._respawns >= 1
        finally:
            loader.close()

    def test_producer_dies_repeatedly_gives_up(self):
        batches = [np.array([i]) for i in range(6)]
        loader = ShmDataLoader(
            fetch_squares, batches, n_slots=2, max_respawns=0,
            _crash_after=2,
        )
        # the _crash_after=-1 reset is skipped when max_respawns=0
        try:
            with pytest.raises(RuntimeError, match="producer died"):
                list(loader)
        finally:
            loader.close()

    def test_prefetch_overlaps_fetch_with_consumption(self):
        """While the consumer still holds batch k, the producer fetches
        batch k+1 and leaves it READY in the ring: shown by order — the
        consumer takes no next batch until it has seen the one after
        waiting — so that the machine's load is in none of it.  A loader
        that fetched on demand would never show it."""
        n = 6
        batches = [np.array([i]) for i in range(n)]
        with ShmDataLoader(fetch_slow, batches, n_slots=4) as loader:
            ring = loader._ring
            for k, batch in enumerate(loader):
                assert int(batch["y"][0]) == k * k
                if k + 1 == n:
                    break
                # the "train step": it ends when the next batch is there
                deadline = time.monotonic() + 60.0
                while True:
                    state, _, seq = ring._hdr((k + 1) % ring.n_slots)
                    if state == _READY and seq == k + 1:
                        break
                    assert time.monotonic() < deadline, (
                        f"batch {k + 1} was not fetched while batch {k} "
                        "was held")
                    time.sleep(0.001)

    def test_from_sampler_preserves_position(self):
        sampler = ElasticSampler(
            32, batch_size_per_process=4, num_processes=1, process_id=0,
            seed=5,
        )
        # Consume 2 steps directly, then hand the rest to the loader.
        it = iter(sampler)
        first_two = [next(it), next(it)]
        del it
        expect = []
        shadow = sampler.reshard(1, 0)
        expect = list(shadow)
        with ShmDataLoader.from_sampler(
            sampler, fetch_squares, n_slots=3
        ) as loader:
            got = list(loader)
        assert len(got) == len(expect) == 6  # 8 steps/epoch - 2 consumed
        for g, e in zip(got, expect):
            np.testing.assert_array_equal(g["y"], np.asarray(e) ** 2)
        # And the loader never touched the sampler's own position.
        assert sampler.completed_steps == 2
        assert len(first_two[0]) == 4


class TestDevicePrefetcher:
    def test_order_and_values_preserved(self):
        import numpy as np

        from dlrover_tpu.data.prefetch import DevicePrefetcher

        batches = [{"x": np.full((4,), i, dtype=np.float32)}
                   for i in range(7)]
        out = list(DevicePrefetcher(batches, depth=3))
        assert len(out) == 7
        for i, b in enumerate(out):
            assert float(b["x"][0]) == float(i)
            assert hasattr(b["x"], "sharding")  # device-resident

    def test_depth_transfers_ahead(self):
        """With depth=k, k puts happen before the first batch is
        consumed (transfer rides ahead of compute)."""
        import numpy as np

        from dlrover_tpu.data.prefetch import DevicePrefetcher

        puts = []

        class Counting(DevicePrefetcher):
            def _put(self, batch):
                puts.append(len(puts))
                return super()._put(batch)

        batches = [np.zeros((2,), np.float32) for _ in range(6)]
        it = iter(Counting(batches, depth=3))
        next(it)
        assert len(puts) >= 3

    def test_bad_depth_rejected(self):
        import pytest

        from dlrover_tpu.data.prefetch import DevicePrefetcher

        with pytest.raises(ValueError):
            DevicePrefetcher([], depth=0)

    def test_sharded_put(self, cpu_mesh_devices):
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from dlrover_tpu.data.prefetch import prefetch_to_device

        mesh = Mesh(np.array(cpu_mesh_devices[:2]), ("dp",))
        sh = {"x": NamedSharding(mesh, P("dp"))}
        batches = [{"x": np.arange(8, dtype=np.float32)}]
        (out,) = list(prefetch_to_device(batches, sharding=sh))
        assert out["x"].sharding == sh["x"]


class TestSequencePacking:
    def test_pack_and_train_on_packed(self):
        """Packed rows feed llama.loss_fn directly; padding (segment -1)
        contributes nothing to attention or loss."""
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.data.packing import (
            pack_sequences,
            packing_efficiency,
        )
        from dlrover_tpu.models import llama

        rng = np.random.RandomState(0)
        docs = [rng.randint(1, 250, size=(n,)) for n in (9, 14, 5, 20, 3)]
        tokens, segs = pack_sequences(docs, seq_len=24)
        assert tokens.shape == segs.shape
        assert packing_efficiency(segs) > 0.5
        # Every document's tokens appear exactly once.
        total = sum(d.size for d in docs)
        assert int((segs >= 0).sum()) == total

        cfg = llama.LlamaConfig.tiny(n_layer=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        loss = llama.loss_fn(
            params,
            {"tokens": jnp.asarray(tokens),
             "segment_ids": jnp.asarray(segs)},
            cfg, moe_aux_weight=0.0,
        )
        assert np.isfinite(float(loss))

    def test_long_doc_split(self):
        from dlrover_tpu.data.packing import pack_sequences

        doc = np.arange(1, 55)  # 54 tokens, seq_len 24 -> 3 pieces
        tokens, segs = pack_sequences([doc], seq_len=24)
        # Pieces never share a segment id within a row (no cross-split
        # attention), and all 54 tokens survive.
        assert int((segs >= 0).sum()) == 54
        for r in range(tokens.shape[0]):
            for s in set(segs[r][segs[r] >= 0].tolist()):
                span = tokens[r][segs[r] == s]
                assert len(span) <= 24

    def test_first_fit_fills_gaps(self):
        from dlrover_tpu.data.packing import pack_sequences

        tokens, segs = pack_sequences(
            [np.ones(20), np.ones(10), np.ones(4)], seq_len=24
        )
        # 20+4 share a row; 10 in the second: 2 rows, not 3.
        assert tokens.shape[0] == 2


class TestNativePacker:
    def test_native_matches_python_layout(self):
        """The C++ first-fit core must produce byte-identical layouts to
        the Python reference (same first-fit semantics)."""
        import numpy as np

        from dlrover_tpu.data.packing import _packer_lib, pack_sequences

        if _packer_lib() is None:
            import pytest

            pytest.skip("no native toolchain")
        rng = np.random.default_rng(7)
        docs = [
            rng.integers(0, 500, size=int(rng.integers(1, 120)))
            for _ in range(500)
        ]
        # Include oversize docs (split path) and empties.
        docs += [rng.integers(0, 500, size=300), np.array([], np.int64)]
        tn, sn = pack_sequences(docs, 96, backend="native")
        tp, sp = pack_sequences(docs, 96, backend="python")
        np.testing.assert_array_equal(tn, tp)
        np.testing.assert_array_equal(sn, sp)

    def test_native_empty_and_exact_fit(self):
        import numpy as np

        from dlrover_tpu.data.packing import _packer_lib, pack_sequences

        if _packer_lib() is None:
            import pytest

            pytest.skip("no native toolchain")
        t, s = pack_sequences([], 16, backend="auto")
        assert t.shape == (1, 16) and (s == -1).all()
        # Exact fits fill rows completely.
        t, s = pack_sequences(
            [np.arange(16), np.arange(16)], 16, backend="native"
        )
        assert t.shape == (2, 16)
        assert (s >= 0).all()
