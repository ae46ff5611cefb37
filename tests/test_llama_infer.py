"""KV-cache decoding tests: the cached path must agree with the full
forward, and greedy decoding with the cache must match token-by-token
full-recompute argmax decoding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama, llama_infer


def _setup(batch=2, **cfg_over):
    cfg = llama.LlamaConfig.tiny(n_layer=2, **cfg_over)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (batch, 7), 0, cfg.vocab_size
    )
    return cfg, params, prompts


class TestKVCacheDecode:
    def test_prefill_matches_full_forward(self):
        cfg, params, prompts = _setup()
        cache = llama_infer.init_cache(cfg, prompts.shape[0], 16)
        logits, cache = llama_infer.forward_step(
            params, prompts, cfg, cache
        )
        ref, _ = llama.forward(params, prompts, cfg,
                               attn_impl="reference")
        # bf16 tolerance: the cache path keeps attention weights in the
        # cache dtype for the p@v product (no fp32 cache copies), which
        # costs ~1e-3 vs the fp32-operand reference.
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref), atol=5e-3
        )
        assert int(cache["offset"]) == prompts.shape[1]

    def test_incremental_matches_full_forward(self):
        """Scoring the prompt one token at a time through the cache
        reproduces the full forward's last-position logits."""
        cfg, params, prompts = _setup()
        B, P = prompts.shape
        cache = llama_infer.init_cache(cfg, B, P)
        for t in range(P):
            logits, cache = llama_infer.forward_step(
                params, prompts[:, t:t + 1], cfg, cache
            )
        ref, _ = llama.forward(params, prompts, cfg,
                               attn_impl="reference")
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref[:, -1]), atol=5e-3
        )
        # And exactly (1e-6) when compute is fp32 end to end.
        cfg32, params32, prompts32 = _setup(dtype=jnp.float32)
        cache32 = llama_infer.init_cache(cfg32, *prompts32.shape)
        for t in range(prompts32.shape[1]):
            l32, cache32 = llama_infer.forward_step(
                params32, prompts32[:, t:t + 1], cfg32, cache32
            )
        ref32, _ = llama.forward(params32, prompts32, cfg32,
                                 attn_impl="reference")
        np.testing.assert_allclose(
            np.asarray(l32[:, 0]), np.asarray(ref32[:, -1]), atol=1e-5
        )

    def test_greedy_generate_matches_full_recompute(self):
        cfg, params, prompts = _setup()
        N = 6
        got = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=N, temperature=0.0
        )
        assert got.shape == (prompts.shape[0], prompts.shape[1] + N)
        # Reference: grow the sequence with argmax of the FULL forward.
        seq = prompts
        for _ in range(N):
            logits, _ = llama.forward(params, seq, cfg,
                                      attn_impl="reference")
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)
            seq = jnp.concatenate(
                [seq, nxt[:, None].astype(seq.dtype)], axis=1
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))

    def test_gqa_and_moe_decode_matches_full_recompute(self):
        """MoE + GQA greedy decode must agree with token-by-token
        argmax over the FULL training forward (parity, not just
        determinism — a consistently wrong decode path must fail).

        fp32 compute: in bf16 a random tiny model's top-2 logits sit
        within rounding noise of each other, so argmax parity only
        exists where the paths are numerically equivalent."""
        # num_experts > top_k and B > 1 so expert collisions at decode
        # T=1 are possible (regression: config-derived capacity at T=1
        # dropped colliding rows); capacity_factor is ample so the
        # TRAINING forward also drops nothing — required for exact
        # parity, since decode always runs drop-free.
        cfg, params, prompts = _setup(
            batch=4, n_head=4, n_kv_head=2, num_experts=4, moe_every=2,
            dtype=jnp.float32, capacity_factor=8.0,
        )
        N = 4
        got = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=N, temperature=0.0
        )
        seq = prompts
        for _ in range(N):
            logits, _ = llama.forward(params, seq, cfg,
                                      attn_impl="reference")
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)
            seq = jnp.concatenate(
                [seq, nxt[:, None].astype(seq.dtype)], axis=1
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))

    def test_sampling_respects_top_k(self):
        cfg, params, prompts = _setup()
        got = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=8,
            rng=jax.random.PRNGKey(3), temperature=1.0, top_k=1,
        )
        # top_k=1 at any temperature IS greedy.
        greedy = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=8, temperature=0.0
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(greedy))


class TestTopP:
    def test_sampling_respects_top_p(self):
        """With top_p covering only the single most likely token, nucleus
        sampling must reduce to greedy regardless of temperature."""
        cfg, params, prompts = _setup()
        greedy = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=5, temperature=0.0
        )
        tiny_p = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=5,
            rng=jax.random.PRNGKey(3), temperature=1.0, top_p=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(tiny_p), np.asarray(greedy)
        )

    def test_top_p_one_matches_full_sampling(self):
        """top_p=1.0 keeps the whole distribution: same rng draws the
        same tokens as unfiltered sampling."""
        cfg, params, prompts = _setup()
        a = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=5,
            rng=jax.random.PRNGKey(5), temperature=0.8, top_p=1.0,
        )
        b = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=5,
            rng=jax.random.PRNGKey(5), temperature=0.8,
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRollingWindowCache:
    # slow-lane (ISSUE 8 satellite): 24s — the ring cache is a memory
    # optimization orthogonal to the serving/dense-cache surfaces the
    # tier-1 suite guards per-PR.
    @pytest.mark.slow
    def test_ring_decode_matches_full_forward_and_shrinks_memory(self):
        """Sliding-window decode through the ROLLING cache: greedy
        parity with the windowed full forward while the cache holds
        max(P, window) slots instead of P + N."""
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=2, dtype=jnp.float32,
            sliding_window=6, max_seq_len=128,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size
        )
        N = 24  # enough decode steps to wrap the ring several times
        got = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=N, temperature=0.0
        )
        seq = prompts
        for _ in range(N):
            logits, _ = llama.forward(params, seq, cfg)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)
            seq = jnp.concatenate(
                [seq, nxt[:, None].astype(seq.dtype)], axis=1
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))

        # The ring really is bounded: forward_step on a ring cache of
        # max(P, W) slots, not P + N.
        cache = llama_infer.init_cache(
            cfg, 2, P := 8 + N, ring_len=max(8, cfg.sliding_window)
        )
        assert cache["layers"][0]["k"].shape[2] == 8
        assert cache["pos"].shape == (8,)

    def test_ring_rejects_oversized_chunk(self):
        from dlrover_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(
            n_layer=1, sliding_window=4, max_seq_len=64
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        cache = llama_infer.init_cache(cfg, 1, 64, ring_len=4)
        with pytest.raises(ValueError, match="ring"):
            llama_infer.forward_step(
                params, jnp.zeros((1, 8), jnp.int32), cfg, cache
            )
        # A continuation chunk that would clobber in-window keys is
        # rejected even when it fits the ring.
        with pytest.raises(ValueError, match="continuation"):
            llama_infer.forward_step(
                params, jnp.zeros((1, 2), jnp.int32), cfg, cache
            )


class TestRaggedDecode:
    """Per-sequence lengths + per-sequence EOS exit (VERDICT r3 missing
    #3: the lockstep decoder had no ragged positioning or early exit)."""

    def _fp32(self, batch=3, n_layer=2):
        cfg = llama.LlamaConfig.tiny(n_layer=n_layer, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return cfg, params

    def test_ragged_matches_one_at_a_time(self):
        """A ragged batch of different prompt lengths decodes each row
        exactly as decoding that row alone at its true length."""
        cfg, params = self._fp32()
        rng = np.random.RandomState(0)
        lens = [3, 7, 5]
        P = max(lens)
        N = 6
        prompts = np.zeros((len(lens), P), np.int32)
        for b, ln in enumerate(lens):
            prompts[b, :ln] = rng.randint(1, cfg.vocab_size, ln)
        out, out_lens = llama_infer.generate_ragged(
            params, cfg, jnp.asarray(prompts), jnp.asarray(lens),
            max_new_tokens=N, temperature=0.0,
        )
        assert out.shape == (3, P + N)
        for b, ln in enumerate(lens):
            solo = llama_infer.generate(
                params, cfg, jnp.asarray(prompts[b:b + 1, :ln]),
                max_new_tokens=N, temperature=0.0,
            )
            assert int(out_lens[b]) == ln + N
            np.testing.assert_array_equal(
                np.asarray(out[b, : ln + N]), np.asarray(solo[0])
            )
            # Tail is clean pad.
            assert (np.asarray(out[b, ln + N:]) == 0).all()

    def test_eos_stops_per_sequence_and_loop_exits_early(self):
        """A sequence whose greedy continuation hits EOS stops there
        (pad after), and once EVERY row is done the while_loop exits —
        observable as out_lens < prompt + max_new for all rows."""
        cfg, params = self._fp32(batch=2)
        rng = np.random.RandomState(1)
        prompts = rng.randint(1, cfg.vocab_size, (2, 5)).astype(np.int32)
        # Find each row's first greedy token and use row 0's as EOS:
        # row 0 then finishes after ONE token.
        ref = llama_infer.generate(
            params, cfg, jnp.asarray(prompts), max_new_tokens=4,
            temperature=0.0,
        )
        eos = int(ref[0, 5])
        out, lens = llama_infer.generate_ragged(
            params, cfg, jnp.asarray(prompts),
            jnp.asarray([5, 5]), max_new_tokens=64,
            eos_token=eos, temperature=0.0,
        )
        assert int(lens[0]) == 6  # prompt + the EOS token itself
        assert (np.asarray(out[0, 6:]) == 0).all()
        # Row 1 keeps its own trajectory (prefix must match the
        # unconstrained decode until/unless it too emits eos).
        row1 = np.asarray(ref[1, 5:])
        got1 = np.asarray(out[1, 5:9])
        stop = np.where(row1 == eos)[0]
        valid = (stop[0] + 1) if len(stop) else 4
        np.testing.assert_array_equal(got1[:valid], row1[:valid])

    def test_all_done_immediately(self):
        """Every first token == EOS: loop body still runs to record the
        scored tokens, lengths are prompt+1."""
        cfg, params = self._fp32(batch=2)
        prompts = np.full((2, 4), 3, np.int32)
        ref = llama_infer.generate(
            params, cfg, jnp.asarray(prompts), max_new_tokens=1,
            temperature=0.0,
        )
        eos = int(ref[0, 4])
        out, lens = llama_infer.generate_ragged(
            params, cfg, jnp.asarray(prompts), jnp.asarray([4, 4]),
            max_new_tokens=32, eos_token=eos, temperature=0.0,
        )
        np.testing.assert_array_equal(np.asarray(lens), [5, 5])
        assert int(out[0, 4]) == eos


class TestDecodeServer:
    def test_continuous_batching_matches_solo_decode(self):
        """7 mixed-length prompts through 2 slots: every output equals
        decoding that prompt alone (greedy), regardless of admission
        order / slot reuse."""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(2)
        prompts = [
            rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in (3, 9, 5, 4, 12, 6, 3)
        ]
        N = 5
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, eos_token=-1,
            prompt_buckets=(4, 8, 16),
        )
        outs = srv.serve(prompts, max_new_tokens=N)
        assert len(outs) == len(prompts)
        for p, got in zip(prompts, outs):
            solo = llama_infer.generate(
                params, cfg, jnp.asarray(p[None, :]),
                max_new_tokens=N, temperature=0.0,
            )
            np.testing.assert_array_equal(got, np.asarray(solo[0]))

    def test_eos_frees_slot_early(self):
        """A request finishing at EOS frees its slot for the queue: all
        requests still come back correct."""
        cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(3)
        prompts = [
            rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in (4, 4, 4)
        ]
        # EOS = the greedy first token of prompt 0.
        first = llama_infer.generate(
            params, cfg, jnp.asarray(prompts[0][None, :]),
            max_new_tokens=1, temperature=0.0,
        )
        eos = int(first[0, 4])
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=32, eos_token=eos,
            prompt_buckets=(4, 8),
        )
        outs = srv.serve(prompts, max_new_tokens=6)
        # Request 0 stopped at its EOS.
        assert outs[0][-1] == eos and len(outs[0]) <= 4 + 6
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p[None, :]),
                max_new_tokens=6, temperature=0.0,
            )[0])
            stop = np.where(solo[4:] == eos)[0]
            n_valid = (stop[0] + 1) if len(stop) else 6
            np.testing.assert_array_equal(got, solo[: 4 + n_valid])


class TestDecodeThroughput:
    def test_batched_rollout_equals_sequential_rows(self):
        """One batched decode produces row-for-row the same tokens as
        sequential single-row calls.  (The THROUGHPUT win of batching
        is an accelerator property — B=1 decode is HBM-bandwidth-bound
        there — not measured on the chip yet: no serving cell, PERF.md
        section 7; on a single CPU core compute scales linearly with B
        and a wall-clock assertion would test the backend, not us.)"""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        N = 16
        B = 8
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (B, 8), 0, cfg.vocab_size
        )
        out = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=N, temperature=0.0
        )
        for b in range(B):
            solo = llama_infer.generate(
                params, cfg, prompts[b:b + 1], max_new_tokens=N,
                temperature=0.0,
            )
            np.testing.assert_array_equal(
                np.asarray(out[b]), np.asarray(solo[0])
            )


class TestDecodeServerGuards:
    def test_capacity_overflow_rejected(self):
        cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=16, prompt_buckets=(8, 16),
        )
        import pytest as _pytest

        with _pytest.raises(ValueError, match="exceeds max_len"):
            srv.serve([np.arange(8, dtype=np.int32) % 7 + 1],
                      max_new_tokens=16)

    def test_sampled_serving_is_not_degenerate(self):
        """temperature>0 serving must not collapse into short loops
        (a constant per-step PRNG key would)."""
        cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=64, temperature=1.0,
            prompt_buckets=(8,), seed=7,
        )
        out = srv.serve(
            [np.arange(4, dtype=np.int32) + 1], max_new_tokens=40
        )[0]
        gen = out[4:]
        # A period-2 loop (the constant-key failure mode) repeats one
        # pair for the whole tail; real sampling of a random tiny model
        # has far more distinct adjacent pairs.
        pairs = {(int(a), int(b)) for a, b in zip(gen[:-1], gen[1:])}
        assert len(pairs) > 5, gen


class TestQuantKVCache:
    """int8 kv cache: per-(seq, head, slot) absmax quantization of the
    cached k/v (the fp8/int8 kv-cache mode of the serving engine the
    reference RL stack delegates to) — halves decode HBM traffic."""

    def test_quantize_kv_error_bound(self):
        """Round-to-nearest absmax int8: elementwise error <= scale/2."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 3, 5, 8)), jnp.float32)
        codes, scale = llama_infer._quantize_kv(x)
        assert codes.dtype == jnp.int8 and scale.shape == (2, 3, 5)
        back = np.asarray(codes, np.float32) * np.asarray(scale)[..., None]
        err = np.abs(back - np.asarray(x))
        assert (err <= np.asarray(scale)[..., None] / 2 + 1e-7).all()

    def test_quant_prefill_and_decode_logits_close(self):
        """fp32 model: the int8-cache logits track the dense-cache
        logits through prefill AND several decode steps."""
        cfg, params, prompts = _setup(dtype=jnp.float32)
        B, P = prompts.shape
        dense = llama_infer.init_cache(cfg, B, P + 4)
        quant = llama_infer.init_cache(cfg, B, P + 4, quant_kv=True)
        ld, dense = llama_infer.forward_step(params, prompts, cfg, dense)
        lq, quant = llama_infer.forward_step(params, prompts, cfg, quant)
        span = float(np.max(np.abs(np.asarray(ld)))) + 1e-6
        assert float(np.max(np.abs(np.asarray(lq - ld)))) / span < 0.05
        tok = jnp.argmax(ld[:, -1, :], axis=-1).astype(prompts.dtype)
        for _ in range(4):
            ld, dense = llama_infer.forward_step(
                params, tok[:, None], cfg, dense
            )
            lq, quant = llama_infer.forward_step(
                params, tok[:, None], cfg, quant
            )
            assert (
                float(np.max(np.abs(np.asarray(lq - ld)))) / span < 0.08
            )
            tok = jnp.argmax(ld[:, -1, :], axis=-1).astype(tok.dtype)

    def test_quant_cache_is_half_the_bytes(self):
        # Production head_dim (the tiny default's D=16 would make the
        # f32 per-slot scale loom large; at D=64 it is a 3% overhead).
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, dtype=jnp.bfloat16, n_head=4, n_kv_head=2,
            d_model=256,
        )
        dense = llama_infer.init_cache(cfg, 2, 32)
        quant = llama_infer.init_cache(cfg, 2, 32, quant_kv=True)

        def nbytes(c):
            return sum(
                int(np.prod(a.shape)) * a.dtype.itemsize
                for layer in c["layers"] for a in layer.values()
            )

        # int8 codes + f32 per-slot scale: ~0.5x of bf16 + scale overhead
        assert nbytes(quant) < 0.6 * nbytes(dense)

    def test_quant_ragged_generate_runs_and_stops_on_eos(self):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = np.zeros((2, 6), np.int32)
        prompts[0, :4] = [1, 2, 3, 4]
        prompts[1, :6] = [5, 6, 7, 1, 2, 3]
        out, lens = llama_infer.generate_ragged(
            params, cfg, jnp.asarray(prompts),
            jnp.asarray([4, 6], np.int32),
            max_new_tokens=6, quant_kv=True,
        )
        assert out.shape == (2, 12)
        assert int(lens[0]) >= 4 and int(lens[1]) >= 6
        # prompt is preserved verbatim at the head of each row
        np.testing.assert_array_equal(np.asarray(out[0, :4]),
                                      prompts[0, :4])
        np.testing.assert_array_equal(np.asarray(out[1, :6]),
                                      prompts[1, :6])

    def test_quant_server_matches_quant_solo_decode(self):
        """Continuous batching with the int8 cache must emit exactly the
        solo int8-cache greedy decode (both paths quantize identically)."""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [
            (np.arange(4, dtype=np.int32) % 7) + 1,
            (np.arange(6, dtype=np.int32) % 5) + 2,
        ]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=32, prompt_buckets=(8,),
            quant_kv=True,
        )
        outs = srv.serve(prompts, max_new_tokens=5)
        for p, got in zip(prompts, outs):
            solo = llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :],
                max_new_tokens=5, quant_kv=True,
            )[0]
            np.testing.assert_array_equal(got, np.asarray(solo))

    def test_quant_ring_decode_close_to_dense_ring(self):
        """Sliding-window ring cache composes with int8 quant."""
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, dtype=jnp.float32, sliding_window=6
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (2, 7), 0, cfg.vocab_size
        )
        dense = llama_infer.init_cache(cfg, 2, 16, ring_len=8)
        quant = llama_infer.init_cache(cfg, 2, 16, ring_len=8,
                                       quant_kv=True)
        ld, dense = llama_infer.forward_step(
            params, prompts, cfg, dense, assume_empty_cache=True
        )
        lq, quant = llama_infer.forward_step(
            params, prompts, cfg, quant, assume_empty_cache=True
        )
        span = float(np.max(np.abs(np.asarray(ld)))) + 1e-6
        assert float(np.max(np.abs(np.asarray(lq - ld)))) / span < 0.05
        tok = jnp.argmax(ld[:, -1, :], axis=-1).astype(prompts.dtype)
        for _ in range(3):
            ld, dense = llama_infer.forward_step(
                params, tok[:, None], cfg, dense
            )
            lq, quant = llama_infer.forward_step(
                params, tok[:, None], cfg, quant
            )
            assert (
                float(np.max(np.abs(np.asarray(lq - ld)))) / span < 0.08
            )
            tok = jnp.argmax(ld[:, -1, :], axis=-1).astype(tok.dtype)


class TestTensorParallelDecode:
    """TP serving: shard params over a 'tp' mesh and run the SAME
    generate/forward_step — GSPMD partitions the einsums (the role
    module surgery plays in vllm's TP serving)."""

    def _mesh(self, n):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:n]), ("tp",))

    def test_tp_forward_matches_single_device(self):
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=2, dtype=jnp.float32
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (2, 7), 0, cfg.vocab_size
        )
        cache = llama_infer.init_cache(cfg, 2, 12)
        ref, _ = llama_infer.forward_step(params, prompts, cfg, cache)

        mesh = self._mesh(4)
        sharded, specs = llama_infer.shard_params_for_decode(
            params, cfg, mesh
        )
        # wq is ('embed','heads') -> P(None, 'tp'); lm_head vocab-sharded
        from jax.sharding import PartitionSpec as P

        assert specs["layers"][0]["wq"] == P(None, "tp")
        assert specs["lm_head"] == P(None, "tp")
        with mesh:
            fwd = jax.jit(
                lambda p, pr: llama_infer.forward_step(
                    p, pr, cfg, llama_infer.init_cache(cfg, 2, 12)
                )[0]
            )
            got = fwd(sharded, prompts)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-4
        )

    def test_tp_generate_greedy_matches_and_composes_with_quant(self):
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=2, dtype=jnp.float32
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(2), (2, 5), 0, cfg.vocab_size
        )
        ref = llama_infer.generate(params, cfg, prompts, max_new_tokens=6)
        mesh = self._mesh(4)
        sharded, _ = llama_infer.shard_params_for_decode(
            params, cfg, mesh
        )
        with mesh:
            out = jax.jit(
                lambda p, pr: llama_infer.generate(
                    p, cfg, pr, max_new_tokens=6
                )
            )(sharded, prompts)
            outq = jax.jit(
                lambda p, pr: llama_infer.generate(
                    p, cfg, pr, max_new_tokens=6, quant_kv=True
                )
            )(sharded, prompts)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # int8-kv under TP must emit exactly what the single-device
        # int8-kv decode emits (same quantization in both).
        refq = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=6, quant_kv=True
        )
        np.testing.assert_array_equal(np.asarray(outq), np.asarray(refq))


class TestSpeculativeDecode:
    """Draft-propose-k / target-verify-in-one-chunk greedy speculative
    decoding: the output must be EXACTLY the target model's greedy
    decode, independent of the draft."""

    def _target(self):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab_size
        )
        return cfg, params, prompts

    def test_same_model_draft_accepts_everything(self):
        cfg, params, prompts = self._target()
        ref = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=10
        )
        stats = {}
        got = llama_infer.generate_speculative(
            params, cfg, params, cfg, prompts, max_new_tokens=10, k=4,
            stats=stats,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        # Perfect draft: every round lands k+1 tokens.
        assert stats["tokens_per_round"] > 4, stats

    def test_disagreeing_draft_still_exact(self):
        cfg, params, prompts = self._target()
        # Different seed => frequent disagreement => rejects exercised.
        draft_params = llama.init_params(jax.random.PRNGKey(9), cfg)
        ref = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=12
        )
        got = llama_infer.generate_speculative(
            params, cfg, draft_params, cfg, prompts,
            max_new_tokens=12, k=3,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_smaller_draft_model_and_quant_compose(self):
        cfg, params, prompts = self._target()
        dcfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        dparams = llama.init_params(jax.random.PRNGKey(3), dcfg)
        ref = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=8, quant_kv=True
        )
        got = llama_infer.generate_speculative(
            params, cfg, dparams, dcfg, prompts, max_new_tokens=8,
            k=2, quant_kv=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_guards(self):
        cfg, params, _ = self._target()
        two = jnp.zeros((2, 4), jnp.int32)
        with pytest.raises(ValueError, match="single-sequence"):
            llama_infer.generate_speculative(
                params, cfg, params, cfg, two, max_new_tokens=4
            )

    def test_sliding_window_speculates_on_dense_cache(self):
        """Windowed models speculate on a DENSE cache (offset rewind
        needs slot masking a ring cannot provide) — output must equal
        the windowed greedy decode through the RING cache exactly."""
        wcfg = llama.LlamaConfig.tiny(
            n_layer=2, dtype=jnp.float32, sliding_window=5,
        )
        wparams = llama.init_params(jax.random.PRNGKey(0), wcfg)
        dcfg = llama.LlamaConfig.tiny(
            n_layer=1, dtype=jnp.float32, sliding_window=5,
        )
        dparams = llama.init_params(jax.random.PRNGKey(3), dcfg)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (1, 6), 1, wcfg.vocab_size
        )
        ref = llama_infer.generate(  # ring-cache oracle
            wparams, wcfg, prompts, max_new_tokens=10
        )
        got = llama_infer.generate_speculative(
            wparams, wcfg, dparams, dcfg, prompts, max_new_tokens=10,
            k=3,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_rejection_sampling_law(self):
        """Monte-Carlo: whatever the draft distribution, the FIRST
        emitted token of a round must be distributed as the target's
        p[0] (the Leviathan et al. correctness property)."""
        rng = np.random.default_rng(0)
        V, k = 8, 3
        # deliberately mismatched target/draft distributions
        p = rng.dirichlet(np.ones(V), size=k + 1)
        q = rng.dirichlet(np.ones(V) * 0.3, size=k)
        N = 40000
        counts = np.zeros(V)
        for _ in range(N):
            d = np.array([rng.choice(V, p=q[i]) for i in range(k)])
            j, nxt = llama_infer._spec_accept_round(p, q, d, rng)
            first = int(d[0]) if j >= 1 else nxt
            counts[first] += 1
        emp = counts / N
        assert np.max(np.abs(emp - p[0])) < 0.015, (emp, p[0])

    def test_batched_acceptance_matches_scalar_spec_law(self):
        """_spec_accept_batch is the vectorized serving-path form of
        _spec_accept_round (the scalar executable spec).  Monte-Carlo:
        both must emit the round's FIRST token with the target's p[0]
        law, and their accepted-length distributions must agree — drift
        between the two implementations ships silently otherwise."""
        rng = np.random.default_rng(0)
        V, k, B = 8, 3, 16
        p = rng.dirichlet(np.ones(V), size=k + 1)
        q = rng.dirichlet(np.ones(V) * 0.3, size=k)
        N = 3000  # x B rows = 48k trials
        counts = np.zeros(V)
        jcounts = np.zeros(k + 1)
        pb = np.broadcast_to(p, (B, k + 1, V))
        qb = np.broadcast_to(q, (B, k, V))
        done = np.zeros(B, bool)
        for _ in range(N):
            d = np.stack(
                [rng.choice(V, p=q[i], size=B) for i in range(k)], axis=1
            )
            j, tok = llama_infer._spec_accept_batch(pb, qb, d, done, rng)
            first = np.where(j >= 1, d[:, 0], tok)
            np.add.at(counts, first, 1)
            np.add.at(jcounts, j, 1)
        emp = counts / (N * B)
        assert np.max(np.abs(emp - p[0])) < 0.01, (emp, p[0])
        # Accepted-length law must match the scalar spec's.
        sc_j = np.zeros(k + 1)
        for _ in range(20000):
            d = np.array([rng.choice(V, p=q[i]) for i in range(k)])
            j, _ = llama_infer._spec_accept_round(p, q, d, rng)
            sc_j[j] += 1
        assert np.max(np.abs(jcounts / (N * B) - sc_j / 20000)) < 0.02, (
            jcounts / (N * B), sc_j / 20000,
        )

    def test_batched_acceptance_frozen_rows_ride_along(self):
        """done rows must come back with j=0 and any token — and their
        presence must not perturb active rows' indexing."""
        rng = np.random.default_rng(1)
        V, k, B = 5, 2, 4
        p = rng.dirichlet(np.ones(V), size=(B, k + 1))
        q = rng.dirichlet(np.ones(V), size=(B, k))
        d = rng.integers(0, V, size=(B, k))
        done = np.array([False, True, False, True])
        j, tok = llama_infer._spec_accept_batch(p, q, d, done, rng)
        assert (j[done] == 0).all()
        assert j.shape == (B,) and tok.shape == (B,)
        assert (tok >= 0).all() and (tok < V).all()

    def test_sampled_speculative_runs_and_differs_by_seed(self):
        cfg, params, prompts = self._target()
        dparams = llama.init_params(jax.random.PRNGKey(9), cfg)
        stats = {}
        a = llama_infer.generate_speculative(
            params, cfg, dparams, cfg, prompts, max_new_tokens=10,
            k=3, temperature=1.0, rng=jax.random.PRNGKey(1),
            stats=stats,
        )
        b = llama_infer.generate_speculative(
            params, cfg, dparams, cfg, prompts, max_new_tokens=10,
            k=3, temperature=1.0, rng=jax.random.PRNGKey(2),
        )
        assert a.shape == b.shape == (1, prompts.shape[1] + 10)
        assert stats["rounds"] >= 1
        assert (np.asarray(a) >= 0).all()
        assert (np.asarray(a) < cfg.vocab_size).all()
        # different seeds should draw different continuations
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_same_model_sampled_draft_high_acceptance(self):
        """Draft == target: p/q == 1 everywhere, so acceptance is
        near-total and every round lands ~k+1 tokens."""
        cfg, params, prompts = self._target()
        stats = {}
        llama_infer.generate_speculative(
            params, cfg, params, cfg, prompts, max_new_tokens=12,
            k=4, temperature=0.7, rng=jax.random.PRNGKey(3),
            stats=stats,
        )
        assert stats["tokens_per_round"] > 3.5, stats

    def test_speculative_top_k_one_is_greedy(self):
        """top_k=1 truncation at any temperature collapses both the
        proposal and acceptance laws to argmax — speculative sampled
        output must equal the plain greedy decode exactly."""
        cfg, params, prompts = self._target()
        dparams = llama.init_params(jax.random.PRNGKey(9), cfg)
        ref = llama_infer.generate(
            params, cfg, prompts, max_new_tokens=8
        )
        got = llama_infer.generate_speculative(
            params, cfg, dparams, cfg, prompts, max_new_tokens=8,
            k=3, temperature=1.0, top_k=1, rng=jax.random.PRNGKey(4),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_speculative_eos_stops_and_matches_greedy_prefix(self):
        """EOS in the greedy stream ends the speculative output at the
        same position greedy generate() emits it."""
        cfg, params, prompts = self._target()
        dparams = llama.init_params(jax.random.PRNGKey(9), cfg)
        N = 14
        ref = np.asarray(llama_infer.generate(
            params, cfg, prompts, max_new_tokens=N
        ))[0]
        gen_part = ref[prompts.shape[1]:]
        # Pick an EOS token that actually occurs mid-stream.
        eos = int(gen_part[len(gen_part) // 2])
        first_at = int(np.argmax(gen_part == eos))
        got = np.asarray(llama_infer.generate_speculative(
            params, cfg, dparams, cfg, prompts, max_new_tokens=N,
            k=3, eos_token=eos,
        ))[0]
        expect = ref[: prompts.shape[1] + first_at + 1]
        np.testing.assert_array_equal(got, expect)
        assert got[-1] == eos


class TestRaggedChunkScoring:
    def test_ragged_multi_token_chunk_matches_per_token_loop(self):
        """A T>1 chunk scored at per-row offsets must produce exactly
        the logits of stepping the same tokens one at a time (the
        primitive batched speculative verify needs)."""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        B, P, T = 2, 6, 3
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size
        )
        lens = jnp.asarray([4, 6], jnp.int32)  # ragged true lengths
        chunk = jax.random.randint(
            jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size
        )

        def fresh_cache():
            c = llama_infer.init_cache(cfg, B, P + T + 2)
            _, c = llama_infer.forward_step(params, prompts, cfg, c)
            return dict(c, offset=lens)

        # chunked: one T-token ragged forward
        chunk_logits, chunk_cache = llama_infer.forward_step(
            params, chunk, cfg, fresh_cache()
        )
        # reference: the same tokens one at a time
        ref_cache = fresh_cache()
        ref_logits = []
        for t in range(T):
            lg, ref_cache = llama_infer.forward_step(
                params, chunk[:, t:t + 1], cfg, ref_cache
            )
            ref_logits.append(lg[:, 0])
        ref = jnp.stack(ref_logits, axis=1)
        np.testing.assert_allclose(
            np.asarray(chunk_logits), np.asarray(ref), atol=2e-4
        )
        np.testing.assert_array_equal(
            np.asarray(chunk_cache["offset"]), np.asarray(lens + T)
        )

    def test_ragged_chunk_int8_cache(self):
        """The T>1 ragged write keeps codes and scales in lockstep."""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        B, P, T = 2, 6, 3
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size
        )
        lens = jnp.asarray([4, 6], jnp.int32)
        chunk = jax.random.randint(
            jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size
        )
        dense = llama_infer.init_cache(cfg, B, P + T + 2)
        quant = llama_infer.init_cache(cfg, B, P + T + 2, quant_kv=True)
        _, dense = llama_infer.forward_step(params, prompts, cfg, dense)
        _, quant = llama_infer.forward_step(params, prompts, cfg, quant)
        ld, _ = llama_infer.forward_step(
            params, chunk, cfg, dict(dense, offset=lens)
        )
        lq, _ = llama_infer.forward_step(
            params, chunk, cfg, dict(quant, offset=lens)
        )
        span = float(np.max(np.abs(np.asarray(ld)))) + 1e-6
        assert float(np.max(np.abs(np.asarray(lq - ld)))) / span < 0.08


class TestBatchedSpeculative:
    def _setup(self):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        draft = llama.init_params(jax.random.PRNGKey(9), cfg)
        prompts = np.zeros((3, 7), np.int32)
        lens = np.asarray([4, 7, 5], np.int32)
        r = np.random.RandomState(0)
        for b in range(3):
            prompts[b, : lens[b]] = r.randint(1, cfg.vocab_size,
                                              size=(lens[b],))
        return cfg, params, draft, jnp.asarray(prompts), jnp.asarray(lens)

    def test_batched_greedy_matches_per_row_solo(self):
        cfg, params, draft, prompts, lens = self._setup()
        N = 9
        out, out_lens = llama_infer.generate_speculative_batched(
            params, cfg, draft, cfg, prompts, lens,
            max_new_tokens=N, k=3,
        )
        out = np.asarray(out)
        for b in range(prompts.shape[0]):
            solo = np.asarray(llama_infer.generate(
                params, cfg, prompts[b: b + 1, : int(lens[b])],
                max_new_tokens=N,
            ))[0]
            np.testing.assert_array_equal(
                out[b, : int(lens[b]) + N], solo
            )
            assert int(out_lens[b]) == int(lens[b]) + N

    def test_batched_eos_stops_rows_independently(self):
        cfg, params, draft, prompts, lens = self._setup()
        N = 12
        # find each row's greedy stream and choose row 0's 3rd token as
        # the shared EOS so different rows stop at different places.
        solo0 = np.asarray(llama_infer.generate(
            params, cfg, prompts[0:1, : int(lens[0])], max_new_tokens=N
        ))[0][int(lens[0]):]
        eos = int(solo0[2])
        out, out_lens = llama_infer.generate_speculative_batched(
            params, cfg, draft, cfg, prompts, lens,
            max_new_tokens=N, k=3, eos_token=eos,
        )
        out = np.asarray(out)
        for b in range(prompts.shape[0]):
            solo = np.asarray(llama_infer.generate(
                params, cfg, prompts[b: b + 1, : int(lens[b])],
                max_new_tokens=N,
            ))[0][int(lens[b]):]
            stop = np.argmax(solo == eos) + 1 if (solo == eos).any() \
                else N
            got_gen = out[b, int(lens[b]): int(out_lens[b])]
            np.testing.assert_array_equal(got_gen, solo[:stop])
        # row 0 definitely stopped early at its 3rd token
        assert int(out_lens[0]) == int(lens[0]) + 3

    def test_batched_sampled_and_quant_smoke(self):
        cfg, params, draft, prompts, lens = self._setup()
        stats = {}
        out, out_lens = llama_infer.generate_speculative_batched(
            params, cfg, draft, cfg, prompts, lens,
            max_new_tokens=8, k=3, temperature=0.9, quant_kv=True,
            rng=jax.random.PRNGKey(5), stats=stats,
        )
        assert out.shape == (3, prompts.shape[1] + 8)
        assert stats["rounds"] >= 1
        for b in range(3):
            assert int(out_lens[b]) == int(lens[b]) + 8
            row = np.asarray(out[b])
            assert (row[: int(out_lens[b])] < cfg.vocab_size).all()


class TestChunkedPrefillAdmission:
    def test_long_prompt_beyond_buckets_matches_solo(self):
        """A prompt longer than the largest bucket admits through the
        chunked prefill and decodes exactly like solo generate."""
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        long_p = (np.arange(20, dtype=np.int32) % 11) + 1  # > bucket 8
        short_p = (np.arange(5, dtype=np.int32) % 7) + 1
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, prompt_buckets=(8,),
        )
        outs = srv.serve([long_p, short_p], max_new_tokens=6)
        for p, got in zip([long_p, short_p], outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :], max_new_tokens=6
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_long_prompt_quant_kv(self):
        cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        long_p = (np.arange(19, dtype=np.int32) % 9) + 1
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=48, prompt_buckets=(8,),
            quant_kv=True,
        )
        outs = srv.serve([long_p], max_new_tokens=5)
        solo = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(long_p)[None, :],
            max_new_tokens=5, quant_kv=True,
        ))[0]
        np.testing.assert_array_equal(outs[0], solo)


class TestSpeculativeServer:
    """Continuous batching x speculation: DecodeServer(draft=...) steps
    all slots through speculative rounds; the per-request token law is
    unchanged."""

    def _models(self):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        dcfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        draft = llama.init_params(jax.random.PRNGKey(7), dcfg)
        return cfg, params, dcfg, draft

    def test_spec_server_matches_solo_greedy(self):
        cfg, params, dcfg, draft = self._models()
        prompts = [
            (np.arange(4, dtype=np.int32) % 7) + 1,
            (np.arange(6, dtype=np.int32) % 5) + 2,
            (np.arange(5, dtype=np.int32) % 9) + 1,
        ]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=48, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=3,
        )
        outs = srv.serve(prompts, max_new_tokens=6)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :], max_new_tokens=6
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_spec_server_eos_frees_slot_and_matches(self):
        cfg, params, dcfg, draft = self._models()
        p0 = (np.arange(4, dtype=np.int32) % 7) + 1
        solo = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(p0)[None, :], max_new_tokens=10
        ))[0][len(p0):]
        eos = int(solo[1])  # stops row 0 after 2 tokens
        prompts = [p0, (np.arange(6, dtype=np.int32) % 5) + 2]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=48, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=3, eos_token=eos,
        )
        outs = srv.serve(prompts, max_new_tokens=10)
        # row 0 ends at its EOS position
        got0 = outs[0][len(p0):]
        stop = int(np.argmax(solo == eos)) + 1
        np.testing.assert_array_equal(got0, solo[:stop])
        # row 1 (admitted into the freed slot) matches its solo decode
        solo1 = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(prompts[1])[None, :],
            max_new_tokens=10,
        ))[0]
        gen1 = solo1[len(prompts[1]):]
        stop1 = (int(np.argmax(gen1 == eos)) + 1
                 if (gen1 == eos).any() else 10)
        np.testing.assert_array_equal(
            outs[1], solo1[: len(prompts[1]) + stop1]
        )

    def test_spec_server_long_prompt_and_quant(self):
        cfg, params, dcfg, draft = self._models()
        long_p = (np.arange(20, dtype=np.int32) % 11) + 1  # > bucket 8
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=64, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=2, quant_kv=True,
        )
        outs = srv.serve([long_p], max_new_tokens=5)
        solo = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(long_p)[None, :],
            max_new_tokens=5, quant_kv=True,
        ))[0]
        np.testing.assert_array_equal(outs[0], solo)

    def test_spec_server_acceptance_telemetry(self):
        """serve() must surface the speculation-efficiency signal:
        a perfect draft (== target) accepts ~k+1 tokens per round, a
        disagreeing random draft ~1."""
        cfg, params, dcfg, draft = self._models()
        prompts = [(np.arange(4, dtype=np.int32) % 7) + 1]
        perfect = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=64, prompt_buckets=(8,),
            draft=(params, cfg), draft_k=3,
        )
        perfect.serve(prompts, max_new_tokens=12)
        assert perfect.last_stats["tokens_per_round"] > 3.0, (
            perfect.last_stats
        )
        bad = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=64, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=3,
        )
        bad.serve(prompts, max_new_tokens=12)
        assert bad.last_stats["tokens_per_round"] < 2.5, bad.last_stats
        assert bad.last_stats["rounds"] >= 1
        assert bad.last_stats["k_final"] == 3  # adapt_k off: k untouched

    def test_spec_server_adaptive_k_shrinks_on_bad_draft(self):
        """A draft that never agrees wastes k forwards per round —
        adapt_k must walk k down to 1, and the output law must stay
        exactly the target's greedy decode throughout the k changes."""
        cfg, params, dcfg, draft = self._models()
        prompts = [(np.arange(4, dtype=np.int32) % 7) + 1,
                   (np.arange(6, dtype=np.int32) % 5) + 2]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=4, adapt_k=True, adapt_every=4,
        )
        outs = srv.serve(prompts, max_new_tokens=24)
        assert srv.last_stats["k_final"] == 1, srv.last_stats
        assert srv.last_stats["k_history"][0] == 4
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :], max_new_tokens=24
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_adapt_policy_arithmetic(self):
        """The pure policy: shrink on weak acceptance, regrow on
        saturation, hard cap at draft_k (the cache headroom was sized
        with it), floor at 1.  The regrow/cap arithmetic is only
        reachable in serve() after a shrink, so it is pinned here."""
        f = llama_infer._adapt_spec_k
        # shrink: acc near 1 halves k, floors at 1
        assert f(4, 4, 1.0) == 2
        assert f(2, 4, 1.0) == 1
        assert f(1, 4, 1.0) == 1  # floor
        # hold: mid acceptance changes nothing
        assert f(4, 4, 3.0) == 4
        # regrow: saturated window doubles, capped at draft_k
        assert f(2, 4, 3.0) == 4
        assert f(1, 4, 2.0) == 2
        assert f(2, 3, 3.0) == 3  # cap clips the doubling
        assert f(4, 4, 5.0) == 4  # never past draft_k
        # shrink threshold scales with k: acc=2.0 at k=4 is weak...
        assert f(4, 4, 2.0) == 2
        # ...but at k=2 it is healthy
        assert f(2, 4, 2.0) == 2

    def test_spec_server_adaptive_k_holds_on_perfect_draft(self):
        """Draft == target saturates every window: k must stay at
        draft_k (and never exceed it — the cache headroom capacity
        check was sized with it)."""
        cfg, params, _, _ = self._models()
        prompts = [(np.arange(4, dtype=np.int32) % 7) + 1]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=96, prompt_buckets=(8,),
            draft=(params, cfg), draft_k=3, adapt_k=True, adapt_every=2,
        )
        srv.serve(prompts, max_new_tokens=20)
        assert srv.last_stats["k_final"] == 3, srv.last_stats
        assert max(srv.last_stats["k_history"]) <= 3

    def test_spec_server_streams_tokens(self):
        """on_token rides the shared emit path: speculative rounds
        stream their accepted bursts too, in continuation order."""
        cfg, params, dcfg, draft = self._models()
        prompts = [(np.arange(4, dtype=np.int32) % 7) + 1,
                   (np.arange(6, dtype=np.int32) % 5) + 2]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=48, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=3,
        )
        streamed: dict = {}
        outs = srv.serve(
            prompts, max_new_tokens=7,
            on_token=lambda r, t: streamed.setdefault(r, []).append(t),
        )
        for rid, (p, o) in enumerate(zip(prompts, outs)):
            assert streamed[rid] == list(o[len(p):]), rid

    def test_spec_server_sampled_smoke_and_seed_sensitivity(self):
        cfg, params, dcfg, draft = self._models()
        prompts = [
            (np.arange(4, dtype=np.int32) % 7) + 1,
            (np.arange(6, dtype=np.int32) % 5) + 2,
        ]

        def run(seed):
            srv = llama_infer.DecodeServer(
                params, cfg, slots=2, max_len=48, prompt_buckets=(8,),
                draft=(draft, dcfg), draft_k=3, temperature=0.9,
                seed=seed,
            )
            return srv.serve(prompts, max_new_tokens=8)

        a, b = run(1), run(2)
        for p, o in zip(prompts, a):
            assert len(o) == len(p) + 8
            assert (o < cfg.vocab_size).all() and (o >= 0).all()
            np.testing.assert_array_equal(o[: len(p)], p)
        # different seeds draw different continuations somewhere
        assert any(
            not np.array_equal(x, y) for x, y in zip(a, b)
        )


class TestTpServer:
    def test_server_with_tp_sharded_params_matches_solo(self):
        """DecodeServer over tensor-parallel-sharded params: the jitted
        step/prefill follow the data onto the mesh (GSPMD), so the
        continuous-batching output must match single-device decode
        exactly."""
        from jax.sharding import Mesh

        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=2, dtype=jnp.float32
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
        sharded, _ = llama_infer.shard_params_for_decode(
            params, cfg, mesh
        )
        prompts = [
            (np.arange(4, dtype=np.int32) % 7) + 1,
            (np.arange(6, dtype=np.int32) % 5) + 2,
        ]
        srv = llama_infer.DecodeServer(
            sharded, cfg, slots=2, max_len=32, prompt_buckets=(8,),
        )
        outs = srv.serve(prompts, max_new_tokens=5)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :], max_new_tokens=5
            ))[0]
            np.testing.assert_array_equal(got, solo)


class TestChunkedDecodeServer:
    """decode_chunk > 1: K tokens per dispatch through one lax.scan —
    K x fewer device round-trips.  The emitted law must be EXACTLY the unchunked server's
    (same per-slot math, batched differently in time)."""

    def _setup(self, n=5):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(3)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(4, 12, size=(n,))
        ]
        return cfg, params, prompts

    def test_chunked_matches_solo_greedy_with_admission_churn(self):
        cfg, params, prompts = self._setup(n=5)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, decode_chunk=4,
        )
        outs = srv.serve(prompts, max_new_tokens=11)  # not a multiple
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None], max_new_tokens=11
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_chunked_eos_mid_chunk_frees_slot_and_matches(self):
        cfg, params, prompts = self._setup(n=2)
        p0 = prompts[0]
        solo = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(p0)[None], max_new_tokens=12
        ))[0][len(p0):]
        eos = int(solo[2])  # lands mid-chunk for K=4 (position 3 of 4)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=64, decode_chunk=4,
            eos_token=eos,
        )
        outs = srv.serve(prompts, max_new_tokens=12)
        stop = int(np.argmax(solo == eos)) + 1
        np.testing.assert_array_equal(outs[0][len(p0):], solo[:stop])
        # the freed slot admitted request 1, which matches ITS solo
        solo1 = np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(prompts[1])[None],
            max_new_tokens=12,
        ))[0]
        gen1 = solo1[len(prompts[1]):]
        stop1 = (int(np.argmax(gen1 == eos)) + 1
                 if (gen1 == eos).any() else 12)
        np.testing.assert_array_equal(
            outs[1], solo1[: len(prompts[1]) + stop1]
        )

    def test_capacity_check_includes_chunk_headroom(self):
        cfg, params, _ = self._setup()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=32, decode_chunk=8,
        )
        # 16 + 10 + 7 = 33 > 32: the 7 potential overshoot writes of a
        # mid-chunk finish must be part of the capacity check.
        with pytest.raises(ValueError, match="headroom"):
            srv.serve(
                [np.ones(16, np.int32)], max_new_tokens=10,
            )
        # 15 + 10 + 7 = 32 fits.
        srv.serve([np.ones(15, np.int32)], max_new_tokens=10)

    def test_chunked_quant_kv_composes(self):
        cfg, params, prompts = self._setup(n=3)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, decode_chunk=3,
            quant_kv=True,
        )
        outs = srv.serve(prompts, max_new_tokens=9)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None], max_new_tokens=9,
                quant_kv=True,
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_sliding_window_model_serves_on_dense_cache(self):
        """A windowed (Mistral-shaped) model through the server: dense
        cache, window mask in attention — exact parity with the
        ring-cache generate() oracle, chunked dispatch included.

        The cross-LAYOUT equality (ring vs dense) is the valuable
        assertion and holds bit-exactly on the pinned CPU backend; if a
        future XLA bump reorders the ring softmax sum and flips a
        near-tied argmax, loosen to per-step logit closeness rather
        than dropping the cross-layout comparison."""
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, dtype=jnp.float32, sliding_window=5,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(7)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(4, 10, size=(4,))
        ]
        for K in (1, 4):
            srv = llama_infer.DecodeServer(
                params, cfg, slots=2, max_len=64, decode_chunk=K,
            )
            outs = srv.serve(prompts, max_new_tokens=12)
            for p, got in zip(prompts, outs):
                solo = np.asarray(llama_infer.generate(
                    params, cfg, jnp.asarray(p)[None],
                    max_new_tokens=12,
                ))[0]
                np.testing.assert_array_equal(got, solo, err_msg=str(K))

    def test_sliding_window_ragged_decode(self):
        """generate_ragged over a windowed model (dense cache): each
        row equals its own windowed generate()."""
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, dtype=jnp.float32, sliding_window=5,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = np.zeros((3, 8), np.int32)
        lens = np.array([5, 8, 3], np.int32)
        rng = np.random.RandomState(2)
        for b in range(3):
            prompts[b, :lens[b]] = rng.randint(
                1, cfg.vocab_size, lens[b]
            )
        out, olens = llama_infer.generate_ragged(
            params, cfg, jnp.asarray(prompts), jnp.asarray(lens),
            max_new_tokens=10, temperature=0.0,
        )
        out = np.asarray(out)
        for b in range(3):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(prompts[b:b+1, :lens[b]]),
                max_new_tokens=10,
            ))[0]
            np.testing.assert_array_equal(
                out[b, : int(olens[b])], solo
            )

    def test_on_token_streams_every_emitted_token_in_order(self):
        """Token streaming: the on_token callback must deliver, per
        request, exactly its continuation in order — first token
        (sampled at prefill) included — across admission churn, both
        chunked and unchunked."""
        cfg, params, prompts = self._setup(n=5)
        for K in (1, 4):
            srv = llama_infer.DecodeServer(
                params, cfg, slots=2, max_len=64, decode_chunk=K,
            )
            streamed: dict = {}
            outs = srv.serve(
                prompts, max_new_tokens=9,
                on_token=lambda r, t: streamed.setdefault(r, []).append(t),
            )
            for rid, (p, o) in enumerate(zip(prompts, outs)):
                assert streamed[rid] == list(o[len(p):]), (K, rid)

    def test_moe_model_serves_exactly(self):
        """A MoE+GQA model through the continuous-batching server
        (chunked dispatch included) — the Mixtral-shaped serving case;
        must equal its solo greedy decode exactly (fp32: argmax parity
        needs numeric equivalence, expert-capacity ample so training
        forward drops nothing)."""
        cfg = llama.LlamaConfig.tiny(
            n_layer=2, n_head=4, n_kv_head=2, num_experts=4,
            moe_every=2, dtype=jnp.float32, capacity_factor=8.0,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(5)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(4, 10, size=(4,))
        ]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, decode_chunk=4,
        )
        outs = srv.serve(prompts, max_new_tokens=8)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None], max_new_tokens=8
            ))[0]
            np.testing.assert_array_equal(got, solo)

    def test_decode_chunk_validation(self):
        cfg, params, _ = self._setup()
        with pytest.raises(ValueError, match="decode_chunk"):
            llama_infer.DecodeServer(
                params, cfg, slots=1, max_len=32, decode_chunk=0,
            )
        # decode_chunk x draft would be silently ignored — reject it.
        with pytest.raises(ValueError, match="draft"):
            llama_infer.DecodeServer(
                params, cfg, slots=1, max_len=32, decode_chunk=4,
                draft=(params, cfg),
            )


class TestPrefixCaching:
    """shared_prefix: the system prompt prefills once into a template;
    admissions copy rows and score only their own tokens.  Contract:
    results and law EXACTLY equal serve([prefix + p for p in prompts])."""

    def _setup(self, n=4):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(11)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(3, 8, size=(n,))
        ]
        return cfg, params, prompts, rng

    def _serve_pair(self, cfg, params, prompts, prefix, **kw):
        a = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,), **kw
        ).serve(prompts, max_new_tokens=8, shared_prefix=prefix)
        b = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,), **kw
        ).serve(
            [np.concatenate([prefix, p]) for p in prompts],
            max_new_tokens=8,
        )
        return a, b

    def test_long_prefix_template_path_exact(self):
        cfg, params, prompts, rng = self._setup()
        # prefix 20 > bucket 8: every admission rides the template.
        prefix = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
        a, b = self._serve_pair(cfg, params, prompts, prefix)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_short_prefix_scratch_path_exact(self):
        cfg, params, prompts, rng = self._setup()
        # combined fits one bucket: scratch prefill, same contract.
        prefix = rng.randint(1, cfg.vocab_size, 2).astype(np.int32)
        prompts = [p[:4] for p in prompts]
        a, b = self._serve_pair(cfg, params, prompts, prefix)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_prefix_composes_with_quant_kv(self):
        cfg, params, prompts, rng = self._setup(n=3)
        prefix = rng.randint(1, cfg.vocab_size, 17).astype(np.int32)
        a, b = self._serve_pair(
            cfg, params, prompts, prefix, quant_kv=True
        )
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_prefix_composes_with_speculative(self):
        cfg, params, prompts, rng = self._setup(n=3)
        dcfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        draft = llama.init_params(jax.random.PRNGKey(7), dcfg)
        prefix = rng.randint(1, cfg.vocab_size, 19).astype(np.int32)
        a, b = self._serve_pair(
            cfg, params, prompts, prefix,
            draft=(draft, dcfg), draft_k=3,
        )
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_empty_prompt_with_chunk_aligned_prefix(self):
        """n == P0 with P0 a multiple of the chunk size: the chunk-skip
        must clamp so one chunk still runs (the first sampled token
        comes from its last logits) — exactness vs the concatenated
        baseline holds."""
        cfg, params, _, rng = self._setup()
        prefix = rng.randint(1, cfg.vocab_size, 16).astype(np.int32)
        prompts = [np.zeros((0,), np.int32),
                   rng.randint(1, cfg.vocab_size, 5).astype(np.int32)]
        a, b = self._serve_pair(cfg, params, prompts, prefix)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_prefix_validation_and_capacity(self):
        cfg, params, prompts, rng = self._setup()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=32, prompt_buckets=(8,),
        )
        with pytest.raises(ValueError, match="non-empty 1-D"):
            srv.serve(prompts, max_new_tokens=4,
                      shared_prefix=np.zeros((2, 2), np.int32))
        # prefix counts against capacity
        prefix = rng.randint(1, cfg.vocab_size, 24).astype(np.int32)
        with pytest.raises(ValueError, match="prefix 24"):
            srv.serve(prompts, max_new_tokens=8, shared_prefix=prefix)


class TestServeJournaled:
    """Elastic serving primitive: append-only completion journal +
    idempotent replay (the serving analogue of flash checkpoint; the
    reference has no elastic serving story at all)."""

    def _setup(self, tmp_path, n=6):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(1)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(4, 12, size=(n,))
        ]
        journal = str(tmp_path / "results.jsonl")
        return cfg, params, prompts, journal

    def _solo(self, params, cfg, p, n=16):
        return np.asarray(llama_infer.generate(
            params, cfg, jnp.asarray(p)[None], max_new_tokens=n
        ))[0]

    def test_first_pass_serves_all_and_journals(self, tmp_path):
        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        served = []
        outs = llama_infer.serve_journaled(
            srv, prompts, 16, journal,
            on_serve=lambda r, t: served.append(r),
        )
        assert sorted(served) == list(range(6))
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, self._solo(params, cfg, p))
        with open(journal) as f:
            assert sum(1 for _ in f) == 6

    def test_replay_after_kill_skips_done_and_tolerates_torn_tail(
        self, tmp_path
    ):
        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        llama_infer.serve_journaled(srv, prompts, 16, journal)
        lines = open(journal).read().strip().split("\n")
        # Simulate a SIGKILL: 3 intact lines + one torn mid-record.
        with open(journal, "w") as f:
            f.write("\n".join(lines[:3]) + "\n" + lines[3][:20])
        srv2 = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        served = []
        outs = llama_infer.serve_journaled(
            srv2, prompts, 16, journal,
            on_serve=lambda r, t: served.append(r),
        )
        # Only the 3 lost requests (incl. the torn one) re-served.
        assert len(served) == 3, served
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, self._solo(params, cfg, p))
        # The torn tail must have been TRUNCATED before the appends: a
        # THIRD incarnation reads every record back (if the partial
        # line had concatenated with the next append, both records
        # would parse as garbage and finished work would re-serve).
        served3 = []
        llama_infer.serve_journaled(
            srv2, prompts, 16, journal,
            on_serve=lambda r, t: served3.append(r),
        )
        assert served3 == [], served3

    def test_bf16_replay_matches_first_incarnation(self, tmp_path):
        """Replay determinism holds at ANY dtype: the server's program
        shapes are fixed by construction (slots/buckets), so re-serving
        a SUBSET after a restart reproduces each remaining request
        byte-for-byte — the invariant elastic serving rests on.  (Solo
        B=1 decode is a different program shape; bf16 may differ there,
        which is irrelevant to replay.)"""
        cfg = llama.LlamaConfig.tiny(n_layer=2)  # default bf16 compute
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(1)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(int(ln),)).astype(
                np.int32
            )
            for ln in rng.randint(4, 12, size=(6,))
        ]
        journal = str(tmp_path / "results.jsonl")
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        first = llama_infer.serve_journaled(srv, prompts, 16, journal)
        lines = open(journal).read().strip().split("\n")
        with open(journal, "w") as f:  # lose the last 3 completions
            f.write("\n".join(lines[:3]) + "\n")
        srv2 = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        second = llama_infer.serve_journaled(srv2, prompts, 16, journal)
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)

    def test_sampling_server_is_rejected(self, tmp_path):
        """Replay of a sampled stream is not byte-identical across
        incarnations — the journal contract is greedy-only."""
        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, temperature=0.7,
        )
        with pytest.raises(ValueError, match="greedy"):
            llama_infer.serve_journaled(srv, prompts, 16, journal)

    def test_fully_journaled_run_serves_nothing(self, tmp_path):
        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        llama_infer.serve_journaled(srv, prompts, 16, journal)
        served = []
        outs = llama_infer.serve_journaled(
            srv, prompts, 16, journal,
            on_serve=lambda r, t: served.append(r),
        )
        assert served == []
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, self._solo(params, cfg, p))

    def test_different_prompts_invalidate_journal_records(
        self, tmp_path
    ):
        """Replay is keyed by (rid, prompt hash): reusing a journal
        path with a DIFFERENT prompt list must re-serve every changed
        request, never return the old run's completion for a colliding
        rid."""
        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        llama_infer.serve_journaled(srv, prompts, 16, journal)
        # Same rids, different prompts for rids 1 and 4.
        rng = np.random.RandomState(7)
        prompts2 = list(prompts)
        for rid in (1, 4):
            prompts2[rid] = rng.randint(
                1, cfg.vocab_size, size=(9,)
            ).astype(np.int32)
        served = []
        outs = llama_infer.serve_journaled(
            srv, prompts2, 16, journal,
            on_serve=lambda r, t: served.append(r),
        )
        assert sorted(served) == [1, 4]
        for p, o in zip(prompts2, outs):
            np.testing.assert_array_equal(o, self._solo(params, cfg, p))

    def test_legacy_records_without_hash_are_reserved(self, tmp_path):
        """Pre-hash journal lines (no "ph" field) cannot be verified
        against the current prompts, so they are ignored — stale
        results are never returned, at the cost of re-serving."""
        import json as _json

        cfg, params, prompts, journal = self._setup(tmp_path)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64
        )
        llama_infer.serve_journaled(srv, prompts, 16, journal)
        lines = [
            _json.loads(line)
            for line in open(journal).read().strip().split("\n")
        ]
        for rec in lines[:2]:
            rec.pop("ph")
        with open(journal, "w") as f:
            for rec in lines:
                f.write(_json.dumps(rec) + "\n")
        served = []
        llama_infer.serve_journaled(
            srv, prompts, 16, journal,
            on_serve=lambda r, t: served.append(r),
        )
        assert sorted(served) == sorted(
            rec["rid"] for rec in lines[:2]
        )


class TestServeStats:
    """last_stats is per-call telemetry for EVERY decode path, not
    just the speculative one — and never stale across calls."""

    def _serve(self, **server_kw):
        cfg = llama.LlamaConfig.tiny(n_layer=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(3)
        prompts = [
            rng.randint(1, cfg.vocab_size, size=(6,)).astype(np.int32)
            for _ in range(3)
        ]
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, **server_kw
        )
        srv.serve(prompts, max_new_tokens=8)
        return srv

    def test_plain_path_populates_stats(self):
        srv = self._serve()
        assert srv.last_stats["path"] == "plain"
        assert srv.last_stats["rounds"] >= 1
        # 3 requests x 8 new tokens, minus the 3 prefill-sampled
        # first tokens which are emitted at admission, not in rounds.
        assert srv.last_stats["emitted_tokens"] == 3 * 8 - 3
        assert srv.last_stats["tokens_per_round"] > 0

    def test_chunk_path_populates_stats(self):
        srv = self._serve(decode_chunk=4)
        assert srv.last_stats["path"] == "decode_chunk"
        assert srv.last_stats["rounds"] >= 1
        assert srv.last_stats["emitted_tokens"] == 3 * 8 - 3

    def test_stats_reset_between_calls(self):
        srv = self._serve()
        first = dict(srv.last_stats)
        rng = np.random.RandomState(4)
        srv.serve(
            [rng.randint(1, srv.cfg.vocab_size, size=(6,)).astype(
                np.int32
            )],
            max_new_tokens=4,
        )
        assert srv.last_stats["emitted_tokens"] == 4 - 1
        assert srv.last_stats != first


class TestIncrementalAdmission:
    """The fleet-replica surface on the REAL server (ISSUE 5):
    submit()/serve_incremental feed slots mid-decode, every request
    carries its own budget, abort() sheds an in-flight slot, and the
    results match batch serve() exactly."""

    def _server(self, slots=2):
        cfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return llama_infer.DecodeServer(
            params, cfg, slots=slots, max_len=48,
            prompt_buckets=(8, 16),
        ), cfg

    def test_incremental_matches_batch_with_per_request_budgets(self):
        srv, cfg = self._server()
        rng = np.random.RandomState(5)
        prompts = [
            rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in (3, 7, 5, 4)
        ]
        budgets = [4, 6, 3, 5]
        finished = {}
        fed = [0]

        def tick():
            # Feed one request per tick while any remain; stop once
            # everything submitted AND finished.
            if fed[0] < len(prompts):
                srv.submit(fed[0], prompts[fed[0]], budgets[fed[0]])
                fed[0] += 1
                return True
            return len(finished) < len(prompts)

        res = srv.serve_incremental(
            tick=tick, on_finish=lambda r, t: finished.__setitem__(r, t),
        )
        assert res == {}  # incremental mode retains nothing
        assert set(finished) == {0, 1, 2, 3}
        for i, p in enumerate(prompts):
            # Each equals its solo batch-serve decode at ITS budget.
            solo = srv.serve([p], max_new_tokens=budgets[i])[0]
            np.testing.assert_array_equal(finished[i], solo)
            assert len(finished[i]) == len(p) + budgets[i]

    def test_abort_sheds_in_flight_slot_and_readmits(self):
        srv, cfg = self._server(slots=1)
        rng = np.random.RandomState(6)
        long_p = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
        short_p = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
        finished = {}
        state = {"fed": False, "aborted": False}

        def tick():
            if not state["fed"]:
                srv.submit("long", long_p, 30)
                srv.submit("short", short_p, 3)
                state["fed"] = True
                return True
            if not state["aborted"] and "long" in srv.active_rids():
                # Shed the long request mid-decode: the single slot
                # must free for "short".
                assert srv.abort("long")
                state["aborted"] = True
                return True
            return "short" not in finished

        srv.serve_incremental(
            tick=tick,
            on_finish=lambda r, t: finished.__setitem__(r, t),
        )
        # The aborted request never finished; the short one did, on
        # the slot the abort freed.
        assert set(finished) == {"short"}
        solo = srv.serve([short_p], max_new_tokens=3)[0]
        np.testing.assert_array_equal(finished["short"], solo)

    def test_submit_capacity_check_rejects_immediately(self):
        srv, cfg = self._server()
        with pytest.raises(ValueError, match="exceeds max_len"):
            srv.submit("x", np.arange(1, 9, dtype=np.int32), 100)


class TestKvSegment:
    """pack/unpack_kv_segment: the prefill->decode wire format
    (ISSUE 8).  Torn bytes are rejected by the embedded CRC; the fp32
    path round-trips byte-exact."""

    def _layers(self, quant=False, layers=2, n=5, KV=2, D=4):
        rng = np.random.RandomState(3)
        out = []
        for _ in range(layers):
            lay = {}
            if quant:
                lay["k"] = rng.randint(
                    -127, 127, (1, KV, n, D)).astype(np.int8)
                lay["v"] = rng.randint(
                    -127, 127, (1, KV, n, D)).astype(np.int8)
                lay["ks"] = rng.rand(1, KV, n).astype(np.float32)
                lay["vs"] = rng.rand(1, KV, n).astype(np.float32)
            else:
                lay["k"] = rng.randn(1, KV, n, D).astype(np.float32)
                lay["v"] = rng.randn(1, KV, n, D).astype(np.float32)
            out.append(lay)
        return out

    def test_fp32_roundtrip_byte_exact(self):
        layers = self._layers()
        payload, fp32_bytes = llama_infer.pack_kv_segment(
            layers, 5, 42, False
        )
        assert fp32_bytes == 2 * 2 * (1 * 2 * 5 * 4) * 4
        seg = llama_infer.unpack_kv_segment(payload)
        assert seg["n"] == 5 and seg["first"] == 42
        assert seg["quant"] is False
        for got, want in zip(seg["layers"], layers):
            for kk in want:
                np.testing.assert_array_equal(got[kk], want[kk])
                assert got[kk].dtype == want[kk].dtype

    def test_quant_payload_under_half_of_fp32(self):
        layers = self._layers(quant=True, D=16, n=8)
        payload, fp32_bytes = llama_infer.pack_kv_segment(
            layers, 8, 1, True
        )
        # int8 codes + f32 per-slot scales: 1/4 + 1/D of the fp32
        # segment, plus the msgpack envelope — well under half.
        assert len(payload) < 0.5 * fp32_bytes

    def test_torn_payload_rejected_everywhere(self):
        payload, _ = llama_infer.pack_kv_segment(
            self._layers(), 5, 0, False
        )
        for cut in (len(payload) // 3, len(payload) // 2,
                    len(payload) - 5):
            torn = bytearray(payload)
            torn[cut] ^= 0xFF
            with pytest.raises(llama_infer.KvSegmentError):
                llama_infer.unpack_kv_segment(bytes(torn))
        with pytest.raises(llama_infer.KvSegmentError):
            llama_infer.unpack_kv_segment(payload[: len(payload) // 2])
        with pytest.raises(llama_infer.KvSegmentError):
            llama_infer.unpack_kv_segment(b"garbage")


class TestKvHandoff:
    """DecodeServer.prefill_request/export_kv/import_kv: the
    disaggregated admission path must reproduce the unified decode."""

    def _setup(self, quant=False):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(7)

        def server(slots=1):
            return llama_infer.DecodeServer(
                params, cfg, slots=slots, max_len=64,
                prompt_buckets=(8,), seed=0, quant_kv=quant,
            )

        prompt = rng.randint(1, cfg.vocab_size, 13).astype(np.int32)
        return cfg, server, prompt

    def _drain(self, srv, out):
        srv.serve_incremental(
            tick=lambda: bool(srv.pending_count() or srv.active_rids()),
            on_finish=lambda r, t: out.__setitem__(r, t),
        )

    def test_fp32_export_is_byte_exact_and_decode_matches(self):
        cfg, server, prompt = self._setup()
        pf = server()
        pf.prefill_request("x", prompt, 6)
        staged = [
            {kk: np.array(v) for kk, v in lay.items()}
            for lay in pf._kv_exports["x"]["layers"]
        ]
        payload, fp32_bytes = pf.export_kv("x")
        assert fp32_bytes > 0
        seg = llama_infer.unpack_kv_segment(payload)
        for got, want in zip(seg["layers"], staged):
            for kk in want:
                np.testing.assert_array_equal(got[kk], want[kk])
        # export consumed the staged entry
        with pytest.raises(ValueError, match="no staged prefill"):
            pf.export_kv("x")
        dec = server()
        dec.import_kv("x", payload, prompt, 6)
        got = {}
        self._drain(dec, got)
        ref = server().serve([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got["x"], ref)

    def test_quant_export_within_dequant_tolerance(self):
        cfg, serverq, prompt = self._setup(quant=True)
        _, serverf, _ = self._setup(quant=False)
        pf_q = serverq()
        pf_f = serverf()
        pf_q.prefill_request("x", prompt, 6)
        pf_f.prefill_request("x", prompt, 6)
        seg_q = llama_infer.unpack_kv_segment(pf_q.export_kv("x")[0])
        seg_f = llama_infer.unpack_kv_segment(pf_f.export_kv("x")[0])
        for li, (lq, lf) in enumerate(
            zip(seg_q["layers"], seg_f["layers"])
        ):
            for code_k, scale_k in (("k", "ks"), ("v", "vs")):
                deq = lq[code_k].astype(np.float32) * \
                    lq[scale_k][..., None]
                if li == 0:
                    # Layer 0 sees identical inputs in both servers:
                    # absmax int8 bounds |err| <= scale/2 elementwise.
                    bound = lq[scale_k][..., None] * 0.51 + 1e-6
                    assert np.all(np.abs(deq - lf[code_k]) <= bound)
                else:
                    # Deeper layers additionally carry the quantized
                    # attention's activation drift — small, not
                    # scale-bounded.
                    np.testing.assert_allclose(
                        deq, lf[code_k], atol=2e-2
                    )
        # And the quant disagg decode equals the quant unified decode.
        pf2 = serverq()
        pf2.prefill_request("y", prompt, 6)
        payload, fp32_bytes = pf2.export_kv("y")
        assert len(payload) < 0.5 * fp32_bytes
        dec = serverq()
        dec.import_kv("y", payload, prompt, 6)
        got = {}
        self._drain(dec, got)
        ref = serverq().serve([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got["y"], ref)

    def test_import_rejects_torn_and_mismatched_segments(self):
        cfg, server, prompt = self._setup()
        pf = server()
        pf.prefill_request("x", prompt, 6)
        payload, _ = pf.export_kv("x")
        dec = server()
        torn = bytearray(payload)
        torn[len(torn) // 2] ^= 0xFF
        with pytest.raises(llama_infer.KvSegmentError):
            dec.import_kv("x", bytes(torn), prompt, 6)
        # Prompt/segment length mismatch: never admit.
        with pytest.raises(llama_infer.KvSegmentError, match="tokens"):
            dec.import_kv("x", payload, prompt[:-1], 6)
        # Quant-config mismatch: never admit.
        _, serverq, _ = self._setup(quant=True)
        with pytest.raises(llama_infer.KvSegmentError, match="quant"):
            serverq().import_kv("x", payload, prompt, 6)
        # A structurally-valid payload whose meta declares the wrong
        # array rank (3-d "k") must reject at validation — the
        # expectation comes from the server's reference layout, never
        # from the payload itself.
        bad_layers = [
            {"k": np.zeros((1, cfg.n_kv_head, len(prompt)), np.float32),
             "v": np.zeros((1, cfg.n_kv_head, len(prompt)), np.float32)}
            for _ in range(cfg.n_layer)
        ]
        bad, _ = llama_infer.pack_kv_segment(
            bad_layers, len(prompt), 0, False
        )
        with pytest.raises(llama_infer.KvSegmentError, match="shape"):
            dec.import_kv("x", bad, prompt, 6)
        assert dec.pending_count() == 0

    def test_prefill_uses_prefix_template(self):
        """A prefix-carrying prefill rides the template store (hit on
        the second request) and the result is unchanged."""
        cfg, server, prompt = self._setup()
        rng = np.random.RandomState(9)
        prefix = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
        full = np.concatenate([prefix, prompt])
        pf = server()
        pf.prefill_request("a", full, 6, prefix_len=20)
        pf.prefill_request("b", full, 6, prefix_len=20)
        assert pf.prefix_misses == 1 and pf.prefix_hits == 1
        assert pf.warm_prefix_fps() == [
            llama_infer.prefix_fingerprint(prefix)
        ]
        payload, _ = pf.export_kv("b")
        dec = server()
        dec.import_kv("b", payload, full, 6)
        got = {}
        self._drain(dec, got)
        ref = server().serve([full], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got["b"], ref)


class TestPrefixStore:
    """The incremental path's per-fingerprint template store: warm
    admissions are byte-identical to untemplated serving, the LRU is
    bounded, and a fingerprint collision rebuilds instead of serving
    another prefix's rows."""

    def _setup(self, cap=2):
        cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, prompt_buckets=(8,),
            seed=0, prefix_cache_cap=cap,
        )
        rng = np.random.RandomState(5)
        return cfg, params, srv, rng

    def _drain(self, srv, out):
        srv.serve_incremental(
            tick=lambda: bool(srv.pending_count() or srv.active_rids()),
            on_finish=lambda r, t: out.__setitem__(r, t),
        )

    def test_incremental_prefix_matches_untemplated(self):
        cfg, params, srv, rng = self._setup()
        prefix = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
        own = [rng.randint(1, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(3)]
        got = {}
        for i, p in enumerate(own):
            srv.submit(f"q{i}", np.concatenate([prefix, p]), 6,
                       prefix_len=20)
        self._drain(srv, got)
        assert srv.prefix_misses == 1 and srv.prefix_hits == 2
        ref_srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, prompt_buckets=(8,),
            seed=0,
        )
        refs = ref_srv.serve(
            [np.concatenate([prefix, p]) for p in own],
            max_new_tokens=6,
        )
        for i in range(3):
            np.testing.assert_array_equal(got[f"q{i}"], refs[i])

    def test_lru_bounded_and_cleared(self):
        cfg, params, srv, rng = self._setup(cap=2)
        fps = []
        for i in range(3):
            prefix = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
            fps.append(llama_infer.prefix_fingerprint(prefix))
            srv._ensure_prefix_template(prefix, fps[-1])
        assert srv.warm_prefix_fps() == fps[1:]  # oldest evicted
        srv.clear_prefix_templates()
        assert srv.warm_prefix_fps() == []
        assert srv.prefix_hits == 0 and srv.prefix_misses == 0

    def test_fingerprint_collision_rebuilds(self):
        """An entry whose stored tokens mismatch the claimed
        fingerprint (collision / stale reuse) must be rebuilt, never
        served."""
        cfg, params, srv, rng = self._setup()
        p1 = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
        p2 = rng.randint(1, cfg.vocab_size, 20).astype(np.int32)
        srv._ensure_prefix_template(p1, "colliding-fp")
        entry = srv._ensure_prefix_template(p2, "colliding-fp")
        assert srv.prefix_misses == 2 and srv.prefix_hits == 0
        np.testing.assert_array_equal(entry["prefix"], p2)


class TestPagedKv:
    """ISSUE 19: the paged KV arena (block pool + per-request block
    table) must be byte-invisible to greedy decode — every serving
    surface reproduces the slotted server's outputs exactly — while
    admitting by blocks actually needed and freeing at block
    granularity (abort, CoW prefix sharing, preemption)."""

    BS = 8
    _model_cache: list = []

    def _models(self):
        if not self._model_cache:
            cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
            params = llama.init_params(jax.random.PRNGKey(0), cfg)
            self._model_cache.append((cfg, params))
        return self._model_cache[0]

    def _prompts(self, cfg, lens, seed=7):
        rng = np.random.RandomState(seed)
        return [rng.randint(1, cfg.vocab_size, L).astype(np.int32)
                for L in lens]

    def _pair(self, cfg, params, **kw):
        """(slotted, paged) servers with identical serving config.
        The base matches the file's dominant slotted shape (slots=2,
        max_len=64, bucket 8) so the reference side reuses compiles
        from the earlier suites."""
        base = dict(slots=2, max_len=64, prompt_buckets=(8,), seed=0)
        base.update(kw)

        def mk(paged):
            return llama_infer.DecodeServer(
                params, cfg, paged=paged, block_size=self.BS, **base
            )

        return mk(False), mk(True)

    def _assert_parity(self, slotted, paged, prompts, mnt,
                       all_free=True, **serve_kw):
        ref = slotted.serve(prompts, max_new_tokens=mnt, **serve_kw)
        got = paged.serve(prompts, max_new_tokens=mnt, **serve_kw)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        arena = paged.kv_arena
        assert arena.conserved()
        if all_free:
            assert arena.free_blocks == arena.n_blocks  # all returned

    def test_greedy_parity_plain(self):
        cfg, params = self._models()
        slotted, paged = self._pair(cfg, params)
        self._assert_parity(
            slotted, paged, self._prompts(cfg, [5, 13, 22]), 8
        )

    def test_greedy_parity_chunked(self):
        cfg, params = self._models()
        slotted, paged = self._pair(cfg, params, decode_chunk=3)
        self._assert_parity(
            slotted, paged, self._prompts(cfg, [6, 14, 21]), 7
        )

    def test_greedy_parity_quant_kv(self):
        cfg, params = self._models()
        # max_len=32: the quant suite's slotted shape (compile reuse).
        slotted, paged = self._pair(cfg, params, quant_kv=True,
                                    max_len=32)
        self._assert_parity(
            slotted, paged, self._prompts(cfg, [5, 13, 22]), 8
        )

    def test_greedy_parity_spec_draft(self):
        cfg, params = self._models()
        dcfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
        draft = llama.init_params(jax.random.PRNGKey(7), dcfg)
        # max_len=48: the spec suite's slotted shape (compile reuse).
        slotted, paged = self._pair(
            cfg, params, draft=(draft, dcfg), draft_k=3, max_len=48
        )
        self._assert_parity(
            slotted, paged, self._prompts(cfg, [4, 6, 5]), 6
        )

    def test_greedy_parity_shared_prefix_template(self):
        """Batch-mode shared prefix: the paged template SHARES whole
        prefix blocks copy-on-write instead of copying rows."""
        cfg, params = self._models()
        slotted, paged = self._pair(cfg, params, max_len=64)
        prefix = self._prompts(cfg, [17], seed=3)[0]
        # all_free=False: the batch template's blocks stay HELD for
        # the run (a later admission may still share them); the next
        # serve() resets the arena.
        self._assert_parity(
            slotted, paged, self._prompts(cfg, [6, 9, 5]), 8,
            all_free=False, shared_prefix=prefix,
        )

    @pytest.mark.parametrize("workload,lens", [
        ("uniform", [20, 24, 22, 26, 21, 25, 23, 20]),
        ("longtail", [4, 5, 4, 40, 6, 4, 30, 5]),
    ])
    def test_matched_memory_buys_seats_not_different_outputs(
        self, workload, lens
    ):
        """At the KV memory of two slab seats (2 x 64 tokens) the block
        pool holds 16 blocks of 8 and seats six: the same bytes, more
        requests in flight when they are short, the same greedy
        outputs on uniform and on long-tail lengths."""
        cfg, params = self._models()
        kw = dict(max_len=64, prompt_buckets=(8, 32, 48), seed=0)
        slab = llama_infer.DecodeServer(params, cfg, slots=2, **kw)
        pool = llama_infer.DecodeServer(
            params, cfg, slots=6, paged=True, block_size=self.BS,
            pool_blocks=2 * (64 // self.BS), **kw)
        assert pool.pool_blocks * self.BS == slab.slots * 64
        assert pool.slots > slab.slots
        prompts = self._prompts(cfg, lens, seed=11)
        ref = slab.serve(prompts, max_new_tokens=6)
        seated = []
        outs = {}
        for i, p in enumerate(prompts):
            pool.submit(i, p, 6)

        def tick():
            seated.append(len(pool.active_rids()))
            return False  # drain: finish everything, then return

        pool.serve_incremental(
            tick=tick, on_finish=lambda rid, toks: outs.__setitem__(
                rid, np.asarray(toks)))
        for i, r in enumerate(ref):
            np.testing.assert_array_equal(outs[i], np.asarray(r))
        arena = pool.kv_arena
        assert arena.conserved()
        assert arena.free_blocks == arena.n_blocks
        if workload == "longtail":
            # short requests take 2 blocks each: more of them are in
            # flight at once than the slab has seats
            assert max(seated) > slab.slots

    def test_cow_divergence_keeps_sharer_byte_identical(self):
        """Two requests share a prefix template's blocks; each
        diverges into its own copied boundary block and the other's
        output is byte-identical to its solo decode (the CoW
        correctness pin)."""
        cfg, params = self._models()
        _, srv = self._pair(cfg, params, max_len=64)
        prefix = self._prompts(cfg, [16], seed=5)[0]
        tails = self._prompts(cfg, [5, 7], seed=6)
        fulls = [np.concatenate([prefix, t]) for t in tails]
        solo = [
            llama_infer.DecodeServer(
                params, cfg, slots=1, max_len=64, prompt_buckets=(8,),
                seed=0,
            ).serve([f], max_new_tokens=8)[0]
            for f in fulls
        ]
        got = {}
        for i, f in enumerate(fulls):
            srv.submit(i, f, 8, prefix_len=len(prefix))
        srv.serve_incremental(
            tick=lambda: bool(
                srv.pending_count() or srv.active_rids()
            ),
            on_finish=lambda r, t: got.__setitem__(r, t),
        )
        # The second admission rode the warm per-fingerprint store
        # (share + boundary copy), not a fresh prefill.
        assert srv.prefix_hits >= 1
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(solo[i])
            )
        assert srv.kv_arena.conserved()

    def test_tight_pool_preempts_and_stays_byte_identical(self):
        """A pool too small for every admitted request to grow to its
        full length must preempt (youngest first) and re-decode — and
        still emit exactly the slotted outputs, no duplicates through
        on_token."""
        cfg, params = self._models()
        slotted, paged = self._pair(
            cfg, params, slots=3, pool_blocks=6
        )
        prompts = self._prompts(cfg, [10, 9, 8], seed=9)
        streamed = {}
        ref = slotted.serve(prompts, max_new_tokens=8)
        got = paged.serve(
            prompts, max_new_tokens=8,
            on_token=lambda r, t: streamed.setdefault(r, []).append(t),
        )
        assert paged.preemptions > 0
        for i, (r, g) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
            # The token stream matches the continuation exactly —
            # a preempted request's re-decode never double-emits.
            np.testing.assert_array_equal(
                np.asarray(streamed[i]),
                np.asarray(g)[len(prompts[i]):],
            )
        assert paged.kv_arena.conserved()

    def test_abort_frees_blocks_and_readmits_within_a_round(self):
        """ISSUE 19c: an abort returns the victim's blocks to the pool
        instantly — a request that was blocked on memory seats within
        one loop iteration of the shed."""
        cfg, params = self._models()
        _, srv = self._pair(cfg, params, pool_blocks=5)
        a, b = self._prompts(cfg, [30, 10], seed=11)
        solo_b = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=48, prompt_buckets=(8,),
            seed=0,
        ).serve([b], max_new_tokens=6)[0]
        srv.submit("A", a, 8)
        srv.submit("B", b, 6)
        ticks = [0]
        abort_at = {}
        b_seated = {}
        got = {}

        def tick():
            ticks[0] += 1
            live = {
                r for s, r in enumerate(srv._live_slot_req)
                if srv._live_active[s]
            }
            if "B" in live and not b_seated:
                b_seated["tick"] = ticks[0]
            if ticks[0] == 3:
                # A holds 4 of 5 blocks; B (needs 2) cannot seat.
                assert "B" not in live
                abort_at["tick"] = ticks[0]
                srv.abort("A")
            return False  # drain: finish B, then return

        srv.serve_incremental(
            tick=tick, on_finish=lambda r, t: got.__setitem__(r, t)
        )
        assert "A" not in got  # aborted: partial output discarded
        np.testing.assert_array_equal(
            np.asarray(got["B"]), np.asarray(solo_b)
        )
        # The shed freed blocks the SAME iteration; B seats at the
        # very next admission pass.
        assert b_seated["tick"] <= abort_at["tick"] + 1
        arena = srv.kv_arena
        assert arena.conserved()
        assert arena.free_blocks == arena.n_blocks

    def test_block_leak_chaos_is_repaired_and_conserved(self):
        """Chaos `serving.block_leak` drops a free on the release
        path; the serve loop's scavenge rebuilds the free list from
        the refcounts — the conservation law `free + used == pool`
        holds after any chaos run."""
        from dlrover_tpu import chaos

        cfg, params = self._models()
        _, srv = self._pair(cfg, params)
        chaos.configure("serving.block_leak:p=1,times=1,seed=5")
        try:
            srv.serve(
                self._prompts(cfg, [5, 9, 13], seed=13),
                max_new_tokens=6,
            )
        finally:
            chaos.reset()
        arena = srv.kv_arena
        assert arena.leaks_repaired >= 1
        assert arena.conserved()
        # free + table-mapped blocks == pool (all tables empty here).
        assert arena.free_blocks + int(arena.lens.sum()) \
            == arena.n_blocks

    def test_paged_handoff_ships_block_lists(self):
        """Disagg handoff from a paged prefill server frames the
        segment as a per-block list (CRC per block); a paged decode
        server imports it straight into pool blocks and reproduces
        the unified slotted decode.  Dense segments stay importable
        (cross-mode fleet)."""
        from dlrover_tpu.serving import kvseg

        cfg, params = self._models()
        prompt = self._prompts(cfg, [13], seed=15)[0]

        def server(paged):
            return llama_infer.DecodeServer(
                params, cfg, slots=1, max_len=48, prompt_buckets=(8,),
                seed=0, paged=paged, block_size=self.BS,
            )

        ref = server(False).serve([prompt], max_new_tokens=6)[0]

        def drain(dec):
            out = {}
            dec.serve_incremental(
                tick=lambda: bool(
                    dec.pending_count() or dec.active_rids()
                ),
                on_finish=lambda r, t: out.__setitem__(r, t),
            )
            return out

        pf = server(True)
        pf.prefill_request("x", prompt, 6)
        payload, _ = pf.export_kv("x")
        # Block framing is visible in the segment meta (and to the
        # kvseg store's telemetry peek) without touching array bytes.
        assert kvseg.segment_block_info(payload) == (
            self.BS, -(-len(prompt) // self.BS)
        )
        dec = server(True)
        dec.import_kv("x", payload, prompt, 6)
        np.testing.assert_array_equal(
            np.asarray(drain(dec)["x"]), np.asarray(ref)
        )
        # A torn BLOCK is caught by the per-block CRC at unpack.
        torn = bytearray(payload)
        torn[len(torn) // 2] ^= 0xFF
        with pytest.raises(llama_infer.KvSegmentError):
            server(True).import_kv("x", bytes(torn), prompt, 6)
        # Cross-mode: a slotted prefill's monolithic segment imports
        # into a paged decode server unchanged.
        pf_dense = server(False)
        pf_dense.prefill_request("y", prompt, 6)
        dense_payload, _ = pf_dense.export_kv("y")
        assert kvseg.segment_block_info(dense_payload) is None
        dec2 = server(True)
        dec2.import_kv("y", dense_payload, prompt, 6)
        np.testing.assert_array_equal(
            np.asarray(drain(dec2)["y"]), np.asarray(ref)
        )

    def test_paged_stats_report_block_pool(self):
        """last_stats under paged mode reports block-pool occupancy
        (tokens held, not slots seated) plus the pool gauges the
        replica poll ships to the gateway."""
        cfg, params = self._models()
        _, srv = self._pair(cfg, params)
        assert srv.block_stats() == {
            "total_blocks": srv.pool_blocks,
            "free_blocks": srv.pool_blocks,
            "block_occupancy": 0.0,
            "preemptions": 0,
        }
        seen = []

        def tick():
            st = srv.last_stats
            if st.get("paged"):
                seen.append(
                    (st["occupancy"], st["free_blocks"],
                     st["total_blocks"])
                )
            return bool(srv.pending_count() or srv.active_rids())

        srv.submit("r", self._prompts(cfg, [9], seed=17)[0], 6)
        srv.serve_incremental(tick=tick)
        mid = [s for s in seen if s[0] > 0]
        assert mid, "no in-flight stats sample saw blocks held"
        occ, free, total = mid[0]
        assert total == srv.pool_blocks
        assert occ == pytest.approx((total - free) / total)

    def test_paged_capacity_guards(self):
        """max_len must align to block_size and a request that could
        never fit the whole pool rejects at submit."""
        cfg, params = self._models()
        with pytest.raises(ValueError, match="multiple of block_size"):
            llama_infer.DecodeServer(
                params, cfg, slots=1, max_len=45, prompt_buckets=(8,),
                paged=True, block_size=self.BS,
            )
        _, srv = self._pair(cfg, params, pool_blocks=3)
        with pytest.raises(ValueError, match="KV blocks"):
            srv.submit("big", self._prompts(cfg, [30])[0], 8)
