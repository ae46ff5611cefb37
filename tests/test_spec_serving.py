"""Speculative serving tests (ISSUE 11): per-request adaptive k, the
remote draft role, spec-aware routing, and the draft-kill degradation
contract.

Two layers:

- pure/protocol units (numpy + jax-free control plane): the per-row
  width truncation law against the scalar executable spec, the
  ``_spec_k_request`` policy arithmetic, proposal-bundle CRC
  verification, gateway spec routing / counter folding / pool signals;
- model-backed integration (tiny float32 llama): spec-mode incremental
  serving is BYTE-IDENTICAL to plain incremental serving under greedy
  decoding, a bad draft walks every stream back to plain decode, and a
  draft death mid-fleet degrades the targets to plain while every
  in-flight request completes exactly-once.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu import chaos
from dlrover_tpu.common import messages as M
from dlrover_tpu.models import llama, llama_infer
from dlrover_tpu.serving import (
    DraftReplicaRunner,
    DraftUnavailable,
    DraftWorker,
    GatewayConfig,
    GatewayCore,
    LoopbackTransport,
    RemoteDraftClient,
    ReplicaRunner,
    ScalePolicy,
    ScaleState,
    decide,
    decide_pools,
)
from dlrover_tpu.serving.draft import (
    handle_draft,
    pack_proposals,
    unpack_proposals,
)

pytestmark = pytest.mark.spec


# ---------------------------------------------------------------------------
# pure acceptance/width law
# ---------------------------------------------------------------------------


class TestPerRowWidthLaw:
    def test_k_row_truncation_matches_scalar_spec_at_each_width(self):
        """Monte-Carlo (satellite): a row speculating at width kb under
        ``k_row`` must follow EXACTLY the scalar spec's law for a
        kb-proposal round — accepted-length distribution and the
        round's first emitted token — whatever the full batch width is.
        """
        rng = np.random.default_rng(0)
        V, k = 8, 3
        p = rng.dirichlet(np.ones(V), size=k + 1)
        q = rng.dirichlet(np.ones(V) * 0.3, size=k)
        B = 12  # 3 rows per width 0..3
        k_row = np.array([0, 1, 2, 3] * 3)
        pb = np.broadcast_to(p, (B, k + 1, V))
        qb = np.broadcast_to(q, (B, k, V))
        done = np.zeros(B, bool)
        N = 4000
        jcounts = {kb: np.zeros(k + 1) for kb in range(k + 1)}
        first_counts = {kb: np.zeros(V) for kb in range(k + 1)}
        for _ in range(N):
            d = np.stack(
                [rng.choice(V, p=q[i], size=B) for i in range(k)],
                axis=1,
            )
            j, tok = llama_infer._spec_accept_batch(
                pb, qb, d, done, rng, k_row=k_row
            )
            assert (j <= k_row).all()
            for b in range(B):
                kb = int(k_row[b])
                jcounts[kb][j[b]] += 1
                first = d[b, 0] if j[b] >= 1 else tok[b]
                first_counts[kb][first] += 1
        # Scalar reference at each width (kb=0 is plain target
        # sampling from p[0]).
        for kb in range(k + 1):
            n = jcounts[kb].sum()
            emp_first = first_counts[kb] / n
            assert np.max(np.abs(emp_first - p[0])) < 0.02, (
                kb, emp_first, p[0],
            )
            if kb == 0:
                assert jcounts[kb][0] == n
                continue
            sc = np.zeros(k + 1)
            for _ in range(12000):
                d = np.array(
                    [rng.choice(V, p=q[i]) for i in range(kb)]
                )
                j, _ = llama_infer._spec_accept_round(
                    p[: kb + 1], q[:kb], d, rng
                )
                sc[j] += 1
            assert np.max(np.abs(jcounts[kb] / n - sc / 12000)) < 0.03, (
                kb, jcounts[kb] / n, sc / 12000,
            )

    def test_spec_k_request_policy_arithmetic(self):
        f = llama_infer._spec_k_request
        # unmeasured: optimistic full width
        assert f(0.0, 4, 3.4) == 4
        # below break-even: plain decode
        assert f(1.0, 4, 3.4) == 0
        assert f(3.3, 4, 3.4) == 0
        # above: width the stream actually fills, capped at draft_k
        assert f(3.5, 4, 3.4) == 3
        assert f(4.9, 4, 3.4) == 4
        assert f(9.0, 4, 3.4) == 4
        assert f(3.5, 2, 3.4) == 2  # cap
        assert f(3.4, 4, 3.4) == 3  # at threshold: speculate


# ---------------------------------------------------------------------------
# proposal bundle protocol (jax-free)
# ---------------------------------------------------------------------------


class TestProposalBundles:
    def test_roundtrip_with_and_without_probs(self):
        q = np.arange(12, dtype=np.float32).reshape(3, 4)
        props = {
            "a": {"d": [1, 2, 3], "q": q},
            "b": {"d": [7, 8, 9], "q": None},
        }
        out = unpack_proposals(pack_proposals(props))
        assert out["a"]["d"] == [1, 2, 3]
        np.testing.assert_array_equal(out["a"]["q"], q)
        assert out["b"]["d"] == [7, 8, 9] and out["b"]["q"] is None

    def test_torn_bundle_rejected(self):
        payload = bytearray(pack_proposals({"a": {"d": [1], "q": None}}))
        payload[len(payload) // 2] ^= 0xFF
        with pytest.raises(DraftUnavailable):
            unpack_proposals(bytes(payload))
        with pytest.raises(DraftUnavailable):
            unpack_proposals(b"junk")

    def test_client_converges_failures_on_draft_unavailable(self):
        class Boom:
            def call(self, msg, **kw):
                raise RuntimeError("dead peer")

        with pytest.raises(DraftUnavailable):
            RemoteDraftClient(Boom()).propose([], 4)

        class Refuses:
            def call(self, msg, **kw):
                return M.DraftProposals(found=False, reason="rolling")

        with pytest.raises(DraftUnavailable):
            RemoteDraftClient(Refuses()).propose([], 4)


# ---------------------------------------------------------------------------
# gateway control plane (jax-free)
# ---------------------------------------------------------------------------


def _mk_core(**cfg):
    cfg.setdefault("spec_decode_min_tokens", 8)
    return GatewayCore(GatewayConfig(**cfg))


class TestSpecRouting:
    def test_long_decode_prefers_spec_replica(self):
        core = _mk_core()
        core.register("plain", 2)
        core.register("fast", 2, spec=True)
        core.submit("r1", [1, 2], 32)  # long: >= spec_decode_min_tokens
        # The plain replica polls first: deferred for the spec one.
        g = core.poll("plain", 2, [])
        assert g.requests == []
        g = core.poll("fast", 2, [])
        assert [r.req_id for r in g.requests] == ["r1"]
        assert core.counters["spec_grants"] == 1

    def test_short_decode_routes_anywhere(self):
        core = _mk_core()
        core.register("plain", 2)
        core.register("fast", 2, spec=True)
        core.submit("r1", [1, 2], 4)  # short: below the threshold
        g = core.poll("plain", 2, [])
        assert [r.req_id for r in g.requests] == ["r1"]
        assert core.counters["spec_grants"] == 0
        assert core.counters["spec_bypass"] == 0

    def test_saturated_spec_capacity_is_bypassed(self):
        core = _mk_core()
        core.register("plain", 2)
        core.register("fast", 1, spec=True)
        core.submit("r1", [1, 2], 32)
        core.submit("r2", [3, 4], 32)
        g = core.poll("fast", 1, [])
        assert [r.req_id for r in g.requests] == ["r1"]
        # fast is now slot-saturated: plain takes the second long one.
        g = core.poll("plain", 2, ["__none__"])
        assert [r.req_id for r in g.requests] == ["r2"]
        assert core.counters["spec_bypass"] == 1

    def test_reserve_window_expiry_bypasses(self):
        clock = [0.0]
        core = GatewayCore(
            GatewayConfig(spec_decode_min_tokens=8, spec_reserve_s=2.0),
            clock=lambda: clock[0],
        )
        core.register("plain", 2)
        core.register("fast", 2, spec=True)
        core.submit("rq", [1], 32)
        assert core.poll("plain", 2, []).requests == []
        clock[0] += 3.0
        g = core.poll("plain", 2, [])
        assert [r.req_id for r in g.requests] == ["rq"]
        assert core.counters["spec_bypass"] == 1

    def test_deferred_long_request_never_blocks_queue_behind(self):
        core = _mk_core()
        core.register("plain", 2)
        core.register("fast", 2, spec=True)
        core.submit("long", [1], 32)
        core.submit("short", [2], 4)
        g = core.poll("plain", 2, [])
        assert [r.req_id for r in g.requests] == ["short"]

    def test_routing_off_by_default(self):
        core = GatewayCore(GatewayConfig())  # spec_decode_min_tokens=0
        core.register("plain", 2)
        core.register("fast", 2, spec=True)
        core.submit("r1", [1], 64)
        g = core.poll("plain", 2, [])
        assert [r.req_id for r in g.requests] == ["r1"]


    @pytest.mark.parametrize("fleet,grants,bypass", [
        ("spec_ample", 6, 0),      # every long decode reaches a spec seat
        ("plain_only", 0, 6),      # no spec replica: all given up to plain
        ("spec_saturated", None, None),  # one spec seat against six
    ])
    def test_every_long_decode_is_a_grant_or_a_bypass(
        self, fleet, grants, bypass
    ):
        """Six long decodes among four short ones: each long request
        is counted exactly once, as a grant to a spec replica or as a
        bypass to a plain one; a short one counts as neither."""
        core = _mk_core()
        slots = {"plain": 2}
        if fleet != "plain_only":
            slots["fast"] = 6 if fleet == "spec_ample" else 1
        for rid, n in slots.items():
            core.register(rid, n, spec=(rid == "fast"))
        for i in range(10):
            core.submit(f"q{i}", [i + 1], 32 if i < 6 else 4)
        held = {rid: [] for rid in slots}
        granted = 0
        order = ["fast", "plain"] if "fast" in slots else ["plain"]
        for _ in range(40):
            for rid in order:
                held[rid] = list(core.poll(
                    rid, slots[rid] - len(held[rid]),
                    [r.req_id for r in held[rid]]).requests) + held[rid]
            for rid in order:
                # the spec replica is slow to finish: while it is full
                # the plain one is what is left for a long decode
                if rid == "plain" or fleet == "spec_ample":
                    for req in held[rid]:
                        core.complete(rid, req.req_id, [1], True, "",
                                      False)
                        granted += 1
                    held[rid] = []
            if granted + sum(len(v) for v in held.values()) == 10:
                break
        c = core.counters
        assert c["spec_grants"] + c["spec_bypass"] == 6
        if grants is None:
            assert c["spec_grants"] >= 1 and c["spec_bypass"] >= 1
        else:
            assert (c["spec_grants"], c["spec_bypass"]) == (grants, bypass)


class TestDraftControlPlane:
    def test_poll_reply_carries_least_loaded_draft_addr(self):
        core = _mk_core()
        core.register("t0", 2, spec=True)
        core.register("d0", 8, role="draft", spec=True,
                      draft_addr="h1:1")
        core.register("d1", 8, role="draft", spec=True,
                      draft_addr="h2:2")
        core.poll("d0", 0, [], stats={"streams": 5})
        core.poll("d1", 0, [], stats={"streams": 1})
        g = core.poll("t0", 2, [])
        assert g.draft_addr == "h2:2"
        # Draining drafts stop being offered.
        core.drain("d1")
        g = core.poll("t0", 2, [])
        assert g.draft_addr == "h1:1"
        core.deregister("d0")
        core.drain("d0")
        assert core.poll("t0", 2, []).draft_addr == ""

    def test_draft_role_never_granted_work(self):
        core = _mk_core()
        core.register("d0", 8, role="draft", spec=True,
                      draft_addr="h:1")
        core.submit("r1", [1], 32)
        assert core.poll("d0", 8, []).requests == []

    def test_spec_counters_fold_as_deltas_and_rebaseline(self):
        core = _mk_core()
        core.register("t0", 2, spec=True)
        core.poll("t0", 2, [], stats={
            "spec_rounds": 10, "spec_accepted": 40,
            "spec_fallbacks": 1,
        })
        core.poll("t0", 2, [], stats={
            "spec_rounds": 15, "spec_accepted": 70,
            "spec_fallbacks": 1,
        })
        c = core.counters
        assert c["spec_rounds"] == 15
        assert c["spec_accepted"] == 70
        assert c["spec_fallbacks"] == 1
        # Restart resets the replica's cumulative numbers: the smaller
        # report re-baselines instead of going negative.
        core.poll("t0", 2, [], stats={
            "spec_rounds": 3, "spec_accepted": 12,
            "spec_fallbacks": 0,
        })
        c = core.counters
        assert c["spec_rounds"] == 18
        assert c["spec_accepted"] == 82

    def test_pools_carry_tokens_per_round_and_draft_signal(self):
        core = _mk_core()
        core.register("t0", 2, spec=True)
        core.register("t1", 2, spec=True)
        core.register("d0", 8, role="draft", spec=True,
                      draft_addr="h:1")
        core.poll("t0", 2, [], stats={"tokens_per_round": 4.0})
        core.poll("t1", 2, [], stats={"tokens_per_round": 2.0})
        snap = core.stats_snapshot()
        assert snap["pools"]["unified"]["tokens_per_round"] == 3.0
        # The draft pool's earned value is what its CONSUMERS measure.
        assert snap["pools"]["draft"]["tokens_per_round"] == 3.0
        assert snap["pools"]["draft"]["alive"] == 1

    def test_done_cache_records_request_telemetry(self):
        core = _mk_core()
        core.register("t0", 2, spec=True)
        core.submit("r1", [1, 2], 32)
        core.poll("t0", 2, [])
        core.complete("t0", "r1", [5, 6], tokens_per_round=3.5,
                      spec_rounds=4)
        rec = core._done.get("r1")
        assert rec["tokens_per_round"] == 3.5 and rec["spec_rounds"] == 4


class TestDraftPoolPolicy:
    def test_decide_sheds_below_break_even_regardless_of_occupancy(self):
        policy = ScalePolicy(min_replicas=0, down_patience=2,
                             tokens_per_round_low=3.3)
        state = ScaleState()
        snap = {"replicas_alive": 2, "queue_depth": 0,
                "occupancy": 0.9, "tokens_per_round": 2.0}
        assert decide(snap, policy, state) == 2
        assert decide(snap, policy, state) == 1  # patience met

    def test_unmeasured_pool_is_never_punished(self):
        policy = ScalePolicy(min_replicas=0, down_patience=1,
                             occupancy_low=0.0,
                             tokens_per_round_low=3.3)
        state = ScaleState()
        snap = {"replicas_alive": 2, "queue_depth": 10,
                "occupancy": 0.9, "tokens_per_round": 0.0}
        assert decide(snap, policy, state) >= 2

    def test_decide_pools_passes_the_signal_through(self):
        policies = {"draft": ScalePolicy(
            min_replicas=0, down_patience=1, tokens_per_round_low=3.3,
        )}
        snap = {"pools": {"draft": {
            "alive": 1, "queue_depth": 0, "occupancy": 1.0,
            "tokens_per_round": 1.5,
        }}}
        targets = decide_pools(snap, policies, {})
        assert targets["draft"] == 0


class TestDraftKillSite:
    def test_site_registered_with_exit_code(self):
        from dlrover_tpu.chaos.plan import EXIT_DRAFT_KILL, SITES

        site = SITES["serving.draft_kill"]
        assert site["kind"] == "crash"
        assert site["exit"] == EXIT_DRAFT_KILL == 82
        assert site["times"] == 1

    def test_method_selects_victim_and_step_ge_gates_on_rolls(self):
        plan = chaos.FaultPlan.parse(
            "serving.draft_kill:method=d1,step_ge=3,seed=5"
        )
        assert plan.fire("serving.draft_kill", method="d0",
                         step=9) is None
        assert plan.fire("serving.draft_kill", method="d1",
                         step=2) is None
        spec = plan.fire("serving.draft_kill", method="d1", step=3)
        assert spec is not None and spec.exit_code == 82
        assert plan.fire("serving.draft_kill", method="d1",
                         step=8) is None  # times=1: spent

    def test_decisions_are_seed_deterministic(self):
        a = chaos.FaultPlan.parse(
            "serving.draft_kill:p=0.5,times=-1,seed=7"
        )
        b = chaos.FaultPlan.parse(
            "serving.draft_kill:p=0.5,times=-1,seed=7"
        )
        seq_a = [a.fire("serving.draft_kill", step=i) is not None
                 for i in range(20)]
        seq_b = [b.fire("serving.draft_kill", step=i) is not None
                 for i in range(20)]
        assert seq_a == seq_b and any(seq_a) and not all(seq_a)


# ---------------------------------------------------------------------------
# model-backed integration
# ---------------------------------------------------------------------------


def _models():
    cfg = llama.LlamaConfig.tiny(n_layer=2, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    dcfg = llama.LlamaConfig.tiny(n_layer=1, dtype=jnp.float32)
    draft = llama.init_params(jax.random.PRNGKey(7), dcfg)
    return cfg, params, dcfg, draft


def _prompts():
    return [
        (np.arange(4, dtype=np.int32) % 7) + 1,
        (np.arange(6, dtype=np.int32) % 5) + 2,
        (np.arange(5, dtype=np.int32) % 9) + 1,
    ]


def _serve_incremental(srv, prompts, mnt):
    """Feed ``prompts`` through the incremental surface and collect
    completions — the server-loop form the satellite's byte-identity
    test runs both servers through."""
    outs = {}
    for rid, p in enumerate(prompts):
        srv.submit(rid, p, mnt)

    def tick():
        return bool(srv.pending_count() or srv.active_rids())

    srv.serve_incremental(
        tick=tick, on_finish=lambda rid, toks: outs.__setitem__(
            rid, np.asarray(toks)
        ),
    )
    return [outs[i] for i in range(len(prompts))]


class TestSpecServerParity:
    def test_spec_incremental_greedy_byte_identical_to_plain(self):
        """Satellite: the spec-mode server loop's output under greedy
        decoding equals plain incremental serving byte-for-byte — for
        the local-draft AND the remote-draft path, same seeds/prompts.
        """
        cfg, params, dcfg, draft = _models()
        prompts = _prompts()
        mnt = 10
        plain = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
        )
        ref = _serve_incremental(plain, prompts, mnt)
        local = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            draft=(draft, dcfg), draft_k=3,
        )
        got = _serve_incremental(local, prompts, mnt)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        remote = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            spec_remote=True, draft_k=3, adapt_k_per_request=True,
        )
        remote.set_remote_draft(
            DraftWorker(draft, dcfg, max_len=96, draft_k=3)
        )
        got = _serve_incremental(remote, prompts, mnt)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_remote_ceiling_draft_accepts_near_full_width(self):
        cfg, params, _, _ = _models()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=64, prompt_buckets=(8,),
            spec_remote=True, draft_k=3,
        )
        srv.set_remote_draft(
            DraftWorker(params, cfg, max_len=64, draft_k=3)
        )
        outs = srv.serve(_prompts(), max_new_tokens=6)
        for p, got in zip(_prompts(), outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :], max_new_tokens=6
            ))[0]
            np.testing.assert_array_equal(got, solo)
        assert srv.last_stats["tokens_per_round"] > 3.0

    def test_sampled_remote_consumes_draft_probs(self):
        """The sampled remote path must run end-to-end (draft ships q,
        the batched acceptance consumes it) and stay seed-reproducible
        against itself."""
        cfg, params, _, _ = _models()

        def build():
            srv = llama_infer.DecodeServer(
                params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
                spec_remote=True, draft_k=3, temperature=0.8, seed=1,
            )
            srv.set_remote_draft(DraftWorker(
                params, cfg, max_len=96, draft_k=3, temperature=0.8,
                seed=2,
            ))
            return srv

        a = build().serve(_prompts()[:1], max_new_tokens=8)
        b = build().serve(_prompts()[:1], max_new_tokens=8)
        np.testing.assert_array_equal(a[0], b[0])


class TestPerRequestAdaptiveK:
    def test_bad_draft_walks_streams_to_plain_and_stays_exact(self):
        cfg, params, dcfg, draft = _models()
        prompts = _prompts()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=128, prompt_buckets=(8,),
            spec_remote=True, draft_k=4, adapt_k_per_request=True,
            spec_ewma_alpha=0.5, spec_probe_every=64,
        )
        srv.set_remote_draft(
            DraftWorker(draft, dcfg, max_len=128, draft_k=4)
        )
        outs = srv.serve(prompts, max_new_tokens=24)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :],
                max_new_tokens=24,
            ))[0]
            np.testing.assert_array_equal(got, solo)
        st = srv.last_stats
        assert st["spec_fallback_rounds"] > 0, st
        assert st["rounds"] < st["spec_fallback_rounds"], st

    def test_good_draft_holds_full_width_above_break_even(self):
        cfg, params, _, _ = _models()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=128, prompt_buckets=(8,),
            spec_remote=True, draft_k=4, adapt_k_per_request=True,
        )
        srv.set_remote_draft(
            DraftWorker(params, cfg, max_len=128, draft_k=4)
        )
        srv.serve(_prompts(), max_new_tokens=24)
        st = srv.last_stats
        assert st["spec_fallback_rounds"] == 0, st
        assert st["tokens_per_round"] > srv.spec_break_even, st

    def test_probe_rounds_remeasure_a_plain_stream(self):
        """A stream at k=0 must re-probe every spec_probe_every of its
        plain rounds — a draft that got better can re-earn width."""
        cfg, params, dcfg, draft = _models()
        srv = llama_infer.DecodeServer(
            params, cfg, slots=1, max_len=160, prompt_buckets=(8,),
            spec_remote=True, draft_k=4, adapt_k_per_request=True,
            spec_ewma_alpha=0.9, spec_probe_every=6,
        )
        srv.set_remote_draft(
            DraftWorker(draft, dcfg, max_len=160, draft_k=4)
        )
        srv.serve(_prompts()[:1], max_new_tokens=40)
        st = srv.last_stats
        # Initial full-width round + at least one k=1 probe.
        assert st["rounds"] >= 2, st
        assert st["spec_fallback_rounds"] > 0, st

    def test_dying_draft_degrades_to_plain_and_completes(self):
        cfg, params, _, _ = _models()

        class Dying:
            def __init__(self, inner, after):
                self.inner, self.calls, self.after = inner, 0, after

            def propose(self, reqs, k, sample=False, close=()):
                self.calls += 1
                if self.calls > self.after:
                    raise DraftUnavailable("chaos: draft died")
                return self.inner.propose(reqs, k, sample=sample,
                                          close=close)

        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            spec_remote=True, draft_k=3,
        )
        srv.set_remote_draft(Dying(
            DraftWorker(params, cfg, max_len=96, draft_k=3), after=2,
        ))
        prompts = _prompts()
        outs = srv.serve(prompts, max_new_tokens=10)
        for p, got in zip(prompts, outs):
            solo = np.asarray(llama_infer.generate(
                params, cfg, jnp.asarray(p)[None, :],
                max_new_tokens=10,
            ))[0]
            np.testing.assert_array_equal(got, solo)
        st = srv.last_stats
        assert st["spec_draft_failures"] == 1
        assert st["spec_fallback_rounds"] > 0


# ---------------------------------------------------------------------------
# fleet integration: draft kill degrades targets, exactly-once holds
# ---------------------------------------------------------------------------


def _gw_dispatch(core):
    def handle(msg):
        if isinstance(msg, M.ServeReplicaRegister):
            core.register(msg.replica_id, msg.slots, msg.role,
                          msg.spec, msg.draft_addr)
            return M.BaseResponse(success=True)
        if isinstance(msg, M.ServeReplicaPoll):
            return core.poll(msg.replica_id, msg.free_slots,
                             msg.active, msg.stats, msg.warm_prefixes)
        if isinstance(msg, M.ServeReplicaDeregister):
            core.deregister(msg.replica_id)
            return M.BaseResponse(success=True)
        if isinstance(msg, M.ServeTokens):
            core.stream(msg.replica_id, msg.req_id, msg.tokens)
            return M.BaseResponse(success=True)
        if isinstance(msg, M.ServeDone):
            outcome = core.complete(
                msg.replica_id, msg.req_id, msg.tokens, msg.ok,
                msg.reason, msg.replayed, msg.tokens_per_round,
                msg.spec_rounds,
            )
            return M.BaseResponse(success=True, reason=outcome)
        return M.BaseResponse(success=True)

    return handle


class TestDraftKillFleet:
    def test_draft_kill_degrades_targets_exactly_once(self, tmp_path):
        """The chaos satellite's in-process form: the draft dies (the
        ``serving.draft_kill`` site fires in its proposal loop) while
        requests are IN FLIGHT on a spec target — the target counts
        spec_fallbacks, finishes every admitted request via plain
        decode, each exactly once, byte-identical to solo greedy."""
        cfg, params, _, _ = _models()
        core = GatewayCore(GatewayConfig(spec_decode_min_tokens=8))
        lb = LoopbackTransport(_gw_dispatch(core))
        worker = DraftWorker(params, cfg, max_len=96, draft_k=3,
                             worker_id="d0")
        # Stub the crash site to a flag (the crash kind os._exits — the
        # subprocess form lives in the chaos e2e lane); step_ge=2 fires
        # it mid-stream, after real speculative rounds happened.
        plan = chaos.FaultPlan.parse(
            "serving.draft_kill:method=d0,step_ge=2,seed=3"
        )
        for spec in plan.specs:
            spec.kind = "flag"
        chaos.configure(plan)
        try:
            class LoopDraftServer:
                def __init__(self, w):
                    self.worker = w
                    self.addr = "loop:d0"

                def stop(self):
                    pass

            drunner = DraftReplicaRunner(
                LoopDraftServer(worker), lb, "d0", poll_interval=0.02
            )
            dth = threading.Thread(target=drunner.run, daemon=True)
            dth.start()
            srv = llama_infer.DecodeServer(
                params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
                spec_remote=True, draft_k=3,
            )
            runner = ReplicaRunner(
                srv, lb, "r0", poll_interval=0.01,
                journal_path=str(tmp_path / "r0.jsonl"),
                draft_connect=lambda addr: RemoteDraftClient(
                    LoopbackTransport(
                        lambda m: handle_draft(worker, m)
                    )
                ),
            )
            rth = threading.Thread(target=runner.run, daemon=True)
            rth.start()
            deadline = time.time() + 30
            while time.time() < deadline and \
                    core.stats_snapshot()["replicas_alive"] < 2:
                time.sleep(0.02)
            prompts = _prompts()
            for i, p in enumerate(prompts):
                core.submit(f"q{i}", [int(t) for t in p], 16)
            deadline = time.time() + 60
            while time.time() < deadline and \
                    core.counters["completed"] < len(prompts):
                time.sleep(0.05)
            assert core.counters["completed"] == len(prompts), \
                core.counters
            assert core.counters["duplicate_completions"] == 0
            # The site fired exactly once, in the proposal loop.
            assert chaos.active_plan().stats()[
                "serving.draft_kill"
            ] == 1
            # Exact output through the degradation.
            for i, p in enumerate(prompts):
                solo = np.asarray(llama_infer.generate(
                    params, cfg, jnp.asarray(p)[None, :],
                    max_new_tokens=16,
                ))[0]
                np.testing.assert_array_equal(
                    core.status(f"q{i}").tokens, solo[len(p):]
                )
            # The target degraded: fallback rounds were reported and
            # folded into the gateway counter.
            deadline = time.time() + 10
            while time.time() < deadline and \
                    core.counters["spec_fallbacks"] == 0:
                time.sleep(0.05)
            assert core.counters["spec_fallbacks"] > 0, core.counters
            assert core.counters["spec_rounds"] >= 2
            runner._draining = True
            runner._stopped = True
            drunner.stop()
            rth.join(timeout=10)
            dth.join(timeout=10)
        finally:
            chaos.reset()

    def test_journal_replay_reports_live_telemetry(self, tmp_path):
        """Satellite: a re-granted request answered from the journal
        reports the SAME tokens_per_round it earned live — the done
        record after replay carries the original telemetry."""
        cfg, params, _, _ = _models()
        core = GatewayCore(GatewayConfig())
        lb = LoopbackTransport(_gw_dispatch(core))
        srv = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            spec_remote=True, draft_k=3,
        )
        srv.set_remote_draft(
            DraftWorker(params, cfg, max_len=96, draft_k=3)
        )
        jp = str(tmp_path / "r0.jsonl")
        runner = ReplicaRunner(srv, lb, "r0", poll_interval=0.01,
                               journal_path=jp)
        rth = threading.Thread(target=runner.run, daemon=True)
        rth.start()
        p = _prompts()[0]
        core.submit("qa", [int(t) for t in p], 12)
        deadline = time.time() + 60
        while time.time() < deadline and \
                core.counters["completed"] < 1:
            time.sleep(0.05)
        live = core._done.get("qa")
        assert live and live["tokens_per_round"] > 3.0, live
        runner._draining = True
        runner._stopped = True
        rth.join(timeout=10)
        # A fresh gateway re-grants the same request to a restarted
        # replica incarnation: the journal answers WITH telemetry.
        core2 = GatewayCore(GatewayConfig())
        lb2 = LoopbackTransport(_gw_dispatch(core2))
        srv2 = llama_infer.DecodeServer(
            params, cfg, slots=2, max_len=96, prompt_buckets=(8,),
            spec_remote=True, draft_k=3,
        )
        runner2 = ReplicaRunner(srv2, lb2, "r0", poll_interval=0.01,
                                journal_path=jp, replay_limit=0)
        rth2 = threading.Thread(target=runner2.run, daemon=True)
        rth2.start()
        core2.submit("qa", [int(t) for t in p], 12)
        deadline = time.time() + 30
        while time.time() < deadline and \
                core2.counters["completed"] < 1:
            time.sleep(0.05)
        rec = core2._done.get("qa")
        assert rec is not None
        assert rec["tokens"] == live["tokens"]
        assert rec["tokens_per_round"] == pytest.approx(
            live["tokens_per_round"]
        )
        assert runner2.replayed >= 1 and runner2.served == 0
        runner2._draining = True
        runner2._stopped = True
        rth2.join(timeout=10)


class TestDraftKvStats:
    def test_kv_stats_track_streams_in_fleet_convention(self):
        """ISSUE 19: the draft worker reports its (dense) stream cache
        in the same ``kv_occupancy`` convention the paged target uses,
        so the gateway's memory roll-up covers the draft pool too."""
        cfg, params, dcfg, draft = _models()
        w = DraftWorker(draft, dcfg, max_len=32, draft_k=2,
                        max_streams=4)
        empty = w.kv_stats()
        assert empty == {"kv_occupancy": 0.0, "kv_tokens_held": 0,
                         "kv_token_capacity": 4 * 32, "streams": 0}
        p = [int(t) for t in _prompts()[0]]
        w.propose([{"rid": "a", "ctx": [], "open": p}], 2)
        st = w.kv_stats()
        assert st["streams"] == 1
        # Committed tokens only: proposals count when the next
        # round's ctx acks them, so the open round holds the prompt.
        assert st["kv_tokens_held"] == len(p)
        assert st["kv_occupancy"] == pytest.approx(
            st["kv_tokens_held"] / st["kv_token_capacity"], abs=1e-4
        )
        # LRU eviction returns the held tokens to the pool.
        for i in range(4):
            w.propose([{"rid": f"b{i}", "ctx": [], "open": p}], 2)
        st = w.kv_stats()
        assert st["streams"] == 4
        assert "a" not in w._streams
