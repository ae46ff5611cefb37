"""Draft replicas: speculative proposals as a fleet service (ISSUE 11).

The seed rounds proved batched speculative decoding inside one process
(``models/llama_infer.py``: draft-roll / chunked verify / rejection-
sampling acceptance, break-even ~3.35 tokens/round on the committed
CPU record, since deleted).  This module makes the DRAFT half a fleet
citizen: a small draft model runs on its own replica (its own chip)
and ships per-round proposals to target replicas over the PR-9
segment-path idiom — a tiny RPC server per publisher, CRC-wrapped
payloads, pull-verified by the consumer:

- :class:`DraftWorker` (jax side) keeps one dense KV cache per stream;
  each :meth:`DraftWorker.propose` call catches every stream's cache up
  from the context delta the target shipped (the tokens the verify
  accepted since the last roll), rolls ``k`` proposals per stream, and
  rewinds past the speculative writes — the same slot-masked-rewind law
  the local draft path uses;
- :class:`DraftServer` fronts the worker with the repo RPC
  (``DraftRoll`` -> ``DraftProposals``), the ``KvSegmentServer`` shape;
- :class:`RemoteDraftClient` (jax-free) is the handle a spec target's
  ``DecodeServer.set_remote_draft`` consumes: it CRC-verifies every
  proposal bundle and converges EVERY failure on
  :class:`DraftUnavailable` — the target then degrades to plain decode
  (``spec_fallbacks``), it never stalls and never decodes torn
  proposals as if they were draft law;
- :class:`DraftReplicaRunner` is the draft replica's control loop:
  register with the gateway as the ``draft`` role (announcing the
  proposal server's address), heartbeat-poll for the lease, honour
  drain, deregister.

Correctness is owned by the TARGET's acceptance: whatever the draft
proposes — stale, torn-and-rejected, or from a different model
entirely — the emitted stream per request is exactly the target
model's own decode (greedy or sampled).  A draft replica can therefore
be killed at ANY point (chaos ``serving.draft_kill``) and the only
observable effect is acceptance telemetry going away.

No jax at module level: the worker imports the model stack lazily, so
the gateway/client half (and every protocol unit test) runs without it.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from dlrover_tpu import chaos
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.messages import (
    BaseResponse,
    DraftProposals,
    DraftRoll,
    Message,
    ServeGrants,
    ServeReplicaDeregister,
    ServeReplicaPoll,
    ServeReplicaRegister,
)

PROPOSALS_VERSION = 1


class DraftUnavailable(RuntimeError):
    """The draft replica could not serve this round's proposals (dead
    peer, torn bundle, chaos kill).  The target's serve loop degrades
    to plain decode — speculation is an optimization, never a
    dependency."""


def pack_proposals(props: Dict[str, Dict[str, Any]]) -> bytes:
    """Pack one round's proposals — ``{rid: {"d": [k ints], "q":
    [k, V] float array | None}}`` — into the CRC-wrapped msgpack
    envelope the KV-segment path uses (body CRC-32 embedded, verified
    by :func:`unpack_proposals`)."""
    import msgpack

    streams = []
    for rid, ent in props.items():
        q = ent.get("q")
        if q is not None:
            q = np.ascontiguousarray(np.asarray(q, np.float32))
        streams.append({
            "rid": str(rid),
            "d": [int(t) for t in ent["d"]],
            "q": q.tobytes() if q is not None else b"",
            "qshape": [int(x) for x in q.shape] if q is not None else [],
        })
    body = msgpack.packb(streams, use_bin_type=True)
    return msgpack.packb(
        {"v": PROPOSALS_VERSION,
         "crc": zlib.crc32(body) & 0xFFFFFFFF, "body": body},
        use_bin_type=True,
    )


def unpack_proposals(payload: bytes) -> Dict[str, Dict[str, Any]]:
    """Verify + unpack a :func:`pack_proposals` bundle.  Raises
    :class:`DraftUnavailable` on ANY damage — torn proposals must
    degrade the round, never be verified against as draft law."""
    import msgpack

    try:
        obj = msgpack.unpackb(payload, raw=False)
        if obj.get("v") != PROPOSALS_VERSION:
            raise ValueError(f"version {obj.get('v')}")
        body = obj["body"]
        crc = int(obj["crc"])
    except Exception as e:  # noqa: BLE001 - converge on DraftUnavailable
        raise DraftUnavailable(
            f"undecodable proposal bundle: {e}"
        ) from None
    if (zlib.crc32(body) & 0xFFFFFFFF) != crc:
        raise DraftUnavailable("proposal bundle CRC mismatch (torn)")
    try:
        streams = msgpack.unpackb(body, raw=False)
        out: Dict[str, Dict[str, Any]] = {}
        for ent in streams:
            q = None
            if ent.get("qshape"):
                q = np.frombuffer(
                    ent["q"], dtype=np.float32
                ).reshape(ent["qshape"])
            out[ent["rid"]] = {"d": list(ent["d"]), "q": q}
        return out
    except Exception as e:  # noqa: BLE001 - converge on DraftUnavailable
        raise DraftUnavailable(
            f"malformed proposal bundle: {e}"
        ) from None


class DraftWorker:
    """The jax side of a draft replica: one dense 1-row KV cache per
    stream, catch-up + k-proposal roll per :meth:`propose` call.

    Position law (mirrors the local draft path's rewind): a stream's
    committed offset always equals ``len(prompt) + tokens the target
    has shipped``.  A roll scores the shipped delta as one chunk
    (writing its kv), samples the first proposal from the chunk's last
    logits, scans the rest, then REWINDS the offset to the committed
    point — the speculative writes beyond it are causally masked and
    overwritten by the next round's delta, exactly the dense-cache
    slot-masking trick ``generate_speculative_batched`` relies on.

    ``round_floor_s`` models the draft chip's per-roll device time on
    CPU benches (one batched roll over all streams = one floor), the
    ``ReplicaRunner.round_floor_s`` pattern.
    """

    def __init__(
        self,
        params,
        cfg,
        *,
        max_len: int = 512,
        draft_k: int = 4,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        max_streams: int = 32,
        seed: int = 0,
        worker_id: str = "draft",
        round_floor_s: float = 0.0,
    ):
        import collections

        import jax

        self.params = params
        self.cfg = cfg
        self.max_len = int(max_len)
        self.draft_k = int(draft_k)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.max_streams = int(max_streams)
        self.worker_id = worker_id
        self.round_floor_s = float(round_floor_s)
        self.rolls = 0
        self.proposed_tokens = 0
        self._mu = threading.Lock()
        #: Serializes whole proposal rounds: the RPC server is
        #: multithreaded and two targets' rolls must not interleave
        #: stream-state mutations (the floor sleep stays OUTSIDE so
        #: concurrent targets overlap it — one batched draft chip).
        self._roll_mu = threading.Lock()
        #: rid -> {"cache": 1-row dense cache, "off": committed int}.
        #: OrderedDict: LRU order for the stream bound.
        self._streams: "collections.OrderedDict" = \
            collections.OrderedDict()
        #: rids whose open was REFUSED (prompt outside this worker's
        #: cache): the target reships the open every round for a
        #: stream it sees no proposals for — remember the refusal so
        #: the retries cost a set lookup, not a raised prefill.
        self._refused: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._jits: Dict[Any, Any] = {}
        self._rng = jax.random.PRNGKey(seed)
        # Prompt buckets: powers of two up to max_len (padded prefill;
        # pad kv is overwritten before it becomes causally visible —
        # the DecodeServer._prefill invariant).
        b, buckets = 16, []
        while b < self.max_len:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_len)
        self._buckets = tuple(buckets)

    # -- jitted programs ---------------------------------------------------

    def _next_key(self):
        import jax

        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _score(self, T: int):
        """Memoized: score a [1, T] chunk continuing the stream's cache
        at its scalar offset; returns (logits [T, V], cache)."""
        key = ("score", T)
        if key not in self._jits:
            import jax

            from dlrover_tpu.models import llama_infer

            def fn(params, cache, chunk):
                logits, cache = llama_infer.forward_step(
                    params, chunk, self.cfg, cache
                )
                return logits[0], cache

            self._jits[key] = jax.jit(fn)
        return self._jits[key]

    def _roll(self, k: int):
        """Memoized: sample proposal 1 from ``last_logits``, scan the
        remaining k-1 draft steps; returns (toks [k], probs [k, V] |
        None, cache) — cache offset advanced past the speculative
        writes (the caller rewinds)."""
        key = ("roll", k)
        if key not in self._jits:
            import jax
            import jax.numpy as jnp

            from dlrover_tpu.models import llama_infer

            sample = self.temperature > 0.0

            def pick(lg1, kk):
                if sample:
                    filt = llama_infer._filter_logits(
                        lg1[None, :] / self.temperature,
                        self.top_k, self.top_p,
                    )
                    tok = jax.random.categorical(kk, filt, axis=-1)[0]
                    return (tok.astype(jnp.int32),
                            jax.nn.softmax(filt, axis=-1)[0])
                return (jnp.argmax(lg1).astype(jnp.int32),
                        jnp.zeros((0,), jnp.float32))

            def fn(params, cache, last_logits, key_):
                keys = jax.random.split(key_, k)
                d1, q1 = pick(last_logits, keys[0])

                def body(carry, kk):
                    cache, tok = carry
                    lg, cache = llama_infer.forward_step(
                        params, tok[None, None], self.cfg, cache
                    )
                    nxt, qq = pick(lg[0, -1, :], kk)
                    return (cache, nxt), (nxt, qq)

                (cache, _), ys = jax.lax.scan(
                    body, (cache, d1), keys[1:]
                )
                toks = jnp.concatenate([d1[None], ys[0]])
                probs = (
                    jnp.concatenate([q1[None, :], ys[1]])
                    if sample else None
                )
                return toks, probs, cache

            self._jits[key] = jax.jit(fn)
        return self._jits[key]

    # -- stream lifecycle --------------------------------------------------

    def _open(self, rid: str, prompt: List[int]) -> Dict[str, Any]:
        """(Re)open one stream: bucketed padded prefill of the prompt
        into a fresh 1-row cache, committed offset = true length."""
        import jax.numpy as jnp

        from dlrover_tpu.models import llama_infer

        p = np.asarray(prompt, np.int32)
        n = len(p)
        if n == 0 or n > self.max_len:
            raise DraftUnavailable(
                f"stream {rid!r}: prompt of {n} tokens outside "
                f"(0, {self.max_len}]"
            )
        cache = llama_infer.init_cache(
            self.cfg, 1, self.max_len, ring=False
        )
        off = 0
        rem = n
        start = 0
        while rem > 0:
            b = next(
                (x for x in self._buckets if x >= rem),
                self._buckets[-1],
            )
            b = min(b, self.max_len - start)
            chunk = np.zeros((b,), np.int32)
            take = min(rem, b)
            chunk[:take] = p[start: start + take]
            cache = dict(cache, offset=jnp.asarray(off, jnp.int32))
            _, cache = self._score(b)(
                self.params, cache, jnp.asarray(chunk)[None, :]
            )
            off += take
            start += take
            rem -= take
        st = {"cache": dict(cache, offset=None), "off": off}
        with self._mu:
            self._streams[rid] = st
            self._streams.move_to_end(rid)
            while len(self._streams) > self.max_streams:
                evicted, _ = self._streams.popitem(last=False)
                logger.info(
                    "draft %s: evicted stream %s (bound %d)",
                    self.worker_id, evicted, self.max_streams,
                )
        return st

    def warm(self) -> None:
        """Compile every program the serving path visits — the open
        bucket, per-round delta scores (1..k+1) and the full-width +
        probe rolls — BEFORE the replica registers.  Deliberately
        bypasses :meth:`propose`: the chaos site and its ``step`` gate
        (completed ROLLS) must only ever see real serving traffic, and
        the roll counters stay zero."""
        import jax.numpy as jnp

        st = self._open("__warm", [1, 2, 3, 4])
        off = st["off"]
        last = None
        for L in range(1, self.draft_k + 2):
            chunk = np.zeros((L,), np.int32)
            cache = dict(
                st["cache"], offset=jnp.asarray(off, jnp.int32)
            )
            logits, _ = self._score(L)(
                self.params, cache, jnp.asarray(chunk)[None, :]
            )
            last = (logits, cache)
        logits, cache = last
        cache = dict(cache, offset=jnp.asarray(off + 1, jnp.int32))
        for kk in {1, self.draft_k}:
            self._roll(kk)(
                self.params, cache, logits[0], self._next_key()
            )
        self.close("__warm")

    def close(self, rid) -> None:
        with self._mu:
            self._streams.pop(str(rid), None)
            self._refused.pop(str(rid), None)

    def stream_count(self) -> int:
        with self._mu:
            return len(self._streams)

    def kv_stats(self) -> Dict[str, Any]:
        """The draft's KV memory view in the fleet's ``kv_occupancy``
        convention (ISSUE 19).  Draft stream caches stay DENSE — each
        is a constant 1-row [max_len] array, tiny next to the target's
        pool, and streams churn with the LRU bound rather than growing
        — so occupancy here is committed tokens over stream capacity,
        the honest analogue of the target's block-pool utilization."""
        with self._mu:
            held = sum(int(st["off"]) for st in self._streams.values())
            n = len(self._streams)
        cap = self.max_streams * self.max_len
        return {
            "kv_occupancy": round(held / cap, 4) if cap else 0.0,
            "kv_tokens_held": held,
            "kv_token_capacity": cap,
            "streams": n,
        }

    # -- the proposal loop -------------------------------------------------

    def propose(self, reqs: List[dict], k: int, sample: bool = False,
                close=()) -> Dict[str, Dict[str, Any]]:
        """One round of proposals for every stream in ``reqs``.  Each
        entry: ``{"rid", "ctx": [tokens emitted since the last roll],
        "open": [prompt]}`` (``open`` present = (re)open first).
        Unknown streams without an ``open`` are SKIPPED (absent from
        the result — the target re-opens them next round).  Returns
        ``{rid: {"d": [k ints], "q": [k, V] float32 | None}}``."""
        import jax.numpy as jnp

        # The proposal loop's chaos site (ISSUE 11): a crash plan
        # os._exits with its deterministic code right here — mid-round,
        # after streams may already hold state — the worst moment for
        # the fleet, the only observable effect on request STREAMS
        # being spec_fallbacks (targets degrade to plain decode).
        if chaos.inject(
            "serving.draft_kill", method=self.worker_id,
            step=self.rolls,
        ) is not None:
            raise DraftUnavailable("chaos: serving.draft_kill fired")
        k = max(1, min(int(k), self.draft_k))
        if sample != (self.temperature > 0.0):
            raise DraftUnavailable(
                f"sampling mismatch: target asked sample={sample}, "
                f"draft built with temperature={self.temperature}"
            )
        out: Dict[str, Dict[str, Any]] = {}
        with self._roll_mu:
            for rid in close:
                self.close(rid)
            for req in reqs:
                rid = str(req["rid"])
                ctx = [int(t) for t in req.get("ctx") or []]
                with self._mu:
                    if rid in self._refused:
                        continue  # that stream rides plain for good
                    st = self._streams.get(rid)
                    if st is not None:
                        self._streams.move_to_end(rid)
                if req.get("open") is not None:
                    try:
                        st = self._open(rid, req["open"])
                    except DraftUnavailable as e:
                        # ONE stream's bad open (prompt outside this
                        # worker's cache) must not fail the whole
                        # round for every other stream — that stream
                        # simply rides plain at its target.
                        logger.warning(
                            "draft %s: open refused for %s: %s",
                            self.worker_id, rid, e,
                        )
                        with self._mu:
                            self._refused[rid] = True
                            while len(self._refused) > 256:
                                self._refused.popitem(last=False)
                        continue
                if st is None or not ctx:
                    # Unknown stream / empty delta: target reopens.
                    continue
                off = st["off"]
                L = len(ctx)
                # Chunk-length BUCKETS: per-round deltas (1..k+1) score
                # at their exact length; longer catch-ups (a probe
                # after a plain stretch ships its whole backlog) pad to
                # the next prompt bucket — otherwise every distinct
                # backlog length would be a fresh XLA compile on the
                # serving hot path.  Pad queries' outputs are discarded
                # and their junk kv writes sit beyond the committed
                # offset, overwritten before any later real query can
                # see them (the padded-prefill invariant).
                if L <= self.draft_k + 1:
                    Lb = L
                else:
                    Lb = next(
                        (x for x in self._buckets if x >= L),
                        self._buckets[-1],
                    )
                if off + Lb + k > self.max_len:
                    # Out of cache: drop the stream; target rides plain.
                    self.close(rid)
                    continue
                chunk = np.zeros((Lb,), np.int32)
                chunk[:L] = np.asarray(ctx, np.int32)
                cache = dict(
                    st["cache"], offset=jnp.asarray(off, jnp.int32)
                )
                logits, cache = self._score(Lb)(
                    self.params, cache, jnp.asarray(chunk)[None, :],
                )
                # Proposals continue from the LAST REAL ctx token's
                # logits; the roll's writes start at the committed
                # offset, overwriting any pad kv first.
                cache = dict(
                    cache, offset=jnp.asarray(off + L, jnp.int32)
                )
                toks, probs, cache = self._roll(k)(
                    self.params, cache, logits[L - 1], self._next_key()
                )
                # Commit exactly the shipped delta; the k-proposal
                # writes beyond it are masked until overwritten.
                st["cache"] = dict(cache, offset=None)
                st["off"] = off + L
                d = [int(t) for t in np.asarray(toks)]
                q = np.asarray(probs, np.float32) if sample else None
                out[rid] = {"d": d, "q": q}
                self.proposed_tokens += k
            self.rolls += 1
        if self.round_floor_s > 0:
            # One batched roll = one draft-chip round (the bench's
            # device-floor model; concurrent target polls overlap their
            # sleeps exactly like a batched draft scan would).  Scaled
            # by the ROLL width: a k=1 probe costs one draft step, not
            # a full-width scan.
            time.sleep(
                self.round_floor_s * k / max(1, self.draft_k)
            )
        return out


def handle_draft(worker: DraftWorker,
                 msg: Message) -> Optional[Message]:
    """The proposal server's dispatch, separable from the RPC wrapper
    so loopback fleets serve rolls with zero sockets."""
    if not isinstance(msg, DraftRoll):
        return BaseResponse(
            success=False,
            reason=f"unknown message {type(msg).__name__}",
        )
    from dlrover_tpu import chaos
    from dlrover_tpu.obs import get_recorder, record_span

    # Draft rolls are the highest-frequency loop in spec serving and
    # carry no per-request trace context, so their round spans are
    # emitted only when the fleet is actually being OBSERVED (a dump
    # directory is configured, or a chaos plan is under study) — an
    # unobserved fleet must not churn its bounded ring with
    # untraceable round spans and evict the control-plane journal the
    # recorder exists to preserve.
    observed = (
        get_recorder().out_dir is not None
        or chaos.active_plan() is not None
    )
    t0 = time.monotonic()
    try:
        props = worker.propose(
            msg.streams, msg.k, sample=msg.sample, close=msg.close
        )
    except Exception as e:  # noqa: BLE001 - a failed roll degrades
        logger.warning("draft %s: roll failed: %s", worker.worker_id, e)
        if observed:
            record_span(
                "draft.roll", "round", t0, time.monotonic(),
                args={"worker": worker.worker_id, "k": int(msg.k),
                      "streams": len(msg.streams), "failed": True},
            )
        return DraftProposals(found=False, reason=str(e)[:200])
    # One speculative draft round as a span (ISSUE 12) — the draft
    # side of the spec draft/verify pair (the target side shows as
    # ``rep.spec_round`` on its replica's lane).
    if observed:
        record_span(
            "draft.roll", "round", t0, time.monotonic(),
            args={"worker": worker.worker_id, "k": int(msg.k),
                  "streams": len(msg.streams)},
        )
    return DraftProposals(found=True, payload=pack_proposals(props))


class DraftServer:
    """RPC front of one draft replica's :class:`DraftWorker` — the
    :class:`~dlrover_tpu.serving.kvseg.KvSegmentServer` shape.  ``addr``
    is what the draft replica announces in its register and the
    gateway hands to spec targets."""

    def __init__(self, worker: DraftWorker, port: int = 0):
        from dlrover_tpu.common.rpc import RpcServer, local_ip

        self.worker = worker
        self._server = RpcServer(port, self.handle)
        self._server.start()
        self.addr = f"{local_ip()}:{self._server.port}"

    def handle(self, msg: Message) -> Optional[Message]:
        return handle_draft(self.worker, msg)

    def stop(self) -> None:
        self._server.stop()


class RemoteDraftClient:
    """The proposal handle a spec target's ``DecodeServer`` consumes
    (``set_remote_draft``).  ``transport`` follows the repo calling
    convention (``call(msg, **kw) -> reply``) — an RpcClient against a
    real draft server or a loopback for in-process fleets.  Every
    failure mode (transport, found=False, torn bundle) converges on
    :class:`DraftUnavailable`; the serve loop then decodes plain."""

    def __init__(self, transport, replica_id: str = "",
                 timeout: float = 10.0):
        self._t = transport
        self._replica_id = replica_id
        self._timeout = timeout

    def propose(self, reqs: List[dict], k: int, sample: bool = False,
                close=()) -> Dict[str, Dict[str, Any]]:
        try:
            resp = self._t.call(DraftRoll(
                replica_id=self._replica_id, k=int(k),
                sample=bool(sample), streams=list(reqs),
                close=[str(r) for r in close],
            ))
        except Exception as e:  # noqa: BLE001 - converge
            raise DraftUnavailable(f"draft roll failed: {e}") from e
        if not isinstance(resp, DraftProposals) or not resp.found:
            raise DraftUnavailable(
                "draft roll refused: "
                f"{getattr(resp, 'reason', 'bad reply type')}"
            )
        return unpack_proposals(resp.payload)

    def close(self) -> None:
        close = getattr(self._t, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 - teardown
                logger.debug("draft client close failed", exc_info=True)


def connect_remote_draft(addr: str, replica_id: str = "",
                         timeout: float = 10.0) -> RemoteDraftClient:
    """Default addr -> handle factory (the replica runner's
    ``draft_connect``): one RpcClient per draft endpoint."""
    from dlrover_tpu.common.rpc import RpcClient

    return RemoteDraftClient(
        RpcClient(addr, timeout=timeout), replica_id=replica_id,
        timeout=timeout,
    )


class DraftReplicaRunner:
    """The draft replica's control loop: register as the ``draft``
    role (announcing the proposal server's address), heartbeat-poll so
    the gateway's lease keeps the draft visible, honour the drain
    flag, deregister.  Proposals themselves ride the
    :class:`DraftServer` data plane — the gateway never sees them."""

    def __init__(
        self,
        server,  # DraftServer (or anything with .worker and .addr)
        transport,
        replica_id: str,
        poll_interval: float = 0.2,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.server = server
        self.transport = transport
        self.replica_id = replica_id
        self.poll_interval = poll_interval
        self._clock = clock
        self._stop = threading.Event()
        self.draining = False

    def register(self) -> None:
        self._call_quiet(ServeReplicaRegister(
            replica_id=self.replica_id,
            slots=self.server.worker.max_streams,
            role="draft", spec=True, draft_addr=self.server.addr,
        ))

    def run(self) -> None:
        """Blocking: register, heartbeat until drained/stopped,
        deregister, stop the proposal server."""
        self.register()
        try:
            while not self._stop.wait(self.poll_interval):
                w = self.server.worker
                reply = self._call_quiet(ServeReplicaPoll(
                    replica_id=self.replica_id, free_slots=0,
                    active=[], stats={
                        "role": "draft",
                        "rolls": w.rolls,
                        "proposed_tokens": w.proposed_tokens,
                        # Memory view (ISSUE 19): committed stream
                        # tokens over capacity — the draft pool's
                        # kv_occupancy in the gateway snapshot.
                        **w.kv_stats(),
                    },
                ))
                if isinstance(reply, ServeGrants):
                    if not reply.known:
                        self.register()
                    if reply.drain:
                        self.draining = True
                        break
        finally:
            self._call_quiet(ServeReplicaDeregister(
                replica_id=self.replica_id
            ))
            stop = getattr(self.server, "stop", None)
            if stop is not None:
                stop()

    def stop(self) -> None:
        self._stop.set()

    def _call_quiet(self, msg):
        try:
            return self.transport.call(msg)
        except Exception as e:  # noqa: BLE001 - best-effort control
            logger.warning(
                "draft %s: %s to gateway failed: %s",
                self.replica_id, type(msg).__name__, e,
            )
            return None
