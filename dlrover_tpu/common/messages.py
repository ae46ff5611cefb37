"""Typed control-plane messages (agent <-> master).

Capability parity with the ~60 message dataclasses of the reference
(``dlrover/python/common/grpc.py:161-512``), but serialized as **msgpack of a
typed registry** rather than pickle-over-gRPC (a reference wart — pickle is
version-fragile and unsafe across trust boundaries).  Only control-plane data
travels here: shard indices, rendezvous worlds, heartbeats, metrics.  Tensors
never do — they ride the shm arena (``dlrover_tpu.common.shm``) or ICI.

Every message is a dataclass registered by class name via
``__init_subclass__``; nested messages / lists / dicts of messages round-trip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import msgpack

_REGISTRY: Dict[str, type] = {}

#: Per-class field-name tuples, filled lazily on first encode.  Lazy
#: because ``__init_subclass__`` runs BEFORE the ``@dataclass``
#: decorator processes the class body, so fields aren't knowable at
#: registration time.
_FIELD_CACHE: Dict[type, tuple] = {}


class Message:
    """Base for all wire messages.  Subclasses must be dataclasses.

    ``_WIRE_OPTIONAL`` names fields that are OMITTED from the encoded
    form while empty/falsy (decode fills them from the dataclass
    default).  This is how a message grows a field — the observability
    trace context (ISSUE 12) — without changing the bytes of messages
    that don't carry it: the serving fast path stays byte-identical,
    and mixed-version peers interoperate (a missing key decodes to the
    default)."""

    _WIRE_OPTIONAL: frozenset = frozenset()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        _REGISTRY[cls.__name__] = cls


def _fields_of(cls: type) -> tuple:
    """(field names, wire-optional names) for ``cls``, cached."""
    entry = _FIELD_CACHE.get(cls)
    if entry is None:
        names = tuple(
            f.name for f in dataclasses.fields(cls)  # type: ignore[arg-type]
        )
        entry = (names, cls._WIRE_OPTIONAL)
        _FIELD_CACHE[cls] = entry
    return entry


# The encode/decode pair below is the serving tier's admission hot
# path (every submit/grant/poll crosses it; ISSUE 9's load-harness
# profile named it).  Two fast paths keep it cheap without losing
# generality:
#
# - per-class field names come from ``_FIELD_CACHE`` instead of a
#   ``dataclasses.fields`` reflection walk per message;
# - scalar containers pass through UNTOUCHED: a prompt of 200 ints (or
#   a stats dict of floats) needs no per-element _encode call and no
#   copied list — msgpack packs the original directly.  Only containers
#   actually holding a Message / dict / list keep the recursive walk.
#
# ``serialize_baseline`` keeps the original reflection-everywhere
# implementation alive as the load bench's measured reference point.

_RECURSE = (Message, dict, list, tuple)


def _encode(obj: Any) -> Any:
    if isinstance(obj, Message):
        cls = type(obj)
        out = {}
        names, optional = _fields_of(cls)
        for name in names:
            v = getattr(obj, name)
            if not v and name in optional:
                continue  # wire-optional and empty: omit (byte compat)
            out[name] = _encode(v) if isinstance(v, _RECURSE) else v
        return {"__msg__": cls.__name__, "f": out}
    if isinstance(obj, dict):
        for v in obj.values():
            if isinstance(v, _RECURSE):
                return {k: _encode(v) for k, v in obj.items()}
        return obj
    if isinstance(obj, (list, tuple)):
        for v in obj:
            if isinstance(v, _RECURSE):
                return [_encode(v) for v in obj]
        return obj if isinstance(obj, list) else list(obj)
    return obj


def _decode(obj: Any) -> Any:
    if isinstance(obj, dict):
        if "__msg__" in obj:
            cls = _REGISTRY[obj["__msg__"]]
            fields = {
                k: _decode(v) if isinstance(v, (dict, list)) else v
                for k, v in obj["f"].items()
            }
            return cls(**fields)
        for v in obj.values():
            if isinstance(v, (dict, list)):
                return {k: _decode(v) for k, v in obj.items()}
        return obj
    if isinstance(obj, list):
        for v in obj:
            if isinstance(v, (dict, list)):
                return [_decode(v) for v in obj]
        return obj
    return obj


def serialize(msg: Message) -> bytes:
    return msgpack.packb(_encode(msg), use_bin_type=True)


def deserialize(data: bytes) -> Message:
    return _decode(msgpack.unpackb(data, raw=False, strict_map_key=False))


def _encode_generic(obj: Any) -> Any:
    """The pre-fast-path encoder (reflection + per-element recursion
    everywhere) — kept as the reference the tests hold ``serialize``
    to, byte for byte (``serialize_baseline``); not used on any wire path."""
    if isinstance(obj, Message):
        optional = type(obj)._WIRE_OPTIONAL
        return {
            "__msg__": type(obj).__name__,
            "f": {
                f.name: _encode_generic(getattr(obj, f.name))
                for f in dataclasses.fields(obj)  # type: ignore[arg-type]
                if getattr(obj, f.name) or f.name not in optional
            },
        }
    if isinstance(obj, dict):
        return {k: _encode_generic(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_encode_generic(v) for v in obj]
    return obj


def serialize_baseline(msg: Message) -> bytes:
    """Byte-identical to :func:`serialize`, via the slow generic walk."""
    return msgpack.packb(_encode_generic(msg), use_bin_type=True)


# ---------------------------------------------------------------------------
# Generic envelope / responses
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BaseResponse(Message):
    success: bool = True
    reason: str = ""


@dataclasses.dataclass
class Empty(Message):
    """No-op probe: deliberately handler-less — tests ping servicers
    with it to exercise the unhandled-message path."""

    pass


# ---------------------------------------------------------------------------
# Node identity & lifecycle
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class NodeMeta(Message):
    """Agent self-registration (reference ``grpc.py NodeMeta``)."""

    node_type: str = "worker"
    node_id: int = 0
    node_rank: int = -1
    host: str = ""
    agent_port: int = 0
    slice_id: str = ""
    host_id: str = ""
    tpu_chips: int = 0
    local_world_size: int = 1


@dataclasses.dataclass
class ReportNodeStatus(Message):
    node_id: int = 0
    node_type: str = "worker"
    status: str = ""
    exit_reason: str = ""
    restart_count: int = 0


@dataclasses.dataclass
class NodeFailure(Message):
    """Agent-reported worker failure (reference ``grpc.py NodeFailure`` /
    ``report_failures master_client.py``)."""

    node_id: int = 0
    node_rank: int = -1
    error_data: str = ""
    level: str = "error"
    restart_count: int = 0


@dataclasses.dataclass
class Heartbeat(Message):
    node_id: int = 0
    timestamp: float = 0.0


@dataclasses.dataclass
class DiagnosisAction(Message):
    """Master's instruction piggybacked on the heartbeat reply (reference
    ``HeartbeatResponse`` carrying ``DiagnosisAction`` s)."""

    action_type: str = "no_action"
    instance: str = ""
    reason: str = ""
    payload: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class HeartbeatResponse(Message):
    actions: List[DiagnosisAction] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Rendezvous
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JoinRendezvous(Message):
    """(reference ``grpc.py JoinRendezvousRequest``)"""

    node_id: int = 0
    node_rank: int = -1
    local_world_size: int = 1
    rdzv_name: str = "elastic-training"
    node_ip: str = ""
    slice_id: str = ""
    # Unique per join *attempt*: lets the master tell an RPC-retried
    # duplicate (same id -> no-op) from a genuine re-join after restart
    # (new id -> evict the stale world membership).
    attempt_id: str = ""


@dataclasses.dataclass
class RendezvousRound(Message):
    round: int = 0


@dataclasses.dataclass
class CommWorldRequest(Message):
    node_id: int = 0
    rdzv_name: str = "elastic-training"


@dataclasses.dataclass
class CommWorld(Message):
    """The agreed world of one rendezvous round: ``world`` maps node_rank ->
    meta dict (id, local_world_size, host, slice).  ``group`` distinguishes
    paired sub-worlds in the network-check rendezvous
    (reference ``grpc.py CommWorldResponse`` / ``rdzv_manager.py:335``)."""

    rdzv_name: str = "elastic-training"
    round: int = 0
    group: int = 0
    world: dict = dataclasses.field(default_factory=dict)
    coordinator: str = ""  # host:port of the elected JAX coordinator


@dataclasses.dataclass
class WaitingNodeNumRequest(Message):
    rdzv_name: str = "elastic-training"


@dataclasses.dataclass
class WaitingNodeNum(Message):
    waiting_num: int = 0


# ---------------------------------------------------------------------------
# Master-hosted KV store (bootstrap plane, reference master_kv_store.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KVStoreSet(Message):
    key: str = ""
    value: bytes = b""


@dataclasses.dataclass
class KVStoreGet(Message):
    key: str = ""


@dataclasses.dataclass
class KVStoreValue(Message):
    key: str = ""
    value: bytes = b""
    found: bool = False


@dataclasses.dataclass
class KVStoreMultiSet(Message):
    kvs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class KVStoreMultiGet(Message):
    keys: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class KVStoreMultiValue(Message):
    kvs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class KVStoreAdd(Message):
    key: str = ""
    delta: int = 1
    # Idempotency token: the master caches token -> result, so an
    # RPC-retried add is applied exactly once (missing field on old
    # senders decodes to "" = no dedup, preserving wire compat).
    token: str = ""


@dataclasses.dataclass
class KVStoreCount(Message):
    value: int = 0


@dataclasses.dataclass
class KVStoreScan(Message):
    """Prefix scan (ISSUE 9): the serving tier's shared registry lists
    its gateway/replica entries (``serve/{job}/gw/``,
    ``serve/{job}/rep/``) without maintaining a racy index key."""

    prefix: str = ""


@dataclasses.dataclass
class KVStoreScanResult(Message):
    kvs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class KVStoreDelete(Message):
    """Delete one key (ISSUE 9): registry GC of stale gateway/replica
    leases needs removal, not just overwrite.

    ``token`` (ISSUE 14, graftcheck PC403): the delete is retried
    ``idempotent=True``, but its reply carries whether THIS call
    removed the key — a DEADLINE-retried duplicate whose first reply
    was lost would answer found=False for a delete that actually
    happened.  The master caches token -> first answer, the same
    exactly-once contract as ``KVStoreAdd``."""

    key: str = ""
    token: str = ""


# ---------------------------------------------------------------------------
# Dynamic data sharding (reference master/shard + grpc.py Task* messages)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DatasetShardParams(Message):
    """Worker -> master: register a dataset for dynamic sharding
    (reference ``grpc.py DatasetShardParams``)."""

    dataset_name: str = ""
    dataset_size: int = 0
    shard_size: int = 0
    batch_size: int = 0
    num_epochs: int = 1
    shuffle: bool = False
    task_type: str = "training"
    storage_type: str = "text"
    num_minibatches_per_shard: int = 0


@dataclasses.dataclass
class TaskRequest(Message):
    dataset_name: str = ""
    worker_id: int = 0
    # Idempotency token: a retried fetch returns the SAME task instead of
    # popping (and leaking) a second shard.
    token: str = ""


@dataclasses.dataclass
class Task(Message):
    """One unit of data to consume: an index range [start, end) of a shard
    (reference ``grpc.py Task``).  ``task_id < 0`` means no task available."""

    task_id: int = -1
    task_type: str = "training"
    dataset_name: str = ""
    start: int = 0
    end: int = 0
    epoch: int = 0


@dataclasses.dataclass
class TaskResult(Message):
    dataset_name: str = ""
    task_id: int = -1
    worker_id: int = 0
    success: bool = True
    err_message: str = ""


@dataclasses.dataclass
class ShardCheckpointRequest(Message):
    dataset_name: str = ""


@dataclasses.dataclass
class ShardCheckpoint(Message):
    """Serialized dataset progress for exactly-once resume
    (reference ``base_dataset_manager.py:60 DatasetShardCheckpoint``)."""

    dataset_name: str = ""
    content: str = ""  # JSON


# ---------------------------------------------------------------------------
# Health check / straggler detection
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class NetworkCheckResult(Message):
    """Per-node result of the paired matmul+psum pre-flight benchmark
    (reference ``report_network_check_status`` + ``grpc.py NetworkStatus``)."""

    node_id: int = 0
    succeeded: bool = True
    elapsed: float = 0.0
    round: int = 0


@dataclasses.dataclass
class NetworkReadyRequest(Message):
    pass


@dataclasses.dataclass
class FaultNodeRequest(Message):
    pass


@dataclasses.dataclass
class FaultNodes(Message):
    nodes: List[int] = dataclasses.field(default_factory=list)
    reason: str = ""


@dataclasses.dataclass
class StragglerRequest(Message):
    pass


@dataclasses.dataclass
class Stragglers(Message):
    nodes: List[int] = dataclasses.field(default_factory=list)
    times: dict = dataclasses.field(default_factory=dict)
    # True when the latest check round has results from every rendezvous
    # participant — agents poll until this settles instead of guessing.
    complete: bool = False


# ---------------------------------------------------------------------------
# Metrics / monitoring
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GlobalStep(Message):
    """(reference ``grpc.py GlobalStepRecord`` -> SpeedMonitor)"""

    node_id: int = 0
    step: int = 0
    timestamp: float = 0.0


@dataclasses.dataclass
class CkptPerf(Message):
    """Per-save flash-checkpoint timings (ISSUE 4): the worker's
    save_to_memory stall feeds the master's goodput accounting — a
    synchronous stall is lost train time even without a restart.
    ISSUE 7 adds the scale-out gauges: the node's AGGREGATE persist
    throughput (sliced persist sums the ranks' disjoint-slice writes)
    and the dirty-fence skip count of the last incremental save."""

    node_id: int = 0
    step: int = 0
    stall_ms: float = 0.0
    staged_mbps: float = 0.0
    persist_mbps: float = 0.0
    agg_persist_mbps: float = 0.0
    # -1 = "not measured by this report" (stall-only reports must not
    # zero a node's skip gauge); >= 0 is a real count.
    tensors_skipped: int = -1


@dataclasses.dataclass
class UsedResource(Message):
    node_id: int = 0
    cpu_percent: float = 0.0
    memory_mb: float = 0.0
    tpu_duty_cycle: float = 0.0
    hbm_used_mb: float = 0.0


@dataclasses.dataclass
class ModelInfo(Message):
    num_params: int = 0
    flops_per_step: float = 0.0
    batch_size_per_step: int = 0
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DiagnosisReport(Message):
    """Agent -> master periodic diagnosis payload (reference
    ``diagnosis/common/diagnosis_data.py``)."""

    node_id: int = 0
    data_type: str = ""
    content: str = ""
    timestamp: float = 0.0


# ---------------------------------------------------------------------------
# Sync service (named barriers, reference sync_service.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SyncJoin(Message):
    sync_name: str = ""
    node_id: int = 0
    node_rank: int = -1


@dataclasses.dataclass
class SyncFinish(Message):
    sync_name: str = ""


@dataclasses.dataclass
class SyncQuery(Message):
    sync_name: str = ""


# ---------------------------------------------------------------------------
# Checkpoint coordination
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CheckpointSync(Message):
    """Cross-node shard-step consistency barrier before commit
    (reference ``servicer._sync_checkpoint :609``)."""

    node_id: int = 0
    step: int = 0


# ---------------------------------------------------------------------------
# Config push (reference get_elastic_run_config / ParallelConfig)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ElasticRunConfigRequest(Message):
    pass


@dataclasses.dataclass
class ElasticRunConfig(Message):
    configs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ParallelConfigRequest(Message):
    node_id: int = 0


@dataclasses.dataclass
class ParallelConfig(Message):
    """Master-tuned runtime knobs hot-reloaded by the trainer (reference
    ``grpc.py ParallelConfig/DataLoaderConfig/OptimizerConfig:439-483``)."""

    dataloader: dict = dataclasses.field(default_factory=dict)
    optimizer: dict = dataclasses.field(default_factory=dict)
    mesh: dict = dataclasses.field(default_factory=dict)
    restart: bool = False
    version: int = 0


@dataclasses.dataclass
class JobExitRequest(Message):
    node_id: int = 0
    reason: str = ""
    success: bool = True


# ---------------------------------------------------------------------------
# Checkpoint replicas (agent <-> agent; reference flash_checkpoint/replica.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ReplicaPush(Message):
    """Backup one process's staged checkpoint shard onto a peer node
    (reference ``CkptReplicaManger.backup replica.py:57``)."""

    owner_node: int = 0
    process_id: int = 0
    step: int = 0
    payload: bytes = b""


@dataclasses.dataclass
class ReplicaFetch(Message):
    process_id: int = 0
    min_step: int = -1


@dataclasses.dataclass
class ReplicaData(Message):
    found: bool = False
    step: int = -1
    payload: bytes = b""


# ---------------------------------------------------------------------------
# Serving fleet (gateway <-> clients, gateway <-> replicas; ISSUE 5).
# The reference has no serving control plane at all (its RL stack shells
# out to an unsupervised vllm, atorch/rl/model_engine/model_engine.py:35);
# these messages are the typed wire surface of dlrover_tpu.serving.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeSubmit(Message):
    """Client -> gateway: one inference request.  ``req_id`` doubles as
    the idempotency token (BoundedTokenCache dedupe): a retried submit
    of a completed request returns the cached result instead of
    decoding twice.

    Prefix-aware routing (ISSUE 8): ``prompt`` carries the FULL token
    sequence; ``prefix_len > 0`` declares its leading tokens a shared
    template whose fingerprint ``prefix_fp`` the gateway routes on
    (warm replicas first) and the replica prefix-caches.

    The same dataclass is the gateway -> replica grant: ``stage``
    selects the path (``full`` = prefill+decode on one replica;
    ``prefill`` = score the prompt and hand the KV segment back;
    ``decode`` = continue from the attached ``kv`` segment, packed by
    ``llama_infer.pack_kv_segment`` with an embedded CRC)."""

    req_id: str = ""
    prompt: List[int] = dataclasses.field(default_factory=list)
    max_new_tokens: int = 16
    deadline_s: float = 0.0  # 0 = no per-request deadline
    prefix_len: int = 0  # leading tokens shared with other requests
    prefix_fp: str = ""  # fingerprint of prompt[:prefix_len]
    stage: str = "full"  # full | prefill | decode (grant direction)
    kv: bytes = b""  # packed KV segment (relayed decode grants only)
    # Peer-to-peer KV handoff (ISSUE 9).  On a decode grant, a
    # non-empty ``kv_addr`` is a TICKET: the decode replica pulls the
    # segment bytes directly from the prefill replica's segment server
    # at that address (``KvSegmentFetch``), verifying ``kv_crc32`` /
    # ``kv_nbytes`` / ``kv_fp`` — the gateway never touched the bytes.
    # On a prefill grant, ``kv_relay=True`` orders the old
    # through-the-gateway payload path (the fallback after a failed
    # pull, and the compat mode for non-P2P replicas).
    kv_addr: str = ""
    kv_fp: str = ""
    kv_crc32: int = 0
    kv_nbytes: int = 0
    kv_relay: bool = False
    #: Distributed-trace context (ISSUE 12): ``{"tid": trace_id,
    #: "sid": parent span id}``.  Wire-optional — a trace-less submit
    #: (or an unsampled request's grant) encodes byte-identically to
    #: the pre-trace wire, keeping the msgpack fast path intact.
    trace: dict = dataclasses.field(default_factory=dict)
    #: Cross-cell spillover (ISSUE 17).  A saturated/dying cell's
    #: gateway forwards the submit to a sibling cell UNDER THE SAME
    #: req_id — the hop rides the existing req_id-keyed dedupe/journal
    #: contracts, so killing either side mid-hop still completes the
    #: request exactly once.  ``spill_from`` names the origin cell;
    #: ``spill_hops`` counts forwards so depth stays bounded (a request
    #: never ping-pongs between two saturated cells).  Both are
    #: wire-optional: a local submit encodes byte-identically to the
    #: pre-spillover wire.
    spill_from: str = ""
    spill_hops: int = 0

    _WIRE_OPTIONAL = frozenset({"trace", "spill_from", "spill_hops"})


@dataclasses.dataclass
class ServeAck(Message):
    """Gateway's immediate answer to a submit: ``accepted`` (queued),
    ``rejected`` with an explicit ``retry_after_s`` (bounded-queue
    backpressure: the client backs off instead of the queue growing
    without bound), or a terminal state from the dedupe cache —
    ``done`` (tokens included), ``failed``, or ``timeout`` (the req_id
    is the idempotency key; retry a failure under a fresh id)."""

    req_id: str = ""
    status: str = "accepted"  # accepted | done | rejected
    tokens: List[int] = dataclasses.field(default_factory=list)
    retry_after_s: float = 0.0
    reason: str = ""


@dataclasses.dataclass
class ServeStatusRequest(Message):
    req_id: str = ""


@dataclasses.dataclass
class ServeStatusReply(Message):
    """``state``: queued | running | done | failed | timeout | unknown.
    ``tokens`` carries the streamed-so-far prefix while running and the
    full completion once done."""

    req_id: str = ""
    state: str = "unknown"
    tokens: List[int] = dataclasses.field(default_factory=list)
    replica: str = ""
    reason: str = ""


@dataclasses.dataclass
class ServeReplicaRegister(Message):
    """``role`` (ISSUE 8): ``unified`` replicas run the full
    prefill+decode path; ``prefill`` replicas only score prompts and
    export KV segments; ``decode`` replicas only continue from imported
    segments (missing field on old senders decodes to "" = unified).

    Speculative serving (ISSUE 11): ``spec`` advertises that this
    replica can run speculative decode rounds (a local draft model, or
    a server sized to accept a remote draft handle) — the gateway's
    grant scan prefers spec replicas for long-decode requests.  A
    ``draft``-role replica additionally announces ``draft_addr``, the
    address of its proposal server, which the gateway hands to spec
    targets in every poll reply."""

    replica_id: str = ""
    slots: int = 0
    role: str = "unified"  # unified | prefill | decode | draft
    spec: bool = False
    draft_addr: str = ""


@dataclasses.dataclass
class ServeReplicaDeregister(Message):
    replica_id: str = ""


@dataclasses.dataclass
class ServeReplicaPoll(Message):
    """Replica -> gateway heartbeat + work pull.  ``active`` lists every
    req_id the replica currently owns (pending + in-flight) so the
    gateway can reconcile lost grants; ``stats`` carries slot occupancy
    / queue depth / TTFT / tokens-per-second / speculative acceptance
    for the fleet gauges and the autoscaler."""

    replica_id: str = ""
    free_slots: int = 0
    active: List[str] = dataclasses.field(default_factory=list)
    stats: dict = dataclasses.field(default_factory=dict)
    #: Prefix-template fingerprints this replica holds warm (ISSUE 8):
    #: replaces the gateway's residency entry wholesale every poll, so
    #: the routing map self-corrects (LRU evictions, restarts).
    warm_prefixes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServeGrants(Message):
    """Gateway -> replica poll reply: new work, cancellations (deadline
    expiries — the replica drops them from its pending queue, or sheds
    the slot mid-decode via ``DecodeServer.abort``), the drain flag
    (stop admitting, finish in-flight, deregister), and ``known``
    (False = the gateway restarted and lost this replica — re-register)."""

    requests: List[ServeSubmit] = dataclasses.field(default_factory=list)
    cancel: List[str] = dataclasses.field(default_factory=list)
    drain: bool = False
    known: bool = True
    #: Current draft-proposal endpoint (ISSUE 11): the address of a
    #: live draft-role replica's proposal server, refreshed every poll
    #: so spec targets attach/detach their remote draft as draft
    #: replicas come and go ("" = no draft alive).
    draft_addr: str = ""


@dataclasses.dataclass
class ServeTokens(Message):
    """Replica -> gateway: streamed tokens for one in-flight request
    (batched per poll round — the burst size is the dispatch batching
    the decode paths buy throughput with)."""

    replica_id: str = ""
    req_id: str = ""
    tokens: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServeDone(Message):
    """Replica -> gateway: terminal completion report.  Idempotent: the
    gateway dedupes by req_id, so a journal replay after a replica kill
    (``replayed=True``) or a re-dispatch race can never complete a
    request twice."""

    replica_id: str = ""
    req_id: str = ""
    tokens: List[int] = dataclasses.field(default_factory=list)
    ok: bool = True
    reason: str = ""
    replayed: bool = False
    #: Per-request speculation telemetry (ISSUE 11): the accepted-
    #: tokens-per-round this request earned and the speculative rounds
    #: it rode.  Journaled with the completion, so a replay reports
    #: the SAME numbers the request earned live (0 = never speculated).
    tokens_per_round: float = 0.0
    spec_rounds: int = 0
    #: Trace context of a JOURNAL-REPLAYED completion (ISSUE 12): the
    #: replica ships the trace id the request earned when served live,
    #: so a replay landing at a fresh gateway (failover adoption) joins
    #: the ORIGINAL trace instead of orphaning a new one.  Empty on
    #: live completions (the gateway already holds the context) and
    #: omitted from the wire (byte compat).
    trace: dict = dataclasses.field(default_factory=dict)

    _WIRE_OPTIONAL = frozenset({"trace"})


@dataclasses.dataclass
class ServeKvReady(Message):
    """Prefill replica -> gateway: the prefill-grant's KV segment is
    ready (stage two of the disaggregated path, ISSUE 8).  ``payload``
    is ``llama_infer.pack_kv_segment`` bytes (CRC embedded);
    ``fp32_bytes`` is the segment's un-quantized size so the int8
    transfer saving is measurable at the gateway without unpacking.

    Peer-to-peer mode (ISSUE 9): ``payload`` stays EMPTY and the
    message carries only a ticket — ``addr`` of the prefill replica's
    segment server plus the segment's ``seg_fp``/``crc32``/``nbytes``
    — which the gateway holds and attaches to the decode grant; the
    decode replica pulls the bytes directly from the peer."""

    replica_id: str = ""
    req_id: str = ""
    payload: bytes = b""
    fp32_bytes: int = 0
    addr: str = ""  # non-empty = ticket mode (P2P)
    seg_fp: str = ""
    crc32: int = 0
    nbytes: int = 0
    #: Trace context (ISSUE 12), wire-optional (byte compat).
    trace: dict = dataclasses.field(default_factory=dict)

    _WIRE_OPTIONAL = frozenset({"trace"})


@dataclasses.dataclass
class KvSegmentFetch(Message):
    """Decode replica -> prefill replica's segment server (ISSUE 9):
    pull the published KV segment for ``req_id``.  ``seg_fp`` pins the
    exact segment the ticket promised — a re-prefilled request must
    never decode from a stale publication under the same req_id."""

    req_id: str = ""
    seg_fp: str = ""


@dataclasses.dataclass
class KvSegmentData(Message):
    found: bool = False
    reason: str = ""
    payload: bytes = b""
    crc32: int = 0


@dataclasses.dataclass
class DraftRoll(Message):
    """Spec target replica -> draft replica's proposal server (ISSUE
    11): one speculative round's proposal fetch for every stream the
    target is speculating.  Each entry of ``streams`` is a dict —
    ``{"rid": str, "ctx": [ints emitted since the last roll], "open":
    [prompt tokens]}`` (``open`` only on the first roll of a stream, or
    after the draft evicted it) — the draft catches its per-stream
    cache up from exactly that delta, rolls ``k`` proposals, and ships
    them back CRC-wrapped (the KV-segment envelope idiom).  ``close``
    piggybacks finished/aborted stream ids for cache hygiene."""

    replica_id: str = ""
    k: int = 4
    sample: bool = False
    streams: List[dict] = dataclasses.field(default_factory=list)
    close: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DraftProposals(Message):
    """Proposal-server reply: ``payload`` is the CRC-wrapped msgpack
    proposal bundle (``serving.draft.pack_proposals``); ``found=False``
    carries the failure reason — the target degrades to plain decode,
    it never waits."""

    found: bool = False
    reason: str = ""
    payload: bytes = b""


@dataclasses.dataclass
class ServeKvReject(Message):
    """Decode replica -> gateway: the decode-grant's KV segment failed
    verification (torn in flight — chaos ``serving.kv_drop``).  The
    gateway drops the payload and re-queues the request for a fresh
    prefill (bounded by ``max_attempts``); a torn segment is NEVER
    decoded from."""

    replica_id: str = ""
    req_id: str = ""
    reason: str = ""


@dataclasses.dataclass
class ServeDrainRequest(Message):
    """Operator/autoscaler -> gateway: drain one replica (scale-down)."""

    replica_id: str = ""


@dataclasses.dataclass
class ServeFleetStatsRequest(Message):
    pass


@dataclasses.dataclass
class ServeFleetStats(Message):
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ObsScrapeRequest(Message):
    """Live flight-recorder scrape (ISSUE 12): pull the process's
    bounded event ring over the existing RPC idiom.  ``since_seq``
    resumes an incremental scrape (0 = everything still in the ring)."""

    since_seq: int = 0


@dataclasses.dataclass
class ObsScrape(Message):
    """Scrape reply: ``events`` are the recorder's structured dicts
    (spans + journal events), ``dropped`` the ring's lifetime drop
    count (bounded ring — every drop is counted, never silent), and
    ``next_seq`` the cursor for the next incremental scrape."""

    process: str = ""
    events: list = dataclasses.field(default_factory=list)
    dropped: int = 0
    next_seq: int = 0


@dataclasses.dataclass
class FleetStatsRequest(Message):
    """Fleet control-plane view (ISSUE 10): per-role desired/observed
    membership, drains in flight and cross-role policy phases."""

    pass


@dataclasses.dataclass
class FleetStats(Message):
    roles: dict = dataclasses.field(default_factory=dict)
    policies: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Embedding store service (PS analogue; reference tfplus KvVariable serving)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EmbeddingOp(Message):
    """One embedding-store RPC: op in {lookup, apply, export,
    export_keys, import, delete, filter, size}.  keys/grads/blob are
    packed numpy bytes."""

    table: str = ""
    op: str = "lookup"
    keys: bytes = b""
    grads: bytes = b""
    blob: bytes = b""
    train: bool = True
    optimizer: dict = dataclasses.field(default_factory=dict)
    rank_filter: int = 0
    world: int = 1
    min_freq: int = 0
    max_version_age: int = 0


@dataclasses.dataclass
class EmbeddingResult(Message):
    success: bool = True
    reason: str = ""
    rows: bytes = b""
    blob: bytes = b""
    count: int = 0


# ---------------------------------------------------------------------------
# Live resharding (ISSUE 6): mesh-to-mesh state moves without restart
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ReshardFetch(Message):
    """Pull one plan segment's bytes from a peer's published shard table.

    ``box`` is the segment's region in global tensor coordinates
    (``[[start, stop], ...]``); the peer slices it out of its local shard
    and answers with CRC-verified bytes."""

    epoch: int = 0
    step: int = -1
    src_rank: int = 0
    key: str = ""  # "<path>|<k>" shard key in the peer's table
    box: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ReshardSegment(Message):
    found: bool = False
    reason: str = ""
    payload: bytes = b""
    crc32: int = 0
    dtype: str = ""
    shape: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ReshardEpochRequest(Message):
    """Worker poll: is a live resize pending? (``epoch`` = last epoch the
    caller observed; the master answers with the current one)."""

    node_id: int = 0
    epoch: int = -1


@dataclasses.dataclass
class ReshardEpochInfo(Message):
    """The master's resize broadcast: at ``epoch`` the job wants
    ``target_num_processes`` processes laid out as ``target_spec``
    (MeshSpec axis sizes).  ``status`` in {idle, preparing, done,
    aborted}."""

    epoch: int = -1
    status: str = "idle"
    target_num_processes: int = 0
    target_spec: dict = dataclasses.field(default_factory=dict)
    deadline_s: float = 0.0


@dataclasses.dataclass
class ReshardReport(Message):
    """A worker's verdict on one resize epoch: live reshard completed
    (``ok``) or failed with ``reason`` (the master then lets the
    checkpoint-restart ladder run)."""

    node_id: int = 0
    epoch: int = 0
    ok: bool = False
    reason: str = ""
    downtime_ms: float = 0.0
    moved_mb: float = 0.0


@dataclasses.dataclass
class ReshardAnnounce(Message):
    """Operator/admin request: announce a live resize epoch (ISSUE 13).
    Until now only the in-process autoscaler could announce; this RPC
    lets an operator (or a test harness) open an epoch from outside.
    The reply is a ``ReshardEpochInfo`` for the announced epoch."""

    node_id: int = 0
    target_num_processes: int = 0
    target_spec: dict = dataclasses.field(default_factory=dict)
    expected_reports: int = 0
    deadline_s: float = 0.0  # 0 = the master's configured default


@dataclasses.dataclass
class JournalFetch(Message):
    """Standby -> primary streaming replication (ISSUE 13): read the
    control-state WAL from byte ``offset``.  ``offset=-1`` asks for the
    current snapshot file instead; the mirror then (re-)reads the WAL
    from byte 0 — frames carry their own seq, so a tail dedupes any
    overlap, and a compaction is detected via the reply's
    ``wal_size``/``wal_ino``."""

    offset: int = 0
    max_bytes: int = 1 << 20


@dataclasses.dataclass
class JournalChunk(Message):
    """A chunk of the primary's WAL (or snapshot, for ``offset=-1``).
    ``eof`` means no bytes past ``offset`` right now (poll again);
    ``found`` is False when the primary runs without a state journal.
    ``wal_size``/``wal_ino`` identify the remote WAL file (size + inode
    of the open fd the bytes were read from): a mirror that sees the
    inode change — or its offset exceed the size — knows the primary
    compacted (atomic-replaced) the file and rebuilds instead of
    appending new-inode bytes at an old-inode offset."""

    data: bytes = b""
    offset: int = 0  # offset of the FIRST byte of ``data``
    eof: bool = True
    found: bool = True
    wal_size: int = -1
    wal_ino: int = 0


# ---------------------------------------------------------------------------
# Multi-cell control plane (ISSUE 15): cell snapshot + placement wire
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellSnapshotRequest(Message):
    """Federation -> cell master: one snapshot read (identity, ring
    view, placement epoch, node/task/pool counts).  Pure read — safe
    for ``idempotent=True`` retries — and the ONLY recurring RPC the
    federation tier makes, TTL-cached on its side so a cell pays at
    most one per refresh interval."""

    cell_id: str = ""


@dataclasses.dataclass
class CellSnapshot(Message):
    """A cell master's snapshot body (``CellManager.snapshot`` plus
    the hosting master's live stats).  ``found=False`` means the
    answering master carries no cell identity (a plain single-master
    job asked by mistake)."""

    cell_id: str = ""
    snapshot: dict = dataclasses.field(default_factory=dict)
    found: bool = True


@dataclasses.dataclass
class CellPlacementUpdate(Message):
    """Federation -> cell master: adopt this role plan (role -> member
    count for THIS cell).  Idempotent by ``epoch`` — the handler
    journals then applies only strictly-newer epochs, so a
    DEADLINE-retried push (or two federations racing) converges on the
    highest epoch without tokens (nothing is consumed)."""

    cell_id: str = ""
    epoch: int = -1
    placement: dict = dataclasses.field(default_factory=dict)
