"""Cross-replica sliced persist + dirty-fence incremental saves (ISSUE 7).

The planning layer between the checkpoint engines and the shard writer:

- **Slicing** (:func:`plan_persist`): when a tensor's box is held by
  several ranks (``owners`` from the staged ``tensors_info`` — derived at
  stage time from the leaf's global device->index map, so every rank
  computes the same assignment with zero negotiation), each owner writes
  only a *disjoint, element-aligned, byte-balanced* sub-range of the
  box's C-order buffer.  Aggregate save bandwidth then scales with the
  replica count instead of funnelling every replicated byte through one
  rank's storage link (Orbax 2605.23066 / cross-replica update sharding
  2004.13336).  Tensors smaller than :data:`SLICE_MIN_BYTES` go whole to
  one deterministically-hashed owner instead of degenerate shreds.

- **Dirty fences** (:class:`DirtyTracker`): a save skips tensors whose
  staged bytes carry the same CRC fingerprint the rank persisted at its
  *holder* step (the probe CRCs the staged bytes where they are — on
  the streamed paths they are ``read()`` off the shm arena chunk by
  chunk for it — and runs on
  the async persist path, never the synchronous train stall), writing
  a meta ``ref`` to the holder's bytes instead.  Chains are flattened —
  every ref targets the step physically holding the bytes — rotation
  keeps referenced steps alive, and fsck verifies the chain.

- **The coverage proof** (:func:`step_covers`): commit is allowed only
  when the present shards' slices provably tile every tensor.  The proof
  is *reused* from the resharding planner: each tensor's byte buffer is
  a 1-D tensor, each slice a 1-D box, and ``build_plan(src, dst)`` +
  ``ReshardPlan.validate()`` prove exact coverage of the full range —
  no gap, no phantom bytes (``reshard/plan.py``, PR 6).

Pure planning + storage metadata reads — importable without jax, so fsck
can run the coverage proof on any host that sees the storage.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Optional, Tuple

import numpy as np

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.shm import ArenaTensor

#: Below this size a tensor is not shredded across owners: it goes whole
#: to one deterministically-chosen owner (hash-balanced across keys).
SLICE_MIN_BYTES = 1 << 16


def slice_bounds(
    nbytes: int, itemsize: int, n_owners: int, owner_index: int
) -> Tuple[int, int]:
    """Byte range ``[lo, hi)`` of one owner's slice of an ``nbytes``
    buffer split across ``n_owners``: element-aligned (no dtype element
    is ever split), contiguous across owners, byte-balanced to within one
    element."""
    if n_owners <= 1:
        return 0, nbytes
    isz = max(1, int(itemsize))
    n_elems = nbytes // isz
    i = int(owner_index)
    lo = (i * n_elems // n_owners) * isz
    if i == n_owners - 1:
        return lo, nbytes
    return lo, ((i + 1) * n_elems // n_owners) * isz


def owner_of_small(key: str, n_owners: int) -> int:
    """Deterministic single owner index for a small tensor — hash-spread
    so many small tensors balance across the replica set."""
    return zlib.crc32(key.encode()) % max(1, n_owners)


def _effective_owners(meta: Optional[dict], world: int) -> Optional[list]:
    """The ranks holding this key's exact box, or ``None`` when unknown
    (then never sliced).  Host leaves are rank-identical by the same
    assumption the restore path has always made, so they are owned by
    the whole world."""
    if meta is None:
        return None
    owners = meta.get("owners")
    if owners is not None:
        return [int(r) for r in owners]
    if meta.get("host"):
        return list(range(world))
    return None


def _byte_range(arr, lo: int, hi: int):
    """Bytes ``[lo, hi)`` of a staged tensor's C-order buffer, flat
    uint8: a view of an array, a narrower handle of an
    :class:`ArenaTensor` (nothing is read here)."""
    if isinstance(arr, ArenaTensor):
        return arr.byte_range(lo, hi)
    contig = np.ascontiguousarray(arr)
    if contig.nbytes == 0:
        return np.empty(0, dtype=np.uint8)
    return contig.reshape(-1).view(np.uint8)[lo:hi]


@dataclasses.dataclass
class SliceHolder:
    """Where one key's slice bytes physically live + the fence
    fingerprint they were persisted with."""

    step: int
    lo: int
    hi: int
    full_nbytes: int
    crc32: int  # CRC of the staged slice bytes == the written blob's CRC


class DirtyTracker:
    """Per-rank memory of what was persisted where — the consumer of the
    arena's per-tensor commit fences.  Lost on restart (the next save is
    then simply full, never wrong)."""

    def __init__(self):
        self._holders: Dict[str, SliceHolder] = {}

    def holder(self, key: str) -> Optional[SliceHolder]:
        return self._holders.get(key)

    def note_plan(self, plan: "PersistPlan", step: int,
                  crcs: Dict[str, int]) -> None:
        """Record a SUCCESSFUL write of ``plan`` at ``step``: written keys
        get this step as holder (with the writer's streamed CRCs); ref'd
        keys keep their existing holder."""
        for key, (lo, hi, full) in plan.layout.items():
            if key in plan.refs:
                continue
            crc = crcs.get(key)
            if crc is None:
                continue
            self._holders[key] = SliceHolder(
                step=int(step), lo=lo, hi=hi, full_nbytes=full,
                crc32=int(crc),
            )

    def reset(self) -> None:
        self._holders.clear()


@dataclasses.dataclass
class PersistPlan:
    """What one rank actually streams for one save."""

    tensors: Dict[str, np.ndarray]  # payloads to write (or ArenaTensors)
    meta_extra: Dict[str, dict]  # per-key shard-meta overlays
    extra: dict  # shard extra (copy; ref_steps/sliced markers added)
    layout: Dict[str, Tuple[int, int, int]]  # key -> (lo, hi, full_nbytes)
    refs: Dict[str, int]  # key -> holder step (skipped writes)
    skipped: int
    written_bytes: int  # tensor bytes this rank streams
    logical_bytes: int  # this rank's full unsliced staged bytes


def plan_persist(
    tensors: Dict[str, np.ndarray],
    extra: dict,
    *,
    process_id: int,
    num_processes: int,
    sliced: bool = True,
    tracker: Optional[DirtyTracker] = None,
    holder_exists=None,
) -> PersistPlan:
    """Turn a staged state into this rank's slice of it.

    ``holder_exists(step)`` (when a ``tracker`` is given) must confirm a
    holder step's shard file is still on storage before a ref may target
    it — a holder lost to GC/quarantine forces a rewrite, never a
    dangling reference.  The dirty probe CRCs the staged slice bytes
    in-process (memory speed; a tensor still in the arena is ``read()``
    chunk by chunk for it); the writes it avoids run at storage-link
    speed, which is the asymmetry incremental saves monetize.

    Registered as a sim-bound pure policy (graftcheck DET70x): slice
    assignment is a function of (tensors, process_id, num_processes)
    only — no ambient effects, so every rank computes the identical
    partition without coordination."""
    from dlrover_tpu.checkpoint.shard_file import _dtype_key, crc32_staged

    info = extra.get("tensors_info") or {}
    out: Dict[str, np.ndarray] = {}
    meta_extra: Dict[str, dict] = {}
    layout: Dict[str, Tuple[int, int, int]] = {}
    refs: Dict[str, int] = {}
    skipped = 0
    written = 0
    logical = 0
    holder_alive: Dict[int, bool] = {}
    # A holder AT the step being written is the very file this write
    # replaces (one step persisted twice, e.g. a breakpoint save racing
    # the event-loop persist): a ref to it would point at itself and
    # destroy the only copy of the bytes.
    cur_step = extra.get("step")
    for key, arr in tensors.items():
        if not isinstance(arr, ArenaTensor):
            arr = np.asarray(arr)
        n = int(arr.nbytes)
        logical += n
        owners = _effective_owners(info.get(key), num_processes)
        lo, hi = 0, n
        if (
            sliced
            and owners
            and len(owners) > 1
            and process_id in owners
            and n > 0
        ):
            if n <= SLICE_MIN_BYTES:
                mine = owner_of_small(key, len(owners))
                lo, hi = (0, n) if owners.index(process_id) == mine else (0, 0)
            else:
                lo, hi = slice_bounds(
                    n, arr.dtype.itemsize, len(owners),
                    owners.index(process_id),
                )
        part = (lo, hi) != (0, n)
        base_meta = {
            "dtype": _dtype_key(arr.dtype),
            "shape": list(arr.shape),
        }
        if part:
            base_meta["slice"] = [lo, hi]
            base_meta["full_nbytes"] = n
        layout[key] = (lo, hi, n)
        view = _byte_range(arr, lo, hi) if part else None
        h = tracker.holder(key) if tracker is not None else None
        if (
            h is not None
            and h.step != cur_step
            and (h.lo, h.hi, h.full_nbytes) == (lo, hi, n)
            and hi > lo
        ):
            alive = holder_alive.get(h.step)
            if alive is None:
                alive = bool(holder_exists(h.step)) if holder_exists else False
                holder_alive[h.step] = alive
            probe = view if view is not None else _byte_range(arr, 0, n)
            if alive and crc32_staged(probe) == h.crc32:
                # Fence untripped: reference the holder's bytes.  The
                # payload written is EMPTY, so full_nbytes must ride the
                # meta even for unsliced entries — the coverage proof
                # reads the covered range from it, never from the
                # (zero) payload size.
                out[key] = np.empty(0, dtype=np.uint8)
                meta_extra[key] = dict(
                    base_meta,
                    full_nbytes=n,
                    ref={"step": h.step, "crc32": h.crc32,
                         "nbytes": hi - lo},
                )
                refs[key] = h.step
                skipped += 1
                continue
        out[key] = view if part else arr
        if part:
            meta_extra[key] = base_meta
        written += int(out[key].nbytes)
    write_extra = dict(extra)
    if refs:
        write_extra["ref_steps"] = sorted({int(s) for s in refs.values()})
    if any("slice" in m for m in meta_extra.values()):
        write_extra["sliced"] = True
    return PersistPlan(
        tensors=out,
        meta_extra=meta_extra,
        extra=write_extra,
        layout=layout,
        refs=refs,
        skipped=skipped,
        written_bytes=written,
        logical_bytes=logical,
    )


# -- the coverage proof (commit gate) ------------------------------------


def step_covers(
    storage,
    ckpt_dir: str,
    step: int,
    manifests: Optional[dict] = None,
) -> Tuple[bool, str]:
    """Prove the step's present shards cover every tensor exactly — the
    reshard planner's :meth:`ReshardPlan.validate` tiling proof, run
    twice:

    1. **Bytes of each box**: pieces are identified by ``(path, box)``
       from the shard's placement info — NOT by the per-rank local key,
       which collides across ranks for sharded (non-replicated) leaves —
       and each box's present byte slices must tile its full C-order
       buffer (each box a 1-D tensor, each slice a 1-D box).
    2. **Boxes of each tensor**: the complete boxes must tile the
       tensor's global shape (the N-D proof), so a dead rank's
       EXCLUSIVE shard of a sharded leaf is caught even when a lying
       done-vote hides the loss.

    Ref entries count as covering their range — their bytes are durable
    elsewhere and fsck verifies the chain.  Returns ``(ok, reason)``;
    any failure means "do not commit"."""
    from dlrover_tpu.checkpoint import shard_file
    from dlrover_tpu.reshard.plan import (
        MeshLayout,
        PlanError,
        TensorInfo,
        build_plan,
    )

    if manifests is None:
        manifests = {}
        try:
            pids = shard_file.list_shard_ids(storage, ckpt_dir, step)
        except Exception as e:  # noqa: BLE001 - unlistable step dir
            return False, f"step dir unlistable: {e}"
        for pid in pids:
            try:
                man = shard_file.read_shard_manifest(
                    storage, ckpt_dir, step, pid
                )
            except shard_file.ShardCorruptionError as e:
                return False, f"shard {pid} meta unreadable: {e}"
            if man is not None:
                manifests[pid] = man
    if not manifests:
        return False, "no shards present"
    box_full: Dict[str, int] = {}  # box id -> full byte size
    paths_expected: set = set()
    paths_present: set = set()
    byte_shards: Dict[int, Dict[str, tuple]] = {}
    nd_tensors: Dict[str, TensorInfo] = {}
    nd_shards: Dict[int, Dict[str, tuple]] = {}
    for pid, man in manifests.items():
        for p in man.extra.get("tree_paths") or []:
            paths_expected.add(p)
        info = man.extra.get("tensors_info") or {}
        keyed: Dict[str, tuple] = {}
        nd_keyed: Dict[str, tuple] = {}
        for key, tm in man.tensors.items():
            im = info.get(key)
            if not isinstance(im, dict) or "path" not in im \
                    or "index" not in im:
                # Unplaceable bytes cannot be proven to cover anything.
                return False, f"shard {pid}: no placement for {key!r}"
            path = im["path"]
            paths_present.add(path)
            box = tuple((int(s), int(e)) for s, e in im["index"])
            bid = f"{path}@{'/'.join(f'{s}:{e}' for s, e in box)}"
            sl = tm.get("slice")
            ref = tm.get("ref") if isinstance(tm.get("ref"), dict) else None
            n_full = int(
                tm.get("full_nbytes")
                # older incremental meta: an unsliced ref's payload IS
                # the full tensor, so the ref's byte count stands in
                or ((ref or {}).get("nbytes", 0) if not sl else 0)
                or tm.get("nbytes")
                or 0
            )
            lo, hi = (int(sl[0]), int(sl[1])) if sl else (0, n_full)
            prev = box_full.get(bid)
            if prev is not None and prev != n_full:
                return (
                    False,
                    f"{bid!r}: full size disagrees across ranks "
                    f"({prev} vs {n_full})",
                )
            box_full[bid] = n_full
            if hi > lo:
                keyed[f"{bid}|{pid}"] = ((lo, hi),)
            gshape = tuple(int(d) for d in im.get("global_shape") or [])
            ti = nd_tensors.get(path)
            if ti is None:
                nd_tensors[path] = TensorInfo(
                    path=path, global_shape=gshape, dtype=None
                )
            elif ti.global_shape != gshape:
                return (
                    False,
                    f"{path!r}: global shape disagrees across ranks "
                    f"({ti.global_shape} vs {gshape})",
                )
            nd_keyed[f"{path}|@{bid}"] = box
        byte_shards[int(pid)] = keyed
        nd_shards[int(pid)] = nd_keyed
    missing_paths = paths_expected - paths_present
    if missing_paths:
        return (
            False,
            f"tensor paths absent from every present shard: "
            f"{sorted(missing_paths)[:3]}",
        )
    tinfos = {
        bid: TensorInfo(path=bid, global_shape=(n,), dtype="uint8")
        for bid, n in box_full.items()
    }
    src = MeshLayout(tensors=tinfos, shards=byte_shards)
    dst = MeshLayout(
        tensors=tinfos,
        shards={
            -1: {
                f"{bid}|full": ((0, n),)
                for bid, n in box_full.items()
                if n > 0
            }
        },
    )
    try:
        build_plan(src, dst).validate()  # proof 1: slice bytes tile boxes
    except PlanError as e:
        return False, str(e)
    nd_dst = MeshLayout(
        tensors=nd_tensors,
        shards={
            -1: {
                f"{path}|full": tuple((0, d) for d in ti.global_shape)
                for path, ti in nd_tensors.items()
            }
        },
    )
    try:
        build_plan(
            MeshLayout(tensors=nd_tensors, shards=nd_shards), nd_dst
        ).validate()  # proof 2: boxes tile the global tensors
    except PlanError as e:
        return False, f"box coverage: {e}"
    return True, "ok"


def commit_gate(storage, ckpt_dir: str, step: int) -> bool:
    """The commit-time wrapper around :func:`step_covers`: log loudly and
    count the block; a gated step keeps the PREVIOUS committed step as
    the restore point, which is exactly the safe outcome."""
    ok, reason = step_covers(storage, ckpt_dir, step)
    if not ok:
        from dlrover_tpu.agent.metrics import integrity_counters

        integrity_counters.inc("ckpt_commit_blocked")
        logger.error(
            "NOT committing step %d: slice coverage unproven (%s)",
            step, reason,
        )
    return ok
