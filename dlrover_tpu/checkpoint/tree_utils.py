"""Pytree <-> flat shard-dict conversion for checkpointing.

The staging format is a flat ``{"<path>|<k>": np.ndarray}`` dict plus
per-tensor placement info (global shape + index slices), so that

- each *process* stores exactly its addressable shards (no gather),
- restore can re-assemble **any** target sharding from the pieces available
  (same-world: exact index match; changed-world: overlap copy — the
  resharding restore SURVEY.md §7 calls out as a hard part).

Restore is target-driven (orbax-style): the caller supplies a pytree of
jax.Arrays / ShapeDtypeStructs whose structure names the paths.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

from dlrover_tpu.common.byte_audit import audit
from dlrover_tpu.common.shm import ArenaTensor


def _norm_index(index, shape) -> Tuple[Tuple[int, int], ...]:
    """Normalize a shard index (tuple of slices) to ((start, stop), ...)."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _box_owners(leaf, gshape):
    """{normalized box: sorted process ids holding that box} from a leaf's
    GLOBAL device->index map — every process computes the same answer
    locally, which is what lets the sliced persist assign disjoint slices
    of replicated state without any cross-rank negotiation.  ``None``
    when the sharding cannot answer (callers then never slice the leaf).
    """
    try:
        sharding = leaf.sharding
        imap = sharding.devices_indices_map(gshape)
        out: Dict[Tuple[Tuple[int, int], ...], set] = {}
        for dev, idx in imap.items():
            out.setdefault(_norm_index(idx, gshape), set()).add(
                int(dev.process_index)
            )
        return {box: sorted(ranks) for box, ranks in out.items()}
    except Exception:  # noqa: BLE001 - unknown sharding kinds: unsliced
        return None


def flatten_to_shards(
    state: Any,
    fetched: Optional[List[Tuple[int, float, float]]] = None,
) -> Tuple[Dict[str, np.ndarray], Dict[str, dict]]:
    """Flatten a pytree of arrays into this process's shard dict.

    Returns (tensors, info): ``tensors["path|k"]`` is the k-th unique local
    shard of leaf ``path``; ``info["path|k"]`` records global_shape + index,
    plus the slicing inputs of ISSUE 7 — ``owners`` (every process id
    holding this same box, from the global indices map) for device arrays
    and ``host: True`` for host leaves (identical on every rank by the
    same assumption the restore path already makes).

    ``fetched``, where a list is given, takes ``(bytes, start, end)`` of
    each device shard's ``np.asarray`` in the order of the walk, on the
    monotonic clock: where the device-to-host copy is waited for.
    """
    leaves = tree_flatten_with_path(state)[0]
    tensors: Dict[str, np.ndarray] = {}
    info: Dict[str, dict] = {}
    for path, leaf in leaves:
        name = keystr(path)
        if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
            gshape = tuple(leaf.shape)
            seen = {}
            for shard in leaf.addressable_shards:
                idx = _norm_index(shard.index, gshape)
                if idx in seen:
                    continue
                t0 = time.monotonic()
                seen[idx] = np.asarray(shard.data)
                if fetched is not None:
                    fetched.append(
                        (seen[idx].nbytes, t0, time.monotonic()))
            owners_by_box = _box_owners(leaf, gshape)
            for k, (idx, arr) in enumerate(sorted(seen.items())):
                key = f"{name}|{k}"
                tensors[key] = arr
                info[key] = {
                    "path": name,
                    "global_shape": list(gshape),
                    "index": [list(p) for p in idx],
                }
                if owners_by_box is not None:
                    info[key]["owners"] = owners_by_box.get(idx)
        else:
            arr = np.asarray(leaf)
            key = f"{name}|0"
            tensors[key] = arr
            info[key] = {
                "path": name,
                "global_shape": list(arr.shape),
                "index": [[0, d] for d in arr.shape],
                "host": True,
            }
    return tensors, info


class ShardSource:
    """All pieces known for the leaves of one checkpoint (possibly from
    several processes' shard files).

    Pieces may arrive *sliced* (ISSUE 7): a flat uint8 byte range of one
    box's C-order buffer, as the cross-replica sliced persist wrote them.
    Slices accumulate per (path, box) and materialize into a normal piece
    the moment they tile the full buffer; a box whose slices never
    complete simply contributes nothing (``assemble`` then reports the
    region uncovered and the restore ladder falls back)."""

    def __init__(self):
        # path -> list of (index, piece); a piece is an array, or — on
        # the warm shm restore — an ArenaTensor still to be read
        self.pieces: Dict[str, List[Tuple[Tuple[Tuple[int, int], ...], Any]]] = {}
        # (path, index) -> {"full", "dtype", "shape", "parts": {(lo,hi): bytes}}
        self._partial: Dict[Tuple[str, tuple], dict] = {}

    def add(
        self,
        tensors: Dict[str, np.ndarray],
        info: Dict[str, dict],
        slices: Optional[Dict[str, dict]] = None,
    ) -> None:
        """``slices[key]``, when present, is the shard file's tensor meta
        for a sliced entry (``slice``/``full_nbytes``/``dtype``/``shape``)
        and ``tensors[key]`` is the flat uint8 slice payload."""
        for key, arr in tensors.items():
            meta = info.get(key)
            if meta is None:
                continue
            idx = tuple(tuple(p) for p in meta["index"])
            sl = (slices or {}).get(key)
            if sl is None:
                self.pieces.setdefault(meta["path"], []).append((idx, arr))
                continue
            lo, hi = (int(v) for v in sl["slice"])
            ent = self._partial.setdefault(
                (meta["path"], idx),
                {
                    "full": int(sl.get("full_nbytes", 0)),
                    "dtype": sl["dtype"],
                    "shape": tuple(int(d) for d in sl["shape"]),
                    "parts": {},
                },
            )
            ent["parts"][(lo, hi)] = np.asarray(arr, np.uint8).reshape(-1)
            self._materialize_if_complete(meta["path"], idx, ent)

    def _materialize_if_complete(self, path: str, idx, ent: dict) -> None:
        if ent.get("done"):
            return
        pos = 0
        parts = sorted(ent["parts"].items())
        for (lo, hi), _ in parts:
            if lo > pos:
                return  # gap: some rank's slice still missing
            pos = max(pos, hi)
        if pos < ent["full"]:
            return
        arr = np.empty(ent["shape"], dtype=np.dtype(ent["dtype"]))
        flat = arr.reshape(-1).view(np.uint8)
        if flat.size != ent["full"]:
            return  # meta lies about the buffer size: leave uncovered
        for (lo, hi), chunk in parts:
            flat[lo:hi] = chunk[: hi - lo]
        self.pieces.setdefault(path, []).append((idx, arr))
        ent["done"] = True

    def paths(self) -> List[str]:
        return list(self.pieces.keys())

    def assemble(
        self, path: str, index: Tuple[Tuple[int, int], ...], dtype=None
    ) -> Optional[np.ndarray]:
        """Build the sub-array of leaf ``path`` covering ``index`` from the
        available pieces.  Exact-match fast path (the piece as it is: an
        :class:`ArenaTensor` stays unread); otherwise overlap-copy
        (resharding; an arena piece is read for it, one at a time).
        Returns None if any region is uncovered."""
        pieces = self.pieces.get(path)
        if not pieces:
            return None
        for idx, arr in pieces:
            if idx == index:
                return arr
        shape = tuple(e - s for s, e in index)
        out = np.empty(shape, dtype=dtype or pieces[0][1].dtype)
        covered = np.zeros(shape, dtype=bool) if out.size else None
        for idx, arr in pieces:
            # Overlap of [idx] and [index] in global coords.
            dst_sl, src_sl = [], []
            ok = True
            for (ps, pe), (rs, re) in zip(idx, index):
                lo, hi = max(ps, rs), min(pe, re)
                if lo >= hi:
                    ok = False
                    break
                dst_sl.append(slice(lo - rs, hi - rs))
                src_sl.append(slice(lo - ps, hi - ps))
            if not ok:
                continue
            if isinstance(arr, ArenaTensor):
                arr = arr.read()
            out[tuple(dst_sl)] = arr[tuple(src_sl)]
            if covered is not None:
                covered[tuple(dst_sl)] = True
        if covered is not None and not covered.all():
            return None
        return out


def _owned(piece: np.ndarray) -> np.ndarray:
    """Ensure a restored piece owns its bytes.

    ``base is not None`` is exactly "this array borrows someone else's
    buffer" (a caller's live host shard, a slice of a larger read); such
    a view must not reach a restored tree that could go on referring to
    it.  Storage-restored pieces (``unpack_shard`` copies) and
    overlap-assembled pieces (fresh ``np.empty``) pass through
    untouched."""
    piece = np.asarray(piece)
    return np.array(piece) if piece.base is not None else piece


def _may_alias_host(device) -> bool:
    """Whether an array ``device_put`` to ``device`` can go on referring
    to the host buffer it was given.  The CPU backend may adopt an
    aligned numpy buffer as the array's own storage; on an accelerator
    the bytes leave the host, and once the transfer is over
    (``block_until_ready``) the array never refers to them again."""
    return device.platform == "cpu"


class _Staging:
    """The host side of arena -> accelerator: two reused buffers, each
    as large as the largest arena piece of the restore.  A piece is
    ``read()`` into a buffer and ``device_put`` from there; the buffer
    is refilled only after the put that read it is ready
    (``device_put`` returns before the bytes have left the host).  Two
    buffers let the read of one piece run beside the transfer of the
    one before; their pages are touched once, by the first pieces, and
    host memory stays a constant however large the state is."""

    def __init__(self, capacity: int):
        self._capacity = int(capacity)
        self._bufs: List[Optional[np.ndarray]] = [None, None]
        self._in_flight: List[Any] = [None, None]
        self._turn = 0

    def put(self, piece: ArenaTensor, device):
        i = self._turn
        self._turn = (i + 1) % len(self._bufs)
        if self._in_flight[i] is not None:
            jax.block_until_ready(self._in_flight[i])
            self._in_flight[i] = None
        if self._bufs[i] is None:
            self._bufs[i] = np.empty(self._capacity, dtype=np.uint8)
        arr = jax.device_put(piece.read(out=self._bufs[i]), device)
        self._in_flight[i] = arr
        return arr

    def drain(self) -> None:
        """Wait for every put still reading a buffer: after this the
        buffers may go."""
        jax.block_until_ready([a for a in self._in_flight if a is not None])
        self._in_flight = [None] * len(self._bufs)


def _leaf_placements(leaf):
    """For a sharding-bearing leaf (a live ``jax.Array`` OR a
    ``ShapeDtypeStruct`` carrying a sharding — the restore-to-any-mesh
    placeholder), return ``(sharding, gshape, [(device, index), ...])``
    for its addressable shards without materializing anything; ``None``
    for plain host leaves.  The indices map is the same source of truth
    the reshard planner's boxes are pinned against."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not hasattr(
        sharding, "addressable_devices_indices_map"
    ):
        return None
    gshape = tuple(leaf.shape)
    imap = sharding.addressable_devices_indices_map(gshape)
    return sharding, gshape, list(imap.items())


def restore_to_target(
    target: Any, source: ShardSource,
    tally: Optional[Dict[str, int]] = None,
) -> Any:
    """Fill ``target`` (pytree of jax.Array / ShapeDtypeStruct / np arrays)
    from ``source``.  Sharding-bearing targets (live arrays, or
    ShapeDtypeStructs with an explicit sharding — e.g. placeholders for a
    mesh the saving world never had) are rebuilt shard-by-shard on their
    devices; others become full np arrays.

    A piece still in the shm arena (:class:`ArenaTensor`) bound for an
    accelerator is ``read()`` into a reused staging buffer and
    ``device_put`` from there (:class:`_Staging`); bound for a host leaf,
    or for a device that may alias host memory
    (:func:`_may_alias_host`), it is ``read()`` straight into an array
    the restored tree owns.  The caller keeps the arena readable and
    unwritten for the length of this call.  A piece that is an array
    goes to ``device_put`` as it is, copied first only if it borrows its
    bytes and the destination could keep referring to them.  ``tally``,
    when given, receives ``staged_bytes`` (reached a device through a
    staging buffer) and ``copied_bytes`` (read or copied into an array
    of their own on the host)."""
    count = {"staged_bytes": 0, "copied_bytes": 0}
    staging = _Staging(max(
        (p.nbytes for ps in source.pieces.values() for _, p in ps
         if isinstance(p, ArenaTensor)),
        default=0,
    ))

    def place(piece, device=None):
        """One piece on its way into the restored tree; ``device`` None
        is a host leaf."""
        keep = device is None or _may_alias_host(device)
        if isinstance(piece, ArenaTensor):
            if not keep:
                count["staged_bytes"] += piece.nbytes
                return staging.put(piece, device)
            out = piece.read()
            count["copied_bytes"] += piece.nbytes
        else:
            piece = np.asarray(piece)
            out = _owned(piece) if keep else piece
            if out is not piece:
                count["copied_bytes"] += int(out.nbytes)
        return out if device is None else jax.device_put(out, device)

    flat, treedef = jax.tree_util.tree_flatten(target)
    paths_leaves = tree_flatten_with_path(target)[0]
    out_leaves = []
    try:
        for (path, leaf) in paths_leaves:
            name = keystr(path)
            placed = _leaf_placements(leaf)
            if placed is not None:
                sharding, gshape, placements = placed
                arrays = []
                for device, index in placements:
                    idx = _norm_index(index, gshape)
                    piece = source.assemble(name, idx, dtype=leaf.dtype)
                    if piece is None:
                        raise KeyError(
                            f"checkpoint missing data for {name} index {idx}"
                        )
                    arrays.append(place(piece, device))
                restored = jax.make_array_from_single_device_arrays(
                    gshape, sharding, arrays
                )
                out_leaves.append(restored)
            else:
                shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
                full_idx = tuple((0, d) for d in shape)
                piece = source.assemble(
                    name, full_idx, dtype=getattr(leaf, "dtype", None)
                )
                if piece is None:
                    raise KeyError(f"checkpoint missing data for {name}")
                out_leaves.append(place(piece))
    finally:
        staging.drain()
    if count["copied_bytes"]:
        audit.record_copy(count["copied_bytes"], "restore_owned_copy")
    if tally is not None:
        tally.update(count)
    return tree_unflatten(treedef, out_leaves)
