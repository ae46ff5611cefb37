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

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

from dlrover_tpu.common.byte_audit import audit


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
) -> Tuple[Dict[str, np.ndarray], Dict[str, dict]]:
    """Flatten a pytree of arrays into this process's shard dict.

    Returns (tensors, info): ``tensors["path|k"]`` is the k-th unique local
    shard of leaf ``path``; ``info["path|k"]`` records global_shape + index,
    plus the slicing inputs of ISSUE 7 — ``owners`` (every process id
    holding this same box, from the global indices map) for device arrays
    and ``host: True`` for host leaves (identical on every rank by the
    same assumption the restore path already makes).
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
                seen[idx] = np.asarray(shard.data)
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
        # path -> list of (index, np.ndarray)
        self.pieces: Dict[str, List[Tuple[Tuple[Tuple[int, int], ...], np.ndarray]]] = {}
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
        available pieces.  Exact-match fast path; otherwise overlap-copy
        (resharding).  Returns None if any region is uncovered."""
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
            out[tuple(dst_sl)] = arr[tuple(src_sl)]
            if covered is not None:
                covered[tuple(dst_sl)] = True
        if covered is not None and not covered.all():
            return None
        return out


def _owned(piece: np.ndarray) -> np.ndarray:
    """Ensure a restored piece owns its bytes.

    ``assemble()``'s exact-match fast path returns the source array
    itself, which on the warm shm restore is a VIEW into the live arena.
    Such a view is valid only while ``CheckpointEngine`` holds the rank's
    fencing lock and the arena mutex, so it must not reach the restored
    tree.  ``base is not None`` is exactly "this array borrows someone
    else's buffer"; storage-restored pieces (``unpack_shard`` copies) and
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


def _hand_over(piece, keep: bool, tally: Dict[str, int]) -> np.ndarray:
    """One piece on its way into the restored tree: made to own its
    bytes when the destination would ``keep`` referring to them, as it
    is otherwise.  ``tally`` counts both kinds."""
    piece = np.asarray(piece)
    out = _owned(piece) if keep else piece
    kind = "in_place_bytes" if out is piece else "copied_bytes"
    tally[kind] += int(out.nbytes)
    return out


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

    A piece that borrows its bytes (a view into the shm arena) is copied
    only where the restored tree would otherwise keep referring to them:
    bound for a host leaf, or for a device that may alias host memory
    (:func:`_may_alias_host`).  Every other piece goes to ``device_put``
    as it is, so the caller keeps borrowed bytes valid and unwritten
    until ``jax.block_until_ready`` of the result has returned.
    ``tally``, when given, receives ``in_place_bytes`` and
    ``copied_bytes``: what was handed on as it was, and what was copied
    on the host first."""
    count = {"in_place_bytes": 0, "copied_bytes": 0}
    flat, treedef = jax.tree_util.tree_flatten(target)
    paths_leaves = tree_flatten_with_path(target)[0]
    out_leaves = []
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
                arrays.append(jax.device_put(
                    _hand_over(piece, _may_alias_host(device), count),
                    device,
                ))
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
            out_leaves.append(_hand_over(piece, True, count))
    if count["copied_bytes"]:
        audit.record_copy(count["copied_bytes"], "restore_owned_copy")
    if tally is not None:
        tally.update(count)
    return tree_unflatten(treedef, out_leaves)
