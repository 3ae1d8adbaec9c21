"""Lightweight synchronized decode-state checkpoints (paper Eq. 10 online).

A *decode snapshot* is the serving analogue of the simulator's synchronized
task checkpoint: every ``lambda`` generated tokens the engine copies one
slot's KV-cache row + decode position + emitted tokens to host memory.  When
the worker holding that slot fails, the request resumes from its last
snapshot on any free slot — paying only the tokens generated since the
snapshot instead of a full re-prefill (the paper's "beyond last checkpoint"
waste).  The cadence comes from :class:`repro.ft.interval.DynamicInterval`
(Lemma 3.1: unstable environments snapshot more often).

The slot get/set helpers are cache-layout agnostic: the per-leaf batch axis
is discovered by probing ``lm.init_cache`` shapes at two batch sizes, so the
same code handles dense (L, B, S, H, D), RWKV (L, B, ...) and hybrid
(n_super, rec, B, ...) cache pytrees.

Robustness (the ``repro.chaos`` ``snapshot_corrupt`` recovery path): every
snapshot carries a content checksum computed at save time;
:meth:`SnapshotStore.verify` re-derives it before a restore, so a torn or
corrupted snapshot is detected instead of silently resuming from garbage
state — the engine then quarantines it and falls back to re-prefill.
:meth:`SnapshotStore.corrupt` is the seeded fault injector for that path.
"""
from __future__ import annotations

import dataclasses
import hashlib

import jax
import numpy as np

from repro.models import lm
from repro.models.config import ModelConfig

__all__ = [
    "cache_batch_axes",
    "slot_get",
    "slot_set",
    "DecodeSnapshot",
    "SnapshotStore",
    "snapshot_digest",
]


def cache_batch_axes(cfg: ModelConfig, cache_len: int):
    """Pytree of ints: the batch axis of every cache leaf.

    Probes ``init_cache`` under ``eval_shape`` at batch sizes 2 and 3 — the
    single axis whose extent changes is the batch axis.  No allocation.
    """
    a2 = jax.eval_shape(lambda: lm.init_cache(cfg, 2, cache_len))
    a3 = jax.eval_shape(lambda: lm.init_cache(cfg, 3, cache_len))

    def axis(l2, l3):
        diffs = [i for i, (x, y) in enumerate(zip(l2.shape, l3.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"ambiguous batch axis for cache leaf {l2.shape}")
        return diffs[0]

    return jax.tree.map(axis, a2, a3)


def slot_get(cache, axes, slot):
    """Extract one batch row (slot) from every cache leaf."""
    return jax.tree.map(
        lambda leaf, a: jax.lax.dynamic_index_in_dim(leaf, slot, axis=a,
                                                     keepdims=False),
        cache, axes)


def slot_set(cache, axes, slot, row):
    """Write a single-slot row pytree back into the batched cache."""
    return jax.tree.map(
        lambda leaf, a, r: jax.lax.dynamic_update_index_in_dim(
            leaf, r.astype(leaf.dtype), slot, axis=a),
        cache, axes, row)


@dataclasses.dataclass
class DecodeSnapshot:
    """Host-side resumable decode state of one request."""

    rid: int
    pos: int                    # absolute position of the next decode write
    tokens: list[int]           # tokens emitted up to the snapshot
    last_token: int
    cache_row: object           # single-slot cache pytree (np arrays)
    step: int                   # engine step at which it was taken
    checksum: str = ""          # content hash set by SnapshotStore.save


def snapshot_digest(snap: DecodeSnapshot) -> str:
    """Content hash over decode registers + tokens + every cache-row leaf."""
    h = hashlib.sha1()
    h.update(np.asarray([snap.rid, snap.pos, snap.last_token],
                        np.int64).tobytes())
    h.update(np.asarray(snap.tokens, np.int64).tobytes())
    for leaf in jax.tree.leaves(snap.cache_row):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


class SnapshotStore:
    """Latest-snapshot-per-request store (the paper keeps only the newest
    synchronized checkpoint; older ones are superseded)."""

    def __init__(self) -> None:
        self._by_rid: dict[int, DecodeSnapshot] = {}
        self.corrupted = 0

    def save(self, snap: DecodeSnapshot) -> None:
        snap.checksum = snapshot_digest(snap)
        self._by_rid[snap.rid] = snap

    def get(self, rid: int) -> DecodeSnapshot | None:
        return self._by_rid.get(rid)

    def drop(self, rid: int) -> None:
        self._by_rid.pop(rid, None)

    def verify(self, snap: DecodeSnapshot) -> bool:
        """True iff the snapshot's content still matches its checksum
        (snapshots without one — hand-built — are trusted)."""
        return not snap.checksum or snap.checksum == snapshot_digest(snap)

    def corrupt(self, seed: int) -> int:
        """Chaos ``snapshot_corrupt``: flip one byte in one stored snapshot.

        Victim snapshot/leaf/byte are pure functions of ``seed`` so a trace
        replay corrupts the exact same state.  Returns 0 when no snapshot
        (or no non-empty leaf) exists, else 1.
        """
        if not self._by_rid:
            return 0
        rids = sorted(self._by_rid)
        snap = self._by_rid[rids[seed % len(rids)]]
        leaves = [np.asarray(l) for l in jax.tree.leaves(snap.cache_row)]
        treedef = jax.tree.structure(snap.cache_row)
        victims = [i for i, l in enumerate(leaves) if l.size]
        if not victims:
            return 0
        vi = victims[seed % len(victims)]
        # device_get rows can be read-only views: flip on a copy and rebuild
        raw = bytearray(np.ascontiguousarray(leaves[vi]).tobytes())
        raw[seed % len(raw)] ^= 0xFF
        leaves[vi] = np.frombuffer(bytes(raw), dtype=leaves[vi].dtype
                                   ).reshape(leaves[vi].shape)
        snap.cache_row = jax.tree.unflatten(treedef, leaves)
        self.corrupted += 1
        return 1

    def __len__(self) -> int:
        return len(self._by_rid)
