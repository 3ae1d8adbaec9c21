"""Lightweight synchronized decode-state checkpoints (paper Eq. 10 online).

A *decode snapshot* is the serving analogue of the simulator's synchronized
task checkpoint: every ``lambda`` generated tokens the engine copies one
slot's decode state — its KV-cache rows, decode position and emitted
tokens — to host memory.  When the worker holding that slot fails, the
request resumes from its last snapshot on any free slot, paying only the
tokens generated since the snapshot instead of a full re-prefill (the
paper's "beyond last checkpoint" waste).  The cadence comes from
:class:`repro.ft.interval.DynamicInterval` (Lemma 3.1: unstable
environments snapshot more often).

**What the state is.**  :class:`SlotLayout` classifies every cache leaf by
what its shape shows, not by model family: it probes ``lm.init_cache``
under ``eval_shape`` at two batch sizes (the batch axis) and at two cache
lengths.  A leaf that grows with the cache length is *append-only* along
that (sequence) axis — dense and MoE self-attention K/V, the enc-dec
decoder's self-attention, a VLM's image-token rows.  Decode writes only
row ``pos`` of such a leaf and never rewrites a row below it, so rows
``[0, pos)`` are fixed once written.  Every other leaf — RWKV state,
RG-LRU state and its local-attention ring (``min(window, cache_len)``
does not grow), enc-dec cross-attention K/V — may change at any step and
is copied whole at every snapshot.  A slot's decode state is the registers
(``pos``, ``last_token``, ``tokens``), rows ``[0, pos)`` of the
append-only leaves and the other leaves whole.  Rows at and above ``pos``
are not part of it: decode attention gives every row above the position
it writes exactly zero weight (``layers.py``: ``where(valid, s, -1e30)``
before the softmax), so their values never reach a logit as long as they
are finite, as a freed slot's earlier rows and a bucketed prefill's pad
rows are.

**Chunks and lineages.**  Append-only rows move in chunks of
``SlotLayout.chunk`` rows (``CHUNK_ROWS``, or the largest divisor below it
of the rows one shard of the sequence axis holds), by two programs of one
shape each (``slot_read_rows``/``slot_write_rows``), so every position
reuses them.  On a cache split along its sequence axis (``kv_seq`` on a
``(1, N)`` mesh) the two programs run per shard, so a chunk moves between
its shard and the host without the cache ever being gathered.  A chunk
lies seq-major on the host (``(rows, ...)``) so a prefix of it is a
contiguous slice.  Each slot keeps a :class:`Lineage` from its prefill
(empty) or its restore (the restored snapshot's chunks): the chunks wholly
below ``pos`` that it has already copied ("sealed"), and a running SHA-1
over their bytes.  A snapshot copies only the chunks from the first
unsealed one up to the one holding ``pos - 1``, plus the other leaves;
sealed chunks are shared, as host arrays, by every later snapshot of the
lineage; the partial last chunk is kept up to ``pos`` only.

**Digest order.**  SHA-1 over each chunk's leaves (chunk by chunk, leaves
in ``jax.tree.leaves`` order, the last chunk up to ``pos``), then every
other leaf, then ``[rid, pos, last_token]`` and ``tokens`` as int64.  The
snapshot's checksum is the lineage's running hash extended with the tail,
the other leaves and that header — computed only from bytes as they came
off the device, never from bytes read back from the store.

Robustness (the ``repro.chaos`` ``snapshot_corrupt`` recovery path):
:meth:`SnapshotStore.verify` re-derives the checksum from the stored
arrays before a restore, so a torn or corrupted snapshot is detected
instead of silently resuming from garbage state — the engine then
quarantines it and falls back to re-prefill.  :meth:`SnapshotStore.corrupt`
is the seeded fault injector for that path; a flip in a sealed chunk lands
in the array the lineage shares, so every later snapshot of that lineage
fails its verify too, until the slot is released or restored: one such
fault costs the request its snapshot resume (a full re-prefill if its last
copy dies), where a whole-row snapshot lost only until the next one.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import lm
from repro.models.config import ModelConfig

__all__ = [
    "CHUNK_ROWS",
    "cache_batch_axes",
    "cache_seq_axes",
    "chunk_rows",
    "slot_get",
    "slot_set",
    "SlotLayout",
    "DecodeSnapshot",
    "Lineage",
    "SnapshotStore",
    "snapshot_digest",
]

# rows of an append-only leaf copied per chunk (2 MiB a chunk for a
# 4-layer, 8 x 128 GQA bf16 K/V cache)
CHUNK_ROWS = 128


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


def cache_seq_axes(cfg: ModelConfig, cache_len: int) -> list[int | None]:
    """Per cache leaf (``jax.tree.leaves`` order): the axis along which it
    grows with the cache length — it is append-only along it — or ``None``
    for a leaf whose shape does not depend on the cache length.

    Probes ``init_cache`` under ``eval_shape`` at ``cache_len`` and
    ``cache_len + 1``.  No allocation.
    """
    a = jax.eval_shape(lambda: lm.init_cache(cfg, 2, cache_len))
    b = jax.eval_shape(lambda: lm.init_cache(cfg, 2, cache_len + 1))
    out = []
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        diffs = [i for i, (x, y) in enumerate(zip(la.shape, lb.shape))
                 if x != y]
        if len(diffs) > 1:
            raise ValueError(
                f"ambiguous sequence axis for cache leaf {la.shape}")
        out.append(diffs[0] if diffs else None)
    return out


def chunk_rows(extent: int) -> int:
    """Rows per chunk for a sequence extent: the largest divisor of
    ``extent`` not above ``CHUNK_ROWS``, so chunks tile the extent; where
    that is under a quarter of ``CHUNK_ROWS`` (an extent with no fitting
    divisor), the whole extent in one chunk."""
    best = max(d for d in range(1, min(CHUNK_ROWS, extent) + 1)
               if extent % d == 0)
    return best if best * 4 >= min(CHUNK_ROWS, extent) else extent


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


def _names(entry) -> tuple[str, ...]:
    """Mesh axes of one ``PartitionSpec`` entry."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


class SlotLayout:
    """Where one slot's decode state sits in the batched cache, and the
    four traceable functions that move it: ``chunk`` rows of every
    append-only leaf from a start row (sequence axis first), and every
    other leaf whole.

    ``shardings`` (the cache's, a pytree of ``NamedSharding``) makes the
    chunk programs run per shard under ``shard_map``: a cache split along
    its sequence axis (the ``kv_seq`` rule on a ``(1, N)`` mesh) keeps
    each chunk inside one shard, since ``chunk`` divides the rows a shard
    holds.  The shard that holds a chunk reads it and the others' copies
    are dropped after an all-gather of chunk size; a write lands only on
    the shard that holds it.  No program gathers the cache."""

    def __init__(self, cfg: ModelConfig, cache_len: int, shardings=None):
        self.batch_axes = cache_batch_axes(cfg, cache_len)
        seq = cache_seq_axes(cfg, cache_len)
        shapes = jax.tree.leaves(
            jax.eval_shape(lambda: lm.init_cache(cfg, 1, cache_len)))
        named = [x for x in jax.tree.leaves(shardings)
                 if isinstance(x, NamedSharding)]
        self.mesh = named[0].mesh if named else None
        specs = ([tuple(x.spec) + (None,) * (len(l.shape) - len(x.spec))
                  for x, l in zip(jax.tree.leaves(shardings), shapes)]
                 if self.mesh is not None
                 else [(None,) * len(l.shape) for l in shapes])
        self.rows_leaves = []    # (leaf index, batch axis, sequence axis)
        self.state_leaves = []   # (leaf index, batch axis)
        self._specs = []         # per rows leaf: its mesh axes per dim
        extents = []             # per rows leaf: rows a shard holds
        for i, (b, s) in enumerate(zip(jax.tree.leaves(self.batch_axes),
                                       seq)):
            if s is None:
                self.state_leaves.append((i, b))
                continue
            self.rows_leaves.append((i, b, s))
            spec = [_names(e) for e in specs[i]]
            self._specs.append(spec)
            extents.append(shapes[i].shape[s] // math.prod(
                self.mesh.shape[n] for n in spec[s]) if spec[s]
                else shapes[i].shape[s])
        self.chunk = chunk_rows(math.gcd(*extents)) if extents else 0

    # -- per-shard bodies (the whole cache where there is no mesh) ----------
    def _offset(self, names, extent):
        """Global index of this shard's first element along an axis split
        over mesh axes ``names`` (0 where it is not split)."""
        idx = 0
        for n in names:
            idx = idx * self.mesh.shape[n] + jax.lax.axis_index(n)
        return idx * extent

    def _window(self, leaf, k, sid, start):
        """This shard's start indices and sizes for ``chunk`` rows from
        ``start`` of slot ``sid`` in rows leaf ``k``, and whether it holds
        them."""
        _, b, s = self.rows_leaves[k]
        spec = self._specs[k]
        lsid = sid - self._offset(spec[b], leaf.shape[b])
        lstart = start - self._offset(spec[s], leaf.shape[s])
        owns = ((lsid >= 0) & (lsid < leaf.shape[b])
                & (lstart >= 0) & (lstart < leaf.shape[s]))
        at = [jnp.zeros((), jnp.int32)] * leaf.ndim
        at[b], at[s] = lsid, lstart
        size = list(leaf.shape)
        size[b], size[s] = 1, self.chunk
        return at, size, owns

    def _read_local(self, leaves, sid, start):
        out = []
        for k, leaf in enumerate(leaves):
            _, b, s = self.rows_leaves[k]
            at, size, owns = self._window(leaf, k, sid, start)
            r = jnp.squeeze(jax.lax.dynamic_slice(leaf, at, size), b)
            r = jnp.moveaxis(r, s - (b < s), 0)
            names = self._specs[k][b] + self._specs[k][s]
            if names:
                # every shard's candidate, then the one its holder read
                holder = jnp.argmax(jax.lax.all_gather(owns, names))
                r = jax.lax.all_gather(r, names)[holder]
            out.append(r)
        return out

    def _write_local(self, leaves, sid, start, rows):
        out = []
        for k, (leaf, r) in enumerate(zip(leaves, rows)):
            _, b, s = self.rows_leaves[k]
            at, size, owns = self._window(leaf, k, sid, start)
            r = jnp.expand_dims(jnp.moveaxis(r, 0, s - (b < s)), b)
            cur = jax.lax.dynamic_slice(leaf, at, size)
            out.append(jax.lax.dynamic_update_slice(
                leaf, jnp.where(owns, r.astype(leaf.dtype), cur), at))
        return out

    def _per_shard(self, fn, *, write: bool):
        if self.mesh is None:
            return fn
        leaf_specs = [P(*spec) for spec in self._specs]
        chunk_specs = [
            P(None, *(spec[d] for d in range(len(spec)) if d not in (b, s)))
            for (_, b, s), spec in zip(self.rows_leaves, self._specs)]
        ins = (leaf_specs, P(), P()) + ((chunk_specs,) if write else ())
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=ins,
            out_specs=leaf_specs if write else chunk_specs,
            check_vma=False)

    # -- the four programs' bodies ------------------------------------------
    def slot_read_rows(self, cache, sid, start):
        """``chunk`` rows from ``start`` of every append-only leaf of slot
        ``sid``, sequence axis first."""
        leaves = jax.tree.leaves(cache)
        return self._per_shard(self._read_local, write=False)(
            [leaves[i] for i, _, _ in self.rows_leaves],
            jnp.asarray(sid, jnp.int32), jnp.asarray(start, jnp.int32))

    def slot_write_rows(self, cache, sid, start, rows):
        """Write ``slot_read_rows``'s layout back at ``start``."""
        leaves, treedef = jax.tree.flatten(cache)
        new = self._per_shard(self._write_local, write=True)(
            [leaves[i] for i, _, _ in self.rows_leaves],
            jnp.asarray(sid, jnp.int32), jnp.asarray(start, jnp.int32),
            list(rows))
        for (i, _, _), leaf in zip(self.rows_leaves, new):
            leaves[i] = leaf
        return jax.tree.unflatten(treedef, leaves)

    def slot_read_state(self, cache, sid):
        """Every leaf that is not append-only, whole, for slot ``sid``."""
        leaves = jax.tree.leaves(cache)
        return slot_get([leaves[i] for i, _ in self.state_leaves],
                        [b for _, b in self.state_leaves], sid)

    def slot_write_state(self, cache, sid, state):
        leaves, treedef = jax.tree.flatten(cache)
        new = slot_set([leaves[i] for i, _ in self.state_leaves],
                       [b for _, b in self.state_leaves], sid, state)
        for (i, _), leaf in zip(self.state_leaves, new):
            leaves[i] = leaf
        return jax.tree.unflatten(treedef, leaves)


@dataclasses.dataclass
class DecodeSnapshot:
    """Host-side resumable decode state of one request."""

    rid: int
    pos: int                    # absolute position of the next decode write
    tokens: list[int]           # tokens emitted up to the snapshot
    last_token: int
    # rows [0, pos) of every append-only leaf, one list of seq-major arrays
    # per chunk; the last chunk may be partial.  Sealed chunks' lists are
    # shared with the slot's lineage and its later snapshots.
    chunks: list[list[np.ndarray]]
    state: list[np.ndarray]     # every other leaf's slot row, whole
    step: int                   # engine step at which it was taken
    checksum: str = ""          # content hash (see the module docstring)
    # SHA-1 state after the chunks wholly below ``pos``, from device bytes
    sealed: object = None


def _hash(h, arrays) -> None:
    for a in arrays:
        h.update(np.ascontiguousarray(a).reshape(-1).view(np.uint8))


def _hash_header(h, snap: DecodeSnapshot) -> None:
    h.update(np.asarray([snap.rid, snap.pos, snap.last_token], np.int64))
    h.update(np.asarray(snap.tokens, np.int64))


def snapshot_digest(snap: DecodeSnapshot) -> str:
    """Content hash of a snapshot's stored arrays, in the digest order."""
    h = hashlib.sha1()
    for chunk in snap.chunks:
        _hash(h, chunk)
    _hash(h, snap.state)
    _hash_header(h, snap)
    return h.hexdigest()


class Lineage:
    """One slot's sealed chunks since its prefill or restore, and the
    running SHA-1 over them as they came off the device."""

    def __init__(self, rows: int, chunks=(), sealed=None):
        self.rows = rows
        self.chunks: list[list[np.ndarray]] = list(chunks)
        self.sealed = sealed if sealed is not None else hashlib.sha1()

    @classmethod
    def resume(cls, rows: int, snap: DecodeSnapshot) -> "Lineage":
        n = snap.pos // rows if rows else 0
        return cls(rows, snap.chunks[:n], snap.sealed.copy())

    def pending(self, pos: int) -> range:
        """Chunks a snapshot at ``pos`` copies: the first unsealed one up to
        the one holding row ``pos - 1``."""
        if not self.rows:
            return range(0)
        return range(len(self.chunks), -(-pos // self.rows))

    def extend(self, copied, state, *, rid: int, pos: int,
               tokens: list[int], last_token: int,
               step: int) -> DecodeSnapshot:
        """The snapshot at ``pos`` from the chunks ``pending(pos)`` copied
        and the other leaves; seals the chunks wholly below ``pos``."""
        tail = []
        for k, chunk in enumerate(copied, start=len(self.chunks)):
            n = pos - k * self.rows
            if n >= self.rows:
                _hash(self.sealed, chunk)
                self.chunks.append(list(chunk))
            else:
                tail = [leaf[:n].copy() for leaf in chunk]
        snap = DecodeSnapshot(
            rid=rid, pos=pos, tokens=list(tokens), last_token=last_token,
            chunks=self.chunks + ([tail] if tail else []), state=list(state),
            step=step, sealed=self.sealed.copy())
        h = self.sealed.copy()
        _hash(h, tail)
        _hash(h, snap.state)
        _hash_header(h, snap)
        snap.checksum = h.hexdigest()
        return snap


class SnapshotStore:
    """Latest-snapshot-per-request store (the paper keeps only the newest
    synchronized checkpoint; older ones are superseded)."""

    def __init__(self) -> None:
        self._by_rid: dict[int, DecodeSnapshot] = {}
        self.corrupted = 0

    def save(self, snap: DecodeSnapshot) -> None:
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

        Victim snapshot/array/byte are pure functions of ``seed`` so a trace
        replay corrupts the exact same state.  The flipped array replaces
        the original in the list that holds it, which for a sealed chunk
        is shared with the lineage, as stored host memory would be.
        Returns 0 when no snapshot (or no non-empty array) exists, else 1.
        """
        if not self._by_rid:
            return 0
        rids = sorted(self._by_rid)
        snap = self._by_rid[rids[seed % len(rids)]]
        victims = [(arrays, i) for arrays in (*snap.chunks, snap.state)
                   for i, a in enumerate(arrays) if a.size]
        if not victims:
            return 0
        arrays, i = victims[seed % len(victims)]
        # device_get arrays can be read-only views: flip on a copy
        flipped = np.array(arrays[i])
        raw = flipped.reshape(-1).view(np.uint8)
        raw[seed % raw.size] ^= 0xFF
        arrays[i] = flipped
        self.corrupted += 1
        return 1

    def __len__(self) -> int:
        return len(self._by_rid)
