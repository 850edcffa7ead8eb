"""Live index subsystem — streaming upserts/deletes over a sealed base.

`LiveFilteredIndex` turns the frozen `FilteredIndex` serving handle into
a mutable one without giving up the batched read path:

* **delta segment** (`DeltaSegment`) — an append-only, host-growable
  store of upserted vectors/bitmaps, mirrored to the device in fixed
  `chunk`-row blocks (sealed chunks upload once; only the partial tail
  chunk re-uploads per search);
* **tombstone bitmap** — one bool per id over base + delta; `delete()`
  marks ids dead and bumps a version so snapshots stay consistent;
* **snapshot epochs** (`LiveSnapshot`) — a cheap consistent read view:
  the delta high-watermark plus a tombstone copy, pinned to its base
  *generation* so an in-flight batch keeps its base alive across a
  concurrent `compact()`;
* **background compaction** — `compact()` folds the surviving base and
  delta rows into a fresh group-sorted `ANNDataset` (the same
  construction `ANNDataset.build` uses, so upsert-everything-then-compact
  is bit-identical to building the index directly), rebuilds the old
  base's method indexes in a worker thread, and atomically swaps the
  base under the generation counter while old-epoch readers drain.

The read path runs the routed method on the base (overfetched by the
base tombstone count, capped at k — so up to k deletions ranked above a
query's live matches cannot crowd them out of the top-k; beyond that
the base segment degrades gracefully until `compact()` folds the
tombstones away, which is the intended cadence), then folds the base
candidates and the delta segment through **one fused Pallas launch**
(`ops.fused_live_topk`): the kernel scans the delta mirror block by
block, with the packed tombstone bitmap applied to *both* candidate
sets on the device just before the launch, and carries the running
top-k in VMEM — no per-stage
overfetch on the delta, no `[S, Q, K]` HBM intermediate, no host merge.
Once the delta outgrows `delta_prune_min_rows`, sealed chunks' mini-IVF
indexes (`ChunkIndex`, built once at chunk-seal time) prune clusters
whose exact ball bound proves they cannot reach any query's top-k, so
the scan stops being full brute force; the partial tail chunk is always
scanned. The pre-PR-6 three-stage path (`masked_topk` overfetch + host
tombstone mask + `merge_topk`) survives as `_run_staged` — a parity
reference, bit-identical to the fused path. Ids are per-generation row
ids: base rows keep their dataset row id, delta rows take
`base_n + insertion_order`; compaction remaps both
(`stats()["generation"]` tells epochs apart).

Compaction **grafts** instead of rebuilding where it can: each built
method index of the old base is spliced onto the compacted dataset via
`Method.graft_index` (IVF posting lists carry surviving rows through
the id remap with frozen centroids; graph methods remap their edge
lists and attach the delta rows), falling back to a full build for
methods that don't implement grafting — making compaction cost
sublinear in base size for the grafted methods.

`ShardedLiveIndex` scales the same surface across row shards: upserts
round-robin over per-shard delta segments, per-shard ids globalise
through the shard row offsets (base) and a global insertion-order map
(delta), and `RouterService`/`AsyncBatchQueue` serve either handle
unchanged. Routing features stay fresh through the `live_stats()`
protocol `repro.core.features` consumes (live per-label counts and
exact live selectivity corrections).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from repro.ann import engine as engine_mod
from repro.ann import labels as lb
from repro.ann import ledger as ledger_mod
from repro.ann import registry as registry_mod
from repro.ann import trace
from repro.ann.dataset import ANNDataset
from repro.ann.engine import ParamSetting, resolve_setting
from repro.ann.index import (FilteredIndex, QueryBatch, SearchResult,
                             exact_distances)
from repro.ann.predicates import Predicate
from repro.ann.sharded import merge_candidates, stack_candidates

DEFAULT_DELTA_CHUNK = 512


def _bucket(k: int, mult: int = 8) -> int:
    """Round up to a multiple of `mult` — the overfetch width follows the
    tombstone count, and bucketing it bounds jit recompilations."""
    return ((int(k) + mult - 1) // mult) * mult


def _label_counts(bitmaps: np.ndarray, universe: int,
                  weights: np.ndarray | None = None) -> np.ndarray:
    """[U] per-label carrier counts from packed [N, W] bitmaps."""
    if bitmaps.shape[0] == 0:
        return np.zeros(universe, dtype=np.int64)
    shifts = np.arange(32, dtype=np.uint32)
    bits = ((bitmaps[:, :, None] >> shifts) & np.uint32(1)).astype(np.int64)
    bits = bits.reshape(bitmaps.shape[0], -1)[:, :universe]
    if weights is not None:
        bits = weights[:, None] * bits
    return bits.sum(0)


class KeyTable:
    """Vectorised open-addressing map: int64 external key -> int64 row.

    Replaces the per-key Python dict in the live key→row table. Lookups
    and inserts run as numpy linear-probe loops over whole batches, so
    `rows_of`/`delete_keys` stay flat (a handful of vectorised probe
    rounds) for multi-million-row deltas instead of one dict op per key.
    Power-of-two table kept at ≤ 0.5 load; re-inserting an existing key
    overwrites its row (a re-used key maps to its newest row).
    """

    __slots__ = ("_keys", "_rows", "_used", "_mask", "_count")

    def __init__(self, capacity_hint: int = 64):
        size = 1 << max(4, int(2 * max(capacity_hint, 1) - 1).bit_length())
        self._keys = np.zeros(size, np.int64)
        self._rows = np.zeros(size, np.int64)
        self._used = np.zeros(size, bool)
        self._mask = size - 1
        self._count = 0

    def __len__(self) -> int:
        return self._count

    @staticmethod
    def _hash(keys: np.ndarray, mask: int) -> np.ndarray:
        """splitmix64 finalizer — avalanche for sequential key ranges."""
        h = keys.astype(np.uint64)
        h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        h = h ^ (h >> np.uint64(31))
        return (h & np.uint64(mask)).astype(np.int64)

    def _grow_to(self, need: int) -> None:
        if 2 * need <= self._mask + 1:
            return
        old_keys = self._keys[self._used]
        old_rows = self._rows[self._used]
        size = 1 << int(2 * need - 1).bit_length()
        self._keys = np.zeros(size, np.int64)
        self._rows = np.zeros(size, np.int64)
        self._used = np.zeros(size, bool)
        self._mask = size - 1
        self._count = 0
        if old_keys.size:
            self.insert(old_keys, old_rows)

    def insert(self, keys, rows) -> None:
        """Batch upsert. Duplicate keys *within* one batch resolve
        last-wins (callers pass unique keys; upsert validates)."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        rows = np.ascontiguousarray(rows, dtype=np.int64)
        if keys.size == 0:
            return
        self._grow_to(self._count + keys.size)
        idx = self._hash(keys, self._mask)
        pending = np.arange(keys.size)
        guard = 0
        while pending.size:
            cur = idx[pending]
            used = self._used[cur]
            ours = used & (self._keys[cur] == keys[pending])
            attempt = ~used | ours
            if attempt.any():
                a = pending[attempt]
                c = cur[attempt]
                was_free = ~used[attempt]
                self._keys[c] = keys[a]
                self._rows[c] = rows[a]
                self._used[c] = True
                # entries that lost a same-slot race re-probe; numpy
                # duplicate-index assignment leaves the last writer's key
                won = self._keys[c] == keys[a]
                self._rows[c[won]] = rows[a[won]]
                self._count += int((was_free & won).sum())
                done = np.zeros(pending.size, bool)
                done[np.nonzero(attempt)[0][won]] = True
                pending = pending[~done]
            idx[pending] = (idx[pending] + 1) & self._mask
            guard += 1
            if guard > self._mask + 2:       # load ≤ 0.5 makes this unreachable
                raise RuntimeError("KeyTable probe loop did not terminate")

    def lookup(self, keys) -> np.ndarray:
        """[R] rows for keys; −1 where the key was never inserted."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.full(keys.shape, -1, np.int64)
        if keys.size == 0 or self._count == 0:
            return out
        idx = self._hash(keys, self._mask)
        pending = np.arange(keys.size)
        guard = 0
        while pending.size:
            cur = idx[pending]
            used = self._used[cur]
            hit = used & (self._keys[cur] == keys[pending])
            out[pending[hit]] = self._rows[cur[hit]]
            pending = pending[used & ~hit]    # empty slot ⇒ key absent
            idx[pending] = (idx[pending] + 1) & self._mask
            guard += 1
            if guard > self._mask + 2:
                raise RuntimeError("KeyTable probe loop did not terminate")
        return out


@dataclasses.dataclass(frozen=True)
class ChunkIndex:
    """Mini-IVF over one sealed delta chunk: coarse k-means centroids
    plus chunk-local posting lists, built once at chunk-seal time.

    `radius[c]` upper-bounds (in f64, rounded up) the L2 distance from
    `centroids[c]` to every member, so `max(0, ‖q−c‖ − radius)²` is an
    exact lower bound on any member's squared distance to q — the
    pruning test the fused read path uses. Chunks are immutable once
    sealed, so the index never updates.

    `label_union[c]` / `label_inter[c]` are the bitwise OR / AND of the
    members' label bitmaps — exact label bounds, so a cluster that
    *cannot* contain a predicate-matching row is pruned even when the
    distance bound can't fire (e.g. a query with < k live base
    candidates). Both are None on indexes persisted before the fields
    existed; such chunks simply skip label pruning."""

    centroids: np.ndarray   # [C, d] f32
    cnorms: np.ndarray      # [C] f64 squared centroid norms
    radius: np.ndarray      # [C] f64 cover radii (rounded up)
    members: np.ndarray     # [chunk] i32 chunk-local rows, cluster-grouped
    starts: np.ndarray      # [C+1] i32 posting-list offsets into members
    label_union: np.ndarray | None = None   # [C, W] u32 OR of member labels
    label_inter: np.ndarray | None = None   # [C, W] u32 AND of member labels

    def arrays(self) -> dict:
        out = {"centroids": self.centroids, "cnorms": self.cnorms,
               "radius": self.radius, "members": self.members,
               "starts": self.starts}
        if self.label_union is not None:
            out["label_union"] = self.label_union
            out["label_inter"] = self.label_inter
        return out

    @classmethod
    def from_arrays(cls, arrays: dict) -> "ChunkIndex":
        out = {f: np.asarray(arrays[f])
               for f in ("centroids", "cnorms", "radius",
                         "members", "starts")}
        # label bounds are optional: pre-existing persisted chunk
        # indexes lack them and just forgo label pruning
        for f in ("label_union", "label_inter"):
            if f in arrays:
                out[f] = np.asarray(arrays[f])
        return cls(**out)


def build_chunk_index(vectors: np.ndarray, *, bitmaps: np.ndarray = None,
                      n_clusters: int = 8, seed: int = 0) -> ChunkIndex:
    """Build the mini-IVF for one sealed chunk (deterministic per seed,
    so a persisted chunk index equals a rebuilt one). With `bitmaps`
    ([n, W] u32 member label bitmaps) the index also carries exact
    per-cluster label union/intersection bounds for predicate pruning."""
    from repro.ann.ivf import assign_to_centroids, kmeans

    n = vectors.shape[0]
    c = max(1, min(int(n_clusters), n))
    cent = kmeans(vectors, c, iters=4, seed=seed)
    assign = assign_to_centroids(vectors, cent)
    order = np.argsort(assign, kind="stable").astype(np.int32)
    lens = np.bincount(assign, minlength=cent.shape[0])
    starts = np.zeros(cent.shape[0] + 1, np.int32)
    starts[1:] = np.cumsum(lens)
    centf = cent.astype(np.float64)
    diff = vectors.astype(np.float64) - centf[assign]
    dist = np.sqrt((diff ** 2).sum(axis=1))
    radius = np.zeros(cent.shape[0], np.float64)
    np.maximum.at(radius, assign, dist)
    radius = radius * (1.0 + 1e-9) + 1e-9    # round up: bound must hold
    union = inter = None
    if bitmaps is not None:
        nc = cent.shape[0]
        w = bitmaps.shape[1]
        union = np.zeros((nc, w), np.uint32)
        # empty clusters read as union=0 / inter=~0: every label test
        # then prunes them, which is safe (their posting list is empty)
        inter = np.full((nc, w), np.uint32(0xFFFFFFFF))
        np.bitwise_or.at(union, assign, bitmaps.astype(np.uint32))
        np.bitwise_and.at(inter, assign, bitmaps.astype(np.uint32))
    return ChunkIndex(cent.astype(np.float32), (centf ** 2).sum(axis=1),
                      radius, order, starts, union, inter)


@dataclasses.dataclass(frozen=True)
class LiveStats:
    """Live-set summary the routing features consume (see
    `repro.core.features`): exact live size, per-label carrier
    fractions, and the bitmap rows needed to correct base selectivity
    counts (subtract tombstoned base rows, add live delta rows).
    `base_ds` is the sealed base the tombstone rows refer to — the
    feature layer counts base matches against *it*, so a compaction
    racing the feature pass can't pair generation-g corrections with a
    generation-g+1 base."""
    n_live: int
    label_freq: np.ndarray          # [U] live per-label carrier fractions
    base_tomb_bitmaps: np.ndarray   # [Tb, W] bitmaps of dead base rows
    delta_bitmaps: np.ndarray       # [Dl, W] bitmaps of live delta rows
    base_ds: object = None          # ANNDataset of this snapshot's base


class DeltaSegment:
    """Append-only host store with a chunked device mirror.

    Host arrays grow by doubling; rows never mutate once appended, so
    concurrent readers can slice up to their snapshot watermark without
    locking. The device mirror covers whole `chunk`-row blocks of
    appended data and is extended (one upload per new block) under a
    private lock; `device_view` pads the partial tail chunk with
    sentinel rows (zero vector + `PAD_SCORE` norm — never selected by
    `masked_topk`) so the kernel sees shapes that change only at chunk
    boundaries. The mirrored bitmaps are word-major ([W, rows]), the
    layout the kernels read.
    """

    def __init__(self, dim: int, width: int, *,
                 chunk: int = DEFAULT_DELTA_CHUNK):
        self.dim = int(dim)
        self.width = int(width)
        self.chunk = max(1, int(chunk))
        self._vec = np.empty((0, self.dim), np.float32)
        self._bm = np.empty((0, self.width), np.uint32)
        self._norms = np.empty((0,), np.float32)
        self._rows = 0
        self._dev = None            # (vectors, norms, bitmaps_wm) jax arrays
        self._dev_rows = 0          # rows covered by the mirror
        self._dev_lock = threading.Lock()
        self._view_cache = None     # (rows, assembled device triple)
        self._chunk_idx: list[ChunkIndex] = []   # mini-IVF per sealed chunk

    @property
    def rows(self) -> int:
        return self._rows

    def _grow(self, need: int) -> None:
        cap = self._vec.shape[0]
        if need <= cap:
            return
        new_cap = max(need, max(self.chunk, 2 * cap))
        for name, fill_shape in (("_vec", (new_cap, self.dim)),
                                 ("_bm", (new_cap, self.width)),
                                 ("_norms", (new_cap,))):
            old = getattr(self, name)
            new = np.zeros(fill_shape, old.dtype)
            new[: self._rows] = old[: self._rows]
            setattr(self, name, new)

    def append(self, vectors: np.ndarray,
               bitmaps: np.ndarray) -> tuple[int, int]:
        """Append rows; returns the local id range [start, stop)."""
        n = vectors.shape[0]
        start = self._rows
        self._grow(start + n)
        self._vec[start: start + n] = vectors
        self._bm[start: start + n] = bitmaps
        self._norms[start: start + n] = np.sum(
            vectors.astype(np.float64) ** 2, axis=1).astype(np.float32)
        self._rows = start + n
        return start, start + n

    def host_view(self, rows: int):
        """(vectors, bitmaps, norms) for the first `rows` rows (views —
        valid for any watermark that was reached before the call)."""
        return self._vec[:rows], self._bm[:rows], self._norms[:rows]

    def device_view(self, rows: int, scope):
        """Device (vectors [R, D], norms [R], bitmaps_wm [W, R]) covering
        the first `rows` rows, padded to a chunk multiple R with
        never-selected sentinel rows. `scope` is a zero-arg context
        factory placing uploads (the owning handle's `_device_scope`)."""
        import jax.numpy as jnp

        from repro.kernels import masked_topk as mk

        full = (rows // self.chunk) * self.chunk
        with self._dev_lock:
            # read-mostly fast path: the assembled triple (including the
            # padded tail) only depends on the watermark, so repeated
            # searches between writes skip the tail rebuild + re-upload
            if self._view_cache is not None and self._view_cache[0] == rows:
                return self._view_cache[1]
            if full > self._dev_rows:
                with scope():
                    vec = jnp.asarray(self._vec[self._dev_rows: full])
                    bm = jnp.asarray(self._bm[self._dev_rows: full].T)
                    nm = jnp.asarray(self._norms[self._dev_rows: full])
                    if self._dev is None:
                        self._dev = (vec, nm, bm)
                    else:
                        self._dev = (
                            jnp.concatenate([self._dev[0], vec]),
                            jnp.concatenate([self._dev[1], nm]),
                            jnp.concatenate([self._dev[2], bm], axis=1))
                self._dev_rows = full
            dev = self._dev
        parts_v = [dev[0][:full]] if full else []
        parts_n = [dev[1][:full]] if full else []
        parts_b = [dev[2][:, :full]] if full else []
        tail = rows - full
        if tail:
            tv = np.zeros((self.chunk, self.dim), np.float32)
            tb = np.zeros((self.width, self.chunk), np.uint32)
            tn = np.full((self.chunk,), mk.PAD_SCORE, np.float32)
            tv[:tail] = self._vec[full:rows]
            tb[:, :tail] = self._bm[full:rows].T
            tn[:tail] = self._norms[full:rows]
            with scope():
                parts_v.append(jnp.asarray(tv))
                parts_n.append(jnp.asarray(tn))
                parts_b.append(jnp.asarray(tb))
        if not parts_v:
            view = (jnp.zeros((0, self.dim), jnp.float32),
                    jnp.zeros((0,), jnp.float32),
                    jnp.zeros((self.width, 0), jnp.uint32))
        elif len(parts_v) == 1:
            view = (parts_v[0], parts_n[0], parts_b[0])
        else:
            view = (jnp.concatenate(parts_v), jnp.concatenate(parts_n),
                    jnp.concatenate(parts_b, axis=1))
        with self._dev_lock:
            # the row prefix below `rows` is immutable, so the view only
            # depends on the watermark — safe to reuse until it moves
            self._view_cache = (rows, view)
        return view

    def device_rows(self) -> int:
        return self._dev_rows

    def host_bytes(self) -> int:
        """Allocated host backing (includes growth headroom)."""
        return self._vec.nbytes + self._bm.nbytes + self._norms.nbytes

    def device_bytes(self) -> int:
        """Mirror footprint: vectors + norms + bitmaps per covered row."""
        return self._dev_rows * (self.dim * 4 + 4 + self.width * 4)

    def drop_device(self) -> None:
        with self._dev_lock:
            self._dev = None
            self._dev_rows = 0
            self._view_cache = None

    # ---- per-chunk mini-IVF ---------------------------------------------
    def chunk_indexes(self, rows: int) -> list[ChunkIndex]:
        """ChunkIndex list covering the sealed chunks below `rows`.

        Built lazily on first request after a chunk seals (≈ one tiny
        k-means per `chunk` appended rows) and cached forever — sealed
        chunks are immutable. Store restores short-circuit the build via
        `adopt_chunk_indexes`."""
        want = int(rows) // self.chunk
        if want <= 0:
            return []
        with self._dev_lock:
            vec = self._vec        # row prefix is immutable; see host_view
            bm = self._bm
            while len(self._chunk_idx) < want:
                i = len(self._chunk_idx)
                lo = i * self.chunk
                self._chunk_idx.append(build_chunk_index(
                    vec[lo: lo + self.chunk],
                    bitmaps=bm[lo: lo + self.chunk], seed=i))
            return self._chunk_idx[:want]

    def adopt_chunk_indexes(self, indexes: dict[int, ChunkIndex]) -> None:
        """Install persisted chunk indexes (the store's restore path).
        Only a contiguous prefix extension of already-built chunks is
        accepted; anything else is rebuilt lazily instead."""
        with self._dev_lock:
            sealed = self._rows // self.chunk
            for i in sorted(indexes):
                if i == len(self._chunk_idx) and i < sealed:
                    self._chunk_idx.append(indexes[i])

    def built_chunk_indexes(self) -> list[ChunkIndex]:
        """The chunk indexes built so far (no building)."""
        with self._dev_lock:
            return list(self._chunk_idx)


class _StageTimings:
    """Instance facade over the engine-level thread-local stage-timing
    accumulator (`repro.ann.engine.StageTimings`): `run_method` calls
    `_stage_add`, the service layer drains with `pop_stage_timings`
    (per thread, so pipelined queue workers don't cross-contaminate).
    The accumulator itself lives in `engine` so kernel wrappers and
    other layers can contribute stages without importing this module."""

    def _stage_add(self, d: dict) -> None:
        for key, val in d.items():
            engine_mod.stage_add(key, val)

    def pop_stage_timings(self) -> dict:
        """Return and clear this thread's accumulated stage timings."""
        return engine_mod.pop_stage_timings()


class _LabelClockMixin:
    """Monotone per-label write clock shared by `LiveFilteredIndex` and
    `ShardedLiveIndex` — the invalidation signal the semantic result
    cache (`repro.ann.cache`) keys on.

    Every `upsert`/`delete` bumps a global write counter and stamps the
    labels present in the written rows with it. A cached entry recorded
    at clock `c` for query labels `L` is provably unaffected by later
    writes iff `label_clock(L) <= c`: any row that can match an
    EQUALITY/AND/OR predicate over a non-empty query label set carries
    at least one of those labels, so writing it stamps them. Entries
    with an *empty* query bitmap (AND matches every row) compare
    against the global clock instead (`label_clock(None)`).

    Concrete classes provide `_lock` and `_universe` and call
    `_clock_init()` in `__init__` and `_clock_touch(counts)` under the
    lock on every write. Compaction does not touch the clock: it remaps
    ids but never changes the live row set."""

    def _clock_init(self) -> None:
        self._label_stamps = np.zeros(self._universe, dtype=np.int64)
        self._write_clock = 0

    def _clock_touch(self, counts: np.ndarray) -> None:
        """Stamp the labels with nonzero `counts` ([U] per-label row
        counts of the written rows); caller holds the lock."""
        self._write_clock += 1
        touched = np.nonzero(counts)[0]
        if touched.size:
            self._label_stamps[touched] = self._write_clock

    def label_clock(self, labels=None) -> int:
        """The latest write clock that touched any of `labels` (int
        indices), or the global write clock when `labels` is None/empty.
        Monotone; 0 means "never written"."""
        with self._lock:
            if labels is None:
                return self._write_clock
            labels = np.asarray(labels, dtype=np.int64)
            if labels.size == 0:
                return self._write_clock
            return int(self._label_stamps[labels].max())


class _StableKeyMixin:
    """Stable external-key plumbing shared by `LiveFilteredIndex` and
    `ShardedLiveIndex` (it had drifted into two near-identical copies).

    Concrete classes provide `_lock`, `_keys`, `_next_key`, `n_total`,
    `delete(rows)`, and `_row_live(rows) -> bool[R]`; the mixin owns the
    `KeyTable` lifecycle (`_key_rows`, built lazily by `_key_index`,
    extended incrementally via `_note_new_keys` on upsert, dropped to
    None at the compaction swap) and the public key API."""

    def _key_index(self) -> KeyTable:
        """key -> current-generation row table (caller holds the lock).
        Re-used keys map to their newest row."""
        if self._key_rows is None:
            n_tot = self.n_total
            table = KeyTable(max(n_tot, 64))
            if n_tot:
                table.insert(self._keys[:n_tot],
                             np.arange(n_tot, dtype=np.int64))
            self._key_rows = table
        return self._key_rows

    def _note_new_keys(self, ks: np.ndarray, start_row: int) -> None:
        """Extend the key table for freshly appended rows (lock held;
        no-op while the table hasn't been built)."""
        if self._key_rows is not None and ks.size:
            self._key_rows.insert(
                ks, np.arange(start_row, start_row + ks.size,
                              dtype=np.int64))

    def _claim_keys(self, keys, n: int) -> np.ndarray:
        """Validate/assign [n] external keys (caller holds the lock)."""
        if keys is None:
            ks = np.arange(self._next_key, self._next_key + n,
                           dtype=np.int64)
        else:
            ks = np.atleast_1d(np.asarray(keys, dtype=np.int64))
            if ks.shape != (n,):
                raise ValueError(
                    f"upsert keys must be [{n}]; got shape {ks.shape}")
            if np.unique(ks).size != n:
                raise ValueError("upsert keys must be unique per batch")
            rows = self._key_index().lookup(ks)
            known = rows >= 0
            if known.any():
                live = self._row_live(rows[known])
                if live.any():
                    bad_key = int(ks[known][live][0])
                    bad_row = int(rows[known][live][0])
                    raise ValueError(
                        f"key {bad_key} already names a live row (id "
                        f"{bad_row}); delete it first to re-point the key")
        if n:
            self._next_key = max(self._next_key, int(ks.max()) + 1)
        return ks

    def keys_of(self, ids, snapshot=None) -> np.ndarray:
        """Stable external keys for (current-generation or snapshot)
        ids: int64 array of `ids`' shape, −1 where the id is −1. Keys
        survive `compact()` and a `repro.ann.store` round trip;
        per-generation ids do not."""
        ids = np.asarray(ids, dtype=np.int64)
        if snapshot is not None:
            keys = snapshot.keys
        else:
            with self._lock:
                keys = self._keys[: self.n_total]
        out = np.full(ids.shape, -1, dtype=np.int64)
        valid = ids >= 0
        if valid.any():
            out[valid] = keys[ids[valid]]
        return out

    def rows_of(self, keys) -> np.ndarray:
        """Current-generation ids for external keys (−1 for a key that
        has never been assigned). A re-used key maps to its newest
        row."""
        keys = np.atleast_1d(np.asarray(keys, dtype=np.int64))
        with self._lock:
            return self._key_index().lookup(keys)

    def delete_keys(self, keys) -> int:
        """Tombstone rows by stable external key; unknown keys raise
        KeyError. Returns the number of newly deleted rows."""
        keys = np.atleast_1d(np.asarray(keys, dtype=np.int64))
        with self._lock:
            rows = self.rows_of(keys)
            if (rows < 0).any():
                missing = keys[rows < 0].tolist()
                raise KeyError(f"unknown external keys: {missing}")
            return self.delete(rows)


class LiveSnapshot:
    """Consistent read epoch over a `LiveFilteredIndex`.

    Captures the delta high-watermark, a tombstone copy, the external-key
    prefix, and the base generation — and *pins* that generation (the
    sealed base handle stays open) until `release()` / the context
    manager exits. Searches that are handed a snapshot see exactly this
    state regardless of concurrent `upsert`/`delete`/`compact` calls.
    """

    __slots__ = ("generation", "base_n", "delta_rows", "tombstones",
                 "tombstone_version", "delta", "keys", "next_key",
                 "_owner", "_released", "_lease")

    def __init__(self, owner, generation, base_n, delta_rows, tombstones,
                 tombstone_version, delta, keys, next_key):
        self.generation = generation
        self.base_n = base_n
        self.delta_rows = delta_rows
        self.tombstones = tombstones
        self.tombstone_version = tombstone_version
        self.delta = delta
        self.keys = keys
        self.next_key = next_key
        self._owner = owner
        self._released = False
        self._lease = None          # ledger pin, set by snapshot()

    @property
    def n_total(self) -> int:
        return self.base_n + self.delta_rows

    @property
    def n_live(self) -> int:
        return self.n_total - int(self.tombstones.sum())

    def release(self) -> None:
        """Unpin the snapshot's generation (idempotent, thread-safe). A
        drained, superseded generation frees its base handle here."""
        with self._owner._lock:        # flag flip atomic wrt double release
            if self._released:
                return
            self._released = True
        if self._lease is not None:
            self._lease.release()
        self._owner._release_reader(self.generation)

    def __enter__(self) -> "LiveSnapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return (f"LiveSnapshot(gen={self.generation}, base_n={self.base_n}, "
                f"delta_rows={self.delta_rows}, "
                f"tombstones={int(self.tombstones.sum())})")


class LiveFilteredIndex(_StableKeyMixin, _LabelClockMixin, _StageTimings):
    """Mutable serving handle: sealed base + delta segment + tombstones.

    Args:
        ds: the sealed base dataset, or None for an empty live index
            (then `name`/`dim`/`universe` are required — e.g. via the
            `empty` constructor). Routed serving (`RouterService`) needs
            a non-empty base for its dataset-level features; direct
            method search works from empty.
        registry: optional `MethodRegistry` for method-name resolution.
        device: optional jax device pin (forwarded to the base handle
            and the delta mirror uploads).
        delta_chunk: delta device-mirror block size in rows.
        base_keys: optional [N] int64 stable external keys for the base
            rows (defaults to the row ids 0..N-1). `repro.ann.store`
            passes the persisted per-generation key map here on reopen.
        next_key: first key `upsert` auto-assigns (defaults past the
            largest base key).
        generation: starting generation counter (restored stores resume
            at the persisted generation instead of 0).
        fused: serve reads through the single-launch fused kernel
            (default). False falls back to the three-stage parity path
            (`_run_staged`) — bit-identical, slower.
        graft: let `compact()` splice built method indexes through
            `Method.graft_index` instead of rebuilding (default). False
            forces the full rebuild.
        delta_prune_min_rows: delta size above which the sealed-chunk
            mini-IVF pruner engages (default `4 * delta_chunk`; the
            ball-bound test isn't worth its host matmul below that).
    """

    def __init__(self, ds: ANNDataset | None = None, *, name: str | None = None,
                 dim: int | None = None, universe: int | None = None,
                 registry=None, device=None,
                 delta_chunk: int = DEFAULT_DELTA_CHUNK,
                 base_keys: np.ndarray | None = None,
                 next_key: int | None = None, generation: int = 0,
                 fused: bool = True, graft: bool = True,
                 delta_prune_min_rows: int | None = None):
        if ds is None:
            if name is None or dim is None or universe is None:
                raise ValueError(
                    "an empty LiveFilteredIndex needs name=, dim= and "
                    "universe= (or pass a base ANNDataset)")
            self._name, self._dim = str(name), int(dim)
            self._universe = int(universe)
            self._width = lb.n_words(self._universe)
            self._base_fx: FilteredIndex | None = None
            self._base_n = 0
            base_counts = np.zeros(self._universe, dtype=np.int64)
        else:
            self._name, self._dim = ds.name, ds.dim
            self._universe = ds.universe
            self._width = ds.bitmaps.shape[1]
            self._base_fx = FilteredIndex(ds, registry=registry,
                                          device=device)
            self._base_n = ds.n
            base_counts = _label_counts(
                ds.group_bitmaps, ds.universe,
                weights=ds.group_size.astype(np.int64))
        self._registry = registry
        self._placement = device
        self._delta_chunk = int(delta_chunk)
        self._delta = DeltaSegment(self._dim, self._width, chunk=delta_chunk)
        self._tomb = np.zeros(self._base_n, bool)
        self._tomb_version = 0
        self._live_label_counts = base_counts
        self._clock_init()
        self._generation = int(generation)
        if base_keys is None:
            self._keys = np.arange(self._base_n, dtype=np.int64)
        else:
            self._keys = np.asarray(base_keys, dtype=np.int64).copy()
            if self._keys.shape != (self._base_n,):
                raise ValueError(
                    f"base_keys must be [{self._base_n}]; got shape "
                    f"{self._keys.shape}")
        self._next_key = int(next_key) if next_key is not None else \
            (int(self._keys.max()) + 1 if self._base_n else 0)
        self._key_rows: KeyTable | None = None   # built lazily
        self._wal = None                      # attached write-ahead log
        self._lock = threading.RLock()
        self._readers: dict[int, int] = {}      # generation -> pin count
        self._retired: dict[int, FilteredIndex | None] = {}
        self._retired_leases: dict[int, object] = {}   # gen -> ledger lease
        self._compact_pool: ThreadPoolExecutor | None = None
        self._compacting: Future | None = None
        self._last_remap: np.ndarray | None = None
        self._features = None       # repro.core.features cache slot
        self.fused = bool(fused)
        self._graft = bool(graft)
        self._delta_prune_min_rows = (4 * self._delta_chunk
                                      if delta_prune_min_rows is None
                                      else int(delta_prune_min_rows))
        self._tomb_words_cache = None   # ((gen, version, n_pad), device arr)
        self._prune_stats = {"calls": 0, "clusters": 0, "pruned": 0,
                             "label_pruned": 0}
        self._closed = False
        # delta/device bytes + reader pins as pull gauges on the process
        # ledger (collected only at scrape/snapshot time)
        self._ledger_key = f"live:{self._name}:{id(self):x}"
        ledger_mod.get_ledger().register_collector(
            self._ledger_key, self._ledger_gauges)

    def _ledger_gauges(self) -> dict:
        with self._lock:
            if self._closed:
                return {"closed": 1}
            d = self._delta
            return {"generation": self._generation,
                    "delta_rows": d.rows,
                    "delta_host_bytes": d.host_bytes(),
                    "delta_device_rows": d.device_rows(),
                    "delta_device_bytes": d.device_bytes(),
                    "tombstones": int(self._tomb.sum()),
                    "pinned_readers": sum(self._readers.values()),
                    "retired_generations": len(self._retired)}

    @classmethod
    def empty(cls, name: str, dim: int, universe: int,
              **kw) -> "LiveFilteredIndex":
        """A live index with no sealed base — everything starts as delta."""
        return cls(None, name=name, dim=dim, universe=universe, **kw)

    # ---- lifecycle ------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def ds(self) -> ANNDataset | None:
        """The current generation's sealed base dataset (None when the
        index started empty and has not compacted yet)."""
        fx = self._base_fx
        return None if fx is None else fx.ds

    @property
    def generation(self) -> int:
        return self._generation

    @property
    def base_n(self) -> int:
        return self._base_n

    @property
    def n_total(self) -> int:
        return self._base_n + self._delta.rows

    @property
    def n_live(self) -> int:
        with self._lock:
            return self.n_total - int(self._tomb.sum())

    @property
    def device(self):
        """Base device tensors (routing-feature kernels). Requires a
        non-empty base."""
        if self._base_fx is None:
            raise RuntimeError(
                f"LiveFilteredIndex({self._name!r}) has no sealed base yet "
                f"(compact() first, or serve it unrouted)")
        return self._base_fx.device

    def close(self) -> None:
        """Stop the handle: wait out a running compaction (its swap is
        skipped once closed), close the base of every generation, drop
        the delta device mirror. Idempotent."""
        ledger_mod.get_ledger().deregister_collector(self._ledger_key)
        with self._lock:
            if self._closed:
                return
            self._closed = True
            comp = self._compacting
        if comp is not None:
            try:
                comp.result(timeout=300)
            except BaseException:
                pass
        with self._lock:
            if self._base_fx is not None:
                self._base_fx.close()
            for fx in self._retired.values():
                if fx is not None:
                    fx.close()
            self._retired.clear()
            for lease in self._retired_leases.values():
                lease.release()
            self._retired_leases.clear()
            self._delta.drop_device()
            self._features = None
        if self._compact_pool is not None:
            self._compact_pool.shutdown(wait=True)
            self._compact_pool = None

    def __enter__(self) -> "LiveFilteredIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                f"LiveFilteredIndex({self._name!r}) is closed")

    def _device_scope(self):
        import contextlib

        if self._placement is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(self._placement)

    # ---- write path -----------------------------------------------------
    def upsert(self, vectors, bitmaps, *, keys=None) -> np.ndarray:
        """Append rows to the delta segment.

        Args:
            vectors: [R, d] (or [d]) float embeddings.
            bitmaps: [R, W] (or [W]) packed uint32 label sets.
            keys: optional [R] int64 stable external keys for the rows
                (auto-assigned sequentially when omitted). A key that
                already names a *live* row is rejected — delete the old
                row first to re-point a key.
        Returns: [R] int64 assigned ids (valid for this generation;
            `compact()` remaps them — `keys_of` gives the stable keys).
        Raises: RuntimeError if closed; ValueError on shape mismatch or
            a duplicate live key.
        """
        vectors = np.asarray(vectors, dtype=np.float32)
        bitmaps = np.asarray(bitmaps, dtype=np.uint32)
        if vectors.ndim == 1:
            vectors = vectors[None]
        if bitmaps.ndim == 1:
            bitmaps = bitmaps[None]
        if vectors.ndim != 2 or vectors.shape[1] != self._dim:
            raise ValueError(
                f"upsert vectors must be [R, {self._dim}]; got "
                f"{vectors.shape}")
        if bitmaps.shape != (vectors.shape[0], self._width):
            raise ValueError(
                f"upsert bitmaps must be [{vectors.shape[0]}, "
                f"{self._width}]; got {bitmaps.shape}")
        # the bit expansion only depends on the arguments — keep it out
        # of the lock so big ingest batches don't stall readers
        counts = _label_counts(bitmaps, self._universe)
        with self._lock:
            self._check_open()
            ks = self._claim_keys(keys, vectors.shape[0])
            wal = self._wal
            if wal is not None:              # logged before applied
                seq = wal.log_upsert(self._generation, ks, vectors, bitmaps)
            start, stop = self._delta.append(vectors, bitmaps)
            self._tomb = np.concatenate(
                [self._tomb, np.zeros(stop - start, bool)])
            self._keys = np.concatenate([self._keys, ks])
            self._note_new_keys(ks, self._base_n + start)
            self._live_label_counts = self._live_label_counts + counts
            self._clock_touch(counts)
            out = np.arange(self._base_n + start, self._base_n + stop,
                            dtype=np.int64)
        if wal is not None:
            wal.commit(seq)                  # durable before acked, off-lock
        return out

    def _row_live(self, rows: np.ndarray) -> np.ndarray:
        """bool[R]: which current-generation rows are not tombstoned
        (mixin hook; caller holds the lock)."""
        return ~self._tomb[rows]

    def delete(self, ids) -> int:
        """Tombstone ids (base or delta rows of the current generation).
        Returns the number of *newly* deleted rows; already-dead ids are
        no-ops. Raises IndexError on out-of-range ids."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        with self._lock:
            self._check_open()
            n_tot = self.n_total
            if ids.size and (ids.min() < 0 or ids.max() >= n_tot):
                raise IndexError(
                    f"delete ids must be in [0, {n_tot}); got range "
                    f"[{ids.min()}, {ids.max()}]")
            wal = self._wal
            if wal is not None:              # replay is idempotent
                seq = wal.log_delete(self._generation, ids)
            fresh = ids[~self._tomb[ids]]
            fresh = np.unique(fresh)
            if fresh.size:
                self._tomb[fresh] = True
                self._tomb_version += 1
                dcounts = _label_counts(self._bitmaps_of(fresh),
                                        self._universe)
                self._live_label_counts = self._live_label_counts - dcounts
                self._clock_touch(dcounts)
            out = int(fresh.size)
        if wal is not None:
            wal.commit(seq)                  # durable before acked, off-lock
        return out

    # stable external keys (`keys_of`/`rows_of`/`delete_keys`/`_claim_keys`)
    # come from _StableKeyMixin.

    # ---- durability hook (repro.ann.store) -------------------------------
    def attach_wal(self, wal) -> None:
        """Attach a write-ahead log: every subsequent `upsert`/`delete`
        appends a record *before* the state mutates, and `compact_async`
        logs a compaction barrier at its snapshot point. Pass None to
        detach. The store owns the WAL lifecycle (rotation, fsync,
        close); the live handle only appends."""
        with self._lock:
            self._wal = wal

    def _bitmaps_of(self, gids: np.ndarray) -> np.ndarray:
        """[R, W] packed bitmaps for current-generation global ids."""
        out = np.zeros((gids.size, self._width), np.uint32)
        base = gids < self._base_n
        if base.any():
            out[base] = self._base_fx.ds.bitmaps[gids[base]]
        if (~base).any():
            out[~base] = self._delta._bm[gids[~base] - self._base_n]
        return out

    def fetch(self, ids, snapshot: LiveSnapshot | None = None) -> np.ndarray:
        """[R, d] vectors for result ids (−1 rows come back as NaN).
        With a snapshot, ids are interpreted in that epoch's id space."""
        snap = snapshot or self.snapshot()
        try:
            ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
            out = np.full((ids.size, self._dim), np.nan, np.float32)
            fx = self._base_for(snap)
            base = (ids >= 0) & (ids < snap.base_n)
            if base.any():
                out[base] = fx.ds.vectors[ids[base]]
            delta = ids >= snap.base_n
            if delta.any():
                dvec, _, _ = snap.delta.host_view(snap.delta_rows)
                out[delta] = dvec[ids[delta] - snap.base_n]
            return out
        finally:
            if snapshot is None:
                snap.release()

    # ---- snapshots / epochs ---------------------------------------------
    def snapshot(self) -> LiveSnapshot:
        """Pin a consistent read epoch (see `LiveSnapshot`). Callers that
        hold one across writes must `release()` it (context manager
        supported); searches without an explicit snapshot take and
        release one internally."""
        with self._lock:
            self._check_open()
            rows = self._delta.rows
            gen = self._generation
            self._readers[gen] = self._readers.get(gen, 0) + 1
            # keys: a view is enough — _keys is only ever *reassigned*
            # (concatenate on upsert, fresh array at the compaction
            # swap), never written in place, so the sliced object stays
            # frozen; tombstones mutate in place and must copy
            snap = LiveSnapshot(self, gen, self._base_n, rows,
                                self._tomb[: self._base_n + rows].copy(),
                                self._tomb_version, self._delta,
                                self._keys[: self._base_n + rows],
                                self._next_key)
        # the pin lease carries the acquiring trace id + caller stack —
        # a snapshot held past the ledger's leak age names its taker
        snap._lease = ledger_mod.get_ledger().acquire(
            "snapshot_pin", self._name, meta={"generation": int(gen)})
        return snap

    def _release_reader(self, gen: int) -> None:
        with self._lock:
            left = self._readers.get(gen, 0) - 1
            if left > 0:
                self._readers[gen] = left
                return
            self._readers.pop(gen, None)
            had_retired = gen in self._retired
            fx = self._retired.pop(gen, None)
            lease = (self._retired_leases.pop(gen, None)
                     if had_retired else None)
        if lease is not None:
            lease.release()
        if fx is not None:
            fx.close()

    def _base_for(self, snap: LiveSnapshot) -> FilteredIndex | None:
        with self._lock:
            if snap.generation == self._generation:
                return self._base_fx
            if snap.generation in self._retired:
                return self._retired[snap.generation]
        raise RuntimeError(
            f"snapshot generation {snap.generation} has been released "
            f"(current generation {self._generation})")

    # ---- read path -------------------------------------------------------
    def _resolve(self, method):
        if isinstance(method, str):
            reg = self._registry or registry_mod.default_registry()
            return reg.get(method)
        return method

    def run_method(self, method, setting: ParamSetting, batch: QueryBatch,
                   *, snapshot: LiveSnapshot | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Raw live execution of one (method, setting): routed method on
        the base, brute-force `masked_topk` on the delta, tombstones
        masked in both, candidates folded through `merge_topk`.

        Returns the `FilteredIndex.run_method` contract: ([Q, k] int32
        ids with −1 pad, [Q, k] float32 ranking scores with +inf at −1).
        Stage timings (`base_s`/`delta_s`/`merge_s`) accumulate on a
        thread-local, drained by `pop_stage_timings()`.
        """
        self._check_open()
        snap = snapshot
        if snap is None:
            snap = self.snapshot()
        try:
            return self._run(method, setting, batch, snap)
        finally:
            if snapshot is None:
                snap.release()

    def _run(self, method, setting, batch: QueryBatch, snap: LiveSnapshot):
        if self.fused and snap.delta_rows:
            return self._run_fused(method, setting, batch, snap)
        return self._run_staged(method, setting, batch, snap)

    def _run_base(self, method, setting, batch: QueryBatch,
                  snap: LiveSnapshot, base_dead: int
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Routed base candidates [Q, KB] (numpy), overfetched by the
        full base tombstone count (bucketed to bound recompiles, clamped
        to the base size) so deletions can't crowd out live rows: among
        the top-(k + dead) ranked matches at most `dead` are tombstoned,
        leaving >= k live ones — any smaller overfetch can miss live
        rows once the dead outnumber k in a query's neighborhood. [Q, 0]
        for an empty base."""
        fx = self._base_for(snap) if snap.base_n else None
        if fx is None:
            return (np.zeros((batch.q, 0), np.int32),
                    np.zeros((batch.q, 0), np.float32))
        k = batch.k
        kb = (max(k, min(_bucket(k + base_dead), snap.base_n))
              if base_dead else k)
        trace.annotate(overfetch=int(kb))
        b_ids, b_raw = fx.run_method(
            self._resolve(method), setting,
            QueryBatch(batch.vectors, batch.bitmaps, batch.pred, kb))
        return (np.asarray(b_ids, dtype=np.int32),
                np.asarray(b_raw, dtype=np.float32))

    def _run_fused(self, method, setting, batch: QueryBatch,
                   snap: LiveSnapshot):
        """Single-launch live read: routed base candidates and the delta
        scan fold through one `ops.fused_live_topk` call; tombstones are
        applied to both candidate sets on the device (packed-word gather
        just before the launch), so there is no host mask, no per-stage
        delta overfetch, and no separate merge launch. Bit-identical to
        `_run_staged`."""
        import jax.numpy as jnp

        from repro.kernels import ops

        k = batch.k
        tomb = snap.tombstones
        base_dead = int(tomb[: snap.base_n].sum())
        t0 = time.perf_counter()
        with trace.span("live.base", base_n=int(snap.base_n),
                        dead=base_dead):
            b_ids, b_raw = self._run_base(method, setting, batch, snap,
                                          base_dead)
        t1 = time.perf_counter()
        with trace.span("live.delta", rows=int(snap.delta_rows),
                        fused=True):
            dvec, dnorm, dbm = snap.delta.device_view(
                snap.delta_rows, self._device_scope)
            tomb_words = self._tomb_words(snap)
            sel = self._delta_select(snap, batch, b_ids, b_raw)
            if sel is not None and sel.size == 0:
                # every sealed cluster was pruned and there is no tail
                # row.  Re-include one pruned row to keep the kernel
                # operand non-empty: a pruned row provably cannot
                # displace any query's top-k, so the result bits are
                # unchanged.
                sel = np.zeros(1, np.int32)
            qv = jnp.asarray(batch.vectors)
            qb = jnp.asarray(batch.bitmaps)
            if sel is None:
                ids, raw = ops.fused_live_topk(
                    qv, qb, b_ids, b_raw, dvec, dnorm, dbm,
                    np.int32(snap.base_n), tomb_words,
                    pred=int(batch.pred), k=k)
            else:
                ids, raw = ops.fused_live_topk_select(
                    qv, qb, b_ids, b_raw, dvec, dnorm, dbm, sel,
                    np.int32(snap.base_n), tomb_words,
                    pred=int(batch.pred), k=k)
            ids = np.asarray(ids, dtype=np.int32)
            raw = np.asarray(raw, dtype=np.float32)
        t2 = time.perf_counter()
        self._stage_add({"base_s": t1 - t0, "delta_s": t2 - t1,
                         "merge_s": 0.0})    # merge happens in-kernel
        return ids, raw

    def _run_staged(self, method, setting, batch: QueryBatch,
                    snap: LiveSnapshot):
        """Pre-PR-6 three-stage live read (base launch → delta
        `masked_topk` → host tombstone mask → `merge_topk`): the parity
        reference for the fused path, and the fallback when the delta is
        empty (nothing to fuse over)."""
        k = batch.k
        tomb = snap.tombstones
        base_dead = int(tomb[: snap.base_n].sum())
        delta_dead = int(tomb[snap.base_n:].sum())
        parts = []
        t0 = time.perf_counter()
        if snap.base_n:
            with trace.span("live.base", base_n=int(snap.base_n),
                            dead=base_dead):
                b_ids, b_raw = self._run_base(method, setting, batch,
                                              snap, base_dead)
            if base_dead:
                valid = b_ids >= 0
                dead = np.zeros_like(valid)
                dead[valid] = tomb[b_ids[valid]]
                b_ids = np.where(dead, np.int32(-1), b_ids)
                b_raw = np.where(dead, np.float32(np.inf), b_raw)
            parts.append((b_ids, b_raw))
        t1 = time.perf_counter()
        if snap.delta_rows:
            import jax.numpy as jnp

            from repro.kernels import ops

            # exact overfetch: top-(k + dead) over the delta always
            # contains the live top-k
            kd = _bucket(k + min(delta_dead, snap.delta_rows))
            with trace.span("live.delta", rows=int(snap.delta_rows),
                            overfetch=int(kd), fused=False):
                dvec, dnorm, dbm = snap.delta.device_view(
                    snap.delta_rows, self._device_scope)
                d_ids, d_raw = ops.masked_topk(
                    jnp.asarray(batch.vectors),
                    jnp.asarray(batch.bitmaps),
                    dvec, dnorm, dbm, pred=int(batch.pred), k=kd)
            d_ids = np.asarray(d_ids, dtype=np.int32)
            d_raw = np.asarray(d_raw, dtype=np.float32)
            # sentinel/pad rows are already −1; rows past the watermark
            # (appended since the snapshot) and tombstoned rows drop here
            valid = (d_ids >= 0) & (d_ids < snap.delta_rows)
            dead = ~valid
            dead[valid] |= tomb[snap.base_n + d_ids[valid]]
            d_ids = np.where(dead, np.int32(-1),
                             d_ids + np.int32(snap.base_n))
            d_raw = np.where(dead, np.float32(np.inf), d_raw)
            parts.append((d_ids, d_raw))
        t2 = time.perf_counter()
        if not parts:
            ids = np.full((batch.q, k), -1, np.int32)
            raw = np.full((batch.q, k), np.inf, np.float32)
        else:
            with trace.span("live.merge"):
                ids, raw = merge_candidates(*stack_candidates(parts),
                                            k=k)
        t3 = time.perf_counter()
        self._stage_add({"base_s": t1 - t0, "delta_s": t2 - t1,
                         "merge_s": t3 - t2})
        return ids, raw

    def _tomb_words(self, snap: LiveSnapshot):
        """[TW] uint32 packed device tombstones for the fused kernel.

        Cached by (generation, tombstone version, padded length): rows
        appended after the pack only add zero bits, so the cached words
        stay valid until a delete bumps the version or the padded length
        grows past the next 4096-row bucket."""
        import jax.numpy as jnp

        n_pad = _bucket(max(snap.n_total, 1), 4096)
        key = (snap.generation, snap.tombstone_version, n_pad)
        cached = self._tomb_words_cache
        if cached is not None and cached[0] == key:
            return cached[1]
        words = np.zeros(n_pad // 8, np.uint8)
        packed = np.packbits(snap.tombstones, bitorder="little")
        words[: packed.size] = packed
        with self._device_scope():
            dev = jnp.asarray(words.view(np.uint32))
        self._tomb_words_cache = (key, dev)
        return dev

    @staticmethod
    def _label_drop(chunk_idx: list[ChunkIndex],
                    batch: QueryBatch) -> np.ndarray:
        """[Q, C] True where a cluster's exact label bounds prove no
        member can satisfy the query's predicate. Chunks persisted
        without label bounds contribute all-False columns (no label
        pruning, distance pruning unaffected)."""
        qb = batch.bitmaps.astype(np.uint32)
        nq = qb.shape[0]
        qx = qb[:, None, :]                       # [Q, 1, W]
        pred = Predicate(batch.pred)
        cols = []
        for c in chunk_idx:
            ncl = c.radius.size
            if c.label_union is None:
                cols.append(np.zeros((nq, ncl), bool))
                continue
            uq = c.label_union[None, :, :] & qx   # [Q, C, W]
            if pred == Predicate.OR:
                # OR needs a shared bit; the union has none of q's bits
                drop = (uq == 0).all(axis=2)
            elif pred == Predicate.AND:
                # AND needs q ⊆ row; a q-bit missing from the union is
                # missing from every member
                drop = (uq != qx).any(axis=2)
            else:                                 # EQUALITY: row == q
                # a q-bit missing from the union, or a bit carried by
                # every member (intersection) that q lacks
                drop = ((uq != qx).any(axis=2)
                        | ((c.label_inter[None, :, :] & ~qx) != 0)
                        .any(axis=2))
            cols.append(drop)
        return np.concatenate(cols, axis=1)

    def _delta_select(self, snap: LiveSnapshot, batch: QueryBatch,
                      b_ids: np.ndarray, b_raw: np.ndarray
                      ) -> np.ndarray | None:
        """Exact ball-bound + label-bound pruning over the sealed
        chunks' mini-IVFs.

        Returns None to scan the whole delta mirror, or a sorted [NS]
        i32 array of delta-local rows that provably contains every
        query's live top-k among the delta. A cluster is dropped only
        when, for *every* query, it provably cannot contribute:

        * distance: the exact lower bound max(0, ‖q−c‖ − radius)² on
          any member's distance exceeds the query's k-th best live
          base-candidate distance (plus a rounding margin) — such rows
          cannot displace the eventual top-k;
        * labels: the cluster's exact label union/intersection is
          incompatible with the query's predicate (OR: no shared bit
          with the union; AND: a required bit missing from the union;
          EQUALITY: a required bit missing from the union, or a bit
          every member carries that the query lacks) — such rows are
          masked out by `masked_topk` anyway.

        Either way the result stays bit-identical to the full scan.
        Label pruning needs no base candidates, so it also fires for
        queries with fewer than k live base matches (where the distance
        threshold is +inf). The partial tail chunk is always scanned."""
        rows = snap.delta_rows
        if rows < self._delta_prune_min_rows:
            return None
        chunk_idx = snap.delta.chunk_indexes(rows)
        if not chunk_idx:
            return None
        # per-query threshold: k-th smallest live base candidate (raw
        # score scale ‖v‖² − 2·q·v); +inf disables distance pruning for
        # queries with fewer than k live base candidates
        if b_ids.shape[1] >= batch.k:
            live = b_ids >= 0
            live[live] = ~snap.tombstones[b_ids[live]]
            cand = np.where(live, b_raw, np.inf).astype(np.float64)
            cand.sort(axis=1)
            bound = cand[:, batch.k - 1]                   # [Q]
        else:
            bound = np.full(batch.q, np.inf)
        qv = batch.vectors.astype(np.float64)
        qn = (qv ** 2).sum(axis=1)
        cent = np.concatenate([c.centroids for c in chunk_idx]
                              ).astype(np.float64)
        cn = np.concatenate([c.cnorms for c in chunk_idx])
        rad = np.concatenate([c.radius for c in chunk_idx])
        d2 = np.maximum(cn[None, :] - 2.0 * (qv @ cent.T) + qn[:, None],
                        0.0)
        lb = np.maximum(np.sqrt(d2) - rad[None, :], 0.0) ** 2   # [Q, C]
        # margin absorbs the kernel's f32 rounding of candidate scores;
        # an infinite bound yields an infinite margin and never drops
        margin = 1e-3 * (1.0 + np.abs(bound))
        dist_drop = (lb - qn[:, None]) > (bound + margin)[:, None]
        label_drop = self._label_drop(chunk_idx, batch)         # [Q, C]
        drop = (dist_drop | label_drop).all(axis=0)
        with self._lock:
            self._prune_stats["calls"] += 1
            self._prune_stats["clusters"] += int(drop.size)
            self._prune_stats["pruned"] += int(drop.sum())
            self._prune_stats["label_pruned"] += int(
                label_drop.all(axis=0).sum())
        if not drop.any():
            return None
        chunk = snap.delta.chunk
        keep_rows = []
        ci = 0
        for i, c in enumerate(chunk_idx):
            ncl = c.radius.size
            kept = ~drop[ci: ci + ncl]
            off = i * chunk
            if kept.all():
                keep_rows.append(off + np.arange(chunk, dtype=np.int64))
            elif kept.any():
                parts = [c.members[c.starts[j]: c.starts[j + 1]]
                         for j in np.nonzero(kept)[0]]
                keep_rows.append(off + np.concatenate(parts
                                                      ).astype(np.int64))
            ci += ncl
        covered = len(chunk_idx) * chunk
        keep_rows.append(np.arange(covered, rows, dtype=np.int64))
        sel = np.concatenate(keep_rows)
        sel.sort()                 # scan order matches the full scan
        return sel.astype(np.int32)

    def search(self, batch: QueryBatch, method,
               setting: ParamSetting | str | None = None, *,
               snapshot: LiveSnapshot | None = None) -> SearchResult:
        """Direct single-method live search (no routing). Args/semantics
        match `FilteredIndex.search`, plus `snapshot=` to read a pinned
        epoch; timings gain `base_s`/`delta_s`/`merge_s`."""
        self._check_open()
        method = self._resolve(method)
        if not isinstance(setting, ParamSetting):
            setting = resolve_setting(method, setting)
        self.pop_stage_timings()
        t0 = time.perf_counter()
        snap = snapshot if snapshot is not None else self.snapshot()
        try:
            ids, raw = self.run_method(method, setting, batch,
                                       snapshot=snap)
            keys = self.keys_of(ids, snapshot=snap)
        finally:
            if snapshot is None:
                snap.release()
        dt = time.perf_counter() - t0
        timings = {"search_s": dt, "total_s": dt}
        timings.update(self.pop_stage_timings())
        return SearchResult(
            ids=ids, distances=exact_distances(raw, ids, batch.vectors),
            decisions=None, timings=timings, keys=keys)

    # ---- routing-feature freshness ---------------------------------------
    def live_stats(self) -> LiveStats:
        """Current live-set summary for the routing features (exact live
        size, live per-label fractions, correction bitmaps)."""
        with self._lock:
            rows = self._delta.rows
            tomb = self._tomb
            n_live = self._base_n + rows - int(tomb.sum())
            base_dead = np.nonzero(tomb[: self._base_n])[0]
            base_bm = (self._base_fx.ds.bitmaps[base_dead]
                       if base_dead.size else
                       np.zeros((0, self._width), np.uint32))
            delta_live = ~tomb[self._base_n: self._base_n + rows]
            delta_bm = self._delta._bm[:rows][delta_live]
            return LiveStats(
                n_live=n_live,
                label_freq=(self._live_label_counts.astype(np.float64)
                            / max(n_live, 1)),
                base_tomb_bitmaps=base_bm,
                delta_bitmaps=delta_bm.copy(),
                base_ds=self.ds)

    # ---- compaction ------------------------------------------------------
    def compact(self, timeout: float | None = None) -> int:
        """Merge base + delta (minus tombstones) into a fresh sealed base
        and swap it in. Blocks until done; returns the new generation.
        See `compact_async` for the non-blocking form."""
        return self.compact_async().result(timeout=timeout)

    def compact_async(self) -> Future:
        """Start (or join) a background compaction.

        The worker thread gathers the surviving rows under a snapshot,
        builds the new group-sorted `ANNDataset` + `FilteredIndex`,
        replays the old base's built method indexes, then swaps
        atomically under the write lock: rows upserted and tombstones
        set *during* the rebuild are carried over (tail rows become the
        new delta; late deletes are translated through the id remap).
        Old-generation readers keep their base until their snapshots
        release. Returns a Future of the new generation; a second call
        while one runs returns the same Future.
        """
        with self._lock:
            self._check_open()
            if self._compacting is not None and not self._compacting.done():
                return self._compacting
            if self._compact_pool is None:
                self._compact_pool = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"compact-{self._name}")
            snap = self.snapshot()
            wal = self._wal
            if wal is not None:
                # barrier record: replay compacts synchronously at this
                # point, reproducing the snapshot's fold exactly
                seq = wal.log_compact(self._generation)
            fut = self._compact_pool.submit(self._compact_job, snap)
            self._compacting = fut
        if wal is not None:
            wal.commit(seq)
        return fut

    def _compact_job(self, snap: LiveSnapshot) -> int:
        try:
            keep_base = ~snap.tombstones[: snap.base_n]
            keep_delta = ~snap.tombstones[snap.base_n:]
            dvec, dbm, _ = snap.delta.host_view(snap.delta_rows)
            base_ds = None if snap.base_n == 0 else self._base_for(snap).ds
            vec_parts, bm_parts = [], []
            if base_ds is not None:
                vec_parts.append(base_ds.vectors[keep_base])
                bm_parts.append(base_ds.bitmaps[keep_base])
            vec_parts.append(dvec[keep_delta])
            bm_parts.append(dbm[keep_delta])
            vectors = np.concatenate(vec_parts) if vec_parts else \
                np.zeros((0, self._dim), np.float32)
            bitmaps = np.concatenate(bm_parts) if bm_parts else \
                np.zeros((0, self._width), np.uint32)
            kept = np.concatenate([
                np.nonzero(keep_base)[0],
                snap.base_n + np.nonzero(keep_delta)[0]])
            new_ds, order = ANNDataset.from_packed(
                self._name, vectors, bitmaps, self._universe,
                return_order=True)
            inv = np.empty(order.size, np.int64)
            inv[order] = np.arange(order.size)
            remap = np.full(snap.n_total, -1, np.int64)
            remap[kept] = inv
            # stable keys follow their rows through the remap
            new_keys = np.empty(new_ds.n, np.int64)
            new_keys[remap[kept]] = snap.keys[kept]
            new_fx = FilteredIndex(new_ds, registry=self._registry,
                                   device=self._placement)
            old_fx = self._base_for(snap) if snap.base_n else None
            if old_fx is not None:
                # graft where the method supports it: splice the old
                # built index through the id remap (sublinear in base
                # size) instead of rebuilding; fall back to a build
                base_remap = remap[: snap.base_n]
                new_from_delta = remap[snap.base_n:]
                new_from_delta = np.sort(
                    new_from_delta[new_from_delta >= 0])
                for m_name, build in old_fx.built_keys():
                    try:
                        m = self._resolve(m_name)
                        grafted = None
                        old_index = old_fx._indexes.get((m_name, build))
                        if self._graft and old_index is not None:
                            grafted = m.graft_index(
                                new_ds, old_index, old_fx.ds, base_remap,
                                new_from_delta, dict(build))
                        if grafted is not None:
                            new_fx.adopt_index(m, build, grafted)
                        else:
                            new_fx.get_index(m_name, build)
                    except KeyError:
                        pass        # method no longer registered
            with self._lock:
                if self._closed:
                    new_fx.close()
                    return self._generation
                rows_now = self._delta.rows
                tvec, tbm, _ = self._delta.host_view(rows_now)
                tail = slice(snap.delta_rows, rows_now)
                new_delta = DeltaSegment(self._dim, self._width,
                                         chunk=self._delta_chunk)
                n_tail = rows_now - snap.delta_rows
                if n_tail:
                    new_delta.append(tvec[tail], tbm[tail])
                new_tomb = np.zeros(new_ds.n + n_tail, bool)
                # deletes that landed after the compaction snapshot
                newly = self._tomb[: snap.n_total] & ~snap.tombstones
                ng = remap[np.nonzero(newly)[0]]
                new_tomb[ng[ng >= 0]] = True
                new_tomb[new_ds.n:] = self._tomb[snap.n_total:
                                                 snap.n_total + n_tail]
                old_gen = self._generation
                old_base = self._base_fx
                self._base_fx = new_fx
                self._base_n = new_ds.n
                self._delta = new_delta
                self._tomb = new_tomb
                self._keys = np.concatenate(
                    [new_keys, self._keys[snap.n_total:
                                          snap.n_total + n_tail]])
                self._key_rows = None
                self._tomb_version += 1
                self._generation = old_gen + 1
                self._features = None       # dataset features went stale
                self._tomb_words_cache = None
                self._last_remap = remap
                if self._readers.get(old_gen):
                    # record the retirement even for an empty base (None)
                    # so pinned snapshots of generation 0 stay resolvable
                    self._retired[old_gen] = old_base
                    old_ds = (old_base.ds if old_base is not None
                              else None)
                    self._retired_leases[old_gen] = \
                        ledger_mod.get_ledger().acquire(
                            "retired_generation", self._name,
                            bytes=(old_ds.vectors.nbytes
                                   + old_ds.bitmaps.nbytes
                                   if old_ds is not None else 0),
                            meta={"generation": int(old_gen)})
                elif old_base is not None:
                    old_base.close()
                return self._generation
        finally:
            snap.release()
            with self._lock:
                self._compacting = None

    # ---- maintenance -----------------------------------------------------
    def export_state(self, snap: LiveSnapshot) -> dict:
        """Full logical state of a pinned snapshot — what a
        `repro.ann.store` checkpoint persists: the sealed base dataset,
        per-row stable keys, the delta rows in insertion order (with
        keys), and the tombstoned ids of the epoch."""
        base_fx = self._base_for(snap) if snap.base_n else None
        dvec, dbm, _ = snap.delta.host_view(snap.delta_rows)
        return {
            "generation": snap.generation,
            "base_ds": None if base_fx is None else base_fx.ds,
            "base_keys": snap.keys[: snap.base_n],
            "delta_vectors": dvec,
            "delta_bitmaps": dbm,
            "delta_keys": snap.keys[snap.base_n:],
            "dead_ids": np.nonzero(snap.tombstones)[0].astype(np.int64),
            "next_key": snap.next_key,
        }

    def last_remap(self) -> np.ndarray | None:
        """Id translation of the most recent `compact()`: `remap[old_id]`
        is the row's id in the new generation, −1 if it was deleted.
        None before the first compaction. Ids are per-generation, so
        clients holding ids across a compaction re-resolve through
        this."""
        return self._last_remap

    def built_keys(self) -> list[tuple]:
        return [] if self._base_fx is None else self._base_fx.built_keys()

    def stats(self) -> dict:
        """State snapshot: generation, live/total row counts, delta and
        tombstone sizes, mirror coverage, compaction status."""
        with self._lock:
            rows = self._delta.rows
            return {
                "dataset": self._name,
                "generation": self._generation,
                "base_n": self._base_n,
                "delta_rows": rows,
                "delta_device_rows": self._delta.device_rows(),
                "tombstones": int(self._tomb.sum()),
                "n_live": self._base_n + rows - int(self._tomb.sum()),
                "tombstone_version": self._tomb_version,
                "next_key": self._next_key,
                "wal_attached": self._wal is not None,
                "compacting": (self._compacting is not None
                               and not self._compacting.done()),
                "retired_generations": sorted(self._retired),
                "fused": self.fused,
                "graft": self._graft,
                "delta_chunk_indexes": len(self._delta._chunk_idx),
                "delta_prune": dict(self._prune_stats),
                "closed": self._closed,
            }


# ---------------------------------------------------------------------------
# sharded live index — round-robin upserts over per-shard delta segments
# ---------------------------------------------------------------------------

class ShardedLiveSnapshot:
    """Consistent cross-shard read epoch: one pinned `LiveSnapshot` per
    shard plus the shard list / bounds / gid maps / global key prefix of
    the epoch, all captured under the sharded index's write lock. Pins
    the epoch (old shard lists survive a compaction swap) until
    `release()`."""

    __slots__ = ("epoch", "shards", "bounds", "snaps", "gmaps", "keys",
                 "next_key", "locs", "base_ds", "_owner", "_released")

    def __init__(self, owner, epoch, shards, bounds, snaps, gmaps,
                 keys, next_key, locs, base_ds):
        self.epoch = epoch
        self.shards = shards
        self.bounds = bounds
        self.snaps = snaps
        self.gmaps = gmaps
        self.keys = keys
        self.next_key = next_key
        self.locs = locs
        self.base_ds = base_ds
        self._owner = owner
        self._released = False

    def release(self) -> None:
        """Unpin this epoch (idempotent, thread-safe)."""
        with self._owner._lock:
            if self._released:
                return
            self._released = True
        for snap in self.snaps:
            snap.release()
        self._owner._release_epoch(self.epoch)

    def __enter__(self) -> "ShardedLiveSnapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class ShardedLiveIndex(_StableKeyMixin, _LabelClockMixin, _StageTimings):
    """Row-sharded live handle: one `LiveFilteredIndex` per shard.

    Upserts round-robin row-by-row across shards; global delta ids are
    assigned in insertion order (`total_base_n + j`) and mapped to
    (shard, local-row) so `delete()` and result globalisation agree.
    `run_method` snapshots every shard under one lock (a consistent
    cross-shard epoch), fans out, globalises per-shard ids, and reduces
    through `merge_topk`. Each shard serves its own read through the
    fused single-launch kernel (the `fused`/`delta_prune_min_rows`
    knobs forward to the per-shard handles), so the sharded handle
    inherits the fused path wholesale. `compact()` rebuilds
    **globally**: all surviving rows merge into one fresh dataset that
    is re-sharded contiguously, so the result is exactly a
    `ShardedFilteredIndex` over the compacted data (rows migrate across
    shard boundaries, so per-shard method indexes are rebuilt, not
    grafted).

    Args mirror `ShardedFilteredIndex` (+ the empty-base form of
    `LiveFilteredIndex` via `name`/`dim`/`universe`).
    """

    def __init__(self, ds: ANNDataset | None = None, n_shards: int = 1, *,
                 name: str | None = None, dim: int | None = None,
                 universe: int | None = None, devices=None, registry=None,
                 parallel: bool = True,
                 delta_chunk: int = DEFAULT_DELTA_CHUNK,
                 base_keys: np.ndarray | None = None,
                 next_key: int | None = None, generation: int = 0,
                 fused: bool = True,
                 delta_prune_min_rows: int | None = None):
        from repro.ann.distributed import shard_bounds, shard_devices

        n_shards = int(n_shards)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1; got {n_shards}")
        if devices is None:
            devices = shard_devices(n_shards)
        self._registry = registry
        self._delta_chunk = int(delta_chunk)
        self._devices = devices
        self._fused = bool(fused)
        self._delta_prune_min_rows = delta_prune_min_rows

        def _shard_kw():
            return dict(registry=registry, delta_chunk=delta_chunk,
                        fused=self._fused,
                        delta_prune_min_rows=self._delta_prune_min_rows)

        self._shard_kw = _shard_kw
        if ds is None:
            if name is None or dim is None or universe is None:
                raise ValueError(
                    "an empty ShardedLiveIndex needs name=, dim= and "
                    "universe= (or pass a base ANNDataset)")
            self._name, self._dim = str(name), int(dim)
            self._universe = int(universe)
            self._base_ds: ANNDataset | None = None
            self.bounds = np.zeros(n_shards + 1, dtype=np.int64)
            self.shards = [
                LiveFilteredIndex.empty(
                    f"{self._name}/shard{i}", self._dim, self._universe,
                    device=devices[i], **_shard_kw())
                for i in range(n_shards)]
        else:
            self._name, self._dim = ds.name, ds.dim
            self._universe = ds.universe
            self._base_ds = ds
            self.bounds = shard_bounds(ds.n, n_shards)
            self.shards = [
                LiveFilteredIndex(
                    ds.row_slice(int(s), int(e),
                                 name=f"{ds.name}/shard{i}"),
                    device=devices[i], **_shard_kw())
                for i, (s, e) in enumerate(zip(self.bounds[:-1],
                                               self.bounds[1:]))]
        self._total_base = 0 if ds is None else ds.n
        self._delta_loc: list[tuple[int, int]] = []  # gid-j -> (shard, row)
        self._shard_gids: list[list[int]] = [[] for _ in self.shards]
        self._gid_arrays: list[np.ndarray] | None = None   # search cache
        self._last_remap: np.ndarray | None = None
        self._next_shard = 0
        if base_keys is None:
            self._keys = np.arange(self._total_base, dtype=np.int64)
        else:
            self._keys = np.asarray(base_keys, dtype=np.int64).copy()
            if self._keys.shape != (self._total_base,):
                raise ValueError(
                    f"base_keys must be [{self._total_base}]; got shape "
                    f"{self._keys.shape}")
        self._next_key = int(next_key) if next_key is not None else \
            (int(self._keys.max()) + 1 if self._total_base else 0)
        self._key_rows: KeyTable | None = None   # key -> gid, built lazily
        self._wal = None
        self._wal_quiet = False               # compaction's internal replay
        self._parallel = bool(parallel) and n_shards > 1
        self._pool = (ThreadPoolExecutor(
            max_workers=n_shards,
            thread_name_prefix=f"live-shard-{self._name}")
            if self._parallel else None)
        self._lock = threading.RLock()
        self._clock_init()
        self._epoch = int(generation)
        self._epoch_readers: dict[int, int] = {}
        self._old_shards: dict[int, list] = {}
        self._compact_pool: ThreadPoolExecutor | None = None
        self._compacting: Future | None = None
        self._features = None
        self._closed = False

    # ---- lifecycle ------------------------------------------------------
    @property
    def fused(self) -> bool:
        """Whether shards serve reads through the fused kernel; setting
        it propagates to every current shard (and to shards created by
        later compactions)."""
        return self._fused

    @fused.setter
    def fused(self, value: bool) -> None:
        self._fused = bool(value)
        for s in self.shards:
            s.fused = self._fused

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def ds(self) -> ANNDataset | None:
        """The current generation's full base dataset (None before the
        first compact of an empty-started index)."""
        return self._base_ds

    @property
    def generation(self) -> int:
        return self._epoch

    @property
    def n_live(self) -> int:
        with self._lock:
            return sum(s.n_live for s in self.shards)

    @property
    def base_n(self) -> int:
        return self._total_base

    @property
    def n_total(self) -> int:
        with self._lock:
            return self._total_base + len(self._delta_loc)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            comp = self._compacting
        if comp is not None:
            try:
                comp.result(timeout=300)
            except BaseException:
                pass
        with self._lock:
            for s in self.shards:
                s.close()
            for old in self._old_shards.values():
                for s in old:
                    s.close()
            self._old_shards.clear()
            self._features = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        if self._compact_pool is not None:
            self._compact_pool.shutdown(wait=True)
            self._compact_pool = None

    def __enter__(self) -> "ShardedLiveIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                f"ShardedLiveIndex({self._name!r}) is closed")

    # ---- write path -----------------------------------------------------
    def upsert(self, vectors, bitmaps, *, keys=None) -> np.ndarray:
        """Append rows, round-robin across shards. Returns [R] global
        ids (current generation); `keys=` as in
        `LiveFilteredIndex.upsert` (stable global keys, auto-assigned
        when omitted)."""
        vectors = np.asarray(vectors, dtype=np.float32)
        bitmaps = np.asarray(bitmaps, dtype=np.uint32)
        if vectors.ndim == 1:
            vectors = vectors[None]
        if bitmaps.ndim == 1:
            bitmaps = bitmaps[None]
        if vectors.ndim != 2 or vectors.shape[1] != self._dim:
            raise ValueError(
                f"upsert vectors must be [R, {self._dim}]; got "
                f"{vectors.shape}")
        width = lb.n_words(self._universe)
        if bitmaps.shape != (vectors.shape[0], width):
            raise ValueError(
                f"upsert bitmaps must be [{vectors.shape[0]}, {width}]; "
                f"got {bitmaps.shape}")
        with self._lock:
            self._check_open()
            n = vectors.shape[0]
            ks = self._claim_keys(keys, n)
            wal = self._wal if not self._wal_quiet else None
            if wal is not None:
                seq = wal.log_upsert(self._epoch, ks, vectors, bitmaps)
            nsh = self.n_shards
            shard_of = (self._next_shard + np.arange(n)) % nsh
            gid0 = self._total_base + len(self._delta_loc)
            d0 = len(self._delta_loc)
            self._delta_loc.extend([None] * n)
            for s in range(nsh):
                rows = np.nonzero(shard_of == s)[0]
                if rows.size == 0:
                    continue
                start_local = self.shards[s]._delta.rows
                self.shards[s].upsert(vectors[rows], bitmaps[rows])
                for off, j in enumerate(rows):
                    self._delta_loc[d0 + int(j)] = (s, start_local + off)
                    self._shard_gids[s].append(gid0 + int(j))
            self._keys = np.concatenate([self._keys, ks])
            self._note_new_keys(ks, gid0)
            self._clock_touch(_label_counts(bitmaps, self._universe))
            self._gid_arrays = None           # searches rebuild lazily
            self._next_shard = (self._next_shard + n) % nsh
            out = np.arange(gid0, gid0 + n, dtype=np.int64)
        if wal is not None:
            wal.commit(seq)                  # durable before acked, off-lock
        return out

    def _row_live(self, rows: np.ndarray) -> np.ndarray:
        """bool[R]: which current-generation global ids are live (mixin
        hook; caller holds the lock)."""
        return np.array([self._gid_live(int(g)) for g in rows], bool)

    def _shard_local(self, gid: int) -> tuple[int, int]:
        """(shard, shard-local id) for a current-generation global id."""
        if gid < self._total_base:
            s = int(np.searchsorted(self.bounds, gid, side="right")) - 1
            return s, gid - int(self.bounds[s])
        s, row = self._delta_loc[gid - self._total_base]
        return s, self.shards[s].base_n + row

    def _gid_live(self, gid: int) -> bool:
        s, lid = self._shard_local(int(gid))
        return not self.shards[s]._tomb[lid]

    def delete(self, ids) -> int:
        """Tombstone global ids; returns the number newly deleted."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        with self._lock:
            self._check_open()
            n_tot = self._total_base + len(self._delta_loc)
            if ids.size and (ids.min() < 0 or ids.max() >= n_tot):
                raise IndexError(
                    f"delete ids must be in [0, {n_tot}); got range "
                    f"[{ids.min()}, {ids.max()}]")
            wal = self._wal if not self._wal_quiet else None
            if wal is not None:
                seq = wal.log_delete(self._epoch, ids)
            per: dict[int, list] = {}
            for gid in ids.tolist():
                s, lid = self._shard_local(gid)
                per.setdefault(s, []).append(lid)
            # stamp before delegating: labels of every named id (a
            # conservative superset — already-dead ids stamp too)
            if ids.size:
                bms = np.concatenate(
                    [self.shards[s]._bitmaps_of(np.asarray(lids, np.int64))
                     for s, lids in per.items()])
                self._clock_touch(_label_counts(bms, self._universe))
            out = sum(self.shards[s].delete(lids)
                      for s, lids in per.items())
        if wal is not None:
            wal.commit(seq)                  # durable before acked, off-lock
        return out

    # stable external keys (`keys_of`/`rows_of`/`delete_keys`/`_claim_keys`)
    # come from _StableKeyMixin (global ids / global keys).

    # ---- durability hook (repro.ann.store) -------------------------------
    def attach_wal(self, wal) -> None:
        """Attach a write-ahead log at the sharded level (global ids and
        keys; per-shard handles stay WAL-less). See
        `LiveFilteredIndex.attach_wal`."""
        with self._lock:
            self._wal = wal

    # ---- read path -------------------------------------------------------
    def _map_shards(self, fn, items):
        if self._pool is not None:
            return list(self._pool.map(fn, items))
        return [fn(it) for it in items]

    def snapshot(self) -> ShardedLiveSnapshot:
        """Pin a consistent cross-shard read epoch (see
        `ShardedLiveSnapshot`); callers must `release()` it."""
        with self._lock:
            self._check_open()
            epoch = self._epoch
            shards = list(self.shards)
            bounds = self.bounds.copy()
            snaps = [s.snapshot() for s in shards]
            if self._gid_arrays is None:      # invalidated by upsert
                self._gid_arrays = [np.asarray(g, dtype=np.int64)
                                    for g in self._shard_gids]
            gmaps = self._gid_arrays
            n_tot = self._total_base + len(self._delta_loc)
            self._epoch_readers[epoch] = \
                self._epoch_readers.get(epoch, 0) + 1
            # keys slice is a view: _keys is reassigned, never mutated
            # in place (see LiveFilteredIndex.snapshot)
            return ShardedLiveSnapshot(self, epoch, shards, bounds,
                                       snaps, gmaps,
                                       self._keys[:n_tot],
                                       self._next_key,
                                       list(self._delta_loc),
                                       self._base_ds)

    def run_method(self, method, setting: ParamSetting, batch: QueryBatch,
                   *, snapshot: ShardedLiveSnapshot | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Raw sharded live execution: consistent per-shard snapshots,
        parallel fan-out, id globalisation (base via shard offsets,
        delta via the insertion-order map), `merge_topk` reduction.
        Pass `snapshot=` to pin several calls to one epoch."""
        self._check_open()
        snap = snapshot if snapshot is not None else self.snapshot()
        shards, bounds = snap.shards, snap.bounds
        snaps, gmaps = snap.snaps, snap.gmaps
        try:
            parent = trace.current()
            times = [0.0] * len(shards)

            def shard_run(jsv):
                # drain the shard's stage timings *in the worker thread*
                # (they live on a thread-local) and return them alongside
                j, sv = jsv
                s0 = time.perf_counter()
                with trace.attach(parent):
                    with trace.span("shard", shard=j):
                        out = sv[0].run_method(method, setting, batch,
                                               snapshot=sv[1])
                times[j] = time.perf_counter() - s0
                return out, sv[0].pop_stage_timings()

            ran = self._map_shards(shard_run,
                                   list(enumerate(zip(shards, snaps))))
            per = [r for r, _ in ran]
            # shards overlap in wall-clock: report the slowest stage
            for key in ("base_s", "delta_s"):
                vals = [t.get(key, 0.0) for _, t in ran]
                if any(vals):
                    self._stage_add({key: max(vals)})
            # per-shard wall seconds + the straggler (the latency the
            # fan-out actually waits for — a sum would hide it)
            self._stage_add({f"shard{j}_s": s
                             for j, s in enumerate(times)})
            self._stage_add({"shard_max_s": max(times)})
            t0 = time.perf_counter()
            parts = []
            for s, ((ids, raw), ssnap) in enumerate(zip(per, snaps)):
                ids = np.asarray(ids, dtype=np.int64)
                raw = np.asarray(raw, dtype=np.float32)
                out = np.full(ids.shape, -1, np.int64)
                is_base = (ids >= 0) & (ids < ssnap.base_n)
                out[is_base] = ids[is_base] + int(bounds[s])
                is_delta = ids >= ssnap.base_n
                if is_delta.any():
                    out[is_delta] = gmaps[s][ids[is_delta] - ssnap.base_n]
                parts.append((out.astype(np.int32), raw))
            gids, graw = merge_candidates(*stack_candidates(parts),
                                          k=batch.k)
            self._stage_add({"merge_s": time.perf_counter() - t0})
            return gids, graw
        finally:
            if snapshot is None:
                snap.release()

    def _release_epoch(self, epoch: int) -> None:
        with self._lock:
            left = self._epoch_readers.get(epoch, 0) - 1
            if left > 0:
                self._epoch_readers[epoch] = left
                return
            self._epoch_readers.pop(epoch, None)
            old = (self._old_shards.pop(epoch, None)
                   if epoch != self._epoch else None)
        if old:
            for s in old:
                s.close()

    def search(self, batch: QueryBatch, method,
               setting: ParamSetting | str | None = None) -> SearchResult:
        """Direct single-method sharded live search (no routing)."""
        self._check_open()
        if isinstance(method, str):
            reg = self._registry or registry_mod.default_registry()
            method = reg.get(method)
        if not isinstance(setting, ParamSetting):
            setting = resolve_setting(method, setting)
        self.pop_stage_timings()
        t0 = time.perf_counter()
        snap = self.snapshot()
        try:
            ids, raw = self.run_method(method, setting, batch,
                                       snapshot=snap)
            keys = self.keys_of(ids, snapshot=snap)
        finally:
            snap.release()
        dt = time.perf_counter() - t0
        timings = {"search_s": dt, "total_s": dt}
        timings.update(self.pop_stage_timings())
        return SearchResult(
            ids=ids, distances=exact_distances(raw, ids, batch.vectors),
            decisions=None, timings=timings, keys=keys)

    def fetch(self, ids, snapshot: ShardedLiveSnapshot | None = None
              ) -> np.ndarray:
        """[R, d] vectors for global result ids (−1 rows come back as
        NaN) — the sharded mirror of `LiveFilteredIndex.fetch`. With a
        snapshot, ids are interpreted in that epoch's global id space."""
        snap = snapshot or self.snapshot()
        try:
            ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
            out = np.full((ids.size, self._dim), np.nan, np.float32)
            base_n = int(snap.bounds[-1])
            base = (ids >= 0) & (ids < base_n)
            if base.any():
                out[base] = snap.base_ds.vectors[ids[base]]
            delta = ids >= base_n
            if delta.any():
                didx = np.nonzero(delta)[0]
                loc = [snap.locs[int(ids[j]) - base_n] for j in didx]
                loc_shard = np.array([l[0] for l in loc], np.int64)
                loc_row = np.array([l[1] for l in loc], np.int64)
                for s, ssnap in enumerate(snap.snaps):
                    mine = loc_shard == s
                    if mine.any():
                        sv, _, _ = ssnap.delta.host_view(ssnap.delta_rows)
                        out[didx[mine]] = sv[loc_row[mine]]
            return out
        finally:
            if snapshot is None:
                snap.release()

    # ---- routing-feature freshness ---------------------------------------
    def live_stats(self) -> LiveStats:
        """Aggregate live-set summary across shards (one consistent
        epoch: shard stats and the base dataset are read under the same
        lock a compaction swap takes)."""
        with self._lock:
            per = [s.live_stats() for s in self.shards]
            base_ds = self._base_ds
        n_live = sum(p.n_live for p in per)
        counts = sum((p.label_freq * p.n_live for p in per),
                     np.zeros(self._universe))
        return LiveStats(
            n_live=n_live,
            label_freq=counts / max(n_live, 1),
            base_tomb_bitmaps=np.concatenate(
                [p.base_tomb_bitmaps for p in per]),
            delta_bitmaps=np.concatenate([p.delta_bitmaps for p in per]),
            base_ds=base_ds)

    # ---- compaction ------------------------------------------------------
    def compact(self, timeout: float | None = None) -> int:
        """Global rebuild + re-shard; blocks, returns the new epoch."""
        return self.compact_async().result(timeout=timeout)

    def compact_async(self) -> Future:
        """Background global compaction: merge every shard's surviving
        base + delta rows (in global id order) into one fresh dataset,
        re-shard it contiguously, swap the shard list atomically, and
        drain old-epoch readers before closing the old shards. Writes
        during the rebuild carry over exactly as in
        `LiveFilteredIndex.compact_async`."""
        with self._lock:
            self._check_open()
            if self._compacting is not None and not self._compacting.done():
                return self._compacting
            if self._compact_pool is None:
                self._compact_pool = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"compact-{self._name}")
            fut = self._compact_pool.submit(self._compact_job)
            self._compacting = fut
            return fut

    def _gather(self, snaps, locs):
        """Surviving rows in global id order + the kept-gid list."""
        vec_parts, bm_parts, kept = [], [], []
        for s, snap in enumerate(snaps):
            if snap.base_n == 0:
                continue
            keep = ~snap.tombstones[: snap.base_n]
            ds = self.shards[s]._base_for(snap).ds
            vec_parts.append(ds.vectors[keep])
            bm_parts.append(ds.bitmaps[keep])
            kept.append(int(self.bounds[s]) + np.nonzero(keep)[0])
        n_delta = len(locs)
        if n_delta:
            dvec = np.zeros((n_delta, self._dim), np.float32)
            dbm = np.zeros((n_delta, lb.n_words(self._universe)), np.uint32)
            alive = np.zeros(n_delta, bool)
            loc_shard = np.array([l[0] for l in locs], np.int64)
            loc_row = np.array([l[1] for l in locs], np.int64)
            for s, snap in enumerate(snaps):
                mine = loc_shard == s
                if not mine.any():
                    continue
                sv, sb, _ = snap.delta.host_view(snap.delta_rows)
                rows = loc_row[mine]
                dvec[mine] = sv[rows]
                dbm[mine] = sb[rows]
                alive[mine] = ~snap.tombstones[snap.base_n + rows]
            vec_parts.append(dvec[alive])
            bm_parts.append(dbm[alive])
            kept.append(self._total_base + np.nonzero(alive)[0])
        if vec_parts:
            return (np.concatenate(vec_parts), np.concatenate(bm_parts),
                    np.concatenate(kept))
        width = lb.n_words(self._universe)
        return (np.zeros((0, self._dim), np.float32),
                np.zeros((0, width), np.uint32),
                np.zeros(0, np.int64))

    def _compact_job(self) -> int:
        from repro.ann.distributed import shard_bounds

        snaps = None
        try:
            with self._lock:
                snaps = [s.snapshot() for s in self.shards]
                locs = list(self._delta_loc)
                old_total = self._total_base + len(locs)
                old_keys = self._keys[:old_total].copy()
                wal = self._wal
                if wal is not None:
                    seq = wal.log_compact(self._epoch)
            if wal is not None:
                wal.commit(seq)
            vectors, bitmaps, kept = self._gather(snaps, locs)
            new_ds, order = ANNDataset.from_packed(
                self._name, vectors, bitmaps, self._universe,
                return_order=True)
            inv = np.empty(order.size, np.int64)
            inv[order] = np.arange(order.size)
            remap = np.full(old_total, -1, np.int64)
            remap[kept] = inv
            new_keys = np.empty(new_ds.n, np.int64)
            new_keys[remap[kept]] = old_keys[kept]
            nsh = self.n_shards
            built = []
            for s in self.shards:
                built.extend(k for k in s.built_keys() if k not in built)
            if new_ds.n >= nsh:
                new_bounds = shard_bounds(new_ds.n, nsh)
                new_shards = [
                    LiveFilteredIndex(
                        new_ds.row_slice(int(a), int(b),
                                         name=f"{self._name}/shard{i}"),
                        device=self._devices[i], **self._shard_kw())
                    for i, (a, b) in enumerate(zip(new_bounds[:-1],
                                                   new_bounds[1:]))]
                new_base: ANNDataset | None = new_ds
            else:
                # fewer surviving rows than shards: restart from empty
                # shards and replay the rows as delta below
                new_bounds = np.zeros(nsh + 1, dtype=np.int64)
                new_shards = [
                    LiveFilteredIndex.empty(
                        f"{self._name}/shard{i}", self._dim,
                        self._universe, device=self._devices[i],
                        **self._shard_kw())
                    for i in range(nsh)]
                new_base = None
            for shard in new_shards:
                if shard._base_fx is None:
                    continue
                for m_name, build in built:
                    try:
                        shard._base_fx.get_index(m_name, build)
                    except KeyError:
                        pass
            with self._lock:
                if self._closed:
                    for s in new_shards:
                        s.close()
                    return self._epoch
                old_shards = self.shards
                old_locs_n = len(locs)
                tail = self._delta_loc[old_locs_n:]
                late_tomb: list[int] = []       # old gids deleted late
                for s, snap in enumerate(snaps):
                    cur = old_shards[s]._tomb
                    newly = cur[: snap.n_total] & ~snap.tombstones
                    lids = np.nonzero(newly)[0]
                    for lid in lids:
                        if lid < snap.base_n:
                            late_tomb.append(int(self.bounds[s]) + int(lid))
                        else:
                            row = int(lid) - snap.base_n
                            gid = self._shard_gids[s][row]
                            late_tomb.append(int(gid))
                # collect tail rows (upserted during the rebuild) in
                # global insertion order, with their current tombstones
                tail_rows = []
                for j, (s, row) in enumerate(tail):
                    shard = old_shards[s]
                    vec = shard._delta._vec[row]
                    bm = shard._delta._bm[row]
                    dead = bool(shard._tomb[shard.base_n + row])
                    tail_rows.append((vec, bm, dead))
                tail_keys = self._keys[old_total: old_total + len(tail)]
                old_epoch = self._epoch
                self.shards = new_shards
                self.bounds = new_bounds
                self._base_ds = new_base
                self._total_base = new_ds.n if new_base is not None else 0
                self._delta_loc = []
                self._shard_gids = [[] for _ in new_shards]
                self._gid_arrays = None
                self._next_shard = 0
                self._keys = (new_keys if new_base is not None
                              else np.zeros(0, np.int64))
                self._key_rows = None
                self._epoch = old_epoch + 1
                self._last_remap = remap
                self._features = None
                # replay: rows that didn't make the snapshot (and every
                # row when the base fell below the shard count), carrying
                # their stable keys; the WAL stays quiet — these rows'
                # original upsert/delete records already cover them
                replay = []
                if new_base is None and new_ds.n:
                    replay.append((new_ds.vectors, new_ds.bitmaps, None,
                                   new_keys))
                if tail_rows:
                    replay.append((
                        np.stack([t[0] for t in tail_rows]),
                        np.stack([t[1] for t in tail_rows]),
                        np.array([t[2] for t in tail_rows], bool),
                        tail_keys))
                self._wal_quiet = True
                try:
                    for vecs, bms, dead, ks in replay:
                        gids = self.upsert(vecs, bms, keys=ks)
                        if dead is not None and dead.any():
                            self.delete(gids[dead])
                    if late_tomb:
                        ng = remap[np.asarray(late_tomb, np.int64)]
                        ng = ng[(ng >= 0) & (ng < self._total_base
                                             + len(self._delta_loc))]
                        if ng.size:
                            self.delete(ng)
                finally:
                    self._wal_quiet = False
                if self._epoch_readers.get(old_epoch):
                    self._old_shards[old_epoch] = old_shards
                else:
                    for s in old_shards:
                        s.close()
                return self._epoch
        finally:
            if snaps is not None:
                for snap in snaps:
                    snap.release()
            with self._lock:
                self._compacting = None

    # ---- maintenance -----------------------------------------------------
    def export_state(self, snap: ShardedLiveSnapshot) -> dict:
        """Full logical state of a pinned cross-shard epoch, in *global*
        id order — the same contract as `LiveFilteredIndex.export_state`
        (what a `repro.ann.store` checkpoint persists)."""
        base_n = int(snap.bounds[-1])
        n_delta = len(snap.locs)
        width = lb.n_words(self._universe)
        dvec = np.zeros((n_delta, self._dim), np.float32)
        dbm = np.zeros((n_delta, width), np.uint32)
        delta_dead = np.zeros(n_delta, bool)
        if n_delta:
            loc_shard = np.array([l[0] for l in snap.locs], np.int64)
            loc_row = np.array([l[1] for l in snap.locs], np.int64)
            for s, ssnap in enumerate(snap.snaps):
                mine = loc_shard == s
                if not mine.any():
                    continue
                sv, sb, _ = ssnap.delta.host_view(ssnap.delta_rows)
                rows = loc_row[mine]
                dvec[mine] = sv[rows]
                dbm[mine] = sb[rows]
                delta_dead[mine] = ssnap.tombstones[ssnap.base_n + rows]
        dead = [base_n + np.nonzero(delta_dead)[0]]
        for s, ssnap in enumerate(snap.snaps):
            lids = np.nonzero(ssnap.tombstones[: ssnap.base_n])[0]
            if lids.size:
                dead.append(int(snap.bounds[s]) + lids)
        return {
            "generation": snap.epoch,
            "base_ds": snap.base_ds,
            "base_keys": snap.keys[:base_n],
            "delta_vectors": dvec,
            "delta_bitmaps": dbm,
            "delta_keys": snap.keys[base_n:],
            "dead_ids": np.sort(np.concatenate(dead)).astype(np.int64),
            "next_key": snap.next_key,
        }

    def last_remap(self) -> np.ndarray | None:
        """Global-id translation of the most recent `compact()` (see
        `LiveFilteredIndex.last_remap`)."""
        return self._last_remap

    def stats(self) -> dict:
        with self._lock:
            return {
                "dataset": self._name,
                "generation": self._epoch,
                "n_shards": self.n_shards,
                "base_n": self._total_base,
                "delta_rows": len(self._delta_loc),
                "n_live": sum(s.n_live for s in self.shards),
                "next_key": self._next_key,
                "wal_attached": self._wal is not None,
                "compacting": (self._compacting is not None
                               and not self._compacting.done()),
                "closed": self._closed,
                "shards": [s.stats() for s in self.shards],
            }
