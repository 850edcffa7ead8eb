"""`FilteredIndex` — the owned serving handle over one dataset.

This replaces the three module-global caches that used to live in
`repro.ann.engine` (`_DEVICE_CACHE`, `_ARRAY_CACHE`, `_INDEX_CACHE`) with
state owned by an explicit handle:

* device tensors (vectors / norms / bitmaps / group tables) are built
  lazily on first use and freed by `close()`;
* per-(method, build-params) indexes are built on demand and individually
  evictable (`evict`);
* the host-array upload cache (`as_device`) is per-handle, so two
  indexes over different datasets can never serve each other's tensors.

Alongside it live the typed request/result objects the serving surface
speaks: `QueryBatch` (vectors + bitmaps + predicate + k, validated on
construction) and `SearchResult` (ids, exact distances, per-query routing
decisions, stage timings). `repro.ann.service.RouterService` binds an
`MLRouter` to a `FilteredIndex` and routes between methods; a bare
`FilteredIndex.search` runs one named method directly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import numpy as np

from repro.ann import registry as registry_mod
from repro.ann.dataset import ANNDataset
from repro.ann.engine import (DeviceData, Method, ParamSetting,
                              resolve_setting)
from repro.ann.predicates import Predicate


class RoutingDecision(NamedTuple):
    """Per-query routing outcome. Tuple-compatible: compares and unpacks
    exactly like the legacy `(method, ps_id)` pairs."""
    method: str
    ps_id: str | None


@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """A validated batch of filtered queries of one predicate type.

    Args:
        vectors: [Q, d] query embeddings (coerced to float32).
        bitmaps: [Q, W] packed query label sets (coerced to uint32).
        pred: the batch's `Predicate` (or its int value).
        k: result width per query (>= 1).
    Raises:
        ValueError: on construction, for non-2-D vectors/bitmaps, a Q
            mismatch between them, an empty batch, or k < 1.
    """
    vectors: np.ndarray       # [Q, d] float32
    bitmaps: np.ndarray       # [Q, W] uint32 packed label sets
    pred: Predicate
    k: int = 10

    def __post_init__(self):
        vectors = np.asarray(self.vectors, dtype=np.float32)
        bitmaps = np.asarray(self.bitmaps, dtype=np.uint32)
        if vectors.ndim != 2:
            raise ValueError(
                f"QueryBatch.vectors must be [Q, d]; got shape "
                f"{vectors.shape}")
        if bitmaps.ndim != 2:
            raise ValueError(
                f"QueryBatch.bitmaps must be [Q, W]; got shape "
                f"{bitmaps.shape}")
        if vectors.shape[0] != bitmaps.shape[0]:
            raise ValueError(
                f"QueryBatch vectors/bitmaps disagree on Q: "
                f"{vectors.shape[0]} vs {bitmaps.shape[0]}")
        if vectors.shape[0] == 0:
            raise ValueError("QueryBatch must contain at least one query")
        if int(self.k) < 1:
            raise ValueError(f"QueryBatch.k must be >= 1; got {self.k}")
        object.__setattr__(self, "vectors", vectors)
        object.__setattr__(self, "bitmaps", bitmaps)
        object.__setattr__(self, "pred", Predicate(self.pred))
        object.__setattr__(self, "k", int(self.k))

    @property
    def q(self) -> int:
        return int(self.vectors.shape[0])

    @property
    def dim(self) -> int:
        return int(self.vectors.shape[1])

    def take(self, idxs) -> "QueryBatch":
        """Sub-batch at the given query indices (for group dispatch)."""
        idxs = np.asarray(idxs)
        return QueryBatch(self.vectors[idxs], self.bitmaps[idxs],
                          self.pred, self.k)

    @staticmethod
    def from_queryset(qs, k: int | None = None) -> "QueryBatch":
        """Adapt a `repro.ann.dataset.QuerySet`."""
        return QueryBatch(qs.vectors, qs.bitmaps, qs.pred,
                          qs.k if k is None else k)


@dataclasses.dataclass
class SearchResult:
    """Typed result batch.

    * `ids` — [Q, k] int32 base ids, −1 padded;
    * `distances` — [Q, k] float32 **exact squared-L2** distances for the
      returned ids (NaN where the id is −1), so callers never recompute
      them from raw vectors;
    * `decisions` — per-query `RoutingDecision` (None for direct
      single-method searches);
    * `timings` — stage wall-clock seconds (`route_s`, `search_s`,
      `total_s`; live indexes additionally report `base_s`, `delta_s`
      and `merge_s` for the base scan / delta scan / candidate fold);
    * `keys` — [Q, k] int64 **stable external keys** for the returned
      rows (−1 pad). Unlike `ids` — which are per-generation row ids a
      live index remaps at every compaction — keys survive
      `compact()` and a `repro.ann.store` save/reopen, so clients
      should hold on to these. For sealed indexes keys equal the row
      ids.
    * `cache` — per-query serving provenance when a
      `repro.ann.cache.SemanticResultCache` fronted the request: a
      [Q] list of ``"exact"`` / ``"semantic"`` / ``None`` (None for a
      query that missed and was searched). None (default) means no
      cache was involved.
    """
    ids: np.ndarray
    distances: np.ndarray
    decisions: list[RoutingDecision] | None = None
    timings: dict = dataclasses.field(default_factory=dict)
    keys: np.ndarray | None = None
    cache: list | None = None

    @property
    def q(self) -> int:
        return int(self.ids.shape[0])

    @property
    def k(self) -> int:
        return int(self.ids.shape[1])


def exact_distances(raw_scores: np.ndarray, ids: np.ndarray,
                    qvecs: np.ndarray) -> np.ndarray:
    """Ranking scores (‖v‖² − 2·q·v) -> exact squared-L2, NaN at −1 pad."""
    qn = np.sum(np.asarray(qvecs, dtype=np.float32) ** 2, axis=1)
    d = np.asarray(raw_scores, dtype=np.float32) + qn[:, None]
    d = np.maximum(d, 0.0)          # float round-off can dip below zero
    return np.where(ids >= 0, d, np.float32(np.nan)).astype(np.float32)


class FilteredIndex:
    """Owned per-dataset serving handle.

    Owns every piece of per-dataset serving state and ties it to one
    lifecycle: device-resident tensors (`device`), the host→device upload
    cache (`as_device`), per-(method, build-params) offline indexes
    (`get_index`), and the per-dataset routing features
    (`repro.core.features.dataset_features` caches onto the handle).
    `close()` — or exiting the context manager — frees all of it.

    Args:
        ds: the dataset this handle serves.
        registry: optional `MethodRegistry` overriding the default when
            method names are resolved (`search("prefilter")` etc.).
        device: optional `jax.Device` to pin this handle's tensors to —
            the placement hook `ShardedFilteredIndex` uses to spread
            shards across a multi-device host. Default: jax's default
            device.
    """

    def __init__(self, ds: ANNDataset, *, registry=None, device=None):
        self.ds = ds
        self._registry = registry
        self._placement = device
        self._device: DeviceData | None = None
        self._indexes: dict = {}     # (method_name, build_tuple) -> index
        self._arrays: dict = {}      # id(host_array) -> (host, device)
        self._features = None        # repro.core.features.DatasetFeatures
        self._closed = False

    # ---- lifecycle ------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """Drop every owned device tensor, upload, built index, and cached
        feature state. Subsequent use raises RuntimeError; closing twice
        is a no-op."""
        self._device = None
        self._indexes.clear()
        self._arrays.clear()
        self._features = None
        self._closed = True

    def __enter__(self) -> "FilteredIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                f"FilteredIndex({self.ds.name!r}) is closed")

    # ---- owned device state ---------------------------------------------
    @property
    def device(self) -> DeviceData:
        """Device-resident dataset tensors (built lazily, owned; placed on
        this handle's pinned device when one was given).

        Raises RuntimeError if the handle is closed."""
        self._check_open()
        if self._device is None:
            with self._device_scope():
                self._device = _build_device_data(self.ds)
        return self._device

    def _device_scope(self):
        """Context placing uploads on the pinned device (no-op if unset)."""
        import contextlib

        import jax

        if self._placement is None:
            return contextlib.nullcontext()
        return jax.default_device(self._placement)

    def as_device(self, x):
        """Cached np→device upload, owned by this handle.

        Args:
            x: a host array. Keyed by identity — re-uploading the same
               array object is free; a new object with equal contents
               uploads again.
        Returns: the device-resident `jax.Array`.
        Raises: RuntimeError if the handle is closed.
        """
        import jax.numpy as jnp

        self._check_open()
        key = id(x)
        hit = self._arrays.get(key)
        if hit is None or hit[0] is not x:
            with self._device_scope():
                hit = (x, jnp.asarray(x))
            self._arrays[key] = hit
        return hit[1]

    # ---- built indexes ---------------------------------------------------
    def _resolve_method(self, method) -> Method:
        if isinstance(method, str):
            reg = self._registry or registry_mod.default_registry()
            return reg.get(method)
        return method

    def get_index(self, method, build_params: tuple | dict | None = None):
        """Built (offline) index for (method, build-params), cached.

        Args:
            method: a `Method` instance or registered method name.
            build_params: the method's build-parameter dict (or its
                sorted-items tuple); None means no build parameters.
        Returns: the method's opaque built-index object.
        Raises: RuntimeError if closed; KeyError for an unknown name.
        """
        self._check_open()
        method = self._resolve_method(method)
        if build_params is None:
            build_params = ()
        if isinstance(build_params, dict):
            build_params = tuple(sorted(build_params.items()))
        key = (method.name, build_params)
        if key not in self._indexes:
            self._indexes[key] = method.build(self.ds, dict(build_params))
        return self._indexes[key]

    def adopt_index(self, method, build_params, index) -> None:
        """Install an already-built index under (method, build-params) —
        the deserialization hook `repro.ann.store` uses to rebuild
        `built_keys()` on load without re-running the offline build.
        Key normalisation matches `get_index`."""
        self._check_open()
        method = self._resolve_method(method)
        if build_params is None:
            build_params = ()
        if isinstance(build_params, dict):
            build_params = tuple(sorted(build_params.items()))
        self._indexes[(method.name, tuple(build_params))] = index

    def built_keys(self) -> list[tuple]:
        """Keys of every built index: (method_name, build_params_tuple).
        `LiveFilteredIndex.compact` replays these against the new base so
        a compaction swap doesn't cold-start the serving methods."""
        return list(self._indexes.keys())

    # ---- stable external keys -------------------------------------------
    @property
    def generation(self) -> int:
        """A sealed index never remaps rows — constant 0, mirroring the
        live handles so telemetry events carry a uniform field."""
        return 0

    def keys_of(self, ids) -> np.ndarray:
        """Stable external keys for result ids (−1 stays −1). A sealed
        `FilteredIndex` never remaps its rows, so keys are the row ids —
        this mirror of `LiveFilteredIndex.keys_of` keeps the serving
        surface uniform across sealed and live handles."""
        ids = np.asarray(ids, dtype=np.int64)
        return np.where(ids >= 0, ids, np.int64(-1))

    def label_clock(self, labels=None) -> int:
        """Sealed data never changes — constant 0, mirroring the live
        handles' per-label write clock so cache invalidation
        (`repro.ann.cache`) reads one uniform surface."""
        return 0

    def evict(self, method_name: str | None = None) -> int:
        """Drop built indexes (all of one method, or every method).
        Returns the number of evicted entries."""
        keys = [k for k in self._indexes
                if method_name is None or k[0] == method_name]
        for k in keys:
            del self._indexes[k]
        return len(keys)

    def stats(self) -> dict:
        """Snapshot of the handle's owned state (for logging/debugging)."""
        return {
            "dataset": self.ds.name,
            "n": self.ds.n,
            "device_resident": self._device is not None,
            "built_indexes": sorted(k[0] for k in self._indexes),
            "cached_uploads": len(self._arrays),
            "features_cached": self._features is not None,
            "closed": self._closed,
        }

    # ---- search ----------------------------------------------------------
    def run_method(self, method, setting: ParamSetting,
                   batch: QueryBatch) -> tuple[np.ndarray, np.ndarray]:
        """Raw single-method execution: ([Q, k] ids, [Q, k] ranking
        scores ‖v‖²−2·q·v). Building blocks for `search` and the bench
        harness; most callers want `search`/`RouterService` instead."""
        if batch.bitmaps.shape[1] != self.ds.bitmaps.shape[1]:
            raise ValueError(
                f"QueryBatch bitmap width {batch.bitmaps.shape[1]} does "
                f"not match dataset width {self.ds.bitmaps.shape[1]}")
        if batch.dim != self.ds.dim:
            raise ValueError(
                f"QueryBatch vector dim {batch.dim} does not match "
                f"dataset dim {self.ds.dim}")
        method = self._resolve_method(method)
        index = self.get_index(method, setting.build)
        return method.search(self, index, batch.vectors, batch.bitmaps,
                             batch.pred, batch.k, setting.search_dict)

    def search(self, batch: QueryBatch, method,
               setting: ParamSetting | str | None = None) -> SearchResult:
        """Direct single-method search (no routing).

        Args:
            batch: the validated query batch.
            method: a `Method` instance or registered method name.
            setting: a `ParamSetting`, a ps_id string, or None (the
                method's max-budget setting).
        Returns: a `SearchResult` with [Q, k] ids + exact squared-L2
            distances (`decisions` is None — no routing happened).
        Raises: RuntimeError if closed; ValueError on dataset/batch
            shape mismatch; KeyError for an unknown method name.
        """
        method = self._resolve_method(method)
        if not isinstance(setting, ParamSetting):
            setting = resolve_setting(method, setting)
        t0 = time.perf_counter()
        ids, raw = self.run_method(method, setting, batch)
        dt = time.perf_counter() - t0
        return SearchResult(
            ids=ids, distances=exact_distances(raw, ids, batch.vectors),
            decisions=None, timings={"search_s": dt, "total_s": dt},
            keys=self.keys_of(ids))


def _build_device_data(ds: ANNDataset) -> DeviceData:
    import jax.numpy as jnp

    g = ds.n_groups
    cent = np.zeros((g, ds.dim), dtype=np.float32)
    for j in range(g):
        s, l = int(ds.group_start[j]), int(ds.group_size[j])
        cent[j] = ds.vectors[s:s + l].mean(0)
    bitmaps = jnp.asarray(ds.bitmaps)
    return DeviceData(
        vectors=jnp.asarray(ds.vectors),
        norms=jnp.asarray(ds.norms_sq),
        bitmaps=bitmaps,
        bitmaps_wm=bitmaps.T,
        group_bitmaps=jnp.asarray(ds.group_bitmaps),
        group_start=jnp.asarray(ds.group_start),
        group_size=jnp.asarray(ds.group_size),
        group_centroids=jnp.asarray(cent),
        group_cnorms=jnp.asarray((cent ** 2).sum(1).astype(np.float32)),
    )


# ---------------------------------------------------------------------------
# default pool — backs the one-PR-cycle deprecation shims in engine.py and
# callers that pass bare ANNDataset objects. Each entry is an ordinary
# owned FilteredIndex; `clear_pool` closes them all.
# ---------------------------------------------------------------------------

_POOL: dict[tuple, FilteredIndex] = {}


def default_index(ds: ANNDataset) -> FilteredIndex:
    """Process-wide shared handle for `ds` (keyed by content identity)."""
    key = ds.cache_key()
    fx = _POOL.get(key)
    if fx is None or fx.closed:
        fx = FilteredIndex(ds)
        _POOL[key] = fx
    return fx


def as_index(obj) -> FilteredIndex:
    """Coerce an ANNDataset to its pooled handle; pass handles through."""
    return obj if isinstance(obj, FilteredIndex) else default_index(obj)


def clear_pool() -> None:
    """Close and drop every pooled handle."""
    for fx in _POOL.values():
        fx.close()
    _POOL.clear()
