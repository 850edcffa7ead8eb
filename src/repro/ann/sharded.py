"""`ShardedFilteredIndex` — one dataset row-partitioned across devices.

This is the execution layer that scales the serving API past one device:
the dataset is split into contiguous row shards (`ANNDataset.row_slice`),
each shard is an ordinary owned `FilteredIndex` pinned to its own device
(round-robin over the host's jax devices — `distributed.shard_devices`),
and a batched search runs every shard before a cross-shard top-k merge
(`ops.merge_topk`, the VMEM-accumulated Pallas reduction).

The handle exposes the same `run_method`/`search`/`close` surface as
`FilteredIndex`, so `RouterService` (and its `ShardedRouterService`
subclass) dispatches through it unchanged: a batch is routed **once** —
one fused MLP forward over features whose selectivity counts are summed
over the shards' own devices (`features.device_match_counts`) — and only
the chosen (method, ps) execution fans out per shard. Shard-local ids are
globalised with each shard's row offset (row slices preserve row order),
which is what lets the merge treat per-shard candidates as disjoint. No
device holds more than its own shard.

How a (method, ps) group fans out follows from the method:

* a method with a search that stays on the device (one that overrides
  `Method.search_resident`: the exact scan) is issued on every shard
  before the host waits on any; each shard's [Q, k] candidates stay on
  its device, only they (Q·k·8 bytes a shard) are copied to the first
  shard's device, and one program there globalises and merges them:
  the host waits once;
* any other method runs `FilteredIndex.run_method` on every shard over
  a thread pool (each shard waits on its own launches) and the host
  merges the numpy candidates through the same kernel.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import trace
from repro.ann.dataset import ANNDataset
from repro.ann.distributed import shard_bounds, shard_devices
from repro.ann.engine import (ParamSetting, has_resident_search,
                              pop_stage_timings, resolve_setting, stage_add)
from repro.ann.index import (FilteredIndex, QueryBatch, SearchResult,
                             exact_distances)


def stack_candidates(parts) -> tuple[np.ndarray, np.ndarray]:
    """Stack per-segment (ids, raw) pairs into [S, Q, K] arrays.

    Segments may disagree on their candidate width K (the live delta
    path overfetches by its tombstone count); narrower segments are
    padded with −1 ids / +inf scores, which `ops.merge_topk` treats as
    invalid slots. Ids must already be global (disjoint across parts).
    """
    kmax = max(i.shape[1] for i, _ in parts)
    ids, raws = [], []
    for i, r in parts:
        i = np.asarray(i, dtype=np.int32)
        r = np.asarray(r, dtype=np.float32)
        pad = kmax - i.shape[1]
        if pad:
            i = np.concatenate(
                [i, np.full((i.shape[0], pad), -1, np.int32)], axis=1)
            r = np.concatenate(
                [r, np.full((r.shape[0], pad), np.inf, np.float32)], axis=1)
        ids.append(i)
        raws.append(r)
    return np.stack(ids), np.stack(raws)


def merge_candidates(ids: np.ndarray, raw: np.ndarray,
                     k: int) -> tuple[np.ndarray, np.ndarray]:
    """Reduce [S, Q, K] globalised candidates to the global top-k through
    the `ops.merge_topk` kernel. Returns ([Q, k] i32 ids with −1 pad,
    [Q, k] f32 scores with +inf at −1)."""
    from repro.kernels import ops

    gids, graw = ops.merge_topk(jnp.asarray(ids), jnp.asarray(raw), k=k)
    return np.asarray(gids), np.asarray(graw)


@partial(jax.jit, static_argnames="k")
def _globalise_merge(ids, raw, offsets, *, k: int):
    """[S] per-shard ([Q, K] local ids, [Q, K] scores), on one device ->
    the global top-k, ids offset by each shard's first row."""
    from repro.kernels import ops

    ids, raw = jnp.stack(ids), jnp.stack(raw)
    ids = jnp.where(ids >= 0, ids + offsets[:, None, None], -1)
    return ops.merge_topk(ids, raw, k=k)


def merge_resident(parts, offsets: np.ndarray, k: int, device):
    """Copy each shard's candidates (jax arrays on its own device, or
    numpy) to `device` and globalise and merge them there in one program;
    the host waits once, for the merged answer. Returns numpy
    ([Q, k] int32 global ids with −1 pad, [Q, k] float32 scores, +inf at
    −1)."""
    ids = [jax.device_put(i, device) for i, _ in parts]
    raw = [jax.device_put(r, device) for _, r in parts]
    off = jax.device_put(np.asarray(offsets, dtype=np.int32), device)
    gids, graw = _globalise_merge(ids, raw, off, k=k)
    return np.asarray(gids), np.asarray(graw)


class ShardedFilteredIndex:
    """Row-sharded serving handle: one `FilteredIndex` per shard plus the
    cross-shard merge. API-compatible with `FilteredIndex` wherever the
    serving layer touches it (`ds`, `run_method`, `search`, lifecycle).

    Args:
        ds: the full dataset. Row-partitioned; the parent is kept for
            the host-side routing features (host arrays are shared views
            — no vector copy); nothing of it goes to a device whole.
        n_shards: number of row shards (ignored when `bounds` is given).
        bounds: optional explicit shard boundaries [S+1] (ragged shards);
            defaults to `distributed.shard_bounds(ds.n, n_shards)`.
        devices: optional list of jax devices, one per shard; defaults to
            round-robin over the host's devices (all shards land on the
            single device of a CPU host — still correct, just serial).
        registry: optional `MethodRegistry` forwarded to every shard.
        parallel: fan a non-resident method's shard execution out over
            a thread pool (jax releases the GIL during device compute, so
            per-device shards overlap). Serial when False or with a
            single shard.

    Raises:
        ValueError: if bounds are not a strictly increasing cover of
            [0, ds.n], or n_shards is out of range.
    """

    def __init__(self, ds: ANNDataset, n_shards: int = 1, *,
                 bounds=None, devices=None, registry=None,
                 parallel: bool = True):
        if bounds is None:
            bounds = shard_bounds(ds.n, n_shards)
        bounds = np.asarray(bounds, dtype=np.int64)
        if bounds.ndim != 1 or bounds.size < 2 or bounds[0] != 0 \
                or bounds[-1] != ds.n or np.any(np.diff(bounds) <= 0):
            raise ValueError(
                f"shard bounds must strictly increase from 0 to n={ds.n}; "
                f"got {bounds.tolist()}")
        self.ds = ds
        self.bounds = bounds
        if devices is None:
            devices = shard_devices(bounds.size - 1)
        self.devices = list(devices[:bounds.size - 1])
        self.shards = [
            FilteredIndex(ds.row_slice(int(s), int(e),
                                       name=f"{ds.name}/shard{i}"),
                          registry=registry, device=devices[i])
            for i, (s, e) in enumerate(zip(bounds[:-1], bounds[1:]))]
        self._registry = registry
        self._parallel = bool(parallel) and len(self.shards) > 1
        self._pool = (ThreadPoolExecutor(
            max_workers=len(self.shards),
            thread_name_prefix=f"shard-{ds.name}") if self._parallel
            else None)
        self._features = None        # routing-feature cache (full dataset)
        self._closed = False

    # ---- lifecycle ------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """Close every shard handle and shut the dispatch pool down.
        Idempotent."""
        for fx in self.shards:
            fx.close()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self._features = None
        self._closed = True

    def __enter__(self) -> "ShardedFilteredIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                f"ShardedFilteredIndex({self.ds.name!r}) is closed")

    # ---- search ----------------------------------------------------------
    def _resolve_method(self, method):
        if isinstance(method, str):
            from repro.ann import registry as registry_mod

            return (self._registry
                    or registry_mod.default_registry()).get(method)
        return method

    def run_method(self, method, setting: ParamSetting,
                   batch: QueryBatch) -> tuple[np.ndarray, np.ndarray]:
        """Raw sharded execution of one (method, setting) over the batch.

        Every shard runs `FilteredIndex.run_method` on its own tensors,
        shard-local ids are globalised with the shard row offsets, and
        the [S, Q, k] candidates reduce to the global top-k through
        `ops.merge_topk` (see the module docstring for the two ways a
        group fans out; the method decides).

        Returns: ([Q, k] int32 global ids with −1 pad, [Q, k] float32
        ranking scores ‖v‖² − 2·q·v with +inf at −1) — identical contract
        to `FilteredIndex.run_method`, so the serving layer can't tell
        the difference.

        Under an active trace the group is one `fanout` span, from the
        first shard's launch until the merged answer is on the host,
        with a `merge` child. Seconds accumulate on the calling thread's
        stage slate (`shard{j}_s`, `shard_max_s` — the straggler that
        bounds fan-out latency — and `merge_s`), drained by
        `pop_stage_timings()`; a resident fan-out waits once, so each of
        its shards reads the seconds until the merged answer was in hand.
        Raises: RuntimeError if closed; ValueError on shape mismatch.
        """
        self._check_open()
        method = self._resolve_method(method)
        with trace.span("fanout", shards=self.n_shards, q=batch.q):
            if has_resident_search(method):
                return self._fan_out_resident(method, setting, batch)
            return self._fan_out_host(method, setting, batch)

    def _fan_out_resident(self, method, setting, batch):
        t0 = time.perf_counter()
        parts = [fx.run_method(method, setting, batch, resident=True)
                 for fx in self.shards]
        t_merge = time.perf_counter()
        rows = parts[0][0].shape[0]
        with trace.span("merge", shards=len(parts)):
            with trace.launch(rows, rows - batch.q):
                ids, raw = merge_resident(parts, self.bounds[:-1], batch.k,
                                          self.devices[0])
        t1 = time.perf_counter()
        for j in range(len(parts)):
            stage_add(f"shard{j}_s", t1 - t0)
        stage_add("shard_max_s", t1 - t0)
        stage_add("merge_s", t1 - t_merge)
        return ids[:batch.q], raw[:batch.q]

    def _fan_out_host(self, method, setting, batch):
        parent = trace.current()
        times = [0.0] * len(self.shards)

        def shard_run(jfx):
            j, fx = jfx
            s0 = time.perf_counter()
            with trace.attach(parent):
                with trace.span("shard", shard=j):
                    out = fx.run_method(method, setting, batch)
            times[j] = time.perf_counter() - s0
            return out

        if self._pool is not None:
            per = list(self._pool.map(shard_run, enumerate(self.shards)))
        else:
            per = [shard_run(jfx) for jfx in enumerate(self.shards)]
        offs = self.bounds[:-1]
        parts = [(np.where(np.asarray(i) >= 0,
                           np.asarray(i) + np.int32(off), -1), r)
                 for (i, r), off in zip(per, offs)]
        t_merge = time.perf_counter()
        with trace.span("merge", shards=len(per)):
            ids, raw = stack_candidates(parts)
            out = merge_candidates(ids, raw, batch.k)
        for j, s in enumerate(times):
            stage_add(f"shard{j}_s", s)
        stage_add("shard_max_s", max(times))
        stage_add("merge_s", time.perf_counter() - t_merge)
        return out

    def pop_stage_timings(self) -> dict[str, float]:
        """Drain the calling thread's per-stage timings (`shard{j}_s`
        fan-out seconds, `shard_max_s` straggler, `merge_s`)."""
        return pop_stage_timings()

    def search(self, batch: QueryBatch, method,
               setting: ParamSetting | str | None = None) -> SearchResult:
        """Direct single-method sharded search (no routing).

        Args/semantics match `FilteredIndex.search`; `search_s` covers
        the whole fan-out + cross-shard merge.
        """
        self._check_open()
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

    @property
    def generation(self) -> int:
        """Sealed sharded indexes never remap rows — generation is a
        constant 0, mirroring `FilteredIndex` so telemetry events carry
        a uniform generation field across handle types."""
        return 0

    # ---- stable external keys -------------------------------------------
    def keys_of(self, ids) -> np.ndarray:
        """Stable external keys for global result ids: identity on a
        sealed sharded index (rows never remap), −1 stays −1 — same
        surface as the live handles."""
        ids = np.asarray(ids, dtype=np.int64)
        return np.where(ids >= 0, ids, np.int64(-1))

    def label_clock(self, labels=None) -> int:
        """Sealed data never changes — constant 0, mirroring the live
        handles' per-label write clock (see `repro.ann.cache`)."""
        return 0

    # ---- maintenance -----------------------------------------------------
    def evict(self, method_name: str | None = None) -> int:
        """Drop built indexes on every shard; returns total evictions."""
        return sum(fx.evict(method_name) for fx in self.shards)

    def stats(self) -> dict:
        """Aggregate + per-shard state snapshot."""
        return {
            "dataset": self.ds.name,
            "n": self.ds.n,
            "n_shards": self.n_shards,
            "shard_rows": np.diff(self.bounds).tolist(),
            "parallel": self._pool is not None,
            "features_cached": self._features is not None,
            "closed": self._closed,
            "shards": [fx.stats() for fx in self.shards],
        }
