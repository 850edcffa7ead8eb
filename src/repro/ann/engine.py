"""Shared execution machinery for filtered-ANN methods.

* `DeviceData` — per-dataset device-resident tensors (vectors, norms,
  bitmaps, group tables). Ownership lives in `repro.ann.index.
  FilteredIndex` (the PR-2 `device_data`/`as_device`/`get_index`
  deprecation shims are gone; see docs/serving.md for the migration).
* word-looped predicate masks that avoid materialising `[Q, N, W]`
  temporaries (predicate type is a *traced* scalar so one compiled
  executable serves all three predicates).
* query chunking: every method's jitted inner function runs on fixed-size
  query chunks (static shapes), with host-side padding of the tail chunk.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann.dataset import ANNDataset
from repro.ann.predicates import Predicate

DEFAULT_QCHUNK = 64
# bytes of gathered f32 candidate vectors one query chunk may materialise
GATHER_BUDGET_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# per-call stage timing plumbing (shared by live/sharded search paths)
# ---------------------------------------------------------------------------

class StageTimings(threading.local):
    """Thread-local per-search stage timing accumulator.

    Search internals call `add(stage, seconds)`; the outermost caller
    drains with `pop()`. Thread-local so concurrent searches (the service
    executor, sharded fan-out threads) never cross-contaminate."""

    def __init__(self):
        self.stages: dict[str, float] = {}

    def add(self, stage: str, seconds: float) -> None:
        self.stages[stage] = self.stages.get(stage, 0.0) + seconds

    def pop(self) -> dict[str, float]:
        out = dict(self.stages)
        self.stages.clear()
        return out


STAGE_TIMINGS = StageTimings()


def stage_add(stage: str, seconds: float) -> None:
    STAGE_TIMINGS.add(stage, seconds)


def pop_stage_timings() -> dict[str, float]:
    """Drain the calling thread's accumulated per-stage timings."""
    return STAGE_TIMINGS.pop()


@dataclasses.dataclass(frozen=True)
class DeviceData:
    vectors: jax.Array        # [N, d] f32
    norms: jax.Array          # [N] f32
    bitmaps: jax.Array        # [N, W] uint32
    bitmaps_wm: jax.Array     # [W, N] uint32 word-major (the kernels' layout)
    group_bitmaps: jax.Array  # [G, W] uint32
    group_start: jax.Array    # [G] i32
    group_size: jax.Array     # [G] i32
    group_centroids: jax.Array  # [G, d] f32
    group_cnorms: jax.Array     # [G] f32


def clear_caches() -> None:
    """Evict the default handle pool (owned caches live on FilteredIndex)."""
    from repro.ann.index import clear_pool

    clear_pool()


# ---------------------------------------------------------------------------
# predicate masks with traced predicate index (one executable, 3 predicates)
# ---------------------------------------------------------------------------

def mask_shared(base_bm: jax.Array, q_bm: jax.Array, pred_idx) -> jax.Array:
    """base [N, W] × query [Q, W] -> bool [Q, N], word-looped (no 3-D temp)."""
    n, w = base_bm.shape
    q = q_bm.shape[0]

    def eq_():
        acc = jnp.ones((q, n), bool)
        for i in range(w):
            acc &= base_bm[None, :, i] == q_bm[:, i, None]
        return acc

    def and_():
        acc = jnp.ones((q, n), bool)
        for i in range(w):
            qw = q_bm[:, i, None]
            acc &= (base_bm[None, :, i] & qw) == qw
        return acc

    def or_():
        acc = jnp.zeros((q, n), bool)
        for i in range(w):
            acc |= (base_bm[None, :, i] & q_bm[:, i, None]) != 0
        return acc

    return jax.lax.switch(pred_idx, [eq_, and_, or_])


def mask_cand(cand_bm: jax.Array, q_bm: jax.Array, pred_idx) -> jax.Array:
    """candidates [Q, C, W] × query [Q, W] -> bool [Q, C]."""
    q, c, w = cand_bm.shape

    def eq_():
        acc = jnp.ones((q, c), bool)
        for i in range(w):
            acc &= cand_bm[:, :, i] == q_bm[:, i, None]
        return acc

    def and_():
        acc = jnp.ones((q, c), bool)
        for i in range(w):
            qw = q_bm[:, i, None]
            acc &= (cand_bm[:, :, i] & qw) == qw
        return acc

    def or_():
        acc = jnp.zeros((q, c), bool)
        for i in range(w):
            acc |= (cand_bm[:, :, i] & q_bm[:, i, None]) != 0
        return acc

    return jax.lax.switch(pred_idx, [eq_, and_, or_])


# ---------------------------------------------------------------------------
# query chunking
# ---------------------------------------------------------------------------

def gather_chunk(n_cand: int, dim: int) -> int:
    """Queries per chunk for a search that gathers [chunk, n_cand, dim]
    f32 candidate vectors: as many as fit `GATHER_BUDGET_BYTES`, between 1
    and `DEFAULT_QCHUNK`. Sized in bytes, not candidate ids, so the gather
    stays bounded at any embedding width."""
    per_query = max(1, n_cand) * max(1, dim) * 4
    return max(1, min(DEFAULT_QCHUNK, GATHER_BUDGET_BYTES // per_query))


def run_chunked(fn, n_queries: int, *arrays, chunk: int = DEFAULT_QCHUNK,
                extra_host=None):
    """Run `fn(chunked_arrays..., extra_host_chunk...)` over fixed-size query
    chunks; pads the tail chunk; returns np.concatenate of outputs.

    arrays: per-query arrays, leading axis Q. extra_host: same, but kept as
    numpy (for host-side lookups already resolved to per-query values).
    `fn` may return a single array or a tuple of per-query arrays — tuple
    outputs are concatenated position-wise (e.g. (ids, dists)).
    """
    outs = []
    for s in range(0, n_queries, chunk):
        e = min(s + chunk, n_queries)
        pad = chunk - (e - s)
        parts = []
        for a in arrays:
            part = a[s:e]
            if pad:
                part = np.concatenate([part, np.repeat(part[-1:], pad, axis=0)], axis=0)
            parts.append(part)
        hparts = []
        if extra_host is not None:
            for a in extra_host:
                part = a[s:e]
                if pad:
                    part = np.concatenate([part, np.repeat(part[-1:], pad, axis=0)], axis=0)
                hparts.append(part)
        res = fn(*parts, *hparts)
        if isinstance(res, tuple):
            outs.append(tuple(np.asarray(r)[: e - s] for r in res))
        else:
            outs.append(np.asarray(res)[: e - s])
    if isinstance(outs[0], tuple):
        return tuple(np.concatenate([o[i] for o in outs], axis=0)
                     for i in range(len(outs[0])))
    return np.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# method interface
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSetting:
    ps_id: str
    build: tuple       # sorted (key, value) pairs — hashable
    search: tuple

    @property
    def build_dict(self):
        return dict(self.build)

    @property
    def search_dict(self):
        return dict(self.search)


def ps(ps_id: str, build: dict | None = None, search: dict | None = None) -> ParamSetting:
    return ParamSetting(ps_id,
                        tuple(sorted((build or {}).items())),
                        tuple(sorted((search or {}).items())))


def resolve_setting(method: "Method", ps_id: str | None) -> ParamSetting:
    """The method's setting for `ps_id`, else its max-budget setting (the
    fallback for deployment datasets the offline table hasn't covered)."""
    settings = method.param_settings()
    for s in settings:
        if s.ps_id == ps_id:
            return s
    return settings[-1]


class Method:
    """Interface all filtered-ANN methods implement.

    Methods are stateless: all per-dataset state (device tensors, upload
    cache, built indexes) is owned by the `FilteredIndex` handle passed
    to `search`.
    """

    name: str = "?"

    def param_settings(self) -> list[ParamSetting]:
        raise NotImplementedError

    def build(self, ds: ANNDataset, build_params: dict):
        """Offline index build; returns opaque index object."""
        return None

    def index_arrays(self, index) -> dict | None:
        """Persistable form of a built index, or None.

        A dict of numpy arrays (possibly empty, for a stateless build)
        means the index is cheap to persist: `repro.ann.store` writes it
        as an ``.npz`` per generation and `index_from_arrays` restores
        it on open. None (the default) means the build is rebuilt from
        the dataset instead — correct for every method, just slower on
        cold open.
        """
        return None

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict):
        """Inverse of `index_arrays`; only called when it returned a
        dict for this method."""
        raise NotImplementedError(
            f"method {self.name!r} does not persist its index")

    def search(self, fx, index, qvecs: np.ndarray, qbms: np.ndarray,
               pred: Predicate, k: int, search_params: dict):
        """Batched filtered search against the owned handle `fx`
        (`repro.ann.index.FilteredIndex`). Returns
        ([Q, k] int32 ids with −1 pad, [Q, k] float32 ranking scores
        ‖v‖² − 2·q·v, +inf where the id is −1)."""
        raise NotImplementedError

    def graft_index(self, new_ds: ANNDataset, old_index, old_ds: ANNDataset,
                    old_to_new: np.ndarray, new_rows: np.ndarray,
                    build_params: dict):
        """Incremental rebuild for compaction: splice the rows of
        `new_ds` into `old_index` via the id remap instead of building
        from scratch.

        `old_to_new` maps old row ids to new ids (−1 = deleted);
        `new_rows` lists the new ids that did not exist in `old_ds`
        (compacted delta rows). Returns the grafted index, or None
        (the default) to signal the caller to fall back to a full
        `build` — correct for every method, just linear in base size.
        """
        return None
