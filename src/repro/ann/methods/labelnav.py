"""LabelNav — the UNG analogue (filter-then-search).

UNG builds per-label-set sub-graphs linked by a label navigating graph.
Our TPU-native layout: vectors are stored **group-sorted** (one contiguous
extent per unique label set); searching is

* Equality — O(1) host hash lookup of the query's group, then one fused
  distance scan over that extent (recall = 1, exactly UNG's sweet spot);
* AND/OR — predicate over the [G, W] *group* bitmaps picks qualifying
  groups, a group-centroid distance ranks them ("navigation"), and the
  nearest `group_cap` groups are scanned up to `per_group_cap` members
  each. Recall degrades when many groups qualify (OR) — UNG's documented
  weakness.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import engine, topk, trace
from repro.ann.dataset import ANNDataset
from repro.ann.predicates import Predicate


@partial(jax.jit, static_argnames=("maxg", "k"))
def _search_eq(qvecs, qgroup, group_start, group_size, vectors, norms,
               *, maxg: int, k: int):
    """Exact-match: scan the query's own group extent."""
    start = group_start[jnp.maximum(qgroup, 0)]                 # [Q]
    size = jnp.where(qgroup < 0, 0, group_size[jnp.maximum(qgroup, 0)])
    offs = jnp.arange(maxg, dtype=jnp.int32)[None, :]           # [1, maxg]
    cand = start[:, None] + offs                                # [Q, maxg]
    valid = offs < size[:, None]
    cand = jnp.where(valid, cand, -1)
    cvec = vectors[jnp.maximum(cand, 0)]
    cn = norms[jnp.maximum(cand, 0)]
    d = topk.score_candidates(qvecs, cvec, cn)
    return topk.topk_ids(d, cand, k)


@partial(jax.jit, static_argnames=("group_cap", "per_group_cap", "k"))
def _search_sub(qvecs, qbms, pred_idx, group_bitmaps, group_start, group_size,
                gcent, gcnorms, vectors, norms,
                *, group_cap: int, per_group_cap: int, k: int):
    """AND/OR: navigate to nearest qualifying groups, scan their extents."""
    nq = qvecs.shape[0]
    ok = engine.mask_shared(group_bitmaps, qbms, pred_idx)      # [Q, G]
    gscore = topk.score_all(qvecs, gcent, gcnorms)              # [Q, G]
    gscore = jnp.where(ok, gscore, topk.INF)
    neg, gsel = jax.lax.top_k(-gscore, group_cap)               # [Q, GC]
    gvalid = jnp.isfinite(neg)                                  # [Q, GC]
    start = group_start[gsel]                                   # [Q, GC]
    size = jnp.where(gvalid, group_size[gsel], 0)
    offs = jnp.arange(per_group_cap, dtype=jnp.int32)[None, None, :]
    cand = start[:, :, None] + offs                             # [Q, GC, PGC]
    valid = offs < size[:, :, None]
    cand = jnp.where(valid, cand, -1).reshape(nq, -1)
    cvec = vectors[jnp.maximum(cand, 0)]
    cn = norms[jnp.maximum(cand, 0)]
    d = topk.score_candidates(qvecs, cvec, cn)
    return topk.topk_ids(d, cand, k)


class LabelNav(engine.Method):
    name = "labelnav"

    def param_settings(self):
        # UNG Table 3: L_search ∈ {100,300,500} -> (group_cap, per_group_cap)
        return [
            engine.ps("L100", {}, {"group_cap": 4, "per_group_cap": 128}),
            engine.ps("L300", {}, {"group_cap": 16, "per_group_cap": 256}),
            engine.ps("L500", {}, {"group_cap": 64, "per_group_cap": 512}),
        ]

    def build(self, ds: ANNDataset, build_params: dict):
        return {"maxg": int(ds.group_size.max())}

    def index_arrays(self, index) -> dict:
        return {"maxg": np.asarray(index["maxg"], dtype=np.int64)}

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict):
        return {"maxg": int(arrays["maxg"])}

    def search(self, fx, index, qvecs, qbms, pred: Predicate, k: int,
               search_params: dict):
        ds = fx.ds
        dev = fx.device
        pred = Predicate(pred)
        nq = qvecs.shape[0]
        if pred == Predicate.EQUALITY:
            qgroup = np.asarray(
                [ds.group_id_of_bitmap(qbms[i]) for i in range(nq)],
                dtype=np.int32)
            maxg = max(8, index["maxg"])
            trace.count("cand_rows", nq * maxg)
            fn = lambda qv, qg: _search_eq(
                qv, qg, dev.group_start, dev.group_size, dev.vectors,
                dev.norms, maxg=maxg, k=k)
            chunk = engine.gather_chunk(maxg, ds.dim)
            return engine.run_chunked(fn, nq, qvecs, qgroup, chunk=chunk)

        gc = min(int(search_params["group_cap"]), ds.n_groups)
        pgc = int(search_params["per_group_cap"])
        trace.count("cand_rows", nq * gc * pgc)
        pred_idx = jnp.int32(int(pred))
        fn = lambda qv, qb: _search_sub(
            qv, qb, pred_idx, dev.group_bitmaps, dev.group_start,
            dev.group_size, dev.group_centroids, dev.group_cnorms,
            dev.vectors, dev.norms, group_cap=gc, per_group_cap=pgc, k=k)
        chunk = engine.gather_chunk(gc * pgc, ds.dim)
        return engine.run_chunked(fn, nq, qvecs, qbms, chunk=chunk)
