"""Post-filter: search-then-filter on an IVF index.

Retrieve the top-k′ (k′ ≫ k) unfiltered candidates from `nprobe` IVF lists
(MXU distance blocks over gathered rows), then verify the predicate on
those k′ and keep the best k valid ones. Mirrors Post-filter HNSW/IVFPQ:
cheap, but recall collapses when selectivity ≪ k/k′ (the k′ cap).
`ef`≈k′ is the quality knob the router tunes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import engine, topk, trace
from repro.ann.dataset import ANNDataset
from repro.ann.ivf import IVFIndex, build_ivf, graft_ivf
from repro.ann.predicates import Predicate


@partial(jax.jit, static_argnames=("nprobe", "kprime", "k"))
def _search(qvecs, qbms, pred_idx, centroids, cnorms, lists,
            vectors, norms, bitmaps, *, nprobe: int, kprime: int, k: int):
    nq = qvecs.shape[0]
    cd = topk.score_all(qvecs, centroids, cnorms)              # [Q, nlist]
    _, probe = jax.lax.top_k(-cd, nprobe)                      # [Q, nprobe]
    cand = lists[probe].reshape(nq, -1)                        # [Q, C]
    cvec = vectors[jnp.maximum(cand, 0)]                       # [Q, C, d]
    cn = norms[jnp.maximum(cand, 0)]
    d = topk.score_candidates(qvecs, cvec, cn)
    d = jnp.where(cand < 0, topk.INF, d)
    # stage 1: unfiltered top-k' (dedup: ivf lists are disjoint, no dups)
    kp = min(kprime, d.shape[1])
    negd, idx = jax.lax.top_k(-d, kp)                          # [Q, k']
    cid = jnp.take_along_axis(cand, idx, axis=1)
    cid = jnp.where(jnp.isinf(negd), -1, cid)
    # stage 2: verify predicate on the k' survivors only
    cbm = bitmaps[jnp.maximum(cid, 0)]                         # [Q, k', W]
    ok = engine.mask_cand(cbm, qbms, pred_idx) & (cid >= 0)
    return topk.topk_ids(-negd, cid, k, valid=ok)


class PostFilter(engine.Method):
    name = "postfilter"

    def param_settings(self):
        # paper Table 3: M/efc (build), ef (search). Our knobs: nlist (build),
        # nprobe + kprime≈ef (search).
        return [
            engine.ps("ef200", {"nlist": 128}, {"nprobe": 8, "kprime": 200}),
            engine.ps("ef800", {"nlist": 128}, {"nprobe": 16, "kprime": 800}),
            engine.ps("ef2000", {"nlist": 128}, {"nprobe": 32, "kprime": 2000}),
        ]

    def build(self, ds: ANNDataset, build_params: dict) -> IVFIndex:
        return build_ivf(ds.vectors, int(build_params.get("nlist", 128)),
                         seed=13)

    def index_arrays(self, index: IVFIndex) -> dict:
        return {"centroids": index.centroids,
                "centroid_norms": index.centroid_norms,
                "lists": index.lists, "list_len": index.list_len}

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict) -> IVFIndex:
        return IVFIndex(centroids=arrays["centroids"],
                        centroid_norms=arrays["centroid_norms"],
                        lists=arrays["lists"],
                        list_len=arrays["list_len"])

    def graft_index(self, new_ds: ANNDataset, old_index: IVFIndex,
                    old_ds: ANNDataset, old_to_new, new_rows, build_params):
        if old_index.centroids.shape[0] == 0 or new_ds.n == 0:
            return None
        return graft_ivf(old_index, new_ds.vectors, old_to_new)

    def search(self, fx, index: IVFIndex, qvecs, qbms, pred: Predicate,
               k: int, search_params: dict):
        dev = fx.device
        pred_idx = jnp.int32(int(Predicate(pred)))
        nprobe = int(search_params["nprobe"])
        kprime = int(search_params["kprime"])
        cent = fx.as_device(index.centroids)
        cn = fx.as_device(index.centroid_norms)
        lists = fx.as_device(index.lists)
        nprobe = min(nprobe, index.centroids.shape[0])
        trace.count("cand_rows",
                    qvecs.shape[0] * nprobe * index.lists.shape[1])
        fn = lambda qv, qb: _search(
            qv, qb, pred_idx, cent, cn, lists, dev.vectors, dev.norms,
            dev.bitmaps, nprobe=nprobe, kprime=kprime, k=k)
        chunk = engine.gather_chunk(nprobe * index.lists.shape[1],
                                   qvecs.shape[1])
        return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms, chunk=chunk)
