"""IVFGamma — the ACORN-γ analogue (hybrid search, predicate-agnostic).

ACORN-γ widens HNSW neighbourhoods γ-fold so that predicate-passing
reachability survives filtering, pruning failing nodes *during* traversal.
The TPU-native counterpart: probe γ× more IVF lists than the unfiltered
baseline would and apply the predicate mask **in-scan**, so every candidate
that reaches top-k already satisfies the filter. γ trades compute for
recall uniformly across predicate types.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import engine, topk
from repro.ann.dataset import ANNDataset
from repro.ann.ivf import IVFIndex, build_ivf, graft_ivf
from repro.ann.predicates import Predicate


@partial(jax.jit, static_argnames=("nprobe", "k"))
def _search(qvecs, qbms, pred_idx, centroids, cnorms, lists,
            vectors, norms, bitmaps, *, nprobe: int, k: int):
    nq = qvecs.shape[0]
    cd = topk.score_all(qvecs, centroids, cnorms)
    _, probe = jax.lax.top_k(-cd, nprobe)
    cand = lists[probe].reshape(nq, -1)                        # [Q, C]
    cvec = vectors[jnp.maximum(cand, 0)]
    cn = norms[jnp.maximum(cand, 0)]
    d = topk.score_candidates(qvecs, cvec, cn)
    cbm = bitmaps[jnp.maximum(cand, 0)]                        # [Q, C, W]
    ok = engine.mask_cand(cbm, qbms, pred_idx) & (cand >= 0)
    return topk.topk_ids(d, cand, k, valid=ok)


class IVFGamma(engine.Method):
    name = "ivf_gamma"

    def param_settings(self):
        # ACORN-γ Table 3: γ ∈ {1,4,8,...} — base nprobe 4, probe 4γ lists.
        return [
            engine.ps("g1", {"nlist": 128}, {"gamma": 1}),
            engine.ps("g4", {"nlist": 128}, {"gamma": 4}),
            engine.ps("g8", {"nlist": 128}, {"gamma": 8}),
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
        nprobe = min(4 * int(search_params["gamma"]), index.centroids.shape[0])
        cent = fx.as_device(index.centroids)
        cn = fx.as_device(index.centroid_norms)
        lists = fx.as_device(index.lists)
        fn = lambda qv, qb: _search(
            qv, qb, pred_idx, cent, cn, lists, dev.vectors, dev.norms,
            dev.bitmaps, nprobe=nprobe, k=k)
        chunk = engine.gather_chunk(nprobe * index.lists.shape[1],
                                   qvecs.shape[1])
        return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms, chunk=chunk)
