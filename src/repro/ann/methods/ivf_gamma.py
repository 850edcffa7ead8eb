"""IVFGamma — the ACORN-γ analogue (hybrid search, predicate-agnostic).

ACORN-γ widens HNSW neighbourhoods γ-fold so that predicate-passing
reachability survives filtering, pruning failing nodes *during* traversal.
The TPU-native counterpart: probe γ× more IVF lists than the unfiltered
baseline would and apply the predicate mask **in-scan**, so every candidate
that reaches top-k already satisfies the filter. γ trades compute for
recall uniformly across predicate types.

The probed lists are scored by one masked scan of the whole base per
64-query chunk (`ops.ivf_scan_topk`): a row is a candidate when its list
is one of the query's probed lists and it passes the predicate. The
queries of a chunk share each read of the base and score on the MXU. A
gather of the probed lists' padded rows was slower on a TPU v5e at every
γ and every batch size, one query included: its launches are padded to
a chunk of queries too, and gathered rows score on the VPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.ann import engine, trace
from repro.ann.dataset import ANNDataset
from repro.ann.ivf import (IVFIndex, build_ivf, graft_ivf, probe_words,
                           probed_lists, row_lists, with_row_list)
from repro.ann.predicates import Predicate
from repro.kernels import ops


@partial(jax.jit, static_argnames=("nprobe", "k", "pred"))
def _scan(qvecs, qbms, centroids, cnorms, list_len, row_list,
          vectors, norms, bitmaps_wm, *, nprobe: int, k: int, pred: int):
    """Filtered top-k over each query's `nprobe` nearest lists, by a masked
    scan of the whole base. Also returns each query's rows in its probed
    lists (list padding left out)."""
    probe = probed_lists(qvecs, centroids, cnorms, nprobe)
    words = probe_words(probe, centroids.shape[0])             # [Q, P]
    ids, dists = ops.ivf_scan_topk(qvecs, qbms, words, vectors, norms,
                                   bitmaps_wm, row_list, pred=pred, k=k)
    return ids, dists, jnp.sum(list_len[probe], axis=1)


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
        return with_row_list(
            build_ivf(ds.vectors, int(build_params.get("nlist", 128)),
                      seed=13), ds.n)

    def index_arrays(self, index: IVFIndex) -> dict:
        return {"centroids": index.centroids,
                "centroid_norms": index.centroid_norms,
                "lists": index.lists, "list_len": index.list_len}

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict) -> IVFIndex:
        return IVFIndex(centroids=arrays["centroids"],
                        centroid_norms=arrays["centroid_norms"],
                        lists=arrays["lists"],
                        list_len=arrays["list_len"],
                        row_list=row_lists(arrays["lists"], ds.n))

    def graft_index(self, new_ds: ANNDataset, old_index: IVFIndex,
                    old_ds: ANNDataset, old_to_new, new_rows, build_params):
        if old_index.centroids.shape[0] == 0 or new_ds.n == 0:
            return None
        return with_row_list(
            graft_ivf(old_index, new_ds.vectors, old_to_new), new_ds.n)

    def search(self, fx, index: IVFIndex, qvecs, qbms, pred: Predicate,
               k: int, search_params: dict):
        dev = fx.device
        nq = qvecs.shape[0]
        nprobe = min(4 * int(search_params["gamma"]), index.centroids.shape[0])
        trace.count("cand_rows", nq * dev.vectors.shape[0])
        trace.count("scan_queries", nq)
        cent = fx.as_device(index.centroids)
        cn = fx.as_device(index.centroid_norms)
        list_len = fx.as_device(index.list_len)
        row_list = fx.as_device(index.row_list)
        fn = lambda qv, qb: _scan(
            qv, qb, cent, cn, list_len, row_list, dev.vectors, dev.norms,
            dev.bitmaps_wm, nprobe=nprobe, k=k, pred=int(Predicate(pred)))
        ids, dists, rows = engine.run_chunked(fn, nq, qvecs, qbms)
        trace.count("probe_rows", int(rows.sum()))
        return ids, dists
