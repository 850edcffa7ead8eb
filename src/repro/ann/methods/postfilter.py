"""Post-filter: search-then-filter on an IVF index.

Retrieve the top-k′ (k′ ≫ k) unfiltered candidates from `nprobe` IVF lists,
then verify the predicate on those k′ and keep the best k valid ones.
Mirrors Post-filter HNSW/IVFPQ: cheap, but recall collapses when
selectivity ≪ k/k′ (the k′ cap). `ef`≈k′ is the quality knob the router
tunes.

The answer is computed without a top-k′. Let P be the rows of the probed
lists, ordered by (score, row id), and F the rows that pass the predicate.
The first k′ rows of P are a prefix of that order, so the rows of F among
them are a prefix of F ∩ P in the same order. Post-filter's answer is
therefore the filtered top-k over the probed lists — ivf_gamma's masked
scan, `ops.ivf_scan_topk`, one launch per 64-query chunk — less every
answer with k′ or more probed rows before it. That count comes from one
[Q, N] score product at HIGHEST and one compare-and-count against the
probe mask; an answer's own score is read from the same product.
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


@partial(jax.jit, static_argnames=("nprobe", "kprime", "k", "pred"))
def scan(qvecs, qbms, centroids, cnorms, list_len, row_list, vectors,
         norms, bitmaps_wm, *, nprobe: int, kprime: int, k: int, pred: int):
    """Post-filter's top-k for a chunk of queries over each one's `nprobe`
    nearest lists at k′ = `kprime`. Also returns each query's rows in its
    probed lists and the number of its answers the k′ cap removed."""
    probe = probed_lists(qvecs, centroids, cnorms, nprobe)
    words = probe_words(probe, centroids.shape[0])
    ids, dists = ops.ivf_scan_topk(qvecs, qbms, words, vectors, norms,
                                   bitmaps_wm, row_list, pred=pred, k=k)
    # rank of each answer among the probed rows by (score, row id)
    s = ops.scores(qvecs, vectors, norms)                      # [Q, N]
    own = jnp.take_along_axis(s, jnp.maximum(ids, 0), axis=1)[:, :, None]
    row = jnp.arange(s.shape[1], dtype=jnp.int32)
    before = ((s[:, None, :] < own)
              | ((s[:, None, :] == own) & (row < ids[:, :, None])))
    rank = jnp.sum(before & ops.probe_mask(words, row_list)[:, None, :],
                   axis=2, dtype=jnp.int32)                    # [Q, k]
    # answers come in rank order: the cap cuts a suffix
    keep = (jnp.cumsum(rank >= kprime, axis=1) == 0) & (ids >= 0)
    cut = jnp.sum((ids >= 0) & ~keep, axis=1)
    return (jnp.where(keep, ids, -1), jnp.where(keep, dists, jnp.inf),
            jnp.sum(list_len[probe], axis=1), cut)


def search_ivf(fx, index: IVFIndex, qvecs, qbms, pred: Predicate, k: int,
               *, nprobe: int, kprime: int):
    """Post-filter search of `fx`'s base through `index` (which carries
    `row_list`), 64 queries a launch; counts what the scan reads."""
    dev = fx.device
    nq = qvecs.shape[0]
    nprobe = min(nprobe, index.centroids.shape[0])
    trace.count("cand_rows", nq * dev.vectors.shape[0])
    trace.count("scan_queries", nq)
    cent = fx.as_device(index.centroids)
    cn = fx.as_device(index.centroid_norms)
    list_len = fx.as_device(index.list_len)
    row_list = fx.as_device(index.row_list)
    fn = lambda qv, qb: scan(
        qv, qb, cent, cn, list_len, row_list, dev.vectors, dev.norms,
        dev.bitmaps_wm, nprobe=nprobe, kprime=kprime, k=k,
        pred=int(Predicate(pred)))
    ids, dists, rows, cut = engine.run_chunked(fn, nq, qvecs, qbms)
    trace.count("probe_rows", int(rows.sum()))
    trace.count("kprime_cut", int(cut.sum()))
    return ids, dists


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
        return search_ivf(fx, index, qvecs, qbms, pred, k,
                          nprobe=int(search_params["nprobe"]),
                          kprime=int(search_params["kprime"]))
