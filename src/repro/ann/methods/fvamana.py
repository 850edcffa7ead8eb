"""FVamana — the FilteredVamana analogue (hybrid graph search).

Offline: α-pruned Vamana-style graph + per-label entry points (the
label-aware part of FilteredVamana's build). Online: fixed-iteration
batched best-first search seeded at the medoid plus the query labels'
entry points; traversal routes through predicate-failing nodes (they keep
the graph navigable) but only predicate-passing pool entries are eligible
for the final top-k — label-aware pruning at result granularity.
`L_search` is the paper's quality knob.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.ann import engine, graph, topk, trace
from repro.ann.dataset import ANNDataset
from repro.ann.labels import unpack_one
from repro.ann.predicates import Predicate


class FVamana(engine.Method):
    name = "fvamana"

    MAX_SEEDS = 5

    def param_settings(self):
        # FilteredVamana Table 3: R ∈ {32,64}, L_search ∈ {16..128}
        return [
            engine.ps("L16", {"r": 32}, {"l_search": 16}),
            engine.ps("L32", {"r": 32}, {"l_search": 32}),
            engine.ps("L64", {"r": 32}, {"l_search": 64}),
            engine.ps("L128", {"r": 32}, {"l_search": 128}),
        ]

    def build(self, ds: ANNDataset, build_params: dict) -> graph.VamanaGraph:
        return graph.build_graph(ds.vectors, ds.bitmaps, ds.universe,
                                 r=int(build_params.get("r", 32)), seed=17)

    def index_arrays(self, index: graph.VamanaGraph) -> dict:
        return {"neighbors": index.neighbors,
                "medoid": np.asarray(index.medoid, dtype=np.int64),
                "label_entry": index.label_entry}

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict) -> graph.VamanaGraph:
        return graph.VamanaGraph(neighbors=arrays["neighbors"],
                                 medoid=int(arrays["medoid"]),
                                 label_entry=arrays["label_entry"])

    def graft_index(self, new_ds: ANNDataset, old_index: graph.VamanaGraph,
                    old_ds: ANNDataset, old_to_new, new_rows, build_params):
        n_surv = int((old_to_new >= 0).sum())
        # grafting pays off only while the surviving graph dominates; a
        # mostly-new dataset searches better on a fresh build
        if n_surv == 0 or new_ds.n == 0 or len(new_rows) > n_surv:
            return None
        return graph.graft_graph(old_index, new_ds.vectors, new_ds.bitmaps,
                                 new_ds.universe, old_to_new, new_rows,
                                 r=int(build_params.get("r", 32)), seed=17)

    def search(self, fx, index: graph.VamanaGraph, qvecs, qbms,
               pred: Predicate, k: int, search_params: dict):
        dev = fx.device
        pred_idx = jnp.int32(int(Predicate(pred)))
        l_search = int(search_params["l_search"])
        nq = qvecs.shape[0]

        # host-side seed assembly: medoid + query-label entry points
        seeds = np.full((nq, self.MAX_SEEDS), -1, dtype=np.int32)
        seeds[:, 0] = index.medoid
        for qi in range(nq):
            labs = sorted(unpack_one(qbms[qi]))[: self.MAX_SEEDS - 1]
            for j, l in enumerate(labs):
                seeds[qi, 1 + j] = index.label_entry[l]

        nbrs = fx.as_device(index.neighbors)
        # seeds, then one expanded node's neighbours per beam iteration
        trace.count("cand_rows", nq * (self.MAX_SEEDS
                                       + l_search * index.neighbors.shape[1]))

        def fn(qv, qb, sd):
            pool_ids, pool_d = graph.beam_search(
                qv, sd, nbrs, dev.vectors, dev.norms,
                l_search=l_search, iters=l_search)
            cbm = dev.bitmaps[jnp.maximum(pool_ids, 0)]
            ok = engine.mask_cand(cbm, qb, pred_idx) & (pool_ids >= 0)
            return topk.topk_ids(pool_d, pool_ids, k, valid=ok)

        return engine.run_chunked(fn, nq, qvecs, qbms, seeds)
