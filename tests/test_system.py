"""End-to-end behaviour: the full offline-stage artifacts (benchmark table,
trained router) route real validation queries to near-oracle recall, and
the RAG-style serve path (LM embed → route → filtered search) runs."""

import os

import numpy as np
import pytest

from repro.ann.dataset import recall_at_k
from repro.ann.index import QueryBatch
from repro.ann.predicates import Predicate
from repro.ann.service import RouterService
from repro.core import training as T
from repro.core.oracle import oracle_recall


def _artifacts():
    p_train, p_val, p_router = T.default_paths()
    if not all(os.path.exists(p) for p in (p_train, p_val, p_router)):
        pytest.skip("offline artifacts not built (run benchmarks first)")
    from repro.core.router import MLRouter

    return (T.Collection.load(p_train), T.Collection.load(p_val),
            MLRouter.load(p_router))


def test_router_near_oracle_on_validation():
    _, coll_val, router = _artifacts()
    recs, oracles = [], []
    for (ds, pt), cell in coll_val.cells.items():
        x, y, _ = T.assemble_xy(
            T.Collection(cells={(ds, pt): cell}, table=coll_val.table),
            router.feature_names)
        r_hat = router.predict_recalls_from_features(x)
        dec = router.route_from_predictions(r_hat, ds, pt, 0.9)
        recs.extend(cell.recall[m][i] for i, (m, _) in enumerate(dec))
        oracles.append(oracle_recall(coll_val, ds, pt))
    agg = float(np.mean(recs))
    orc = float(np.concatenate(oracles).mean())
    # paper: router 0.986 aggregate, ≤0.9% behind oracle
    assert agg >= 0.95
    assert orc - agg <= 0.03


def test_router_pareto_dominates_single_methods():
    """No single method beats the router on BOTH recall and latency —
    the recall-QPS balance claim of §6.3 (a single max-budget method can
    match recall, but only at worse latency)."""
    _, coll_val, router = _artifacts()
    single = {m: {"rec": [], "time": 0.0} for m in T.METHOD_ORDER}
    routed_rec, routed_time = [], 0.0
    for (ds, pt), cell in coll_val.cells.items():
        x, _, _ = T.assemble_xy(
            T.Collection(cells={(ds, pt): cell}, table=coll_val.table),
            router.feature_names)
        dec = router.route_from_predictions(
            router.predict_recalls_from_features(x), ds, pt, 0.9)
        qps_of = {(m, ps): v["qps"]
                  for (d2, p2, m, ps), v in router.table.entries.items()
                  if (d2, p2) == (ds, pt)}
        for i, (m, ps) in enumerate(dec):
            routed_rec.append(cell.recall[m][i])
            routed_time += 1.0 / max(qps_of.get((m, ps), 1e-9), 1e-9)
        for m in T.METHOD_ORDER:
            single[m]["rec"].extend(cell.recall[m])
            best = max((s for s in cell.sweep if s[0] == m),
                       key=lambda s: (round(s[2], 3), s[3]))
            single[m]["time"] += len(cell.recall[m]) / max(best[3], 1e-9)
    r_rec = float(np.mean(routed_rec))
    assert r_rec >= 0.95
    for m, d in single.items():
        m_rec = float(np.mean(d["rec"]))
        # Pareto: anything matching the router's recall must be slower
        if m_rec >= r_rec - 0.002:
            assert d["time"] > routed_time, (m, m_rec, d["time"], routed_time)


def test_service_search_executes(tiny_index, tiny_queries):
    """Full dispatch path on fresh data with the shipped router."""
    _, _, router = _artifacts()
    qs = tiny_queries[Predicate.AND]
    svc = RouterService(tiny_index, router, t=0.9)
    res = svc.search(QueryBatch(qs.vectors, qs.bitmaps, Predicate.AND, 10))
    rec = recall_at_k(res.ids, qs.ground_truth).mean()
    assert rec > 0.6
    assert len(res.decisions) == qs.q


def test_rag_serve_path(tiny_ds, tiny_index):
    """LM produces the query embedding; the router picks the method; the
    engine searches — the end-to-end serving story."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_smoke_config
    from repro.models import common, lm
    from repro.ann import labels as lb

    from repro.launch.mesh import make_mesh

    _, _, router = _artifacts()
    cfg = get_smoke_config("qwen2-0.5b")
    params = common.init_params(lm.model_desc(cfg), jax.random.PRNGKey(0))
    ctx = lm.ModelCtx(mesh=make_mesh((1, 1), ("data", "model")),
                      qc_prefill=16, gla_chunk=16)
    toks = jnp.ones((2, 16), jnp.int32)
    with ctx.mesh:
        logits, cache = lm.forward_prefill(params, {"tokens": toks}, cfg, ctx)
    # embedding = final hidden state proxy: use logits slice projected down
    emb = np.asarray(logits[:, 0, :tiny_ds.dim], np.float32)
    qbms = np.stack([lb.pack_one([0], tiny_ds.universe)] * 2)
    svc = RouterService(tiny_index, router)
    res = svc.search_chunked(QueryBatch(emb, qbms, Predicate.OR, 5), t=0.5)
    assert res.ids.shape == (2, 5)
    assert len(res.decisions) == 2
    mask = tiny_ds.matching_mask(qbms[0], Predicate.OR)
    assert all(mask[i] for i in res.ids.ravel() if i >= 0)
