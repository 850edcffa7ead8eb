"""The unified serving API: `FilteredIndex` ownership/lifecycle,
`QueryBatch` validation, `SearchResult` distances, the method registry,
the versioned router artifact, and `RouterService` dispatch."""

import os
import pickle

import numpy as np
import pytest

from repro.ann import engine
from repro.ann import registry as registry_mod
from repro.ann.dataset import ground_truth_topk
from repro.ann.index import (FilteredIndex, QueryBatch, RoutingDecision,
                             SearchResult, default_index)
from repro.ann.predicates import Predicate
from repro.ann.service import RouterService
from repro.core.router import MLRouter
from repro.data.ann_synth import DatasetSpec, make_queries, synthesize


# ---------------------------------------------------------------------------
# QueryBatch validation
# ---------------------------------------------------------------------------

def _batch_args(tiny_ds, q=4):
    return (tiny_ds.vectors[:q].copy(), tiny_ds.bitmaps[:q].copy())


def test_query_batch_validates_shapes(tiny_ds):
    vec, bm = _batch_args(tiny_ds)
    with pytest.raises(ValueError, match="disagree on Q"):
        QueryBatch(vec, bm[:2], Predicate.AND)
    with pytest.raises(ValueError, match="vectors must be"):
        QueryBatch(vec[0], bm, Predicate.AND)
    with pytest.raises(ValueError, match="bitmaps must be"):
        QueryBatch(vec, bm[0], Predicate.AND)
    with pytest.raises(ValueError, match="k must be"):
        QueryBatch(vec, bm, Predicate.AND, k=0)
    with pytest.raises(ValueError, match="at least one query"):
        QueryBatch(vec[:0], bm[:0], Predicate.AND)


def test_query_batch_coerces_dtypes_and_takes(tiny_ds):
    vec, bm = _batch_args(tiny_ds)
    b = QueryBatch(vec.astype(np.float64), bm.astype(np.int64),
                   int(Predicate.OR), k=3)
    assert b.vectors.dtype == np.float32
    assert b.bitmaps.dtype == np.uint32
    assert b.pred is Predicate.OR
    sub = b.take([0, 2])
    assert sub.q == 2 and sub.k == 3
    np.testing.assert_array_equal(sub.vectors, b.vectors[[0, 2]])


def test_search_rejects_mismatched_bitmap_width(tiny_index, tiny_ds):
    vec, bm = _batch_args(tiny_ds)
    wide = np.concatenate([bm, bm], axis=1)
    with pytest.raises(ValueError, match="bitmap width"):
        tiny_index.search(QueryBatch(vec, wide, Predicate.AND), "prefilter")
    # run_method is the choke point every serving path goes through
    m = registry_mod.get_method("prefilter")
    with pytest.raises(ValueError, match="bitmap width"):
        tiny_index.run_method(m, m.param_settings()[0],
                              QueryBatch(vec, wide, Predicate.AND))
    with pytest.raises(ValueError, match="vector dim"):
        tiny_index.run_method(m, m.param_settings()[0],
                              QueryBatch(vec[:, :-2], bm, Predicate.AND))


# ---------------------------------------------------------------------------
# FilteredIndex ownership + lifecycle
# ---------------------------------------------------------------------------

OTHER_SPEC = DatasetSpec("other", 500, 24, 40, 6, 8, 1.3, 2.0, 0.5, 0.3, 11)


def test_two_indexes_never_share_state(tiny_ds):
    other = synthesize(OTHER_SPEC)
    with FilteredIndex(tiny_ds) as fa, FilteredIndex(other) as fb:
        assert fa.device.vectors is not fb.device.vectors
        assert fa.device.bitmaps is not fb.device.bitmaps
        m = registry_mod.get_method("labelnav")
        ia = fa.get_index(m, m.param_settings()[0].build)
        ib = fb.get_index(m, m.param_settings()[0].build)
        assert ia["maxg"] == int(tiny_ds.group_size.max())
        assert ib["maxg"] == int(other.group_size.max())
        assert ia["centroids"].shape == (tiny_ds.n_groups, tiny_ds.dim)
        assert ib["centroids"].shape == (other.n_groups, other.dim)
        # same dataset, two handles: still no sharing (owned, not global)
        with FilteredIndex(tiny_ds) as fa2:
            assert fa2.device.vectors is not fa.device.vectors


def test_close_frees_and_blocks(tiny_ds):
    fx = FilteredIndex(tiny_ds)
    _ = fx.device
    fx.get_index("labelnav", ())
    fx.as_device(tiny_ds.norms_sq)
    assert fx.stats()["device_resident"]
    assert fx.stats()["built_indexes"] == ["labelnav"]
    assert fx.stats()["cached_uploads"] == 1
    fx.close()
    assert fx.closed
    assert fx._device is None and not fx._indexes and not fx._arrays
    with pytest.raises(RuntimeError, match="closed"):
        fx.device
    with pytest.raises(RuntimeError, match="closed"):
        fx.get_index("labelnav", ())


def test_evict_drops_built_indexes(tiny_index):
    m = registry_mod.get_method("labelnav")
    tiny_index.get_index(m, m.param_settings()[0].build)
    assert tiny_index.evict("labelnav") >= 1
    assert "labelnav" not in tiny_index.stats()["built_indexes"]


def test_default_pool_reuses_and_clears(tiny_ds):
    fa = default_index(tiny_ds)
    assert default_index(tiny_ds) is fa
    engine.clear_caches()          # shimmed onto the pool
    fb = default_index(tiny_ds)
    assert fb is not fa
    assert fa.closed
    fb.close()


# ---------------------------------------------------------------------------
# SearchResult distances
# ---------------------------------------------------------------------------

def test_distances_are_exact_squared_l2(tiny_ds, tiny_index, tiny_queries):
    qs = tiny_queries[Predicate.AND]
    res = tiny_index.search(
        QueryBatch(qs.vectors, qs.bitmaps, Predicate.AND, 10), "prefilter")
    assert isinstance(res, SearchResult)
    gt = ground_truth_topk(tiny_ds, qs.vectors, qs.bitmaps,
                           Predicate.AND, 10)
    # same result *sets* as the host brute force (ranking ties aside)
    for qi in range(qs.q):
        assert set(res.ids[qi].tolist()) == set(gt[qi].tolist())
    for qi in range(qs.q):
        for j in range(10):
            vid = res.ids[qi, j]
            if vid < 0:
                assert np.isnan(res.distances[qi, j])
            else:
                want = ((tiny_ds.vectors[vid] - qs.vectors[qi]) ** 2).sum()
                assert res.distances[qi, j] == pytest.approx(want, rel=2e-3,
                                                             abs=1e-2)
        # exact distances must be sorted ascending over valid hits
        valid = res.distances[qi][res.ids[qi] >= 0]
        assert (np.diff(valid) >= -1e-4).all()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class _DummyMethod(engine.Method):
    name = "dummy"

    def param_settings(self):
        return [engine.ps("d1")]


def test_registry_register_overwrite_and_views():
    from repro.ann.methods import ALL_METHODS, CANDIDATE_METHODS

    m1, m2 = _DummyMethod(), _DummyMethod()
    try:
        registry_mod.register_method(m1, candidate=True)
        # live views reflect the registration without core edits
        assert CANDIDATE_METHODS["dummy"] is m1
        assert "dummy" in list(ALL_METHODS)
        with pytest.raises(ValueError, match="already registered"):
            registry_mod.register_method(m2)
        registry_mod.register_method(m2, overwrite=True, candidate=False)
        assert registry_mod.get_method("dummy") is m2
        assert "dummy" not in list(CANDIDATE_METHODS)   # demoted
        assert ALL_METHODS["dummy"] is m2
    finally:
        registry_mod.unregister_method("dummy")
    assert "dummy" not in list(ALL_METHODS)
    with pytest.raises(KeyError, match="unknown method"):
        registry_mod.get_method("dummy")


def test_registry_rejects_unnamed():
    with pytest.raises(ValueError, match="name"):
        registry_mod.register_method(engine.Method())


# ---------------------------------------------------------------------------
# versioned router artifact + service round-trip
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_identical_decisions(tmp_path, tiny_ds,
                                                tiny_index, tiny_queries,
                                                toy_router):
    router = toy_router
    qs = tiny_queries[Predicate.AND]
    batch = QueryBatch(qs.vectors, qs.bitmaps, Predicate.AND, 10)
    svc = RouterService(tiny_index, router, t=0.9)
    res = svc.search(batch)

    art = str(tmp_path / "router")
    router.save(art)
    assert sorted(os.listdir(art)) == ["router.json", "table.json",
                                       "weights.npz"]
    svc2 = RouterService(tiny_index, MLRouter.load(art), t=0.9)
    res2 = svc2.search(batch)
    assert res2.decisions == res.decisions
    assert all(isinstance(d, RoutingDecision) for d in res2.decisions)
    np.testing.assert_array_equal(res2.ids, res.ids)
    np.testing.assert_allclose(svc2.predict(batch), svc.predict(batch),
                               rtol=1e-6)

    # chunked serving path agrees with the one-shot path
    res3 = svc2.search_chunked(batch, chunk=7)
    np.testing.assert_array_equal(res3.ids, res.ids)
    assert res3.decisions == res.decisions

    # explain() is consistent with the decisions it explains
    exp = svc.explain(batch)
    assert [(e.method, e.ps_id) for e in exp] == res.decisions
    assert all(set(e.r_hat) == set(router.methods) for e in exp)


def test_artifact_rejects_foreign_and_future(tmp_path, toy_router):
    import json

    router = toy_router
    art = str(tmp_path / "router")
    router.save(art)
    manifest = json.load(open(os.path.join(art, "router.json")))
    manifest["version"] = 99
    json.dump(manifest, open(os.path.join(art, "router.json"), "w"))
    with pytest.raises(ValueError, match="newer"):
        MLRouter.load(art)
    manifest["version"] = 1
    manifest["format"] = "something.else"
    json.dump(manifest, open(os.path.join(art, "router.json"), "w"))
    with pytest.raises(ValueError, match="not a repro.router"):
        MLRouter.load(art)
    with pytest.raises(ValueError, match="existing file"):
        router.save(os.path.join(art, "router.json"))


def test_legacy_pickle_no_longer_loads(tmp_path, toy_router):
    """The one-PR-cycle pickle loader is gone: loading a pickle file (or
    any non-directory path) fails with a migration hint."""
    router = toy_router
    p = str(tmp_path / "router.pkl")
    with open(p, "wb") as f:
        pickle.dump({"methods": router.methods}, f)
    with pytest.raises(ValueError, match="no longer supported"):
        MLRouter.load(p)
    with pytest.raises(ValueError, match="no longer supported"):
        MLRouter.load(str(tmp_path / "never_written"))


def test_deprecation_shims_removed():
    """PR-2's one-PR-cycle shims are gone from the public surface."""
    assert not hasattr(engine, "device_data")
    assert not hasattr(engine, "as_device")
    assert not hasattr(engine, "get_index")
    assert not hasattr(MLRouter, "route_and_search")
    assert not hasattr(MLRouter, "_load_legacy_pickle")
    engine.clear_caches()          # the pool-evict helper stays


def test_feature_cache_owned_by_handle(tiny_ds):
    """Dataset-feature state lives on the handle and dies with close()."""
    from repro.core import features as F

    with FilteredIndex(tiny_ds) as fx:
        a = F.dataset_features(tiny_ds, fx=fx)
        assert F.dataset_features(tiny_ds, fx=fx) is a   # handle cache hit
        assert fx.stats()["features_cached"]
    assert fx._features is None                          # freed by close()
    # handle-less calls cache in the weak per-instance fallback map
    # (features._FALLBACK_FEATURES), living only as long as the dataset
    F.clear_feature_cache()
    b = F.dataset_features(tiny_ds)
    assert F.dataset_features(tiny_ds) is b
    assert b is not a
