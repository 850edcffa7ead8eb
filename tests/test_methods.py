"""Filtered-ANN method invariants on the tiny dataset, run through the
owned `FilteredIndex` handle."""

import dataclasses

import numpy as np
import pytest

from repro.ann import bench
from repro.ann.dataset import recall_at_k
from repro.ann.methods import ALL_METHODS, CANDIDATE_METHODS
from repro.ann.predicates import Predicate, PREDICATES


@pytest.mark.parametrize("pred", PREDICATES)
def test_prefilter_recall_is_one(tiny_index, tiny_queries, pred):
    m = ALL_METHODS["prefilter"]
    r = bench.run_method(tiny_index, m, m.param_settings()[0],
                         tiny_queries[pred])
    assert r.mean_recall == pytest.approx(1.0)


@pytest.mark.parametrize("name", list(CANDIDATE_METHODS))
@pytest.mark.parametrize("pred", PREDICATES)
def test_results_satisfy_predicate(tiny_ds, tiny_index, tiny_queries, name, pred):
    """Every returned id must satisfy the query predicate (no false hits)."""
    m = CANDIDATE_METHODS[name]
    qs = tiny_queries[pred]
    r = bench.run_method(tiny_index, m, m.param_settings()[-1], qs)
    for qi in range(qs.q):
        mask = tiny_ds.matching_mask(qs.bitmaps[qi], pred)
        for vid in r.ids[qi]:
            if vid >= 0:
                assert mask[vid], (name, pred, qi, vid)


@pytest.mark.parametrize("name", list(CANDIDATE_METHODS))
def test_no_duplicate_results(tiny_index, tiny_queries, name):
    m = CANDIDATE_METHODS[name]
    qs = tiny_queries[Predicate.OR]
    r = bench.run_method(tiny_index, m, m.param_settings()[-1], qs)
    for qi in range(qs.q):
        ids = r.ids[qi][r.ids[qi] >= 0]
        assert len(ids) == len(set(ids.tolist())), (name, qi)


def test_labelnav_equality_exact(tiny_index, tiny_queries):
    """The UNG analogue is exact on Equality (its structural sweet spot)."""
    m = CANDIDATE_METHODS["labelnav"]
    r = bench.run_method(tiny_index, m, m.param_settings()[0],
                         tiny_queries[Predicate.EQUALITY])
    assert r.mean_recall == pytest.approx(1.0)


def test_param_settings_monotone_recall(tiny_index, tiny_queries):
    """Bigger search budgets should not reduce recall materially."""
    qs = tiny_queries[Predicate.AND]
    for name in ("postfilter", "ivf_gamma", "fvamana"):
        m = CANDIDATE_METHODS[name]
        settings = m.param_settings()
        lo = bench.run_method(tiny_index, m, settings[0], qs).mean_recall
        hi = bench.run_method(tiny_index, m, settings[-1], qs).mean_recall
        assert hi >= lo - 0.05, (name, lo, hi)


def test_recall_at_k_contract():
    gt = np.array([[1, 2, -1, -1], [5, 6, 7, 8]], dtype=np.int32)
    res = np.array([[2, 9, 9, 9], [5, 6, 7, 8]], dtype=np.int32)
    rec = recall_at_k(res, gt)
    assert rec[0] == pytest.approx(0.5)   # 1 of min(k=4,|TopK|=2)
    assert rec[1] == pytest.approx(1.0)


def test_empty_result_query(tiny_ds, tiny_index):
    """A label set absent from the dataset gives zero Equality matches."""
    from repro.ann import labels as lb
    from repro.ann.dataset import QuerySet

    qbm = lb.pack_one([0, 1, 2, 3, 4, 5, 6, 7], tiny_ds.universe)[None, :]
    if tiny_ds.group_id_of_bitmap(qbm[0]) >= 0:
        pytest.skip("label set unexpectedly present")
    qs = QuerySet(dataset="tiny", pred=Predicate.EQUALITY,
                  vectors=tiny_ds.vectors[:1].copy(), bitmaps=qbm,
                  ground_truth=np.full((1, 10), -1, np.int32), k=10)
    m = CANDIDATE_METHODS["labelnav"]
    r = bench.run_method(tiny_index, m, m.param_settings()[0], qs)
    assert (r.ids == -1).all()
    assert np.isinf(r.dists).all()        # score contract: +inf at −1 pad
    assert r.mean_recall == pytest.approx(1.0)   # vacuous query


def test_prefilter_kernel_path_parity(tiny_index, tiny_queries):
    """`PreFilter(use_kernel=True)` (the TPU `ops.masked_topk` route, in
    interpret mode here) matches the jnp reference path exactly."""
    from repro.ann.methods.prefilter import PreFilter

    ref, kern = PreFilter(use_kernel=False), PreFilter(use_kernel=True)
    st = ref.param_settings()[0]
    for pred in PREDICATES:
        qs = tiny_queries[pred]
        # keep the interpret-mode kernel cheap: 8 queries
        sub_v, sub_b = qs.vectors[:8], qs.bitmaps[:8]
        ids_ref, d_ref = ref.search(tiny_index, None, sub_v, sub_b,
                                    pred, qs.k, {})
        ids_k, d_k = kern.search(tiny_index, None, sub_v, sub_b,
                                 pred, qs.k, {})
        np.testing.assert_array_equal(ids_ref, ids_k)
        np.testing.assert_allclose(d_ref, d_k, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# ivf_gamma: the probe-masked scan gives the per-query gather's answer
# ---------------------------------------------------------------------------
#
# The reference gathers each query's probed lists, as ivf_gamma did before
# it scanned. Vectors on a 1/4 grid make every score exact on both sides,
# so the distances must agree bit for bit; only the order among equal
# distances may differ (list order against row order).

@pytest.fixture(scope="module")
def grid_index(tiny_ds):
    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex

    ds = ANNDataset.from_packed("grid", np.round(tiny_ds.vectors * 4) / 4,
                                tiny_ds.bitmaps, tiny_ds.universe)
    fx = FilteredIndex(ds)
    yield fx
    fx.close()


def _scan_and_gather(fx, index, setting, qs):
    """(ids, dists) of the method and of the gather reference."""
    from repro.ann.index import QueryBatch
    from repro.ann.methods.ivf_gamma import probed_lists

    m = CANDIDATE_METHODS["ivf_gamma"]
    qv = np.round(qs.vectors * 4) / 4
    scan = fx.run_method(m, setting, QueryBatch(qv, qs.bitmaps, qs.pred,
                                                qs.k))
    nprobe = min(4 * setting.search_dict["gamma"], index.centroids.shape[0])
    probe = np.asarray(probed_lists(qv, index.centroids,
                                    index.centroid_norms, nprobe))
    ds = fx.ds
    ids = np.full((qs.q, qs.k), -1, np.int64)
    dists = np.full((qs.q, qs.k), np.inf, np.float32)
    for qi in range(qs.q):
        cand = index.lists[probe[qi]].ravel()
        cand = cand[cand >= 0]
        cand = cand[ds.matching_mask(qs.bitmaps[qi], qs.pred)[cand]]
        v = ds.vectors[cand].astype(np.float64)
        d = (v * v).sum(1) - 2 * v @ qv[qi].astype(np.float64)
        top = np.argsort(d, kind="stable")[:qs.k]
        ids[qi, :top.size], dists[qi, :top.size] = cand[top], d[top]
    return scan, (ids, dists)


def _assert_same_up_to_ties(a, b):
    (ia, da), (ib, db) = a, b
    np.testing.assert_array_equal(da, db)
    for qi in range(ia.shape[0]):
        kth = da[qi][-1]
        sa = set(ia[qi][(ia[qi] >= 0) & (da[qi] < kth)].tolist())
        sb = set(ib[qi][(ib[qi] >= 0) & (db[qi] < kth)].tolist())
        assert sa == sb, qi
        assert ((ia[qi] >= 0) == (ib[qi] >= 0)).all(), qi


@pytest.mark.parametrize("ps_id", ["g1", "g4", "g8"])
@pytest.mark.parametrize("pred", PREDICATES)
def test_ivf_gamma_scan_matches_gather(grid_index, tiny_queries, pred,
                                       ps_id):
    from repro.ann.engine import resolve_setting

    m = CANDIDATE_METHODS["ivf_gamma"]
    setting = resolve_setting(m, ps_id)
    index = grid_index.get_index(m, setting.build_dict)
    scan, gather = _scan_and_gather(grid_index, index, setting,
                                    tiny_queries[pred])
    _assert_same_up_to_ties(scan, gather)
    assert (scan[0] >= 0).any()


@pytest.mark.parametrize("pred", PREDICATES)
def test_ivf_gamma_scan_matches_gather_after_graft(grid_index, tiny_queries,
                                                   pred):
    """Compaction grafts the lists onto a remapped base: every third row
    deleted, 40 new rows; the scan reads the grafted lists' row_list."""
    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex
    from repro.ann.methods.ivf_gamma import row_lists

    m = CANDIDATE_METHODS["ivf_gamma"]
    setting = m.param_settings()[-1]
    old_ds = grid_index.ds
    old = grid_index.get_index(m, setting.build_dict)
    keep = np.nonzero(np.arange(old_ds.n) % 3 != 0)[0]
    vec = np.concatenate([old_ds.vectors[keep],
                          old_ds.vectors[:40] + np.float32(0.25)])
    bms = np.concatenate([old_ds.bitmaps[keep], old_ds.bitmaps[:40]])
    new_ds, order = ANNDataset.from_packed("grid2", vec, bms,
                                           old_ds.universe, return_order=True)
    pos = np.empty(order.size, np.int64)
    pos[order] = np.arange(order.size)
    old_to_new = np.full(old_ds.n, -1, np.int64)
    old_to_new[keep] = pos[:keep.size]
    grafted = m.graft_index(new_ds, old, old_ds, old_to_new,
                            pos[keep.size:], setting.build_dict)
    np.testing.assert_array_equal(grafted.row_list,
                                  row_lists(grafted.lists, new_ds.n))
    assert (grafted.row_list >= 0).all()
    with FilteredIndex(new_ds) as fx:
        fx.adopt_index(m, setting.build_dict, grafted)
        scan, gather = _scan_and_gather(fx, grafted, setting,
                                        tiny_queries[pred])
    _assert_same_up_to_ties(scan, gather)


@pytest.mark.parametrize("ps_id,nq", [("g1", 1), ("g8", 64), ("g4", 70)])
def test_ivf_gamma_counts_scanned_and_probed_rows(grid_index, tiny_queries,
                                                  ps_id, nq):
    """Every query is scanned, one query alone too: `cand_rows` counts the
    whole base a query, `probe_rows` the real rows of its probed lists."""
    from repro.ann.engine import resolve_setting
    from repro.ann.index import QueryBatch
    from repro.ann.methods.ivf_gamma import probed_lists
    from repro.ann.trace import Tracer

    m = CANDIDATE_METHODS["ivf_gamma"]
    setting = resolve_setting(m, ps_id)
    idx = grid_index.get_index(m, setting.build_dict)
    qs = tiny_queries[Predicate.OR]
    qv = np.resize(qs.vectors, (nq, qs.vectors.shape[1]))
    batch = QueryBatch(qv, np.resize(qs.bitmaps, (nq, qs.bitmaps.shape[1])),
                       Predicate.OR, qs.k)
    tr = Tracer(sample=1.0)
    with tr.trace("search"):
        grid_index.run_method(m, setting, batch)
    c = tr.histograms()["search"]["counters"]
    probe = np.asarray(probed_lists(qv, idx.centroids, idx.centroid_norms,
                                    4 * setting.search_dict["gamma"]))
    assert c["scan_queries"] == nq
    assert c["cand_rows"] == nq * grid_index.ds.n
    assert c["probe_rows"] == int(idx.list_len[probe].sum())
    assert c["launches"] == -(-nq // 64)


# ---------------------------------------------------------------------------
# postfilter: the masked scan plus the k′ rank check gives the two-stage
# answer
# ---------------------------------------------------------------------------
#
# The reference is post-filter's definition in float64 numpy: the first k′
# rows of the probed lists by (score, row id), then the predicate, then the
# best k. On the 1/4 grid every score is exact, so ties are real and the
# row id decides them on both sides: ids and distances must agree exactly.

def _postfilter_ref(ds, index, qv, qbms, pred, k, nprobe, kprime):
    """(ids, dists, answers the k′ cap removed) of the two-stage search."""
    from repro.ann.methods.ivf_gamma import probed_lists

    nprobe = min(nprobe, index.centroids.shape[0])
    probe = np.asarray(probed_lists(qv, index.centroids,
                                    index.centroid_norms, nprobe))
    nq = qv.shape[0]
    ids = np.full((nq, k), -1, np.int64)
    dists = np.full((nq, k), np.inf, np.float32)
    cut = 0
    for qi in range(nq):
        cand = index.lists[probe[qi]].ravel()
        cand = cand[cand >= 0]
        v = ds.vectors[cand].astype(np.float64)
        d = (v * v).sum(1) - 2 * v @ qv[qi].astype(np.float64)
        order = np.lexsort((cand, d))                  # by (score, row id)
        ok = ds.matching_mask(qbms[qi], pred)
        first = order[:kprime]
        top = first[ok[cand[first]]][:k]
        ids[qi, :top.size], dists[qi, :top.size] = cand[top], d[top]
        cut += min(k, int(ok[cand].sum())) - top.size
    return ids, dists, cut


def _postfilter_run(fx, setting, qs, method="postfilter", search=None):
    """(method's ids and dists, its counters) for the grid-rounded
    queries of `qs`."""
    from repro.ann.index import QueryBatch
    from repro.ann.trace import Tracer

    m = CANDIDATE_METHODS[method]
    qv = np.round(qs.vectors * 4) / 4
    tr = Tracer(sample=1.0)
    with tr.trace("search"):
        if search is None:
            got = fx.run_method(m, setting, QueryBatch(qv, qs.bitmaps,
                                                       qs.pred, qs.k))
        else:
            got = search(qv)
    return got, qv, tr.histograms()["search"]["counters"]


def _assert_postfilter(fx, index, setting, qs, nprobe, kprime):
    got, qv, c = _postfilter_run(fx, setting, qs)
    ids, dists, cut = _postfilter_ref(fx.ds, index, qv, qs.bitmaps, qs.pred,
                                      qs.k, nprobe, kprime)
    np.testing.assert_array_equal(got[0], ids)
    np.testing.assert_array_equal(got[1], dists)
    assert c.get("kprime_cut", 0) == cut
    return got, c


def _pf_setting(nprobe, kprime):
    from repro.ann.engine import ps

    return ps(f"np{nprobe}-k{kprime}", {"nlist": 128},
              {"nprobe": nprobe, "kprime": kprime})


@pytest.mark.parametrize("ps_id", ["ef200", "ef800", "ef2000"])
@pytest.mark.parametrize("pred", PREDICATES)
def test_postfilter_matches_two_stage_reference(grid_index, tiny_queries,
                                                pred, ps_id):
    from repro.ann.engine import resolve_setting

    m = CANDIDATE_METHODS["postfilter"]
    setting = resolve_setting(m, ps_id)
    index = grid_index.get_index(m, setting.build_dict)
    sp = setting.search_dict
    got, _ = _assert_postfilter(grid_index, index, setting,
                                tiny_queries[pred], sp["nprobe"],
                                sp["kprime"])
    assert (got[0] >= 0).any()


@pytest.mark.parametrize("kprime", [1, 6, 25])
@pytest.mark.parametrize("pred", PREDICATES)
def test_postfilter_kprime_cap_binds(grid_index, tiny_queries, pred, kprime):
    """A k′ below the probed rows drops the answers ranked past it, and
    `kprime_cut` counts them."""
    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(32, kprime)
    index = grid_index.get_index(m, setting.build_dict)
    got, c = _assert_postfilter(grid_index, index, setting,
                                tiny_queries[pred], 32, kprime)
    assert c["kprime_cut"] > 0
    uncapped, _ = _assert_postfilter(grid_index, index, _pf_setting(32, 10**6),
                                     tiny_queries[pred], 32, 10**6)
    # the cap only ever cuts a suffix of the uncapped answer
    kept = got[0] >= 0
    np.testing.assert_array_equal(got[0][kept], uncapped[0][kept])
    assert (np.diff(kept.astype(int), axis=1) <= 0).all()


def test_postfilter_fewer_probed_rows_than_kprime(grid_index, tiny_queries):
    """Every probed row within k′: nothing is cut, and the answer is the
    filtered top-k of the probed lists."""
    m = CANDIDATE_METHODS["postfilter"]
    setting = m.param_settings()[0]                    # nprobe 8, k′ 200
    index = grid_index.get_index(m, setting.build_dict)
    assert index.list_len.max() * 8 < 200
    qs = tiny_queries[Predicate.OR]
    got, c = _assert_postfilter(grid_index, index, setting, qs, 8, 200)
    assert c["kprime_cut"] == 0
    from repro.ann.engine import ps

    g2 = ps("g2", {"nlist": 128}, {"gamma": 2})        # 8 probed lists too
    scan, _ = _scan_and_gather(grid_index, index, g2, qs)
    np.testing.assert_array_equal(got[0], scan[0])
    np.testing.assert_array_equal(got[1], scan[1])


@pytest.mark.parametrize("kprime", [1, 2, 3, 5])
def test_postfilter_ties_go_to_the_lower_row_id(tiny_ds, tiny_queries,
                                                kprime):
    """Rows planted three times over, each copy with other labels: equal
    scores at the k′ boundary are ranked by row id."""
    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex

    base = np.round(tiny_ds.vectors[:200] * 4) / 4
    vec = np.concatenate([base, base, base])
    bms = np.concatenate([tiny_ds.bitmaps[:200], tiny_ds.bitmaps[200:400],
                          tiny_ds.bitmaps[400:600]])
    ds = ANNDataset.from_packed("dups", vec, bms, tiny_ds.universe)
    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(32, kprime)
    with FilteredIndex(ds) as fx:
        index = fx.get_index(m, setting.build_dict)
        for pred in PREDICATES:
            qs = tiny_queries[pred]
            # queries on planted rows: the best score is a three-way tie
            qs = dataclasses.replace(qs, vectors=base[:qs.q].copy())
            _assert_postfilter(fx, index, setting, qs, 32, kprime)


@pytest.mark.parametrize("pred", PREDICATES)
def test_postfilter_skips_rows_a_list_cap_dropped(grid_index, tiny_queries,
                                                  pred):
    """Rows a `max_list_cap` left out of every list are not probed rows:
    they neither answer nor count towards k′."""
    from repro.ann.ivf import build_ivf, with_row_list

    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(16, 8)
    capped = with_row_list(build_ivf(grid_index.ds.vectors, 128, seed=13,
                                     max_list_cap=3), grid_index.ds.n)
    assert (capped.row_list < 0).any()
    with type(grid_index)(grid_index.ds) as fx:
        fx.adopt_index(m, setting.build_dict, capped)
        got, _ = _assert_postfilter(fx, capped, setting, tiny_queries[pred],
                                    16, 8)
    dropped = np.nonzero(capped.row_list < 0)[0]
    assert not np.isin(got[0], dropped).any()


@pytest.mark.parametrize("pred", PREDICATES)
def test_postfilter_matches_reference_after_graft(grid_index, tiny_queries,
                                                  pred):
    """Compaction grafts the lists onto a remapped base (every third row
    deleted, 40 new rows); the grafted index carries its row_list."""
    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex
    from repro.ann.ivf import row_lists

    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(16, 12)
    old_ds = grid_index.ds
    old = grid_index.get_index(m, setting.build_dict)
    keep = np.nonzero(np.arange(old_ds.n) % 3 != 0)[0]
    vec = np.concatenate([old_ds.vectors[keep],
                          old_ds.vectors[:40] + np.float32(0.25)])
    bms = np.concatenate([old_ds.bitmaps[keep], old_ds.bitmaps[:40]])
    new_ds, order = ANNDataset.from_packed("grid2", vec, bms,
                                           old_ds.universe, return_order=True)
    pos = np.empty(order.size, np.int64)
    pos[order] = np.arange(order.size)
    old_to_new = np.full(old_ds.n, -1, np.int64)
    old_to_new[keep] = pos[:keep.size]
    grafted = m.graft_index(new_ds, old, old_ds, old_to_new,
                            pos[keep.size:], setting.build_dict)
    np.testing.assert_array_equal(grafted.row_list,
                                  row_lists(grafted.lists, new_ds.n))
    with FilteredIndex(new_ds) as fx:
        fx.adopt_index(m, setting.build_dict, grafted)
        _assert_postfilter(fx, grafted, setting, tiny_queries[pred], 16, 12)


def test_postfilter_index_restores_its_row_list(grid_index):
    m = CANDIDATE_METHODS["postfilter"]
    setting = m.param_settings()[-1]
    built = grid_index.get_index(m, setting.build_dict)
    back = m.index_from_arrays(grid_index.ds, setting.build_dict,
                               m.index_arrays(built))
    np.testing.assert_array_equal(back.row_list, built.row_list)


@pytest.mark.parametrize("ef", [2, 50])
@pytest.mark.parametrize("pred", PREDICATES)
def test_sieve_miss_fallback_is_postfilter(grid_index, tiny_queries, pred,
                                           ef):
    """Sieve's misses are answered by post-filter over its own IVF at
    nprobe 8 and k′ = ef_search."""
    from repro.ann.engine import ps
    from repro.ann.labels import unpack_one

    m = CANDIDATE_METHODS["sieve"]
    setting = ps(f"ef{ef}", {"hist_pct": 0.25, "list_cap": 1024},
                 {"ef_search": ef})
    index = grid_index.get_index(m, setting.build_dict)
    qs = tiny_queries[pred]
    got, qv, c = _postfilter_run(grid_index, setting, qs, method="sieve")
    miss = []
    for qi in range(qs.q):
        labs = unpack_one(qs.bitmaps[qi])
        mat = [lab for lab in labs if lab in index["row_of"]]
        if pred == Predicate.OR:
            miss.append(len(mat) < len(labs) or len(labs) > 8)
        else:
            miss.append(not mat)
    miss = np.array(miss)
    assert miss.any()
    ids, dists, cut = _postfilter_ref(grid_index.ds, index["ivf"], qv[miss],
                                      qs.bitmaps[miss], pred, qs.k, 8, ef)
    np.testing.assert_array_equal(got[0][miss], ids)
    np.testing.assert_array_equal(got[1][miss], dists)
    assert c.get("kprime_cut", 0) == cut
    assert c["scan_queries"] == int(miss.sum())
    if ef == 2:
        assert cut > 0


def test_postfilter_kernel_path_in_interpret_mode(grid_index, tiny_queries,
                                                  monkeypatch):
    """The chunk function with the Pallas scan (interpret mode) gives the
    XLA twin's answer."""
    import functools

    from repro.ann.methods import postfilter
    from repro.kernels import ops

    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(16, 9)
    index = grid_index.get_index(m, setting.build_dict)
    qs = tiny_queries[Predicate.AND]
    want, _ = _assert_postfilter(grid_index, index, setting, qs, 16, 9)
    monkeypatch.setattr(ops, "ivf_scan_topk", functools.partial(
        ops.ivf_scan_topk, interpret=True))
    postfilter.scan.clear_cache()
    try:
        got, _, c = _postfilter_run(grid_index, setting, qs)
    finally:
        postfilter.scan.clear_cache()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert c["kprime_cut"] > 0


@pytest.mark.parametrize("ps_id,nq", [("ef200", 1), ("ef2000", 64),
                                      ("ef800", 70)])
def test_postfilter_counts_scanned_and_probed_rows(grid_index, tiny_queries,
                                                   ps_id, nq):
    """As ivf_gamma: `cand_rows` counts the whole base a query,
    `scan_queries` every query, `probe_rows` the real rows of its probed
    lists; one launch per 64 queries."""
    from repro.ann.engine import resolve_setting
    from repro.ann.methods.ivf_gamma import probed_lists

    m = CANDIDATE_METHODS["postfilter"]
    setting = resolve_setting(m, ps_id)
    nprobe = setting.search_dict["nprobe"]
    idx = grid_index.get_index(m, setting.build_dict)
    qs = tiny_queries[Predicate.OR]
    qs = dataclasses.replace(
        qs, vectors=np.resize(qs.vectors, (nq, qs.vectors.shape[1])),
        bitmaps=np.resize(qs.bitmaps, (nq, qs.bitmaps.shape[1])),
        ground_truth=np.resize(qs.ground_truth, (nq, qs.k)))
    got, qv, c = _postfilter_run(grid_index, setting, qs)
    probe = np.asarray(probed_lists(qv, idx.centroids, idx.centroid_norms,
                                    nprobe))
    assert c["scan_queries"] == nq
    assert c["cand_rows"] == nq * grid_index.ds.n
    assert c["probe_rows"] == int(idx.list_len[probe].sum())
    assert c.get("kprime_cut", 0) == _postfilter_ref(
        grid_index.ds, idx, qv, qs.bitmaps, qs.pred, qs.k, nprobe,
        setting.search_dict["kprime"])[2]
    assert c["launches"] == -(-nq // 64)


@pytest.mark.parametrize("kprime", [60, 100, 140])
def test_postfilter_cap_cuts_a_suffix_when_check_scores_disagree(
        grid_index, tiny_queries, monkeypatch, kprime):
    """On the chip the rank check's [Q, N] product may round a score
    otherwise than the scan did. Planted here by reversing the check's
    scores: the cap still cuts the answer at its first dropped answer,
    never leaving a pad before an id."""
    from repro.ann.methods import postfilter
    from repro.kernels import ops

    m = CANDIDATE_METHODS["postfilter"]
    setting = _pf_setting(32, kprime)
    qs = tiny_queries[Predicate.OR]
    want, _, _ = _postfilter_run(grid_index, _pf_setting(32, 10**6), qs)
    scores = ops.scores
    monkeypatch.setattr(ops, "scores", lambda q, b, n: -scores(q, b, n))
    postfilter.scan.clear_cache()
    try:
        got, _, c = _postfilter_run(grid_index, setting, qs)
    finally:
        postfilter.scan.clear_cache()
    kept = got[0] >= 0
    assert c["kprime_cut"] > 0
    assert (np.diff(kept.astype(int), axis=1) <= 0).all()
    np.testing.assert_array_equal(got[0][kept], want[0][kept])
