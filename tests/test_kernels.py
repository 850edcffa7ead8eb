"""Pallas kernel validation (interpret mode) against the jnp oracles and
the legacy multi-block-merge path: shape/dtype sweeps, seeded random
bitmaps, ragged blocking, and edge cases. Cases are built row-major (the
oracles' layout); `_wm` hands the kernels' wrappers their word-major
base bitmaps."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand_case(rng, q, n, d, w, dtype=np.float32, label_density=0.1):
    qv = rng.normal(size=(q, d)).astype(dtype)
    base = rng.normal(size=(n, d)).astype(dtype)
    norms = (base.astype(np.float64) ** 2).sum(1).astype(np.float32)
    bm = (rng.random((n, w, 32)) < label_density)
    bm = (bm * (1 << np.arange(32, dtype=np.uint64))).sum(-1).astype(np.uint32)
    qb = (rng.random((q, w, 32)) < 0.05)
    qb = (qb * (1 << np.arange(32, dtype=np.uint64))).sum(-1).astype(np.uint32)
    return (jnp.asarray(qv), jnp.asarray(qb), jnp.asarray(base),
            jnp.asarray(norms), jnp.asarray(bm))


def _wm(case):
    """(qv, qb, base, norms, bitmaps [N, W]) -> same with bitmaps [W, N]."""
    return (*case[:4], case[4].T)


def _same_sets(ids_a, ids_b):
    for i in range(ids_a.shape[0]):
        a = set(np.asarray(ids_a[i][ids_a[i] >= 0]).tolist())
        b = set(np.asarray(ids_b[i][ids_b[i] >= 0]).tolist())
        if a != b:
            return False
    return True


@pytest.mark.parametrize("q,n,d,w", [
    (8, 1000, 32, 1), (16, 2048, 64, 4), (4, 300, 96, 2), (32, 4096, 128, 8),
])
@pytest.mark.parametrize("pred", [0, 1, 2])
def test_masked_topk_shapes(q, n, d, w, pred, rng):
    case = _rand_case(rng, q, n, d, w)
    ids, dists = ops.masked_topk(*_wm(case), pred=pred, k=10)
    rids, rdists = ref.masked_topk_ref(*case, pred=pred, k=10)
    assert ids.shape == (q, 10)
    assert _same_sets(ids, rids)
    # distances of valid hits must match
    valid = np.asarray(ids) >= 0
    np.testing.assert_allclose(np.asarray(dists)[valid],
                               np.asarray(rdists)[np.asarray(rids) >= 0],
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_masked_topk_dtypes(dtype, rng):
    case = _rand_case(rng, 8, 1024, 64, 2, dtype=np.float32)
    if dtype == jnp.bfloat16:
        case = (case[0].astype(jnp.bfloat16), case[1],
                case[2].astype(jnp.bfloat16), case[3], case[4])
    ids, _ = ops.masked_topk(*_wm(case), pred=2, k=5)
    rids, _ = ref.masked_topk_ref(*case, pred=2, k=5)
    assert _same_sets(ids, rids)


def test_masked_topk_no_matches(rng):
    qv, qb, base, norms, bm = _rand_case(rng, 4, 512, 16, 1)
    bm = jnp.zeros_like(bm)          # nothing matches AND/OR
    qb = jnp.ones_like(qb)
    ids, dists = ops.masked_topk(qv, qb, base, norms, bm.T, pred=1, k=10)
    assert (np.asarray(ids) == -1).all()


def test_masked_topk_fewer_than_k(rng):
    qv, qb, base, norms, bm = _rand_case(rng, 4, 512, 16, 1)
    bm = jnp.zeros_like(bm).at[:3].set(jnp.asarray(qb[0])[None, :])
    qb = jnp.tile(qb[:1], (4, 1))
    ids, _ = ops.masked_topk(qv, qb, base, norms, bm.T, pred=0, k=10)
    assert ((np.asarray(ids) >= 0).sum(1) == 3).all()


@pytest.mark.parametrize("q,n", [(1, 50), (7, 131), (16, 256), (40, 400)])
@pytest.mark.parametrize("pred", [0, 1, 2])
def test_selectivity_matches_ref(q, n, pred):
    rng = np.random.default_rng(q * 1000 + n)
    _, qb, _, _, bm = _rand_case(rng, q, n, 8, 2)
    got = ops.selectivity(qb, bm.T, pred=pred)
    want = ref.selectivity_ref(qb, bm, pred=pred)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_selectivity_empty_query_equality(rng):
    _, _, _, _, bm = _rand_case(rng, 2, 256, 8, 2)
    qb = jnp.zeros((2, 2), jnp.uint32)
    got = ops.selectivity(qb, bm.T, pred=0)
    want = ref.selectivity_ref(qb, bm, pred=0)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_kernel_block_shape_sweep(rng):
    case = _rand_case(rng, 16, 2048, 64, 2)
    want, _ = ref.masked_topk_ref(*case, pred=1, k=10)
    for bq, bn in [(8, 256), (16, 1024), (16, 2048)]:
        ids, _ = ops.masked_topk(*_wm(case), pred=1, k=10, bq=bq, bn=bn)
        assert _same_sets(ids, want), (bq, bn)


# ---------------------------------------------------------------------------
# VMEM-accumulating kernel vs legacy multi-block merge (parity)
# ---------------------------------------------------------------------------

def _assert_topk_parity(case, pred, k, **kw):
    ids, dists = ops.masked_topk(*_wm(case), pred=pred, k=k, **kw)
    mids, mdists = ops.masked_topk_multiblock(*_wm(case), pred=pred, k=k,
                                              **kw)
    assert ids.shape == mids.shape
    assert _same_sets(ids, mids), pred
    a, b = np.asarray(dists), np.asarray(mdists)
    np.testing.assert_allclose(np.sort(np.where(np.isinf(a), 1e30, a), axis=1),
                               np.sort(np.where(np.isinf(b), 1e30, b), axis=1),
                               rtol=1e-5, atol=1e-4)
    # valid-hit counts per query must agree exactly
    assert ((np.asarray(ids) >= 0).sum(1) ==
            (np.asarray(mids) >= 0).sum(1)).all()


@pytest.mark.parametrize("pred", [0, 1, 2])
def test_accum_matches_multiblock(pred, rng):
    case = _rand_case(rng, 16, 3072, 32, 2)
    _assert_topk_parity(case, pred, k=10, bq=8, bn=1024)


@pytest.mark.parametrize("q,n", [(5, 777), (13, 1025), (3, 100)])
@pytest.mark.parametrize("pred", [0, 1, 2])
def test_accum_matches_multiblock_ragged(q, n, pred):
    """Q/N not multiples of bq/bn: padding + sentinel cleanup parity."""
    rng = np.random.default_rng(q * 7 + n)
    case = _rand_case(rng, q, n, 16, 2)
    _assert_topk_parity(case, pred, k=7, bq=8, bn=256)


@pytest.mark.parametrize("pred", [0, 1, 2])
def test_accum_k_exceeds_matches(pred, rng):
    """k larger than the number of predicate-passing candidates."""
    qv, qb, base, norms, bm = _rand_case(rng, 4, 700, 16, 1)
    bm = jnp.zeros_like(bm).at[:5].set(jnp.asarray(qb[0])[None, :])
    qb = jnp.tile(qb[:1], (4, 1))
    case = (qv, qb, base, norms, bm)
    _assert_topk_parity(case, pred, k=16, bq=8, bn=256)


@pytest.mark.parametrize("pred", [0, 1, 2])
def test_accum_empty_label_queries(pred, rng):
    """All-zero query bitmaps: EQUALITY/AND match empty-label base rows
    (incl. vacuous containment), OR matches nothing."""
    qv, qb, base, norms, bm = _rand_case(rng, 6, 515, 16, 2)
    qb = jnp.zeros_like(qb)
    bm = bm.at[:4].set(0)            # a few empty-label base rows
    case = (qv, qb, base, norms, bm)
    _assert_topk_parity(case, pred, k=10, bq=8, bn=256)
    ids, _ = ops.masked_topk(*_wm(case), pred=pred, k=10, bq=8, bn=256)
    rids, _ = ref.masked_topk_ref(*case, pred=pred, k=10)
    assert _same_sets(ids, rids)


@pytest.mark.parametrize("pred", [0, 1, 2])
def test_accum_single_block(pred, rng):
    """N below one block: the nb axis degenerates to a single step."""
    case = _rand_case(rng, 4, 200, 16, 1)
    _assert_topk_parity(case, pred, k=5, bq=8, bn=256)


# ---------------------------------------------------------------------------
# cross-shard merge kernel
# ---------------------------------------------------------------------------

def _merge_case(rng, s, q, k, frac_valid=0.7):
    """Per-shard sorted top-k candidates with disjoint global ids and a
    random invalid suffix per (shard, query) row."""
    d = np.sort(np.abs(rng.normal(size=(s, q, k))).astype(np.float32), -1)
    ids = np.arange(s * q * k, dtype=np.int32).reshape(s, q, k)
    nval = rng.binomial(k, frac_valid, size=(s, q))
    for si in range(s):
        for qi in range(q):
            d[si, qi, nval[si, qi]:] = np.inf
            ids[si, qi, nval[si, qi]:] = -1
    return jnp.asarray(ids), jnp.asarray(d)


@pytest.mark.parametrize("s,q,k", [(1, 8, 10), (2, 17, 10), (4, 33, 5),
                                   (8, 8, 16)])
def test_merge_topk_matches_ref(s, q, k):
    rng = np.random.default_rng(s * 100 + q)
    ids, d = _merge_case(rng, s, q, k)
    gi, gd = ops.merge_topk(ids, d)
    ri, rd = ref.merge_topk_ref(ids, d)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(rd))


def test_merge_topk_narrower_k(rng):
    ids, d = _merge_case(rng, 4, 12, 10)
    gi, gd = ops.merge_topk(ids, d, k=3)
    ri, rd = ref.merge_topk_ref(ids, d, k=3)
    assert gi.shape == (12, 3)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(rd))


def test_merge_topk_all_invalid_rows(rng):
    ids, d = _merge_case(rng, 3, 9, 8)
    ids = np.asarray(ids).copy()
    d = np.asarray(d).copy()
    ids[:, 4, :] = -1
    d[:, 4, :] = np.inf
    gi, gd = ops.merge_topk(jnp.asarray(ids), jnp.asarray(d))
    assert (np.asarray(gi)[4] == -1).all()
    assert np.isinf(np.asarray(gd)[4]).all()
    ri, _ = ref.merge_topk_ref(jnp.asarray(ids), jnp.asarray(d))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))


def test_merge_topk_fewer_than_k_global(rng):
    """Fewer valid candidates than k across *all* shards: trailing −1s."""
    ids, d = _merge_case(rng, 2, 6, 10, frac_valid=0.15)
    gi, gd = ops.merge_topk(ids, d)
    ri, rd = ref.merge_topk_ref(ids, d)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    nval = (np.asarray(ids) >= 0).sum(axis=(0, 2))
    got = (np.asarray(gi) >= 0).sum(1)
    np.testing.assert_array_equal(got, np.minimum(nval, 10))


# ---------------------------------------------------------------------------
# live-index (delta path) edge cases: S=1 pass-through, k wider than the
# candidate axis, all-tombstoned segments
# ---------------------------------------------------------------------------

def test_merge_topk_single_segment_pass_through(rng):
    """S=1 skips the Pallas fold; semantics must be unchanged even for
    *unsorted* inputs with interleaved invalid slots."""
    d = np.abs(rng.normal(size=(1, 11, 8))).astype(np.float32)
    ids = rng.permutation(11 * 8).astype(np.int32).reshape(1, 11, 8)
    d[0, :, 3] = np.inf                    # invalid mid-row slots
    ids[0, :, 5] = -1
    gi, gd = ops.merge_topk(jnp.asarray(ids), jnp.asarray(d))
    ri, rd = ref.merge_topk_ref(jnp.asarray(ids), jnp.asarray(d))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(rd))


@pytest.mark.parametrize("s", [1, 3])
def test_merge_topk_k_exceeds_candidate_width(s, rng):
    """k > K (the delta segment holds fewer surviving candidates than
    requested): the surplus must come back as −1 ids / +inf dists."""
    ids, d = _merge_case(rng, s, 9, 4)
    gi, gd = ops.merge_topk(ids, d, k=10)
    ri, rd = ref.merge_topk_ref(ids, d, k=10)
    assert gi.shape == (9, 10)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(rd))
    nval = (np.asarray(ids) >= 0).sum(axis=(0, 2))
    np.testing.assert_array_equal((np.asarray(gi) >= 0).sum(1),
                                  np.minimum(nval, 10))


def test_merge_topk_all_invalid_everywhere(rng):
    """An all-tombstoned segment set: every slot invalid -> all −1/+inf
    (the exact-distance layer then reports NaN at the −1 pad)."""
    ids = np.full((2, 7, 6), -1, np.int32)
    d = np.full((2, 7, 6), np.inf, np.float32)
    gi, gd = ops.merge_topk(jnp.asarray(ids), jnp.asarray(d), k=5)
    assert (np.asarray(gi) == -1).all()
    assert np.isinf(np.asarray(gd)).all()
    from repro.ann.index import exact_distances
    dist = exact_distances(np.asarray(gd), np.asarray(gi),
                           np.zeros((7, 4), np.float32))
    assert np.isnan(dist).all()


def test_masked_topk_k_exceeds_rows(rng):
    """k larger than the whole (padded) segment: parity with the padded
    reference oracle, trailing −1s."""
    case = _rand_case(rng, 4, 40, 8, 1)
    ids, d = ops.masked_topk(*_wm(case), pred=1, k=64, bq=8, bn=256)
    rids, rd = ref.masked_topk_ref(*case, pred=1, k=64)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    valid = np.asarray(ids) >= 0
    np.testing.assert_allclose(np.asarray(d)[valid],
                               np.asarray(rd)[valid], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# XLA fast path vs Pallas kernel (bit-identity)
# ---------------------------------------------------------------------------
#
# Off TPU the ops dispatch to a pure-XLA formulation of the same fold
# (stable top_k over candidates in kernel fold order). These tests force
# score ties (duplicated rows, a coarse value grid) and assert the two
# paths agree bit for bit — ids, distances and fill pattern — so the
# dispatch can never change a result depending on backend.
#
# Vectors live on an integer grid (multiples of 1/4) so every product
# and partial sum in the score matmul is exactly representable: the two
# backends may reduce in different orders (gemm edge kernels differ per
# shape) but must land on the same bits, making the comparison test the
# fold semantics rather than matmul rounding.

def _tie_case(rng, q, n, d=24, w=2):
    qv = (rng.integers(-6, 7, (q, d)) / 4.0).astype(np.float32)
    base = (rng.integers(-6, 7, (n, d)) / 4.0).astype(np.float32)
    base[n // 2: n // 2 + n // 4] = base[: n // 4]   # exact duplicates
    norms = (base.astype(np.float64) ** 2).sum(1).astype(np.float32)
    qb = (rng.integers(0, 2, (q, w)) * rng.integers(1, 8, (q, w))
          ).astype(np.uint32)
    bm = (rng.integers(0, 2, (n, w)) * rng.integers(1, 8, (n, w))
          ).astype(np.uint32)
    return (jnp.asarray(qv), jnp.asarray(qb), jnp.asarray(base),
            jnp.asarray(norms), jnp.asarray(bm))


def _assert_bitwise(a, b):
    ai, ad = np.asarray(a[0]), np.asarray(a[1])
    bi, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_array_equal(ai, bi)
    np.testing.assert_array_equal(np.isfinite(ad), np.isfinite(bd))
    np.testing.assert_array_equal(ad[np.isfinite(ad)], bd[np.isfinite(bd)])


@pytest.mark.parametrize("pred", [0, 1, 2])
@pytest.mark.parametrize("q,n,k", [(1, 64, 5), (7, 256, 41), (25, 1024, 10)])
def test_masked_topk_xla_matches_kernel(pred, q, n, k, rng):
    case = _tie_case(rng, q, n)
    _assert_bitwise(ops.masked_topk(*_wm(case), pred=pred, k=k),
                    ops.masked_topk(*_wm(case), pred=pred, k=k,
                                    interpret=True))


@pytest.mark.parametrize("s,q,kk,k", [(2, 8, 10, 10), (3, 25, 41, 10),
                                      (5, 64, 10, 41)])
def test_merge_topk_xla_matches_kernel(s, q, kk, k, rng):
    d = np.round(rng.normal(size=(s, q, kk)).astype(np.float32) ** 2, 1)
    ids = rng.integers(0, 10, (s, q, kk)).astype(np.int32)  # heavy id ties
    ids[d > 2.0] = -1
    args = (jnp.asarray(ids), jnp.asarray(d))
    _assert_bitwise(ops.merge_topk(*args, k=k),
                    ops.merge_topk(*args, k=k, interpret=True))


@pytest.mark.parametrize("pred", [0, 1, 2])
@pytest.mark.parametrize("q,nd,kb,k", [(1, 64, 5, 5), (7, 192, 41, 41),
                                       (25, 512, 10, 10)])
def test_fused_live_xla_matches_kernel(pred, q, nd, kb, k, rng):
    qv, qb, dvec, dn, db = _tie_case(rng, q, nd)
    ci = rng.integers(0, 4096, (q, kb)).astype(np.int32)
    ci[rng.random((q, kb)) < 0.2] = -1
    cd = np.round(rng.normal(size=(q, kb)).astype(np.float32) ** 2, 1)
    n_pad = (4096 + nd + 4095) // 4096 * 4096
    tomb = rng.random(n_pad) < 0.3
    tw = jnp.asarray(np.packbits(tomb, bitorder="little").view(np.uint32))
    args = (qv, qb, jnp.asarray(ci), jnp.asarray(cd), dvec, dn, db.T,
            jnp.int32(4096), tw)
    _assert_bitwise(ops.fused_live_topk(*args, pred=pred, k=k),
                    ops.fused_live_topk(*args, pred=pred, k=k,
                                        interpret=True))
    sel = jnp.asarray(np.unique(
        rng.integers(0, nd, nd // 2)).astype(np.int32))
    argsel = (qv, qb, jnp.asarray(ci), jnp.asarray(cd), dvec, dn, db.T,
              sel, jnp.int32(4096), tw)
    _assert_bitwise(ops.fused_live_topk_select(*argsel, pred=pred, k=k),
                    ops.fused_live_topk_select(*argsel, pred=pred, k=k,
                                               interpret=True))
