"""The benchmark's own generators against the program's: the vectorised
label draw reproduces `repro.data.ann_synth`'s labels per row and Zipf
rank curve, rows come out in the program's group order, and the same
seed gives the same inputs."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import gen  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "bench", "configs",
                                   "hc768-exact.json")))["corpus"]
RANKS = [0, 4, 9, 49, 99, 299]


def _rank_curve(freq):
    f = np.sort(np.asarray(freq, dtype=np.float64))[::-1]
    return (np.cumsum(f) / f.sum())[RANKS]


@pytest.mark.parametrize("seed", [1, 2])
def test_labels_match_ann_synth(seed):
    """Labels per row within 2% of ann_synth's mean, and the share of
    label occurrences held by the top 1/5/10/50/100/300 labels within
    0.03 (n = 20,000, the synth_768d_hc label distribution)."""
    from repro.data.ann_synth import DatasetSpec, synthesize

    n = 20000
    ds = synthesize(DatasetSpec(
        "ref", n, 8, SPEC["universe"], SPEC["latent_dim"], SPEC["n_clusters"],
        SPEC["zipf_a"], SPEC["avg_labels"], SPEC["coupling"], SPEC["noise"],
        seed))
    ref_counts = np.unpackbits(ds.bitmaps.view(np.uint8), axis=1,
                               bitorder="little").sum(1)
    rng = gen.rng_of(seed, 0)
    assign = rng.integers(0, SPEC["n_clusters"], size=n)
    bm, counts = gen.draw_labels(rng, assign, SPEC)
    got = np.unpackbits(bm.view(np.uint8), axis=1, bitorder="little").sum(1)
    assert np.array_equal(got, counts)
    assert abs(counts.mean() - ref_counts.mean()) / ref_counts.mean() < 0.02
    np.testing.assert_allclose(
        _rank_curve(gen.label_frequency(bm, SPEC["universe"])),
        _rank_curve(gen.label_frequency(ds.bitmaps, SPEC["universe"])),
        atol=0.03)


def test_corpus_is_in_program_order_and_seeded():
    from repro.ann.dataset import ANNDataset

    spec = dict(SPEC, n=3000, dim=48)
    a = gen.make_corpus(spec, 2 ** 31 + 11)
    b = gen.make_corpus(spec, 2 ** 31 + 11)
    c = gen.make_corpus(spec, 5)
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.bitmaps, b.bitmaps)
    assert not np.array_equal(a.vectors, c.vectors)
    ds, order = ANNDataset.from_packed("x", a.vectors, a.bitmaps,
                                       spec["universe"], return_order=True)
    assert np.array_equal(order, np.arange(spec["n"]))
    np.testing.assert_allclose(ds.norms_sq, a.norms_sq, rtol=1e-6)
    assert a.vectors.shape == (3000, 48) and a.vectors.dtype == np.float32


def test_queries_and_arrivals_follow_the_mix():
    spec = dict(SPEC, n=2000, dim=32)
    corpus = gen.make_corpus(spec, 3)
    pool = gen.query_pool(corpus, 40, 7)
    assert pool.vectors.shape == (120, 32)
    assert np.bincount(pool.preds).tolist() == [40, 40, 40]
    labels = np.unpackbits(pool.bitmaps.view(np.uint8), axis=1,
                           bitorder="little").sum(1)
    assert (labels[pool.preds == 1] <= 3).all()
    assert ((labels[pool.preds == 2] >= 1) & (labels[pool.preds == 2] <= 8)).all()
    mix = {"rate_qps": 200, "pred_weights": [1, 1, 1], "pool_seed": 5}
    due = gen.arrivals(mix, 5.0, 11)
    assert due.size == 1000 and (np.diff(due) >= 0).all()
    assert 0 <= due[0] and due[-1] < 5.0
    assert np.array_equal(due, gen.arrivals(mix, 5.0, 11))
    order = gen.request_order(pool, mix, 1000, 11)
    assert np.bincount(pool.preds[order]).tolist() == [334, 333, 333]


@pytest.mark.parametrize("seeds", [(11, 12), (2 ** 31 + 5, 3)])
def test_seeds_reorder_the_same_work(seeds):
    """Two seeds offer the same arrival gaps, predicates and batches of
    the same queries, in other orders."""
    spec = dict(SPEC, n=2000, dim=32)
    pool = gen.query_pool(gen.make_corpus(spec, 3), 64, 7)
    mix = {"rate_qps": 100, "pred_weights": [1, 1, 1], "pool_seed": 5}
    a, b = (gen.arrivals(mix, 4.0, s) for s in seeds)
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)), atol=1e-12)
    oa, ob = (gen.request_order(pool, mix, 400, s) for s in seeds)
    assert not np.array_equal(oa, ob)
    assert np.array_equal(np.bincount(pool.preds[oa]),
                          np.bincount(pool.preds[ob]))
    ba, bb = (gen.batch_rows(pool, 16, s) for s in seeds)
    assert len(ba) == len(bb) == 12
    assert [pool.preds[r[0]] for r in ba] == [0, 1, 2] * 4
    assert all((pool.preds[r] == pool.preds[r[0]]).all() for r in ba)
    assert not all(np.array_equal(x, y) for x, y in zip(ba, bb))
    assert np.array_equal(np.sort(np.concatenate(ba)),
                          np.sort(np.concatenate(bb)))
