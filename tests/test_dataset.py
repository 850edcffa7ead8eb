"""`ANNDataset.from_packed` on rows that are already in label-set group
order keeps them where they are, and the squared norms taken in row
blocks are the whole corpus's float64 sums to the bit."""

import numpy as np
import pytest

from repro.ann import dataset
from repro.ann.dataset import ANNDataset


def _corpus(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    v = (rng.standard_normal((n, 24))
         * rng.uniform(0.01, 100, size=(n, 1))).astype(np.float32)
    bm = rng.integers(0, 8, size=(n, 2)).astype(np.uint32)
    return v, bm


def test_group_sorted_rows_are_kept_without_a_copy(tiny_ds):
    ds, order = ANNDataset.from_packed("again", tiny_ds.vectors,
                                       tiny_ds.bitmaps, tiny_ds.universe,
                                       return_order=True)
    np.testing.assert_array_equal(order, np.arange(tiny_ds.n))
    assert np.shares_memory(ds.vectors, tiny_ds.vectors)
    for f in ("bitmaps", "group_of", "group_bitmaps", "group_start",
              "group_size", "norms_sq"):
        np.testing.assert_array_equal(getattr(ds, f), getattr(tiny_ds, f))


def test_unsorted_rows_are_grouped_as_before():
    v, bm = _corpus(5000)
    ds, order = ANNDataset.from_packed("u", v, bm, 64, return_order=True)
    assert not np.array_equal(order, np.arange(v.shape[0]))
    assert np.all(np.diff(ds.group_of) >= 0)
    np.testing.assert_array_equal(ds.vectors, v[order])
    np.testing.assert_array_equal(ds.bitmaps, bm[order])
    again, order2 = ANNDataset.from_packed("s", ds.vectors, ds.bitmaps, 64,
                                           return_order=True)
    np.testing.assert_array_equal(order2, np.arange(v.shape[0]))
    np.testing.assert_array_equal(again.norms_sq, ds.norms_sq)


@pytest.mark.parametrize("n", [0, 1, dataset.NORM_BLOCK_ROWS - 1,
                               dataset.NORM_BLOCK_ROWS,
                               2 * dataset.NORM_BLOCK_ROWS + 3])
def test_blocked_norms_equal_one_float64_pass(n):
    v, _ = _corpus(n, seed=n)
    want = np.sum(v.astype(np.float64) ** 2, axis=1).astype(np.float32)
    got = dataset.norms_sq(v)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
