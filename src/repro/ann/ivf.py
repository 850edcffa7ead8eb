"""IVF coarse quantizer: k-means build (numpy, offline) + padded list layout.

Lists are stored as a dense padded `[nlist, max_list]` int32 matrix (−1
padding), and each row's list as `row_list`. The methods that search an
IVF (ivf_gamma, postfilter, sieve's fallback) choose a query's probed
lists with `probed_lists` and score them by a masked scan of the whole
base, which reads the probed lists as a bitmap (`probe_words`) against
`row_list`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import topk


@dataclasses.dataclass
class IVFIndex:
    centroids: np.ndarray       # [nlist, d] float32
    centroid_norms: np.ndarray  # [nlist] float32
    lists: np.ndarray           # [nlist, max_list] int32, −1 pad
    list_len: np.ndarray        # [nlist] int32
    # [N] int32, each row's list (−1: none); filled by the methods whose
    # scan reads it (`with_row_list`), from `lists`, and never persisted
    row_list: np.ndarray | None = None


def row_lists(lists: np.ndarray, n: int) -> np.ndarray:
    """[n] int32: the list that holds each row, −1 for a row no list holds
    (one a `max_list_cap` dropped)."""
    out = np.full(n, -1, dtype=np.int32)
    rows_c, _ = np.nonzero(lists >= 0)
    out[lists[lists >= 0]] = rows_c
    return out


def with_row_list(index: IVFIndex, n: int) -> IVFIndex:
    return dataclasses.replace(index, row_list=row_lists(index.lists, n))


def probed_lists(qvecs, centroids, cnorms, nprobe: int):
    """[Q, nprobe] ids of the lists each query probes: its nearest
    centroids."""
    cd = topk.score_all(qvecs, centroids, cnorms)
    return jax.lax.top_k(-cd, nprobe)[1]


def probe_words(probe, nlist: int):
    """[Q, ceil(nlist / 32)] uint32 bitmap of each query's probed lists
    (`probe` [Q, nprobe]): bit ``l & 31`` of word ``l >> 5`` set for each
    probed list l, the layout `ops.ivf_scan_topk` reads."""
    nq = probe.shape[0]
    hit = jnp.zeros((nq, -(-nlist // 32) * 32), jnp.uint32).at[
        jnp.arange(nq)[:, None], probe].set(1)
    return jnp.sum(hit.reshape(nq, -1, 32)
                   << jnp.arange(32, dtype=jnp.uint32), axis=2,
                   dtype=jnp.uint32)


def kmeans(x: np.ndarray, k: int, iters: int = 8, seed: int = 0,
           sample: int = 20000) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    if n > sample:
        x_fit = x[rng.choice(n, sample, replace=False)]
    else:
        x_fit = x
    k = min(k, x_fit.shape[0])
    cent = x_fit[rng.choice(x_fit.shape[0], k, replace=False)].copy()
    for _ in range(iters):
        d = (cent ** 2).sum(1)[None, :] - 2.0 * x_fit @ cent.T
        assign = d.argmin(1)
        for j in range(k):
            m = assign == j
            if m.any():
                cent[j] = x_fit[m].mean(0)
    return cent.astype(np.float32)


def assign_to_centroids(x: np.ndarray, cent: np.ndarray, block: int = 8192) -> np.ndarray:
    out = np.empty(x.shape[0], dtype=np.int64)
    cn = (cent ** 2).sum(1)
    for s in range(0, x.shape[0], block):
        xb = x[s:s + block]
        d = cn[None, :] - 2.0 * xb @ cent.T
        out[s:s + block] = d.argmin(1)
    return out


def pack_lists(assign: np.ndarray, nlist: int,
               max_list_cap: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Cluster assignments -> (`[nlist, max_list]` padded lists, fill counts).

    Each list fills in ascending row-id order and overflowing lists drop
    their highest row ids — the vectorised form of the original
    one-row-at-a-time fill loop, shared by `build_ivf` and `graft_ivf`
    so both produce the same layout by construction.
    """
    n = assign.shape[0]
    lens = np.bincount(assign, minlength=nlist)
    max_list = int(lens.max()) if lens.size else 1
    if max_list_cap is not None:
        max_list = min(max_list, max_list_cap)
    lists = np.full((nlist, max_list), -1, dtype=np.int32)
    order = np.argsort(assign, kind="stable")
    starts = np.zeros(nlist + 1, dtype=np.int64)
    np.cumsum(lens, out=starts[1:])
    pos = np.arange(n, dtype=np.int64) - starts[assign[order]]
    ok = pos < max_list
    lists[assign[order][ok], pos[ok]] = order[ok].astype(np.int32)
    return lists, np.minimum(lens, max_list).astype(np.int32)


def build_ivf(vectors: np.ndarray, nlist: int, *, seed: int = 0,
              max_list_cap: int | None = None) -> IVFIndex:
    cent = kmeans(vectors, nlist, seed=seed)
    nlist = cent.shape[0]
    assign = assign_to_centroids(vectors, cent)
    lists, fill = pack_lists(assign, nlist, max_list_cap)
    return IVFIndex(centroids=cent,
                    centroid_norms=(cent ** 2).sum(1).astype(np.float32),
                    lists=lists, list_len=fill)


def graft_ivf(old: IVFIndex, new_vectors: np.ndarray, old_to_new: np.ndarray,
              *, max_list_cap: int | None = None) -> IVFIndex:
    """Splice a compacted dataset into an existing IVF without re-running
    k-means.

    Centroids stay frozen; surviving rows keep their old cluster (their
    vector didn't change, so re-running `assign_to_centroids` would give
    the same argmin), carried through the id remap `old_to_new`
    (old row -> new row, −1 = deleted). Only rows with no carried
    assignment — compacted delta rows plus any old rows a capped layout
    had dropped — are assigned fresh. Bit-identical to re-assigning and
    re-packing every row of `new_vectors` against the frozen centroids,
    at O(|new rows| · nlist) instead of O(n · nlist) distance work.
    """
    nlist = old.centroids.shape[0]
    n_new = new_vectors.shape[0]
    assign = np.full(n_new, -1, dtype=np.int64)
    rows_c, _ = np.nonzero(old.lists >= 0)
    mapped = old_to_new[old.lists[old.lists >= 0].astype(np.int64)]
    keep = mapped >= 0
    assign[mapped[keep]] = rows_c[keep]
    un = np.nonzero(assign < 0)[0]
    if un.size:
        assign[un] = assign_to_centroids(new_vectors[un], old.centroids)
    lists, fill = pack_lists(assign, nlist, max_list_cap)
    return IVFIndex(centroids=old.centroids, centroid_norms=old.centroid_norms,
                    lists=lists, list_len=fill)
