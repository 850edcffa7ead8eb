"""The benchmark's own data and traffic generators.

They are copies, vectorised, of the distributions the program uses
(`repro.data.ann_synth.synthesize` and `make_queries`, paper §6.1.3), so
that no later change to the program can move the yardstick:

* corpus vectors: Gaussian clusters on a `latent_dim`-dimensional
  manifold embedded into `dim` ambient dimensions, plus ambient noise;
  made on the device in fixed row blocks, one jitted call per block;
* corpus labels: per row 1 + Poisson(avg_labels − 1) distinct labels,
  each draw from the row's cluster-preferred pool with probability
  `coupling`, else from a global Zipf(`zipf_a`) popularity over a random
  permutation of the vocabulary;
* queries: a base vector plus Gaussian noise at 10% of the median base
  norm; EQUALITY takes the label set of a random existing row, AND 1-3
  labels of one, OR 2-8 labels drawn by label frequency;
* arrivals: a fixed number of requests (rate × seconds) at sorted
  uniform times, which is a Poisson process conditioned on its count.

The query pool, the arrival gaps and the predicate of each arrival come
from the traffic mix's own `pool_seed`; `--seed` draws only an order:
which queries of a predicate form a closed-loop batch, and the offset at
which the open-loop sequence starts. Every seed so offers the same work,
and runs with different seeds differ by the order alone.

Rows are made in the program's label-set group order (first appearance
of each distinct bitmap, stable): the labels are drawn first, sorted,
and each row's vector drawn after, so the ids the program returns index
the benchmark's own arrays directly and no 3 GB array is permuted.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

PREDS = (0, 1, 2)                  # EQUALITY, AND, OR
VEC_BLOCK = 1 << 17                # corpus rows per on-device generator call
LABEL_DRAWS = 24                   # label draws per row (first k distinct kept)


def rng_of(seed: int, *stream: int) -> np.random.Generator:
    """Generator for a whole-number seed of any size and sign, split into
    independent streams."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray            # [N, D] float32, group-sorted
    bitmaps: np.ndarray            # [N, W] uint32, group-sorted
    norms_sq: np.ndarray           # [N] float32 squared norms
    universe: int
    label_counts: np.ndarray       # [N] labels per row
    label_freq: np.ndarray         # [U] rows carrying each label

    @property
    def n(self) -> int:
        return int(self.vectors.shape[0])

    @property
    def dim(self) -> int:
        return int(self.vectors.shape[1])


def zipf_popularity(rng, universe: int, a: float) -> np.ndarray:
    pop = np.arange(1, universe + 1, dtype=np.float64) ** (-a)
    pop /= pop.sum()
    perm = rng.permutation(universe)   # label id independent of rank
    return pop[np.argsort(perm)]


def draw_labels(rng, assign: np.ndarray, spec: dict):
    """[N, W] uint32 bitmaps and [N] label counts, vectorised."""
    n = assign.shape[0]
    u, c = int(spec["universe"]), int(spec["n_clusters"])
    pop = zipf_popularity(rng, u, float(spec["zipf_a"]))
    pref_size = max(1, min(u, int(np.ceil(u / c)) + 2))
    pref = np.stack([rng.choice(u, size=pref_size, replace=False, p=pop)
                     for _ in range(c)]).astype(np.int32)
    want = np.minimum(rng.poisson(max(float(spec["avg_labels"]) - 1.0, 0.0),
                                  size=n) + 1, u)
    m = LABEL_DRAWS
    coupled = rng.random((n, m)) < float(spec["coupling"])
    from_pref = pref[assign[:, None],
                     rng.integers(0, pref_size, size=(n, m))]
    cdf = np.cumsum(pop)
    glob = np.minimum(np.searchsorted(cdf, rng.random((n, m)) * cdf[-1],
                                      side="right"), u - 1).astype(np.int32)
    cand = np.where(coupled, from_pref, glob)
    new = np.ones((n, m), dtype=bool)
    for j in range(1, m):
        new[:, j] = ~(cand[:, :j] == cand[:, j:j + 1]).any(axis=1)
    keep = new & (np.cumsum(new, axis=1) <= want[:, None])
    w = max(1, (u + 31) // 32)
    bm = np.zeros((n, w), dtype=np.uint32)
    rows = np.arange(n)
    for j in range(m):
        r = rows[keep[:, j]]
        lab = cand[r, j].astype(np.int64)
        bm[r, lab >> 5] |= np.left_shift(np.uint32(1),
                                         (lab & 31).astype(np.uint32))
    return bm, keep.sum(axis=1)


def group_order(bitmaps: np.ndarray) -> np.ndarray:
    """Stable row order by label-set group, groups numbered by first
    appearance: the order `ANNDataset.from_packed` stores rows in."""
    rows = np.ascontiguousarray(bitmaps).view(
        np.dtype((np.void, bitmaps.dtype.itemsize * bitmaps.shape[1])))[:, 0]
    _, first, inv = np.unique(rows, return_index=True, return_inverse=True)
    rank = np.empty(first.size, dtype=np.int64)
    rank[np.argsort(first, kind="stable")] = np.arange(first.size)
    return np.argsort(rank[inv.ravel()], kind="stable")


def label_frequency(bitmaps: np.ndarray, universe: int,
                    block: int = 1 << 16) -> np.ndarray:
    """[U] number of rows that carry each label."""
    out = np.zeros(bitmaps.shape[1] * 32, dtype=np.int64)
    for s in range(0, bitmaps.shape[0], block):
        b = np.ascontiguousarray(bitmaps[s:s + block]).view(np.uint8)
        out += np.unpackbits(b, axis=1, bitorder="little").sum(
            axis=0, dtype=np.int64)
    return out[:universe]


def _vector_block_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("dim", "latent"))
    def block(key, centers, basis, assign, noise, *, dim, latent):
        k1, k2 = jax.random.split(key)
        lat = centers[assign] + jax.random.normal(
            k1, (assign.shape[0], latent), jnp.float32)
        amb = jnp.dot(lat, basis, precision=jax.lax.Precision.HIGHEST)
        v = amb + noise * jax.random.normal(
            k2, (assign.shape[0], dim), jnp.float32)
        return v, jnp.sum(v * v, axis=1)

    return block


def corpus_vectors(seed: int, assign: np.ndarray, spec: dict):
    """[N, D] float32 vectors and their [N] squared norms, made on the
    default device block by block."""
    import jax
    import jax.numpy as jnp

    d, m, c = int(spec["dim"]), int(spec["latent_dim"]), int(spec["n_clusters"])
    key = jax.random.PRNGKey(int(rng_of(seed, 1).integers(0, 2 ** 31 - 1)))
    kc, kb, kr = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (c, m), jnp.float32) * 4.0
    basis = jax.random.normal(kb, (m, d), jnp.float32) / np.float32(np.sqrt(m))
    block = _vector_block_fn()
    n = assign.shape[0]
    bs = min(VEC_BLOCK, n)
    out = np.empty((n, d), dtype=np.float32)
    norms = np.empty(n, dtype=np.float32)
    noise = jnp.float32(spec["noise"])
    for i, s in enumerate(range(0, n, bs)):
        a = np.zeros(bs, dtype=np.int32)
        part = assign[s:s + bs]
        a[:part.size] = part
        v, nv = jax.device_get(block(jax.random.fold_in(kr, i), centers,
                                     basis, jnp.asarray(a), noise, dim=d,
                                     latent=m))
        out[s:s + part.size] = v[:part.size]
        norms[s:s + part.size] = nv[:part.size]
    return out, norms


def make_corpus(spec: dict, seed: int) -> Corpus:
    """The deployment's corpus from `spec` (a configuration's `corpus`)."""
    rng = rng_of(seed, 0)
    n = int(spec["n"])
    assign = rng.integers(0, int(spec["n_clusters"]), size=n).astype(np.int32)
    bitmaps, counts = draw_labels(rng, assign, spec)
    order = group_order(bitmaps)
    bitmaps, counts, assign = bitmaps[order], counts[order], assign[order]
    vectors, norms = corpus_vectors(seed, assign, spec)
    return Corpus(vectors=vectors, bitmaps=bitmaps, norms_sq=norms,
                  universe=int(spec["universe"]), label_counts=counts,
                  label_freq=label_frequency(bitmaps, int(spec["universe"])))


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QueryPool:
    vectors: np.ndarray            # [P, D] float32
    bitmaps: np.ndarray            # [P, W] uint32
    preds: np.ndarray              # [P] int predicate of each query


def _pack(labels, w: int) -> np.ndarray:
    out = np.zeros(w, dtype=np.uint32)
    for l in labels:
        out[int(l) >> 5] |= np.uint32(1) << np.uint32(int(l) & 31)
    return out


def _labels_of(bitmap: np.ndarray) -> np.ndarray:
    bits = np.unpackbits(np.ascontiguousarray(bitmap).view(np.uint8),
                         bitorder="little")
    return np.nonzero(bits)[0]


def make_queries(corpus: Corpus, pred: int, count: int, rng) -> QueryPool:
    """`count` queries of one predicate over `corpus` (paper §6.1.3)."""
    n, d = corpus.n, corpus.dim
    base = rng.integers(0, n, size=count)
    med = float(np.median(np.sqrt(corpus.norms_sq)))
    vec = corpus.vectors[base] + np.float32(0.1 * med / np.sqrt(d)) * \
        rng.standard_normal((count, d), dtype=np.float32)
    w = corpus.bitmaps.shape[1]
    p = corpus.label_freq / max(corpus.label_freq.sum(), 1)
    bms = np.zeros((count, w), dtype=np.uint32)
    for i in range(count):
        src = corpus.bitmaps[rng.integers(0, n)]
        if pred == 0:                       # EQUALITY: an existing set
            bms[i] = src
        elif pred == 1:                     # AND: 1-3 labels of one
            labs = _labels_of(src)
            take = int(rng.integers(1, min(3, labs.size) + 1))
            bms[i] = _pack(rng.choice(labs, size=take, replace=False), w)
        else:                               # OR: 2-8 by frequency
            take = int(rng.integers(2, 9))
            bms[i] = _pack(np.unique(rng.choice(corpus.universe, size=take,
                                                replace=True, p=p)), w)
    return QueryPool(vec.astype(np.float32), bms,
                     np.full(count, pred, dtype=np.int32))


def query_pool(corpus: Corpus, per_pred: int, seed: int) -> QueryPool:
    """`per_pred` distinct queries of each predicate, from `seed`."""
    parts = [make_queries(corpus, p, per_pred, rng_of(seed, 10 + p))
             for p in PREDS]
    return QueryPool(np.concatenate([q.vectors for q in parts]),
                     np.concatenate([q.bitmaps for q in parts]),
                     np.concatenate([q.preds for q in parts]))


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------

def arrivals(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix.

    `rate_qps` × `seconds` requests. Their gaps are those of sorted
    uniform times drawn from the mix's `pool_seed` (a Poisson process
    conditioned on its count), put in an order drawn from `seed`: every
    seed offers the same set of gaps, in another order."""
    count = int(round(float(mix["rate_qps"]) * seconds))
    u = np.sort(rng_of(int(mix["pool_seed"]), 20).random(count)) * seconds
    gaps = np.diff(np.concatenate([[0.0], u]))
    return np.cumsum(np.roll(gaps, -rotation(seed, count)))


def rotation(seed: int, count: int) -> int:
    """The offset by which `seed` rotates an open-loop sequence."""
    return int(rng_of(seed, 20).integers(0, max(count, 1)))


def request_order(pool: QueryPool, mix: dict, count: int, seed: int):
    """Pool indices of `count` requests. The predicates come in the mix's
    `pred_weights` proportions (EQUALITY, AND, OR; largest remainder), in
    an order drawn from `pool_seed` and rotated with the arrival gaps, so
    each gap keeps its predicate; each predicate's queries are cycled in
    an order drawn from `seed`."""
    w = np.asarray(mix.get("pred_weights", [1, 1, 1]), dtype=np.float64)
    share = w / w.sum() * count
    n = np.floor(share).astype(np.int64)
    n[np.argsort(n - share)[:count - int(n.sum())]] += 1
    preds = rng_of(int(mix["pool_seed"]), 21).permutation(
        np.repeat(np.arange(3), n))
    preds = np.roll(preds, -rotation(seed, count))
    rng = rng_of(seed, 21)
    out = np.empty(count, dtype=np.int64)
    for p in PREDS:
        idx = np.nonzero(pool.preds == p)[0]
        sel = np.nonzero(preds == p)[0]
        if sel.size:
            out[sel] = rng.permutation(idx)[np.arange(sel.size) % idx.size]
    return out


def batch_rows(pool: QueryPool, size: int, seed: int) -> list:
    """The pool cut into batches of `size` queries of one predicate, the
    rows of each predicate in an order drawn from `seed`, the batches
    cycling EQUALITY → AND → OR: every seed serves the same queries in
    other batches."""
    rng = rng_of(seed, 22)
    rows = {p: rng.permutation(np.nonzero(pool.preds == p)[0]) for p in PREDS}
    n = min(r.size for r in rows.values()) // size
    return [rows[p][i * size:(i + 1) * size] for i in range(n) for p in PREDS]
