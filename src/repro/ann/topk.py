"""Distance + top-k primitives shared by all filtered-ANN methods.

Distances are squared-L2 ranked via ``||v||² − 2·v·q`` (the query norm is
rank-invariant and omitted). Candidate top-k runs on fixed-shape padded id
arrays with −1 padding; duplicate candidates are suppressed with the
sort-adjacency trick (equal ids ⇒ equal distances ⇒ adjacent after a stable
sort by (distance, id)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)


def score_all(qvecs: jax.Array, base: jax.Array, base_norms: jax.Array) -> jax.Array:
    """Full [Q, N] ranking scores (squared-L2 up to a per-query constant).
    Off the TPU in fixed row blocks (`ops.xla_scores`), so a row scores
    the same in a shard as in the whole corpus."""
    if jax.default_backend() != "tpu":
        from repro.kernels import ops

        return ops.xla_scores(qvecs, base, base_norms[None, :])
    return base_norms[None, :] - 2.0 * (qvecs @ base.T)


def score_candidates(qvecs: jax.Array, cand_vecs: jax.Array,
                     cand_norms: jax.Array) -> jax.Array:
    """Per-candidate scores. qvecs [Q,d], cand_vecs [Q,C,d] -> [Q,C]."""
    dots = jnp.einsum("qd,qcd->qc", qvecs, cand_vecs)
    return cand_norms - 2.0 * dots


def topk_ids(scores: jax.Array, ids: jax.Array, k: int,
             valid=None, dedup: bool = False):
    """Top-k smallest-score candidate ids.

    scores [Q, C] float32; ids [Q, C] int32 (−1 = padding); valid optional
    bool [Q, C]. Returns (ids [Q, k] int32 with −1 fill, scores [Q, k]).
    """
    bad = ids < 0
    if valid is not None:
        bad = bad | ~valid
    scores = jnp.where(bad, INF, scores)
    if dedup:
        order = jnp.argsort(scores, axis=-1, stable=True)
        s = jnp.take_along_axis(scores, order, axis=-1)
        i = jnp.take_along_axis(ids, order, axis=-1)
        dup = jnp.concatenate(
            [jnp.zeros_like(i[:, :1], dtype=bool), (i[:, 1:] == i[:, :-1]) & (i[:, 1:] >= 0)],
            axis=-1)
        s = jnp.where(dup, INF, s)
        scores, ids = s, i
    neg, idx = jax.lax.top_k(-scores, k)
    out_ids = jnp.take_along_axis(ids, idx, axis=-1)
    out_scores = -neg
    out_ids = jnp.where(jnp.isinf(out_scores), -1, out_ids)
    return out_ids.astype(jnp.int32), out_scores


def merge_topk(ids_a, scores_a, ids_b, scores_b, k: int):
    """Merge two padded top-k sets (used by the distributed all-gather merge)."""
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    scores = jnp.concatenate([scores_a, scores_b], axis=-1)
    return topk_ids(scores, ids, k, dedup=True)
