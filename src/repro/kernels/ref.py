"""Pure-jnp oracles for the Pallas kernels (bitwise-identical semantics).

The oracles take the plain row-major [N, W] bitmaps, independent of the
kernels' word-major layout, and score at full f32 matmul precision (a
TPU's default f32 matmul rounds its inputs to bf16)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _scores(qvecs, base, norms):
    return norms[None, :].astype(jnp.float32) - 2.0 * jnp.dot(
        qvecs, base.T, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def predicate_mask_ref(bitmaps, qbms, pred: int):
    """bitmaps [N, W], qbms [Q, W] -> bool [Q, N]."""
    b = bitmaps[None, :, :]
    q = qbms[:, None, :]
    if pred == 0:
        return jnp.all(b == q, axis=-1)
    if pred == 1:
        return jnp.all((b & q) == q, axis=-1)
    if pred == 2:
        return jnp.any((b & q) != 0, axis=-1)
    raise ValueError(pred)


def masked_topk_ref(qvecs, qbms, base, norms, bitmaps, *, pred: int, k: int):
    """Exact masked top-k: ids [Q, k] i32 (−1 pad), dists [Q, k] f32.

    k may exceed N (a delta segment smaller than the requested width):
    the candidate axis is padded so the surplus comes back as −1/+inf."""
    scores = _scores(qvecs, base, norms)
    mask = predicate_mask_ref(bitmaps, qbms, pred)
    s = jnp.where(mask, scores, jnp.inf)
    if k > s.shape[1]:
        s = jnp.concatenate(
            [s, jnp.full((s.shape[0], k - s.shape[1]), jnp.inf, s.dtype)],
            axis=1)
    neg, idx = jax.lax.top_k(-s, k)
    ids = jnp.where(jnp.isinf(neg), -1, idx).astype(jnp.int32)
    return ids, -neg


def selectivity_ref(qbms, bitmaps, *, pred: int):
    return jnp.sum(predicate_mask_ref(bitmaps, qbms, pred),
                   axis=1).astype(jnp.int32)


def fused_live_topk_ref(qvecs, qbms, cand_ids, cand_dists, dvec, dnorms,
                        dbm, base_n, tomb, *, pred: int, k: int):
    """Oracle for the fused live read: tombstone-mask the routed base
    candidates, brute-force the delta rows (global id = base_n + row),
    concatenate base-first (ties resolve to base, matching the kernel's
    fold order) and extract the k smallest. `tomb` is bool [n_total]."""
    nd = dvec.shape[0]
    d_ids = base_n + jnp.arange(nd, dtype=jnp.int32)
    scores = _scores(qvecs, dvec, dnorms)
    mask = predicate_mask_ref(dbm, qbms, pred)
    live = ~tomb[jnp.clip(d_ids, 0, tomb.shape[0] - 1)]
    s = jnp.where(mask & live[None, :], scores, jnp.inf)

    ci = cand_ids.astype(jnp.int32)
    dead = tomb[jnp.clip(ci, 0, tomb.shape[0] - 1)] | (ci < 0)
    cd = jnp.where(dead | ~jnp.isfinite(cand_dists), jnp.inf, cand_dists)

    q = qvecs.shape[0]
    all_d = jnp.concatenate([cd, s], axis=1)
    all_i = jnp.concatenate(
        [jnp.where(jnp.isinf(cd), -1, ci),
         jnp.broadcast_to(d_ids[None, :], (q, nd))], axis=1)
    if k > all_d.shape[1]:
        pad = k - all_d.shape[1]
        all_d = jnp.concatenate(
            [all_d, jnp.full((q, pad), jnp.inf, all_d.dtype)], axis=1)
        all_i = jnp.concatenate(
            [all_i, jnp.full((q, pad), -1, all_i.dtype)], axis=1)
    neg, sel = jax.lax.top_k(-all_d, k)
    out_i = jnp.take_along_axis(all_i, sel, axis=1)
    out_i = jnp.where(jnp.isinf(neg), -1, out_i).astype(jnp.int32)
    return out_i, -neg


def merge_topk_ref(ids, dists, *, k: int | None = None):
    """Cross-shard merge oracle: flatten [S, Q, K] candidates to
    [Q, S*K] and re-extract the k smallest. Invalid slots (id −1 or
    non-finite dist) come back as id −1 / dist +inf, trailing. k may
    exceed S*K — the candidate axis is padded with invalid slots."""
    s, q, kk = ids.shape
    if k is None:
        k = kk
    i_all = jnp.moveaxis(ids, 0, 1).reshape(q, s * kk)
    d_all = jnp.moveaxis(dists, 0, 1).reshape(q, s * kk)
    d_all = jnp.where((i_all < 0) | ~jnp.isfinite(d_all), jnp.inf, d_all)
    if k > s * kk:
        d_all = jnp.concatenate(
            [d_all, jnp.full((q, k - s * kk), jnp.inf, d_all.dtype)], axis=1)
        i_all = jnp.concatenate(
            [i_all, jnp.full((q, k - s * kk), -1, i_all.dtype)], axis=1)
    neg, sel = jax.lax.top_k(-d_all, k)
    out_ids = jnp.take_along_axis(i_all, sel, axis=1)
    out_ids = jnp.where(jnp.isinf(neg), -1, out_ids).astype(jnp.int32)
    return out_ids, -neg
