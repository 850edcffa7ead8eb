"""The plain reference and the comparison that decides `correct`.

Nothing here imports the program. The reference works from the
benchmark's own corpus and queries (`bench.gen`) and its own router
artifact (`bench.router`):

* exact masked top-k in plain `jax.numpy`, scores ‖v‖² − 2·q·v at
  `Precision.HIGHEST`, the predicate evaluated word by word over the
  row-major bitmaps, one `lax.top_k` per query chunk and block of rows
  (the corpus is split into one contiguous block per chip of the cell;
  blocks merge on the host in the order `lax.top_k` gives over the
  whole corpus);
* exact squared distances of any ids in float64 on the host;
* selectivity as exact match counts over every row, on the device.

`check` turns the served answers into the numbers compared with the
configuration's limits. The control is the same reference one
precision step below what the configuration states (its `control`:
three bfloat16 passes, `bf16x3`, for float32 scores at HIGHEST; one
pass, `bf16`, for other float32 scores), put in the program's place to
show that the comparison fails it.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np


# ---------------------------------------------------------------------------
# plain jnp pieces
# ---------------------------------------------------------------------------

def _mask(bitmaps, qbms, pred: int):
    """[Q, N] predicate mask over row-major [N, W] bitmaps, word-looped."""
    import jax.numpy as jnp

    acc = None
    for i in range(bitmaps.shape[1]):
        b = bitmaps[None, :, i]
        qw = qbms[:, i, None]
        if pred == 0:
            hit = b == qw
        elif pred == 1:
            hit = (b & qw) == qw
        else:
            hit = (b & qw) != 0
        acc = hit if acc is None else (acc | hit if pred == 2 else acc & hit)
    return acc


def _split_bf16(x):
    """x = hi + lo + rest, hi and lo bfloat16 values held in float32.
    `reduce_precision` rounds where it stands: a convert pair to bfloat16
    and back may be elided by the compiler (excess precision), which
    would leave hi = x and lo = 0 in one place and not in another."""
    import jax

    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def lowered(x, mode: str):
    """`x` as a one-pass product in `mode` reads it: `bf16` rounds to
    bfloat16, `highest` leaves it."""
    if mode == "bf16":
        return _split_bf16(x)[0]
    if mode == "highest":
        return x
    raise ValueError(mode)


def dots(q, v, mode: str):
    """[Q, N] q·vᵀ. `highest`: float32 at HIGHEST. `bf16`: one pass of
    bfloat16 products, float32 accumulation. `bf16x3`: the three
    bfloat16 passes of `Precision.HIGH` (hi·hi + hi·lo + lo·hi, the
    lo·lo term dropped), spelled out so it reads the same on any
    backend."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    if mode == "bf16x3":
        qh, ql = _split_bf16(q)
        vh, vl = _split_bf16(v)
        return (jnp.dot(qh, vh.T, precision=hp) + jnp.dot(qh, vl.T, precision=hp)
                + jnp.dot(ql, vh.T, precision=hp))
    return jnp.dot(lowered(q, mode), lowered(v, mode).T, precision=hp)


def _topk_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("pred", "k", "mode"))
    def fn(qv, qb, vectors, norms, bitmaps, *, pred, k, mode):
        s = norms[None, :] - 2.0 * dots(qv, vectors, mode)
        s = jnp.where(_mask(bitmaps, qb, pred), s, jnp.inf)
        neg, idx = jax.lax.top_k(-s, k)
        ok = jnp.isfinite(neg)
        return jnp.where(ok, idx, -1).astype(jnp.int32), jnp.where(ok, -neg, jnp.inf)

    return fn


@dataclasses.dataclass
class DeviceBlock:
    """Rows [offset, offset + n) of the corpus on one device."""
    offset: int
    vectors: object
    norms: object
    bitmaps: object


def block_bounds(n: int, blocks: int) -> list[int]:
    """Row bounds of `blocks` contiguous blocks of nearly equal size."""
    return [n * b // blocks for b in range(blocks + 1)]


def to_device(corpus, devices) -> list[DeviceBlock]:
    """The corpus split into one contiguous block of rows per device of
    `devices` (a device may repeat)."""
    import jax

    bounds = block_bounds(corpus.n, len(devices))
    return [DeviceBlock(a, *(jax.device_put(x[a:b], d) for x in (
                corpus.vectors, corpus.norms_sq, corpus.bitmaps)))
            for a, b, d in zip(bounds[:-1], bounds[1:], devices)]


def merge(ids: np.ndarray, scores: np.ndarray, k: int):
    """[Q, C] candidates with global ids (−1 / +inf pads) -> the k best
    of each row by (score, id): the order `lax.top_k` of the negated
    scores gives over the whole corpus, where the lower index wins a
    tie."""
    key_id = np.where(ids >= 0, ids.astype(np.int64), np.iinfo(np.int64).max)
    order = np.lexsort((key_id, scores), axis=1)[:, :k]
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(scores, order, axis=1))


def topk(dev: list[DeviceBlock], qv: np.ndarray, qb: np.ndarray,
         preds: np.ndarray, k: int, *, mode: str = "highest", chunk: int = 32):
    """Exact masked top-k of every query: ([P, k] ids, [P, k] f32
    scores, −1 / +inf pads). Queries run in chunks of one predicate, each
    chunk on every block of rows at once."""
    import jax

    fn = _topk_fn()
    p = qv.shape[0]
    ids = np.full((p, k), -1, np.int32)
    sc = np.full((p, k), np.inf, np.float32)
    for pred in np.unique(preds):
        rows = np.nonzero(preds == pred)[0]
        for s in range(0, rows.size, chunk):
            r = rows[s:s + chunk]
            pad = np.concatenate([r, np.repeat(r[-1:], chunk - r.size)])
            parts = jax.device_get([
                fn(qv[pad], qb[pad], b.vectors, b.norms, b.bitmaps,
                   pred=int(pred), k=k, mode=mode) for b in dev])
            i, d = merge(
                np.concatenate([np.where(i >= 0, i + b.offset, -1)
                                for (i, _), b in zip(parts, dev)],
                               axis=1).astype(np.int32),
                np.concatenate([d for _, d in parts], axis=1), k)
            ids[r], sc[r] = i[:r.size], d[:r.size]
    return ids, sc


def served_distances(scores: np.ndarray, ids: np.ndarray,
                     qv: np.ndarray) -> np.ndarray:
    """Scores + ‖q‖² in float32, NaN at −1: what a server that scored
    with these numbers would report as squared distances."""
    qn = np.sum(qv.astype(np.float32) ** 2, axis=1)
    d = np.maximum(scores + qn[:, None], 0.0)
    return np.where(ids >= 0, d, np.nan).astype(np.float32)


# ---------------------------------------------------------------------------
# host references
# ---------------------------------------------------------------------------

def distances64(vectors: np.ndarray, qv: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """[R, k] exact squared L2 in float64; NaN at −1."""
    safe = np.clip(ids, 0, vectors.shape[0] - 1)
    diff = vectors[safe].astype(np.float64) - qv[:, None, :].astype(np.float64)
    d = np.einsum("rkd,rkd->rk", diff, diff)
    return np.where(ids >= 0, d, np.nan)


def eval_pred(row_bm: np.ndarray, q_bm: np.ndarray, pred: int) -> np.ndarray:
    """row_bm [..., W] against q_bm broadcast to it -> bool [...]."""
    if pred == 0:
        return (row_bm == q_bm).all(-1)
    if pred == 1:
        return ((row_bm & q_bm) == q_bm).all(-1)
    return ((row_bm & q_bm) != 0).any(-1)


def _count_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("pred",))
    def fn(qb, bitmaps, *, pred):
        return jnp.sum(_mask(bitmaps, qb, pred), axis=1, dtype=jnp.int32)

    return fn


def match_counts(bitmaps, qb: np.ndarray, preds: np.ndarray,
                 chunk: int = 32) -> np.ndarray:
    """[P] exact number of rows of the device bitmaps [N, W] that match
    each query's predicate."""
    fn = _count_fn()
    out = np.zeros(qb.shape[0], dtype=np.int64)
    for pred in np.unique(preds):
        rows = np.nonzero(preds == pred)[0]
        for s in range(0, rows.size, chunk):
            r = rows[s:s + chunk]
            pad = np.concatenate([r, np.repeat(r[-1:], chunk - r.size)])
            out[r] = np.asarray(fn(qb[pad], bitmaps, pred=int(pred)))[:r.size]
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Answers:
    """Distinct served answers: pool index, ids, distances, decisions."""
    pool_idx: np.ndarray           # [R]
    ids: np.ndarray                # [R, k] int
    dists: np.ndarray              # [R, k] float32
    decisions: list                # [R] (method, ps_id)
    weight: np.ndarray             # [R] times this answer was served


def distinct(pool_idx, ids, dists, decisions) -> Answers:
    """Collapse repeated identical answers to one row each."""
    seen: dict = {}
    order = []
    for j in range(len(pool_idx)):
        key = (int(pool_idx[j]), ids[j].tobytes(), dists[j].tobytes(),
               tuple(decisions[j]))
        if key in seen:
            seen[key] += 1
        else:
            seen[key] = 1
            order.append((key, j))
    sel = np.asarray([j for _, j in order], dtype=np.int64)
    if sel.size == 0:
        k = ids.shape[1] if ids.ndim == 2 else 0
        return Answers(np.zeros(0, np.int64), np.zeros((0, k), np.int32),
                       np.zeros((0, k), np.float32), [], np.zeros(0))
    return Answers(np.asarray(pool_idx)[sel], ids[sel], dists[sel],
                   [tuple(decisions[j]) for j in sel],
                   np.asarray([seen[key] for key, _ in order], np.float64))


def recall_rows(ids: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-row recall@k against the reference ids (1 where it has none)."""
    out = np.ones(ids.shape[0])
    for r in range(ids.shape[0]):
        want = set(int(i) for i in ref[r] if i >= 0)
        if want:
            got = set(int(i) for i in ids[r] if i >= 0)
            out[r] = len(got & want) / min(ref.shape[1], len(want))
    return out


def check(ans: Answers, corpus, pool, ref_ids: np.ndarray, ref_decisions,
          pool_methods, *, exact: bool) -> dict:
    """Numbers compared with the configuration's limits, plus recall.

    * `bad_rows`: answers that break a guarantee: an id out of range, a
      −1 pad before a returned id, a duplicate id, a row that fails the
      query's predicate, a non-finite distance at a returned id or a
      number at a pad, a method outside the pool; for an exact
      configuration also fewer or more ids than the reference returns;
    * `dist_err`: widest gap between a returned distance and the float64
      distance of its id, over ‖q‖² (the scale the scores are computed
      at);
    * `rank_gap` (exact configurations): widest amount by which the
      j-th returned distance exceeds the reference's j-th, over ‖q‖²;
    * `route_diff`: share of answers whose (method, setting) differs
      from the reference router's decision, over the answers whose
      reference decision is firm (`ref_decisions` entry not None);
    * `route_firm`: the share of answers with a firm reference decision;
    * `empty_share`: share of answers with no id although the reference
      finds at least one row that satisfies the predicate.
    """
    n = corpus.n
    r_ = ans.ids.shape[0]
    if r_ == 0:
        return {"bad_rows": 1, "dist_err": float("inf"),
                "rank_gap": float("inf"), "route_diff": 1.0, "route_firm": 0.0,
                "empty_share": 1.0,
                "recall": 0.0, "checked": 0}
    ids = ans.ids.astype(np.int64)
    qv = pool.vectors[ans.pool_idx]
    qb = pool.bitmaps[ans.pool_idx]
    preds = pool.preds[ans.pool_idx]
    valid = ids >= 0
    bad = ((ids < -1) | (ids >= n)).any(1)
    bad |= (~valid[:, :-1] & valid[:, 1:]).any(1)
    s = np.sort(np.where(valid, ids, -1 - np.arange(ids.shape[1])), axis=1)
    bad |= (s[:, 1:] == s[:, :-1]).any(1)
    safe = np.clip(ids, 0, n - 1)
    ok = np.ones_like(valid)
    for p in np.unique(preds):
        r = preds == p
        ok[r] = eval_pred(corpus.bitmaps[safe[r]], qb[r][:, None, :], int(p))
    bad |= (valid & ~ok).any(1)
    d = ans.dists.astype(np.float64)
    bad |= (valid & ~np.isfinite(d)).any(1)
    bad |= (~valid & ~np.isnan(d)).any(1)
    bad |= np.asarray([m not in pool_methods for m, _ in ans.decisions])
    ref = ref_ids[ans.pool_idx]
    if exact:
        bad |= valid.sum(1) != (ref >= 0).sum(1)
    qn = np.sum(qv.astype(np.float64) ** 2, axis=1)[:, None]
    d64 = distances64(corpus.vectors, qv, np.where(bad[:, None], -1, ids))
    err = np.abs(d - d64) / qn
    ref_dec = [ref_decisions[i] for i in ans.pool_idx]
    firm = [tuple(a) != tuple(b) for a, b in zip(ans.decisions, ref_dec)
            if b is not None]
    out = {"bad_rows": int(bad.sum()),
           "dist_err": float(np.nanmax(err)) if np.isfinite(err).any() else 0.0,
           "route_diff": float(np.mean(firm)) if firm else 0.0,
           "route_firm": len(firm) / r_,
           "empty_share": float(np.mean(~valid.any(1) & (ref >= 0).any(1))),
           "checked": int(r_)}
    if exact:
        g = np.sort(np.nan_to_num(d64, nan=np.inf), axis=1)
        rd = np.sort(np.nan_to_num(distances64(corpus.vectors, qv, ref),
                                   nan=np.inf), axis=1)
        both = np.isfinite(g) & np.isfinite(rd)
        with np.errstate(invalid="ignore"):
            gap = np.where(both, (g - rd) / qn, -np.inf)
        out["rank_gap"] = float(max(gap.max(), 0.0))
    rec = recall_rows(ids, ref)
    out["recall"] = float(np.sum(rec * ans.weight) / np.sum(ans.weight))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, number, limit)]) for every limited number."""
    rows = [(name, numbers[name], float(lim)) for name, lim in limits.items()
            if name in numbers]
    return all(v <= lim for _, v, lim in rows), rows
