"""Jitted wrappers around the Pallas kernels: padding to block multiples,
sentinel cleanup, backend dispatch.

`masked_topk` calls the VMEM-accumulating kernel, which emits final [Q, k]
dists/ids directly — there is no [n_blocks, Q, k] HBM intermediate and no
cross-block merge here. The legacy per-block kernel + merge survives as
`masked_topk_multiblock` purely as a parity reference for tests.

Base bitmaps are passed **word-major** ([W, N], `DeviceData.bitmaps_wm`),
the layout the kernels read lane-dense; query bitmaps stay [Q, W]. The
wrappers pad Q to a multiple of 8 sublanes, N to a multiple of 128 lanes
and the top-k width to whole vregs, and slice the padding off again.

Off TPU (``interpret=None``, the default) the top-k ops run a
**fold-identical XLA formulation** instead of the interpret-mode kernel:
the VMEM fold is a stable selection — smallest score first, ties to the
earliest-folded candidate — which is exactly `jax.lax.top_k`'s
lowest-index tie rule over the candidates laid out in fold order (base
carry first, then blocks by ascending id). The score expression is the
kernel's, so on inputs where the matmul bits agree the results are
bit-identical (the parity tests pin this on an exactly-representable
grid); on arbitrary floats the backends may differ in the last ulp of a
distance, exactly as two matmul shapes already can. Interpret mode
emulates the kernel's insertion loop per grid step at Python speed, fine
for parity tests but ~6× slower than XLA on the live read path; passing
an explicit ``interpret=True/False`` still forces the Pallas kernel.
On a TPU the default always takes the compiled kernel: there is no XLA
fallback there."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import masked_topk as mk
from repro.kernels import bitmap_filter as bf


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(x, mult, fill=0, axis=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    shape = list(x.shape)
    shape[axis] = pad
    return jnp.concatenate([x, jnp.full(shape, fill, dtype=x.dtype)],
                           axis=axis)


def _tiles(q: int, n: int, bq: int, bn: int) -> tuple[int, int]:
    """Effective (bq, bn): at most the requested tiles, shrunk to the
    problem rounded up to whole sublanes (8) and lanes (128)."""
    return min(bq, -(-max(q, 1) // 8) * 8), min(bn, mk.lane_pad(max(n, 1)))


def _tombstone_bits(tomb_words, ids):
    """Packed-tombstone lookup: ids [...] i32 global row ids -> bool dead.

    `tomb_words` is [TW] uint32 with bit ``r & 31`` of word ``r >> 5`` set
    for dead row r (numpy ``packbits(bitorder='little')`` layout). Ids are
    clipped into range before the gather: out-of-range ids (−1 pads,
    sentinel rows past the watermark) read an arbitrary bit, which is
    harmless because callers already treat them as invalid."""
    tw = tomb_words.shape[0]
    safe = jnp.clip(ids, 0, tw * 32 - 1)
    words = jnp.take(tomb_words, safe >> 5, axis=0)
    bit = jnp.right_shift(words, (safe & 31).astype(jnp.uint32))
    return (bit & jnp.uint32(1)) != 0


def _stable_topk(all_d, all_i, k):
    """k smallest of (dists, ids) laid out in kernel fold order; ties go
    to the lowest index — `jax.lax.top_k`'s documented tie rule — which
    is exactly `_fold_topk`'s first-match argmin. Invalid slots (score >=
    PAD_SCORE or id < 0) come back as −1 ids with +inf dists, trailing."""
    q, c = all_d.shape
    if k > c:
        all_d = jnp.concatenate(
            [all_d, jnp.full((q, k - c), mk.PAD_SCORE, all_d.dtype)], axis=1)
        all_i = jnp.concatenate(
            [all_i, jnp.full((q, k - c), -1, all_i.dtype)], axis=1)
    neg, sel = jax.lax.top_k(-all_d, k)
    out_i = jnp.take_along_axis(all_i, sel, axis=1)
    bad = (out_i < 0) | (-neg >= mk.PAD_SCORE)
    return jnp.where(bad, -1, out_i), jnp.where(bad, jnp.inf, -neg)


XLA_SCORE_ROWS = 512       # base rows per product of the off-TPU scores


def xla_scores(qvecs, base, norms_row):
    """`mk._scores` off the TPU, in fixed blocks of `XLA_SCORE_ROWS` base
    rows. The CPU backend rounds a row's dot product differently at
    different row counts, so one product shape for every block makes a
    row's score independent of how many rows share its scan (a shard, a
    delta segment, the whole corpus)."""
    n, d = base.shape
    pad = (-n) % XLA_SCORE_ROWS
    blocks = jnp.pad(base, ((0, pad), (0, 0))).reshape(
        -1, XLA_SCORE_ROWS, d)
    dots = jax.lax.map(lambda b: jax.lax.dot_general(
        qvecs, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), blocks)      # [NB, Q, rows]
    dots = jnp.moveaxis(dots, 0, 1).reshape(qvecs.shape[0], -1)[:, :n]
    return norms_row.astype(jnp.float32) - 2.0 * dots


def scores(qvecs, base, norms):
    """[Q, N] ranking scores ‖v‖² − 2·q·v at HIGHEST, by XLA: the
    kernels' score expression as one product on the TPU, `xla_scores`'s
    fixed blocks off it."""
    if _on_tpu():
        return mk._scores(qvecs, base, norms[None, :])
    return xla_scores(qvecs, base, norms[None, :])


def probe_mask(probe, row_list):
    """bool [Q, N]: row n lies in one of query q's probed IVF lists.
    probe [Q, P] and row_list [N] as `ivf_scan_topk` takes them."""
    return mk._probe_mask_block(row_list[None, :], probe)


def _masked_topk_xla(qvecs, qbms, base, norms, bitmaps_wm, *, pred, k):
    """XLA formulation of the masked scan: same score expression and
    predicate word-loop as the kernel, one stable top_k over the rows in
    ascending-id order (= the kernel's block fold order)."""
    scores = xla_scores(qvecs, base, norms[None, :])
    mask = mk._predicate_mask_block(bitmaps_wm, qbms, pred)
    s = jnp.where(mask, scores, mk.PAD_SCORE)
    ids = jnp.broadcast_to(
        jnp.arange(base.shape[0], dtype=jnp.int32)[None, :], s.shape)
    return _stable_topk(s, ids, k)


def _pad_case(qvecs, qbms, base, norms, bitmaps_wm, bq, bn):
    """Pad all operands to block multiples; padded base rows get sentinel
    norms (never selected: zero vectors + PAD norm give exactly PAD score).
    Norms come back as the kernel's [1, N] row."""
    bq_eff, bn_eff = _tiles(qvecs.shape[0], base.shape[0], bq, bn)
    return (_pad_rows(qvecs, bq_eff), _pad_rows(qbms, bq_eff),
            _pad_rows(base, bn_eff),
            _pad_rows(norms, bn_eff, fill=mk.PAD_SCORE)[None, :],
            _pad_rows(bitmaps_wm, bn_eff, axis=1), bq_eff, bn_eff)


@partial(jax.jit, static_argnames=("pred", "k", "bq", "bn", "interpret"))
def masked_topk(qvecs, qbms, base, norms, bitmaps_wm, *, pred: int, k: int,
                bq: int = mk.DEFAULT_BQ, bn: int = mk.DEFAULT_BN,
                interpret: bool | None = None):
    """Fused filtered brute-force top-k. Returns (ids [Q,k] i32, dists [Q,k]).

    qvecs [Q, D], qbms [Q, W], base [N, D], norms [N], bitmaps_wm [W, N]
    (word-major). Handles arbitrary Q/N by padding to block multiples; the
    kernel carries the running top-k across base blocks in VMEM and
    returns [Q, k] directly. Off TPU the default is the bit-identical XLA
    formulation; pass an explicit `interpret` to force the Pallas kernel.
    """
    if interpret is None:
        if not _on_tpu():
            return _masked_topk_xla(qvecs, qbms, base, norms, bitmaps_wm,
                                    pred=pred, k=k)
        interpret = False
    q = qvecs.shape[0]
    n = base.shape[0]
    qv, qb, bs, nm, bm, bq_eff, bn_eff = _pad_case(
        qvecs, qbms, base, norms, bitmaps_wm, bq, bn)
    outd, outi = mk.masked_topk_accum(
        qv, qb, bs, nm, bm, pred=pred, k=k, bq=bq_eff, bn=bn_eff,
        interpret=interpret)
    ids, dists = outi[:q, :k], outd[:q, :k]
    # drop padded-row hits and sentinel scores
    bad = (ids < 0) | (ids >= n) | (dists >= mk.PAD_SCORE)
    return jnp.where(bad, -1, ids), jnp.where(bad, jnp.inf, dists)


def _ivf_scan_xla(qvecs, qbms, probe, base, norms, bitmaps_wm, row_list, *,
                  pred, k):
    """XLA formulation of `mk.ivf_scan_accum`: `_masked_topk_xla` with the
    kernel's probe mask ANDed in."""
    scores = xla_scores(qvecs, base, norms[None, :])
    mask = (mk._predicate_mask_block(bitmaps_wm, qbms, pred)
            & mk._probe_mask_block(row_list[None, :], probe))
    s = jnp.where(mask, scores, mk.PAD_SCORE)
    ids = jnp.broadcast_to(
        jnp.arange(base.shape[0], dtype=jnp.int32)[None, :], s.shape)
    return _stable_topk(s, ids, k)


@partial(jax.jit, static_argnames=("pred", "k", "bq", "bn", "interpret"))
def ivf_scan_topk(qvecs, qbms, probe, base, norms, bitmaps_wm, row_list, *,
                  pred: int, k: int, bq: int = mk.DEFAULT_BQ,
                  bn: int = mk.DEFAULT_BN, interpret: bool | None = None):
    """Filtered top-k over the rows of each query's probed IVF lists, by a
    masked scan of the whole base. Returns (ids [Q,k] i32, dists [Q,k]).

    As `masked_topk`, plus probe [Q, P] uint32 (bit ``l & 31`` of word
    ``l >> 5`` set for each probed list l) and row_list [N] int32 (each
    row's list, −1 for a row in no list). Ties go to the lower row id.
    Off TPU the default is the XLA formulation; pass an explicit
    `interpret` to force the Pallas kernel.
    """
    if interpret is None:
        if not _on_tpu():
            return _ivf_scan_xla(qvecs, qbms, probe, base, norms, bitmaps_wm,
                                 row_list, pred=pred, k=k)
        interpret = False
    q = qvecs.shape[0]
    n = base.shape[0]
    qv, qb, bs, nm, bm, bq_eff, bn_eff = _pad_case(
        qvecs, qbms, base, norms, bitmaps_wm, bq, bn)
    pr = _pad_rows(probe, bq_eff)
    rl = _pad_rows(row_list, bn_eff, fill=-1)[None, :]
    outd, outi = mk.ivf_scan_accum(
        qv, qb, pr, bs, nm, bm, rl, pred=pred, k=k, bq=bq_eff, bn=bn_eff,
        interpret=interpret)
    ids, dists = outi[:q, :k], outd[:q, :k]
    bad = (ids < 0) | (ids >= n) | (dists >= mk.PAD_SCORE)
    return jnp.where(bad, -1, ids), jnp.where(bad, jnp.inf, dists)


@partial(jax.jit, static_argnames=("pred", "k", "bq", "bn", "interpret"))
def masked_topk_multiblock(qvecs, qbms, base, norms, bitmaps_wm, *, pred: int,
                           k: int, bq: int = mk.DEFAULT_BQ,
                           bn: int = mk.DEFAULT_BN,
                           interpret: bool | None = None):
    """Legacy path: per-block [NB, Q, k] kernel output merged by
    moveaxis/reshape/top_k. Parity reference only — see `masked_topk`."""
    if interpret is None:
        interpret = not _on_tpu()
    q = qvecs.shape[0]
    n = base.shape[0]
    qv, qb, bs, nm, bm, bq_eff, bn_eff = _pad_case(
        qvecs, qbms, base, norms, bitmaps_wm, bq, bn)
    outd, outi = mk.masked_topk_blocks(
        qv, qb, bs, nm, bm, pred=pred, k=k, bq=bq_eff, bn=bn_eff,
        interpret=interpret)
    nb = outd.shape[0]
    qp = qv.shape[0]
    d_all = jnp.moveaxis(outd, 0, 1).reshape(qp, nb * k)
    i_all = jnp.moveaxis(outi, 0, 1).reshape(qp, nb * k)
    bad = (i_all >= n) | (i_all < 0) | (d_all >= mk.PAD_SCORE)
    d_all = jnp.where(bad, jnp.inf, d_all)
    neg, sel = jax.lax.top_k(-d_all, k)
    ids = jnp.take_along_axis(i_all, sel, axis=1)
    ids = jnp.where(jnp.isinf(neg), -1, ids)
    return ids[:q], -neg[:q]


@partial(jax.jit, static_argnames=("k", "bq", "interpret"))
def merge_topk(ids, dists, *, k: int | None = None, bq: int = mk.DEFAULT_BQ,
               interpret: bool | None = None):
    """Cross-shard top-k merge. Returns (ids [Q, k] i32, dists [Q, k] f32).

    Args:
        ids: [S, Q, K] int32 per-shard candidate ids, −1 at invalid slots.
            Ids must already be globalised (disjoint across shards).
        dists: [S, Q, K] float32 per-shard scores; +inf (or any value ≥
            `masked_topk.PAD_SCORE`) marks invalid slots alongside id −1.
        k: output width; defaults to K (merge per-shard top-K into a
            global top-K). k > K is allowed — the candidate axis is
            padded with invalid slots, so the surplus comes back as −1
            ids with +inf dists (the delta-segment path hits this when a
            segment holds fewer candidates than the requested k).
        bq: query tile size; interpret: force/suppress interpret mode
            (default: interpret off-TPU).

    The kernel carries the running [Q, k] result across the shard axis in
    VMEM scratch (same accumulation as `masked_topk`), so the merge makes
    one pass over the [S, Q, K] candidates with no [Q, S*K] reshuffle.
    S=1 — and any S off TPU (`interpret=None`) — skips the Pallas launch
    entirely: the shard-major flatten is the kernel's fold order, so one
    stable XLA `top_k` reproduces the VMEM fold bit for bit. Invalid
    outputs come back as id −1 with dist +inf.
    """
    use_xla = interpret is None and not _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()
    s, q, kk = ids.shape
    if k is None:
        k = kk
    d = jnp.where((ids < 0) | (dists >= mk.PAD_SCORE) | jnp.isnan(dists),
                  mk.PAD_SCORE, dists.astype(jnp.float32))
    if k > kk:
        d = jnp.concatenate(
            [d, jnp.full((s, q, k - kk), mk.PAD_SCORE, d.dtype)], axis=2)
        ids = jnp.concatenate(
            [ids, jnp.full((s, q, k - kk), -1, ids.dtype)], axis=2)
        kk = k
    if s == 1 or use_xla:           # shard-major flatten = fold order
        return _stable_topk(jnp.moveaxis(d, 0, 1).reshape(q, s * kk),
                            jnp.moveaxis(ids, 0, 1).reshape(q, s * kk), k)
    bq_eff, _ = _tiles(q, 1, bq, 1)
    d = _pad_rows(_pad_rows(d, bq_eff, mk.PAD_SCORE, axis=1), mk.LANES,
                  mk.PAD_SCORE, axis=2)
    ids = _pad_rows(_pad_rows(ids, bq_eff, -1, axis=1), mk.LANES, -1, axis=2)
    outd, outi = mk.merge_topk_accum(d, ids, k=k, bq=bq_eff,
                                     interpret=interpret)
    outd, outi = outd[:q, :k], outi[:q, :k]
    bad = (outi < 0) | (outd >= mk.PAD_SCORE)
    return jnp.where(bad, -1, outi), jnp.where(bad, jnp.inf, outd)


def _live_candidates(cand_ids, cand_dists, delta_ids, tomb_words):
    """Tombstones applied in XLA, before any launch: dead, invalid or
    non-finite base candidates become (−1, PAD_SCORE) slots, and dead
    delta rows get id −1 — the kernel then needs no tombstone table."""
    ci = cand_ids.astype(jnp.int32)
    cd = jnp.where((ci < 0) | ~jnp.isfinite(cand_dists)
                   | (cand_dists >= mk.PAD_SCORE)
                   | _tombstone_bits(tomb_words, ci),
                   mk.PAD_SCORE, cand_dists.astype(jnp.float32))
    ci = jnp.where(cd >= mk.PAD_SCORE, -1, ci)
    di = jnp.where(_tombstone_bits(tomb_words, delta_ids), -1, delta_ids)
    return ci, cd, di


def _fused_live_xla(qvecs, qbms, ci, cd, dvec, dnorms, dbm_wm, di, *,
                    pred, k):
    """XLA formulation of the fused live read: same score expression and
    predicate loop as `mk.fused_live_accum`; candidates laid out
    base-first then delta rows in mirror order (= the kernel's fold order)
    under one stable top_k."""
    scores = xla_scores(qvecs, dvec, dnorms[None, :])
    mask = mk._predicate_mask_block(dbm_wm, qbms, pred)
    s = jnp.where(mask & (di >= 0)[None, :], scores, mk.PAD_SCORE)
    return _stable_topk(jnp.concatenate([cd, s], axis=1),
                        jnp.concatenate(
                            [ci, jnp.broadcast_to(di[None, :], s.shape)],
                            axis=1), k)


def _fused_core(qvecs, qbms, cand_ids, cand_dists, dvec, dnorms, dbm_wm,
                delta_ids, tomb_words, *, pred, k, bq, bn, interpret):
    """Shared tombstone cleanup and padding around `mk.fused_live_accum`;
    `interpret is None` (the off-TPU default) takes the XLA formulation
    instead."""
    ci, cd, di = _live_candidates(cand_ids, cand_dists, delta_ids,
                                  tomb_words)
    if interpret is None:
        return _fused_live_xla(qvecs, qbms, ci, cd, dvec, dnorms, dbm_wm,
                               di, pred=pred, k=k)
    q = qvecs.shape[0]
    bq_eff, bn_eff = _tiles(q, dvec.shape[0], bq, bn)
    qv = _pad_rows(qvecs, bq_eff)
    qb = _pad_rows(qbms, bq_eff)
    # candidate slots padded to whole vregs (and Q rows to whole tiles)
    cd = _pad_rows(_pad_rows(cd, bq_eff, fill=mk.PAD_SCORE), mk.LANES,
                   fill=mk.PAD_SCORE, axis=1)
    ci = _pad_rows(_pad_rows(ci, bq_eff, fill=-1), mk.LANES, fill=-1, axis=1)
    if ci.shape[1] == 0:             # no base candidates: one empty vreg
        cd = jnp.full((ci.shape[0], mk.LANES), mk.PAD_SCORE, jnp.float32)
        ci = jnp.full((ci.shape[0], mk.LANES), -1, jnp.int32)
    dv = _pad_rows(dvec, bn_eff)
    dn = _pad_rows(dnorms, bn_eff, fill=mk.PAD_SCORE)[None, :]
    db = _pad_rows(dbm_wm, bn_eff, axis=1)
    di = _pad_rows(di, bn_eff, fill=-1)[None, :]
    outd, outi = mk.fused_live_accum(qv, qb, cd, ci, dv, dn, db, di,
                                     pred=pred, k=k, bq=bq_eff, bn=bn_eff,
                                     interpret=interpret)
    ids, dists = outi[:q, :k], outd[:q, :k]
    bad = (ids < 0) | (dists >= mk.PAD_SCORE)
    return jnp.where(bad, -1, ids), jnp.where(bad, jnp.inf, dists)


@partial(jax.jit, static_argnames=("pred", "k", "bq", "bn", "interpret"))
def fused_live_topk(qvecs, qbms, cand_ids, cand_dists, dvec, dnorms, dbm_wm,
                    base_n, tomb_words, *, pred: int, k: int,
                    bq: int = mk.DEFAULT_BQ, bn: int = mk.DEFAULT_BN,
                    interpret: bool | None = None):
    """Fused live top-k: one launch folding routed base candidates with a
    full brute-force scan of the delta mirror, tombstones applied to both
    candidate sets (in XLA, just before the launch).

    Args:
        cand_ids/cand_dists: [Q, KB] routed base candidates (global ids,
            −1 / +inf at invalid slots). KB may be 0.
        dvec/dnorms/dbm_wm: delta device mirror, bitmaps word-major
            [W, ND] (sentinel rows carry PAD_SCORE norms and never
            surface).
        base_n: i32 scalar — delta row r has global id base_n + r. Traced,
            so generation changes don't recompile.
        tomb_words: [TW] uint32 packed tombstones over base + delta rows
            (little-endian bit order).

    Returns (ids [Q, k] i32 with −1 pads, dists [Q, k] f32 with +inf pads);
    bit-identical to the staged base→masked_topk→merge_topk path.
    """
    if interpret is None and _on_tpu():
        interpret = False
    nd = dvec.shape[0]
    di = jnp.arange(nd, dtype=jnp.int32) + jnp.int32(base_n)
    return _fused_core(qvecs, qbms, cand_ids, cand_dists, dvec, dnorms,
                       dbm_wm, di, tomb_words, pred=pred, k=k, bq=bq, bn=bn,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("pred", "k", "bq", "bn", "interpret"))
def fused_live_topk_select(qvecs, qbms, cand_ids, cand_dists, dvec, dnorms,
                           dbm_wm, sel, base_n, tomb_words, *, pred: int,
                           k: int, bq: int = mk.DEFAULT_BQ,
                           bn: int = mk.DEFAULT_BN,
                           interpret: bool | None = None):
    """Fused live top-k over a *selected subset* of delta rows.

    `sel` is [NS] i32 delta-local row indices (−1 pads) chosen by the
    per-chunk mini-IVF pruner; the kernel scans only the gathered rows.
    Semantically identical to `fused_live_topk` whenever the pruner's
    exact ball bound holds (rows it drops cannot enter any query's top-k).
    """
    if interpret is None and _on_tpu():
        interpret = False
    safe = jnp.maximum(sel, 0)
    dv = jnp.take(dvec, safe, axis=0)
    dn = jnp.where(sel < 0, mk.PAD_SCORE, jnp.take(dnorms, safe))
    db = jnp.take(dbm_wm, safe, axis=1)
    di = jnp.where(sel < 0, -1, sel + jnp.int32(base_n))
    return _fused_core(qvecs, qbms, cand_ids, cand_dists, dv, dn, db,
                       di, tomb_words, pred=pred, k=k, bq=bq, bn=bn,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("pred", "bq", "bn", "interpret"))
def selectivity(qbms, bitmaps_wm, *, pred: int, bq: int = 128,
                bn: int = 2048, interpret: bool | None = None):
    """Per-query predicate match counts [Q] i32 (qbms [Q, W], bitmaps_wm
    [W, N] word-major)."""
    if interpret is None:
        interpret = not _on_tpu()
    q = qbms.shape[0]
    n = bitmaps_wm.shape[1]
    bq_eff, bn_eff = _tiles(q, n, bq, bn)
    qb = _pad_rows(qbms, bq_eff)
    bm = _pad_rows(bitmaps_wm, bn_eff, axis=1)
    counts = bf.selectivity_count(qb, bm, pred=pred, bq=bq_eff, bn=bn_eff,
                                  interpret=interpret)[:, 0]
    # padded base rows have all-zero bitmaps: they match EQUALITY and AND
    # (vacuous containment) iff the query label set is empty — subtract
    # that contribution exactly. OR never matches a zero bitmap.
    pad_n = bm.shape[1] - n
    if pad_n and pred in (0, 1):
        empty_q = jnp.all(qb == 0, axis=1)
        counts = counts - jnp.where(empty_q, pad_n, 0).astype(jnp.int32)
    return counts[:q]
