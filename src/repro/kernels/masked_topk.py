"""Pallas TPU kernel: fused predicate-mask + distance + running top-k.

This is the hot loop of filtered brute-force scan (Pre-filter and the
per-shard step of the distributed search). The TPU-native design:

  * grid = (query tiles, base blocks) with
    ``dimension_semantics=("parallel", "arbitrary")`` — query tiles are
    independent, base blocks are a sequential reduction axis;
  * each step loads a [BQ, D] query tile and a [BN, D] base block into
    VMEM, computes the score block ||v||² − 2·v·q on the MXU
    (`dot_general` contracting both minor dims, f32 accumulation),
  * evaluates the label predicate word-parallel on the VPU directly on the
    packed uint32 bitmaps (no [Q, N, W] temporary). The base bitmaps are
    **word-major** ([W, N]): word i of a block is the lane-dense row
    ``bm_ref[i:i+1, :]``, compared against the query word column
    ``qbm_ref[:, i:i+1]`` broadcast along lanes. A row-major [N, W] block
    would need a lane-to-sublane relayout per word, which the TPU compiler
    either refuses or takes minutes over;
  * and folds the block into a **running top-k carried in VMEM scratch**:
    the carry [BQ, KP] from previous base blocks is concatenated with the
    masked score block and re-extracted by k-step min-extraction, so the
    kernel emits final [Q, KP] dists/ids directly — no [n_blocks, Q, k]
    HBM intermediate and no host/XLA cross-block merge. KP is k rounded
    up to a whole number of 128-lane vregs; the slots past k stay at
    PAD_SCORE / −1 and the wrappers in `repro.kernels.ops` slice them off.

Every 1-D operand (base norms, delta ids) travels as a [1, N] row so its
blocks are lane-dense, and the selectivity counts come back as a [Q, 1]
column (see `bitmap_filter`).

The same VMEM-carried accumulation (factored as `_fold_topk`) also powers
`merge_topk_accum`, the cross-shard reduction of `ShardedFilteredIndex`:
per-shard [S, Q, K] top-k candidates are folded shard by shard into one
global [Q, KP] result, with shards as the sequential grid axis.

The fused live kernel takes no tombstone table: the wrapper applies the
tombstones in XLA before the launch (dead base candidates become PAD
slots, dead delta rows get id −1), so the kernel only ever reads
lane-dense blocks.

VMEM budget at the default BQ=128, BN=1024, D=768, W=32, k ≤ 128 (f32):
double-buffered inputs ≈ 2·(3 MiB base + 384 KiB queries + 128 KiB
bitmaps + 64 KiB query bitmaps + 32 KiB norms) ≈ 7.2 MiB, outputs and
carry ≈ 0.4 MiB, and the fold's [BQ, KP+BN] working set (scores, ids,
column iota, mask) ≈ 3 MiB — about 11 MiB, inside the explicit
`VMEM_LIMIT_BYTES` (32 MiB of the 128 MiB a v5e core has). Wider k or
larger blocks grow the fold working set linearly.

The legacy per-block variant (`masked_topk_blocks`) is kept as a parity
reference for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BN = 1024
LANES = 128
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
PAD_SCORE = 3.0e38  # sentinel for masked-out candidates (finite: inf breaks min-extraction ties)


def lane_pad(k: int) -> int:
    """k rounded up to a whole number of 128-lane vregs."""
    return -(-k // LANES) * LANES


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _predicate_mask_block(bm_wm, qbm, pred: int):
    """bm_wm [W, BN] uint32 (word-major), qbm [BQ, W] uint32 -> bool
    [BQ, BN].

    Only basic slices are taken, so the same code runs on jax arrays (the
    XLA twins in `ops`) and on Pallas refs (each slice is one load)."""
    w = bm_wm.shape[0]
    acc = None
    for i in range(w):
        b = bm_wm[i:i + 1, :]           # [1, BN]  lane-dense row
        qw = qbm[:, i:i + 1]            # [BQ, 1]
        if pred == 0:      # EQUALITY
            hit = b == qw
        elif pred == 1:    # AND (containment)
            hit = (b & qw) == qw
        elif pred == 2:    # OR (overlap)
            hit = (b & qw) != 0
        else:
            raise ValueError(pred)
        if acc is None:
            acc = hit
        elif pred == 2:
            acc = acc | hit
        else:
            acc = acc & hit
    bq, bn = qbm.shape[0], bm_wm.shape[1]
    if acc is None:    # W == 0: every row matches EQUALITY/AND, none OR
        return jnp.full((bq, bn), pred != 2)
    return jnp.broadcast_to(acc, (bq, bn))


def _scores(q, base, norms_row):
    """[BQ, BN] ranking scores ‖v‖² − 2·q·v at full f32 precision."""
    dots = jax.lax.dot_general(
        q, base, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return norms_row.astype(jnp.float32) - 2.0 * dots


def _masked_scores(q_ref, qbm_ref, base_ref, norms_ref, bm_ref, pred: int):
    """Score block [BQ, BN] with masked-out candidates at PAD_SCORE."""
    scores = _scores(q_ref[...], base_ref[...], norms_ref[...])
    mask = _predicate_mask_block(bm_ref, qbm_ref, pred)
    return jnp.where(mask, scores, PAD_SCORE)


def _fold_topk(accd_ref, acci_ref, blk_d, blk_i, k: int) -> None:
    """Fold a candidate block into the running top-k carried in VMEM.

    `accd_ref`/`acci_ref` are [BQ, KP] VMEM scratch holding the carry
    from previous blocks (slots past k at PAD_SCORE / −1); `blk_d`/
    `blk_i` are the new [BQ, C] masked score/id block (PAD_SCORE / −1 at
    invalid slots, C a multiple of 128). The carry and the block are
    concatenated and re-extracted by k-step min-extraction — ties go to
    the lowest column, i.e. the earliest-folded candidate — leaving the
    scratch holding the merged top-k. Shared by the base-block reduction
    (`_accum_kernel`), the cross-shard merge (`_merge_kernel`) and the
    fused live read (`_fused_live_kernel`).
    """
    cand_d = jnp.concatenate([accd_ref[...], blk_d], axis=1)   # [BQ, KP+C]
    cand_i = jnp.concatenate([acci_ref[...], blk_i], axis=1)
    bq, c = cand_d.shape
    kp = accd_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)

    def step(i, carry):
        cand_d, out_d, out_i = carry
        m = jnp.min(cand_d, axis=1, keepdims=True)               # [BQ, 1]
        am = jnp.min(jnp.where(cand_d == m, col, c), axis=1,
                     keepdims=True)                              # first argmin
        sel = col == am
        picked = jnp.sum(jnp.where(sel, cand_i, 0), axis=1, keepdims=True)
        here = kcol == i
        out_d = jnp.where(here, m, out_d)
        out_i = jnp.where(here, jnp.where(m >= PAD_SCORE, -1, picked), out_i)
        return jnp.where(sel, PAD_SCORE, cand_d), out_d, out_i

    _, out_d, out_i = jax.lax.fori_loop(
        0, k, step,
        (cand_d, jnp.full((bq, kp), PAD_SCORE, jnp.float32),
         jnp.full((bq, kp), -1, jnp.int32)))
    accd_ref[...] = out_d
    acci_ref[...] = out_i


def _init_carry(accd_ref, acci_ref) -> None:
    accd_ref[...] = jnp.full(accd_ref.shape, PAD_SCORE, jnp.float32)
    acci_ref[...] = jnp.full(acci_ref.shape, -1, jnp.int32)


def _write_carry(accd_ref, acci_ref, outd_ref, outi_ref) -> None:
    outd_ref[...] = accd_ref[...]
    outi_ref[...] = acci_ref[...]


def _accum_kernel(q_ref, qbm_ref, base_ref, norms_ref, bm_ref,
                  outd_ref, outi_ref, accd_ref, acci_ref, *,
                  pred: int, k: int, bn: int):
    """Running-top-k kernel body: carry [BQ, KP] across the nb grid axis
    in VMEM scratch, write [BQ, KP] outputs once on the last base block."""
    pid_n = pl.program_id(1)

    @pl.when(pid_n == 0)
    def _init():
        _init_carry(accd_ref, acci_ref)

    s = _masked_scores(q_ref, qbm_ref, base_ref, norms_ref, bm_ref, pred)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ids_blk = jnp.where(s >= PAD_SCORE, -1, col + pid_n * bn)
    _fold_topk(accd_ref, acci_ref, s, ids_blk, k)

    @pl.when(pid_n == pl.num_programs(1) - 1)
    def _write():
        _write_carry(accd_ref, acci_ref, outd_ref, outi_ref)


def _topk_outputs(q: int, bq: int, kp: int):
    """(out_specs, out_shape, scratch_shapes) for a [Q, KP] running top-k
    whose carry lives in VMEM across the second grid axis."""
    spec = pl.BlockSpec((bq, kp), lambda qt, j: (qt, 0))
    return ([spec, spec],
            [jax.ShapeDtypeStruct((q, kp), jnp.float32),
             jax.ShapeDtypeStruct((q, kp), jnp.int32)],
            [pltpu.VMEM((bq, kp), jnp.float32),
             pltpu.VMEM((bq, kp), jnp.int32)])


def masked_topk_accum(qvecs, qbms, base, norms, bitmaps_wm, *, pred: int,
                      k: int, bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                      interpret: bool = False):
    """Raw pallas_call: VMEM-accumulated running top-k over base blocks.

    qvecs [Q, D] (Q % bq == 0), base [N, D] (N % bn == 0, bn % 128 == 0),
    norms [1, N], qbms [Q, W], bitmaps_wm [W, N] word-major. Output:
    dists [Q, KP] f32, ids [Q, KP] i32 with KP = `lane_pad(k)`; columns
    past k are PAD_SCORE / −1.
    """
    q, d = qvecs.shape
    w, n = bitmaps_wm.shape
    assert q % bq == 0 and n % bn == 0 and bn % LANES == 0, (q, bq, n, bn)
    kp = lane_pad(k)
    out_specs, out_shape, scratch = _topk_outputs(q, bq, kp)
    kernel = functools.partial(_accum_kernel, pred=pred, k=k, bn=bn)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(q // bq, n // bn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bq, w), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bn, d), lambda qt, nb: (nb, 0)),
            pl.BlockSpec((1, bn), lambda qt, nb: (0, nb)),
            pl.BlockSpec((w, bn), lambda qt, nb: (0, nb)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="masked_topk_accum",
    )(qvecs, qbms, base, norms, bitmaps_wm)
    return outd, outi


# ---------------------------------------------------------------------------
# cross-shard top-k merge — the reduction step of ShardedFilteredIndex
# ---------------------------------------------------------------------------

def _merge_kernel(d_ref, i_ref, outd_ref, outi_ref, accd_ref, acci_ref, *,
                  k: int):
    """Fold one shard's [BQ, K] candidate block into the VMEM carry; write
    the merged [BQ, KP] once on the last shard. Same accumulation pattern
    as `_accum_kernel`, with shards as the sequential reduction axis."""
    pid_s = pl.program_id(1)

    @pl.when(pid_s == 0)
    def _init():
        _init_carry(accd_ref, acci_ref)

    _fold_topk(accd_ref, acci_ref, d_ref[0], i_ref[0], k)

    @pl.when(pid_s == pl.num_programs(1) - 1)
    def _write():
        _write_carry(accd_ref, acci_ref, outd_ref, outi_ref)


def merge_topk_accum(dists, ids, *, k: int, bq: int = DEFAULT_BQ,
                     interpret: bool = False):
    """Raw pallas_call: merge per-shard top-k candidates into a global
    top-k, carrying the running result in VMEM scratch across the shard
    grid axis.

    dists [S, Q, K] f32 (PAD_SCORE at invalid slots), ids [S, Q, K] i32
    (−1 at invalid slots; already globalised — ids must be disjoint across
    shards), Q % bq == 0, K % 128 == 0, k <= K. Output: dists [Q, KP] f32,
    ids [Q, KP] i32 — the k smallest candidates per query over all S·K
    slots, PAD_SCORE / −1 past k.
    """
    s, q, kk = dists.shape
    assert q % bq == 0 and k <= kk and kk % LANES == 0, (q, bq, k, kk)
    out_specs, out_shape, scratch = _topk_outputs(q, bq, lane_pad(k))
    kernel = functools.partial(_merge_kernel, k=k)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(q // bq, s),
        in_specs=[
            pl.BlockSpec((1, bq, kk), lambda qt, sh: (sh, qt, 0)),
            pl.BlockSpec((1, bq, kk), lambda qt, sh: (sh, qt, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="merge_topk_accum",
    )(dists, ids)
    return outd, outi


# ---------------------------------------------------------------------------
# fused live search — base candidates + delta scan, one launch
# ---------------------------------------------------------------------------

def _fused_live_kernel(q_ref, qbm_ref, candd_ref, candi_ref, dvec_ref,
                       dnorm_ref, dbm_ref, did_ref,
                       outd_ref, outi_ref, accd_ref, acci_ref, *,
                       pred: int, k: int):
    """Single-launch live read: fold the routed base candidates and the
    brute-force delta scan into one VMEM-carried running top-k.

    Grid = (query tiles, delta blocks). On the first delta block the base
    candidate set [BQ, KB] (tombstoned slots already PAD / −1) is folded
    into the freshly initialised carry; every step then scores one
    [BN, D] delta block, masks it by predicate and by its id row (−1 for
    pads and tombstoned rows), and folds it through the same `_fold_topk`
    accumulator. The final [Q, KP] is written once on the last block —
    no [S, Q, K] HBM intermediate, no host merge. Because the base carry
    is folded before any delta block, score ties resolve to base rows,
    matching the staged path's merge order exactly."""
    pid_n = pl.program_id(1)

    @pl.when(pid_n == 0)
    def _init():
        _init_carry(accd_ref, acci_ref)
        _fold_topk(accd_ref, acci_ref, candd_ref[...], candi_ref[...], k)

    s = _masked_scores(q_ref, qbm_ref, dvec_ref, dnorm_ref, dbm_ref, pred)
    ids_row = did_ref[...]                       # [1, BN] global ids, −1 dead
    s = jnp.where(ids_row < 0, PAD_SCORE, s)
    ids_blk = jnp.where(s >= PAD_SCORE, -1,
                        jnp.broadcast_to(ids_row, s.shape))
    _fold_topk(accd_ref, acci_ref, s, ids_blk, k)

    @pl.when(pid_n == pl.num_programs(1) - 1)
    def _write():
        _write_carry(accd_ref, acci_ref, outd_ref, outi_ref)


def fused_live_accum(qvecs, qbms, cand_dists, cand_ids, dvec, dnorms, dbm_wm,
                     delta_ids, *, pred: int, k: int,
                     bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                     interpret: bool = False):
    """Raw pallas_call for the fused live read.

    qvecs [Q, D] (Q % bq == 0), cand_dists/cand_ids [Q, KB] routed base
    candidates (global ids, −1/PAD at invalid or tombstoned slots,
    KB % 128 == 0), dvec [ND, D] (ND % bn == 0) delta mirror with dnorms
    [1, ND] (PAD_SCORE at sentinel rows), dbm_wm [W, ND] word-major,
    delta_ids [1, ND] i32 global ids (−1 at pads and tombstoned rows).
    Output: dists [Q, KP] f32, ids [Q, KP] i32 — final merged live top-k,
    PAD_SCORE / −1 past k.
    """
    q, d = qvecs.shape
    w, nd = dbm_wm.shape
    kb = cand_ids.shape[1]
    assert q % bq == 0 and nd % bn == 0 and bn % LANES == 0, (q, bq, nd, bn)
    assert kb % LANES == 0, kb
    out_specs, out_shape, scratch = _topk_outputs(q, bq, lane_pad(k))
    kernel = functools.partial(_fused_live_kernel, pred=pred, k=k)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(q // bq, nd // bn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bq, w), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bq, kb), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bq, kb), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bn, d), lambda qt, nb: (nb, 0)),
            pl.BlockSpec((1, bn), lambda qt, nb: (0, nb)),
            pl.BlockSpec((w, bn), lambda qt, nb: (0, nb)),
            pl.BlockSpec((1, bn), lambda qt, nb: (0, nb)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="fused_live_accum",
    )(qvecs, qbms, cand_dists, cand_ids, dvec, dnorms, dbm_wm, delta_ids)
    return outd, outi


# ---------------------------------------------------------------------------
# legacy per-block variant — kept as the parity reference for tests
# ---------------------------------------------------------------------------

def _block_kernel(q_ref, qbm_ref, base_ref, norms_ref, bm_ref,
                  outd_ref, outi_ref, *, pred: int, k: int, bn: int):
    pid_n = pl.program_id(1)
    s = _masked_scores(q_ref, qbm_ref, base_ref, norms_ref, bm_ref, pred)
    bq = s.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    base_id = pid_n * bn
    for i in range(k):                      # k-step min extraction in VMEM
        m = jnp.min(s, axis=1)
        am = jnp.argmin(s, axis=1).astype(jnp.int32)
        outd_ref[0, :, i] = m
        outi_ref[0, :, i] = jnp.where(m >= PAD_SCORE, -1, am + base_id)
        s = jnp.where(col == am[:, None], PAD_SCORE, s)


def masked_topk_blocks(qvecs, qbms, base, norms, bitmaps_wm, *, pred: int,
                       k: int, bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                       interpret: bool = False):
    """Raw pallas_call: returns per-(base-block) top-k (legacy path).

    Same operand layout as `masked_topk_accum`. Output: dists [NB, Q, k]
    f32, ids [NB, Q, k] i32.
    """
    q, d = qvecs.shape
    w, n = bitmaps_wm.shape
    assert q % bq == 0 and n % bn == 0, (q, bq, n, bn)
    n_blocks = n // bn
    grid = (q // bq, n_blocks)
    kernel = functools.partial(_block_kernel, pred=pred, k=k, bn=bn)
    outd, outi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bq, w), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((bn, d), lambda qt, nb: (nb, 0)),
            pl.BlockSpec((1, bn), lambda qt, nb: (0, nb)),
            pl.BlockSpec((w, bn), lambda qt, nb: (0, nb)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, k), lambda qt, nb: (nb, qt, 0)),
            pl.BlockSpec((1, bq, k), lambda qt, nb: (nb, qt, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, q, k), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, q, k), jnp.int32),
        ],
        interpret=interpret,
    )(qvecs, qbms, base, norms, bitmaps_wm)
    return outd, outi
