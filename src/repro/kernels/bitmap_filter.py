"""Pallas TPU kernel: predicate selectivity counting over packed bitmaps.

Computes |{i : P(L_i, L_q)}| for a query batch — the router's per-query
`selectivity` feature (the paper's Roaring-bitmap step). Grid is
(query tiles, base blocks) with ``dimension_semantics=("parallel",
"arbitrary")``: base blocks are a sequential reduction axis whose partial
counts accumulate in VMEM scratch; the [BQ, 1] output block is written
once, on the last base block (same block-accumulation pattern as the
running top-k in `masked_topk`). Base bitmaps are word-major ([W, N]) for
the reason given there."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.masked_topk import (LANES, _compiler_params,
                                       _predicate_mask_block)


def _kernel(qbm_ref, bm_ref, out_ref, acc_ref, *, pred: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

    mask = _predicate_mask_block(bm_ref, qbm_ref, pred)
    acc_ref[...] += jnp.sum(jnp.where(mask, 1, 0), axis=1, keepdims=True)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _write():
        out_ref[...] = acc_ref[...]


def selectivity_count(qbms, bitmaps_wm, *, pred: int, bq: int = 128,
                      bn: int = 2048, interpret: bool = False):
    """qbms [Q, W], bitmaps_wm [W, N] word-major -> counts [Q, 1] int32.
    Q % bq == 0, N % bn == 0, bn % 128 == 0."""
    q, w = qbms.shape
    n = bitmaps_wm.shape[1]
    assert q % bq == 0 and n % bn == 0 and bn % LANES == 0, (q, bq, n, bn)
    kernel = functools.partial(_kernel, pred=pred)
    return pl.pallas_call(
        kernel,
        grid=(q // bq, n // bn),
        in_specs=[
            pl.BlockSpec((bq, w), lambda qt, nb: (qt, 0)),
            pl.BlockSpec((w, bn), lambda qt, nb: (0, nb)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda qt, nb: (qt, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="selectivity_count",
    )(qbms, bitmaps_wm)
