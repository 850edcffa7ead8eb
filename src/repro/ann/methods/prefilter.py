"""Pre-filter: exact masked brute-force scan (recall = 1 by construction).

The compute hot-spot of the whole engine — on TPU backends the search is
routed through the Pallas `ops.masked_topk` kernel (VMEM-accumulated,
final [Q, k] emitted directly); the jnp path below is the numerically
identical CPU/parity reference. `PreFilter(use_kernel=True)` forces the
kernel (interpret mode off-TPU) for parity testing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann import engine, topk, trace
from repro.ann.dataset import ANNDataset
from repro.ann.predicates import Predicate


@partial(jax.jit, static_argnames=("k",))
def _search(qvecs, qbms, pred_idx, vectors, norms, bitmaps, *, k: int):
    scores = topk.score_all(qvecs, vectors, norms)            # [Q, N]
    mask = engine.mask_shared(bitmaps, qbms, pred_idx)        # [Q, N]
    scores = jnp.where(mask, scores, topk.INF)
    neg, idx = jax.lax.top_k(-scores, k)
    ids = jnp.where(jnp.isinf(neg), -1, idx).astype(jnp.int32)
    return ids, -neg


@partial(jax.jit, static_argnames=("pred", "k", "kernel"))
def _scan_resident(qvecs, qbms, vectors, norms, bitmaps, bitmaps_wm, *,
                   pred: int, k: int, kernel: bool):
    """Every `engine.DEFAULT_QCHUNK`-query chunk of a padded batch in one
    program: per chunk the launch `search` makes (the Pallas scan, or the
    jnp reference), the chunks' answers concatenated on the device."""
    c = engine.DEFAULT_QCHUNK
    outs = []
    for s in range(0, qvecs.shape[0], c):
        qv, qb = qvecs[s:s + c], qbms[s:s + c]
        if kernel:
            from repro.kernels import ops

            outs.append(ops.masked_topk(qv, qb, vectors, norms, bitmaps_wm,
                                        pred=pred, k=k))
        else:
            outs.append(_search(qv, qb, jnp.int32(pred), vectors, norms,
                                bitmaps, k=k))
    return tuple(jnp.concatenate(part) for part in zip(*outs))


class PreFilter(engine.Method):
    name = "prefilter"

    def __init__(self, use_kernel: bool | None = None):
        # None = auto (kernel on TPU, jnp reference elsewhere)
        self.use_kernel = use_kernel

    def param_settings(self):
        return [engine.ps("exact")]

    def build(self, ds: ANNDataset, build_params: dict):
        return None

    def index_arrays(self, index) -> dict:
        return {}          # stateless build: persists as nothing

    def index_from_arrays(self, ds: ANNDataset, build_params: dict,
                          arrays: dict):
        return None

    def _use_kernel(self) -> bool:
        return (jax.default_backend() == "tpu" if self.use_kernel is None
                else self.use_kernel)

    def search_resident(self, fx, index, qvecs, qbms, pred: Predicate,
                        k: int, search_params: dict):
        """One launch for the whole batch, padded to whole chunks, its
        answer left on `fx`'s device."""
        dev = fx.device
        q = qvecs.shape[0]
        trace.count("cand_rows", q * dev.vectors.shape[0])
        qv, qb = engine.pad_queries(q, (qvecs, qbms))
        with trace.launch(qv.shape[0], qv.shape[0] - q):
            return _scan_resident(qv, qb, dev.vectors, dev.norms,
                                  dev.bitmaps, dev.bitmaps_wm,
                                  pred=int(Predicate(pred)), k=k,
                                  kernel=self._use_kernel())

    def search(self, fx, index, qvecs, qbms, pred: Predicate, k: int,
               search_params: dict):
        dev = fx.device
        p = int(Predicate(pred))
        trace.count("cand_rows", qvecs.shape[0] * dev.vectors.shape[0])
        if self._use_kernel():
            from repro.kernels import ops

            fn = lambda qv, qb: ops.masked_topk(
                qv, qb, dev.vectors, dev.norms, dev.bitmaps_wm, pred=p, k=k)
            return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms)
        pred_idx = jnp.int32(p)
        fn = lambda qv, qb: _search(qv, qb, pred_idx, dev.vectors,
                                    dev.norms, dev.bitmaps, k=k)
        return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms)
