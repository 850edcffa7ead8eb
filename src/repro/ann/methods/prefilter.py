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

from repro.ann import engine, topk
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

    def search(self, fx, index, qvecs, qbms, pred: Predicate, k: int,
               search_params: dict):
        dev = fx.device
        p = int(Predicate(pred))
        use_kernel = (jax.default_backend() == "tpu"
                      if self.use_kernel is None else self.use_kernel)
        if use_kernel:
            from repro.kernels import ops

            fn = lambda qv, qb: ops.masked_topk(
                qv, qb, dev.vectors, dev.norms, dev.bitmaps_wm, pred=p, k=k)
            return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms)
        pred_idx = jnp.int32(p)
        fn = lambda qv, qb: _search(qv, qb, pred_idx, dev.vectors,
                                    dev.norms, dev.bitmaps, k=k)
        return engine.run_chunked(fn, qvecs.shape[0], qvecs, qbms)
