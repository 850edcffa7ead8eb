#!/usr/bin/env python3
"""Chip smoke test: drive the routed filtered-search serving path once on
a TPU, through the objects a user calls, and check what comes out.

    python chip_smoke.py              # one chip, phases 1-5
    python chip_smoke.py --chips 4    # the sharded phase on four chips

Phases of the default run (each prints one line; the last line of the
output is ``{"ok": true, "device": {...}}``):

1. device check: the first JAX device must be a TPU (nothing runs on the
   CPU instead), and the persistent compilation cache is turned on;
2. exact path at real scale: a 1,048,576 x 768 corpus over a 1,000-label
   vocabulary (the row count and width of the paper's laion and
   synth_768d_hc corpora) is synthesized, uploaded through
   `FilteredIndex`, and 256 queries per predicate are served through
   `RouterService` with a router that sends every query to prefilter.
   Ids must equal the plain jnp oracle `ref.masked_topk_ref` run on the
   chip, up to exact distance ties; the oracle is cross-checked against
   the host `ground_truth_topk` on a few queries;
3. routed path: all six candidate methods are built over a smaller corpus
   of the same width, an `MLRouter` with random per-method MLPs routes
   three batches through `RouterService` and 64 single queries through
   `AsyncBatchQueue`, and every registered method runs once directly;
4. live path: `LiveFilteredIndex` over the phase-3 corpus takes upserts
   (sealed delta chunks plus a partial tail) and deletes, and its results
   must equal a brute-force oracle over the live rows;
5. kernel proof: every recorded call of a kernel-backed op is lowered
   again and its compiled text must hold the Pallas kernel
   (``tpu_custom_call``); peak device memory is printed.

``--chips 4`` runs only the sharded phase: `ShardedFilteredIndex` over
four chips served by `ShardedRouterService`, compared with a one-chip
`RouterService` on chip 0.

The printed seconds are set-up and sanity numbers (first calls include
compilation), not benchmark results. Everything is generated from
``--seed``; nothing is read from ``artifacts/``. Any failed check exits
non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import sys
import threading
import time

import numpy as np

PREDS = (0, 1, 2)                 # EQUALITY, AND, OR
PRED_NAMES = {0: "EQUALITY", 1: "AND", 2: "OR"}
# kernel-backed ops and the Pallas kernel each must compile to on a TPU
KERNEL_OPS = {
    "masked_topk": "masked_topk_accum",
    "selectivity": "selectivity_count",
    "merge_topk": "merge_topk_accum",
    "fused_live_topk": "fused_live_accum",
    "fused_live_topk_select": "fused_live_accum",
}
ROUTED_N_REASON = (
    "halved from 262144: the host index builds of every method (Vamana "
    "graph dominant, growing about n^1.5) took 751 s at 131072, 268 s at "
    "65536 and 90 s at 32768 on an 8-core CPU host, and 258 s at 32768 on "
    "the host of a one-chip TPU v5e machine; at 65536 that host would "
    "bring the run near its 1200 s limit next to the 1M exact phase, so "
    "one more halving")


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    exact_n: int = 1 << 20        # phase-2 corpus rows
    routed_n: int = 32768         # phase-3/4 corpus rows (see ROUTED_N_REASON)
    dim: int = 768
    universe: int = 1000
    q_exact: int = 256            # queries per predicate, phase 2
    q_routed: int = 64            # queries per predicate, phases 3-4
    gt_check: int = 8             # host ground-truth cross-check queries
    k: int = 10
    delta_chunk: int = 512
    ref_chunk: int = 16           # queries per on-chip oracle call


FULL = Sizes()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def report(phase: str, fields: dict) -> None:
    print(f"{phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


def import_repo():
    """Put the checkout's src/ on the path; fail if it is not there."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeError(f"no repro package under {src}: run chip_smoke.py "
                         f"from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


# ---------------------------------------------------------------------------
# phase 1 — device
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def phase_device(chips: int) -> dict:
    """The first device must be a TPU and `chips` of them must be
    visible; turns the compilation cache on."""
    info = device_info()
    check(info["platform"] == "tpu",
          f"JAX found no TPU (first device platform "
          f"{info['platform']!r}); the smoke run does not fall back to it")
    check(info["count"] >= chips,
          f"--chips {chips} needs {chips} devices; JAX sees {info['count']}")
    import jax

    from repro.common import enable_compilation_cache

    enable_compilation_cache()
    report("phase 1 device", {
        **info, "cache_dir": jax.config.jax_compilation_cache_dir})
    return info


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def corpus(name: str, n: int, sizes: Sizes, seed: int):
    """synth_768d_hc's generator (1,000 labels, Zipf 1.2 label skew, 2.5
    labels per row, 96 clusters on a 20-d manifold) at `n` rows and the
    published width."""
    from repro.data.ann_synth import DatasetSpec, synthesize

    return synthesize(DatasetSpec(name, n, sizes.dim, sizes.universe, 20,
                                  96, 1.2, 2.5, 0.4, 0.45, seed))


def make_router(ds_name: str, methods, seed: int, scaler=None):
    """`MLRouter` with randomly initialised per-method MLPs over a
    synthetic `BenchmarkTable` that uses each method's real settings.
    `scaler` standardises the features (identity by default)."""
    import jax

    from repro.ann import registry as registry_mod
    from repro.core import features as F
    from repro.core import mlp
    from repro.core.router import MLRouter
    from repro.core.table import BenchmarkTable

    rand = np.random.default_rng(seed)
    table = BenchmarkTable.new()
    for pt in PREDS:
        for name in methods:
            for s in registry_mod.get_method(name).param_settings():
                table.add(ds_name, pt, name, s.ps_id,
                          recall=float(rand.uniform(0.7, 1.0)),
                          qps=float(rand.uniform(100, 2000)))
    models = {m: mlp.params_to_numpy(
        mlp.init_mlp((5, 16, 8, 1), jax.random.PRNGKey(seed + j)))
        for j, m in enumerate(methods)}
    return MLRouter(feature_names=F.MINIMAL_FEATURES, methods=list(methods),
                    models=models,
                    scaler=(mlp.Scaler(np.zeros(5), np.ones(5)) if scaler is None
                            else scaler),
                    table=table)


def routed_router(ds, fx, qsets: dict, seed: int):
    """Random router over the candidate methods whose scaler standardises
    the served batches' features, so decisions follow selectivity and
    predicate and spread over several methods."""
    from repro.ann import registry as registry_mod
    from repro.core import features as F
    from repro.core import mlp

    x = np.concatenate([F.feature_matrix(ds, b.bitmaps, b.pred,
                                         F.MINIMAL_FEATURES, fx=fx)
                        for b in qsets.values()])
    return make_router(ds.name, list(registry_mod.candidate_methods()), seed,
                       scaler=mlp.Scaler.fit(x))


def batches(ds, q: int, k: int, seed: int) -> dict:
    from repro.ann.index import QueryBatch
    from repro.data.ann_synth import make_queries

    out = {}
    for p in PREDS:
        qs = make_queries(ds, p, q, k=k, seed=seed, with_ground_truth=False)
        out[p] = QueryBatch(qs.vectors, qs.bitmaps, p, k)
    return out


def reference_topk(vectors, norms, bitmaps, batch, chunk: int):
    """Exact masked top-k ids by the plain jnp oracle, on the device that
    holds `vectors`, in query chunks (host [Q, k], −1 pad)."""
    import jax

    from repro.kernels import ref

    fn = jax.jit(ref.masked_topk_ref, static_argnames=("pred", "k"))
    dev = list(vectors.devices())[0]
    ids = []
    for s in range(0, batch.q, chunk):
        qv = jax.device_put(batch.vectors[s:s + chunk], dev)
        qb = jax.device_put(batch.bitmaps[s:s + chunk], dev)
        i, _ = fn(qv, qb, vectors, norms, bitmaps, pred=int(batch.pred),
                  k=batch.k)
        ids.append(np.asarray(jax.block_until_ready(i)))
    return np.concatenate(ids)


def check_exact(got, want, vectors, bitmaps, alive, batch, what: str) -> int:
    """`got` must equal the exact ids `want` except where the rows' true
    (float64) distances tie to f32 resolution — f32 ranking may order
    near-ties either way. Swapped-in ids must still be live predicate
    matches. Returns the number of rows that differ only by such ties."""
    from repro.ann.predicates import eval_predicate_np

    check(got.shape == want.shape,
          f"{what}: result shape {got.shape}, expected {want.shape}")
    swapped = 0
    for qi in np.nonzero((got != want).any(axis=1))[0]:
        a, b = got[qi], want[qi]
        check(np.array_equal(a >= 0, b >= 0),
              f"{what}: query {qi} returns {(a >= 0).sum()} hits, the "
              f"oracle {(b >= 0).sum()}")
        d = (a != b) & (a >= 0)
        va = a[a >= 0]
        check(np.unique(va).size == va.size,
              f"{what}: query {qi} returns a duplicate id")
        ok = eval_predicate_np(bitmaps[a[d]], batch.bitmaps[qi][None],
                               batch.pred)
        if alive is not None:
            ok &= alive[a[d]]
        check(ok.all(), f"{what}: query {qi} returns a non-matching row")
        q = batch.vectors[qi].astype(np.float64)

        def raw(ids):
            v = vectors[ids].astype(np.float64)
            return (v ** 2).sum(1) - 2.0 * v @ q

        da, db = raw(a[d]), raw(b[d])
        check(np.allclose(da, db, rtol=1e-5, atol=1e-3),
              f"{what}: query {qi} ids differ beyond distance ties "
              f"(max gap {np.max(np.abs(da - db)):.3g})")
        swapped += 1
    return swapped


def check_well_formed(res, ds, batch, what: str) -> None:
    """[Q, k] ids in range, −1 only trailing, unique, predicate matches,
    finite distances where an id is returned."""
    from repro.ann.predicates import eval_predicate_np

    ids = np.asarray(res.ids)
    check(ids.shape == (batch.q, batch.k),
          f"{what}: ids shape {ids.shape}, expected {(batch.q, batch.k)}")
    check(((ids >= -1) & (ids < ds.n)).all(), f"{what}: id out of range")
    valid = ids >= 0
    check((valid[:, :-1] | ~valid[:, 1:]).all(),
          f"{what}: −1 pad before a valid id")
    check(np.isfinite(res.distances[valid]).all(),
          f"{what}: non-finite distance at a returned id")
    for qi in range(batch.q):
        row = ids[qi][valid[qi]]
        check(np.unique(row).size == row.size,
              f"{what}: query {qi} returns a duplicate id")
        check(eval_predicate_np(ds.bitmaps[row], batch.bitmaps[qi][None],
                                batch.pred).all(),
              f"{what}: query {qi} returns a row that fails its predicate")


class CallRecorder:
    """Records the distinct argument signatures of the kernel-backed ops
    (`repro.kernels.ops`) while the serving objects run, so phase 5 can
    lower exactly the calls that were served."""

    def __init__(self):
        self.calls: dict = {}
        self._lock = threading.Lock()

    @staticmethod
    def _spec(a):
        import jax

        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        if isinstance(a, (np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype)
        return a

    @contextlib.contextmanager
    def patch(self):
        from repro.kernels import ops

        originals = {name: getattr(ops, name) for name in KERNEL_OPS}

        def wrap(name, fn):
            def recorded(*args, **kwargs):
                specs = tuple(self._spec(a) for a in args)
                key = (name, tuple(str(s) for s in specs),
                       tuple(sorted(kwargs.items())))
                with self._lock:
                    self.calls.setdefault(key, (name, specs, dict(kwargs)))
                return fn(*args, **kwargs)
            return recorded

        for name, fn in originals.items():
            setattr(ops, name, wrap(name, fn))
        try:
            yield self
        finally:
            for name, fn in originals.items():
                setattr(ops, name, fn)


# ---------------------------------------------------------------------------
# phase 2 — exact path at real scale
# ---------------------------------------------------------------------------

def phase_exact(sizes: Sizes, seed: int) -> dict:
    import jax

    from repro.ann import registry as registry_mod
    from repro.ann.dataset import ground_truth_topk
    from repro.ann.index import FilteredIndex
    from repro.ann.service import RouterService

    t0 = time.perf_counter()
    ds = corpus("synth_768d_hc-1m", sizes.exact_n, sizes, seed)
    synth_s = time.perf_counter() - t0
    out = {"n": ds.n, "dim": ds.dim, "words": int(ds.bitmaps.shape[1]),
           "synth_s": synth_s}
    with FilteredIndex(ds) as fx:
        t0 = time.perf_counter()
        dev = fx.device
        jax.block_until_ready((dev.vectors, dev.bitmaps_wm))
        out["upload_s"] = time.perf_counter() - t0
        svc = RouterService(fx, make_router(ds.name, ["prefilter"], seed),
                            methods=registry_mod.all_methods())
        qsets = batches(ds, sizes.q_exact, sizes.k, seed)
        for p, batch in qsets.items():
            name = PRED_NAMES[p]
            t0 = time.perf_counter()
            res = svc.search(batch)
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            again = svc.search(batch)
            steady_s = time.perf_counter() - t0
            check(all(d.method == "prefilter" for d in res.decisions),
                  f"exact {name}: a query was not routed to prefilter")
            check(np.array_equal(res.ids, again.ids),
                  f"exact {name}: two identical searches disagree")
            t0 = time.perf_counter()
            want = reference_topk(dev.vectors, dev.norms, dev.bitmaps,
                                  batch, sizes.ref_chunk)
            ref_s = time.perf_counter() - t0
            ties = check_exact(res.ids, want, ds.vectors, ds.bitmaps, None,
                               batch, f"exact {name}")
            g = min(sizes.gt_check, batch.q)
            gt = ground_truth_topk(ds, batch.vectors[:g], batch.bitmaps[:g],
                                   batch.pred, batch.k)
            check_exact(want[:g], gt, ds.vectors, ds.bitmaps, None,
                        batch.take(np.arange(g)), f"oracle vs host {name}")
            out[name] = {"q": batch.q, "first_s": first_s,
                         "steady_s": steady_s,
                         "oracle_s": ref_s, "tie_rows": ties,
                         "hits": int((res.ids >= 0).sum())}
    report("phase 2 exact", out)
    return out


# ---------------------------------------------------------------------------
# phase 3 — routed path
# ---------------------------------------------------------------------------

def build_all(fx) -> tuple[float, dict]:
    from repro.ann import registry as registry_mod

    per = {}
    t_all = time.perf_counter()
    for name, m in registry_mod.all_methods().items():
        t0 = time.perf_counter()
        for s in m.param_settings():
            fx.get_index(m, s.build)
        per[name] = time.perf_counter() - t0
    return time.perf_counter() - t_all, per


def phase_routed(sizes: Sizes, seed: int) -> dict:
    from repro.ann import registry as registry_mod
    from repro.ann.dataset import recall_at_k
    from repro.ann.index import FilteredIndex
    from repro.ann.service import AsyncBatchQueue, RouterService

    ds = corpus("synth_768d_hc-routed", sizes.routed_n, sizes, seed + 1)
    out = {"n": ds.n, "dim": ds.dim}
    if sizes.routed_n == FULL.routed_n:
        out["cut"] = ROUTED_N_REASON
    with FilteredIndex(ds) as fx:
        build_s, per = build_all(fx)
        out["build_s"] = build_s
        out["build_s_per_method"] = per
        dev = fx.device
        qsets = batches(ds, sizes.q_routed, sizes.k, seed + 2)
        svc = RouterService(fx, routed_router(ds, fx, qsets, seed))
        shares, recall, first, steady, served = {}, {}, {}, {}, {}
        for p, batch in qsets.items():
            name = PRED_NAMES[p]
            t0 = time.perf_counter()
            res = svc.search(batch)
            first[name] = time.perf_counter() - t0
            t0 = time.perf_counter()
            again = svc.search(batch)
            steady[name] = time.perf_counter() - t0
            check(np.array_equal(res.ids, again.ids),
                  f"routed {name}: two identical searches disagree")
            check_well_formed(res, ds, batch, f"routed {name}")
            want = reference_topk(dev.vectors, dev.norms, dev.bitmaps,
                                  batch, sizes.ref_chunk)
            recall[name] = float(recall_at_k(res.ids, want).mean())
            for d in res.decisions:
                shares[d.method] = shares.get(d.method, 0) + 1
            served[p] = res
        total = sum(shares.values())
        out["decision_share"] = {m: c / total
                                 for m, c in sorted(shares.items())}
        out["recall_at_10"] = recall
        out["first_s"] = first
        out["steady_s"] = steady

        # 64 single queries through the async micro-batch queue, cycling
        # over the three predicates; each must match its batched result
        picks = [(i % 3, i // 3) for i in range(sizes.q_routed)]
        t0 = time.perf_counter()
        with AsyncBatchQueue(svc, max_batch=16, max_wait_ms=2.0) as queue:
            futs = [queue.submit(qsets[p].vectors[i], qsets[p].bitmaps[i],
                                 p, sizes.k)
                    for p, i in picks]
            got = [f.result(timeout=600) for f in futs]
        out["queue_s"] = time.perf_counter() - t0
        for (p, i), r in zip(picks, got):
            res = served[p]
            check(r.decision == res.decisions[i],
                  f"queue {PRED_NAMES[p]} query {i}: decision "
                  f"{r.decision} != batched {res.decisions[i]}")
            check(np.array_equal(r.ids, res.ids[i]),
                  f"queue {PRED_NAMES[p]} query {i}: ids differ from the "
                  f"batched search")

        # every registered method once, directly, at its highest setting
        batch = qsets[1]
        want = reference_topk(dev.vectors, dev.norms, dev.bitmaps, batch,
                              sizes.ref_chunk)
        direct = {}
        for name in registry_mod.all_methods():
            res = fx.search(batch, name)
            check_well_formed(res, ds, batch, f"direct {name}")
            direct[name] = float(recall_at_k(res.ids, want).mean())
        check_exact(fx.search(batch, "prefilter").ids, want, ds.vectors,
                    ds.bitmaps, None, batch, "direct prefilter")
        out["direct_recall_at_10_AND"] = direct
    report("phase 3 routed", out)
    return out


# ---------------------------------------------------------------------------
# phase 4 — live path
# ---------------------------------------------------------------------------

def phase_live(sizes: Sizes, seed: int) -> dict:
    import jax.numpy as jnp

    from repro.ann import registry as registry_mod
    from repro.ann.live import LiveFilteredIndex
    from repro.ann.service import RouterService

    ds = corpus("synth_768d_hc-routed", sizes.routed_n, sizes, seed + 1)
    rng = np.random.default_rng(seed + 3)
    chunk = sizes.delta_chunk
    scale = float(np.median(np.sqrt(ds.norms_sq)))
    # the oracle's copy of the live state, in global-id order
    vectors, bitmaps = ds.vectors, ds.bitmaps
    dead = np.zeros(ds.n, bool)
    out = {"n_base": ds.n, "delta_chunk": chunk}
    with LiveFilteredIndex(ds, delta_chunk=chunk) as live:
        svc = RouterService(live, make_router(ds.name, ["prefilter"], seed),
                            methods=registry_mod.all_methods())
        qsets = batches(ds, sizes.q_routed, sizes.k, seed + 4)
        # round 0: two sealed delta chunks plus a partial tail, scanned in
        # full; round 1: three more chunks of rows far from every query,
        # which the sealed-chunk pruner drops (the `_select` kernel path)
        for r, (n_up, offset) in enumerate([(2 * chunk + chunk // 2, 0.0),
                                            (3 * chunk, 4.0)]):
            src = rng.integers(0, ds.n, n_up)
            v = ds.vectors[src] + np.float32(
                0.05 * scale / np.sqrt(ds.dim)) * rng.normal(
                    size=(n_up, ds.dim)).astype(np.float32)
            v = (v + np.float32(offset * scale)).astype(np.float32)
            b = ds.bitmaps[rng.integers(0, ds.n, n_up)]
            live.upsert(v, b)
            vectors = np.concatenate([vectors, v])
            bitmaps = np.concatenate([bitmaps, b])
            dead = np.concatenate([dead, np.zeros(n_up, bool)])
            gone = rng.choice(live.n_total, size=live.n_total // 100,
                              replace=False)
            live.delete_keys(live.keys_of(gone))
            dead[gone] = True
            check(vectors.shape[0] == live.n_total
                  and live.n_live == int((~dead).sum()),
                  "live: the oracle's state is out of step with the index")
            # oracle: exact masked top-k over the surviving rows, on chip
            rows = np.nonzero(~dead)[0]
            ov = jnp.asarray(vectors[rows])
            on = jnp.asarray((vectors[rows].astype(np.float64) ** 2)
                             .sum(1).astype(np.float32))
            ob = jnp.asarray(bitmaps[rows])

            def oracle(batch):
                want = reference_topk(ov, on, ob, batch, sizes.ref_chunk)
                return np.where(want >= 0, rows[np.maximum(want, 0)], -1)

            stats = {}
            for p, batch in qsets.items():
                name = PRED_NAMES[p]
                t0 = time.perf_counter()
                res = svc.search(batch)
                first_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                again = svc.search(batch)
                steady_s = time.perf_counter() - t0
                check(np.array_equal(res.ids, again.ids),
                      f"live round {r} {name}: two identical searches "
                      f"disagree")
                stats[name] = {
                    "tie_rows": check_exact(res.ids, oracle(batch), vectors,
                                            bitmaps, ~dead, batch,
                                            f"live round {r} {name}"),
                    "first_s": first_s, "steady_s": steady_s}
            # the staged three-launch path must give the same answers
            live.fused = False
            try:
                staged = svc.search(qsets[1])
            finally:
                live.fused = True
            check_exact(staged.ids, oracle(qsets[1]), vectors, bitmaps,
                        ~dead, qsets[1], f"live round {r} staged AND")
            out[f"round{r}"] = {"delta_rows": live.n_total - ds.n,
                                "dead": int(dead.sum()), **stats}
        out["delta_prune"] = live.stats()["delta_prune"]
    report("phase 4 live", out)
    return out


# ---------------------------------------------------------------------------
# phase 5 — kernel proof
# ---------------------------------------------------------------------------

_KERNEL_LINE = re.compile(r"%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)* = .*"
                          r'custom_call_target="tpu_custom_call"')


def kernels_in(text: str) -> set:
    """Names of the Pallas kernels (``tpu_custom_call``) in compiled HLO."""
    return {m.group(1) for m in _KERNEL_LINE.finditer(text)}


def phase_kernels(recorder: CallRecorder, need: set) -> dict:
    """Lower every recorded served call again and require its Pallas
    kernel in the compiled text; `need` names the ops that must have been
    served at all. A cross-shard merge of one segment legitimately skips
    the kernel (`ops.merge_topk`'s S == 1 path)."""
    import jax

    from repro.kernels import ops

    seen, found, compile_s = set(), set(), 0.0
    for name, specs, kwargs in recorder.calls.values():
        seen.add(name)
        if name == "merge_topk" and specs[0].shape[0] == 1:
            continue
        t0 = time.perf_counter()
        text = getattr(ops, name).lower(*specs, **kwargs).compile().as_text()
        compile_s += time.perf_counter() - t0
        names = kernels_in(text)
        check(KERNEL_OPS[name] in names,
              f"served call ops.{name}{tuple(str(s) for s in specs)} "
              f"compiled without its Pallas kernel {KERNEL_OPS[name]} "
              f"(kernels found: {sorted(names) or 'none'})")
        found |= names
    check(need <= seen, f"ops never served: {sorted(need - seen)}")
    out = {"calls": len(recorder.calls), "kernels": sorted(found),
           "compile_s": compile_s}
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    out["peak_bytes_in_use"] = peaks
    report("phase 5 kernels", out)
    return out


# ---------------------------------------------------------------------------
# --chips 4 — sharded serving across chips
# ---------------------------------------------------------------------------

def phase_sharded(sizes: Sizes, seed: int, n_chips: int) -> dict:
    import jax

    from repro.ann import registry as registry_mod
    from repro.ann.index import FilteredIndex
    from repro.ann.service import RouterService, ShardedRouterService
    from repro.ann.sharded import ShardedFilteredIndex

    ds = corpus("synth_768d_hc-routed", sizes.routed_n, sizes, seed + 1)
    chip0 = jax.devices()[0]
    out = {"n": ds.n, "shards": n_chips}
    with ShardedFilteredIndex(ds, n_shards=n_chips) as sfx, \
            FilteredIndex(ds, device=chip0) as fx:
        qsets = batches(ds, sizes.q_routed, sizes.k, seed + 2)
        router = routed_router(ds, fx, qsets, seed)
        exact_pool = {m: registry_mod.get_method("prefilter")
                      for m in router.methods}
        placed = [list(sh.device.vectors.devices()) for sh in sfx.shards]
        check(all(len(p) == 1 for p in placed),
              "a shard's vectors span several devices")
        shard_devs = [p[0] for p in placed]
        check(len(set(shard_devs)) == n_chips,
              f"shards share chips: {shard_devs}")
        out["shard_devices"] = [str(d) for d in shard_devs]
        routed = RouterService(fx, router)
        sharded = ShardedRouterService(sfx, router)
        exact_one = RouterService(fx, router, methods=exact_pool)
        exact_all = ShardedRouterService(sfx, router, methods=exact_pool)
        stats = {}
        for p, batch in qsets.items():
            name = PRED_NAMES[p]
            a, b = sharded.search(batch), routed.search(batch)
            check(a.decisions == b.decisions,
                  f"sharded {name}: decisions differ from one chip")
            ea, eb = exact_all.search(batch), exact_one.search(batch)
            check(np.array_equal(ea.ids, eb.ids),
                  f"sharded {name}: exact-method ids differ from one chip")
            stats[name] = {"exact_rows_equal": int(batch.q),
                           "routed_rows_equal": int(
                               (a.ids == b.ids).all(axis=1).sum()),
                           "sharded_s": a.timings["total_s"]}
        out.update(stats)
    report("sharded", out)
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run(chips: int, seed: int, sizes: Sizes = FULL) -> dict:
    import_repo()
    info = phase_device(chips)
    recorder = CallRecorder()
    with recorder.patch():
        if chips == 1:
            phase_exact(sizes, seed)
            phase_routed(sizes, seed)
            phase_live(sizes, seed)
            need = set(KERNEL_OPS)
        else:
            phase_sharded(sizes, seed, chips)
            need = {"masked_topk", "selectivity", "merge_topk"}
    phase_kernels(recorder, need)
    return {**info, "count": chips}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-5 on one chip; 4: the sharded phase")
    args = ap.parse_args(argv)
    try:
        dev = run(args.chips, args.seed)
    except SmokeError as e:
        log(f"chip_smoke: FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
