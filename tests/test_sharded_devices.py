"""A corpus row-sharded over four devices: the exact scan's fan-out
against the plain reference, routing's selectivity counted per shard, and
what the routed sharded path leaves on each device.

JAX fixes its device count when it starts, so the work happens in one
child process with four virtual CPU devices (this file run as a script),
which prints one JSON line; the tests read it. The vectors sit on a
quarter grid, so every score is exact in float32 whatever the order of
its sums, and the program and the reference must agree to the bit.

The routed run steers the program onto its TPU branches (the per-shard
selectivity kernel, the Pallas scan's wrapper) by having
`jax.default_backend` name the TPU, while the kernels' own backend check
(`ops._on_tpu`) keeps asking the real backend, so they run their CPU
formulations."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREDS = (0, 1, 2)
BOUNDS = {"equal": None, "ragged": [0, 97, 500, 700, 1200]}


def _grid(x):
    return (np.round(np.asarray(x) * 4) / 4).astype(np.float32)


def _toy_router(ds):
    import jax

    from repro.core import features as F
    from repro.core import mlp as mlp_mod
    from repro.core.router import MLRouter
    from repro.core.table import BenchmarkTable

    table = BenchmarkTable.new()
    for pt in PREDS:
        table.add(ds.name, pt, "prefilter", "exact", recall=1.0, qps=100.0)
    models = {"prefilter": mlp_mod.params_to_numpy(
        mlp_mod.init_mlp((5, 16, 8, 1), jax.random.PRNGKey(0)))}
    return MLRouter(feature_names=F.MINIMAL_FEATURES, methods=["prefilter"],
                    models=models, scaler=mlp_mod.Scaler(np.zeros(5),
                                                         np.ones(5)),
                    table=table)


def _routed_on_tpu_branches(ds, queries, devs) -> dict:
    """Serve every predicate through `ShardedRouterService` on the TPU
    branches; report the handles built, the largest array left on any
    device, where the merge ran and the tracer's totals."""
    import gc

    import jax

    from repro.ann import registry, sharded
    from repro.ann.index import FilteredIndex, QueryBatch
    from repro.ann.service import ShardedRouterService
    from repro.ann.trace import Tracer
    from repro.kernels import ops

    built, merged_on, scanned_on, counted_on = [], [], [], []
    init, merge = FilteredIndex.__init__, sharded._globalise_merge
    resident, selectivity = sharded.merge_resident, ops.selectivity

    def where(a):
        return str(next(iter(a.devices())))

    def spy_init(self, ds_, **kw):
        built.append(int(ds_.n))
        init(self, ds_, **kw)

    def spy_merge(ids, raw, offsets, *, k):
        merged_on.append(where(ids[0]))
        return merge(ids, raw, offsets, k=k)

    def spy_resident(parts, *a, **kw):
        scanned_on.append([where(i) for i, _ in parts])
        return resident(parts, *a, **kw)

    def spy_selectivity(*a, **kw):
        out = selectivity(*a, **kw)
        counted_on.append(where(out))
        return out

    real_backend = jax.default_backend()
    FilteredIndex.__init__ = spy_init
    sharded._globalise_merge = spy_merge
    sharded.merge_resident = spy_resident
    ops.selectivity = spy_selectivity
    ops._on_tpu = lambda: real_backend == "tpu"
    jax.default_backend = lambda: "tpu"
    out = {}
    try:
        tracer = Tracer(sample=1.0)
        with sharded.ShardedFilteredIndex(ds, 4, devices=devs) as sfx:
            svc = ShardedRouterService(
                sfx, _toy_router(ds),
                methods={"prefilter": registry.get_method("prefilter")},
                tracer=tracer)
            out["ids"] = {}
            for p in PREDS:
                qs = queries[p]
                res = svc.search(QueryBatch(_grid(qs.vectors), qs.bitmaps,
                                            p, 10))
                out["ids"][p] = res.ids.tolist()
            gc.collect()
            largest, wide = {}, {}
            for a in jax.live_arrays():
                for d in a.devices():
                    largest[str(d)] = max(largest.get(str(d), 0),
                                          max(a.shape, default=0))
                    if a.ndim == 2 and a.shape[1] == ds.dim:
                        wide[str(d)] = wide.get(str(d), 0) + a.shape[0]
            out["largest_dim"] = largest
            out["wide_rows"] = wide
            out["shard_rows"] = [s.ds.n for s in sfx.shards]
            out["shard_devices"] = [str(d) for d in sfx.devices]
            out["has_whole_copy"] = [hasattr(sfx, a) for a in
                                     ("feature_index", "device")]
        out["built"] = built
        out["merged_on"] = merged_on
        out["scanned_on"] = scanned_on
        out["counted_on"] = counted_on
        h = tracer.histograms()
        out["spans"] = {n: {"count": h[n]["count"],
                            "host_us": h[n]["host_us"],
                            "counters": h[n]["counters"]}
                        for n in ("search", "fanout", "merge") if n in h}
    finally:
        FilteredIndex.__init__ = init
        sharded._globalise_merge = merge
        sharded.merge_resident = resident
        ops.selectivity = selectivity
        jax.default_backend = lambda: real_backend
        ops._on_tpu = lambda: real_backend == "tpu"
    return out


def _child() -> None:
    import jax
    import jax.numpy as jnp

    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex, QueryBatch, exact_distances
    from repro.ann.live import ShardedLiveIndex
    from repro.ann.sharded import ShardedFilteredIndex
    from repro.core import features
    from repro.data.ann_synth import DatasetSpec, make_queries, synthesize
    from repro.kernels import ref

    devs = jax.devices()
    assert len(devs) == 4
    raw = synthesize(DatasetSpec("t4", 1200, 24, 40, 6, 8, 1.3, 2.0, 0.5,
                                 0.3, 7))
    ds = ANNDataset.from_packed("grid4", _grid(raw.vectors), raw.bitmaps,
                                raw.universe)
    queries = {p: make_queries(ds, p, 20, seed=3, with_ground_truth=False)
               for p in PREDS}
    out = {"routed": _routed_on_tpu_branches(ds, queries, devs)}

    # the exact scan, sharded, and the plain reference over the whole corpus
    out["scan"] = {}
    for name, bounds in BOUNDS.items():
        with ShardedFilteredIndex(ds, 4, bounds=bounds, devices=devs) as sfx:
            for p in PREDS:
                qs = queries[p]
                qv = _grid(qs.vectors)
                res = sfx.search(QueryBatch(qv, qs.bitmaps, p, 10),
                                 "prefilter")
                ids, sc = ref.masked_topk_ref(
                    jnp.asarray(qv), jnp.asarray(qs.bitmaps),
                    jnp.asarray(ds.vectors), jnp.asarray(ds.norms_sq),
                    jnp.asarray(ds.bitmaps), pred=p, k=10)
                ids = np.asarray(ids)
                out["scan"][f"{name}-{p}"] = {
                    "ids": res.ids.tolist(), "want_ids": ids.tolist(),
                    "dists": np.nan_to_num(res.distances, nan=-1).tolist(),
                    "want_dists": np.nan_to_num(exact_distances(
                        np.asarray(sc), ids, qv), nan=-1).tolist()}

    # selectivity: counts summed over each handle's shards, and the same
    # counts over every row of the corpus
    qbms = np.concatenate([queries[p].bitmaps for p in PREDS])
    out["counts"] = {}
    handles = {"one": lambda: FilteredIndex(ds, device=devs[0]),
               "sharded": lambda: ShardedFilteredIndex(ds, 4, devices=devs),
               "sharded_live": lambda: ShardedLiveIndex(ds, 4, devices=devs)}
    for name, make in handles.items():
        fx = make()
        try:
            out["counts"][name] = {
                p: [features.device_match_counts(fx, qbms, p).tolist(),
                    features._match_counts(qbms, ds.bitmaps, p).tolist()]
                for p in PREDS}
        finally:
            fx.close()
    print("RESULT " + json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def child():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("pred", PREDS)
@pytest.mark.parametrize("bounds", sorted(BOUNDS))
def test_sharded_scan_matches_the_plain_reference(child, bounds, pred):
    r = child["scan"][f"{bounds}-{pred}"]
    np.testing.assert_array_equal(r["ids"], r["want_ids"])
    np.testing.assert_allclose(r["dists"], r["want_dists"],
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(r["ids"]) >= 0).any()


@pytest.mark.parametrize("handle", ["one", "sharded", "sharded_live"])
def test_selectivity_counts_summed_over_shards_are_exact(child, handle):
    for p in PREDS:
        got, want = child["counts"][handle][str(p)]
        assert got == want
        assert max(want) > 0


def test_routed_sharded_path_holds_no_whole_corpus_on_any_device(child):
    r = child["routed"]
    assert r["has_whole_copy"] == [False, False]
    # the four shards' handles, and no handle over all 1,200 rows
    assert sorted(r["built"]) == sorted(r["shard_rows"])
    assert sum(r["shard_rows"]) == 1200
    assert set(r["largest_dim"]) == set(r["shard_devices"])
    assert max(r["largest_dim"].values()) <= max(r["shard_rows"])
    # the only d-wide rows on a device are its shard's vectors (no
    # per-group centroids, which only labelnav uploads) and at most one
    # 64-query chunk of a batch
    for dev, rows in zip(r["shard_devices"], r["shard_rows"]):
        assert rows <= r["wide_rows"][dev] <= rows + 64


def test_routed_sharded_answers_match_the_scan(child):
    for p in PREDS:
        assert child["routed"]["ids"][str(p)] == \
            child["scan"][f"equal-{p}"]["want_ids"]


def test_every_shard_scans_and_counts_on_its_own_device(child):
    """A shard's tensors are committed to its device: its scan and its
    selectivity count run there, not as copies on the default device."""
    r = child["routed"]
    assert r["scanned_on"] == [r["shard_devices"]] * len(PREDS)
    counted = r["counted_on"]
    assert len(counted) % 4 == 0 and counted
    assert [counted[i:i + 4] for i in range(0, len(counted), 4)] == \
        [r["shard_devices"]] * (len(counted) // 4)


def test_merge_runs_on_the_first_shards_device_once_per_group(child):
    r = child["routed"]
    assert r["merged_on"] == [r["shard_devices"][0]] * len(PREDS)
    fan, merge = r["spans"]["fanout"], r["spans"]["merge"]
    assert fan["count"] == merge["count"] == r["spans"]["search"]["count"]
    # per group: one launch per shard (issued, not waited on) and the
    # merge's launch, which holds the group's one wait
    assert fan["counters"]["launches"] == 5 * fan["count"]
    assert merge["counters"]["launches"] == merge["count"]
    assert 0 < fan["host_us"]


if __name__ == "__main__":
    _child()
