"""A corpus row-sharded over a cell's chips, on the CPU at tiny sizes.

JAX fixes its device count when it starts, so the runs happen in one
child process with four virtual CPU devices (this file run as a script);
the tests read its JSON lines. The child serves a configuration with
`shards: 4` on a four-chip cell through `ShardedFilteredIndex` and
`ShardedRouterService`, checks it against the reference spread over the
four devices, plants faults (a wrong id where each shard produces its
answers; the last shard's part left out of the merge; shard-local ids
left without their row offsets), and asks for four shards on two chips. The blocked reference itself is
checked in this process: over 1, 2 and 4 blocks it returns what one
block does, ties across a block boundary included."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import gen, reference, run, trace_reduce  # noqa: E402

SEED = 2 ** 31 + 13
CELLS = [   # (name, configuration, chips)
    ("exact-sharded4", "hc768-exact-sharded4", 4),
    ("exact-sharded4-on2", "hc768-exact-sharded4", 2),
]


# ---------------------------------------------------------------------------
# the child process
# ---------------------------------------------------------------------------

def _tiny_bench(tmp: str) -> dict:
    """The catalog with a `shards: 4` copy of `hc768-exact`, written under
    `tmp` and cut as `test_bench_harness.tiny` cuts it, and the sharded
    cells on it; the mix is cut likewise."""
    orig = run.resolve

    def resolve(bench, workload):
        cell, cfg, mix = orig(bench, workload)
        return cell, cfg, dict(mix, pool_per_pred=64, batch=32)

    run.resolve = resolve
    bench = run.catalog()
    src = {c["name"]: c for c in bench["configs"]}["hc768-exact"]
    cfg = copy.deepcopy(run.load_json(os.path.join(ROOT, src["file"])))
    cfg["name"] = "hc768-exact-sharded4"
    cfg["corpus"]["n"] = 3000
    cfg["router_pool_per_pred"] = 32
    cfg["shards"] = 4
    path = os.path.join(tmp, "hc768-exact-sharded4.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    bench["configs"] = [dict(src, name=cfg["name"], file=path)]
    bench["workloads"] = [{"name": n, "config": c, "traffic": "b256-closed",
                           "chips": chips, "why": "sharded on the CPU"}
                          for n, c, chips in CELLS]
    return bench


def _merged(per, offsets, k):
    """The sharded handle's merge of per-shard (ids, raw), each shard's
    ids offset by its entry of `offsets`."""
    from repro.ann.sharded import merge_candidates, stack_candidates

    parts = [(np.where(np.asarray(i) >= 0, np.asarray(i) + np.int32(o), -1),
              r) for (i, r), o in zip(per, offsets)]
    return merge_candidates(*stack_candidates(parts), k)


def _last_shard_dropped(self, method, setting, batch):
    per = [fx.run_method(method, setting, batch) for fx in self.shards[:-1]]
    return _merged(per, self.bounds[:-2], batch.k)


def _ids_left_local(self, method, setting, batch):
    per = [fx.run_method(method, setting, batch) for fx in self.shards]
    return _merged(per, [0] * len(per), batch.k)


def _child(tmp: str) -> None:
    """One JSON line per run, each with its `scenario`."""
    import jax

    run.import_program()
    from repro.ann.index import FilteredIndex
    from repro.ann.sharded import ShardedFilteredIndex

    run.OUT_DIR = os.path.join(tmp, "out")
    bench = _tiny_bench(tmp)
    seen = {}
    setup, window = run.setup, run.window_closed

    def spy_setup(*a, **kw):
        dep = setup(*a, **kw)
        seen["fx"] = type(dep.fx).__name__
        seen["svc"] = type(dep.svc).__name__
        seen["shard_devices"] = [str(next(iter(s.device.vectors.devices())))
                                 for s in dep.fx.shards]
        seen["shard_rows"] = [s.ds.n for s in dep.fx.shards]
        return dep

    def spy_window(*a, **kw):
        w = window(*a, **kw)
        seen["served"] = sum(len(i) for i in w.ids)
        seen["prefilter_calls"] = sum(w.prefilter_calls)
        return w

    run.setup, run.window_closed = spy_setup, spy_window

    def emit(scenario, res=None, **extra):
        row = {"scenario": scenario, **seen, **extra}
        if res is not None:
            row.update(correct=res["correct"], attempted=res["attempted"],
                       failed=res["failed"], metrics=res["metrics"],
                       device=res["device"], check=res["check"])
        print(json.dumps(row), flush=True)
        seen.clear()

    assert len(jax.devices()) == 4
    emit("exact-sharded4", run.run_cell("exact-sharded4", SEED, 1.0, False,
                                        require_tpu=False, bench=bench))
    emit("exact-traced", run.run_cell("exact-sharded4", SEED + 1, 1.0, True,
                                      require_tpu=False, bench=bench))

    orig = FilteredIndex.run_method

    def altered(self, method, setting, batch, **kw):
        ids, raw = orig(self, method, setting, batch, **kw)
        ids = np.array(ids)
        if ids[0, 0] >= 0:
            ids[0, 0] = (ids[0, 0] + 1) % self.ds.n
        return ids, raw

    FilteredIndex.run_method = altered
    emit("exact-altered", run.run_cell("exact-sharded4", SEED + 2, 1.0, False,
                                       require_tpu=False, bench=bench))
    FilteredIndex.run_method = orig

    sharded = ShardedFilteredIndex.run_method
    for n, (scenario, fault) in enumerate((
            ("exact-shard-dropped", _last_shard_dropped),
            ("exact-ids-local", _ids_left_local))):
        ShardedFilteredIndex.run_method = fault
        emit(scenario, run.run_cell("exact-sharded4", SEED + 3 + n, 1.0,
                                    False, require_tpu=False, bench=bench))
    ShardedFilteredIndex.run_method = sharded

    made = []
    make_corpus = gen.make_corpus
    gen.make_corpus = lambda *a, **kw: made.append(1) or make_corpus(*a, **kw)
    try:
        run.run_cell("exact-sharded4-on2", SEED, 1.0, False,
                     require_tpu=False, bench=bench)
        emit("on2", refused=False, corpus_made=bool(made))
    except run.BenchError as e:
        emit("on2", refused=True, error=str(e), corpus_made=bool(made))


# ---------------------------------------------------------------------------
# the tests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def child(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sharded")
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jc"),
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__), str(tmp)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    rows = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"scenario"')]
    return {r["scenario"]: r for r in rows}


def test_sharded_cell_is_served_on_four_devices_and_correct(child):
    r = child["exact-sharded4"]
    assert r["fx"] == "ShardedFilteredIndex"
    assert r["svc"] == "ShardedRouterService"
    assert len(set(r["shard_devices"])) == 4
    assert sum(r["shard_rows"]) == 3000
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"]["recall_at_10"]["value"] == 1.0
    assert r["device"]["count"] == 4
    assert list(r["check"])[-1] == "failed"


def test_sharded_trace_counts_each_query_once(child):
    """`prefilter_calls`, the roofline's count of served queries, counts
    a query once however many shards scan it."""
    r = child["exact-traced"]
    assert r["correct"], r["check"]
    assert r["prefilter_calls"] == r["served"] == r["attempted"]


def test_altered_id_on_a_shard_is_not_correct(child):
    r = child["exact-altered"]
    assert not r["correct"]
    assert (r["check"]["bad_rows"]["value"] > 0
            or r["check"]["dist_err"]["value"]
            > r["check"]["dist_err"]["limit"])


def test_shard_left_out_of_the_merge_is_not_correct(child):
    """Rows of the last shard never reach an answer: answers rank worse
    than the reference's, and predicates only it matches come back
    short."""
    r = child["exact-shard-dropped"]
    assert not r["correct"]
    assert r["check"]["rank_gap"]["value"] > r["check"]["rank_gap"]["limit"]
    assert r["check"]["bad_rows"]["value"] > 0


def test_ids_left_shard_local_are_not_correct(child):
    """Ids without their shard's row offset name rows that fail the
    predicate or repeat across shards."""
    r = child["exact-ids-local"]
    assert not r["correct"]
    assert r["check"]["bad_rows"]["value"] > 0


def test_shards_other_than_chips_are_refused_before_setup(child):
    r = child["on2"]
    assert r["refused"] and not r["corpus_made"]
    assert "4 shards" in r["error"] and "2 chips" in r["error"]


def _corpus(n: int, d: int, seed: int) -> gen.Corpus:
    """Small-integer vectors, so every dot product is exact in float32
    whatever the order of its sums, and duplicate rows tie exactly."""
    rng = np.random.default_rng(seed)
    v = rng.integers(-3, 4, size=(n, d)).astype(np.float32)
    for a, b in ((0, 1), (47, 48), (49, 50), (51, 52), (99, 100),
                 (10, 148), (60, 61), (3, 197)):
        v[b] = v[a]         # 50, 100 and 150 are the edges of four blocks
    bm = rng.integers(0, 4, size=(n, 2)).astype(np.uint32)
    bm[::3] = 1                          # many rows share a label set
    for a, b in ((0, 1), (47, 48), (49, 50), (51, 52), (99, 100),
                 (10, 148), (60, 61), (3, 197)):
        bm[b] = bm[a]
    return gen.Corpus(v, bm, np.sum(v * v, axis=1), 64,
                      np.ones(n, np.int64), np.ones(64, np.int64))


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_blocked_reference_equals_one_block(blocks):
    """Queries that are corpus rows themselves, so each query's own row
    and its duplicate tie for first place; the blocks of 200 rows end at
    50, 100 and 150."""
    import jax

    c = _corpus(200, 16, 3)
    rows = np.array([0, 1, 47, 48, 49, 50, 99, 100, 10, 148, 60, 3, 197, 5])
    qv = c.vectors[rows]
    qb = np.concatenate([c.bitmaps[rows[:5]], np.ones((9, 2), np.uint32)])
    preds = np.array([0, 1, 2, 0, 1, 2, 0, 1, 2, 0, 1, 2, 0, 1])
    dev1 = jax.devices()[0]
    want = reference.topk(reference.to_device(c, [dev1]), qv, qb, preds, 10)
    got = reference.topk(reference.to_device(c, [dev1] * blocks), qv, qb,
                         preds, 10)
    assert reference.block_bounds(200, blocks) == list(
        range(0, 201, 200 // blocks))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # ties were crossed: some answer holds two rows of equal score
    assert any(np.isfinite(s[0]) and s[0] == s[1] for s in want[1])


def test_merge_breaks_ties_by_the_lower_id():
    ids = np.array([[7, 3, -1, 5]], np.int32)
    sc = np.array([[1.0, 1.0, np.inf, 0.5]], np.float32)
    i, s = reference.merge(ids, sc, 3)
    assert i.tolist() == [[5, 3, 7]] and s.tolist() == [[0.5, 1.0, 1.0]]


def test_trace_reduction_over_four_chips():
    """Busy time is averaged over the chips that ran ops, idle gaps come
    from the first chip, and a kernel's seconds are summed over all."""
    win = ["bench.window", 0.0, 1000.0]
    planes = [{"name": "/host:CPU", "lines": [{"name": "t", "events": [win]}]}]
    for c in range(4):
        ops = [["%masked_topk_accum.1 = f32[1]", 100.0, 100.0 + 100.0 * c]]
        planes.append({"name": f"/device:TPU:{c}",
                       "lines": [{"name": trace_reduce.OP_LINE,
                                  "events": ops}]})
    r = trace_reduce.reduce(planes)
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx((100 + 200 + 300 + 400) / 4 * 1e-9)
    assert trace_reduce.kernel_seconds(r, "masked_topk_accum") == \
        pytest.approx(1000e-9)
    assert sorted(s for _, s in r["idle_gaps"]) == pytest.approx(
        [100e-9, 800e-9])


if __name__ == "__main__":
    _child(sys.argv[1])
