"""The four-chip cell `hc768-sharded4-b256` and the readers of the
program's fan-out spans (`fanout_ms.batch`, `fanout_host_ms.batch`,
`merge_ms.batch`).

The cell's configuration is the exact one's with four times the rows in
four shards. A traced run of the cell, cut to a few thousand rows, runs
in a child process with four virtual CPU devices (this file run as a
script) and reads the readers; each reader gives nothing where the
program has no fan-out spans (an open loop, no tracer, a program from
before them)."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELL = "hc768-sharded4-b256"
FANOUT = ("fanout_ms.batch", "fanout_host_ms.batch", "merge_ms.batch")


def test_cell_is_the_exact_configuration_on_four_shards():
    bench = run.catalog()
    cell, cfg, mix = run.resolve(bench, CELL)
    _, exact, exact_mix = run.resolve(bench, "hc768-exact-b256")
    assert cell["chips"] == cfg["shards"] == 4
    assert cfg["corpus"]["n"] == 4 * exact["corpus"]["n"] == 4194304
    assert mix == exact_mix
    same = {k for k in exact if k not in ("name", "source", "deployment",
                                          "corpus", "assumed")}
    assert {k: cfg[k] for k in same} == {k: exact[k] for k in same}
    assert cfg["source"].startswith(exact["source"])
    assert dict(cfg["corpus"], n=0) == dict(exact["corpus"], n=0)


def test_sharded_configuration_is_a_deployment_of_its_own():
    # a configuration is told apart by its source and its cuts of scale:
    # the sharded deployment names where its sharding comes from
    bench = run.catalog()
    keys = [(c["source"], tuple(sorted(c["reduced"])))
            for c in bench["configs"]]
    assert len(set(keys)) == len(keys)
    entry = next(c for c in bench["configs"] if c["name"] == "hc768-sharded4")
    assert len(entry["source"]) <= 200 and "\n" not in entry["source"]


def test_fanout_metrics_are_declared_for_the_sharded_cell_only():
    bench = run.catalog()
    for w in ("hc768-exact-b256", "hc768-routed-b256", CELL):
        names = {m["name"] for m in run.metrics_for(bench, "per_layer", w)}
        assert (set(FANOUT) <= names) == (w == CELL)
    names = {m["name"] for m in run.metrics_for(bench, "per_layer", CELL)}
    assert {"route_ms.batch", "execute_ms.batch", "idle_share.batch",
            "masked_topk_accum_roofline", "launches.batch"} <= names
    assert "qps" in {m["name"] for m in
                     run.metrics_for(bench, "end_to_end", CELL)}


def _ctx(kind="closed", spans=None, trace=None):
    return types.SimpleNamespace(kind=kind, spans=spans, trace=trace)


@pytest.mark.parametrize("metric", FANOUT)
def test_reader_with_and_without_fanout_spans(metric):
    read = run.reader(metric)
    plain = {"sum_us": 9000.0, "count": 3, "counts": [3], "host_us": 600.0,
             "counters": {}}
    one_chip_ops = {"ops": {"masked_topk_accum": 1.0}}
    # no tracer, an empty one, a one-chip program (no fan-out spans and
    # no merge kernel)
    assert read(_ctx(spans=None)) is None
    assert read(_ctx(spans={}, trace=one_chip_ops)) is None
    one_chip = {n: dict(plain) for n in ("search", "route", "execute")}
    assert read(_ctx(spans=one_chip, trace=one_chip_ops)) is None
    full = dict(one_chip, fanout=dict(plain, sum_us=6000.0, host_us=300.0),
                merge=dict(plain, sum_us=4500.0))
    sharded_ops = {"ops": {"masked_topk_accum": 1.0,
                           "merge_topk_accum": 0.0045}}
    assert read(_ctx(kind="open", spans=full, trace=sharded_ops)) is None
    want = {"fanout_ms.batch": 2.0, "fanout_host_ms.batch": 0.1,
            "merge_ms.batch": 1.5}[metric]
    assert read(_ctx(spans=full, trace=sharded_ops)) == pytest.approx(want)
    # a merge span, or a merge kernel, alone: a program from before the
    # fan-out spans, or a run without the device trace
    assert read(_ctx(spans=dict(one_chip, merge=full["merge"]),
                     trace=one_chip_ops)) is None
    if metric == "merge_ms.batch":
        assert read(_ctx(spans=full, trace=None)) is None
        assert read(_ctx(spans=full, trace=one_chip_ops)) is None


# ---------------------------------------------------------------------------
# a traced run of the cell on four virtual devices
# ---------------------------------------------------------------------------

def _child(tmp: str) -> None:
    import jax

    orig = run.resolve

    def resolve(bench, workload):
        cell, cfg, mix = orig(bench, workload)
        cfg = copy.deepcopy(cfg)
        cfg["corpus"]["n"] = 3000
        cfg["router_pool_per_pred"] = 32
        return cell, cfg, dict(mix, pool_per_pred=64, batch=32)

    run.resolve = resolve
    run.OUT_DIR = os.path.join(tmp, "out")
    assert len(jax.devices()) == 4
    res = run.run_cell(CELL, 2 ** 31 + 17, 1.0, True, require_tpu=False)
    print("RESULT " + json.dumps({
        "correct": res["correct"], "check": res["check"],
        "attempted": res["attempted"], "device": res["device"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
        flush=True)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fanout")
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jc"),
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__), str(tmp)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_traced_cell_is_correct_on_four_devices(traced):
    assert traced["correct"], traced["check"]
    assert traced["attempted"] > 0
    assert traced["device"]["count"] == 4


def test_traced_cell_reads_the_fanout_metrics(traced):
    m = traced["metrics"]
    assert {"fanout_ms.batch", "fanout_host_ms.batch"} <= set(m)
    # off the TPU the merge is an XLA top-k: no merge kernel to read
    assert "merge_ms.batch" not in m
    assert 0 < m["fanout_host_ms.batch"] <= m["fanout_ms.batch"]
    assert m["fanout_ms.batch"] <= m["execute_ms.batch"]
    assert 0 < m["execute_host_ms.batch"] <= m["execute_ms.batch"]


if __name__ == "__main__":
    _child(sys.argv[1])
