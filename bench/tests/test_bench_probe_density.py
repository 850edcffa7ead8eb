"""The reader of ivf_gamma's scan counters, `probe_density.batch`: a
traced run of the routed cell on the CPU at tiny sizes reads it, and the
reader returns nothing where the program has no such counters (an open
loop, no tracer, a program without the scan)."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402
from bench.tests.test_bench_harness import tiny  # noqa: E402,F401


def test_probe_density_is_declared_for_the_routed_cell():
    bench = run.catalog()
    names = {m["name"] for m in
             run.metrics_for(bench, "per_layer", "hc768-routed-b256")}
    assert "probe_density.batch" in names
    names = {m["name"] for m in
             run.metrics_for(bench, "per_layer", "hc768-exact-b256")}
    assert "probe_density.batch" not in names


def test_traced_routed_run_reads_probe_density(tiny):  # noqa: F811
    res = run.run_cell("hc768-routed-b256", 2 ** 31 + 11, 1.0, True,
                       require_tpu=False, bench=tiny)
    assert res["correct"], res["check"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # the tiny router sends OR batches to ivf_gamma g8: the scan
    assert 0 < m["probe_density.batch"] <= 100


def test_probe_density_reads_the_scan_counters():
    read = run.reader("probe_density.batch")
    dims = {"n": 1000, "d": 8, "w": 1, "k": 10}

    def ctx(counters, kind="closed"):
        return types.SimpleNamespace(
            kind=kind, dims=dims,
            spans={"search": {"sum_us": 9.0, "count": 3,
                              "counters": counters}})

    assert read(types.SimpleNamespace(kind="closed", dims=dims,
                                      spans=None)) is None
    assert read(ctx({})) is None                   # a program without them
    assert read(ctx({"cand_rows": 900, "queries": 30})) is None
    assert read(ctx({"scan_queries": 0, "probe_rows": 0})) is None
    scans = {"scan_queries": 20, "probe_rows": 6000}
    assert read(ctx(scans, kind="open")) is None
    assert read(ctx(scans)) == pytest.approx(100 * 6000 / (20 * 1000))
