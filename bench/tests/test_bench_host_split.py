"""The readers of the program's host-time spans and launch counters:
a traced run of each closed cell on the CPU at tiny sizes reads them,
and each reader returns nothing where the program has no such span or
counter (an open loop, no tracer, a tracer without the totals)."""

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

HOST_SPLIT = ("route_host_ms.batch", "execute_host_ms.batch",
              "launches.batch", "pad_share.batch", "cand_per_query.batch")


def test_host_split_metrics_are_declared_for_their_cells():
    bench = run.catalog()
    for w in ("hc768-exact-b256", "hc768-routed-b256"):
        names = {m["name"] for m in run.metrics_for(bench, "per_layer", w)}
        want = set(HOST_SPLIT) - ({"cand_per_query.batch"}
                                  if w == "hc768-exact-b256" else set())
        assert want <= names


@pytest.mark.parametrize("workload", ["hc768-exact-b256",
                                      "hc768-routed-b256"])
def test_traced_run_reads_host_split(tiny, workload):  # noqa: F811
    res = run.run_cell(workload, 2 ** 31 + 11, 1.0, True,
                       require_tpu=False, bench=tiny)
    assert res["correct"], res["check"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < m["route_host_ms.batch"] <= m["route_ms.batch"]
    assert 0 < m["execute_host_ms.batch"] <= m["execute_ms.batch"]
    if workload == "hc768-exact-b256":
        # CPU: routing's selectivity runs on the host, so a 32-query
        # batch is one 64-slot prefilter chunk (32 padded) and the MLP
        assert "cand_per_query.batch" not in m
        assert m["launches.batch"] == 2
        assert m["pad_share.batch"] == pytest.approx(100 * 32 / (64 + 32))
    else:
        assert m["launches.batch"] >= 2
        assert 0 <= m["pad_share.batch"] < 100
        assert m["cand_per_query.batch"] > 0


def _ctx(kind="closed", spans=None):
    return types.SimpleNamespace(kind=kind, spans=spans)


@pytest.mark.parametrize("metric", HOST_SPLIT)
def test_reader_finds_nothing_without_the_program_totals(metric):
    read = run.reader(metric)
    plain = {"sum_us": 9.0, "count": 3, "counts": [3]}
    assert read(_ctx(spans=None)) is None
    assert read(_ctx(spans={})) is None
    # a tracer that keeps no host time or counters (an older program)
    assert read(_ctx(spans={n: dict(plain) for n in
                            ("search", "route", "execute")})) is None
    full = {n: dict(plain, host_us=1500.0,
                    counters={"launches": 6, "slots": 300, "pad_slots": 44,
                              "cand_rows": 900, "queries": 30})
            for n in ("search", "route", "execute")}
    assert read(_ctx(kind="open", spans=full)) is None
    want = {"route_host_ms.batch": 0.5, "execute_host_ms.batch": 0.5,
            "launches.batch": 2.0, "pad_share.batch": 100 * 44 / 300,
            "cand_per_query.batch": 30.0}[metric]
    assert read(_ctx(spans=full)) == pytest.approx(want)
