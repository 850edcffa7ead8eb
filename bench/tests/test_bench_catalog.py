"""The harness finds configurations, traffic mixes, per-layer readers and
work counts by name, a new file in each place is picked up with no edit
to any existing file, the work counts match hand counts, and the trace
reduction gives known busy, idle and kernel time."""

from __future__ import annotations

import json
import os
import sys
import time
import types
import uuid

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def test_every_cell_and_metric_resolves():
    bench = run.catalog()
    for w in bench["workloads"]:
        cell, cfg, mix = run.resolve(bench, w["name"])
        assert cfg["name"] == w["config"]
        assert mix["kind"] in ("closed", "open")
        assert set(cfg["limits"]) >= {"bad_rows", "dist_err", "route_diff"}
    for m in bench["per_layer"]:
        assert callable(run.reader(m["name"]))
    names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in names
    for w in bench["workloads"]:
        got = {m["name"] for m in run.metrics_for(bench, "end_to_end",
                                                  w["name"])}
        assert "setup_s" in got and len(got) >= 2
        assert run.metrics_for(bench, "per_layer", w["name"])


def test_new_files_are_picked_up_by_name():
    tag = "t" + uuid.uuid4().hex[:8]
    made = {
        os.path.join(run.BENCH, "configs", f"{tag}.json"):
            json.dumps({"name": tag, "k": 10}),
        os.path.join(run.BENCH, "traffic", f"{tag}.json"):
            json.dumps({"kind": "closed", "batch": 8}),
        os.path.join(run.BENCH, "metrics", f"{tag}.ms.py"):
            "def read(ctx):\n    return ctx.value * 2\n",
        os.path.join(run.BENCH, "work", f"{tag}.py"):
            "def work(q, n, d, w, k):\n    return q * n, 4 * n * d\n",
    }
    try:
        for path, text in made.items():
            with open(path, "w") as f:
                f.write(text)
        bench = {"configs": [{"name": tag,
                              "file": f"bench/configs/{tag}.json"}],
                 "workloads": [{"name": f"{tag}-cell", "config": tag,
                                "traffic": tag, "chips": 1}]}
        cell, cfg, mix = run.resolve(bench, f"{tag}-cell")
        assert cfg == {"name": tag, "k": 10} and mix["batch"] == 8
        assert run.reader(f"{tag}.ms")(types.SimpleNamespace(value=21)) == 42
        assert run.work_of(tag)(2, 3, 4, 5, 6) == (6, 48)
    finally:
        for path in made:
            os.remove(path)


def test_unknown_names_are_errors():
    bench = run.catalog()
    with pytest.raises(run.BenchError):
        run.resolve(bench, "no-such-cell")
    with pytest.raises(run.BenchError):
        run.reader("no_such_metric")
    with pytest.raises(run.BenchError):
        run.peaks_of("no such device")
    assert run.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_masked_scan_work_matches_hand_count():
    work = run.work_of("masked_topk_accum")
    # 256 queries x 1,048,576 rows x 768 dims, 32 bitmap words, k = 10
    flops, nbytes = work(256, 1 << 20, 768, 32, 10)
    assert flops == 2 * 256 * 1048576 * 768 == 412316860416
    base = 1048576 * 768 * 4 + 1048576 * 4 + 1048576 * 32 * 4
    queries = 256 * 768 * 4 + 256 * 32 * 4
    assert nbytes == base + queries + 256 * 10 * 8 == 3360477184
    # on a TPU v5e the batch is bound by bytes in one bfloat16 pass (4.10
    # ms against 2.09 ms), by compute at HIGHEST's six (12.56 ms)
    peaks = run.peaks_of("TPU v5 lite")
    assert nbytes / peaks["hbm_bytes_per_s"] > flops / peaks["flops_bf16_per_s"]
    highest = peaks["flops_bf16_per_s"] / peaks["matmul_passes"]["highest"]
    assert flops / highest == pytest.approx(12.56e-3, rel=1e-3)
    assert flops / highest > nbytes / peaks["hbm_bytes_per_s"]


@pytest.mark.parametrize("precision,least_ms", [("highest", 12.5579),
                                                ("default", 4.1031)])
def test_roofline_follows_the_configured_precision(precision, least_ms):
    """Two 256-query batches over 1,048,576 x 768 in 50 ms of kernel time:
    the least time is the compute leg at the configuration's precision
    where that is the longer one, else the bytes."""
    ctx = types.SimpleNamespace(
        trace={"ops": {"masked_topk_accum": 0.05}},
        peaks=run.peaks_of("TPU v5 lite"),
        cfg={"matmul_precision": precision},
        prefilter_calls=[256, 0, 256],
        dims={"n": 1 << 20, "d": 768, "w": 32, "k": 10},
        work=run.work_of)
    share = run.reader("masked_topk_accum_roofline")(ctx)
    assert share == pytest.approx(100.0 * 2 * least_ms * 1e-3 / 0.05,
                                  rel=1e-4)
    assert run.reader("masked_topk_accum_roofline")(
        types.SimpleNamespace(**dict(vars(ctx), prefilter_calls=[]))) is None


def test_host_probe_names_the_slowest_batch():
    """Batches repeat every three; the one held up past the median of its
    own pool batch is named, with each counter's rise over it."""
    probe = run.HostProbe()
    for i in range(9):
        probe.mark()
        time.sleep(0.15 if i == 4 else 0.01)
    probe.mark()
    slow = probe.slowest(3)
    assert slow["batch"] == 4 and slow["excess_s"] > 0.1
    assert slow["batch_s"] >= 0.15
    assert slow["cpu_s"] >= 0 and "window" in slow


def _synthetic_trace():
    """Window 0..1000 ns; device ops [100, 300) and [250, 400) (overlap),
    [600, 700); host annotations: search over 0..450, idle over 450..1000."""
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0.0, 1000.0],
            ["bench.search", 0.0, 450.0],
            ["bench.idle", 450.0, 550.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_x", 0.0, 1000.0]]},
            {"name": "XLA Ops", "events": [
                ["masked_topk_accum", 100.0, 200.0],
                ["fusion.3", 250.0, 150.0],
                ["masked_topk_accum.1", 600.0, 100.0]]}]},
    ]


def test_op_names():
    assert trace_reduce.op_name(
        "%masked_topk_accum.1 = (f32[64,128]{1,0}, s32[64,128]{1,0}) "
        "custom-call(f32[64,768]{1,0} %qvecs.1)") == "masked_topk_accum"
    assert trace_reduce.op_name("%copy-done.2 = f32[1,16]") == "copy-done"
    assert trace_reduce.op_name("%fusion = f32[64,10]") == "fusion"
    assert trace_reduce.op_name("masked_topk_accum") == "masked_topk_accum"


def test_trace_reduction_on_a_synthetic_trace():
    r = trace_reduce.reduce(_synthetic_trace())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)         # 100..400, 600..700
    assert r["devices"] == 1
    assert trace_reduce.kernel_seconds(r, "masked_topk_accum") == \
        pytest.approx(300e-9)
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # gaps 0..100 (search), 400..600 (idle), 700..1000 (idle)
    assert r["idle_gaps"][0] == ["bench.idle", pytest.approx(300e-9)]
    assert sorted(s for _, s in r["idle_gaps"]) == pytest.approx(
        [100e-9, 200e-9, 300e-9])
    assert "bench.search" in gaps


def test_trace_reduction_on_a_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three 64-query masked scans over
    65,536 rows, each followed by a 5 ms host sleep and a selectivity
    count (`small_trace.json`, the device op line and the benchmark's
    host annotations kept)."""
    with open(os.path.join(HERE, "small_trace.json")) as f:
        planes = json.load(f)
    r = trace_reduce.reduce(planes)
    ops = [ev for p in planes if p["name"].startswith("/device:TPU:")
           for ln in p["lines"] for ev in ln["events"]]
    win = [ev for p in planes for ln in p["lines"] for ev in ln["events"]
           if ev[0] == "bench.window"][0]
    inside = [(max(s, win[1]), min(s + d, win[1] + win[2]))
              for _, s, d in ops if s + d > win[1] and s < win[1] + win[2]]
    busy = trace_reduce._union(inside)
    assert r["busy_s"] == pytest.approx(sum(b - a for a, b in busy) * 1e-9)
    assert r["window_s"] == pytest.approx(win[2] * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    kern = trace_reduce.kernel_seconds(r, "masked_topk_accum")
    assert kern > 0
    assert kern == pytest.approx(sum(
        max(0.0, min(s + d, win[1] + win[2]) - max(s, win[1]))
        for n, s, d in ops
        if trace_reduce.op_name(n) == "masked_topk_accum") * 1e-9)
    # each 5 ms host sleep is a device gap named by its annotation
    assert any(n == "bench.idle" and s > 4e-3 for n, s in r["idle_gaps"])
