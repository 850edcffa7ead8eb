"""The benchmark harness on the CPU at tiny sizes: a whole run of each
cell kind, the comparison failing an answer altered where the program
produces it, the control failing where the program passes, and the
refusal to run without a TPU or without the program."""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import control, run  # noqa: E402


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Cells cut to a few thousand rows (768 dims: the widths, and so the
    rounding, of the real cells) with small pools and windows, and no
    persistent compilation cache."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "out"))
    # enable_cache sets these process-wide; they are put back afterwards
    saved = {opt: getattr(jax.config, opt) for opt in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    orig = run.resolve

    def resolve(bench, workload):
        cell, cfg, mix = orig(bench, workload)
        cfg = copy.deepcopy(cfg)
        cfg["corpus"]["n"] = 3000
        cfg["router_pool_per_pred"] = 32
        mix = dict(mix, pool_per_pred=64)
        if mix["kind"] == "closed":
            mix["batch"] = 32
        else:
            mix.update(max_batch=4, rate_qps=40)
        return cell, cfg, mix

    monkeypatch.setattr(run, "resolve", resolve)
    bench = run.catalog()
    if "hc768-routed-online" not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append({"name": "hc768-routed-online",
                                   "config": "hc768-routed",
                                   "traffic": "poisson-single", "chips": 1,
                                   "why": "open loop"})
    yield bench
    for opt, value in saved.items():
        jax.config.update(opt, value)


@pytest.mark.parametrize("workload", ["hc768-exact-b256",
                                      "hc768-routed-b256",
                                      "hc768-routed-online"])
def test_run_is_correct_and_well_formed(tiny, workload):
    res = run.run_cell(workload, 2 ** 31 + 7, 1.0, False, require_tpu=False,
                       bench=tiny)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["recall_at_10"]["value"] > 0.3
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "check"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(res)


def test_traced_run_reads_layers(tiny):
    res = run.run_cell("hc768-exact-b256", 5, 1.0, True, require_tpu=False,
                       bench=tiny)
    assert res["correct"]
    assert res["metrics"]["route_ms.batch"]["value"] > 0
    assert res["metrics"]["execute_ms.batch"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", ["hc768-exact-b256",
                                      "hc768-routed-b256",
                                      "hc768-routed-online"])
def test_altered_answer_is_not_correct(tiny, monkeypatch, workload):
    """A fault planted where answers are produced: each method's first
    answer of every call names the next row instead."""
    from repro.ann.index import FilteredIndex

    orig = FilteredIndex.run_method

    def altered(self, method, setting, batch, **kw):
        ids, raw = orig(self, method, setting, batch, **kw)
        ids = np.array(ids)
        if ids[0, 0] >= 0:
            ids[0, 0] = (ids[0, 0] + 1) % self.ds.n
        return ids, raw

    monkeypatch.setattr(FilteredIndex, "run_method", altered)
    res = run.run_cell(workload, 9, 1.0, False, require_tpu=False, bench=tiny)
    assert not res["correct"]
    assert (res["check"]["bad_rows"]["value"] > 0
            or res["check"]["dist_err"]["value"]
            > res["check"]["dist_err"]["limit"])


@pytest.mark.parametrize("workload", ["hc768-exact-b256",
                                      "hc768-routed-b256",
                                      "hc768-routed-online"])
def test_half_the_batch_left_out_is_not_correct(tiny, monkeypatch, workload):
    """A fault planted where answers are produced: each method call
    answers the first half of its queries and leaves the rest empty."""
    from repro.ann.index import FilteredIndex

    orig = FilteredIndex.run_method

    def halved(self, method, setting, batch, **kw):
        ids, raw = orig(self, method, setting, batch, **kw)
        ids, raw = np.array(ids), np.array(raw)
        ids[ids.shape[0] // 2:] = -1
        raw[raw.shape[0] // 2:] = np.inf
        return ids, raw

    monkeypatch.setattr(FilteredIndex, "run_method", halved)
    res = run.run_cell(workload, 9, 1.0, False, require_tpu=False, bench=tiny)
    assert not res["correct"]
    check = res["check"]
    assert (check["bad_rows"]["value"] > 0
            or check["empty_share"]["value"] > check["empty_share"]["limit"])


def test_misrouted_answers_are_not_correct(tiny, monkeypatch):
    """A fault planted in routing: the selectivity feature reads 0, so
    the router decides on wrong inputs."""
    from repro.core import features

    monkeypatch.setattr(features, "batch_selectivity",
                        lambda ds, qbms, pred, **kw: np.zeros(qbms.shape[0]))
    res = run.run_cell("hc768-routed-b256", 9, 1.0, False,
                       require_tpu=False, bench=tiny)
    assert not res["correct"]
    assert res["check"]["route_diff"]["value"] > 0


@pytest.mark.parametrize("workload", ["hc768-exact-b256",
                                      "hc768-routed-b256"])
def test_control_is_not_correct(tiny, workload):
    """The reference one precision step below the configuration's, put
    in the program's place, fails the comparison the program passes."""
    rows = list(control.readings(workload, [3, 4], 1.0, require_tpu=False,
                                 bench=tiny))
    for r in rows:
        assert r["correct_program"], r
        assert not r["correct_control"], r


def _run_cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hc768-exact-b256",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _lines_with_metrics(text):
    return [ln for ln in text.splitlines() if '"metrics"' in ln]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_cli(ROOT, env)
    assert p.returncode != 0
    assert not _lines_with_metrics(p.stdout)
    assert "TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "out",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _run_cli(str(tmp_path), env)
    assert p.returncode != 0
    assert not _lines_with_metrics(p.stdout)
