"""CPU rehearsal of `chip_smoke.py`: its phases at tiny sizes through the
XLA twins of the kernels, its correctness checks against broken inputs,
its refusal to run without a TPU, and where the compilation cache goes."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod       # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


cs = _load()
TINY = cs.Sizes(exact_n=3000, routed_n=2000, dim=48, universe=100,
                q_exact=24, q_routed=20, gt_check=4, delta_chunk=64,
                ref_chunk=8)


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"),
               **extra)
    return env


def test_phase_exact_tiny():
    rec = cs.CallRecorder()
    with rec.patch():
        out = cs.phase_exact(TINY, seed=0)
    assert out["n"] == TINY.exact_n and out["dim"] == TINY.dim
    for name in cs.PRED_NAMES.values():
        assert out[name]["q"] == TINY.q_exact
        assert out[name]["hits"] > 0


def test_phase_routed_tiny():
    out = cs.phase_routed(TINY, seed=0)
    assert abs(sum(out["decision_share"].values()) - 1.0) < 1e-2
    assert len(out["decision_share"]) >= 2     # decisions spread
    assert out["direct_recall_at_10_AND"]["prefilter"] == 1.0
    assert set(out["build_s_per_method"]) >= {"fvamana", "sieve", "labelnav",
                                              "ivf_gamma", "postfilter"}


def test_phase_live_tiny():
    rec = cs.CallRecorder()
    with rec.patch():
        out = cs.phase_live(TINY, seed=0)
    served = {name for name, _, _ in rec.calls.values()}
    # both fused variants and the staged merge ran
    assert {"fused_live_topk", "fused_live_topk_select", "merge_topk",
            "masked_topk"} <= served
    assert out["round1"]["delta_rows"] > 4 * TINY.delta_chunk
    assert out["delta_prune"]["pruned"] > 0


def test_kernel_proof_rejects_xla_twin():
    """Off a TPU the ops take their XLA twins: the proof must fail."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    rec = cs.CallRecorder()
    with rec.patch():
        ops.masked_topk(rng.normal(size=(4, 16)).astype(np.float32),
                        np.ones((4, 2), np.uint32),
                        rng.normal(size=(300, 16)).astype(np.float32),
                        np.ones(300, np.float32), np.ones((2, 300), np.uint32),
                        pred=1, k=5)
    assert len(rec.calls) == 1
    with pytest.raises(cs.SmokeError, match="without its Pallas kernel"):
        cs.phase_kernels(rec, set())


def test_kernel_proof_requires_served_ops():
    with pytest.raises(cs.SmokeError, match="never served"):
        cs.phase_kernels(cs.CallRecorder(), {"selectivity"})


def test_kernel_names_found_in_compiled_text():
    text = ('  ROOT %merge_topk_accum.1 = (f32[8,128]) custom-call(%a), '
            'custom_call_target="tpu_custom_call", backend_config={}\n'
            '  %fusion.2 = f32[8] fusion(%x), kind=kLoop\n')
    assert cs.kernels_in(text) == {"merge_topk_accum"}
    assert cs.kernels_in("%dot.3 = f32[2,2] dot(%a, %b)") == set()


@pytest.fixture(scope="module")
def exact_case():
    from repro.ann.index import FilteredIndex, QueryBatch
    from repro.data.ann_synth import make_queries

    ds = cs.corpus("tiny", 800, TINY, seed=2)
    qs = make_queries(ds, 2, 6, k=5, seed=0, with_ground_truth=False)
    batch = QueryBatch(qs.vectors, qs.bitmaps, 2, 5)
    with FilteredIndex(ds) as fx:
        dev = fx.device
        want = cs.reference_topk(dev.vectors, dev.norms, dev.bitmaps,
                                 batch, chunk=4)
    return ds, batch, want


def test_check_exact_accepts_oracle_and_ties(exact_case):
    ds, batch, want = exact_case
    assert cs.check_exact(want, want, ds.vectors, ds.bitmaps, None, batch,
                          "same") == 0
    # swapping two rows of identical distance is a tie, not an error
    vec = np.concatenate([ds.vectors, ds.vectors[want[0, :1]]])
    bm = np.concatenate([ds.bitmaps, ds.bitmaps[want[0, :1]]])
    got = want.copy()
    got[0, 0] = ds.n
    assert cs.check_exact(got, want, vec, bm, None, batch, "tie") == 1


def test_check_exact_rejects_wrong_ids(exact_case):
    ds, batch, want = exact_case
    got = want.copy()
    got[0, 0] = want[0, -1] if want[0, -1] != want[0, 0] else -1
    with pytest.raises(cs.SmokeError):
        cs.check_exact(got, want, ds.vectors, ds.bitmaps, None, batch,
                       "dup")
    got = want.copy()
    got[1] = np.roll(got[1], 1)                  # order beyond ties
    with pytest.raises(cs.SmokeError):
        cs.check_exact(got, want, ds.vectors, ds.bitmaps, None, batch,
                       "order")
    alive = np.ones(ds.n, bool)
    alive[want[2, 0]] = False                    # a deleted row comes back
    got = want.copy()
    got[2, [0, 1]] = want[2, [1, 0]]
    with pytest.raises(cs.SmokeError):
        cs.check_exact(got, want, ds.vectors, ds.bitmaps, alive, batch,
                       "dead")


def test_main_fails_without_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert out.strip() == ""


def test_main_fails_outside_a_checkout(tmp_path):
    """Alone in a directory (no src/repro next to it) the script exits
    non-zero and prints no result, before touching JAX."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_sharded_phase_on_four_virtual_devices():
    code = ("import sys, json; sys.path.insert(0, %r); import chip_smoke as cs;"
            "cs.import_repo();"
            "out = cs.phase_sharded(cs.Sizes(routed_n=1200, dim=32, "
            "universe=60, q_routed=12), 0, 4);"
            "print(json.dumps(out['shard_devices']))" % ROOT)
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    devices = json.loads(p.stdout.strip().splitlines()[-1])
    assert len(set(devices)) == 4


@pytest.mark.parametrize("env_dir", [True, False])
def test_enable_compilation_cache_placement(tmp_path, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands;
    without it, the cache goes to the fixed checkout path."""
    code = ("import jax; from repro.common import enable_compilation_cache,"
            " JAX_CACHE_DIR; enable_compilation_cache();"
            "print(jax.config.jax_compilation_cache_dir); print(JAX_CACHE_DIR)")
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    env = _cpu_env(**extra)
    if not env_dir:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    configured, fixed = p.stdout.strip().splitlines()[-2:]
    if env_dir:
        assert configured == str(tmp_path)
    else:
        assert configured == fixed == os.path.join(ROOT, "artifacts",
                                                    "jax_cache")
