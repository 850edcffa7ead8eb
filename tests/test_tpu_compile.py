"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

Interpret mode accepts kernels the chip's compiler refuses (lane-misaligned
slices, unsupported gathers, VMEM overruns), so these tests lower the
kernel-backed ops at the published width (D = 768, a 1,000-label
vocabulary = 32 bitmap words) for a described `v5e:2x2` topology and
compile them with the TPU compiler that ships with jax — no chip needed.
Each compile takes a few seconds; nothing runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, W = 768, 32
N_BASE = 65536          # 64 default-size base blocks
N_DELTA = 4096
PREDS = (0, 1, 2)


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip; the persistent compilation cache is off
    around these compiles (a chip-less compile cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:    # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered, kernel: str) -> None:
    text = lowered.compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"%{kernel}" in text, f"{kernel} missing from the compiled text"


def _query_specs(sh, q):
    return _spec(sh, (q, D), jnp.float32), _spec(sh, (q, W), jnp.uint32)


# k = 344: a live base overfetch (k + 1% tombstones of a 32k-row base)
@pytest.mark.parametrize("q,k", [(25, 10), (25, 100), (256, 10), (64, 344)])
@pytest.mark.parametrize("pred", PREDS)
def test_masked_topk_compiles(one_chip, pred, q, k):
    sh = one_chip
    lowered = ops.masked_topk.lower(
        *_query_specs(sh, q), _spec(sh, (N_BASE, D), jnp.float32),
        _spec(sh, (N_BASE,), jnp.float32),
        _spec(sh, (W, N_BASE), jnp.uint32), pred=pred, k=k,
        interpret=False)
    _assert_kernel(lowered, "masked_topk_accum")


@pytest.mark.parametrize("q", [25, 256])
@pytest.mark.parametrize("pred", PREDS)
def test_selectivity_compiles(one_chip, pred, q):
    sh = one_chip
    lowered = ops.selectivity.lower(
        _spec(sh, (q, W), jnp.uint32), _spec(sh, (W, N_BASE), jnp.uint32),
        pred=pred, interpret=False)
    _assert_kernel(lowered, "selectivity_count")


@pytest.mark.parametrize("q,kk,k", [(25, 10, 10), (25, 100, 100),
                                    (256, 10, 10)])
def test_merge_topk_compiles(one_chip, q, kk, k):
    sh = one_chip
    lowered = ops.merge_topk.lower(
        _spec(sh, (4, q, kk), jnp.int32), _spec(sh, (4, q, kk), jnp.float32),
        k=k, interpret=False)
    _assert_kernel(lowered, "merge_topk_accum")


def _live_specs(sh, q, kb):
    return (*_query_specs(sh, q), _spec(sh, (q, kb), jnp.int32),
            _spec(sh, (q, kb), jnp.float32),
            _spec(sh, (N_DELTA, D), jnp.float32),
            _spec(sh, (N_DELTA,), jnp.float32),
            _spec(sh, (W, N_DELTA), jnp.uint32))


def _tomb_spec(sh):
    return _spec(sh, ((N_BASE + N_DELTA) // 32,), jnp.uint32)


@pytest.mark.parametrize("q,kb,k", [(25, 10, 10), (25, 344, 100)])
@pytest.mark.parametrize("pred", PREDS)
def test_fused_live_topk_compiles(one_chip, pred, q, kb, k):
    sh = one_chip
    lowered = ops.fused_live_topk.lower(
        *_live_specs(sh, q, kb), _spec(sh, (), jnp.int32), _tomb_spec(sh),
        pred=pred, k=k, interpret=False)
    _assert_kernel(lowered, "fused_live_accum")


@pytest.mark.parametrize("pred", PREDS)
def test_fused_live_topk_select_compiles(one_chip, pred):
    sh = one_chip
    lowered = ops.fused_live_topk_select.lower(
        *_live_specs(sh, 25, 10), _spec(sh, (N_DELTA // 2,), jnp.int32),
        _spec(sh, (), jnp.int32), _tomb_spec(sh), pred=pred, k=10,
        interpret=False)
    _assert_kernel(lowered, "fused_live_accum")


# 128 IVF lists: four probe words
@pytest.mark.parametrize("q", [25, 64])
@pytest.mark.parametrize("pred", PREDS)
def test_ivf_scan_topk_compiles(one_chip, pred, q):
    sh = one_chip
    lowered = ops.ivf_scan_topk.lower(
        *_query_specs(sh, q), _spec(sh, (q, 4), jnp.uint32),
        _spec(sh, (N_BASE, D), jnp.float32),
        _spec(sh, (N_BASE,), jnp.float32),
        _spec(sh, (W, N_BASE), jnp.uint32),
        _spec(sh, (N_BASE,), jnp.int32), pred=pred, k=10, interpret=False)
    _assert_kernel(lowered, "ivf_scan_accum")


@pytest.mark.parametrize("pred", PREDS)
def test_resident_scan_compiles(one_chip, pred, monkeypatch):
    """One shard's exact scan of a 256-query batch for a row-sharded
    handle: the four 64-query chunks' Pallas scans in one program."""
    from repro.ann.methods.prefilter import _scan_resident

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    sh = one_chip
    lowered = _scan_resident.lower(
        *_query_specs(sh, 256), _spec(sh, (N_BASE, D), jnp.float32),
        _spec(sh, (N_BASE,), jnp.float32),
        _spec(sh, (N_BASE, W), jnp.uint32),
        _spec(sh, (W, N_BASE), jnp.uint32), pred=pred, k=10, kernel=True)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert "%masked_topk_accum" in text


def test_resident_merge_compiles(one_chip, monkeypatch):
    """The merge of four shards' [256, 10] candidates on one chip: ids
    offset by each shard's first row, then the merge kernel."""
    from repro.ann.sharded import _globalise_merge

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    sh = one_chip
    lowered = _globalise_merge.lower(
        [_spec(sh, (256, 10), jnp.int32)] * 4,
        [_spec(sh, (256, 10), jnp.float32)] * 4,
        _spec(sh, (4,), jnp.int32), k=10)
    _assert_kernel(lowered, "merge_topk_accum")


@pytest.mark.parametrize("pred", PREDS)
def test_postfilter_scan_compiles(one_chip, pred, monkeypatch):
    """Post-filter ef2000's chunk function: 64 queries, 32 of 128 lists
    probed, k′ = 2000: the probe-masked Pallas scan, then the [Q, N]
    product and the k′ rank count in XLA."""
    from repro.ann.methods.postfilter import scan

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    sh = one_chip
    nlist = 128
    lowered = scan.lower(
        *_query_specs(sh, 64), _spec(sh, (nlist, D), jnp.float32),
        _spec(sh, (nlist,), jnp.float32), _spec(sh, (nlist,), jnp.int32),
        _spec(sh, (N_BASE,), jnp.int32), _spec(sh, (N_BASE, D), jnp.float32),
        _spec(sh, (N_BASE,), jnp.float32),
        _spec(sh, (W, N_BASE), jnp.uint32), nprobe=32, kprime=2000, k=10,
        pred=pred)
    _assert_kernel(lowered, "ivf_scan_accum")
