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
