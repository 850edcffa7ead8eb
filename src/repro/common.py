"""Shared utilities: artifact paths, persistent compilation cache, timers."""

from __future__ import annotations

import contextlib
import os
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def artifacts_dir(*sub: str) -> str:
    d = os.path.join(os.environ.get("REPRO_ARTIFACTS", os.path.join(_REPO_ROOT, "artifacts")), *sub)
    os.makedirs(d, exist_ok=True)
    return d


JAX_CACHE_DIR = os.path.join(_REPO_ROOT, "artifacts", "jax_cache")


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here. Otherwise the cache lives at the fixed
    checkout path `JAX_CACHE_DIR`: the directory is part of what finds an
    entry again, so it never follows ``REPRO_ARTIFACTS`` or anything that
    changes from run to run."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(JAX_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@contextlib.contextmanager
def timer():
    """`with timer() as t: ...; t()` -> elapsed seconds."""
    t0 = time.perf_counter()
    elapsed = [0.0]
    yield lambda: elapsed[0]
    elapsed[0] = time.perf_counter() - t0
