"""Row partition and placement of a corpus sharded over devices.

`repro.ann.sharded.ShardedFilteredIndex` (and the live
`ShardedLiveIndex`) split a corpus into contiguous row shards, one per
device: `shard_bounds` gives the partition and `shard_devices` the
placement. Each shard answers its own top-k and only the [Q, k]
candidates meet for the merge, so the bytes that cross devices per query
are `shards × k × 8`, whatever the corpus size.
"""

from __future__ import annotations

import numpy as np

import jax


def shard_bounds(n: int, n_shards: int) -> np.ndarray:
    """Balanced contiguous row partition: [S+1] boundaries with every
    shard size n//S or n//S + 1 (the first `n % S` shards take the extra
    row). Raises ValueError unless 1 <= n_shards <= n."""
    if not 1 <= n_shards <= n:
        raise ValueError(f"need 1 <= n_shards <= n; got {n_shards}, n={n}")
    base, extra = divmod(n, n_shards)
    sizes = np.full(n_shards, base, dtype=np.int64)
    sizes[:extra] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


def shard_devices(n_shards: int) -> list:
    """One jax device per shard, round-robin over the host's devices
    (every shard shares the single device of a CPU host)."""
    devs = jax.local_devices()
    return [devs[i % len(devs)] for i in range(n_shards)]
