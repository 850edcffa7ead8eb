#!/usr/bin/env python3
"""Open-loop rate sweep of an open-loop cell, for choosing its fixed rate.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 10 --rates 50,100,200

In one process the cell's deployment is set up and warmed once, then
one window runs at each rate (the mix's other parameters unchanged). A
rate is sustained when the backlog does not grow over its window: the
median latency of the last fifth of the requests stays within twice
that of the first fifth, and every request is answered. One JSON line
per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def sweep(workload: str, seed: int, seconds: float, rates: list[float], *,
          require_tpu: bool = True, bench: dict | None = None):
    run.import_program()
    bench = bench or run.catalog()
    cell, cfg, mix = run.resolve(bench, workload)
    if mix["kind"] != "open":
        raise run.BenchError(f"{workload} is not an open-loop cell")
    run.device_check(int(cell["chips"]), require_tpu)
    run.enable_cache()
    dep = run.setup(cfg, mix, seed, trace=False)
    run.warm_open(dep, mix)
    for rate in rates:
        w = run.window_open(dep, dict(mix, rate_qps=rate), seconds, seed,
                            run.Profiler(False))
        lat = w.latency_s
        fifth = max(1, lat.size // 5)
        head = float(np.median(lat[:fifth]))
        tail = float(np.median(lat[-fifth:]))
        s = w.queue_stats or {}
        yield {"rate_qps": rate, "requests": int(lat.size),
               "failed": w.failed,
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p99_ms": float(np.percentile(lat, 99) * 1e3),
               "head_ms": head * 1e3, "tail_ms": tail * 1e3,
               "sustained": bool(w.failed == 0 and tail <= 2.0 * head),
               "batch_q_mean": (s.get("queries", 0) / s["batches"]
                                if s.get("batches") else None),
               "max_queue_depth": s.get("max_queue_depth"),
               "elapsed_s": w.elapsed_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    try:
        for row in sweep(args.workload, args.seed, args.seconds,
                         [float(r) for r in args.rates.split(",")]):
            print(json.dumps(row), flush=True)
    except run.BenchError as e:
        run.log(f"sweep: FAILED: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
