#!/usr/bin/env python3
"""Readings of the program and of the control, for setting the limits.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

In one process: the cell's deployment is set up once (its corpus and
router come from the configuration, not from the seed), then for each
seed the order of the query pool is drawn, a short window runs at the cell's own
load, and the window's answers are compared with the reference twice:
as the program gave them, and with the reference one precision step
lower put in the program's place (`run.control_answers`, in the
configuration's `control` precision). One JSON line per seed; the control has
to read above the limits where the program reads below them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import reference, run  # noqa: E402


def readings(workload: str, seeds: list[int], seconds: float, *,
             mode: str | None = None, require_tpu: bool = True,
             bench: dict | None = None):
    run.import_program()
    bench = bench or run.catalog()
    cell, cfg, mix = run.resolve(bench, workload)
    run.device_check(int(cell["chips"]), require_tpu)
    run.enable_cache()
    dep = run.setup(cfg, mix, seeds[0], trace=False)
    dev = run.reference_corpus(dep)
    mode = mode or cfg["control"]
    pool_methods = set(cfg["pool"])
    closed = mix["kind"] == "closed"
    for seed in seeds:
        dep.seed = seed
        (run.warm_closed if closed else run.warm_open)(dep, mix)
        prof = run.Profiler(False)
        w = (run.window_closed(dep, mix, seconds, prof) if closed
             else run.window_open(dep, mix, seconds, seed, prof))
        import numpy as np

        ans = reference.distinct(np.concatenate(w.pool_idx),
                                 np.concatenate(w.ids),
                                 np.concatenate(w.dists), w.decisions)
        prog = run.compare(dep, ans, pool_methods, dev=dev)
        ctrl = run.compare(dep, ans, pool_methods, mode=mode,
                           answers_from_reference=True, dev=dev)
        yield {"seed": seed, "failed": w.failed, "program": prog,
               "control": ctrl, "mode": mode,
               "correct_program": reference.judge(prog, cfg["limits"])[0],
               "correct_control": reference.judge(ctrl, cfg["limits"])[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--mode", help="control precision other than the "
                    "configuration's (bf16x3 or bf16)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for row in readings(args.workload, seeds, args.seconds,
                            mode=args.mode):
            print(json.dumps(row), flush=True)
    except run.BenchError as e:
        run.log(f"control: FAILED: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
