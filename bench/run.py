#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration
(`bench/configs/<config>.json`: the deployment: corpus, method pool,
router artifact, the limits of the comparison) and a traffic mix
(`bench/traffic/<traffic>.json`). The run

1. checks that the first JAX device is a TPU and that the cell's chips
   are there, and exits non-zero with no result otherwise;
2. sets up: makes the corpus and the router artifact, hands them to the
   program (`ANNDataset.from_packed`, `FilteredIndex`, `RouterService`;
   with a configuration's `shards` > 1, `ShardedFilteredIndex` with one
   row shard per chip of the cell and `ShardedRouterService`), builds the
   pool's indexes (on every shard), draws the query pool from the mix's
   `pool_seed` and its order from `--seed`, and
   warms up every shape the window will use (`setup_s`);
3. measures for `--seconds`: a closed loop of `RouterService.search`
   batches (up to the end of a whole pass over the pool), or open-loop
   arrivals through `AsyncBatchQueue.submit`;
4. frees the program's state and checks the window's answers against
   the plain reference (`bench/reference.py`), printing each compared
   number beside its limit;
5. prints one JSON line: the cell's end-to-end metrics with `--trace 0`;
   with `--trace 1` (spans on, the JAX profiler over the window) its
   per-layer metrics, each read by `bench/metrics/<metric>.py`.

The persistent compilation cache lives in `bench/.jax_cache` unless
JAX_COMPILATION_CACHE_DIR names another directory.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import gen, reference, router, trace_reduce  # noqa: E402

CACHE_DIR = os.path.join(BENCH, ".jax_cache")
OUT_DIR = os.path.join(BENCH, "out")
WARM_FEW = 8                       # queries per (method, setting) warm-up
LATE_GRACE_S = 60.0                # wait for open-loop answers past the close


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the catalog: cells, configurations, mixes and readers found by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def catalog() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def resolve(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of a cell name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(ROOT, cfgs[cell["config"]]["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    shards, chips = int(cfg.get("shards", 1)), int(cell["chips"])
    if shards > 1 and shards != chips:
        raise BenchError(f"configuration {cfg['name']!r} has {shards} shards, "
                         f"one per chip, but the cell asks for {chips} chips")
    return cell, cfg, mix


def metrics_for(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(path: str):
    name = "bench_plugin_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """`read(ctx) -> float | None` of `bench/metrics/<metric>.py`."""
    return load_module(os.path.join(BENCH, "metrics", metric + ".py")).read


def work_of(kernel: str):
    """`work(q, n, d, w, k) -> (flops, bytes)` of `bench/work/<kernel>.py`."""
    return load_module(os.path.join(BENCH, "work", kernel + ".py")).work


def peaks_of(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# program and device
# ---------------------------------------------------------------------------

def import_program() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_check(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (first device platform "
                         f"{info['platform']!r}); nothing runs elsewhere")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{info['count']}")
    return info


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces and backend compilations while `on` is set."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on:
            if name == self.TRACE:
                self.traces += 1
            elif name == self.COMPILE:
                self.compiles += 1


class GcPauses:
    """Pauses of the Python garbage collector while `on` is set: how many
    full (generation 2) collections ran, and the longest pause."""

    def __init__(self):
        self.on = False
        self.full = 0
        self.max_s = 0.0
        self._t0 = None
        gc.callbacks.append(self._event)

    def close(self) -> None:
        gc.callbacks.remove(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic()
        elif self.on and self._t0 is not None:
            self.max_s = max(self.max_s, time.monotonic() - self._t0)
            self.full += info["generation"] == 2


class HostProbe:
    """Host counters read at the end of every batch of a closed window,
    to tell what held up its slowest batch: the process's CPU seconds
    (`cpu_s`) and major page faults, the main thread's seconds waiting
    for a CPU (`runq_s`), the host's steal and I/O-wait seconds summed
    over its CPUs, and the kernel's pressure-stall totals (`psi_*`, the
    seconds in which some task waited for a CPU, memory or I/O). A
    counter that the kernel does not offer is left out."""

    HZ = os.sysconf("SC_CLK_TCK")

    def __init__(self):
        self.marks = []                 # (monotonic s, {counter: value})

    @staticmethod
    def _read(path: str) -> str | None:
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return None

    def counters(self) -> dict:
        out = {}
        stat = self._read("/proc/self/stat")
        if stat:
            f = stat.rsplit(")", 1)[1].split()
            out["cpu_s"] = (int(f[11]) + int(f[12])) / self.HZ
            out["majflt"] = int(f[9])
        sched = self._read("/proc/thread-self/schedstat")
        if sched:
            out["runq_s"] = int(sched.split()[1]) * 1e-9
        host = self._read("/proc/stat")
        if host:
            f = host.split("\n", 1)[0].split()
            out["iowait_s"] = int(f[5]) / self.HZ
            out["steal_s"] = int(f[8]) / self.HZ
        for res in ("cpu", "memory", "io"):
            psi = self._read(f"/proc/pressure/{res}")
            if psi:
                out[f"psi_{res}_s"] = int(psi.split("total=", 1)[1].split()[0]) * 1e-6
        return out

    def mark(self) -> None:
        self.marks.append((time.monotonic(), self.counters()))

    def slowest(self, period: int) -> dict | None:
        """The batch that ran longest past the median of the same pool
        batch (batches repeat every `period`), with each counter's rise
        over it and over the whole window."""
        t = np.array([m[0] for m in self.marks])
        if t.size < 2:
            return None
        dur = np.diff(t)
        slot = np.arange(dur.size) % period
        med = np.array([np.median(dur[slot == s]) for s in slot])
        j = int(np.argmax(dur - med))
        rise = lambda a, b: {k: b[k] - a[k] for k in b if k in a}  # noqa: E731
        return {"batch": j, "excess_s": float(dur[j] - med[j]),
                "batch_s": float(dur[j]),
                **rise(self.marks[j][1], self.marks[j + 1][1]),
                "window": rise(self.marks[0][1], self.marks[-1][1])}


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks)) if peaks else 0


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Deployment:
    cfg: dict
    corpus: gen.Corpus
    art: router.Artifact
    pool: gen.QueryPool
    fx: object
    svc: object
    tracer: object
    steps: dict
    seed: int


def setup(cfg: dict, mix: dict, seed: int, trace: bool) -> Deployment:
    """The deployment of a configuration. Its `shards` (absent: 1)
    row-partitions the corpus, one shard per chip of the cell."""
    import jax
    import jax.numpy as jnp

    from repro.ann import registry
    from repro.ann.dataset import ANNDataset
    from repro.ann.index import FilteredIndex
    from repro.ann.service import RouterService, ShardedRouterService
    from repro.ann.sharded import ShardedFilteredIndex
    from repro.ann.trace import Tracer

    shards = int(cfg.get("shards", 1))
    steps = {}
    t = time.monotonic()
    corpus = gen.make_corpus(cfg["corpus"], int(cfg["corpus_seed"]))
    steps["corpus_s"] = time.monotonic() - t
    t = time.monotonic()
    ds, order = ANNDataset.from_packed(cfg["name"], corpus.vectors,
                                       corpus.bitmaps, corpus.universe,
                                       return_order=True)
    if not np.array_equal(order, np.arange(ds.n)):
        raise BenchError("the program stores the rows in another order "
                         "than the benchmark's group order")
    if shards > 1:
        fx = ShardedFilteredIndex(ds, shards, devices=jax.devices()[:shards])
        parts = fx.shards
    else:
        fx = FilteredIndex(ds)
        parts = [fx]
    # each shard's own tensors: the sharded handle's `device` would upload
    # a second, whole copy of the corpus
    jax.block_until_ready([p.device.vectors for p in parts])
    steps["load_s"] = time.monotonic() - t
    t = time.monotonic()
    methods = {m: registry.get_method(m) for m in cfg["pool"]}
    for p in parts:
        for m in methods.values():
            for s in m.param_settings():
                p.get_index(m, s.build)
    steps["build_s"] = time.monotonic() - t
    t = time.monotonic()
    dev_bitmaps = jnp.asarray(corpus.bitmaps)
    art = router.make(cfg, corpus, dev_bitmaps,
                      {n: [s.ps_id for s in m.param_settings()]
                       for n, m in methods.items()})
    del dev_bitmaps
    tracer = (Tracer(sample=1.0, recent_capacity=1, flight_capacity=1)
              if trace else None)
    service = ShardedRouterService if shards > 1 else RouterService
    svc = service(fx, router.to_program(art, ds.name), t=art.t,
                  methods=methods, tracer=tracer)
    pool = gen.query_pool(corpus, int(mix["pool_per_pred"]),
                          int(mix["pool_seed"]))
    steps["router_pool_s"] = time.monotonic() - t
    return Deployment(cfg, corpus, art, pool, fx, svc, tracer, steps, seed)


def batches_of(dep: Deployment, size: int) -> list:
    """The pool's batches of one predicate in this run's order."""
    out = gen.batch_rows(dep.pool, size, dep.seed)
    if not out:
        raise BenchError(f"pool_per_pred is smaller than a batch of {size}")
    return out


def query_batch(dep: Deployment, idx: np.ndarray):
    from repro.ann.index import QueryBatch

    pred = int(dep.pool.preds[idx[0]])
    return QueryBatch(dep.pool.vectors[idx], dep.pool.bitmaps[idx], pred,
                      int(dep.cfg["k"]))


def warm_closed(dep: Deployment, mix: dict) -> None:
    """Every pool batch once: the window replays exactly these."""
    for idx in batches_of(dep, int(mix["batch"])):
        dep.svc.search(query_batch(dep, idx))


def warm_open(dep: Deployment, mix: dict) -> None:
    """Routing at every micro-batch size a predicate group can have;
    every (method, setting) of the pool on a few of each predicate's
    queries (a method pads its queries to a chunk of fixed size, so a few
    compile what many would); then every pool query through its routed
    method, in groups of the largest micro-batch."""
    from repro.ann import engine

    mb = int(mix["max_batch"])
    for p in gen.PREDS:
        rows = np.nonzero(dep.pool.preds == p)[0]
        for q in range(1, mb + 1):
            dep.svc.route(query_batch(dep, rows[:q]))
        few = query_batch(dep, rows[:WARM_FEW])
        for m in dep.svc.methods.values():
            for s in m.param_settings():
                dep.fx.run_method(m, engine.resolve_setting(m, s.ps_id), few)
        for s in range(0, rows.size, mb):
            b = query_batch(dep, rows[s:s + mb])
            dep.svc.execute(b, dep.svc.route(b))


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

class Profiler:
    """The JAX profiler over the whole window."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = os.path.join(OUT_DIR, "profile")
        self.running = False
        self._ann = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # device ops and the harness's annotations; no Python call tracer,
        # which would slow the host side of the traced window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.running = True

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self) -> dict | None:
        if not self.on:
            return None
        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise BenchError("the profiler wrote no trace")
        out = trace_reduce.reduce_file(paths[-1])
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


@dataclasses.dataclass
class Window:
    pool_idx: list
    ids: list
    dists: list
    decisions: list
    attempted: int
    failed: int
    elapsed_s: float
    latency_s: np.ndarray | None = None
    late_s: np.ndarray | None = None
    queue_stats: dict | None = None
    prefilter_calls: list = dataclasses.field(default_factory=list)
    slowest: dict | None = None


def window_closed(dep: Deployment, mix: dict, seconds: float,
                  prof: Profiler) -> Window:
    """Batches back to back, one in flight, until the first whole pass
    over the pool's batches that ends after `seconds`: every window serves
    each batch equally often, so the rate does not jump with where the
    last (up to seconds-long) batch happens to end."""
    import jax

    batches = [(idx, query_batch(dep, idx))
               for idx in batches_of(dep, int(mix["batch"]))]
    w = Window([], [], [], [], 0, 0, 0.0)
    probe = HostProbe()
    i = 0
    t0 = time.monotonic()
    prof.start()
    probe.mark()
    while True:
        idx, batch = batches[i % len(batches)]
        i += 1
        w.attempted += batch.q
        try:
            with jax.profiler.TraceAnnotation("bench.search"):
                res = dep.svc.search(batch)
        except Exception as e:          # a failed batch counts, the run goes on
            log(f"window: batch failed: {type(e).__name__}: {e}")
            w.failed += batch.q
            res = None
        now = time.monotonic()
        probe.mark()
        if res is not None:
            w.pool_idx.append(idx)
            w.ids.append(res.ids)
            w.dists.append(res.distances)
            w.decisions.extend(tuple(d) for d in res.decisions)
            if prof.running:
                w.prefilter_calls.append(
                    sum(d.method == "prefilter" for d in res.decisions))
        if now - t0 >= seconds and i % len(batches) == 0:
            break
    w.elapsed_s = time.monotonic() - t0
    prof.stop()
    w.slowest = probe.slowest(len(batches))
    return w


def window_open(dep: Deployment, mix: dict, seconds: float, seed: int,
                prof: Profiler) -> Window:
    import jax

    from repro.ann.service import AsyncBatchQueue

    due = gen.arrivals(mix, seconds, seed)
    order = gen.request_order(dep.pool, mix, due.size, seed)
    n = due.size
    done = np.full(n, np.nan)
    late = np.zeros(n)
    futs = [None] * n
    k = int(dep.cfg["k"])

    def finished(j):
        def cb(_f):
            done[j] = time.monotonic()
        return cb

    queue = AsyncBatchQueue(dep.svc, max_batch=int(mix["max_batch"]),
                            max_wait_ms=float(mix["max_wait_ms"]))
    try:
        t0 = time.monotonic()
        prof.start()
        for j in range(n):
            target = t0 + due[j]
            wait = target - time.monotonic()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(wait)
            i = order[j]
            late[j] = time.monotonic() - target
            with jax.profiler.TraceAnnotation("bench.submit"):
                futs[j] = queue.submit(dep.pool.vectors[i],
                                       dep.pool.bitmaps[i],
                                       int(dep.pool.preds[i]), k)
            futs[j].add_done_callback(finished(j))
        with jax.profiler.TraceAnnotation("bench.drain"):
            deadline = t0 + seconds + LATE_GRACE_S
            for f in futs:
                try:
                    f.result(timeout=max(0.0, deadline - time.monotonic()))
                except Exception:       # counted below as failed
                    pass
        elapsed = time.monotonic() - t0
        prof.stop()
        stats = queue.stats()
    finally:
        queue.close()
    w = Window([], [], [], [], n, 0, elapsed, queue_stats=stats, late_s=late)
    lat = np.full(n, np.inf)
    for j, f in enumerate(futs):
        if not f.done() or f.exception() is not None:
            w.failed += 1
            continue
        r = f.result()
        lat[j] = done[j] - (t0 + due[j])
        w.pool_idx.append(np.asarray([order[j]]))
        w.ids.append(np.asarray(r.ids)[None])
        w.dists.append(np.asarray(r.distances)[None])
        w.decisions.append(tuple(r.decision))
    w.latency_s = lat
    return w


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def per_layer(bench: dict, workload: str, ctx) -> dict:
    out = {}
    for m in metrics_for(bench, "per_layer", workload):
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, bench: dict | None = None) -> dict:
    """One run; returns the result object (the last line's content)."""
    import_program()
    bench = bench or catalog()
    cell, cfg, mix = resolve(bench, workload)
    info = device_check(int(cell["chips"]), require_tpu)
    enable_cache()
    counter = CompileCounter()
    dep = setup(cfg, mix, seed, trace)
    t = time.monotonic()
    closed = mix["kind"] == "closed"
    (warm_closed if closed else warm_open)(dep, mix)
    dep.steps["warm_s"] = time.monotonic() - t
    setup_s = time.monotonic() - T_PROCESS
    log(f"setup: {json.dumps({**dep.steps, 'setup_s': setup_s})}")

    prof = Profiler(trace)
    if dep.tracer is not None:
        dep.tracer.clear()              # spans of the window only
    # the set-up's objects (corpus, indexes, compiled programs) live for
    # the whole run: collect once and keep them out of later collections
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    counter.on = pauses.on = True
    w = (window_closed(dep, mix, seconds, prof) if closed
         else window_open(dep, mix, seconds, seed, prof))
    counter.on = pauses.on = False
    counter.close()
    pauses.close()
    peak = memory_peak()
    spans = dep.tracer.histograms() if dep.tracer is not None else None
    seen = {"traces": counter.traces, "compiles": counter.compiles,
            "attempted": w.attempted, "failed": w.failed,
            "elapsed_s": w.elapsed_s, "gc_full": pauses.full,
            "gc_max_ms": pauses.max_s * 1e3}
    if w.late_s is not None:
        seen["late_p99_ms"] = float(np.percentile(w.late_s, 99) * 1e3)
        seen["late_max_ms"] = float(np.max(w.late_s) * 1e3)
    if w.slowest is not None:
        seen["slowest"] = w.slowest
    print(f"window: {json.dumps(seen)}", flush=True)
    reduced = prof.reduce()

    # free the program's state before the reference takes the chip
    pool_methods = set(cfg["pool"])
    dep.fx.close()
    dep.svc = dep.fx = None
    gc.unfreeze()
    gc.collect()

    t = time.monotonic()
    ans = reference.distinct(
        np.concatenate(w.pool_idx) if w.pool_idx else np.zeros(0, np.int64),
        np.concatenate(w.ids) if w.ids else np.zeros((0, int(cfg["k"])), np.int32),
        np.concatenate(w.dists) if w.dists else np.zeros((0, int(cfg["k"])), np.float32),
        w.decisions)
    numbers = compare(dep, ans, pool_methods)
    correct, rows = reference.judge(numbers, cfg["limits"])
    correct = correct and w.failed == 0
    log(f"reference: {json.dumps({'seconds': time.monotonic() - t, **numbers})}")

    e2e = {"setup_s": setup_s, "recall_at_10": numbers["recall"]}
    if closed:
        e2e["qps"] = (w.attempted - w.failed) / w.elapsed_s
    else:
        e2e["p50_ms"] = float(np.percentile(w.latency_s, 50) * 1e3)
        e2e["p99_ms"] = float(np.percentile(w.latency_s, 99) * 1e3)
    device = {**info, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(w.attempted),
              "failed": int(w.failed)}
    if trace:
        ctx = types.SimpleNamespace(
            kind=mix["kind"], cfg=cfg, spans=spans, trace=reduced,
            queue_stats=w.queue_stats,
            late_ms=None if w.late_s is None else w.late_s * 1e3,
            prefilter_calls=w.prefilter_calls,
            dims={"n": dep.corpus.n, "d": dep.corpus.dim,
                  "w": int(dep.corpus.bitmaps.shape[1]), "k": int(cfg["k"])},
            peaks=peaks_of(info["kind"]) if require_tpu else None,
            work=work_of)
        result["metrics"] = per_layer(bench, workload, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        names = [m["name"] for m in metrics_for(bench, "end_to_end", workload)]
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {n: {"value": float(e2e[n]), "unit": units[n]}
                             for n in names}
        result["device"] = device
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in rows}
    result["check"]["failed"] = {"value": w.failed, "limit": 0}
    return result


def reference_corpus(dep: Deployment):
    """The reference's copy of the corpus: one block of rows on each chip
    that holds a shard."""
    import jax

    return reference.to_device(
        dep.corpus, jax.devices()[:int(dep.cfg.get("shards", 1))])


def compare(dep: Deployment, ans, pool_methods, *, mode: str = "highest",
            answers_from_reference: bool = False, dev=None) -> dict:
    """The plain reference over the answered pool queries, then the
    comparison. With `answers_from_reference` the reference computed in
    `mode` is put in the program's place (the control). `dev`: the
    reference's device copy of the corpus, when the caller holds one."""
    cfg, pool, corpus = dep.cfg, dep.pool, dep.corpus
    k = int(cfg["k"])
    dev = dev or reference_corpus(dep)
    u = np.unique(ans.pool_idx)
    ref_ids = np.full((pool.preds.size, k), -1, np.int32)
    ids_u, _ = reference.topk(dev, pool.vectors[u], pool.bitmaps[u],
                              pool.preds[u], k)
    ref_ids[u] = ids_u
    sel = sum(reference.match_counts(b.bitmaps, pool.bitmaps[u],
                                     pool.preds[u])
              for b in dev) / corpus.n
    dec_u = router.decide(dep.art, sel, pool.preds[u],
                          margin=float(cfg["route_margin"]))
    ref_dec = [None] * pool.preds.size
    for j, i in enumerate(u):
        ref_dec[i] = dec_u[j]
    if answers_from_reference:
        ans = control_answers(dep, ans, dev, u, sel, mode)
    del dev
    return reference.check(ans, corpus, pool, ref_ids, ref_dec, pool_methods,
                           exact=bool(cfg["exact"]))


def control_answers(dep: Deployment, ans, dev, u, sel, mode: str):
    """The reference one precision step lower (`mode`), in the program's
    place: an exact configuration's top-k recomputed in `mode`; an
    approximate one keeps the served ids, re-scored in `mode`, and takes
    its decisions from the router's MLPs run in `mode`."""
    import jax
    import jax.numpy as jnp

    pool, k = dep.pool, int(dep.cfg["k"])
    if dep.cfg["exact"]:
        ids, sc = reference.topk(dev, pool.vectors[u], pool.bitmaps[u],
                                 pool.preds[u], k, mode=mode)
        return reference.Answers(
            u, ids, reference.served_distances(sc, ids, pool.vectors[u]),
            [ans.decisions[0]] * u.size, np.ones(u.size))
    qv = pool.vectors[ans.pool_idx]
    safe = np.clip(ans.ids, 0, dep.corpus.n - 1)
    dt = jnp.einsum("rkd,rd->rk",
                    reference.lowered(jnp.asarray(dep.corpus.vectors[safe]), mode),
                    reference.lowered(jnp.asarray(qv), mode),
                    precision=jax.lax.Precision.HIGHEST)
    sc = np.asarray(jnp.asarray(dep.corpus.norms_sq)[safe] - 2.0 * dt)
    dists = reference.served_distances(sc, ans.ids, qv)
    pos = {int(i): j for j, i in enumerate(u)}
    dec_u = router.decide(dep.art, sel, pool.preds[u], mode=mode)
    dec = [dec_u[pos[int(i)]] for i in ans.pool_idx]
    return reference.Answers(ans.pool_idx, ans.ids, dists, dec, ans.weight)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        log(f"bench: FAILED: {e}")
        return 2
    for name, row in result["check"].items():
        log(f"check {name}: {row['value']!r} (limit {row['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
