"""From a JAX profiler trace to the device numbers of a traced run.

The trace is first flattened to plain data (`flatten`): planes, their
lines, and events as (name, start_ns, duration_ns). Then `reduce`:

* the window is the host annotation `bench.window`, which the harness
  holds open while the profiler runs;
* device ops are the events of each TPU plane's `XLA Ops` line, clipped
  to the window; busy time is the union of their intervals, averaged
  over the devices that ran anything (`busy_s`);
* `ops` sums device seconds per op name (the HLO instruction name
  without its numeric suffix); `device_ops` is its top ten;
* `idle_gaps` are the ten longest stretches of the window with no op on
  the device, each named by the innermost `bench.*` host annotation
  open at its midpoint (what the benchmark was calling when the device
  went idle).
"""

from __future__ import annotations

import re

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
WINDOW = "bench.window"
TOP = 10
_OP = re.compile(r"%?([^\s=]+?)(?:\.\d+)?(?:\s|=|$)")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event without its numeric
    suffix: `%masked_topk_accum.1 = (...) custom-call(...)` ->
    `masked_topk_accum`."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def flatten(pd) -> list[dict]:
    """`jax.profiler.ProfileData` -> plain planes/lines/events."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)]
                                     for ev in line.events]})
        out.append({"name": plane.name, "lines": lines})
    return out


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(planes: list[dict]) -> dict:
    host = [ev for p in planes if not p["name"].startswith(DEVICE_PREFIX)
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith("bench.")]
    win = [ev for ev in host if ev[0] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    anns = [(ev[1], ev[1] + ev[2], ev[0]) for ev in host if ev[0] != WINDOW]
    ops: dict[str, float] = {}
    busy, unions = [], []
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        iv = []
        for ln in p["lines"]:
            if ln["name"] != OP_LINE:
                continue
            for name, s, d in ln["events"]:
                a, b = max(s, w0), min(s + d, w1)
                if b > a:
                    iv.append((a, b))
                    key = op_name(name)
                    ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        if iv:
            u = _union(iv)
            unions.append(u)
            busy.append(sum(b - a for a, b in u) * 1e-9)
    gaps = []
    for u in unions[:1]:
        edges = [(w0, w0)] + u + [(w1, w1)]
        for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
            if b > a:
                mid = 0.5 * (a + b)
                open_ = [x for x in anns if x[0] <= mid < x[1]]
                label = (max(open_, key=lambda x: x[0])[2] if open_
                         else "bench.none")
                gaps.append([label, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (w1 - w0) * 1e-9,
            "devices": len(busy),
            "ops": ops,
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": gaps[:TOP]}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce(flatten(ProfileData.from_file(path)))


def kernel_seconds(reduced: dict, kernel: str) -> float:
    """Device seconds of the ops named after a Pallas kernel."""
    return reduced["ops"].get(kernel, 0.0)
