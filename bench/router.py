"""The deployed router artifact, made from the configuration's
`router_seed`, and its plain reference.

The artifact stands in for the paper's trained router and measured
table, which the repository does not hold (listed under `assumed`):

* one MLP (5 → 16 → 8 → 1, ReLU) per pool method, He-normal weights and
  zero biases drawn in numpy from `router_seed`; the weights that read
  the `lid_mean` column are zero, since one deployment serves one
  corpus and that column is a constant there;
* a benchmark table B over each pool method's real parameter settings,
  recall uniform in [0.7, 1.0] and QPS uniform in [100, 2000];
* a scaler fitted on the features of a query pool drawn from
  `router_seed`: the selectivity column and the predicate one-hot from
  the benchmark's own exact match counts; the `lid_mean` column left
  as it is (mean 0, scale 1).

`decide` is Algorithm 2 in plain numpy over the same artifact, the
MLPs in float64 (or, for the control, with bfloat16 products).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import gen, reference

SIZES = (5, 16, 8, 1)
LID_COLUMN = 1                     # [selectivity, lid_mean, pred one-hot x3]


@dataclasses.dataclass
class Artifact:
    methods: list                  # pool method names, in router order
    layers: dict                   # method -> [(w, b), ...] float32
    mean: np.ndarray               # [5] scaler
    std: np.ndarray
    table: dict                    # (pred, method) -> [(ps_id, recall, qps)]
    t: float


def features(sel: np.ndarray, preds: np.ndarray) -> np.ndarray:
    """[P, 5] raw features in the program's MINIMAL_FEATURES layout, the
    `lid_mean` column zero here (its weights are zero)."""
    x = np.zeros((sel.shape[0], 5), dtype=np.float64)
    x[:, 0] = sel
    x[np.arange(sel.shape[0]), 2 + preds] = 1.0
    return x.astype(np.float32)


def make(cfg: dict, corpus, dev_bitmaps, settings: dict) -> Artifact:
    """`settings`: method -> [ps_id, ...] of the program's methods;
    `dev_bitmaps`: the corpus's [N, W] bitmaps on the device."""
    seed = int(cfg["router_seed"])
    rng = gen.rng_of(seed, 30)
    methods = list(cfg["pool"])
    table = {}
    for pred in gen.PREDS:
        for m in methods:
            table[(pred, m)] = [(ps, float(rng.uniform(0.7, 1.0)),
                                 float(rng.uniform(100, 2000)))
                                for ps in settings[m]]
    layers = {}
    for m in methods:
        ls = []
        for din, dout in zip(SIZES[:-1], SIZES[1:]):
            w = (rng.standard_normal((din, dout)) * np.sqrt(2.0 / din))
            ls.append((w.astype(np.float32), np.zeros(dout, np.float32)))
        ls[0][0][LID_COLUMN, :] = 0.0
        layers[m] = ls
    pool = gen.query_pool(corpus, int(cfg["router_pool_per_pred"]), seed)
    sel = reference.match_counts(dev_bitmaps, pool.bitmaps, pool.preds) / corpus.n
    x = features(sel, pool.preds).astype(np.float64)
    mean, std = x.mean(0), x.std(0) + 1e-8
    mean[LID_COLUMN], std[LID_COLUMN] = 0.0, 1.0
    return Artifact(methods, layers, mean, std, table, float(cfg["t"]))


def to_program(art: Artifact, ds_name: str):
    """The program's `MLRouter` holding this artifact."""
    from repro.core import features as F
    from repro.core.mlp import Scaler
    from repro.core.router import MLRouter
    from repro.core.table import BenchmarkTable

    table = BenchmarkTable.new()
    for (pred, m), rows in art.table.items():
        for ps, rec, qps in rows:
            table.add(ds_name, pred, m, ps, recall=rec, qps=qps)
    models = {m: [{"w": w, "b": b} for w, b in ls]
              for m, ls in art.layers.items()}
    return MLRouter(feature_names=list(F.MINIMAL_FEATURES),
                    methods=list(art.methods), models=models,
                    scaler=Scaler(art.mean.copy(), art.std.copy()),
                    table=table)


def _lowered(x: np.ndarray, mode: str) -> np.ndarray:
    """`x` as a product in `mode` reads it: `bf16` rounds to bfloat16
    (to nearest even), any other mode leaves it."""
    if mode == "bf16":
        b = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
        b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
        return b.view(np.float32).astype(np.float64)
    return x


def forward(art: Artifact, xs: np.ndarray, mode: str = "float64") -> np.ndarray:
    """[P, M] predicted recall per pool method."""
    out = []
    for m in art.methods:
        h = xs.astype(np.float64)
        for j, (w, b) in enumerate(art.layers[m]):
            w = w.astype(np.float64)
            h, w = _lowered(h, mode), _lowered(w, mode)
            h = h @ w + b
            if j < len(art.layers[m]) - 1:
                h = np.maximum(h, 0.0)
        out.append(h[:, 0])
    return np.stack(out, axis=1)


def decide(art: Artifact, sel: np.ndarray, preds: np.ndarray,
           mode: str = "float64", margin: float = 0.0) -> list:
    """Algorithm 2: per query (method, ps_id), or None where the decision
    is not firm: a predicted recall of a method that has a passing setting
    lies within `margin` of the threshold, or, with no method passing,
    the two highest predictions lie within `margin` of each other. There
    the rounding of the MLP decides, and either answer is right."""
    x = features(sel, preds)
    xs = ((x - art.mean) / art.std).astype(np.float32)
    r = forward(art, xs, mode)
    out = []
    t = art.t
    for i in range(r.shape[0]):
        pred = int(preds[i])
        best = []
        for m in art.methods:
            rows = art.table[(pred, m)]
            ok = [row for row in rows if row[1] >= t]
            hit = max(ok, key=lambda row: row[2]) if ok else None
            fb = hit or max(rows, key=lambda row: (row[1], row[2]))
            best.append((hit, fb))
        passing = [j for j in range(len(art.methods))
                   if r[i, j] >= t and best[j][0] is not None]
        near = any(abs(r[i, j] - t) < margin for j in range(len(art.methods))
                   if best[j][0] is not None)
        if passing:
            j = max(passing, key=lambda j: (best[j][0][2], -j))
            dec = (art.methods[j], best[j][0][0])
        else:
            j = int(np.argmax(r[i]))
            top2 = np.sort(r[i])[-2:]
            near = near or (top2.size == 2 and top2[1] - top2[0] < margin)
            dec = (art.methods[j], best[j][1][0])
        out.append(None if near else dec)
    return out
