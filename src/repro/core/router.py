"""ML Router — paper §4 / Algorithm 2, batched end to end.

Batch pipeline (no per-query Python loop anywhere on the hot path):
features for the whole query batch come from one vectorised
`features.feature_matrix` pass (selectivity/co-occurrence via a single
group-table reduction, or the Pallas selectivity kernel on TPU); the M
per-method MLP-Reg models are stacked into a single [M, ...] parameter
pytree and evaluated with one jitted vmapped forward; Algorithm 2
(threshold filter `r̂_m ≥ T` → max-QPS passing method from the offline
benchmark table B → argmax-r̂ fallback) runs as numpy array ops over
precomputed per-method `(ps_id, qps)` tables from
`BenchmarkTable.routing_arrays`.

TPU-idiomatic addition (DESIGN.md §3): batched group dispatch — route a
*batch* of queries with one fused forward, then execute each chosen
(method, ps) group as a single batched search. That dispatch lives in
`repro.ann.service.RouterService`. Persistence is a versioned artifact
directory (`router.json` manifest + `weights.npz` + `table.json`); the
pre-artifact pickle format is no longer loadable — re-save old routers
with `MLRouter.save(dir)` from a checkout that still reads them.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from repro.ann import trace
from repro.ann.dataset import ANNDataset
from repro.ann.predicates import Predicate
from repro.core import features as F
from repro.core import mlp
from repro.core.table import BenchmarkTable

# versioned router artifact directory (router.json manifest + npz weights
# + benchmark table); MLRouter.load also reads the legacy pickle format.
ARTIFACT_FORMAT = "repro.router"
ARTIFACT_VERSION = 1
_MANIFEST = "router.json"
_WEIGHTS = "weights.npz"
_TABLE = "table.json"


def artifact_versions(path: str) -> dict:
    """Version + content stamps of a router artifact directory, without
    loading it: ``{"router_version": int, "table_version": int,
    "content_sha1": str}``.

    The format versions catch an artifact written by a different code
    era; the content digest (sha1 over the manifest, weights and table
    bytes) catches an artifact that was re-trained or swapped in place
    — same format, different router. `repro.ann.store.IndexStore`
    records all three at link time and re-validates the triple on every
    `open()`, so an index can never silently serve through a router or
    benchmark table that changed under it. Raises ValueError if `path`
    is not a router artifact directory.
    """
    import hashlib

    from repro.ann.dataset import sha1_file
    from repro.core.table import table_file_version

    manifest_path = os.path.join(path, _MANIFEST)
    if not os.path.isdir(path) or not os.path.exists(manifest_path):
        raise ValueError(
            f"{path!r} is not a router artifact directory (no {_MANIFEST})")
    with open(manifest_path) as f:
        manifest = json.load(f)
    if manifest.get("format") != ARTIFACT_FORMAT:
        raise ValueError(
            f"{path!r} is not a {ARTIFACT_FORMAT} artifact "
            f"(format={manifest.get('format')!r})")
    table_path = os.path.join(path, manifest.get("table", _TABLE))
    if not os.path.exists(table_path):
        raise ValueError(
            f"router artifact {path!r} is missing its benchmark table "
            f"file {os.path.basename(table_path)!r}")
    # combined digest of per-file chunked hashes (constant memory)
    h = hashlib.sha1()
    for fname in (_MANIFEST, manifest.get("weights", _WEIGHTS),
                  manifest.get("table", _TABLE)):
        fpath = os.path.join(path, fname)
        if os.path.exists(fpath):
            h.update(sha1_file(fpath).encode())
    return {
        "router_version": int(manifest.get("version", -1)),
        "table_version": table_file_version(table_path),
        "content_sha1": h.hexdigest(),
    }


@dataclasses.dataclass
class MLRouter:
    feature_names: list            # e.g. F.MINIMAL_FEATURES
    methods: list                  # candidate method names, fixed order
    models: dict                   # method -> MLP params (numpy)
    scaler: mlp.Scaler
    table: BenchmarkTable
    _stacked: object = dataclasses.field(default=None, init=False,
                                         repr=False, compare=False)

    # ---- prediction -----------------------------------------------------
    def predict_recalls(self, ds: ANNDataset, qbms: np.ndarray,
                        pred: Predicate, *, fx=None) -> np.ndarray:
        """[Q, M] predicted recall@10 per candidate method (one vectorised
        feature pass + one stacked-MLP forward for the whole batch).
        `fx`: the caller's owned `FilteredIndex`, so the TPU feature
        kernel reuses its device tensors instead of the default pool."""
        with trace.span("route.features"):
            x = F.feature_matrix(ds, qbms, pred, self.feature_names, fx=fx)
        with trace.span("route.mlp"):
            return self.predict_recalls_from_features(x)

    def retrained(self, models: dict, scaler: "mlp.Scaler",
                  table: BenchmarkTable | None = None) -> "MLRouter":
        """Fresh router with new weights but this router's feature set
        and method order (the online adapter's retrain constructor —
        a new instance so the serving swap is one reference assignment
        and the stacked-params cache starts cold)."""
        return MLRouter(feature_names=list(self.feature_names),
                        methods=list(self.methods), models=models,
                        scaler=scaler,
                        table=self.table if table is None else table)

    def stacked_params(self):
        """All M per-method models as one [M, ...]-leaved pytree (cached)."""
        if self._stacked is None:
            self._stacked = mlp.stack_params(
                [mlp.params_from_numpy(self.models[m]) for m in self.methods])
        return self._stacked

    def predict_recalls_from_features(self, x_raw: np.ndarray) -> np.ndarray:
        xs = self.scaler.transform(x_raw)
        with trace.launch(xs.shape[0]):
            out = mlp.forward_stacked(self.stacked_params(), xs)   # [M, Q, 1]
            out = np.asarray(out[:, :, 0])
        return out.T.astype(np.float32)                        # [Q, M]

    # ---- Algorithm 2 ------------------------------------------------------
    def route_from_predictions(self, r_hat: np.ndarray, ds_name: str,
                               pred: Predicate, t: float):
        """Vectorised Algorithm 2. Returns list of (method, ps_id) per query.

        Selection is pure array ops over the per-method routing tables:
        argmax of QPS masked to passing methods, argmax-r̂ fallback rows
        where nothing passes.
        """
        pt = int(Predicate(pred))
        has_pass, qps, ps_pass, ps_fallback = self.table.routing_arrays(
            ds_name, pt, self.methods, t)
        r = np.asarray(r_hat, dtype=np.float64)
        passing = (r >= t) & has_pass[None, :]                 # [Q, M]
        any_pass = passing.any(axis=1)
        # argmax picks the first maximal index, matching the scalar loop's
        # max()-in-method-order tie-breaking
        j_pass = np.argmax(np.where(passing, qps[None, :], -np.inf), axis=1)
        j_fb = np.argmax(r, axis=1)
        j_star = np.where(any_pass, j_pass, j_fb)
        ps_sel = np.where(any_pass, ps_pass[j_star], ps_fallback[j_star])
        names = np.array(self.methods, dtype=object)[j_star]
        return list(zip(names.tolist(), ps_sel.tolist()))

    def route_from_predictions_loop(self, r_hat: np.ndarray, ds_name: str,
                                    pred: Predicate, t: float):
        """Scalar per-query Algorithm 2 (the seed implementation) — the
        parity oracle for `route_from_predictions`, shared by the tests
        and the routing-latency benchmark. Not a hot path."""
        pt = int(Predicate(pred))
        ps_of, qps_of = {}, {}
        for m in self.methods:
            hit = self.table.best_qps_setting(ds_name, pt, m, t)
            if hit is not None:
                ps_of[m], qps_of[m] = hit[0], hit[1]["qps"]
        decisions = []
        for qi in range(r_hat.shape[0]):
            passing = [m for j, m in enumerate(self.methods)
                       if r_hat[qi, j] >= t and m in ps_of]
            if passing:
                m_star = max(passing, key=lambda m: qps_of[m])
                decisions.append((m_star, ps_of[m_star]))
            else:  # fallback: argmax predicted recall, max-recall setting
                m_star = self.methods[int(np.argmax(r_hat[qi]))]
                hit = self.table.best_qps_setting(ds_name, pt, m_star, t) \
                    or self.table.max_recall_setting(ds_name, pt, m_star)
                decisions.append((m_star, hit[0] if hit else None))
        return decisions

    def route(self, ds: ANNDataset, qbms: np.ndarray, pred: Predicate,
              t: float):
        r_hat = self.predict_recalls(ds, qbms, pred)
        return self.route_from_predictions(r_hat, ds.name, pred, t)

    # ---- persistence ----
    def save(self, path: str) -> None:
        """Write the versioned artifact directory at `path`:

            path/router.json   — manifest (format, version, features,
                                 method order, layer counts)
            path/weights.npz   — per-method MLP layers + scaler
            path/table.json    — offline benchmark table B
        """
        if os.path.isfile(path):
            raise ValueError(
                f"router artifact path {path!r} is an existing file; the "
                f"versioned artifact is a directory")
        os.makedirs(path, exist_ok=True)
        arrays = {"scaler/mean": np.asarray(self.scaler.mean),
                  "scaler/std": np.asarray(self.scaler.std)}
        n_layers = {}
        for m in self.methods:
            layers = self.models[m]
            n_layers[m] = len(layers)
            for i, layer in enumerate(layers):
                arrays[f"model/{m}/{i}/w"] = np.asarray(layer["w"])
                arrays[f"model/{m}/{i}/b"] = np.asarray(layer["b"])
        np.savez(os.path.join(path, _WEIGHTS), **arrays)
        self.table.save(os.path.join(path, _TABLE))
        manifest = {
            "format": ARTIFACT_FORMAT,
            "version": ARTIFACT_VERSION,
            "feature_names": list(self.feature_names),
            "methods": list(self.methods),
            "n_layers": n_layers,
            "weights": _WEIGHTS,
            "table": _TABLE,
        }
        with open(os.path.join(path, _MANIFEST), "w") as f:
            json.dump(manifest, f, indent=1)

    @staticmethod
    def load(path: str) -> "MLRouter":
        """Load a versioned router artifact directory.

        Raises ValueError for anything that is not an artifact directory
        — including the pre-artifact pickle files, whose loader was
        removed after its one-PR-cycle deprecation window."""
        if not os.path.isdir(path):
            raise ValueError(
                f"{path!r} is not a router artifact directory; the legacy "
                f"pickle format is no longer supported — re-save it with "
                f"MLRouter.save(dir)")
        return MLRouter._load_artifact(path)

    @staticmethod
    def _load_artifact(path: str) -> "MLRouter":
        with open(os.path.join(path, _MANIFEST)) as f:
            manifest = json.load(f)
        if manifest.get("format") != ARTIFACT_FORMAT:
            raise ValueError(
                f"{path!r} is not a {ARTIFACT_FORMAT} artifact "
                f"(format={manifest.get('format')!r})")
        if int(manifest.get("version", -1)) > ARTIFACT_VERSION:
            raise ValueError(
                f"router artifact version {manifest['version']} is newer "
                f"than supported version {ARTIFACT_VERSION}")
        with np.load(os.path.join(path, manifest["weights"])) as z:
            scaler = mlp.Scaler(z["scaler/mean"].copy(),
                                z["scaler/std"].copy())
            models = {}
            for m in manifest["methods"]:
                models[m] = [
                    {"w": z[f"model/{m}/{i}/w"].copy(),
                     "b": z[f"model/{m}/{i}/b"].copy()}
                    for i in range(int(manifest["n_layers"][m]))]
        table = BenchmarkTable.load(os.path.join(path, manifest["table"]))
        return MLRouter(feature_names=list(manifest["feature_names"]),
                        methods=list(manifest["methods"]),
                        models=models, scaler=scaler, table=table)
