"""Find cells, configurations, per-layer metrics and reducers by name.

``BENCHMARK.json`` at the root of the checkout is the list of what exists;
what belongs to one cell, configuration or metric sits in a file of its own:

    benchmark/workloads/<cell>.json          one cell
    <configs[].file> + config.json beside it  one configuration
    benchmark/layer_metrics/<metric>.json     one per-layer metric
    benchmark/reducers/<kind>.py              one kind of reader

and what belongs to one family of models (its plain reference, its count of
operations and bytes) is a file that the configuration's ``config.json`` names
by its path (``reference``, ``flops``), loaded from there.

A later PR adds files and manifest entries and edits nothing that is there.
JAX-free: the parent process imports this.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(ROOT, "outputs", "benchmark")  # run dirs, results: git ignores outputs/


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or inconsistent."""


class BenchFailure(Exception):
    """The run cannot produce a result (as opposed to an incorrect one)."""


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{path} does not exist") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path} is not JSON: {exc}") from None


_MODULES: dict = {}


def load_module(path: str):
    """A file of the benchmark's Python, loaded by where it lies and not by
    an import name: a later PR's reducer, reference or count is found without
    any table of names."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        if not os.path.exists(path):
            raise ManifestError(f"{path} does not exist")
        name = "_bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def family_module(config: dict, key: str):
    """The module a configuration's ``config.json`` names under ``key``
    (``reference`` or ``flops``): a path under the checkout's root."""
    path = config["meta"].get(key)
    if not path:
        raise ManifestError(f"configuration {config['name']!r} names no {key!r} in its config.json")
    return load_module(os.path.join(config["root"], path))


class Manifest:
    """``BENCHMARK.json`` plus the files it names, resolved against ``root``
    (the checkout) and ``bench_dir`` (this directory; tests point both at a
    temporary copy to show that new files need no edit of code)."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None) -> None:
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self.data = _load(os.path.join(root, "BENCHMARK.json"))

    # -- cells ---------------------------------------------------------------

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.data["workloads"]]

    def cell(self, name: str) -> dict:
        """The manifest entry of a cell merged over its own file; the two
        must agree on what both state."""
        entry = next((w for w in self.data["workloads"] if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (known: {self.cell_names()})"
            )
        cell = _load(os.path.join(self.bench_dir, "workloads", f"{name}.json"))
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != entry[key]:
                raise ManifestError(
                    f"workloads/{name}.json says {key}={cell.get(key)!r}, "
                    f"BENCHMARK.json says {entry[key]!r}"
                )
        return {**cell, "name": name}

    # -- configurations ------------------------------------------------------

    def config(self, name: str) -> dict:
        """``{"model_path": <absolute path of the file as it is run>,
        "model": <its contents>, "meta": <config.json beside it>, "entry":
        <the manifest's entry>, "root": <the checkout>}``."""
        entry = next((c for c in self.data["configs"] if c["name"] == name), None)
        if entry is None:
            raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")
        model_path = os.path.join(self.root, entry["file"])
        meta_path = os.path.join(os.path.dirname(model_path), "config.json")
        meta = _load(meta_path) if os.path.exists(meta_path) else {}
        for key in ("source", "reduced"):  # stated twice: the two must agree
            if key in meta and meta[key] != entry[key]:
                raise ManifestError(
                    f"{meta_path} says {key}={meta[key]!r}, BENCHMARK.json says {entry[key]!r}"
                )
        return {
            "name": name,
            "model_path": model_path,
            "model": _load(model_path),
            "meta": meta,
            "entry": entry,
            "root": self.root,
        }

    # -- metrics -------------------------------------------------------------

    def end_to_end(self, cell_name: str) -> list[dict]:
        return [m for m in self.data["end_to_end"] if _in_cell(m, cell_name)]

    def layer_metrics(self, cell_name: str) -> list[dict]:
        """The per-layer metrics of a cell: manifest entry merged over the
        metric's own file (reducer kind and arguments)."""
        out = []
        for entry in self.data["per_layer"]:
            if not _in_cell(entry, cell_name):
                continue
            spec = _load(
                os.path.join(self.bench_dir, "layer_metrics", f"{entry['name']}.json")
            )
            for key in ("unit", "moves", "layer"):
                if spec.get(key) != entry[key]:
                    raise ManifestError(
                        f"layer_metrics/{entry['name']}.json says {key}="
                        f"{spec.get(key)!r}, BENCHMARK.json says {entry[key]!r}"
                    )
            out.append({**spec, **entry})
        return out

    def reducer(self, kind: str):
        """The ``reduce(ctx, args)`` function of ``reducers/<kind>.py``."""
        path = os.path.join(self.bench_dir, "reducers", f"{kind}.py")
        if not os.path.exists(path):
            raise ManifestError(f"no reducer kind {kind!r}: {path} does not exist")
        return load_module(path).reduce


def _in_cell(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """Published peaks of one chip of this exact ``device_kind``; a kind the
    table does not hold is an error, not a default."""
    table = _load(os.path.join(bench_dir, "harness", "peaks.json"))
    peaks = table.get(device_kind)
    if not isinstance(peaks, dict):
        known = sorted(k for k, v in table.items() if isinstance(v, dict))
        raise ManifestError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.json with its source (known: {known})"
        )
    return peaks
