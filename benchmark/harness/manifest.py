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

What a cell reports follows from what its configuration runs: a metric that
only some cells can report says which feature it ``needs``, a configuration
says which features it ``runs``, and ``Manifest.breaches`` holds
``BENCHMARK.json``'s ``workloads`` lists, the owners of the device scopes and
the rooflines' readers to that (rules R1 to R3 there).

A later PR adds files and manifest entries and edits nothing that is there.
JAX-free: the parent process imports this.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Iterable, NamedTuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(ROOT, "outputs", "benchmark")  # run dirs, results: git ignores outputs/


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or inconsistent."""


class BenchFailure(Exception):
    """The run cannot produce a result (as opposed to an incorrect one)."""


class Breach(NamedTuple):
    """One breach of ``Manifest.breaches``' rules: the rule, the metric and
    the cell it is about (``None`` where it is about no one metric or cell),
    and the sentence that says what to change."""

    rule: str
    metric: str | None
    cell: str | None
    sentence: str

    def __str__(self) -> str:
        return f"{self.rule}: {self.sentence}"


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

    def cell_entry(self, name: str) -> dict:
        entry = next((w for w in self.data["workloads"] if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (known: {self.cell_names()})"
            )
        return entry

    def cell(self, name: str) -> dict:
        """The manifest entry of a cell merged over its own file; the two
        must agree on what both state."""
        entry = self.cell_entry(name)
        cell = _load(os.path.join(self.bench_dir, "workloads", f"{name}.json"))
        for key in ("config", "traffic", "chips", "why"):
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

    def metric_spec(self, entry: dict) -> dict:
        """A per-layer metric's manifest entry merged over the metric's own
        file (reducer kind and arguments, the feature it ``needs``); the two
        must agree on what both state."""
        spec = _load(os.path.join(self.bench_dir, "layer_metrics", f"{entry['name']}.json"))
        for key in ("unit", "moves", "layer"):
            if spec.get(key) != entry[key]:
                raise ManifestError(
                    f"layer_metrics/{entry['name']}.json says {key}="
                    f"{spec.get(key)!r}, BENCHMARK.json says {entry[key]!r}"
                )
        return {**spec, **entry}

    def layer_metrics(self, cell_name: str) -> list[dict]:
        """The per-layer metrics of a cell, each as ``metric_spec`` gives it."""
        return [self.metric_spec(e) for e in self.data["per_layer"] if _in_cell(e, cell_name)]

    # -- what a cell reports follows from what its configuration runs ---------

    def features(self, cell_name: str) -> set[str]:
        """What a cell runs: the ``runs`` of its configuration's
        ``config.json``, and ``collectives`` on more than one chip."""
        entry = self.cell_entry(cell_name)
        runs = self.config(entry["config"])["meta"].get("runs", [])
        return set(runs) | ({"collectives"} if entry["chips"] > 1 else set())

    def breaches(self, declared_scopes: Iterable[str]) -> list[Breach]:
        """Every breach of the rule, each as a sentence that names the metric,
        the cell and the list to change. A feature is a word that some
        metric's file gives under ``needs``.

        R1, lists follow features: a metric with a ``needs`` lists under
        ``workloads`` in ``BENCHMARK.json`` exactly the cells that run the
        feature and report the end-to-end metric it ``moves``; a metric
        without one has no list and is owed wherever what it moves is reported.

        R2, every device scope has one owner: over the metrics that
        ``scope_op_time`` reads no scope is named twice, together they name
        ``declared_scopes`` (the program's own list; the caller hands it over
        because this module imports nothing of the program) and ``""``, the
        ops in no scope, and all leave the same ops out (``except_ops``). In
        each cell the scopes its metrics own are those, less the ones whose
        owner needs a feature that the cell does not run.

        R3, a roofline sits beside its time: ``<x>_roofline`` has the ``needs``
        and the ``moves`` of ``<x>_ms``, and reads the same scopes or kernels.
        """
        cells = self.cell_names()
        view = _View(
            entries=self.data["per_layer"],
            specs={e["name"]: self.metric_spec(e) for e in self.data["per_layer"]},
            cells=cells,
            runs={c: self.features(c) for c in cells},
            reported={c: {m["name"] for m in self.end_to_end(c)} for c in cells},
            config_of={c: self.cell_entry(c)["config"] for c in cells},
        )
        return [*_lists_follow_features(view), *_scopes_have_one_owner(view, declared_scopes),
                *_rooflines_sit_beside_their_times(view)]

    def reducer(self, kind: str):
        """The ``reduce(ctx, args)`` function of ``reducers/<kind>.py``."""
        path = os.path.join(self.bench_dir, "reducers", f"{kind}.py")
        if not os.path.exists(path):
            raise ManifestError(f"no reducer kind {kind!r}: {path} does not exist")
        return load_module(path).reduce


def _in_cell(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def _reads(spec: dict):
    """What a metric's reader selects the device's ops by, in a form that a
    roofline and its time share: scopes with the ops left out of them, or one
    regex of kernel names."""
    args = spec.get("args", {})
    if "scopes" in args:
        return ("scopes", tuple(args["scopes"]), args.get("except_ops"))
    if "regex" in args:
        return ("kernels", args["regex"])
    if "kernels" in args:
        return ("kernels", "|".join(args["kernels"]))
    return None


class _View(NamedTuple):
    """What the three rules read of a manifest."""

    entries: list  # the per-layer entries of BENCHMARK.json, in its order
    specs: dict  # metric -> its entry merged over its own file
    cells: list
    runs: dict  # cell -> the features it runs
    reported: dict  # cell -> the end-to-end metrics it reports
    config_of: dict  # cell -> its configuration's name


def _lists_follow_features(v: _View):
    for entry in v.entries:
        name, spec = entry["name"], v.specs[entry["name"]]
        need, moves, listed = spec.get("needs"), spec["moves"], entry.get("workloads")
        its_list = "that metric's `workloads` in BENCHMARK.json"
        if need is None:
            if listed is not None:
                yield Breach(
                    "R1", name, None,
                    f"`{name}` has a `workloads` list in BENCHMARK.json and no `needs`: say in "
                    f"benchmark/layer_metrics/{name}.json which feature it needs, or drop the "
                    f"list (it is then owed in every cell that reports `{moves}`)")
            continue
        if listed is None:
            yield Breach(
                "R1", name, None,
                f"`{name}` needs `{need}`, so its entry in BENCHMARK.json has a `workloads` list "
                f"of the cells that run `{need}`: it has none")
            listed = []
        for cell in listed:
            if cell not in v.runs:
                yield Breach("R1", name, cell,
                             f"`{name}`'s `workloads` names `{cell}`, which is no cell")
        for cell in v.cells:
            owed = need in v.runs[cell] and moves in v.reported[cell]
            if owed and cell not in listed:
                yield Breach(
                    "R1", name, cell,
                    f"cell `{cell}` runs `{need}`, so it owes `{name}`: add it to {its_list}")
            elif cell in listed and need not in v.runs[cell]:
                yield Breach(
                    "R1", name, cell,
                    f"cell `{cell}` does not run `{need}`, which `{name}` needs: take it out of "
                    f"{its_list} (or, if it does run it, add `{need}` to `runs` in the "
                    f"config.json of `{v.config_of[cell]}`)")
            elif cell in listed and not owed:
                yield Breach(
                    "R1", name, cell,
                    f"cell `{cell}` does not report `{moves}`, which `{name}` moves: take it out "
                    f"of {its_list}")
    needed = {spec["needs"] for spec in v.specs.values() if "needs" in spec}
    for cell in v.cells:
        for word in sorted(v.runs[cell] - needed - {"collectives"}):
            yield Breach(
                "R1", None, cell,
                f"the configuration `{v.config_of[cell]}` of cell `{cell}` runs `{word}`, which "
                f"no metric needs: a feature exists because a file under "
                f"benchmark/layer_metrics/ names it")


def _scopes_have_one_owner(v: _View, declared_scopes: Iterable[str]):
    declared = set(declared_scopes) | {""}
    owner: dict[str, str] = {}
    for name, spec in v.specs.items():
        if spec["reducer"] != "scope_op_time":
            continue
        for scope in spec["args"]["scopes"]:
            if scope in owner:
                yield Breach(
                    "R2", name, None,
                    f"the scope `{scope}` is owned by `{owner[scope]}` and by `{name}`: a device "
                    f"scope has one owner; take it out of the `scopes` of one of the two")
            elif scope not in declared:
                yield Breach(
                    "R2", name, None,
                    f"`{name}` owns the scope `{scope}`, which the program does not declare")
            owner.setdefault(scope, name)
    left_out = {v.specs[name]["args"].get("except_ops") for name in owner.values()}
    if len(left_out) > 1:
        yield Breach(
            "R2", None, None,
            f"the scope metrics leave different ops out ({sorted(map(str, left_out))}): with one "
            f"`except_ops` they and those ops' own time add up to the device's busy time")
    for scope in sorted(declared - set(owner)):
        yield Breach(
            "R2", None, None,
            f"the declared scope `{scope or 'no scope'}` has no owner: name it in the `scopes` "
            f"of a scope_op_time metric (a file under benchmark/layer_metrics/) that `needs` "
            f"the feature whose programs carry it")
    for cell in v.cells:
        here = {e["name"] for e in v.entries if _in_cell(e, cell)}
        for scope, name in sorted(owner.items()):
            need = v.specs[name].get("needs")
            if scope in declared and name not in here and (need is None or need in v.runs[cell]):
                yield Breach(
                    "R2", name, cell,
                    f"in cell `{cell}` nothing owns the scope `{scope or 'no scope'}`: its owner "
                    f"`{name}` is not listed there; add the cell to that metric's `workloads`")


def _rooflines_sit_beside_their_times(v: _View):
    for name, spec in v.specs.items():
        if not name.endswith("_roofline"):
            continue
        beside = name[: -len("_roofline")] + "_ms"
        its_time = v.specs.get(beside)
        if its_time is None:
            yield Breach("R3", name, None,
                         f"`{name}` has no `{beside}` beside it: a roofline divides by its time")
            continue
        for key in ("needs", "moves"):
            if spec.get(key) != its_time.get(key):
                yield Breach(
                    "R3", name, None,
                    f"`{name}` says {key}={spec.get(key)!r} and `{beside}` says "
                    f"{its_time.get(key)!r}: a roofline sits beside its time, so give both the "
                    f"same `{key}`")
        reads, time_reads = _reads(spec), _reads(its_time)
        if reads is None or reads != time_reads:
            yield Breach(
                "R3", name, None,
                f"`{name}` reads {reads} and `{beside}` reads {time_reads}: give "
                f"both the same `scopes` and `except_ops`, or the same kernels (`regex` = the "
                f"keys of `kernels` joined by `|`)")


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
