"""What a cell reports follows from what its configuration runs
(``benchmark/harness/manifest.py`` ``Manifest.breaches``): the three rules,
held for every cell alike and worked out here a second time from the files, so
that no test decides by a cell's name what the cell must or may not report."""

import subprocess
import sys

import pytest

from _paths import ROOT

from benchmark.harness.manifest import Manifest

_MANIFEST = Manifest()
CELLS = _MANIFEST.cell_names()
PER_LAYER = {e["name"]: _MANIFEST.metric_spec(e) for e in _MANIFEST.data["per_layer"]}
WITH_NEEDS = sorted(name for name, spec in PER_LAYER.items() if "needs" in spec)
ROOFLINES = sorted(name for name in PER_LAYER if name.endswith("_roofline"))


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def declared():
    from acco_tpu.telemetry import DECLARED_DEVICE_SCOPES

    return list(DECLARED_DEVICE_SCOPES)


@pytest.fixture(scope="module")
def breaches(manifest, declared):
    return manifest.breaches(declared)


def about(breaches, rule, cell):
    """The breaches of one rule that are about this cell or about no one cell."""
    return [str(b) for b in breaches if b.rule == rule and b.cell in (cell, None)]


def reports(manifest, cell, spec):
    return spec["moves"] in {m["name"] for m in manifest.end_to_end(cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_r1_a_cells_lists_follow_the_features_it_runs(manifest, breaches, cell):
    assert about(breaches, "R1", cell) == []
    runs = manifest.features(cell)
    listed = {s["name"] for s in manifest.layer_metrics(cell)}
    for name, spec in PER_LAYER.items():
        owed = spec.get("needs") in runs | {None} and reports(manifest, cell, spec)
        if "needs" in spec:
            assert (name in listed) is owed, (name, spec["needs"], sorted(runs))
        else:  # no list: owed wherever what it moves is reported
            assert "workloads" not in spec and name in listed


@pytest.mark.parametrize("metric", WITH_NEEDS)
def test_r1_a_metric_with_a_need_lists_the_cells_that_run_it(manifest, metric):
    """Letter for letter, in the order of the cells: the list in
    ``BENCHMARK.json`` is what the features give."""
    spec = PER_LAYER[metric]
    assert spec["workloads"] == [c for c in CELLS if spec["needs"] in manifest.features(c)
                                 and reports(manifest, c, spec)]
    assert spec["workloads"], "a feature that no cell runs measures nothing"


def test_r1_a_feature_is_a_word_some_metric_needs(manifest):
    needed = {spec["needs"] for spec in PER_LAYER.values() if "needs" in spec}
    for cell in CELLS:
        assert manifest.features(cell) <= needed, cell
        assert ("collectives" in manifest.features(cell)) is (manifest.cell(cell)["chips"] > 1)


@pytest.mark.parametrize("cell", CELLS)
def test_r2_every_scope_the_cell_runs_has_one_owner_there(manifest, breaches, declared, cell):
    """The scopes a cell's metrics own are the declared ones and ``""``
    (no scope), less those whose owner needs a feature the cell does not run:
    with the collectives' own time they add up to the device's busy time."""
    assert about(breaches, "R2", cell) == []
    runs = manifest.features(cell)
    owners = {n: s for n, s in PER_LAYER.items() if s["reducer"] == "scope_op_time"}
    all_owned = [scope for s in owners.values() for scope in s["args"]["scopes"]]
    assert sorted(all_owned) == sorted([*declared, ""])  # none twice, none missing, none made up
    assert len({s["args"]["except_ops"] for s in owners.values()}) == 1
    not_run = {scope for s in owners.values() if s.get("needs") not in runs | {None}
               for scope in s["args"]["scopes"]}
    here = [scope for s in manifest.layer_metrics(cell) if s["reducer"] == "scope_op_time"
            for scope in s["args"]["scopes"]]
    assert sorted(here) == sorted(set(all_owned) - not_run)


@pytest.mark.parametrize("cell", CELLS)
def test_r3_a_roofline_sits_beside_its_time_in_the_cell(manifest, breaches, cell):
    assert about(breaches, "R3", cell) == []
    listed = {s["name"] for s in manifest.layer_metrics(cell)}
    for name in listed & set(ROOFLINES):
        assert name[: -len("_roofline")] + "_ms" in listed, name


@pytest.mark.parametrize("roofline", ROOFLINES)
def test_r3_a_roofline_reads_what_its_time_reads(roofline):
    share, time = PER_LAYER[roofline], PER_LAYER[roofline[: -len("_roofline")] + "_ms"]
    assert share.get("needs") == time.get("needs") and share["moves"] == time["moves"]
    assert share["unit"] == "%" and time["unit"] == "ms/round"
    if share["reducer"] == "scope_roofline":
        assert time["reducer"] == "scope_op_time" and share["args"]["work"]
        assert {k: share["args"][k] for k in ("scopes", "except_ops")} == time["args"]
    else:
        assert (share["reducer"], time["reducer"]) == ("kernel_roofline", "trace_op_time")
        assert "|".join(share["args"]["kernels"]) == time["args"]["regex"]
        assert set(share["args"]["kernels"].values()) <= {"global", "local"}


def test_the_programs_list_of_scopes_is_read_without_jax():
    """``run.py``'s parent hands the rule the program's own list of scopes,
    and must stay off JAX to leave the chip to its children."""
    code = ("import sys, acco_tpu.telemetry.trace as t; print(len(t.DECLARED_DEVICE_SCOPES)); "
            "sys.exit('jax' in sys.modules)")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                          timeout=60)
    assert done.returncode == 0 and int(done.stdout) >= 16, done.stderr[-2000:]
