"""Gate suite for the static-analysis subsystem (ISSUE 10).

Two proof obligations, both tier-1 fast:

- the real programs PASS: every program a production run dispatches
  (ACCO even+odd, DPU, DDP, eval, serve prefill buckets + decode) is
  AOT-lowered from avals on the CPU backend and must clear the
  donation, census, dtype, and sharding-rule-coverage gates;
- each analyzer FAILS on its seeded violation: a gate that cannot fail
  proves nothing, so every analyzer is shown firing on a fixture built
  to violate exactly its invariant (``tests/fixtures/lint``).

Overlap is the exception (the CPU backend never forms async collective
pairs — see ``acco_tpu/analysis/programs.py``): the analyzer is proved
on canned scheduled-HLO fixtures here, and the production verdict runs
on the TPU AOT toolchain via ``tools/lint.py --overlap``.
"""

import os
import warnings
from collections import namedtuple

import jax
import jax.numpy as jnp
import pytest

from acco_tpu.analysis.census import check_census
from acco_tpu.analysis.donation import check_donation
from acco_tpu.analysis.dtypes import check_dtype_policy, train_state_rules
from acco_tpu.analysis.host_lint import lint_file, lint_paths
from acco_tpu.analysis.overlap import check_overlap
from acco_tpu.analysis.slow_markers import (
    audit_durations,
    audit_recorded,
    merge_records,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "lint")


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


@pytest.fixture(scope="session")
def registry(eight_devices):
    """Every dispatched program, lowered once per session (~15 s total;
    the per-program compile is cached on the Program object)."""
    from acco_tpu.analysis.programs import build_all_tiny

    return build_all_tiny()


# -- the real programs pass --------------------------------------------------


def test_registry_covers_every_dispatched_program(registry):
    names = {p.name for p in registry}
    assert {"acco_round_even", "acco_round_odd", "dpu_round",
            "ddp_step", "eval", "serve_decode"} <= names
    assert any(n.startswith("serve_prefill_") for n in names)


def test_donation_gate_passes_on_every_program(registry):
    for p in registry:
        rep = check_donation(p.lowered, p.compiled(), p.hlo())
        assert rep.ok, f"{p.name}: {rep.summary()}"


def test_train_round_state_donation_is_honored(registry):
    """The donation that matters most: the round state (incl. the
    [ns*Pp] pending-grads vector, the largest allocation in the round)
    must actually alias — an even-parity round with every declared
    donation honored, and no program anywhere with a dropped one."""
    even = next(p for p in registry if p.name == "acco_round_even")
    rep = check_donation(even.lowered, even.compiled(), even.hlo())
    assert len(rep.aliased) == 13 and not rep.elided, rep.summary()


def test_serve_pool_donation_audit(registry):
    """Satellite audit: the KV pools are donated through every serve
    program (prefill buckets and decode both rebind k_pages/v_pages) —
    a dropped pool donation would double the largest serving allocation."""
    serve = [p for p in registry if p.kind == "serve"]
    assert len(serve) >= 2
    for p in serve:
        rep = check_donation(p.lowered, p.compiled(), p.hlo())
        assert len(rep.aliased) == 2 and not rep.dropped, (
            f"{p.name}: {rep.summary()}"
        )


def test_census_gate_passes_on_every_program(registry):
    for p in registry:
        rep = check_census(
            p.hlo(), p.expect_comm_bytes, p.expect_comm_ops,
            small_elems=p.small_elems,
        )
        assert rep.ok, f"{p.name}: {rep.summary()}"


def test_census_measures_the_analytic_ring_bytes(registry):
    """The measured wire bytes must EQUAL the comm model, not just sit
    inside the tolerance band — the model is exact for ring collectives."""
    even = next(p for p in registry if p.name == "acco_round_even")
    rep = check_census(even.hlo(), even.expect_comm_bytes,
                       even.expect_comm_ops, small_elems=even.small_elems)
    assert rep.measured_bytes == int(even.expect_comm_bytes)


def test_dtype_gate_passes_on_every_program(registry):
    for p in registry:
        rep = check_dtype_policy(p.state_tree, p.dtype_rules)
        assert rep.ok, f"{p.name}: {rep.summary()}"
        assert rep.checked > 0


def test_cpu_backend_forms_no_async_pairs(registry):
    """Documents WHY overlap is a TPU-lane gate: the CPU backend
    schedules every ring hop as a blocking collective-permute. If this
    ever starts failing, the overlap gate can move into tier-1."""
    even = next(p for p in registry if p.name == "acco_round_even")
    rep = check_overlap(even.hlo(), small_elems=even.small_elems)
    assert rep.async_pairs == 0 and not rep.ok


# -- each analyzer fails on its seeded violation ------------------------------


def test_overlap_passes_on_overlapped_schedule():
    rep = check_overlap(_fixture("scheduled_good.hlo"))
    assert rep.ok and rep.async_pairs == 2 and rep.covered_windows == 2


def test_overlap_fails_on_blocking_collective():
    rep = check_overlap(_fixture("scheduled_blocking.hlo"))
    assert not rep.ok and rep.blocking_large == 1 and rep.async_pairs == 0


def test_overlap_small_collective_exemption():
    """The same blocking op below the size floor is exempt — but the
    schedule still fails for having no async pairs at all."""
    rep = check_overlap(_fixture("scheduled_blocking.hlo"),
                        small_elems=1 << 30)
    assert rep.blocking_large == 0 and not rep.ok


def test_donation_fails_on_dropped_donation():
    """Seeded drop: a dtype-changing output cannot alias its donated
    input, so XLA silently copies — exactly what the gate must catch."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = jax.jit(lambda x: (x * 2).astype(jnp.bfloat16),
                    donate_argnums=0)
        lowered = f.lower(jax.ShapeDtypeStruct((4096,), jnp.float32))
        compiled = lowered.compile()
    rep = check_donation(lowered, compiled, compiled.as_text())
    assert not rep.ok and len(rep.dropped) == 1


def test_census_fails_on_unexpected_collective():
    rep = check_census(_fixture("scheduled_blocking.hlo"),
                       expected_bytes=0.0)
    assert not rep.ok and "collective-free" in rep.summary()


def test_census_fails_on_wrong_wire_bytes():
    """The good schedule moves 2x8 MiB of permute payload; a comm model
    claiming half that is out of tolerance."""
    rep = check_census(_fixture("scheduled_good.hlo"),
                       expected_bytes=8388608.0)
    assert not rep.ok


def test_census_fails_on_op_count_out_of_range():
    rep = check_census(_fixture("scheduled_good.hlo"),
                       expected_bytes=16777216.0, expected_ops=(3, 4))
    assert not rep.ok


_Opt = namedtuple("_Opt", ["params", "mu", "nu", "count"])
_Zero1 = namedtuple("_Zero1", ["opt", "sched_grads", "grads_committed"])
_State = namedtuple("_State", ["flat_params", "pending_grads", "zero1",
                               "round_idx"])


def _fake_state(mu_dtype=jnp.float32, extra=None):
    s = jax.ShapeDtypeStruct
    state = _State(
        flat_params=s((8,), jnp.bfloat16),
        pending_grads=s((16,), jnp.float32),
        zero1=_Zero1(
            opt=_Opt(params=s((8,), jnp.float32), mu=s((8,), mu_dtype),
                     nu=s((8,), jnp.float32), count=s((), jnp.int32)),
            sched_grads=s((), jnp.int32),
            grads_committed=s((), jnp.float32),
        ),
        round_idx=s((), jnp.int32),
    )
    return {"state": state, **(extra or {})} if extra else state


def test_dtype_fails_on_bf16_adam_moment():
    """Seeded violation: Adam's mu silently landing in bf16 is the
    trains-worse-without-erroring failure the policy exists to catch."""
    rep = check_dtype_policy(_fake_state(mu_dtype=jnp.bfloat16),
                             train_state_rules(jnp.bfloat16))
    assert not rep.ok
    assert any("mu" in v.path and "bfloat16" in v.message
               for v in rep.violations)


def test_dtype_fails_on_uncovered_leaf():
    """Closed world: a NEW state leaf with no declared policy fails the
    gate until its dtype rule is written down."""
    rules = train_state_rules(jnp.bfloat16)
    rep = check_dtype_policy(
        _fake_state(extra={"mystery": jax.ShapeDtypeStruct((4,),
                                                           jnp.float64)}),
        rules,
    )
    assert not rep.ok
    assert any(v.rule is None and "mystery" in v.path
               for v in rep.violations)


def test_dtype_passes_on_policy_conformant_tree():
    rep = check_dtype_policy(_fake_state(), train_state_rules(jnp.bfloat16))
    assert rep.ok and rep.checked == 9


def test_rules_gate_passes_on_every_program(registry):
    """The placement analogue of the dtype walk: every dispatched
    program's state tree is fully covered by its sharding rule table,
    with no leaf matched twice."""
    from acco_tpu.analysis.rules import check_rule_coverage

    for p in registry:
        rep = check_rule_coverage(p.state_tree, p.rule_table)
        assert rep.ok, f"{p.name}: {rep.summary()}"
        assert rep.checked > 0


def test_rules_gate_fails_on_unmatched_leaf():
    """Seeded violation: a new state field nobody placed must fail the
    gate until a rule is written down (closed world — the leaf would
    otherwise silently replicate on a pod)."""
    from acco_tpu.analysis.rules import check_rule_coverage
    from acco_tpu.sharding import train_state_table

    table = train_state_table("ddp", ("dp",), None)
    rep = check_rule_coverage({"flat_params": 0, "mystery_buffer": 0}, table)
    assert not rep.ok
    assert [v.kind for v in rep.violations] == ["unmatched"]
    assert "mystery_buffer" in rep.violations[0].message


def test_rules_gate_fails_on_ambiguous_rule_pair():
    """Seeded violation: two rules matching one leaf — first-match-wins
    would silently pick one, and a table reorder would flip the
    placement, so the gate treats the overlap itself as the bug."""
    from jax.sharding import PartitionSpec as P

    from acco_tpu.analysis.rules import check_rule_coverage
    from acco_tpu.sharding import Rule, RuleTable

    table = RuleTable(
        "seeded-overlap",
        (Rule(r"^opt/", P()), Rule(r"mu$", P("dp"))),
    )
    rep = check_rule_coverage({"opt": {"mu": 0, "nu": 0}}, table)
    assert not rep.ok
    kinds = {v.path: v.kind for v in rep.violations}
    assert kinds == {"opt/mu": "ambiguous"}
    assert rep.checked == 2  # opt/nu matched exactly once and passed


def test_rules_gate_fails_on_missing_table():
    """A dispatched program without a rule table has unreviewed
    placement — that absence is itself a gate failure."""
    from acco_tpu.analysis.rules import check_rule_coverage

    rep = check_rule_coverage({"flat_params": 0}, None)
    assert not rep.ok and "no sharding rule table" in rep.summary()


def test_host_lint_fires_on_every_seeded_rule():
    findings = lint_file(os.path.join(FIXTURES, "bad_host.py"))
    rules = {f.rule for f in findings}
    assert rules == {"unused-import", "jit-missing-donation",
                     "host-sync-in-loop", "thread-without-join"}


def test_metrics_gate_fires_on_every_seeded_rule():
    """Seeded violations: the static telemetry-name check must report
    both undeclared metrics and both undeclared spans in the fixture —
    and nothing else (the declared and free-category calls pass)."""
    from acco_tpu.analysis.metrics_gate import check_file

    rep = check_file(os.path.join(FIXTURES, "bad_metrics.py"))
    assert not rep.ok
    assert sorted(f.rule for f in rep.findings) == [
        "undeclared-metric", "undeclared-metric",
        "undeclared-span", "undeclared-span",
    ]
    messages = " ".join(f.message for f in rep.findings)
    assert "totally_made_up_metric" in messages
    assert "another_bogus_name" in messages
    assert "ckpt/snapshit" in messages
    assert "not/a/span" in messages
    # the declared + cat="test" call sites were checked, not flagged
    assert rep.checked > len(rep.findings)


def test_metrics_gate_passes_on_clean_source():
    from acco_tpu.analysis.metrics_gate import check_file

    src = (
        "from acco_tpu.telemetry import metrics\n"
        "def f(tracer, name):\n"
        "    metrics.emit('train_rounds_total', 1)\n"
        "    metrics.emit(name, 1)  # dynamic: runtime check's job\n"
        "    with tracer.span('train/eval'):\n"
        "        pass\n"
        "    tracer.complete_event('t::x', 1.0, cat='test')\n"
    )
    rep = check_file("inline.py", source=src)
    # dynamic name + free-category event are not literal-checked sites
    assert rep.ok and rep.checked == 2


@pytest.mark.parametrize(
    "call, findings",
    [
        ("jax.named_scope('acco/optimizer')", 0),
        ("jax.named_scope('model/block')", 0),
        ("jax.named_scope('acco/optimiser')", 1),  # misspelled
        ("jax.named_scope('train/dispatch')", 1),  # a span, not a scope
        ("jax.named_scope(name)", 1),  # not a literal: nothing checks it at runtime
    ],
)
def test_metrics_gate_checks_named_scope_literals(call, findings):
    """ISSUE 23: a ``jax.named_scope`` outside DEVICE_SCOPES is device
    time no per-layer metric owns."""
    from acco_tpu.analysis.metrics_gate import check_file

    src = f"import jax\ndef f(name):\n    with {call}:\n        pass\n"
    rep = check_file("inline.py", source=src)
    assert rep.checked == 1
    assert [f.rule for f in rep.findings] == ["undeclared-scope"] * findings


@pytest.fixture(scope="module")
def repo_metrics_report():
    """The walk ``tools/lint.py --ci`` runs, once for the cases below."""
    from acco_tpu.analysis.metrics_gate import check_repo

    return check_repo(REPO)


def test_repo_metrics_gate_is_clean(repo_metrics_report):
    """The enforced baseline: every literal telemetry name in the
    package, the tools and the entry points is declared."""
    rep = repo_metrics_report
    assert rep.ok, [str(f) for f in rep.findings]
    assert rep.checked > 40  # the subsystem's own call sites keep it honest


def _declared_metric_names():
    from acco_tpu.telemetry.metrics import DECLARED

    return [spec.name for spec in DECLARED]


@pytest.mark.parametrize("name", _declared_metric_names())
def test_every_declared_metric_has_an_emit_site(repo_metrics_report, name):
    """The gate's other direction, a case a name so a failure says which:
    a declaration no call site emits is a measurement nothing takes.
    Delete it (or emit it where it is measured); there is no exemption."""
    assert name not in repo_metrics_report.orphaned([name])


def test_metrics_gate_reports_a_declared_name_nothing_emits():
    """Seeded: the fixture emits two declared names; held to the whole of
    DECLARED, every other name is orphaned, and the gate says so."""
    from acco_tpu.analysis.metrics_gate import check_file
    from acco_tpu.telemetry.metrics import REGISTRY

    rep = check_file(os.path.join(FIXTURES, "bad_metrics.py"))
    orphans = rep.orphaned(REGISTRY.declared_names())
    assert "train_rounds_total" not in orphans and "train_loss" not in orphans
    assert "ckpt_saves_total" in orphans and "serve_ttft_ms" in orphans
    assert len(orphans) == len(REGISTRY.declared_names()) - 2


@pytest.mark.parametrize(
    "source, declared, orphans",
    [
        # a conditional expression of literals spells both
        ("metrics.emit('a_total' if hit else 'b_total', 1)",
         ["a_total", "b_total", "c_total"], ["c_total"]),
        # literal prefix + a dict literal's key: only names so spelled
        ("terms = {'lb_loss': 1.0}\n"
         "for k, v in terms.items():\n"
         "    metrics.emit('train_' + k, v)",
         ["train_lb_loss", "train_z_loss", "other_lb_loss"],
         ["other_lb_loss", "train_z_loss"]),
        # a variable name keeps nothing alive
        ("metrics.emit(name, 1)", ["a_total"], ["a_total"]),
        # emit_many's dict literal
        ("metrics.emit_many({'a_total': 1, 'b_total': 2})",
         ["a_total", "b_total"], []),
    ],
)
def test_metrics_gate_orphans_by_call_shape(source, declared, orphans):
    from acco_tpu.analysis.metrics_gate import check_file

    rep = check_file("inline.py", source=source)
    assert rep.orphaned(declared) == orphans


def test_host_lint_suppression_markers():
    src = (
        "import jax\n"
        "def f(xs, state):\n"
        "    for x in xs:\n"
        "        x.item()  # lint: host-sync-ok\n"
        "    g = jax.jit(lambda state: state)  # lint: no-donate-ok\n"
        "    return g(state)\n"
    )
    assert lint_file("inline.py", source=src) == []


def test_host_lint_unused_import_exemptions():
    src = (
        "from __future__ import annotations\n"
        "import os\n"
        "import sys\n"
        "__all__ = [\"os\"]\n"
    )
    findings = lint_file("inline.py", source=src)
    assert [f.rule for f in findings] == ["unused-import"]
    assert "'sys'" in findings[0].message


def test_repo_host_lint_is_clean():
    """The enforced baseline: the package, tools, and tests (import
    hygiene) carry zero findings — same walk ``tools/lint.py --ci`` runs."""
    from acco_tpu.analysis.host_lint import DEFAULT_EXCLUDE_DIRS

    findings = lint_paths(
        [os.path.join(REPO, "acco_tpu"), os.path.join(REPO, "tools")]
    )
    findings += lint_paths(
        [os.path.join(REPO, "tests")], rules={"unused-import"},
        exclude_dirs=DEFAULT_EXCLUDE_DIRS + ("fixtures",),
    )
    assert findings == [], "\n".join(str(f) for f in findings)


def test_slow_marker_audit_flags_unmarked_slow_test():
    rep = audit_durations({
        "tests/test_x.py::test_fast": {"duration": 0.2, "slow": False},
        "tests/test_x.py::test_big": {"duration": 31.0, "slow": False},
        "tests/test_x.py::test_marked": {"duration": 400.0, "slow": True},
    })
    assert not rep.ok and len(rep.violations) == 1
    assert "test_big" in rep.violations[0]


def test_slow_marker_audit_missing_file_is_pass_with_note(tmp_path):
    rep = audit_recorded(str(tmp_path / "nope.json"))
    assert rep.ok and rep.checked == 0 and rep.note


def test_slow_marker_merge_roundtrip(tmp_path):
    path = str(tmp_path / "durations.json")
    merge_records(path, {"a::t1": {"duration": 30.0, "slow": False}})
    merge_records(path, {"a::t2": {"duration": 1.0, "slow": False}})
    rep = audit_recorded(path)
    assert rep.checked == 2 and not rep.ok and len(rep.violations) == 1


def test_lint_cli_fast_gates():
    """The CLI glue around the analyzers (host lint + ruff-or-skip +
    slow markers) — the compile-heavy program gates are covered via the
    session registry above instead of re-lowering everything."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "lint_cli", os.path.join(REPO, "tools", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    # dataclass field-annotation resolution looks the module up by name
    sys.modules["lint_cli"] = mod
    spec.loader.exec_module(mod)
    assert mod.gate_host_lint().ok
    assert mod.gate_ruff().ok
    assert mod.gate_slow_markers().ok
    assert mod.gate_metrics().ok
    assert 32 in mod.OVERLAP_EXPECTED_FAIL  # recorded dp=32 baseline
