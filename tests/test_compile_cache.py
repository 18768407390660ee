"""Compile-once subsystem (acco_tpu/compile): persistent-cache key
stability + parallel AOT warmup.

The cache contract under test: the HLO-keyed persistent cache must serve
a SECOND trainer of the same config entirely from disk (every round
program a hit), must MISS when a compile-relevant knob changes (the
program is genuinely different — serving stale HLO would be a
correctness bug), and must still HIT when only runtime-side knobs change
(checkpoint cadence is not part of any compiled program — recompiling
for it would be the startup-cost bug this subsystem exists to kill).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acco_tpu.configuration import config_from_dict
from acco_tpu.data.tokenizer import ByteTokenizer
from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.trainer import DecoupledTrainer

CFG = LlamaConfig(
    vocab_size=257,
    hidden_size=32,
    intermediate_size=64,
    num_layers=2,
    num_heads=2,
    num_kv_heads=2,
    max_position_embeddings=32,
)


def _docs(n=64, rows_len=24, seed=0):
    # const-len-clean rows (>= max_length): the const-len verdict stays
    # True, so the optimistic warmup never restarts and each trainer
    # compiles exactly one program set.
    rng = np.random.default_rng(seed)
    return [
        {"input_ids": rng.integers(0, 256, size=rows_len).tolist()}
        for _ in range(n)
    ]


def _args(cache_dir, **over):
    base = dict(
        method_name="acco",
        batch_size=1,
        n_grad_accumulation=1,
        learning_rate=1e-3,
        weight_decay=0.0,
        nb_steps_tot=32,
        max_length=16,
        scheduler_name="constant",
        warmup=0,
        use_mixed_precision=False,
        n_warmup_steps=0,
        eval=False,
        save=False,
        const_len_batch=True,
        checkpoint_every_s=10_000,
        compile_cache_dir=str(cache_dir),
        warmup_compile=True,
    )
    base.update(over)
    return config_from_dict(base)


def _trainer(cache_dir, tmp_path, *, scan_unroll=1, **over):
    model = LlamaModel(
        CFG, param_dtype=jnp.float32, scan_unroll=scan_unroll
    )
    return DecoupledTrainer(
        model,
        ByteTokenizer(),
        _docs(),
        None,
        _args(cache_dir, **over),
        seed=0,
        run_dir=str(tmp_path),
    )


def _cache_files(cache_dir):
    import os

    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    """Isolated cache dir for one test; jax's global cache config (and
    its memoized is-cache-used verdict) restored afterwards so the rest
    of the suite stays uncached. A cache the caller of pytest placed
    through the environment would win over the test's own dir, so the
    variable is hidden for the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from acco_tpu.compile import drain_abandoned_compiles

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_enable = jax.config.jax_enable_compilation_cache
    prev_metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    yield str(tmp_path / "compile-cache")
    # a trainer that was constructed but never train()ed leaves its
    # warmup threads compiling in the background; drain them so their
    # cache traffic can't cross into the next test (and so reset_cache
    # below doesn't race a live compile)
    drain_abandoned_compiles()
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_enable_compilation_cache", prev_enable)
    jax.config.update(
        "jax_compilation_cache_include_metadata_in_key", prev_metadata
    )
    cc.reset_cache()


ROUND_PROGRAMS = {"seed", "round_even", "round_odd"}


def test_same_config_twice_all_round_programs_hit(
    eight_devices, tmp_path, compile_cache_dir
):
    t1 = _trainer(compile_cache_dir, tmp_path / "r1")
    rep1 = t1.join_warmup()
    assert rep1 is not None and rep1.ok, rep1 and rep1.programs
    assert set(rep1.programs) == ROUND_PROGRAMS
    # fresh dir: everything compiled, nothing served
    assert rep1.cache["hits"] == 0
    assert rep1.cache["misses"] >= len(ROUND_PROGRAMS)
    files_after_first = _cache_files(compile_cache_dir)
    assert files_after_first >= len(ROUND_PROGRAMS)

    t2 = _trainer(compile_cache_dir, tmp_path / "r2")
    rep2 = t2.join_warmup()
    assert rep2.ok
    # The durable contract first: nothing new compiled into the dir. A
    # genuine cache-key instability writes a NEW file per differing
    # program and fails this deterministically.
    assert _cache_files(compile_cache_dir) == files_after_first
    # The whole program set is served from the persistent cache. Each
    # program's counters (ProgramCompileRecord.cache) are attributed AT
    # EVENT TIME to the compiling thread's registered window
    # (compile.attribute_cache_events), so concurrent compiles elsewhere
    # in the process — an abandoned warmup, another trainer — can't leak
    # in and no event can be dropped by a snapshot race. The old
    # before/after thread-ident deltas needed a retry-with-a-third-
    # trainer fallback here; the exact counters assert directly.
    assert rep2.cache["hits"] >= len(ROUND_PROGRAMS), (
        rep2.cache,
        {n: r.cache for n, r in rep2.programs.items()},
    )
    # warm compile is a deserialization: strictly cheaper than cold
    cold = sum(r.compile_ms for r in rep1.programs.values())
    warm = sum(r.compile_ms for r in rep2.programs.values())
    assert warm < cold


def test_compile_relevant_knob_flip_misses(
    eight_devices, tmp_path, compile_cache_dir
):
    """scan_unroll changes the compiled layer loop: every program's HLO
    is different and must MISS — a hit here would mean the cache key is
    too coarse and a config change could run stale code."""
    t1 = _trainer(compile_cache_dir, tmp_path / "r1")
    assert t1.join_warmup().ok
    files_before = _cache_files(compile_cache_dir)

    t2 = _trainer(compile_cache_dir, tmp_path / "r2", scan_unroll=True)
    rep = t2.join_warmup()
    assert rep.ok
    assert rep.cache["hits"] == 0
    assert rep.cache["misses"] >= len(ROUND_PROGRAMS)
    assert _cache_files(compile_cache_dir) > files_before


def test_comm_impl_flip_misses_round_programs(
    eight_devices, tmp_path, compile_cache_dir
):
    """comm_impl changes only the ZeRO-1 collectives: the parity round
    programs (which carry the update) must miss, while the compute-only
    seed program is identical and may still hit."""
    t1 = _trainer(compile_cache_dir, tmp_path / "r1", comm_impl="xla")
    assert t1.join_warmup().ok
    files_before = _cache_files(compile_cache_dir)

    t2 = _trainer(compile_cache_dir, tmp_path / "r2", comm_impl="ring")
    rep = t2.join_warmup()
    assert rep.ok
    assert rep.cache["misses"] >= 2  # round_even + round_odd recompiled
    assert _cache_files(compile_cache_dir) > files_before


def test_runtime_only_knob_flip_still_hits(
    eight_devices, tmp_path, compile_cache_dir
):
    """checkpoint_every_s (and the other host-side cadences) are not part
    of any compiled program: flipping them must not cost a recompile."""
    t1 = _trainer(compile_cache_dir, tmp_path / "r1")
    assert t1.join_warmup().ok
    files_before = _cache_files(compile_cache_dir)

    t2 = _trainer(
        compile_cache_dir,
        tmp_path / "r2",
        checkpoint_every_s=1.5,
        delta_step_for_log=3,
        prefetch_depth=7,
    )
    rep = t2.join_warmup()
    assert rep.ok
    assert rep.cache["hits"] >= len(ROUND_PROGRAMS)
    assert _cache_files(compile_cache_dir) == files_before


def test_warmup_report_shape_and_train_cold(
    eight_devices, tmp_path, compile_cache_dir
):
    """Cold end-to-end: warmup report carries per-program lower/compile
    timings, the AOT executables are installed, and train() runs through
    them (every program compiled fresh in this process — the safe
    envelope)."""
    t = _trainer(compile_cache_dir, tmp_path / "run")
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    rep = t.compile_report
    assert rep is not None and rep.ok
    for rec in rep.programs.values():
        assert rec.lower_ms > 0 and rec.compile_ms > 0
        assert rec.compiled is not None
    # the AOT executables were installed on the step object
    assert set(t.step_obj.compiled_programs) == ROUND_PROGRAMS
    assert rep.cache_dir is not None


def test_ddp_warmup_single_program(eight_devices, tmp_path, compile_cache_dir):
    t = _trainer(compile_cache_dir, tmp_path / "r1", method_name="ddp")
    rep = t.join_warmup()
    assert rep.ok
    assert set(rep.programs) == {"step"}


def test_abstract_state_matches_real_init(eight_devices):
    """The avals warmup lowers against must be byte-for-byte the real
    state's (shape, dtype, sharding) — a drift would silently compile
    programs the trainer never dispatches."""
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

    model = LlamaModel(CFG, param_dtype=jnp.float32)
    mesh = make_mesh({DATA_AXIS: 8})
    step = AccoTrainStep(
        model,
        mesh,
        get_schedule("constant", 1e-3, 0, 32),
        mode="acco",
        weight_decay=0.0,
        beta1=0.9,
        beta2=0.95,
        const_len_batch=True,
    )
    abstract = step.abstract_state(seed=0)
    real = step.init_state(model.init(jax.random.PRNGKey(0)))
    flat_a, flat_r = jax.tree.leaves(abstract), jax.tree.leaves(real)
    assert len(flat_a) == len(flat_r)
    for a, r in zip(flat_a, flat_r):
        assert a.shape == r.shape
        assert a.dtype == r.dtype
        assert a.sharding == r.sharding


def test_aot_fallback_on_aval_mismatch(caplog):
    """aot_call_with_fallback degrades to the jit path (once, logged)
    when the compiled executable rejects its inputs."""
    from acco_tpu.compile import aot_call_with_fallback

    calls = []

    def bad_compiled(*a):
        raise TypeError("aval mismatch")

    def jit_fn(*a):
        calls.append(a)
        return "jit"

    import logging

    log = logging.getLogger("test-aot-fallback")
    wrapped = aot_call_with_fallback(bad_compiled, jit_fn, "round", log=log)
    with caplog.at_level(logging.WARNING, logger="test-aot-fallback"):
        assert wrapped(1, 2) == "jit"
    assert "rejected its inputs" in caplog.text
    assert wrapped(3) == "jit"  # one-way: no second AOT attempt
    assert len(calls) == 2


def test_cache_placed_from_outside_is_left_alone(
    eight_devices, tmp_path, compile_cache_dir, monkeypatch
):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache:
    neither a direct call nor a trainer's config points it elsewhere."""
    from acco_tpu.compile import active_cache_dir, setup_compilation_cache

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    # jax reads the variable once, at import: stand in for that
    jax.config.update("jax_compilation_cache_dir", placed)

    elsewhere = tmp_path / "elsewhere"
    assert setup_compilation_cache(str(elsewhere)) == placed
    trainer = _trainer(tmp_path / "elsewhere-too", tmp_path / "run")
    assert trainer.compile_cache_dir == placed
    assert active_cache_dir() == placed
    assert not elsewhere.exists() and not (tmp_path / "elsewhere-too").exists()
    assert trainer.join_warmup().cache_dir == placed


def test_default_cache_is_in_the_checkout_from_any_cwd(
    tmp_path, compile_cache_dir, monkeypatch
):
    """Unset, the cache is <checkout>/outputs/compile_cache, resolved
    against the checkout and not the working directory."""
    import os

    from acco_tpu.compile import setup_compilation_cache

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(checkout, "outputs", "compile_cache")
    monkeypatch.chdir(tmp_path)
    assert setup_compilation_cache() == expected
    # what config/train/*.yaml spell, and main.py hands over as it is
    assert setup_compilation_cache("outputs/compile_cache") == expected
    assert not (tmp_path / "outputs").exists()
    # '' is the opt-out: nothing changes
    assert setup_compilation_cache("") == expected


def test_no_entry_point_makes_up_a_cache_path():
    """No mkdtemp, pid or clock in any cache path: a directory that moves
    never hits."""
    import os
    import re

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    suspects = re.compile(r"mkdtemp|gettempdir|getpid|strftime|time\.time")
    for rel in (
        "main.py", "serve.py", "chip_smoke.py",
        "tools/compile_report.py", "acco_tpu/compile/cache.py",
    ):
        with open(os.path.join(checkout, rel)) as f:
            for n, line in enumerate(f, 1):
                if "cache" in line.lower() and suspects.search(line):
                    raise AssertionError(f"{rel}:{n}: {line.strip()}")


def test_a_cache_hit_never_serves_another_sources_scope_names(compile_cache_dir):
    """ISSUE 23: a named scope is metadata, and JAX's cache key leaves
    metadata out by default — a program that differs from a cached one
    only in its scopes would be served the cached executable, names and
    all, and a profile would attribute its time to the other source's
    scopes. ``setup_compilation_cache`` makes metadata part of the key."""
    import contextlib

    import jax.numpy as jnp

    from acco_tpu.compile import setup_compilation_cache

    setup_compilation_cache(compile_cache_dir)
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def program(scoped):
        def f(x):
            with (
                jax.named_scope("acco/optimizer")
                if scoped
                else contextlib.nullcontext()
            ):
                return jnp.sin(x) * 2 + 1

        return jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()

    assert "acco/optimizer" not in program(scoped=False)  # fills the cache
    assert "acco/optimizer" in program(scoped=True)
