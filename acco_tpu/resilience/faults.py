"""Fault injection: one registry for tests, configs and chaos drills.

Two layers, one implementation (ISSUE 7 satellite — the kill-mid-save /
truncate / ShutdownAfterRounds helpers used to live only under
``tests/``, so a config-driven injector would have grown a drifting
copy):

**Filesystem/process faults** — the failure modes a preempted or killed
trainer actually produces, used by the resilience tests and reusable
from operational drills:

- :func:`strip_meta` — make a committed ``step_*`` dir look
  killed-before-commit (remove the meta.json commit marker).
- :func:`truncate_state_file` — tear bytes off a committed checkpoint's
  largest state file (a partial block write behind a valid meta.json;
  the manifest validation must catch it). ``n_bytes`` larger than the
  file zeroes it — the torn write that *preserved the file name*.
- :func:`wipe_manifest` — rewrite meta.json with an empty state
  manifest (a commit that recorded nothing; validation must refuse it).
- :func:`run_saver_killed_subprocess` — a REAL saver SIGKILLed between
  the Orbax state commit and the meta.json finalize.
- :class:`ShutdownAfterRounds` — deterministic SIGTERM stand-in: latch
  the shutdown request at the N-th round-boundary poll.
- :func:`send_self_sigterm` — real signal delivery.

**Numerical faults** — the config-driven injector behind the
``fault_injection:`` train-yaml key: :class:`FaultInjector` fires registered fault
kinds at chosen rounds of the train loop, poisoning the *inputs* or the
*carried state* of the compiled round programs — never the programs
themselves — so the in-program anomaly guard and the host watchdog are
exercised exactly as a real anomaly would exercise them:

- ``nan_grads`` — NaN the block's ``valid`` weights: every microbatch
  gradient and count go NaN *through the compiled accumulation*, the
  uniform data-path injection for ACCO/DPU/DDP alike.
- ``spike_grads`` — scale the staged ``pending_grads`` by ``factor``
  (finite spike for the ``guard_max_grad_norm`` cap and the host
  monitor's z-score; ACCO/DPU only — DDP stages no gradients).
- ``corrupt_params`` — overwrite the first ``n`` working parameters
  with ``value`` (default NaN). Persistent: every later loss/grad is
  poisoned, the guard skips every round, and only the watchdog's
  auto-rollback can recover.
- ``corrupt_opt`` — same, into the optimizer's first-moment shard: the
  gradients stay finite but the *update* goes nonfinite (the guard's
  second signal).

Spec formats accepted by :func:`parse_fault_specs` /
``FaultInjector.from_config``: a list of dicts
(``[{kind: nan_grads, round: 3}, {kind: corrupt_params, round: 5,
n: 128}]``), a single dict, or compact strings (``"nan_grads@3"``).
Round indexes are 0-based dispatch counts of the current run's train
loop (the seed round is not counted); each spec fires exactly once.

**Serve faults** — the inference-side mirror (ISSUE 20):
:class:`ServeFaultInjector` fires :data:`SERVE_FAULT_KINDS`
(``engine_raise`` / ``slow_decode`` / ``kv_exhaust`` /
``client_abandon``) at chosen 0-based steps of the continuous-batching
scheduler, driven by the ``ACCO_SERVE_CHAOS`` env var, the serve yaml's
``fault_injection:`` key, or ``tools/load_harness.py --chaos`` — the
admission-control / cancellation / drain behaviors are drilled, not
just asserted.
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import textwrap
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from acco_tpu.resilience.preemption import ShutdownHandler

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_module_log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Filesystem / process faults (promoted from tests/faults.py)
# ---------------------------------------------------------------------------


class ShutdownAfterRounds(ShutdownHandler):
    """Request shutdown once the trainer has polled ``should_stop()``
    ``n_rounds`` times — i.e. exactly at round boundary N, every run,
    regardless of host speed. Inject via
    ``DecoupledTrainer(..., shutdown_handler=ShutdownAfterRounds(n))``.
    """

    def __init__(self, n_rounds: int, **kw) -> None:
        super().__init__(**kw)
        self.n_rounds = int(n_rounds)
        self.polls = 0

    def should_stop(self) -> bool:
        self.polls += 1
        if self.polls >= self.n_rounds:
            self.request()
        return super().should_stop()


def strip_meta(step_dir: str) -> str:
    """Make a committed ``step_*`` dir look killed-before-commit by
    removing its meta.json (the commit marker). Returns ``step_dir``."""
    os.remove(os.path.join(step_dir, "meta.json"))
    return step_dir


def truncate_state_file(step_dir: str, n_bytes: int = 64) -> str:
    """Tear ``n_bytes`` off the end of the largest file under
    ``step_dir/state`` — a partial write that survived a crash behind a
    committed meta.json (``n_bytes`` >= the file size leaves an intact
    NAME over zero bytes — the torn write that preserved file names).
    Returns the truncated file's path."""
    state = os.path.join(step_dir, "state")
    files = [
        os.path.join(root, name)
        for root, _, names in os.walk(state)
        for name in names
    ]
    target = max(files, key=os.path.getsize)
    size = os.path.getsize(target)
    with open(target, "r+b") as f:
        f.truncate(max(size - n_bytes, 0))
    return target


def wipe_manifest(step_dir: str) -> str:
    """Rewrite a committed meta.json with an EMPTY state manifest — a
    commit that recorded no state files (validation must refuse it
    rather than vacuously pass). Returns ``step_dir``."""
    import json

    meta_path = os.path.join(step_dir, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    from acco_tpu.utils.checkpoint import MANIFEST_KEY

    meta[MANIFEST_KEY] = {}
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    return step_dir


def run_saver_killed_subprocess(
    ckpt_dir: str, step: int, n: int = 4096, timeout: float = 180.0
) -> str:
    """Run a real saver in a subprocess and hard-kill it (SIGKILL, no
    cleanup handlers) after the Orbax state write but before the
    meta.json finalize. Returns the orphan ``step_<step>`` dir it left
    behind; asserts the process really died by signal, not by exiting.
    """
    code = textwrap.dedent(
        f"""
        import os
        import numpy as np

        from acco_tpu.utils.checkpoint import save_checkpoint

        state = {{"w": np.arange({int(n)}, dtype=np.float32),
                  "step": np.zeros((), np.int32)}}
        save_checkpoint({ckpt_dir!r}, {int(step)}, state, {{}},
                        write_meta=False)
        os.kill(os.getpid(), 9)  # die before the finalize step
        """
    )
    # a drill of the host-side save path: the child stays off any chip
    # the parent process holds
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == -9, (
        f"saver subprocess should die by SIGKILL, got rc={proc.returncode}: "
        f"{proc.stderr[-2000:]}"
    )
    path = os.path.join(os.path.abspath(ckpt_dir), f"step_{int(step)}")
    assert os.path.isdir(path), "killed saver should leave its state behind"
    return path


def send_self_sigterm() -> None:
    """Deliver a real SIGTERM to this process (the handler only latches a
    flag, so this is safe in-process)."""
    os.kill(os.getpid(), signal.SIGTERM)


# ---------------------------------------------------------------------------
# Numerical fault registry (the config-driven injector)
# ---------------------------------------------------------------------------

# kind -> inject(state, block, **params) -> (state, block). Injections
# happen on the HOST between dispatches, on the data or the carried
# state — the compiled round programs are untouched, so the guard is
# exercised exactly as by a real anomaly.
FAULT_KINDS: Dict[str, Callable] = {}


def register_fault(kind: str):
    def wrap(fn: Callable) -> Callable:
        FAULT_KINDS[kind] = fn
        return fn

    return wrap


def _device_put_like(np_value, like):
    """device_put preserving the leaf's exact sharding — the AOT-warmed
    executables dispatch on exact shardings, so an injection must not
    perturb the program signature."""
    import jax

    return jax.device_put(np_value, like.sharding)


@register_fault("nan_grads")
def _inject_nan_grads(state, block, **params):
    """NaN the block's ``valid`` weights: ``grad_sum += g * NaN`` inside
    the compiled accumulation poisons every gradient AND the count, for
    any method. ACCO stages them (next round's comm consumes and skips);
    DDP consumes them in the same step."""
    import numpy as np

    valid = block["valid"]
    block = dict(block)
    block["valid"] = _device_put_like(
        np.full(valid.shape, np.nan, np.float32), valid
    )
    return state, block


@register_fault("spike_grads")
def _inject_spike_grads(state, block, factor: float = 1e6, **params):
    """Scale the staged pending gradients — a finite spike for the
    static norm cap / host z-score (ACCO & DPU; DDP has no staged
    gradients to spike). The default keeps the squared norm inside
    float32 range, so the cap — not finiteness — is what trips."""
    import numpy as np

    _require_single_process("spike_grads")
    if not hasattr(state, "pending_grads"):
        raise ValueError(
            "spike_grads needs a state with staged gradients (ACCO/DPU); "
            "for DDP use nan_grads (data path) or corrupt_params/"
            "corrupt_opt (state path)"
        )
    import jax

    spiked = _device_put_like(
        np.asarray(jax.device_get(state.pending_grads), np.float32)
        * np.float32(factor),
        state.pending_grads,
    )
    return state._replace(pending_grads=spiked), block


def _require_single_process(kind: str) -> None:
    """The state-corrupting injectors round-trip dp-sharded leaves
    through the host (device_get -> mutate -> device_put), which only
    works when every shard is process-addressable. On a multi-host mesh
    device_get of such a leaf raises deep inside jax at the injection
    round — fail at the drill's start with an actionable message
    instead. (``nan_grads`` stays multi-host safe: it poisons the
    host-local data path, not sharded state.)"""
    import jax

    if jax.process_count() > 1:
        raise NotImplementedError(
            f"fault kind {kind!r} mutates dp-sharded state through the "
            "host and is single-process only; on multi-host runs use "
            "nan_grads (data path) or run the chaos drill on one host"
        )


def _corrupt_prefix(leaf, n: int, value: float):
    import jax
    import numpy as np

    host = np.array(jax.device_get(leaf))  # copy: device_get is read-only
    host[: max(1, int(n))] = value
    return _device_put_like(host, leaf)


@register_fault("corrupt_params")
def _inject_corrupt_params(state, block, n: int = 64, value: float = float("nan"), **params):
    """Overwrite the first ``n`` parameters in BOTH the working copy and
    the sharded fp32 master (``zero1.opt.params``): persistent poison.
    The master matters — every commit all-gathers fresh working params
    FROM the master, so corrupting the working copy alone self-heals
    after one committed round (a transient, not the persistent-corruption
    scenario this fault exists for). With the master poisoned, every
    tentative update is nonfinite, the guard skips every round (keeping
    the poisoned-but-frozen state bit-exact), and only the watchdog's
    auto-rollback can recover."""
    _require_single_process("corrupt_params")
    new_opt = state.zero1.opt._replace(
        params=_corrupt_prefix(state.zero1.opt.params, n, value)
    )
    return (
        state._replace(
            flat_params=_corrupt_prefix(state.flat_params, n, value),
            zero1=state.zero1._replace(opt=new_opt),
        ),
        block,
    )


@register_fault("corrupt_opt")
def _inject_corrupt_opt(state, block, n: int = 64, value: float = float("nan"), **params):
    """Overwrite the first ``n`` entries of the optimizer's first-moment
    shard: gradients stay finite, the UPDATE goes nonfinite — the
    guard's second on-device signal must catch it."""
    _require_single_process("corrupt_opt")
    opt = state.zero1.opt
    new_opt = opt._replace(mu=_corrupt_prefix(opt.mu, n, value))
    return (
        state._replace(zero1=state.zero1._replace(opt=new_opt)),
        block,
    )


class FaultSpec:
    """One scheduled fault: ``kind`` at 0-based loop ``round``, extra
    params passed through to the registered injector; fires once."""

    def __init__(self, kind: str, round_idx: int, **params: Any) -> None:
        if kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {kind!r}; registered: "
                f"{sorted(FAULT_KINDS)}"
            )
        self.kind = kind
        self.round = int(round_idx)
        if self.round < 0:
            raise ValueError(f"fault round must be >= 0, got {self.round}")
        self.params = dict(params)
        self.fired = False

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        extra = "".join(f", {k}={v!r}" for k, v in self.params.items())
        return f"FaultSpec({self.kind!r}@{self.round}{extra})"


def parse_fault_specs(cfg: Any) -> List[FaultSpec]:
    """Normalize a ``fault_injection:`` config value into FaultSpecs.

    Accepts None/empty (no faults), a single dict, a list of dicts
    (``{kind: ..., round: ..., **params}``), or compact ``"kind@round"``
    strings (also in a list). Unknown kinds and malformed entries raise
    at parse time — a chaos drill that silently injects nothing would
    report a robustness the stack does not have.
    """
    if cfg is None or cfg == "" or cfg is False:
        return []
    if isinstance(cfg, (str, dict)):
        cfg = [cfg]
    specs: List[FaultSpec] = []
    for entry in cfg:
        if isinstance(entry, str):
            kind, sep, rnd = entry.partition("@")
            if not sep:
                raise ValueError(
                    f"fault string {entry!r} must be 'kind@round'"
                )
            specs.append(FaultSpec(kind.strip(), int(rnd)))
        elif isinstance(entry, dict):
            entry = dict(entry)
            kind = entry.pop("kind", None)
            rnd = entry.pop("round", None)
            if kind is None or rnd is None:
                raise ValueError(
                    f"fault dict {entry!r} needs 'kind' and 'round' keys"
                )
            specs.append(FaultSpec(str(kind), int(rnd), **entry))
        else:
            raise ValueError(f"unsupported fault spec entry: {entry!r}")
    return specs


class FaultInjector:
    """Fire scheduled faults into the train loop.

    The trainer calls :meth:`apply` with its run-local dispatch index
    right before each round; matching un-fired specs poison the state
    and/or block. ``pending`` goes False once every spec has fired, so
    the steady-state loop pays one attribute check per round.
    """

    def __init__(
        self, specs: List[FaultSpec], log: Optional[logging.Logger] = None
    ) -> None:
        self.specs = list(specs)
        self.log = log or _module_log

    @classmethod
    def from_config(
        cls, cfg: Any, log: Optional[logging.Logger] = None
    ) -> Optional["FaultInjector"]:
        specs = parse_fault_specs(cfg)
        return cls(specs, log=log) if specs else None

    @property
    def pending(self) -> bool:
        return any(not s.fired for s in self.specs)

    @property
    def fired(self) -> List[FaultSpec]:
        return [s for s in self.specs if s.fired]

    def apply(self, round_idx: int, state: Any, block: Any) -> Tuple[Any, Any]:
        for spec in self.specs:
            if spec.fired or spec.round != int(round_idx):
                continue
            spec.fired = True
            self.log.warning(
                "fault injection: %s at round %d %s", spec.kind, round_idx,
                spec.params or "",
            )
            state, block = FAULT_KINDS[spec.kind](state, block, **spec.params)
        return state, block


# ---------------------------------------------------------------------------
# Serve-side chaos (ISSUE 20): faults fired at scheduler step indices
# ---------------------------------------------------------------------------

# kind -> inject(injector, scheduler, **params). Fired by the scheduler
# at the TOP of step() (before admission), on the serving-loop thread —
# so every injection is serialized with normal scheduling exactly like a
# real event would be. Kinds mirror production failure classes:
#
# - ``engine_raise``   — the decode dispatch blows up: the raise
#   propagates out of step() into ServingLoop's fail_all path (every
#   in-flight request fails loudly, the loop survives);
# - ``slow_decode``    — one decode takes ``seconds`` longer (a
#   stragglers/step-time-spike drill for timeouts and deadlines);
# - ``kv_exhaust``     — the page pool drains to ``leave`` free pages
#   for ``hold_steps`` steps: admission must shed (503, never 500) and
#   growth must preempt, then the pool recovers;
# - ``client_abandon`` — the newest in-flight request's client vanishes:
#   the cancellation path must free its pages (the zombie-leak drill).
SERVE_FAULT_KINDS: Dict[str, Callable] = {}


def register_serve_fault(kind: str):
    def wrap(fn: Callable) -> Callable:
        SERVE_FAULT_KINDS[kind] = fn
        return fn

    return wrap


@register_serve_fault("engine_raise")
def _serve_engine_raise(injector, scheduler, **params):
    raise RuntimeError("injected serve fault: engine_raise")


@register_serve_fault("slow_decode")
def _serve_slow_decode(injector, scheduler, seconds: float = 0.05, **params):
    """Make the NEXT engine.decode call sleep ``seconds`` first; the
    wrapper restores the original before delegating, so exactly one
    decode is slow."""
    engine = scheduler.engine
    orig = engine.decode

    def slow_once(*a, **k):
        engine.decode = orig
        time.sleep(float(seconds))
        return orig(*a, **k)

    engine.decode = slow_once


@register_serve_fault("kv_exhaust")
def _serve_kv_exhaust(
    injector, scheduler, leave: int = 0, hold_steps: int = 5, **params
):
    """Allocate the pool down to ``leave`` free pages and hold them for
    ``hold_steps`` scheduler steps (the injector frees them)."""
    n = scheduler.allocator.available - int(leave)
    if n <= 0:
        return
    pages = scheduler.allocator.alloc(n)
    if pages:
        injector.hold_pages(scheduler, pages, hold_steps=int(hold_steps))


@register_serve_fault("client_abandon")
def _serve_client_abandon(injector, scheduler, **params):
    """Cancel the newest in-flight request as an abandoning client
    would (handler gone, nobody waiting) — the scheduler must free its
    pages via the cancellation path."""
    active = [r for r in scheduler.slots if r is not None]
    if active:
        victim = max(active, key=lambda r: r.admit_seq)
    elif scheduler.waiting:
        victim = scheduler.waiting[-1]
    else:
        return
    scheduler.cancel(victim, reason="abandoned")


class ServeFaultSpec:
    """One scheduled serve fault: ``kind`` at 0-based scheduler ``step``
    (counted over step() calls of the current scheduler); fires once."""

    def __init__(self, kind: str, step_idx: int, **params: Any) -> None:
        if kind not in SERVE_FAULT_KINDS:
            raise ValueError(
                f"unknown serve fault kind {kind!r}; registered: "
                f"{sorted(SERVE_FAULT_KINDS)}"
            )
        self.kind = kind
        self.step = int(step_idx)
        if self.step < 0:
            raise ValueError(f"fault step must be >= 0, got {self.step}")
        self.params = dict(params)
        self.fired = False

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        extra = "".join(f", {k}={v!r}" for k, v in self.params.items())
        return f"ServeFaultSpec({self.kind!r}@{self.step}{extra})"


def parse_serve_fault_specs(cfg: Any) -> List["ServeFaultSpec"]:
    """Normalize a serve chaos config (``ACCO_SERVE_CHAOS`` env /
    ``fault_injection:`` serve-yaml key / ``--chaos`` flags) into
    ServeFaultSpecs. Same grammar as the train injector: a list of
    dicts (``{kind: kv_exhaust, step: 4, hold_steps: 8}``), a single
    dict, or compact comma-separable strings (``"client_abandon@5"``).
    Unknown kinds raise at parse time — a drill that silently injects
    nothing would report a robustness the stack does not have."""
    if cfg is None or cfg == "" or cfg is False:
        return []
    if isinstance(cfg, str):
        cfg = [s for s in cfg.split(",") if s.strip()]
    if isinstance(cfg, dict):
        cfg = [cfg]
    specs: List[ServeFaultSpec] = []
    for entry in cfg:
        if isinstance(entry, str):
            kind, sep, step = entry.strip().partition("@")
            if not sep:
                raise ValueError(
                    f"serve fault string {entry!r} must be 'kind@step'"
                )
            specs.append(ServeFaultSpec(kind.strip(), int(step)))
        elif isinstance(entry, dict):
            entry = dict(entry)
            kind = entry.pop("kind", None)
            step = entry.pop("step", None)
            if kind is None or step is None:
                raise ValueError(
                    f"serve fault dict {entry!r} needs 'kind' and 'step'"
                )
            specs.append(ServeFaultSpec(str(kind), int(step), **entry))
        else:
            raise ValueError(f"unsupported serve fault spec: {entry!r}")
    return specs


class ServeFaultInjector:
    """Fire scheduled serve faults into the continuous-batching loop.

    Wire via ``ContinuousBatchingScheduler(fault_injector=...)``; the
    scheduler calls :meth:`before_step` with its 0-based step index at
    the top of every step(). Matching un-fired specs fire (counted in
    ``serve_faults_injected_total``); pages held by ``kv_exhaust`` are
    released here once their hold expires.
    """

    ENV_VAR = "ACCO_SERVE_CHAOS"

    def __init__(
        self,
        specs: List[ServeFaultSpec],
        log: Optional[logging.Logger] = None,
    ) -> None:
        self.specs = list(specs)
        self.log = log or _module_log
        self._holds: List[Tuple[Any, list, int]] = []  # (sched, pages, release)

    @classmethod
    def from_config(
        cls, cfg: Any, log: Optional[logging.Logger] = None
    ) -> Optional["ServeFaultInjector"]:
        specs = parse_serve_fault_specs(cfg)
        return cls(specs, log=log) if specs else None

    @classmethod
    def from_env(
        cls, log: Optional[logging.Logger] = None
    ) -> Optional["ServeFaultInjector"]:
        return cls.from_config(os.environ.get(cls.ENV_VAR), log=log)

    @property
    def pending(self) -> bool:
        return any(not s.fired for s in self.specs) or bool(self._holds)

    @property
    def fired(self) -> List[ServeFaultSpec]:
        return [s for s in self.specs if s.fired]

    def hold_pages(self, scheduler, pages: list, hold_steps: int) -> None:
        release = scheduler._step_idx + max(1, int(hold_steps))
        self._holds.append((scheduler, pages, release))
        self.log.warning(
            "kv_exhaust holding %d pages until scheduler step %d",
            len(pages), release,
        )

    def before_step(self, scheduler, step_idx: int) -> None:
        from acco_tpu.telemetry import metrics

        for hold in self._holds[:]:
            sched, pages, release = hold
            if sched is scheduler and step_idx >= release:
                sched.allocator.free(pages)
                self._holds.remove(hold)
                self.log.warning(
                    "kv_exhaust released %d pages at step %d",
                    len(pages), step_idx,
                )
        for spec in self.specs:
            if spec.fired or spec.step != int(step_idx):
                continue
            # mark fired BEFORE injecting: engine_raise propagates out
            # of step() by design and must not re-fire forever
            spec.fired = True
            metrics.emit("serve_faults_injected_total", 1)
            self.log.warning(
                "serve fault injection: %s at step %d %s",
                spec.kind, step_idx, spec.params or "",
            )
            SERVE_FAULT_KINDS[spec.kind](self, scheduler, **spec.params)
