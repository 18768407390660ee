"""Orbax-backed checkpointing with actual resume.

The reference only *saves*: rank 0 writes ``model.state_dict()`` every
1800 s and at the end of training; optimizer-state saving is commented out
and there is no restore path (`/root/reference/trainer_decoupled.py:559-574,
592-598`, SURVEY.md §5 'checkpoint / resume'). This module is the designed
improvement: the **full sharded train state** (params + fp32 optimizer
shard + Adam moments + ACCO round buffers) plus a JSON meta blob (host-side
counters: grads done, wall-clock, data epoch) are written atomically per
step directory, and restore rebuilds every leaf on its original
``NamedSharding`` — so a resumed run continues bit-where-it-left-off on any
mesh of the same shape.

Layout::

    <ckpt_dir>/step_<n>/state/...   (Orbax StandardCheckpointer tree)
    <ckpt_dir>/step_<n>/meta.json

Completeness contract (crash recovery, `acco_tpu/resilience`):
``meta.json`` is written *last* and *atomically* (tmp + rename), so its
presence marks the checkpoint committed; it also carries a
``state_manifest`` of every state file's size, so a torn write that
truncates a file after commit (or a meta.json surviving a lost state
dir) is detectable without attempting a full Orbax restore.
``latest_checkpoint`` walks the step dirs newest-first and returns the
newest checkpoint that passes validation, skipping and reporting
incomplete or corrupt ones — a crash mid-save can cost at most the
in-flight checkpoint, never the run.
"""

from __future__ import annotations

import json
import logging
import os
import re
from typing import Any, Iterator, Optional

import jax

_STEP_RE = re.compile(r"^step_(\d+)$")
MANIFEST_KEY = "state_manifest"

_module_log = logging.getLogger(__name__)


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def state_manifest(path: str) -> dict:
    """Relative path -> byte size for every file under a ``step_*`` dir
    (``meta.json`` and its tmp excluded: the manifest is computed at
    commit time, before meta.json exists)."""
    manifest = {}
    for root, _, files in os.walk(path):
        for name in files:
            full = os.path.join(root, name)
            rel = os.path.relpath(full, path)
            if rel in ("meta.json", "meta.json.tmp"):
                continue
            manifest[rel] = os.path.getsize(full)
    return manifest


def finalize_meta(path: str, meta: dict) -> None:
    """Commit a ``step_*`` dir: write ``meta.json`` (with the state
    manifest folded in) atomically, LAST — its appearance is the commit
    point, and the tmp+rename means no reader can ever see a torn one."""
    meta = dict(meta)
    meta[MANIFEST_KEY] = state_manifest(path)
    tmp = os.path.join(path, "meta.json.tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    os.replace(tmp, os.path.join(path, "meta.json"))


def save_checkpoint(
    ckpt_dir: str, step: int, state: Any, meta: dict, write_meta: bool = True
) -> str:
    """Write ``state`` (any pytree of jax.Arrays) + ``meta`` under
    ``ckpt_dir/step_<step>``; returns that path. Fully synchronous — the
    overlapped path is ``acco_tpu.resilience.CheckpointManager``.

    Multi-process: every process must call this (the Orbax save of a
    multi-host sharded array is a collective); pass ``write_meta=rank==0``
    so only one process writes the side file. meta.json is written last —
    its presence marks the checkpoint complete (see latest_checkpoint).
    """
    path = os.path.join(os.path.abspath(ckpt_dir), f"step_{step}")
    os.makedirs(path, exist_ok=True)
    ckptr = _checkpointer()
    state_path = os.path.join(path, "state")
    ckptr.save(state_path, state, force=True)
    ckptr.wait_until_finished()
    if write_meta:
        finalize_meta(path, meta)
    return path


def checkpoint_candidates(ckpt_dir: str) -> Iterator[str]:
    """``step_*`` dirs under ``ckpt_dir``, newest step first, complete or
    not — validity is the caller's question (validate_checkpoint)."""
    ckpt_dir = os.path.abspath(ckpt_dir)  # Orbax rejects relative paths
    if not os.path.isdir(ckpt_dir):
        return
    steps = []
    for name in os.listdir(ckpt_dir):
        m = _STEP_RE.match(name)
        if m:
            steps.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    for _, path in sorted(steps, reverse=True):
        yield path


def validate_checkpoint(path: str) -> Optional[str]:
    """None if ``path`` is a committed, intact ``step_*`` dir; otherwise a
    human-readable reason it must be skipped.

    Cheap on purpose (stat calls, no Orbax restore): the failure modes it
    catches are the ones a killed/preempted saver actually leaves behind —
    no meta.json (died before commit), unparseable meta.json (legacy torn
    write, pre-atomic-rename), missing state dir, and manifest size
    mismatches (truncated/partial state files). Checkpoints from before
    the manifest was recorded validate on the meta.json + state-dir
    checks alone.
    """
    meta_path = os.path.join(path, "meta.json")
    if not os.path.exists(meta_path):
        return "incomplete: no meta.json (save died before commit)"
    try:
        with open(meta_path) as f:
            meta = json.load(f)
        if not isinstance(meta, dict):
            raise ValueError(f"expected a dict, got {type(meta).__name__}")
    except Exception as exc:
        return f"corrupt meta.json ({exc})"
    if not os.path.isdir(os.path.join(path, "state")):
        return "state dir missing"
    manifest = meta.get(MANIFEST_KEY)
    if not isinstance(manifest, dict):
        return None  # pre-manifest checkpoint: complete as far as we can tell
    if not manifest:
        # A manifest IS recorded but names zero state files: the commit
        # raced an empty/teared state dir. Without this check the
        # per-file loop below is vacuous and a contentless checkpoint
        # validates "complete".
        return "state manifest empty (commit recorded no state files)"
    for rel, size in manifest.items():
        full = os.path.join(path, rel)
        try:
            actual = os.path.getsize(full)
        except OSError:
            return f"state file missing: {rel}"
        if actual != int(size):
            return f"state file truncated: {rel} ({actual} != {size} bytes)"
    return None


def read_meta(path: str) -> dict:
    """The ``meta.json`` of a ``step_*`` dir (validated ones have it)."""
    with open(os.path.join(os.path.abspath(path), "meta.json")) as f:
        return json.load(f)


def latest_checkpoint(ckpt_dir: str, log=None) -> Optional[str]:
    """Newest *valid* ``step_*`` dir under ``ckpt_dir`` (fallback chain:
    incomplete and corrupt/truncated dirs are skipped and reported, and
    the next-newest complete step wins), or None."""
    log = log or _module_log
    for path in checkpoint_candidates(ckpt_dir):
        reason = validate_checkpoint(path)
        if reason is None:
            return path
        log.warning("skipping checkpoint %s: %s", path, reason)
    return None


def abstract_from_rules(state_template: Any, mesh, table) -> Any:
    """Rule-generated restore target: the tree of ``state_template``
    (arrays or avals — anything with shape/dtype) with every leaf's
    ``NamedSharding`` produced by matching its path against the sharding
    rule ``table`` (e.g. ``step.rule_table()``). This is the
    checkpoint-side face of :mod:`acco_tpu.sharding`: restore shardings
    come from the same rules that placed the state at save time, so a
    checkpoint written before the rule engine existed restores
    bit-exactly through the table (regression-tested in
    tests/test_resilience.py)."""
    from acco_tpu.sharding import sharded_abstract

    return sharded_abstract(table, state_template, mesh)


def restore_checkpoint(path: str, abstract_state: Any) -> tuple[Any, dict]:
    """Restore ``(state, meta)`` from a ``step_*`` dir.

    ``abstract_state`` fixes structure/shape/dtype/sharding: pass either a
    live template state (e.g. ``step.init_state(params)``), a matching
    tree of ``jax.ShapeDtypeStruct`` with shardings, or the output of
    :func:`abstract_from_rules` (shardings generated from a sharding
    rule table).

    Two legacy-layout fallbacks keep old checkpoints restorable:

    - checkpoints from before the training-health watchdog lack the
      ``health`` leaf on ``AccoState``/``DDPState``; they restore with
      fresh (all-healthy) counters — the counters are run-scoped
      statistics, so nothing real is lost;
    - checkpoints from before the accumulator-buffer removal carry two
      extra ``AccoState`` leaves (``grad_accum``/``count_local``); those
      restore through a fallback that drops the redundant buffers (their
      contents are derivable from ``pending_*`` + parity).
    """
    # Orbax rejects relative paths outright ("Checkpoint path should be
    # absolute"), and that rejection used to be masked by the legacy-
    # layout retry below into a baffling structure-mismatch error when a
    # user passed a relative resume_from. Normalize at the boundary,
    # like save_checkpoint always did.
    path = os.path.abspath(path)
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if hasattr(x, "sharding")
        else x,
        abstract_state,
    )
    ckptr = _checkpointer()
    state_path = os.path.join(path, "state")
    try:
        state = ckptr.restore(state_path, target)
    except Exception as first_exc:
        # The legacy retries are only plausible when there IS a saved
        # state on disk — a missing/renamed dir must surface as itself
        # (not as a confusing legacy-structure error). Deliberately not
        # gated on the exception message: Orbax's mismatch wording is
        # version-dependent, and matching it would either false-positive
        # on paths containing 'tree' or silently break legacy restore on
        # an Orbax upgrade. If every retry fails, chain so the original
        # cause is never lost. Order: newest legacy layout first
        # (pre-watchdog, no health leaf), then the oldest (7-leaf
        # accumulator AccoState — which also predates health).
        if not os.path.isdir(state_path):
            raise
        try:
            state = _restore_pre_watchdog(ckptr, state_path, target)
        except Exception as pre_watchdog_exc:
            # Chain through the middle attempt too: a pre-watchdog
            # restore that failed for a REAL reason (sharding/dtype
            # mismatch, I/O error) is often the diagnostic one, and
            # `from first_exc` alone would drop it.
            pre_watchdog_exc.__cause__ = first_exc
            try:
                state = _restore_legacy_acco(ckptr, state_path, target)
            except Exception as legacy_exc:
                raise legacy_exc from pre_watchdog_exc
    return state, read_meta(path)


def _fresh_health(template: Any) -> Any:
    """Fresh (all-healthy) watchdog counters laid out per the target's
    ``health`` template — the fill for checkpoints that predate the
    health leaf (the counters are run-scoped statistics; starting a
    resumed run healthy is the correct semantics)."""
    import jax

    from acco_tpu.parallel.common import init_health

    return jax.tree.map(
        lambda init, tmpl: jax.device_put(init, tmpl.sharding)
        if hasattr(tmpl, "sharding")
        else init,
        init_health(),
        template,
    )


def _restore_pre_watchdog(ckptr, state_path: str, target: Any) -> Any:
    """Restore a pre-watchdog checkpoint (AccoState/DDPState without the
    ``health`` leaf) into the current layout, filling fresh health
    counters; re-raises for any other structure mismatch."""
    from typing import NamedTuple

    from acco_tpu.parallel.acco import AccoState
    from acco_tpu.parallel.ddp import DDPState

    if isinstance(target, AccoState):

        class PreWatchdogAccoState(NamedTuple):
            flat_params: Any
            pending_grads: Any
            pending_count: Any
            zero1: Any
            round_idx: Any

        legacy = PreWatchdogAccoState(
            flat_params=target.flat_params,
            pending_grads=target.pending_grads,
            pending_count=target.pending_count,
            zero1=target.zero1,
            round_idx=target.round_idx,
        )
        restored = ckptr.restore(state_path, legacy)
        return AccoState(
            *restored, health=_fresh_health(target.health)
        )
    if isinstance(target, DDPState):

        class PreWatchdogDDPState(NamedTuple):
            flat_params: Any
            zero1: Any

        legacy = PreWatchdogDDPState(
            flat_params=target.flat_params, zero1=target.zero1
        )
        restored = ckptr.restore(state_path, legacy)
        return DDPState(*restored, health=_fresh_health(target.health))
    return ckptr.restore(state_path, target)  # re-raise the real error


def _restore_legacy_acco(ckptr, state_path: str, target: Any) -> Any:
    """Restore a pre-refactor 7-leaf AccoState layout (which also
    predates the health leaf) into the current one; re-raises for any
    other structure mismatch."""
    from acco_tpu.parallel.acco import AccoState

    if not isinstance(target, AccoState):
        return ckptr.restore(state_path, target)  # re-raise the real error
    from typing import NamedTuple

    class LegacyAccoState(NamedTuple):
        flat_params: Any
        grad_accum: Any
        count_local: Any
        pending_grads: Any
        pending_count: Any
        zero1: Any
        round_idx: Any

    legacy = LegacyAccoState(
        flat_params=target.flat_params,
        grad_accum=target.pending_grads,
        count_local=target.pending_count,
        pending_grads=target.pending_grads,
        pending_count=target.pending_count,
        zero1=target.zero1,
        round_idx=target.round_idx,
    )
    restored = ckptr.restore(state_path, legacy)
    return AccoState(
        flat_params=restored.flat_params,
        pending_grads=restored.pending_grads,
        pending_count=restored.pending_count,
        zero1=restored.zero1,
        round_idx=restored.round_idx,
        health=_fresh_health(target.health),
    )


# -- serving-side loading (acco_tpu/serve, perplexity_eval) -----------------


def resolve_serving_checkpoint(path: str, log=None) -> str:
    """Resolve ``path`` to a usable ``step_*`` dir for inference.

    Accepts either a specific ``step_*`` dir (validated, hard error if
    unusable — the user named it explicitly) or a checkpoint root, which
    goes through the :func:`latest_checkpoint` fallback chain (newest
    complete step wins, torn saves skipped and reported).
    """
    log = log or _module_log
    path = os.path.abspath(os.path.expanduser(path))
    if _STEP_RE.match(os.path.basename(path)):
        reason = validate_checkpoint(path)
        if reason is not None:
            raise FileNotFoundError(f"checkpoint {path} unusable: {reason}")
        return path
    found = latest_checkpoint(path, log=log)
    if found is None:
        raise FileNotFoundError(
            f"no valid step_* checkpoint under {path} (is it a checkpoint "
            "dir, or did every save die before commit?)"
        )
    return found


def _find_leaf(tree: Any, name: str):
    """Depth-first search for a dict key in a raw-restored Orbax tree
    (NamedTuple states come back as nested dicts keyed by field name)."""
    if isinstance(tree, dict):
        if name in tree:
            return tree[name]
        for value in tree.values():
            hit = _find_leaf(value, name)
            if hit is not None:
                return hit
    return None


def _to_row_major(step_dir: str, flat, template):
    """A ``step_*`` dir's ``flat_params`` leaf in ``ravel_pytree`` order."""
    from acco_tpu.parallel.flat_layout import (
        LAYOUT_META_KEY,
        ROW_MAJOR_TAG,
        FlatLayout,
    )

    tag = read_meta(step_dir).get(LAYOUT_META_KEY, ROW_MAJOR_TAG)
    if tag == ROW_MAJOR_TAG:
        return flat
    if template is None:
        raise ValueError(
            f"checkpoint {step_dir} holds its flat vector in layout {tag!r}: "
            "reading it back needs the model's parameter tree (template=)"
        )
    layout = FlatLayout(template)
    if layout.tag != tag:
        raise ValueError(
            f"checkpoint {step_dir} holds its flat vector in layout {tag!r}; "
            f"this build reads {layout.tag!r} and {ROW_MAJOR_TAG!r}"
        )
    if flat.size < layout.n_flat:
        raise ValueError(
            f"checkpoint {step_dir} holds {flat.size} elements but the model's "
            f"layout needs {layout.n_flat} — wrong model config for this checkpoint?"
        )
    return layout.to_row_major(flat[: layout.n_flat])


def load_flat_params(step_dir: str, n_params: int, log=None, template=None):
    """Portable fp32 flat parameter vector from a ``step_*`` dir, in
    ``ravel_pytree`` order.

    Final saves export ``params.npz`` (rank 0, ``flat_params`` key) — the
    cheap path: a plain numpy load, no Orbax, no train-state template.
    Periodic saves don't export it, so the fallback raw-restores the
    Orbax state tree WITHOUT a template (serving has no optimizer/round
    buffers to describe) and digs out the ``flat_params`` leaf. Either
    way the vector may carry ZeRO alignment padding past ``n_params``;
    the caller's model-init template defines the real size, so trim.
    ``params.npz`` is always in ``ravel_pytree`` order; the Orbax state is
    in the order its ``meta.json`` names (parallel/flat_layout.py), and is
    brought to ``ravel_pytree`` order through ``template``, the model's
    parameter tree (arrays or avals).
    """
    import numpy as np

    log = log or _module_log
    npz_path = os.path.join(step_dir, "params.npz")
    if os.path.exists(npz_path):
        flat = np.load(npz_path)["flat_params"]
        source = "params.npz"
    else:
        ckptr = _checkpointer()
        restored = ckptr.restore(os.path.join(step_dir, "state"))
        flat = _find_leaf(restored, "flat_params")
        if flat is None:
            raise ValueError(
                f"no flat_params leaf in {step_dir}/state — not a "
                "checkpoint this build can serve from"
            )
        source = "orbax state (no params.npz — periodic save)"
        flat = _to_row_major(step_dir, np.asarray(flat).reshape(-1), template)
    flat = np.asarray(flat, dtype=np.float32).reshape(-1)
    if flat.size < n_params:
        raise ValueError(
            f"checkpoint {step_dir} holds {flat.size} params but the model "
            f"needs {n_params} — wrong model config for this checkpoint?"
        )
    if flat.size > n_params:
        log.info(
            "trimming %d padding params (ZeRO alignment) from %s",
            flat.size - n_params, source,
        )
        flat = flat[:n_params]
    log.info("loaded %d params from %s (%s)", flat.size, step_dir, source)
    return flat
