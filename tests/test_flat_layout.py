"""The flat vector's order (acco_tpu/parallel/flat_layout.py), on the CPU.

A big leaf lives in the vector tile by tile, (R/8, C/128, 8, 128), so that on
the TPU taking it out is a bitcast (tests/test_flat_layout_aot.py holds the
compiled program to that). Here: the order is a bijection with inert padding,
the three schedules compute what they computed in ``ravel_pytree``'s order,
and a checkpoint says which order it holds.
"""

import contextlib
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from acco_tpu.configuration import config_from_dict
from acco_tpu.data.tokenizer import ByteTokenizer
from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel import acco as acco_mod, ddp as ddp_mod
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.flat_layout import (
    LAYOUT_META_KEY,
    ROW_MAJOR_TAG,
    FlatLayout,
    restore_flat_state,
)
from acco_tpu.parallel.mesh import make_mesh
from acco_tpu.trainer import DecoupledTrainer
from acco_tpu.utils.checkpoint import (
    latest_checkpoint,
    load_flat_params,
    read_meta,
    save_checkpoint,
)

# wte [517, 128] is tiled with its rows padded to 520, the [2, 128] norms with
# theirs padded to 8, the projections are tiled stacks and final_norm [128]
# stays row-major: both kinds of leaf and interior padding, at a size the CPU runs
CFG = LlamaConfig(
    vocab_size=517, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=2, num_kv_heads=2, max_position_embeddings=32,
)
WS, N_ACC, SEQ = 8, 1, 16
SCHEDULES = ["acco", "dpu", "ddp"]


class RowMajorLayout:
    """``ravel_pytree``'s order behind FlatLayout's interface: what the flat
    vector was before the tile order, built here so the tests can run the
    round programs in both orders."""

    tag = ROW_MAJOR_TAG
    bitcast_share = 0.0

    def __init__(self, tree):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = [tuple(leaf.shape) for leaf in leaves]
        self.n_flat = self.n_row_major = sum(int(np.prod(s)) for s in self.shapes)

    def ravel(self, tree):
        return jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(tree)])

    def unravel(self, flat):
        leaves, cursor = [], 0
        for shape in self.shapes:
            n = int(np.prod(shape))
            leaves.append(flat[cursor : cursor + n].reshape(shape))
            cursor += n
        return self.treedef.unflatten(leaves)

    def to_row_major(self, flat):
        return flat

    from_row_major = to_row_major


@pytest.fixture
def row_major(monkeypatch):
    """``with row_major():`` the step classes build their layout from
    RowMajorLayout."""

    @contextlib.contextmanager
    def on():
        with monkeypatch.context() as patch:
            patch.setattr(acco_mod, "FlatLayout", RowMajorLayout)
            patch.setattr(ddp_mod, "FlatLayout", RowMajorLayout)
            yield

    return on


# -- the order itself ----------------------------------------------------------

TREES = {
    # rows not a multiple of 8 (517 -> 520)
    "ragged_rows": {"w": (517, 128), "b": (128,)},
    # a last dimension that is not a multiple of 128 stays row-major, whatever its size
    "ragged_cols": {"w": (1024, 100), "v": (640, 128)},
    # 1-D leaves stay row-major whatever their size; few rows pad up to one tile
    "one_d": {"big": (65536,), "bias": (4, 256), "m": (512, 128)},
    # 3-D and 4-D stacks: the leading dimensions stay major, each matrix pads alone
    "stack_3d": {"layers": {"w": (3, 100, 256), "b": (3, 256)}, "head": (256, 384)},
    "stack_4d": {"experts": (2, 4, 72, 128), "norm": (2, 128)},
    # a short second-to-last dimension folds into the columns ([L, D, 3, D] -> [L, D, 3 D])
    "folded": {"w_qkv": (2, 128, 3, 128), "wo": (2, 128, 128)},
}


def _tree(name, dtype=np.float32):
    rng = np.random.default_rng(sorted(TREES).index(name))
    return jax.tree.map(
        lambda shape: rng.normal(size=shape).astype(dtype),
        TREES[name],
        is_leaf=lambda x: isinstance(x, tuple),
    )


@pytest.mark.parametrize("name", sorted(TREES))
def test_ravel_unravel_round_trip_and_row_major(name):
    tree = _tree(name)
    layout = FlatLayout(tree)
    flat = layout.ravel(tree)
    assert isinstance(flat, np.ndarray) and flat.shape == (layout.n_flat,)
    back = layout.unravel(flat)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    # the same on the device side, bit for bit
    np.testing.assert_array_equal(np.asarray(layout.ravel(jax.tree.map(jnp.asarray, tree))), flat)
    # every tiled slab starts on a 1-D tile and is whole tiles; padding is zero
    for slab in layout.slabs:
        if slab.matrix is not None:
            assert slab.offset % 1024 == 0 and slab.size % 1024 == 0
    real = layout.ravel(jax.tree.map(np.ones_like, tree)) != 0
    assert real.sum() == layout.n_row_major and not flat[~real].any()
    # ravel_pytree's order, both ways
    row_major = np.asarray(ravel_pytree(tree)[0])
    np.testing.assert_array_equal(layout.to_row_major(flat), row_major)
    np.testing.assert_array_equal(layout.from_row_major(row_major), flat)


@pytest.mark.parametrize("name", sorted(TREES))
def test_grad_through_unravel_is_ravel_of_the_leaves_grads(name):
    tree = jax.tree.map(jnp.asarray, _tree(name))
    weights = jax.tree.map(lambda x: jnp.cos(3.0 * x) + 2.0, tree)
    layout = FlatLayout(tree)

    def loss_of_tree(t):
        return sum(
            jnp.sum(jnp.sin(x) * w)
            for x, w in zip(jax.tree.leaves(t), jax.tree.leaves(weights))
        )

    flat = layout.ravel(tree)
    g_flat = jax.jit(jax.grad(lambda v: loss_of_tree(layout.unravel(v))))(flat)
    g_tree = jax.grad(loss_of_tree)(tree)
    np.testing.assert_array_equal(np.asarray(g_flat), np.asarray(layout.ravel(g_tree)))


def test_which_leaves_are_tiled():
    layout = FlatLayout(_tree("one_d"))
    tiled = {s.shape: (s.matrix, s.size) for s in layout.slabs}
    assert tiled == {
        (65536,): (None, 65536), (4, 256): ((4, 256), 8 * 256), (512, 128): ((512, 128), 65536),
    }
    ragged = {s.shape: s.matrix for s in FlatLayout(_tree("ragged_cols")).slabs}
    assert ragged == {(1024, 100): None, (640, 128): (640, 128)}
    folded = FlatLayout(_tree("folded"))
    assert {s.shape: s.matrix for s in folded.slabs} == {
        (2, 128, 3, 128): (2, 128, 384), (2, 128, 128): (2, 128, 128),
    }
    assert folded.bitcast_share == 1.0 and 0.0 < layout.bitcast_share < 1.0
    with pytest.raises(ValueError, match="expected a"):
        folded.unravel(np.zeros(3, np.float32))


# -- the round programs in both orders -------------------------------------------


def _batch(seed, n_acc=N_ACC):
    ids = jax.random.randint(
        jax.random.PRNGKey(seed), (n_acc, WS, SEQ), 0, CFG.vocab_size, dtype=jnp.int32
    )
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((n_acc, WS), jnp.float32),
    }


def _step(schedule):
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    kw = dict(
        weight_decay=0.1, beta1=0.9, beta2=0.95, param_dtype=jnp.float32,
        nan_guard=True,
    )
    sched = get_schedule("constant", 1e-3, 0, 1000)
    if schedule == "ddp":
        return DDPTrainStep(model, make_mesh(), sched, **kw)
    return AccoTrainStep(model, make_mesh(), sched, mode=schedule, **kw)


def _ten_rounds(schedule):
    step = _step(schedule)
    state = step.init_state(step.model.init(jax.random.PRNGKey(0)))
    if schedule == "ddp":
        advance = step.step_fn()
    else:
        state, _ = step.seed_fn()(state, _batch(99))
        advance = step.round_fn()
    for r in range(10):
        state, metrics = advance(state, _batch(r))
        assert not bool(metrics.skipped)
    return step, state


def _vectors(step, state):
    """Every flat vector of the state, by name, as [k, padded] host rows."""
    opt = state.zero1.opt
    named = {"flat_params": state.flat_params, "p": opt.params, "mu": opt.mu, "nu": opt.nu}
    if hasattr(state, "pending_grads"):
        named["pending_grads"] = state.pending_grads
    return {
        k: np.asarray(v).reshape(-1, step.geom.padded_size) for k, v in named.items()
    }


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_ten_rounds_equal_row_major_and_padding_stays_zero(eight_devices, row_major, schedule):
    step, state = _ten_rounds(schedule)
    layout = step.layout
    assert isinstance(layout, FlatLayout) and layout.n_flat > layout.n_row_major
    assert step.geom.n_params == layout.n_flat
    assert int(state.zero1.opt.count) == (5 if schedule == "acco" else 10)

    with row_major():
        rm_step, rm_state = _ten_rounds(schedule)
    assert isinstance(rm_step.layout, RowMajorLayout)

    real = layout.ravel(
        layout.treedef.unflatten([np.ones(s.shape, np.float32) for s in layout.slabs])
    ) != 0
    rm_rows = _vectors(rm_step, rm_state)
    for name, rows in _vectors(step, state).items():
        for row, rm_row in zip(rows, rm_rows[name]):
            # interior padding and the tail: exactly zero after ten guarded
            # rounds with weight decay
            assert not row[: layout.n_flat][~real].any(), name
            assert not row[layout.n_flat :].any(), name
            # leaf for leaf what the row-major order computes; the norms the
            # guard takes sum in another order, hence float32 rounding
            got = layout.unravel(row[: layout.n_flat])
            want = rm_step.layout.unravel(rm_row[: layout.n_row_major])
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(a, np.asarray(b), rtol=2e-5, atol=1e-7, err_msg=name)


# -- checkpoints -------------------------------------------------------------------


def _save(tmp_path, state, meta):
    return save_checkpoint(str(tmp_path), 7, state, dict(count_grad_tot=7, **meta))


@pytest.mark.parametrize("schedule", ["acco", "ddp"])
def test_checkpoint_orders(eight_devices, row_major, tmp_path, caplog, schedule):
    """A tagged save restores as it is; an untagged one (``ravel_pytree``
    order: every checkpoint before the tag) restores to equal parameters and
    moments, converted once and said so; an unknown tag is refused by name."""
    step, state = _ten_rounds(schedule)
    template = step.init_state(step.model.init(jax.random.PRNGKey(1)))

    path = _save(tmp_path / "tagged", state, {LAYOUT_META_KEY: step.layout.tag})
    restored, meta = restore_flat_state(path, template, step)
    assert meta[LAYOUT_META_KEY] == step.layout.tag
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with row_major():
        rm_step, rm_state = _ten_rounds(schedule)
    path = _save(tmp_path / "untagged", rm_state, {})
    with caplog.at_level(logging.WARNING):
        converted, _ = restore_flat_state(path, template, step)
    assert "converted its flat vectors" in caplog.text and ROW_MAJOR_TAG in caplog.text
    assert jax.tree.structure(converted) == jax.tree.structure(template)
    rm_rows = _vectors(rm_step, rm_state)
    for name, rows in _vectors(step, converted).items():
        assert rows.shape[1] == step.geom.padded_size
        for row, rm_row in zip(rows, rm_rows[name]):
            np.testing.assert_array_equal(
                step.layout.to_row_major(row[: step.layout.n_flat]),
                rm_row[: step.layout.n_row_major],
                err_msg=name,
            )
    for a, b in zip(jax.tree.leaves(converted), jax.tree.leaves(template)):
        assert a.sharding == b.sharding and a.dtype == b.dtype
    assert int(converted.zero1.opt.count) == int(rm_state.zero1.opt.count)
    # and the converted state trains on
    advance = step.step_fn() if schedule == "ddp" else step.round_fn()
    _, metrics = advance(converted, _batch(10))
    assert np.isfinite(float(metrics.loss)) and not bool(metrics.skipped)

    path = _save(tmp_path / "other", rm_state, {LAYOUT_META_KEY: "tile16x128-from1"})
    with pytest.raises(ValueError, match="tile16x128-from1"):
        restore_flat_state(path, template, step)


# -- the trainer: the export, and a checkpoint of the parent commit ----------------


def _docs(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"input_ids": rng.integers(0, 256, size=int(rng.integers(8, 24))).tolist()}
        for _ in range(n)
    ]


def _trainer(tmp_path, **over):
    base = dict(
        method_name="acco", batch_size=1, n_grad_accumulation=1, learning_rate=1e-3,
        weight_decay=0.1, adam_beta1=0.9, adam_beta2=0.95, nb_steps_tot=32,
        label_smoothing_factor=0.0, max_length=16, scheduler_name="constant",
        warmup=0, use_mixed_precision=False, n_warmup_steps=0, eval=False,
        eval_step=0, save=False, const_len_batch=True, checkpoint_every_s=10_000,
        run_name="t-layout",
    )
    base.update(over)
    return DecoupledTrainer(
        LlamaModel(CFG, param_dtype=jnp.float32), ByteTokenizer(), _docs(),
        _docs(16, seed=1), config_from_dict(base), seed=0, run_dir=str(tmp_path),
    )


def _ckpt_root(tmp_path):
    return os.path.join(str(tmp_path), "checkpoints", "t-layout")


def test_export_stays_in_ravel_pytree_order(eight_devices, tmp_path):
    """``params.npz`` written by a run in tile order loads through serve.py's
    and perplexity_eval.py's readers (``ravel_pytree`` of the model's init
    tree, unchanged) to the tree the state holds; the Orbax state, where a
    periodic save exported no npz, comes back the same through its tag."""
    trainer = _trainer(tmp_path, save=True)
    summary = trainer.train()
    assert summary["flat_bitcast_share"] == trainer.step_obj.layout.bitcast_share > 0.5
    step, state = trainer.step_obj, trainer.final_state
    held = step.layout.unravel(np.asarray(state.flat_params)[: step.layout.n_flat])

    path = latest_checkpoint(_ckpt_root(tmp_path))
    assert read_meta(path)[LAYOUT_META_KEY] == step.layout.tag
    template = trainer.model.init(jax.random.PRNGKey(0))
    flat_template, unravel = ravel_pytree(template)
    # perplexity_eval.py's reader
    loaded = np.load(os.path.join(path, "params.npz"))["flat_params"]
    assert loaded.size == flat_template.size
    for a, b in zip(jax.tree.leaves(unravel(loaded.astype(flat_template.dtype))), jax.tree.leaves(held)):
        np.testing.assert_array_equal(np.asarray(a), b)
    # serve.py's reader, from the npz and then from the Orbax state alone
    for has_npz in (True, False):
        if not has_npz:
            os.remove(os.path.join(path, "params.npz"))
        flat = load_flat_params(path, int(flat_template.size), template=template)
        for a, b in zip(jax.tree.leaves(unravel(jnp.asarray(flat))), jax.tree.leaves(held)):
            np.testing.assert_array_equal(np.asarray(a), b)
    with pytest.raises(ValueError, match="template"):
        load_flat_params(path, int(flat_template.size))


def test_parent_commits_checkpoint_resumes_to_the_same_loss(
    eight_devices, row_major, tmp_path, caplog
):
    """A ``step_*`` directory as the parent commit wrote it (``ravel_pytree``
    order, no tag in ``meta.json``) resumes under the tile order to the loss
    an uninterrupted run reaches at the same boundary."""
    whole = _trainer(tmp_path / "whole", nb_steps_tot=64).train()

    with row_major():
        _trainer(tmp_path / "resumed", save=True, nb_steps_tot=32).train()
    path = latest_checkpoint(_ckpt_root(tmp_path / "resumed"))
    meta_path = os.path.join(path, "meta.json")
    meta = read_meta(path)
    assert meta.pop(LAYOUT_META_KEY) == ROW_MAJOR_TAG  # the parent wrote no such key
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    with caplog.at_level(logging.WARNING):
        second = _trainer(
            tmp_path / "resumed", nb_steps_tot=64, resume_from=_ckpt_root(tmp_path / "resumed")
        )
        resumed = second.train()
    assert "converted its flat vectors" in caplog.text
    assert isinstance(second.step_obj.layout, FlatLayout)
    assert resumed["count_grad_tot"] == whole["count_grad_tot"]
    np.testing.assert_allclose(resumed["final_loss"], whole["final_loss"], rtol=1e-5)
