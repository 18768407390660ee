"""The order of elements inside the flat parameter vector.

The round programs differentiate the loss with respect to ONE flat vector
(parallel/common.make_flat_loss_fn) and ZeRO-1 shards that vector
(parallel/zero1.py). What a leaf costs to take out of it, and its gradient
to put back, is decided by the order of its elements in the vector.

On the TPU a 1-D array lives in tiles of 1024 elements and a matrix in tiles
of 8 rows x 128 columns, row-major inside the tile. A row-major slab of the
vector is therefore NOT the matrix's memory: reshaping one into the other is
a pass over the leaf, forward and backward (12 B a parameter a round with
the slice: PERF.md, PR 28). Stored tile by tile, in the order
(leading dimensions, R/8, C/128, 8, 128), the slab IS the matrix's memory
and the compiler sees ``reshape -> transpose -> reshape`` as a bitcast
(tests/test_flat_layout_aot.py holds the compiled program to it).

The rule reads the leaf's shape and nothing else: a leaf with at least two
dimensions and a last dimension that is a multiple of 128 is stored in tile
order, its rows padded up to a multiple of 8; every other leaf (1-D, or of
an odd width) is stored row-major, after the tiled slabs, so that every slab
starts on a 1-D tile. There is no size threshold: the compiled programs
decided it. Left row-major, a model's ``[L, h]`` biases and norms made XLA
view the WHOLE vector as ``[n / h, h]`` to cut them out of it (their widths
divide the vector's length): a pass over every parameter, 3.8 ms a round in
OLMoE (PERF.md, PR 26). Tiled, they cost their padding (``[4, h]`` takes the
room of ``[8, h]``: under 0.03% of any benchmarked vector).

The padding holds zeros and stays zero: its gradient is the zero a slice's
transpose writes, and AdamW with decoupled decay maps (p, g, mu, nu) = 0 to
0. The CPU runs the same order (there the transpose is a real one), so a
checkpoint means one thing everywhere; ``tag`` names the order in a
checkpoint's ``meta.json``.

Row-major order (``jax.flatten_util.ravel_pytree``'s) survives in two
places only: ``from_row_major`` reads checkpoints written before the tag
existed, ``to_row_major`` writes the portable ``params.npz`` that serve.py
and perplexity_eval.py read through ``ravel_pytree``.

tp / pp have flat layouts of their own (parallel/tp.py TpLayout,
ComposedLayout): row-major per shard, untouched by this module.
"""

from __future__ import annotations

import dataclasses
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

# the order of a vector no FlatLayout wrote: ravel_pytree's, TpLayout's
ROW_MAJOR_TAG = "row-major"
# meta.json's key for the order a checkpoint's flat vectors are in
LAYOUT_META_KEY = "flat_layout"

TILE_ROWS, TILE_COLS = 8, 128


@dataclasses.dataclass(frozen=True)
class _Slab:
    shape: tuple  # the leaf's
    offset: int  # where the slab starts in the vector
    size: int  # elements it takes there, row padding included
    # (leading dimensions, rows, columns) the leaf is tiled as; None: row-major
    matrix: tuple | None

    @property
    def padded_rows(self) -> int:
        return _round_up(self.matrix[-2], TILE_ROWS)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _xp(x):
    return np if isinstance(x, np.ndarray) else jnp


def _matrix_view(shape: tuple) -> tuple | None:
    """The (leading dimensions, rows, columns) a leaf is tiled as, or None
    for a leaf that stays row-major. A second-to-last dimension shorter
    than a tile (GPT-Neo's ``w_qkv`` is ``[L, D, 3, D]``, used as
    ``[D, 3 D]``) folds into the columns."""
    if len(shape) < 2 or shape[-1] % TILE_COLS:
        return None
    while len(shape) > 2 and shape[-2] < TILE_ROWS:
        shape = (*shape[:-2], shape[-2] * shape[-1])
    return shape


class FlatLayout:
    """``ravel`` / ``unravel`` between a parameter tree and its flat vector,
    from the tree's shapes alone (arrays, tracers or ShapeDtypeStructs).
    Both take numpy arrays (on the host) or jax arrays (traced or not) and
    keep the dtype they are given.

    The vector holds the tile-ordered slabs first, in the tree's order, and
    the row-major leaves after them as one tail, in the tree's order too."""

    tag = f"tile{TILE_ROWS}x{TILE_COLS}"  # what a checkpoint's meta.json calls this order

    def __init__(self, tree):
        leaves, self.treedef = jax.tree.flatten(tree)
        shapes = [tuple(int(d) for d in leaf.shape) for leaf in leaves]
        views = [_matrix_view(shape) for shape in shapes]
        slabs: list = [None] * len(shapes)
        cursor = 0
        # tiled slabs first (whole tiles each, so each starts on one), then the tail
        for i in sorted(range(len(shapes)), key=lambda i: views[i] is None):
            if views[i] is None:
                size = math.prod(shapes[i])
            else:
                *lead, rows, cols = views[i]
                size = math.prod(lead) * _round_up(rows, TILE_ROWS) * cols
            slabs[i] = _Slab(shapes[i], cursor, size, views[i])
            cursor += size
        self.slabs = tuple(slabs)
        self.n_flat = cursor
        self.n_row_major = sum(math.prod(shape) for shape in shapes)
        self._unravel_with_transpose = jax.custom_vjp(self._unravel)
        self._unravel_with_transpose.defvjp(
            lambda flat: (self._unravel(flat), None),
            lambda _, cotangents: (self.ravel(cotangents),),
        )

    @property
    def bitcast_share(self) -> float:
        """Share of the vector's elements that sit in tile-ordered slabs:
        what unpack takes out, and its transpose puts back, without a pass."""
        tiled = sum(s.size for s in self.slabs if s.matrix is not None)
        return tiled / max(self.n_flat, 1)

    # -- one leaf ---------------------------------------------------------

    @staticmethod
    def _pack(slab: _Slab, x):
        xp = _xp(x)
        if slab.matrix is None:
            return xp.reshape(x, (-1,))
        *lead, rows, cols = slab.matrix
        x = xp.reshape(x, slab.matrix)
        x = xp.pad(x, [(0, 0)] * len(lead) + [(0, slab.padded_rows - rows), (0, 0)])
        x = xp.reshape(
            x, (*lead, slab.padded_rows // TILE_ROWS, TILE_ROWS, cols // TILE_COLS, TILE_COLS)
        )
        return xp.reshape(xp.swapaxes(x, -3, -2), (-1,))

    @staticmethod
    def _unpack(slab: _Slab, piece):
        xp = _xp(piece)
        if slab.matrix is None:
            return xp.reshape(piece, slab.shape)
        *lead, rows, cols = slab.matrix
        x = xp.reshape(
            piece,
            (*lead, slab.padded_rows // TILE_ROWS, cols // TILE_COLS, TILE_ROWS, TILE_COLS),
        )
        x = xp.reshape(xp.swapaxes(x, -3, -2), (*lead, slab.padded_rows, cols))
        return xp.reshape(x[..., :rows, :], slab.shape)

    # -- the tree ---------------------------------------------------------

    def ravel(self, tree):
        """Tree -> ``[n_flat]`` vector; the padding is zeros."""
        leaves = self.treedef.flatten_up_to(tree)
        got = [tuple(x.shape) for x in leaves]
        if got != [s.shape for s in self.slabs]:
            raise ValueError(
                f"the tree's leaves {got} are not this layout's "
                f"{[s.shape for s in self.slabs]}"
            )
        packed = sorted(zip(self.slabs, leaves), key=lambda pair: pair[0].offset)
        return _xp(leaves[0]).concatenate([self._pack(s, x) for s, x in packed])

    def unravel(self, flat):
        """``[n_flat]`` vector -> tree, every leaf in the vector's dtype.

        Its transpose is ``ravel`` of the leaves' cotangents, said so to
        JAX: transposed slice by slice it is a sum of ``pad`` s, one a leaf,
        which XLA fuses into whatever first reads the gradient and evaluates
        in full for every element (the guard's passes ran at 200 GB/s for
        660: my chip runs, PR 28)."""
        if flat.shape != (self.n_flat,):
            raise ValueError(
                f"expected a [{self.n_flat}] vector in layout {self.tag!r}, got {flat.shape}"
            )
        if isinstance(flat, np.ndarray):
            return self._unravel(flat)
        return self._unravel_with_transpose(flat)

    def _unravel(self, flat):
        return self.treedef.unflatten(
            [self._unpack(s, flat[s.offset : s.offset + s.size]) for s in self.slabs]
        )

    # -- row-major order: old checkpoints in, the portable export out -------

    def to_row_major(self, flat):
        """This layout's vector -> ``ravel_pytree`` order (``[n_row_major]``)."""
        xp = _xp(flat)
        return xp.concatenate(
            [xp.reshape(leaf, (-1,)) for leaf in jax.tree.leaves(self.unravel(flat))]
        )

    def from_row_major(self, flat):
        """A ``ravel_pytree``-order vector -> this layout's."""
        if flat.shape != (self.n_row_major,):
            raise ValueError(
                f"expected a row-major [{self.n_row_major}] vector, got {flat.shape}"
            )
        xp = _xp(flat)
        leaves, cursor = [], 0
        for slab in self.slabs:
            n = math.prod(slab.shape)
            leaves.append(xp.reshape(flat[cursor : cursor + n], slab.shape))
            cursor += n
        return self.ravel(self.treedef.unflatten(leaves))


# -- checkpoints: which order a step_* directory holds -----------------------

# the train states' leaves that are flat vectors (AccoState, DDPState and
# their Zero1State / AdamWState), by field name
_FLAT_FIELDS = frozenset({"flat_params", "pending_grads", "params", "mu", "nu"})


def _is_flat_vector(path) -> bool:
    return getattr(path[-1], "name", None) in _FLAT_FIELDS


def flat_layout_tag(step) -> str:
    """The tag a step's checkpoints carry: its FlatLayout's, or row-major
    for a step under tp / pp (TpLayout's order, as it always was)."""
    return step.layout.tag if step.layout is not None else ROW_MAJOR_TAG


def restore_flat_state(path: str, state, step, log=None):
    """``restore_checkpoint(path, state)`` for a step's train state, in
    whatever order the ``step_*`` directory holds its flat vectors.

    ``meta.json`` names the order (``LAYOUT_META_KEY``); a directory from
    before the tag existed is in ``ravel_pytree`` order. Where the step now
    keeps a FlatLayout, such a directory is restored at ITS geometry and
    every flat vector converted once, leaf by leaf on the host; a tag that
    is neither the step's nor row-major is refused by name.
    """
    from acco_tpu.parallel.zero1 import ShardGeometry
    from acco_tpu.utils.checkpoint import read_meta, restore_checkpoint

    saved = read_meta(path).get(LAYOUT_META_KEY, ROW_MAJOR_TAG)
    want = flat_layout_tag(step)
    if saved == want:
        return restore_checkpoint(path, state)
    if saved != ROW_MAJOR_TAG:
        raise ValueError(
            f"checkpoint {path} holds its flat vectors in layout {saved!r}; "
            f"this build reads {want!r} and {ROW_MAJOR_TAG!r} (the order of "
            "checkpoints written before the layout was tagged)"
        )
    layout = step.layout
    old, new = ShardGeometry(layout.n_row_major, step.num_shards), step.geom

    def at_old_geometry(keys, leaf):
        if not _is_flat_vector(keys):
            return leaf
        k = leaf.shape[0] // new.padded_size
        return jax.ShapeDtypeStruct(
            (k * old.padded_size,), leaf.dtype, sharding=leaf.sharding
        )

    def convert(keys, saved_leaf, leaf):
        if not _is_flat_vector(keys):
            return saved_leaf
        if not saved_leaf.is_fully_addressable:
            raise NotImplementedError(
                f"checkpoint {path} is in {ROW_MAJOR_TAG} order and this run "
                "spans several processes: convert it in a single-process run "
                "of the same mesh (resume and save once), then resume here"
            )
        rows = np.asarray(saved_leaf).reshape(-1, old.padded_size)
        out = np.zeros((rows.shape[0], new.padded_size), rows.dtype)
        for row, dst in zip(rows, out):
            dst[: layout.n_flat] = layout.from_row_major(row[: layout.n_row_major])
        return jax.device_put(out.reshape(-1), leaf.sharding)

    template = jax.tree_util.tree_map_with_path(at_old_geometry, state)
    restored, meta = restore_checkpoint(path, template)
    state = jax.tree_util.tree_map_with_path(convert, restored, state)
    (log or logging.getLogger(__name__)).warning(
        "checkpoint %s is in %s order (written before the flat layout was "
        "tagged): converted its flat vectors to %s on the host, once; the "
        "next save carries the tag",
        path, ROW_MAJOR_TAG, want,
    )
    return state, meta
