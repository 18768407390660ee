"""Ring reduce-scatter / all-gather built from ``lax.ppermute``.

Why these exist: on the target libtpu,
``lax.psum_scatter`` and ``lax.all_gather`` on the big flat ZeRO-1 vector
lower to *blocking* all-reduce ops (pincer emitter) that the latency-hiding
scheduler cannot move — the compiled ACCO round ran compute, then comm,
serially (`tools/overlap_hlo.py` verdict on the stock path: NOT PROVEN,
2 blocking collectives). ``lax.ppermute``, by contrast, compiles to async
``collective-permute-start/done`` pairs, and the scheduler demonstrably
places independent compute inside the in-flight windows. Expressing the
ZeRO-1 collectives as ppermute rings therefore:

- makes every hop asynchronous and schedulable behind the gradient
  branch's fwd/bwd (the overlap ACCO exists for — the role of the
  reference's com_thread/com_stream, `trainer_decoupled.py:129-168`);
- moves (n-1)/n of the payload per phase — half the bytes of the
  all-reduce lowering the stock path got;
- uses both ICI ring directions (payload split into a forward and a
  backward half-ring), like the hardware pincer emitters.

Semantics match ``lax.psum_scatter(tiled=True)`` / ``lax.all_gather(
tiled=True)`` exactly (equivalence-tested on the CPU mesh,
tests/test_ring_collectives.py); reduction order differs by float
rounding only.

**Layout: chunks are addressed by offset, never by a ``[n, S]`` view.**
On the TPU a ``[n*S]`` vector is tiled ``T(1024)``; a 2-D array with
fewer than 8 rows is tiled ``T(4,128)``. So ``x.reshape(n, S)`` with
n < 8 is no bitcast there: the compiler emits a loop that re-tiles the
whole vector, and ``.at[row].set`` on such a buffer becomes a scatter
and ``concatenate(axis=1).reshape(-1)`` a second loop back. The bodies
therefore take ``lax.dynamic_slice`` at ``c * S`` (fused into the hop's
add) and write each gathered chunk once with
``lax.dynamic_update_slice`` into one ``[n*S]`` output, in place.
Measured on a v5e 2x2 at dp=4, GPT-Neo-2.7B widths, S = 112,145,280
(my chip runs, PR 24, in PERF.md, against the ledger's PR 23; the
ledger's PR 24 lines repeat the rates): device self time under
``acco/reduce_scatter`` 78.8 -> 7.3 ms a round, under
``acco/all_gather`` 63.9 -> 12.6 ms, the round 390.7 -> 274.4 ms, ACCO
20,921 -> 29,748 and DDP 20,768 -> 28,965 tokens/s/chip; the compiled
ring pair holds no ``while`` and 0.84 GiB of temporaries where it held
four loops and 2.93 GiB (tests/test_ring_layout_aot.py holds it to
that). Offsets are int32, so a device's vector stays under 2**31
elements (a ``ValueError`` at trace time otherwise).

**Hierarchical rings for large axes**: the XLA async-collective
conversion gives up on long unrolled rings (28/60/0 async start/done
pairs counted in the programs compiled for 8/16/32 devices, the SAME
model; ``tests/test_ring_canary.py``), so past ``_FLAT_RING_MAX`` devices the collectives run as two
nested rings over a ``g x m`` factorization (intra-group then
inter-group, each phase <= _FLAT_RING_MAX hops, chunk ownership chosen
strided so device ``d`` still ends with tiled chunk ``d``). Same
semantics, ~same total bytes.

**Round-4 finding — the >=32-device blocking is DEVICE-COUNT-gated in
the compiler, not chain-structure-gated** (tools/permute_probe.py, all
at a 32-chip v5e AOT topology): a standalone 8-hop chain lowers
BLOCKING for every permutation structure tried — one 32-cycle, two
disjoint 16-cycles (what these hierarchical phases and any two-level
dp mesh emit), four 8-cycles, a 16-cycle with the other 16 devices
idle, and even a coordinate-snake ring whose every hop is a physical
ICI neighbor — while the identical programs at 8/16 devices convert
fully async. No effective flag: ``xla_enable_async_collective_permute``,
latency-bound thresholds (0 and 1e9), ``xla_max_concurrent_async_
collective_permutes``, limited-ICI-routing block size, and the LHS
knobs all leave it blocking; the stock ``psum_scatter``/``all_gather``
lower to two blocking all-reduces at 32 devices under every async flag
too. Comm hiding past 16 ICI-ring participants is therefore
unreachable without compiler changes on this libtpu (0.0.34). The
hierarchical ring is still the right large-axis emission — blocking
ppermute rings move ~half the bytes of the blocking all-reduce pincer
— and ``tests/test_ring_canary.py`` re-checks the 16-in/32-out cliff
so a libtpu that lifts the gate is noticed. Deployment guidance: keep
any axis that must overlap (the ZeRO-1 dp axis) at <= 16 ICI
participants and take further scale over additional mesh axes
(dp x pp / dp x tp placements — README placement table) or DCN
multislice.

Single mesh axis only: ``ppermute`` permutes over one named axis. The
context-parallel (dp, sp) joint-shard layout keeps the stock XLA path
(zero1_update_shard falls back automatically).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Longest flat unrolled ring XLA still makes async (counted in the
# compiled programs: 16 devices = 60 async pairs, 32 devices = 0). Axes larger than this use the
# two-phase hierarchical ring.
_FLAT_RING_MAX = 16


def _digit_perms(n_axis: int, stride: int, z: int):
    """(fwd, bwd) pairs for the simultaneous rings that advance the
    mixed-radix digit of the given ``stride`` and radix ``z``: device
    ``i``'s digit is ``(i // stride) % z``; every device with the same
    other digits forms one ring. ``stride=1`` gives intra-group rings,
    ``stride=g`` inter-group rings, and deeper strides the higher levels
    of the recursive decomposition."""

    def step(i, d):
        p = (i // stride) % z
        return i + (((p + d) % z) - p) * stride

    fwd = [(i, step(i, 1)) for i in range(n_axis)]
    bwd = [(i, step(i, -1)) for i in range(n_axis)]
    return fwd, bwd


def _check_int32_offsets(total: int) -> None:
    """The bodies address the vector by int32 element offsets."""
    if total >= 2**31:
        raise ValueError(
            f"ring collectives address the flat vector by int32 offsets: "
            f"{total} elements a device is past 2**31 - 1"
        )


def _rs_body(x_local, axis_name, n, idx, fwd, bwd):
    """Core bidirectional ring reduce-scatter over an arbitrary ring of
    size ``n`` at position ``idx`` with permutation tables ``fwd/bwd``:
    [n*S] addends -> [S] reduced chunk ``idx``."""
    if n == 1:
        return x_local
    _check_int32_offsets(x_local.shape[0])
    S = x_local.shape[0] // n
    # Ragged halves are fine: the two rings just carry unequal payloads.
    half = S // 2

    def fwd_half(c):
        return lax.dynamic_slice(x_local, ((c % n) * S,), (half,))

    def bwd_half(c):
        return lax.dynamic_slice(x_local, ((c % n) * S + half,), (S - half,))

    # Forward ring (+1 shifts): the partial for chunk c starts at device
    # c+1 and arrives home after n-1 hops; device d therefore holds the
    # partial for chunk (d - 1 - k) after hop k.
    acc_f = fwd_half(idx - 1)
    # Backward ring (-1 shifts): mirror image.
    acc_b = bwd_half(idx + 1)
    for k in range(1, n):
        acc_f = lax.ppermute(acc_f, axis_name, fwd)
        acc_b = lax.ppermute(acc_b, axis_name, bwd)
        acc_f = acc_f + fwd_half(idx - 1 - k)
        acc_b = acc_b + bwd_half(idx + 1 + k)
    return jnp.concatenate([acc_f, acc_b])


def _ag_body(shard, axis_name, n, idx, fwd, bwd):
    """Core bidirectional ring all-gather over an arbitrary ring:
    [S] local shard -> [n*S] tiled concatenation, every chunk written
    once, in place, at its own offset."""
    if n == 1:
        return shard
    S = shard.shape[0]
    _check_int32_offsets(n * S)
    half = S // 2
    out = lax.dynamic_update_slice(
        jnp.zeros((n * S,), shard.dtype), shard, (idx * S,)
    )
    cur_f, cur_b = shard[:half], shard[half:]
    for k in range(1, n):
        cur_f = lax.ppermute(cur_f, axis_name, fwd)
        cur_b = lax.ppermute(cur_b, axis_name, bwd)
        # After k forward hops the forward payload came from device d-k;
        # after k backward hops the backward payload came from d+k.
        out = lax.dynamic_update_slice(out, cur_f, (((idx - k) % n) * S,))
        out = lax.dynamic_update_slice(
            out, cur_b, (((idx + k) % n) * S + half,)
        )
    return out


def _largest_div(n: int) -> int | None:
    """Largest divisor of n that is <= _FLAT_RING_MAX (and >= 2); None
    when n has no small divisor (prime > _FLAT_RING_MAX — that segment
    stays a flat ring, the best a 1-D decomposition can do)."""
    for g in range(min(n - 1, _FLAT_RING_MAX), 1, -1):
        if n % g == 0:
            return g
    return None


def _rs_level(x_local, axis_name, size, pos, stride):
    """Recursive reduce-scatter over the ring that varies one mixed-radix
    digit (radix ``size`` at ``stride``): [size*S] -> [S] chunk ``pos``.
    Sizes past _FLAT_RING_MAX split into ``g x m`` sub-digits (g the
    largest small divisor) — intra rings first on the strided chunk
    regrouping, then recurse on the inter ring — so every emitted ring
    is short enough for XLA's async conversion, at any total size."""
    if size <= _FLAT_RING_MAX or (g := _largest_div(size)) is None:
        n_axis = lax.axis_size(axis_name)
        return _rs_body(
            x_local, axis_name, size, pos, *_digit_perms(n_axis, stride, size)
        )
    m = size // g
    q, r = pos // g, pos % g
    S = x_local.shape[0] // size
    # Strided chunk regrouping: digit-r members own chunks {c: c % g == r}
    # so the final owner of chunk q*g + r is position (q, r) — tiled
    # ownership preserved at every level (zero1's boundary masks).
    y = x_local.reshape(m, g, S).transpose(1, 0, 2).reshape(size * S)
    p1 = _rs_level(y, axis_name, g, r, stride)
    return _rs_level(p1, axis_name, m, q, stride * g)


def _ag_level(shard, axis_name, size, pos, stride):
    """Recursive all-gather — the exact inverse of ``_rs_level``'s
    level order and regrouping."""
    if size <= _FLAT_RING_MAX or (g := _largest_div(size)) is None:
        n_axis = lax.axis_size(axis_name)
        return _ag_body(
            shard, axis_name, size, pos, *_digit_perms(n_axis, stride, size)
        )
    m = size // g
    q, r = pos // g, pos % g
    S = shard.shape[0]
    p1 = _ag_level(shard, axis_name, m, q, stride * g)
    y = _ag_level(p1, axis_name, g, r, stride)
    return y.reshape(g, m, S).transpose(1, 0, 2).reshape(g * m * S)


def ring_reduce_scatter(x_local: jax.Array, axis_name: str) -> jax.Array:
    """[n*S] per-device addends -> [S] fully-reduced shard (device i gets
    chunk i of the sum). Must run inside shard_map over ``axis_name``.

    Flat bidirectional ring up to _FLAT_RING_MAX devices (n-1 async hops
    per direction); recursive hierarchical rings beyond it (every level's
    ring <= _FLAT_RING_MAX hops, any factorable size — 32, 512, ...).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x_local
    return _rs_level(x_local, axis_name, n, lax.axis_index(axis_name), 1)


def ring_all_gather(shard: jax.Array, axis_name: str) -> jax.Array:
    """[S] local shard -> [n*S] concatenation (tiled all-gather). Must run
    inside shard_map over ``axis_name``. Flat ring up to _FLAT_RING_MAX,
    recursive hierarchical beyond (the exact inverse of the
    reduce-scatter's strided regrouping)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return shard
    return _ag_level(shard, axis_name, n, lax.axis_index(axis_name), 1)
