"""Ring (ppermute) ZeRO-1 collectives ≡ the stock XLA collectives.

The ring implementations exist for overlap (async collective-permute
pairs the TPU scheduler can hide behind compute — ring_collectives.py
module docstring); their math must be identical to
psum_scatter/all_gather up to float reduction order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.mesh import make_mesh
from acco_tpu.parallel.ring_collectives import (
    ring_all_gather,
    ring_reduce_scatter,
)

WS = 8


def _dp_mesh(n):
    return make_mesh(devices=jax.devices()[:n])  # 1-D "dp" over n devices


def _on_ring(body, mesh, x, out_specs):
    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P("dp"),), out_specs=out_specs,
            check_vma=False,
        )
    )
    return fn(jax.device_put(x, NamedSharding(mesh, P("dp"))))


# even, odd (ragged halves) and one-element chunks (an empty forward half)
@pytest.mark.parametrize("chunk", [16, 17, 1])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_matches_xla_collectives(eight_devices, n, chunk):
    """f32 reduce-scatter up to rounding, bf16 all-gather exactly."""
    mesh = _dp_mesh(n)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(n * n * chunk,)), jnp.float32
    )

    def body(x):
        rs = ring_reduce_scatter(x, "dp")
        rs_ref = jax.lax.psum_scatter(x, "dp", tiled=True)
        shard = rs_ref.astype(jnp.bfloat16)
        ag = ring_all_gather(shard, "dp")
        ag_ref = jax.lax.all_gather(shard, "dp", tiled=True)
        return rs - rs_ref, ag, ag_ref

    d_rs, ag, ag_ref = _on_ring(body, mesh, x, (P("dp"), P("dp"), P("dp")))
    np.testing.assert_allclose(np.asarray(d_rs), 0.0, atol=2e-5)
    assert ag.dtype == ag_ref.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(ag), np.asarray(ag_ref))  # no math, exact


def _replay_reduce_scatter(x, n):
    """The ring's reduce-scatter in NumPy, hop for hop: ``x[d]`` is device
    ``d``'s ``[n*S]`` addends; returns the ``[n, S]`` reduced chunks."""
    S = x.shape[1] // n
    half = S // 2
    c = x.reshape(n, n, S)
    dev = np.arange(n)
    acc_f = c[dev, (dev - 1) % n, :half]
    acc_b = c[dev, (dev + 1) % n, half:]
    for k in range(1, n):
        # device d receives what d-1 (forward) and d+1 (backward) held
        acc_f = np.roll(acc_f, 1, axis=0) + c[dev, (dev - 1 - k) % n, :half]
        acc_b = np.roll(acc_b, -1, axis=0) + c[dev, (dev + 1 + k) % n, half:]
    return np.concatenate([acc_f, acc_b], axis=1)


@pytest.mark.parametrize("chunk", [16, 17, 1])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_reduce_scatter_addition_order(eight_devices, n, chunk):
    """Bit-equal to the replay: the order of the float additions is what a
    run's loss depends on, so a rewrite of the bodies may not change it."""
    mesh = _dp_mesh(n)
    x = np.random.default_rng(n * 100 + chunk).normal(
        size=(n, n * chunk)
    ).astype(np.float32)
    got = _on_ring(
        lambda xl: ring_reduce_scatter(xl, "dp"), mesh,
        jnp.asarray(x.reshape(-1)), P("dp"),
    )
    np.testing.assert_array_equal(
        np.asarray(got).reshape(n, chunk), _replay_reduce_scatter(x, n)
    )


@pytest.mark.parametrize(
    "collective, dtype, too_long, fits",  # a device's input, in elements
    [
        (ring_reduce_scatter, jnp.float32, 2**31, 2**31 - 4),
        (ring_all_gather, jnp.bfloat16, 2**29, 2**29 - 1),  # x n = 4 gathered
    ],
)
def test_ring_refuses_a_vector_past_int32_offsets(
    eight_devices, collective, dtype, too_long, fits
):
    """Traced from shapes only: nothing that large is allocated."""
    n = 4
    sharded = jax.shard_map(
        lambda v: collective(v, "dp"), mesh=_dp_mesh(n), in_specs=(P("dp"),),
        out_specs=P("dp"), check_vma=False,
    )
    with pytest.raises(ValueError, match=str(2**31)):
        jax.eval_shape(sharded, jax.ShapeDtypeStruct((n * too_long,), dtype))
    jax.eval_shape(sharded, jax.ShapeDtypeStruct((n * fits,), dtype))


def test_acco_round_ring_matches_xla(eight_devices):
    """Full ACCO rounds with comm_impl='ring' track the 'xla' path."""
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, max_position_embeddings=16,
    )
    mesh = make_mesh()
    sched = get_schedule("constant", 1e-3, 0, 100)
    kw = dict(weight_decay=0.1, beta1=0.9, beta2=0.95, param_dtype=jnp.float32)
    model = LlamaModel(cfg, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    states, steps = {}, {}
    for impl in ("xla", "ring"):
        step = AccoTrainStep(model, mesh, sched, mode="acco", comm_impl=impl, **kw)
        steps[impl] = step
        states[impl] = step.init_state(params)

    rng = np.random.default_rng(3)
    for r in range(5):
        ids = jnp.asarray(rng.integers(0, 64, (1, WS, 16)), jnp.int32)
        batch = {
            "input_ids": ids,
            "attention_mask": jnp.ones_like(ids),
            "labels": ids,
            "valid": jnp.ones((1, WS), jnp.float32),
        }
        for impl in ("xla", "ring"):
            fn = steps[impl].seed_fn() if r == 0 else steps[impl].round_fn()
            states[impl], m = fn(states[impl], batch)
    np.testing.assert_allclose(
        np.asarray(states["ring"].flat_params),
        np.asarray(states["xla"].flat_params),
        rtol=1e-5,
        atol=1e-6,
    )


@pytest.mark.parametrize("n_dev", [27, 32])
def test_hierarchical_ring_matches_stock_32_devices(n_dev):
    """Past _FLAT_RING_MAX the collectives run as two nested rings
    (XLA stops making >16-hop unrolled rings async:
    tests/test_ring_canary.py); semantics must still match psum_scatter/all_gather tiled —
    including the strided chunk regrouping that preserves device d's
    ownership of tiled chunk d. 32 virtual devices in a subprocess (the
    suite's fixture pins 8)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=NDEV"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from acco_tpu.parallel.ring_collectives import (
            _FLAT_RING_MAX, ring_all_gather, ring_reduce_scatter,
        )
        assert len(jax.devices()) == NDEV > _FLAT_RING_MAX
        mesh = jax.make_mesh((NDEV,), ("dp",))
        S = 6  # ragged halves exercised (odd splits)
        x = jnp.arange(NDEV * NDEV * S, dtype=jnp.float32).reshape(NDEV, NDEV * S)

        def rs(xl):
            return ring_reduce_scatter(xl[0], "dp")

        got = jax.jit(jax.shard_map(
            rs, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        ))(x)
        want = np.asarray(x).sum(0)  # tiled: device i owns chunk i
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

        def ag(sh):
            return ring_all_gather(sh, "dp")[None]

        shards = jnp.arange(NDEV * S, dtype=jnp.float32)
        got2 = jax.jit(jax.shard_map(
            ag, mesh=mesh, in_specs=P("dp"), out_specs=P(None, "dp"),
            check_vma=False,
        ))(shards)
        # EVERY device reconstructs the full vector in global chunk order
        rows = np.asarray(got2).reshape(NDEV, NDEV * S)
        np.testing.assert_array_equal(
            rows, np.tile(np.asarray(shards), (NDEV, 1))
        )
        print("HIER_OK")
        """
    )
    import os

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    code = code.replace("NDEV", str(n_dev))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "HIER_OK" in out.stdout, f"{out.stdout}\n{out.stderr[-2000:]}"
