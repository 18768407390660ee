"""ZeRO-1 sharded AdamW vs torch.optim.AdamW, and schedule parity vs HF."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from acco_tpu.ops.adamw import AdamWState, init_adamw_state
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.mesh import make_mesh
from acco_tpu.parallel.zero1 import (
    ShardGeometry,
    UpdateHealth,
    zero1_update_shard,
)

WD, B1, B2, EPS = 0.1, 0.9, 0.95, 1e-8


class TestShardGeometry:
    def test_ragged(self):
        g = ShardGeometry(n_params=37, world_size=8)
        assert g.shard_size == 5 and g.padded_size == 40
        # last shard holds params 35..36 then 3 pad positions
        mask = np.asarray(g.shard_pad_mask(jnp.int32(7)))
        assert mask.tolist() == [1, 1, 0, 0, 0]
        assert np.asarray(g.shard_pad_mask(jnp.int32(0))).tolist() == [1] * 5

    def test_even(self):
        g = ShardGeometry(n_params=40, world_size=8)
        assert g.shard_size == 5 and g.padded_size == 40
        assert np.asarray(g.shard_pad_mask(jnp.int32(7))).sum() == 5

    def test_pad_roundtrip(self):
        g = ShardGeometry(7, 4)
        x = jnp.arange(7.0)
        assert np.array_equal(g.unpad_flat(g.pad_flat(x)), x)


def _torch_adamw_steps(params0, grads_per_step, lrs):
    """Reference trajectory from torch.optim.AdamW (the optimizer the
    reference shards, trainer_decoupled.py:303-309)."""
    import torch

    p = torch.nn.Parameter(torch.tensor(np.asarray(params0), dtype=torch.float64))
    opt = torch.optim.AdamW([p], lr=1.0, weight_decay=WD, betas=(B1, B2), eps=EPS)
    out = []
    for g, lr in zip(grads_per_step, lrs):
        opt.param_groups[0]["lr"] = float(lr)
        p.grad = torch.tensor(np.asarray(g), dtype=torch.float64)
        opt.step()
        out.append(p.detach().numpy().copy())
    return out


def test_sharded_adamw_matches_torch(eight_devices):
    """8-way sharded update on a ragged 37-param vector == torch AdamW."""
    mesh = make_mesh()
    geom = ShardGeometry(37, 8)
    rng = np.random.default_rng(0)
    params0 = rng.normal(size=37).astype(np.float32)
    n_steps = 5
    # per-device unreduced grad contributions for each step
    device_grads = rng.normal(size=(n_steps, 8, 37)).astype(np.float32)
    lrs = [1e-3, 1e-3, 5e-4, 5e-4, 1e-4]

    opt0 = init_adamw_state(geom.pad_flat(jnp.asarray(params0)))

    def body(opt, grads_local, lr):
        # grads_local: this device's [padded] contribution (pre-reduce)
        new_flat, new_opt = zero1_update_shard(
            grads_local, opt, jnp.float32(8.0), lr, geom, WD, B1, B2, EPS,
            out_dtype=jnp.float32,
        )
        return new_flat, new_opt

    opt_spec = AdamWState(params=P("dp"), mu=P("dp"), nu=P("dp"), count=P())
    stepper = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(opt_spec, P("dp"), P()),
            out_specs=(P(), opt_spec),
            check_vma=False,
        )
    )

    opt = opt0
    got = []
    for s in range(n_steps):
        # global grads array [8*padded]: device d's slice is its local view
        padded = np.stack(
            [np.pad(device_grads[s, d], (0, 3)) for d in range(8)]
        ).reshape(-1)
        new_flat, opt = stepper(opt, jnp.asarray(padded), jnp.float32(lrs[s]))
        got.append(np.asarray(new_flat)[:37])

    want = _torch_adamw_steps(
        params0, device_grads.sum(axis=1) / 8.0, lrs
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


def test_padding_positions_stay_zero(eight_devices):
    mesh = make_mesh()
    geom = ShardGeometry(37, 8)
    opt0 = init_adamw_state(geom.pad_flat(jnp.arange(37.0)))
    opt_spec = AdamWState(params=P("dp"), mu=P("dp"), nu=P("dp"), count=P())

    def body(opt, grads, lr):
        return zero1_update_shard(
            grads, opt, jnp.float32(1.0), lr, geom, WD, B1, B2, EPS,
            out_dtype=jnp.float32,
        )

    stepper = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(opt_spec, P("dp"), P()),
                      out_specs=(P(), opt_spec), check_vma=False)
    )
    # each device contributes a full-length [padded] grad vector
    grads = jnp.ones((8 * 40,), jnp.float32)
    new_flat, new_opt = stepper(opt0, grads, jnp.float32(0.1))
    assert np.all(np.asarray(new_flat)[37:] == 0.0)
    assert np.all(np.asarray(new_opt.mu)[37:] == 0.0)


# -- the guarded commit: verdict first, then one write -----------------------
# zero1_update_shard applies the guard's verdict to what it returns. What it
# must return is what the formula it replaced returned: the unguarded
# (tentative) update where the verdict is ok, the old state and the old flat
# vector, bit for bit, where it is not.

N_G = 37  # ragged over 4 shards, so the pad mask is live


def _guard_inputs(case, ws, tp, geom):
    """Per-device unreduced gradients, optimizer state and the working
    vector (deliberately NOT cast(params): ACCO's odd round starts from
    the speculative one) for one health case."""
    rng = np.random.default_rng(7)
    n_dev, Pp = ws * tp, geom.padded_size
    grads = rng.normal(size=(n_dev, Pp)).astype(np.float32)
    params = rng.normal(size=(tp, Pp)).astype(np.float32)
    mu = 0.1 * rng.normal(size=(tp, Pp)).astype(np.float32)
    nu = np.abs(0.01 * rng.normal(size=(tp, Pp))).astype(np.float32)
    pad = np.arange(Pp) >= N_G
    for a in (params, mu, nu):
        a[:, pad] = 0.0
    old_flat = (params + 0.25).astype(np.float32)
    if case == "nonfinite_grad":
        grads[n_dev - 1, 3] = np.nan
    elif case == "overflow_params":  # finite grads, the UPDATE overflows
        mu[0, 5] = 3e38
        nu[0, 5] = 1e-30
    elif case == "over_cap":
        grads *= 1e3
    opt = AdamWState(
        params=jnp.asarray(params.reshape(-1)),
        mu=jnp.asarray(mu.reshape(-1)),
        nu=jnp.asarray(nu.reshape(-1)),
        count=jnp.int32(3),
    )
    return jnp.asarray(grads.reshape(-1)), opt, jnp.asarray(old_flat.reshape(-1))


@pytest.mark.parametrize("tp", [1, 2], ids=["dp", "dp-x-tp"])
@pytest.mark.parametrize("ws", [1, 4])
@pytest.mark.parametrize(
    "case", ["healthy", "nonfinite_grad", "overflow_params", "over_cap"]
)
@pytest.mark.parametrize(
    "program", ["even", "odd", "generic-even", "generic-odd"]
)
def test_guarded_commit_is_the_old_formula(eight_devices, program, case, ws, tp):
    """``where(ok, tentative, old)`` on every leaf, without the tentative
    copy: ACCO's speculative round (static commit=False), the committing
    round of ACCO / DPU / DDP (static True), and the parity-generic
    program (traced), at one shard and four, alone and inside a tp group
    whose replicated prefix is synced before the verdict."""
    geom = ShardGeometry(N_G, ws)
    grads, opt, old_flat = _guard_inputs(case, ws, tp, geom)
    if tp > 1:
        mesh = make_mesh({"tp": tp, "dp": ws}, devices=eight_devices[: tp * ws])
        tp_kw = dict(tp_axis="tp", n_repl=6)
        shard_spec = P(("tp", "dp"))  # one [S] slice a device
        flat_spec = P("tp")  # the local [padded] vector, replicated over dp
    else:
        mesh = make_mesh({"dp": ws}, devices=eight_devices[:ws])
        tp_kw, shard_spec, flat_spec = {}, P("dp"), P()
    opt_spec = AdamWState(shard_spec, shard_spec, shard_spec, count=P())
    commits = program in ("odd", "generic-odd")

    def run(with_health, commit):
        def body(grads, opt, old_flat, parity):
            c = (parity == 1) if commit is None else commit
            return zero1_update_shard(
                grads, opt, jnp.float32(ws), jnp.float32(1e-2), geom, WD, B1,
                B2, EPS, out_dtype=jnp.bfloat16, with_health=with_health,
                max_grad_norm=50.0, commit=c,
                old_flat=old_flat.astype(jnp.bfloat16), **tp_kw,
            )

        out_specs = (flat_spec, opt_spec) + (
            (UpdateHealth(P(), P()),) if with_health else ()
        )
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(shard_spec, opt_spec, flat_spec, P()),
                out_specs=out_specs, check_vma=False,
            )
        )(grads, opt, old_flat, jnp.int32(commits))

    flat, new_opt, health = run(
        True, None if program.startswith("generic") else commits
    )
    tentative_flat, tentative_opt = run(False, True)

    assert bool(health.ok) == (case == "healthy")
    if case == "healthy":
        want_flat = tentative_flat
        want_opt = tentative_opt if commits else opt
        g = np.asarray(grads).reshape(tp, ws, -1).sum(1) / (ws * tp)
        if tp > 1:  # the replicated prefix is summed over tp, counted once
            g[:, :6] = g[:, :6].sum(0)
            g[1:, :6] = 0.0
        np.testing.assert_allclose(
            float(health.grad_norm),
            np.sqrt(np.square(g[:, :N_G]).sum()),
            rtol=1e-5,
        )
    else:
        want_flat, want_opt = old_flat.astype(jnp.bfloat16), opt
        assert np.isfinite(float(health.grad_norm)) == (case != "nonfinite_grad")
    # A skipped update is the old state bit for bit. A committed one is the
    # unguarded program's to the last bit but one: the CPU backend contracts
    # a multiply and an add into one FMA or not as its loop fusion falls,
    # and the two programs fuse differently.
    ulp = 1 if case == "healthy" else 0
    np.testing.assert_allclose(
        np.asarray(flat, np.float32), np.asarray(want_flat, np.float32),
        rtol=ulp * 2.0**-7, atol=0,
    )
    for got, want in zip(jax.tree.leaves(new_opt), jax.tree.leaves(want_opt)):
        if ulp and got.dtype == jnp.float32:
            np.testing.assert_array_max_ulp(np.asarray(got), np.asarray(want), ulp)
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestSchedules:
    def _hf_lrs(self, name, base_lr, warmup, total, n):
        import torch
        from transformers import get_scheduler

        p = torch.nn.Parameter(torch.zeros(1))
        opt = torch.optim.AdamW([p], lr=base_lr)
        sched = get_scheduler(
            name, optimizer=opt, num_warmup_steps=warmup, num_training_steps=total
        )
        lrs = []
        for _ in range(n):
            lrs.append(opt.param_groups[0]["lr"])
            opt.step()
            sched.step()
        return lrs

    @pytest.mark.parametrize("name", ["cosine", "linear"])
    def test_matches_hf(self, name):
        base, warmup, total = 6e-4, 10, 100
        fn = get_schedule(name, base, warmup, total)
        want = self._hf_lrs(name, base, warmup, total, 100)
        got = [float(fn(jnp.int32(s))) for s in range(100)]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)

    def test_constant(self):
        fn = get_schedule("constant", 1e-3, 0, 100)
        assert float(fn(jnp.int32(50))) == pytest.approx(1e-3)

    def test_unknown_raises(self):
        with pytest.raises(ValueError):
            get_schedule("nope", 1e-3, 0, 100)
