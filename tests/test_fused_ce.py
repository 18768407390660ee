"""Fused lm-head+CE Pallas kernel vs the materialized reference path.

Interpreter mode on CPU (the same kernel code the TPU compiles), value
AND gradients (wrt hidden and the head matrix) against
``ops.losses.causal_lm_loss(hidden @ lm_head, ...)`` at float32
tolerance, across the semantics surface: shift, IGNORE_INDEX masking,
label smoothing, real_vocab (Megatron padding) exclusion, num_valid
override, and non-tile-aligned row/vocab counts (internal padding).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.ops.fused_ce import fused_ce_loss, supports_fused_ce
from acco_tpu.ops.losses import IGNORE_INDEX, causal_lm_loss

B, L, D, V = 2, 33, 128, 277  # deliberately unaligned rows and vocab


def _setup(key, v=V, dtype=jnp.float32):
    kh, kw, kt = jax.random.split(key, 3)
    hidden = jax.random.normal(kh, (B, L, D), dtype)
    w = jax.random.normal(kw, (D, v), dtype) * 0.1
    labels = jax.random.randint(kt, (B, L), 0, v)
    return hidden, w, labels


def _ref(hidden, w, labels, **kw):
    logits = jnp.einsum(
        "bld,dv->blv", hidden, w, preferred_element_type=jnp.float32
    )
    return causal_lm_loss(logits, labels, **kw)


def _fused(hidden, w, labels, **kw):
    return fused_ce_loss(
        hidden, w, labels, block_rows=16, block_vocab=128,
        interpret=True, **kw
    )


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_value_matches_materialized(smoothing):
    hidden, w, labels = _setup(jax.random.PRNGKey(0))
    got = _fused(hidden, w, labels, label_smoothing=smoothing)
    want = _ref(hidden, w, labels, label_smoothing=smoothing)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_ignore_index_masking():
    hidden, w, labels = _setup(jax.random.PRNGKey(1))
    labels = labels.at[:, 10:20].set(IGNORE_INDEX)
    labels = labels.at[1, :].set(IGNORE_INDEX)
    got = _fused(hidden, w, labels)
    want = _ref(hidden, w, labels)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_real_vocab_exclusion():
    # Megatron-padded head: columns >= real_vocab excluded from the
    # softmax and the smoothing mean
    hidden, w, labels = _setup(jax.random.PRNGKey(2))
    real = V - 21
    labels = jnp.clip(labels, 0, real - 1)
    got = _fused(hidden, w, labels, real_vocab=real, label_smoothing=0.1)
    want = _ref(hidden, w, labels, real_vocab=real, label_smoothing=0.1)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_no_shift_and_num_valid():
    hidden, w, labels = _setup(jax.random.PRNGKey(3))
    got = _fused(hidden, w, labels, shift=False, num_valid=123.0)
    want = _ref(hidden, w, labels, shift=False, num_valid=123.0)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_gradients_match(smoothing):
    hidden, w, labels = _setup(jax.random.PRNGKey(4))
    labels = labels.at[:, -5:].set(IGNORE_INDEX)

    def mk(fn):
        return jax.grad(
            lambda h, w: fn(h, w, labels, label_smoothing=smoothing),
            argnums=(0, 1),
        )

    gh, gw = mk(_fused)(hidden, w)
    rh, rw = mk(_ref)(hidden, w)
    np.testing.assert_allclose(gh, rh, atol=1e-6, rtol=1e-4)
    np.testing.assert_allclose(gw, rw, atol=1e-6, rtol=1e-4)


def test_gradients_real_vocab():
    hidden, w, labels = _setup(jax.random.PRNGKey(5))
    real = V - 21
    labels = jnp.clip(labels, 0, real - 1)

    def mk(fn):
        return jax.grad(
            lambda h, w: fn(h, w, labels, real_vocab=real), argnums=(0, 1)
        )

    gh, gw = mk(_fused)(hidden, w)
    rh, rw = mk(_ref)(hidden, w)
    np.testing.assert_allclose(gh, rh, atol=1e-6, rtol=1e-4)
    np.testing.assert_allclose(gw, rw, atol=1e-6, rtol=1e-4)
    # padded columns must receive zero head gradient
    np.testing.assert_allclose(gw[:, real:], 0.0, atol=1e-7)


def test_bf16_inputs():
    hidden, w, labels = _setup(jax.random.PRNGKey(6), dtype=jnp.bfloat16)
    got = _fused(hidden, w, labels)
    logits = jnp.einsum(
        "bld,dv->blv", hidden, w, preferred_element_type=jnp.float32
    )
    want = causal_lm_loss(logits, labels)
    np.testing.assert_allclose(got, want, rtol=2e-2)


def test_tile_aligned_shapes():
    # exact multiples of the block sizes: no padding path at all
    hidden, w, labels = _setup(jax.random.PRNGKey(7), v=256)
    hidden = hidden[:, :17]  # N = 2*16 = 32 rows -> two 16-row blocks
    labels = labels[:, :17] % 256
    got = _fused(hidden, w[:, :256], labels)
    want = _ref(hidden, w[:, :256], labels)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_envelope():
    assert supports_fused_ce(8184, 768, 50257)
    assert not supports_fused_ce(8184, 100, 50257)  # unaligned hidden


def test_flat_loss_fn_pallas_matches_materialized(monkeypatch):
    """The train-path seam: make_flat_loss_fn(fused_loss='pallas')
    computes the same loss and flat-parameter gradient as the
    materialized path on a real (tiny) Llama."""
    from jax.flatten_util import ravel_pytree

    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.parallel.common import make_flat_loss_fn

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    cfg = LlamaConfig(
        vocab_size=257, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_position_embeddings=64,
    )
    model = LlamaModel(cfg, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    flat, unravel = ravel_pytree(params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 257)
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
    }
    f_mat = make_flat_loss_fn(model, unravel, flat.size, 0.05)
    f_pal = make_flat_loss_fn(
        model, unravel, flat.size, 0.05, fused_loss="pallas"
    )
    l_mat, g_mat = jax.value_and_grad(f_mat)(flat, batch)
    l_pal, g_pal = jax.value_and_grad(f_pal)(flat, batch)
    np.testing.assert_allclose(l_pal, l_mat, rtol=1e-5)
    np.testing.assert_allclose(g_pal, g_mat, atol=2e-5, rtol=1e-3)


def test_resolve_fused_loss_gate():
    """The shared train/eval capability gate (ops/losses.py):
    downgrade chains and the real_vocab interactions."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.losses import resolve_fused_loss

    small = LlamaModel(  # hidden 64: outside the kernel envelope
        LlamaConfig(
            vocab_size=257, hidden_size=64, intermediate_size=128,
            num_layers=1, num_heads=2, num_kv_heads=2,
            max_position_embeddings=32,
        ),
        param_dtype=jnp.float32,
    )
    ok = LlamaModel(
        LlamaConfig(
            vocab_size=257, hidden_size=128, intermediate_size=256,
            num_layers=1, num_heads=2, num_kv_heads=2,
            max_position_embeddings=32,
        ),
        param_dtype=jnp.float32,
    )
    msgs = []
    # pallas inside the envelope: stays pallas, with or without padding
    assert resolve_fused_loss("pallas", ok, None) == "pallas"
    assert resolve_fused_loss("pallas", ok, 250) == "pallas"
    # outside the envelope: -> chunk; with Megatron padding -> off
    assert resolve_fused_loss("pallas", small, None, warn=msgs.append) == "chunk"
    assert resolve_fused_loss("pallas", small, 250, warn=msgs.append) is False
    assert len(msgs) == 2 and "envelope" in msgs[0]
    # on the TPU platform a kernel that was asked for and cannot run is
    # an error, not a slower program ('auto' is a choice: silently off)
    with pytest.raises(ValueError, match="envelope"):
        resolve_fused_loss("pallas", small, None, platform="tpu")
    assert resolve_fused_loss("auto", small, None, platform="tpu") is False
    # chunk predates real_vocab support
    assert resolve_fused_loss("chunk", ok, 250) is False
    assert resolve_fused_loss(True, ok, None) == "chunk"
    assert resolve_fused_loss(False, ok, None) is False
    # no hidden/lm_head surface -> off
    assert resolve_fused_loss("pallas", object(), None) is False


def test_tiles_row_block_sublane_aligned():
    """ADVICE r4: the VMEM-budget halving loop (large D) and small
    non-power-of-two row counts must still yield a sublane-aligned row
    block — Mosaic can refuse an unaligned (e.g. 200-row) block on real
    TPU even though the interpreter accepts it."""
    from acco_tpu.ops.fused_ce import _tiles

    for D, V, n_rows in (
        (12288, 16384, 400),  # halving loop: 400 -> 200 -> align 192
        (768, 50257, 12),  # tiny batch: 12 -> align up to 16
        (4096, 128256, 8),
        (8192, 32000, 513),
    ):
        rb, vt = _tiles(D, V, n_rows, 512, 2048)
        assert rb % 16 == 0 and rb >= 16, (D, n_rows, rb)


def test_model_ce_chunk_rejects_unsupported_args():
    """ADVICE r4: the chunk branch silently ignored shift/num_valid/
    vocab_axis/real_vocab; misuse must fail at trace time."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.losses import model_ce

    model = LlamaModel(
        LlamaConfig(
            vocab_size=257, hidden_size=64, intermediate_size=128,
            num_layers=1, num_heads=2, num_kv_heads=2,
            max_position_embeddings=16,
        ),
        param_dtype=jnp.float32,
    )
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    am = jnp.ones((1, 8), jnp.int32)
    for bad in (
        dict(shift=False),
        dict(num_valid=jnp.float32(1.0)),
        dict(real_vocab=250),
        dict(vocab_axis="tp"),
    ):
        with pytest.raises(ValueError, match="fused_loss='chunk'"):
            model_ce(
                model, params, ids, am, ids,
                label_smoothing=0.0, fused="chunk", **bad,
            )


def test_resolve_fused_loss_auto_policy():
    """'auto' (the config default): pallas where measured/placed to win
    — sharded vocab, CP, Llama-3-class vocabs on TPU — False elsewhere,
    never chunk, silent (policy, not a request) when the envelope
    rejects its pick."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.losses import resolve_fused_loss

    def mk(vocab=50304, hidden=128):
        return LlamaModel(
            LlamaConfig(
                vocab_size=vocab, hidden_size=hidden,
                intermediate_size=2 * hidden, num_layers=1, num_heads=2,
                num_kv_heads=2, max_position_embeddings=32,
            ),
            param_dtype=jnp.float32,
        )

    msgs = []
    warn = msgs.append
    # non-TPU: always off (the kernel is Mosaic-only)
    assert resolve_fused_loss("auto", mk(), None, warn, platform="cpu") is False
    # TPU, sharded vocab (tp / pipelined): pallas
    assert (
        resolve_fused_loss(
            "auto", mk(), None, warn, n_vocab_shards=4, platform="tpu"
        )
        == "pallas"
    )
    # TPU, context parallelism: pallas
    assert (
        resolve_fused_loss(
            "auto", mk(), None, warn, seq_sharded=True, platform="tpu"
        )
        == "pallas"
    )
    # TPU, single-chip 50k flagship vocab: stays materialized until the
    # chip battery measures the crossover
    assert resolve_fused_loss("auto", mk(), None, warn, platform="tpu") is False
    # TPU, Llama-3-class vocab: pallas
    assert (
        resolve_fused_loss("auto", mk(vocab=128256), None, warn, platform="tpu")
        == "pallas"
    )
    # policy pick outside the envelope: silently off, never chunk
    assert (
        resolve_fused_loss(
            "auto", mk(hidden=96), None, warn, n_vocab_shards=4, platform="tpu"
        )
        is False
    )
    # no hidden/lm_head surface: silently off for auto
    assert resolve_fused_loss("auto", object(), None, warn, platform="tpu") is False
    assert msgs == []  # every auto decision above is warning-free


class TestVocabParallel:
    """vocab_parallel_fused_ce_loss vs the materialized vocab-parallel
    CE through a real 4-device shard_map: values and gradients, with
    Megatron padding and smoothing."""

    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:4]), ("tp",))

    def _run(self, fn, mesh, hidden, w, labels):
        from jax.sharding import PartitionSpec as P

        body = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(), P(None, "tp"), P()),
            out_specs=P(),
            check_vma=False,
        )
        loss = body(hidden, w, labels)
        grads = jax.grad(
            lambda h, w: body(h, w, labels), argnums=(0, 1)
        )(hidden, w)
        return loss, grads

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    @pytest.mark.parametrize("pad_cols", [0, 19])
    def test_matches_materialized_vp_ce(self, monkeypatch, smoothing,
                                        pad_cols):
        from acco_tpu.ops.fused_ce import vocab_parallel_fused_ce_loss
        from acco_tpu.ops.losses import vocab_parallel_causal_lm_loss

        monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
        mesh = self._mesh()
        v_padded = 512  # 128/shard
        real = v_padded - pad_cols
        kh, kw, kt = jax.random.split(jax.random.PRNGKey(8), 3)
        hidden = jax.random.normal(kh, (2, 17, 128), jnp.float32)
        w = jax.random.normal(kw, (128, v_padded), jnp.float32) * 0.1
        labels = jax.random.randint(kt, (2, 17), 0, real)
        labels = labels.at[:, -3:].set(IGNORE_INDEX)
        rv = real if pad_cols else None

        def fused(h, wl, lab):
            return vocab_parallel_fused_ce_loss(
                h, wl, lab, "tp", smoothing, real_vocab=rv,
                block_rows=16, block_vocab=64,
            )

        def mat(h, wl, lab):
            logits = jnp.einsum(
                "bld,dv->blv", h, wl, preferred_element_type=jnp.float32
            )
            return vocab_parallel_causal_lm_loss(
                logits, lab, "tp", smoothing, real_vocab=rv
            )

        l_f, g_f = self._run(fused, mesh, hidden, w, labels)
        l_m, g_m = self._run(mat, mesh, hidden, w, labels)
        np.testing.assert_allclose(l_f, l_m, rtol=1e-5)
        for gf, gm in zip(g_f, g_m):
            np.testing.assert_allclose(gf, gm, atol=2e-5, rtol=1e-3)
        if pad_cols:
            np.testing.assert_allclose(g_f[1][:, real:], 0.0, atol=1e-7)

    def test_unaligned_local_vocab_neighbor_ids(self, monkeypatch):
        """v_local % vt != 0: shard s's locally-PADDED columns carry
        global ids owned by shard s+1 — a neighbor's target id must hit
        the -1 sentinel, not the padded column's -1e30 masked logit
        (which poisons the psum'd true-logit to ~1e30)."""
        from acco_tpu.ops.fused_ce import vocab_parallel_fused_ce_loss
        from acco_tpu.ops.losses import vocab_parallel_causal_lm_loss

        monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
        mesh = self._mesh()
        v_total, v_local = 640, 160  # 160 % 64 != 0 -> local pad to 192
        kh, kw = jax.random.split(jax.random.PRNGKey(9))
        hidden = jax.random.normal(kh, (2, 9, 128), jnp.float32)
        w = jax.random.normal(kw, (128, v_total), jnp.float32) * 0.1
        # every label in a poisoned range: ids [160, 192) live on shard 1
        # but match shard 0's padded columns without the sanitization
        labels = jax.random.randint(
            jax.random.PRNGKey(10), (2, 9), 160, 192
        )

        def fused(h, wl, lab):
            return vocab_parallel_fused_ce_loss(
                h, wl, lab, "tp", block_rows=16, block_vocab=64
            )

        def mat(h, wl, lab):
            logits = jnp.einsum(
                "bld,dv->blv", h, wl, preferred_element_type=jnp.float32
            )
            return vocab_parallel_causal_lm_loss(logits, lab, "tp")

        l_f, g_f = self._run(fused, mesh, hidden, w, labels)
        l_m, g_m = self._run(mat, mesh, hidden, w, labels)
        np.testing.assert_allclose(l_f, l_m, rtol=1e-5)
        for gf, gm in zip(g_f, g_m):
            np.testing.assert_allclose(gf, gm, atol=2e-5, rtol=1e-3)


def test_gradients_two_kernel_backward(monkeypatch):
    """ACCO_FUSED_CE_PARTIAL_CAP=1 forces the split dH/dW backward (the
    large-vocab-x-hidden form); gradients must match the reference
    exactly like the single-kernel path does."""
    monkeypatch.setenv("ACCO_FUSED_CE_PARTIAL_CAP", "1")
    hidden, w, labels = _setup(jax.random.PRNGKey(12))
    labels = labels.at[:, -4:].set(IGNORE_INDEX)

    def mk(fn):
        return jax.grad(
            lambda h, w: fn(h, w, labels, label_smoothing=0.1),
            argnums=(0, 1),
        )

    gh, gw = mk(_fused)(hidden, w)
    rh, rw = mk(_ref)(hidden, w)
    np.testing.assert_allclose(gh, rh, atol=1e-6, rtol=1e-4)
    np.testing.assert_allclose(gw, rw, atol=1e-6, rtol=1e-4)


def test_pp_pallas_ce_matches_materialized(monkeypatch):
    """Pipeline parallelism with fused_loss='pallas': the pipelined
    vocab-parallel kernel CE (vocab split over pp) reproduces the
    materialized pp loss and final parameters."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=192,
        num_layers=4, num_heads=2, num_kv_heads=2,
        max_position_embeddings=16,
    )
    mesh = make_mesh({DATA_AXIS: 2, "pp": 4})
    opt = dict(weight_decay=0.1, beta1=0.9, beta2=0.95,
               param_dtype=jnp.float32)
    sched = get_schedule("cosine", 1e-2, 2, 50)
    params = LlamaModel(cfg, param_dtype=jnp.float32).init(
        jax.random.PRNGKey(0)
    )

    def run(fused):
        model = LlamaModel(cfg, param_dtype=jnp.float32)
        step = DDPTrainStep(
            model, mesh, sched, pipeline_axis="pp", fused_loss=fused,
            **opt,
        )
        state = step.init_state(params)
        fn = step.step_fn()
        losses = []
        for i in range(2):
            ids = jax.random.randint(
                jax.random.PRNGKey(70 + i), (4, 2, 16), 0, 512,
                dtype=jnp.int32,
            )
            b = {
                "input_ids": ids,
                "attention_mask": jnp.ones_like(ids),
                "labels": ids,
                "valid": jnp.ones((4, 2), jnp.float32),
            }
            state, m = fn(state, b)
            losses.append(float(m.loss))
        return losses, state

    l_mat, s_mat = run(False)
    l_pal, s_pal = run("pallas")
    np.testing.assert_allclose(l_pal, l_mat, rtol=1e-5)
    # atol 5e-6: the kernel's blocked logsumexp reassociates the vocab
    # reduction; measured worst case on jaxlib 0.4.36 CPU is ONE of
    # 624128 params at 3.16e-6 abs after the Adam update — a few f32
    # ULPs at that magnitude, not a kernel bug.
    np.testing.assert_allclose(
        np.asarray(s_pal.flat_params), np.asarray(s_mat.flat_params),
        rtol=2e-5, atol=5e-6,
    )


def test_pp_sp_pallas_ce_matches_materialized(monkeypatch):
    """pp x sp with fused_loss='pallas': the pipelined kernel CE's sp
    branch (pre-shifted labels, psum'd num_valid denominator) matches
    the materialized composed loss."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=192,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_position_embeddings=16,
    )
    mesh = make_mesh({DATA_AXIS: 2, "pp": 2, "sp": 2})
    opt = dict(weight_decay=0.1, beta1=0.9, beta2=0.95,
               param_dtype=jnp.float32)
    sched = get_schedule("cosine", 1e-2, 2, 50)
    params = LlamaModel(cfg, param_dtype=jnp.float32).init(
        jax.random.PRNGKey(0)
    )

    def run(fused):
        model = LlamaModel(
            cfg, param_dtype=jnp.float32, attention="ring",
            sequence_axis="sp", zigzag=True,
        )
        step = DDPTrainStep(
            model, mesh, sched, pipeline_axis="pp", seq_axis="sp",
            fused_loss=fused, **opt,
        )
        state = step.init_state(params)
        fn = step.step_fn()
        losses = []
        for i in range(2):
            ids = jax.random.randint(
                jax.random.PRNGKey(80 + i), (2, 2, 16), 0, 512,
                dtype=jnp.int32,
            )
            b = {
                "input_ids": ids,
                "attention_mask": jnp.ones_like(ids),
                "labels": ids,
                "valid": jnp.ones((2, 2), jnp.float32),
            }
            state, m = fn(state, b)
            losses.append(float(m.loss))
        return losses, state

    l_mat, s_mat = run(False)
    l_pal, s_pal = run("pallas")
    np.testing.assert_allclose(l_pal, l_mat, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_pal.flat_params), np.asarray(s_mat.flat_params),
        rtol=2e-5, atol=1e-6,
    )


def test_dp_sp_pallas_ce_matches_materialized(monkeypatch):
    """Plain dp x sp (context parallelism, no pipeline) with
    fused_loss='pallas': the flat-path kernel CE's sp branch
    (pre-shifted labels, psum'd num_valid denominator — the convention
    ported from make_pp_loss_fn, VERDICT r4 #4) matches the
    materialized CP loss and final parameters, so the long-sequence
    regime never materializes [B, Lc, V] logits."""
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=192,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_position_embeddings=16,
    )
    mesh = make_mesh({DATA_AXIS: 4, "sp": 2})
    opt = dict(weight_decay=0.1, beta1=0.9, beta2=0.95,
               param_dtype=jnp.float32)
    sched = get_schedule("cosine", 1e-2, 2, 50)
    params = LlamaModel(cfg, param_dtype=jnp.float32).init(
        jax.random.PRNGKey(0)
    )

    def run(fused):
        model = LlamaModel(
            cfg, param_dtype=jnp.float32, attention="ring",
            sequence_axis="sp", zigzag=True,
        )
        step = DDPTrainStep(
            model, mesh, sched, seq_axis="sp", fused_loss=fused, **opt
        )
        state = step.init_state(params)
        fn = step.step_fn()
        losses = []
        for i in range(2):
            ids = jax.random.randint(
                jax.random.PRNGKey(90 + i), (2, 4, 16), 0, 512,
                dtype=jnp.int32,
            )
            b = {
                "input_ids": ids,
                "attention_mask": jnp.ones_like(ids),
                "labels": ids,
                "valid": jnp.ones((2, 4), jnp.float32),
            }
            state, m = fn(state, b)
            losses.append(float(m.loss))
        return losses, state

    l_mat, s_mat = run(False)
    l_pal, s_pal = run("pallas")
    np.testing.assert_allclose(l_pal, l_mat, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_pal.flat_params), np.asarray(s_mat.flat_params),
        rtol=2e-5, atol=1e-6,
    )


def test_cp_eval_pallas_matches_materialized(monkeypatch, tmp_path):
    """The trainer's CP eval body under fused_loss='pallas' (kernel CE,
    no [B, Lc, V] logits) returns the same eval loss as the
    materialized CP eval — train 2 steps each way, compare both the
    final train params and the eval value."""
    import numpy as _np

    from acco_tpu.configuration import config_from_dict
    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.trainer import DecoupledTrainer

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    rng = _np.random.default_rng(3)
    docs = [
        {"input_ids": rng.integers(0, 500, size=24).tolist()}
        for _ in range(32)
    ]

    def run(fused):
        args = config_from_dict(
            dict(
                method_name="ddp", batch_size=1, n_grad_accumulation=1,
                learning_rate=1e-3, weight_decay=0.0, adam_beta1=0.9,
                adam_beta2=0.95, nb_steps_tot=2, max_length=16,
                scheduler_name="constant", warmup=0,
                use_mixed_precision=False, eval=False, save=False,
                mesh_shape={"dp": 4, "sp": 2}, fused_loss=fused,
                run_name=f"cpeval-{fused}",
            )
        )
        model = LlamaModel(
            LlamaConfig(
                vocab_size=512, hidden_size=128, intermediate_size=192,
                num_layers=1, num_heads=2, num_kv_heads=2,
                max_position_embeddings=16,
            ),
            param_dtype=jnp.float32, attention="ring",
            sequence_axis="sp", zigzag=True,
        )
        t = DecoupledTrainer(
            model, ByteTokenizer(), docs, docs[:8], args, seed=0,
            run_dir=str(tmp_path / str(fused)),
        )
        t.train()
        return float(t.evaluate(t.final_state.flat_params))

    e_mat = run(False)
    e_pal = run("pallas")
    assert np.isfinite(e_mat)
    np.testing.assert_allclose(e_pal, e_mat, rtol=1e-5)


def test_flat_loss_fn_pallas_gptneo(monkeypatch):
    """GPT-Neo through the same seam: make_flat_loss_fn with
    fused_loss='pallas' matches the materialized path (value + grad)."""
    from jax.flatten_util import ravel_pytree

    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
    from acco_tpu.parallel.common import make_flat_loss_fn

    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    cfg = GPTNeoConfig(
        vocab_size=257, hidden_size=128, num_layers=2, num_heads=2,
        max_position_embeddings=64, window_size=16,
        attention_layers=["global", "local"],
    )
    model = GPTNeoModel(cfg, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    flat, unravel = ravel_pytree(params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 257)
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
    }
    f_mat = make_flat_loss_fn(model, unravel, flat.size, 0.0)
    f_pal = make_flat_loss_fn(
        model, unravel, flat.size, 0.0, fused_loss="pallas"
    )
    l_mat, g_mat = jax.value_and_grad(f_mat)(flat, batch)
    l_pal, g_pal = jax.value_and_grad(f_pal)(flat, batch)
    np.testing.assert_allclose(l_pal, l_mat, rtol=1e-5)
    np.testing.assert_allclose(g_pal, g_mat, atol=2e-5, rtol=1e-3)
