"""The kernels of the main path, compiled for the chip without the chip.

The Pallas interpreter accepts what Mosaic refuses (a block shape not
aligned to the tiling, more VMEM than a kernel may use), so every kernel
is compiled forward and backward, at the widths the configs run, by the
TPU compiler that is installed here, for a ``v5e:2x2`` that is described
and not attached (``on-chip-measurement`` guide, section 2). Each case
must come out holding a Mosaic custom call. Nothing runs: this says the
chip's compiler accepts the kernels, not that they compute the right
numbers (``chip_smoke.py`` checks that, on the chip).

One child process compiles every case and the parametrised test reads
its per-case results. A process that has described the topology holds
libtpu's lock file, and any child that then loads libtpu aborts within
seconds; the other ``tpu_aot`` tests compile in children of their own, so
this pytest process must stay off libtpu too. About 2-4 s a case here.
"""

import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPOLOGY = "v5e:2x2"
MOSAIC = "tpu_custom_call"


# -- builders: each returns what ``_program`` describes; they import jax and so
# run in the child only ------------------------------------------------------


def _program(loss, argnums, avals, devices=1, specs=None):
    """``jax.grad(loss, argnums)`` is compiled at ``avals``: (shape, dtype)
    pairs, or a pytree of ShapeDtypeStructs. Over ``devices`` > 1, ``loss``
    is a function of the mesh (axis "x") that returns the loss, and
    ``specs`` places each aval."""
    return dict(loss=loss, argnums=argnums, avals=avals, devices=devices, specs=specs)


def _fused_attention(shape, window=0, pad=False):
    import jax.numpy as jnp

    from acco_tpu.ops.fused_attention import fused_dot_product_attention

    B, H, Hkv, L, D = shape

    def loss(q, k, v, pad_mask):
        out = fused_dot_product_attention(
            q, k, v, pad_mask=pad_mask if pad else None, window=window,
            interpret=False,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return _program(
        loss, (0, 1, 2),
        [
            ((B, H, L, D), jnp.bfloat16),
            ((B, Hkv, L, D), jnp.bfloat16),
            ((B, Hkv, L, D), jnp.bfloat16),
            ((B, L), jnp.int32),
        ],
    )


def _banded_attention(shape):
    import jax.numpy as jnp

    from acco_tpu.ops.banded_attention import banded_dot_product_attention

    B, H, L, D, W = shape

    def loss(q, k, v):
        out = banded_dot_product_attention(q, k, v, window=W, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return _program(loss, (0, 1, 2), [((B, H, L, D), jnp.bfloat16)] * 3)


def _fused_ce(shape):
    import jax.numpy as jnp

    from acco_tpu.ops.fused_ce import fused_ce_loss

    B, L, D, V = shape

    def loss(h, w, labels):
        return fused_ce_loss(h, w, labels, interpret=False)

    return _program(
        loss, (0, 1),
        [((B, L, D), jnp.bfloat16), ((D, V), jnp.bfloat16), ((B, L), jnp.int32)],
    )


def _vocab_parallel_ce(shape, tp=2):
    """The sharded kernel through a shard_map over ``tp`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from acco_tpu.ops.fused_ce import vocab_parallel_fused_ce_loss

    B, L, D, V = shape
    padded = V + (-V) % tp
    specs = (P(), P(None, "x"), P())

    def build(mesh):
        return jax.shard_map(
            lambda h, w, labels: vocab_parallel_fused_ce_loss(
                h, w, labels, "x", real_vocab=V
            ),
            mesh=mesh, in_specs=specs, out_specs=P(), check_vma=False,
        )

    return _program(
        build, (0, 1),
        [((B, L, D), jnp.bfloat16), ((D, padded), jnp.bfloat16), ((B, L), jnp.int32)],
        devices=tp, specs=specs,
    )


def _ring(fn_name, shape, sp=4, window=None):
    """The ring block kernel through the whole ring over ``sp`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from acco_tpu.ops import ring_attention

    B, H, L, D = shape
    Lc = L // sp
    spec = P(None, None, "x")

    def inner(q, k, v):
        if window is None:
            return getattr(ring_attention, fn_name)(q, k, v, "x", block_impl="fused")
        idx = jax.lax.axis_index("x")
        return ring_attention.windowed_ring_attention(
            q, k, v, "x", jnp.int32(window),
            idx * Lc + jnp.arange(Lc),
            lambda src: src * Lc + jnp.arange(Lc),
            block_impl="fused",
        )

    def build(mesh):
        body = jax.shard_map(
            inner, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False
        )
        return lambda q, k, v: jnp.sum(body(q, k, v).astype(jnp.float32) ** 2)

    return _program(
        build, (0, 1, 2), [((B, H, L, D), jnp.bfloat16)] * 3,
        devices=sp, specs=(spec,) * 3,
    )


def _llama_fused_remat(remat):
    """A whole (tiny) model's backward under a remat policy."""
    import jax
    import jax.numpy as jnp

    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(
        LlamaConfig(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
            num_kv_heads=2, intermediate_size=256, max_position_embeddings=128,
        ),
        param_dtype=jnp.bfloat16, remat=remat, attention="fused",
    )
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    def loss(params, ids):
        return jnp.mean(model.apply(params, ids).astype(jnp.float32) ** 2)

    return _program(loss, 0, [params, ((2, 128), jnp.int32)])


def _moe_experts(shape):
    """The experts' half of a block (ops/moe.py): dispatch, the three
    grouped matmuls through the megablox kernel, combine."""
    import jax.numpy as jnp

    from acco_tpu.ops.moe import dropless_experts

    T, K, E, D, F = shape

    def loss(h, gates, w_gate, w_up, w_down, experts):
        out = dropless_experts(h, gates, experts, w_gate, w_up, w_down, platform="tpu")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return _program(
        loss, (0, 1, 2, 3, 4),
        [((T, D), jnp.bfloat16), ((T, K), jnp.float32), ((E, D, F), jnp.bfloat16),
         ((E, D, F), jnp.bfloat16), ((E, F, D), jnp.bfloat16), ((T, K), jnp.int32)],
    )


def _flash_attention(shape):
    """The stock flash kernel at the tiles ``flash_block_sizes`` chooses."""
    import jax.numpy as jnp

    from acco_tpu.ops.attention import flash_dot_product_attention

    B, H, L, D = shape

    def loss(q, k, v):
        return jnp.sum(flash_dot_product_attention(q, k, v).astype(jnp.float32) ** 2)

    return _program(loss, (0, 1, 2), [((B, H, L, D), jnp.bfloat16)] * 3)


def _olmoe_layer(remat):
    """One OLMoE layer at the published widths and 4096 positions (a small
    vocabulary: the head is not the point), as the benchmark's cell runs it:
    stock flash attention, QK-norm, 64 experts top-8."""
    import jax
    import jax.numpy as jnp

    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(
        LlamaConfig(
            vocab_size=512, hidden_size=2048, num_layers=1, num_heads=16, num_kv_heads=16,
            intermediate_size=1024, max_position_embeddings=4096, tie_word_embeddings=False,
            qk_norm=True, num_experts=64, num_experts_per_tok=8,
        ),
        param_dtype=jnp.bfloat16, remat=remat, attention="auto", platform="tpu",
    )
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    def loss(params, ids):
        return jnp.mean(model.apply(params, ids).astype(jnp.float32) ** 2)

    return _program(loss, 0, [params, ((1, 4096), jnp.int32)])


# -- cases: name -> (builder, args, kwargs, what the result must satisfy)
#   mosaic: exact number of Mosaic custom calls, or None for "at least one"
#   absent: a buffer that must not exist in the executable
CASES = {
    # fused attention (ops/fused_attention.py), [B, H, Hkv, L, D]
    "fused_attn_neo125m": (_fused_attention, ((8, 12, 12, 1024, 64),), {}, {}),
    "fused_attn_llama3_gqa": (_fused_attention, ((1, 32, 8, 512, 128),), {}, {}),
    "fused_attn_gqa_l1024": (_fused_attention, ((2, 8, 2, 1024, 64),), {}, {}),
    "fused_attn_window256_pad": (
        _fused_attention, ((2, 12, 12, 1024, 64),), dict(window=256, pad=True), {},
    ),
    # the envelope's ceiling: one 16 MB score tile
    "fused_attn_l2048": (_fused_attention, ((2, 12, 12, 2048, 64),), {}, {}),
    # banded attention (ops/banded_attention.py), [B, H, L, D, W]
    # one forward and two backward kernels. The three shapes the cells run
    # (neo125m-*, neo27b-l4-acco-1chip, neo27b-l4-dp4); 2.7B widths at a
    # length no cell runs; a length past 2048, where a step takes a row block
    # and the kernel computes its offsets from the grid index
    "banded_attn_neo125m": (_banded_attention, ((8, 12, 1024, 64, 256),), {}, dict(mosaic=3)),
    "banded_attn_neo27b_l2048_b2": (
        _banded_attention, ((2, 20, 2048, 128, 256),), {}, dict(mosaic=3),
    ),
    "banded_attn_neo27b_l2048_b4": (
        _banded_attention, ((4, 20, 2048, 128, 256),), {}, dict(mosaic=3),
    ),
    "banded_attn_neo27b": (_banded_attention, ((8, 20, 1024, 128, 256),), {}, dict(mosaic=3)),
    "banded_attn_l4096": (_banded_attention, ((2, 2, 4096, 64, 256),), {}, dict(mosaic=3)),
    # fused lm-head + cross-entropy (ops/fused_ce.py), [B, L, D, V]
    "fused_ce_768x50257": (_fused_ce, ((8, 1024, 768, 50257),), {}, {}),
    "fused_ce_4096x128256": (_fused_ce, ((1, 512, 4096, 128256),), {}, {}),
    "fused_ce_2560x50257": (_fused_ce, ((2, 512, 2560, 50257),), {}, {}),
    # the large-D end: the tile budget was calibrated at D=4096, so a
    # drift of its footprint factor shows here at compile time
    "fused_ce_8192x32000": (_fused_ce, ((1, 256, 8192, 32000),), {}, {}),
    "fused_ce_12288x16384": (_fused_ce, ((1, 384, 12288, 16384),), {}, {}),
    "fused_ce_vocab_parallel_8b": (
        _vocab_parallel_ce, ((4, 512, 4096, 128256),), {}, {},
    ),
    # the ring block kernel (ops/block_attention.py) through the ring over
    # four devices; the [B, H, Lc, Lc] f32 score tile must not be in HBM
    "ring_block_contiguous": (
        _ring, ("ring_attention", (4, 12, 4096, 64)), {},
        dict(absent="f32[4,12,1024,1024]"),
    ),
    "ring_block_zigzag": (
        _ring, ("zigzag_ring_attention", (4, 12, 4096, 64)), {},
        dict(absent="f32[4,12,1024,1024]"),
    ),
    "ring_block_windowed": (
        _ring, ("windowed_ring_attention", (4, 12, 2048, 64)), dict(window=256),
        dict(absent="f32[4,12,512,512]"),
    ),
    # The 'dots' policy saves the kernel's named outputs (attn_out,
    # attn_lse: layers.wrap_remat), so the backward holds no second
    # forward kernel: 2 custom calls, as with remat off. A third means the
    # policy lost the names and every layer's forward kernel runs twice.
    "llama_fused_remat_dots": (_llama_fused_remat, ("dots",), {}, dict(mosaic=2)),
    "llama_fused_remat_off": (_llama_fused_remat, (False,), {}, dict(mosaic=2)),
    # sparse experts (ops/moe.py), [tokens, experts a token, experts, hidden, width]:
    # three grouped matmuls forward, two gradient matmuls each backward
    "moe_experts_olmoe": (_moe_experts, ((4096, 8, 64, 2048, 1024),), {}, dict(mosaic=9)),
    # the stock flash kernel (ops/attention.py) at the tiles chosen from the
    # shape, [B, H, L, D]: one forward and two backward kernels. The cell's
    # shape; a length only 512 divides, at D=64; the longest length swept
    "flash_attn_olmoe": (_flash_attention, ((1, 16, 4096, 128),), {}, dict(mosaic=3)),
    "flash_attn_l1536_d64": (_flash_attention, ((2, 12, 1536, 64),), {}, dict(mosaic=3)),
    "flash_attn_l8192": (_flash_attention, ((1, 8, 8192, 128),), {}, dict(mosaic=3)),
    # the 'dots' policy saves the grouped matmuls' named outputs (moe_gate,
    # moe_up, moe_down) and the flash kernel's (attn_out, attn_lse, named by
    # ops.attention._named_flash): 9 grouped-matmul kernels, ONE flash forward
    # and its two backward kernels. 13 means the flash kernel's residuals lost
    # their names and every layer's forward runs twice, as it did until PR 29.
    # Full remat runs the flash forward and the three forward grouped matmuls
    # again.
    "olmoe_layer_remat_dots": (_olmoe_layer, ("dots",), {}, dict(mosaic=12)),
    "olmoe_layer_remat_full": (_olmoe_layer, (True,), {}, dict(mosaic=16)),
}


# -- the child ----------------------------------------------------------------


def compile_all(out_path: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, REPO)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep it out (guide, section 2)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    results = {}
    try:
        devices = list(
            topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY).devices
        )
    except Exception as exc:  # no TPU compiler here: the test skips
        results["__skip__"] = f"{type(exc).__name__}: {exc}"
        devices = []
    for name, (builder, args, kwargs, _) in CASES.items() if devices else ():
        t0 = time.time()
        try:
            prog = builder(*args, **kwargs)
            mesh = Mesh(np.array(devices[: prog["devices"]]), ("x",))
            specs = prog["specs"] or (P(),) * len(prog["avals"])

            def place(aval, spec):
                shard = NamedSharding(mesh, spec)
                if isinstance(aval, tuple):
                    return jax.ShapeDtypeStruct(*aval, sharding=shard)
                return jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard), aval
                )

            loss = prog["loss"](mesh) if prog["devices"] > 1 else prog["loss"]
            hlo = (
                jax.jit(jax.grad(loss, argnums=prog["argnums"]))
                .lower(*map(place, prog["avals"], specs)).compile().as_text()
            )
            results[name] = {
                "hlo_mosaic": hlo.count(MOSAIC),
                "kernels": re.findall(rf"%([\w.\-]+) = [^\n]*{MOSAIC}", hlo),
                "seconds": time.time() - t0,
            }
            absent = CASES[name][3].get("absent")
            if absent:
                results[name]["absent_found"] = absent in hlo
        except Exception as exc:
            results[name] = {"error": f"{type(exc).__name__}: {exc}"[-3000:]}
    with open(out_path, "w") as f:
        json.dump(results, f)


# -- the test -----------------------------------------------------------------


@pytest.fixture(scope="session")
def compiled(tmp_path_factory) -> dict:
    out_path = str(tmp_path_factory.mktemp("tpu_compile") / "results.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for switch in ("ACCO_FUSED_ATTN_INTERPRET", "ACCO_FUSED_CE_INTERPRET"):
        env.pop(switch, None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), out_path],
        capture_output=True, text=True, timeout=800, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(out_path) as f:
        return json.load(f)


@pytest.mark.tpu_aot
@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_the_chip_with_a_mosaic_call(compiled, case):
    if "__skip__" in compiled:
        pytest.skip(f"{TOPOLOGY} cannot be described here: {compiled['__skip__']}")
    result, expect = compiled[case], CASES[case][3]
    assert "error" not in result, result["error"]
    if expect.get("mosaic") is None:
        assert result["hlo_mosaic"] > 0, "no Mosaic custom call in the executable"
    else:
        assert result["hlo_mosaic"] == expect["mosaic"], result
    assert not result.get("absent_found"), f"{expect.get('absent')} is in HBM"


@pytest.mark.tpu_aot
def test_the_olmoe_layers_flash_kernels_carry_the_chosen_tiles(compiled):
    """The chip's trace names a kernel by its innermost scope, and the
    stock backward kernels' scopes spell their tile sizes: the compiled
    layer runs the tiles ``flash_block_sizes`` chose, none at the stock
    128 x 128, under the names ``flash_attn_kernel_ms`` reads, and one
    forward kernel (under ``remat=dots`` there were two until PR 29)."""
    if "__skip__" in compiled:
        pytest.skip(f"{TOPOLOGY} cannot be described here: {compiled['__skip__']}")
    from acco_tpu.ops.attention import flash_block_sizes

    t = flash_block_sizes(4096, 128)
    result = compiled["olmoe_layer_remat_dots"]
    assert "error" not in result, result["error"]
    flash = sorted(k.rsplit(".", 1)[0] for k in result["kernels"] if k.startswith("flash_"))
    assert flash == [
        "flash_attention",
        f"flash_mha_bwd_dkv_block_q_major_{t.block_q_major_dkv}_block_q_{t.block_q_dkv}"
        f"_block_k_major_{t.block_k_major_dkv}_block_k_{t.block_k_dkv}",
        f"flash_mha_bwd_dq_block_q_major_{t.block_q_dq}"
        f"_block_k_major_{t.block_k_major_dq}_block_k_{t.block_k_dq}",
    ]
    assert not any("_128_block_q_128" in k for k in flash)  # the stock default's name


@pytest.mark.tpu_aot
@pytest.mark.parametrize(
    "case", ["banded_attn_neo125m", "banded_attn_neo27b_l2048_b2", "banded_attn_neo27b_l2048_b4"]
)
def test_the_banded_kernels_carry_the_chosen_steps(compiled, case):
    """Each banded kernel's name spells its grid step after the prefix the
    benchmark's ``attn_kernel_ms`` finds it by: at the shapes the cells run,
    the compiled kernels are the ones ``banded_block_sizes`` chose."""
    if "__skip__" in compiled:
        pytest.skip(f"{TOPOLOGY} cannot be described here: {compiled['__skip__']}")
    from acco_tpu.ops.banded_attention import banded_block_sizes

    _, H, L, D, W = CASES[case][1][0]
    step = banded_block_sizes(L, W, D, H)
    result = compiled[case]
    assert "error" not in result, result["error"]
    # under jax.grad the calls' names gain jvp_ / transpose_jvp_ in front
    found = sorted(re.search(r"acco_banded_attn_\w+?(?=_*\.|_*$)", k)[0] for k in result["kernels"])
    assert found == [f"acco_banded_attn_{kind}_{step.tag()}" for kind in ("dkv", "dq", "fwd")]


if __name__ == "__main__":
    compile_all(sys.argv[1])
