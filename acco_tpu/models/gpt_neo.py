"""GPT-Neo causal LM: alternating global / local-sliding-window attention.

Parity with the reference's pretraining model (HF ``GPTNeoForCausalLM``
built from `/root/reference/config/model/gpt-neo-125M.json`: 12 layers
alternating global/local, hidden 768, window 256, gelu_new, learned
position embeddings, **unscaled** attention scores — GPT-Neo's historical
quirk of omitting the 1/sqrt(d) factor is preserved so checkpoints and loss
curves are comparable).

TPU-first: the per-layer window is data (an ``[n_layers]`` int array
scanned alongside the stacked weights), so global and local layers share
one compiled ``lax.scan`` body instead of unrolled per-layer programs.

Context parallelism (``sequence_axis``): the learned position embedding
shards by the statically-known per-shard absolute positions (contiguous
or zig-zag layout) and every layer runs
``ops.ring_attention.windowed_ring_attention``, which carries the
sliding-window mask into the ring and skips fully-out-of-window chunk
pairs — the reference's flagship pretrain model on the long-context path.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from acco_tpu.models.layers import (
    gelu_new,
    layer_norm,
    merge_heads,
    normal_init,
    split_heads,
    wrap_remat,
)
from acco_tpu.ops.attention import (
    attention_mask_bias,
    dot_product_attention,
    resolve_attention_impl,
)
from acco_tpu.ops.ring_attention import (
    windowed_ring_attention,
    zigzag_positions,
)


@dataclasses.dataclass(frozen=True)
class GPTNeoConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # None -> 4 * hidden
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    window_size: int = 256
    attention_layers: Sequence[str] = dataclasses.field(
        default_factory=lambda: ["global", "local"] * 6
    )
    activation_function: str = "gelu_new"
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    bos_token_id: int = 50256
    eos_token_id: int = 50256

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def layer_windows(self) -> list[int]:
        """Per-layer window sizes; 0 = global."""
        if len(self.attention_layers) != self.num_layers:
            raise ValueError(
                f"attention_layers has {len(self.attention_layers)} entries "
                f"for {self.num_layers} layers"
            )
        return [
            0 if kind == "global" else self.window_size
            for kind in self.attention_layers
        ]

    @classmethod
    def from_json(cls, path: str) -> "GPTNeoConfig":
        with open(path) as f:
            raw = json.load(f)
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in raw.items() if k in fields}
        if kwargs.get("intermediate_size", "keep") is None:
            kwargs.pop("intermediate_size")
        return cls(**kwargs)


class GPTNeoModel:
    def __init__(
        self,
        config: GPTNeoConfig,
        param_dtype=jnp.bfloat16,
        remat=False,
        attention: str = "auto",
        sequence_axis: str | None = None,
        scan_unroll: int | bool = 1,
        zigzag: bool = False,
        tensor_axis: str | None = None,
        vocab_pad_to: int | None = None,
        platform: str | None = None,  # pin 'tpu' for AOT proof builders
        # (hbm_check): banded-local gating must model the program the
        # chip runs, not the forced-CPU build host
    ):
        self.platform = platform
        self.scan_unroll = scan_unroll
        # Context parallelism: the sequence dim shards over this mesh axis
        # and every layer runs windowed_ring_attention. The two GPT-Neo
        # specifics the Llama CP path doesn't have are handled statically:
        # the learned position embedding is looked up at the shard's
        # absolute positions (contiguous offset or zigzag_positions — the
        # layout is a pure function of the shard index), and local layers
        # carry their sliding-window mask into the ring body, where
        # fully-out-of-window chunk pairs skip their matmuls (lax.cond).
        self.sequence_axis = sequence_axis
        self.zigzag = bool(zigzag)
        from acco_tpu.ops.attention import normalize_attention_impl

        impl = normalize_attention_impl(attention)
        if impl == "ring" and not sequence_axis:
            raise ValueError("attention='ring' requires sequence_axis")
        if impl == "flash":
            # The rule: the stock flash kernel is refused for this
            # family, whose context ends at 2048 and whose window of 256
            # is narrower than the kernel's 512-token blocks, so a
            # block-sparse mask could skip no whole block and the kernel
            # would do causal work plus masking. No ledger line holds a
            # timing of it against the einsum or the banded kernel
            # (unmeasured; ROADMAP S1): the refusal keeps a path nobody
            # has run on this family out of a user's reach, and the
            # message below words it more strongly than the evidence.
            raise ValueError(
                "GPT-Neo's alternating local-sliding-window layers use the "
                "XLA attention path by design: its max context (2048) is "
                "below the measured flash/splash-kernel crossover (window "
                "256 is too narrow for block-sparse wins; see the "
                "constructor comment), so a fused kernel would lose at "
                "every supported length; use attention='xla'/'auto' (or "
                "'ring' with sequence_axis for context parallelism)"
            )
        # 'fused' (the bespoke full-tile VMEM kernel, ops/fused_attention)
        # is the exception to the above: it has none of the stock kernel's
        # online-softmax block machinery, carries the
        # sliding window as a traced SMEM scalar (so the one scanned layer
        # body still serves both layer kinds), and removes the [B,H,L,L]
        # score HBM traffic entirely. 'auto' resolves to it per shape.
        # Local layers additionally dispatch (lax.cond in _block_body) to
        # the BANDED kernel (ops/banded_attention): a grid step takes whole
        # heads (the rows, heads and tile banded_block_sizes chooses from
        # the shape) and works through them a tile of query rows at a time,
        # each against only its tile + W in-window keys: a 256-token window
        # skips ~(L-W-tile)/L of the score work instead of masking it.
        self.attention = impl
        self.config = config
        self.param_dtype = param_dtype
        self.remat = remat
        # Megatron-style tensor parallelism (parallel/tp.py): heads/ffn
        # sharded over the axis, vocab-parallel wte/lm-head; the fused
        # qkv is stored [N, D, 3, D] so each third splits cleanly. Makes
        # the reference's GPT-Neo-2.7B pretrain config placeable on
        # 16 GB v5e chips (tools/hbm_check.py) — dp-only, its staged f32
        # gradients + bf16 params alone exceed one chip's HBM.
        self.tensor_axis = tensor_axis
        # Megatron vocab padding (parallel/tp.pad_vocab): see LlamaModel.
        self.padded_vocab = int(vocab_pad_to or config.vocab_size)
        if self.padded_vocab < config.vocab_size:
            raise ValueError(
                f"vocab_pad_to={vocab_pad_to} < vocab_size={config.vocab_size}"
            )

    def init(self, key: jax.Array) -> dict:
        cfg, dt = self.config, self.param_dtype
        D, F, N = cfg.hidden_size, cfg.ffn_dim, cfg.num_layers
        std = cfg.initializer_range
        k_wte, k_wpe, k_layers = jax.random.split(key, 3)

        def stack_init(key, shape):
            keys = jax.random.split(key, N)
            return jnp.stack([normal_init(k, shape, std, dt) for k in keys])

        ks = jax.random.split(k_layers, 6)
        return {
            "wte": normal_init(k_wte, (self.padded_vocab, D), std, dt),
            "wpe": normal_init(k_wpe, (cfg.max_position_embeddings, D), std, dt),
            "layers": {
                "ln1_scale": jnp.ones((N, D), dt),
                "ln1_bias": jnp.zeros((N, D), dt),
                # fused qkv, stored [D, 3, D] (GPT-Neo projections carry
                # no bias); the explicit q/k/v axis keeps each third
                # contiguous so tensor parallelism can split the head dim
                "w_qkv": stack_init(ks[0], (D, 3, D)),
                "wo": stack_init(ks[1], (D, D)),
                "wo_bias": jnp.zeros((N, D), dt),
                "ln2_scale": jnp.ones((N, D), dt),
                "ln2_bias": jnp.zeros((N, D), dt),
                "w_fc": stack_init(ks[2], (D, F)),
                "b_fc": jnp.zeros((N, F), dt),
                "w_proj": stack_init(ks[3], (F, D)),
                "b_proj": jnp.zeros((N, D), dt),
            },
            "lnf_scale": jnp.ones((D,), dt),
            "lnf_bias": jnp.zeros((D,), dt),
        }

    def tp_param_specs(self) -> dict:
        """Tensor-parallel split spec per leaf (parallel/tp.TpLayout).
        Same scheme as the Llama family: vocab-parallel wte (dim 0 after
        the leading layer-stack dim shift does not apply — wte has no
        stack dim), column-split projections (w_qkv's head dim 3, w_fc's
        ffn dim 2), row-split output projections with a psum after (wo 1,
        w_proj 1). Biases: b_fc lives on the sharded ffn dim (1 after the
        stack dim); wo_bias/b_proj are added AFTER the psum and stay
        replicated, as do the layer norms and wpe.

        Thin shim: the split choices live in the ``params:gpt_neo:tp``
        rule table (acco_tpu/sharding/tables.py)."""
        from acco_tpu.sharding import model_split_specs

        return model_split_specs(self, "tp")

    def unpad_vocab(self, params: dict) -> dict:
        """Strip Megatron vocab padding for export (see LlamaModel)."""
        if self.padded_vocab == self.config.vocab_size:
            return params
        out = dict(params)
        out["wte"] = params["wte"][: self.config.vocab_size]
        return out

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
    ) -> jax.Array:  # [B, L, V] f32 logits ([B, L, V/tp] local under tp)
        x = self.hidden(params, input_ids, attention_mask)
        with jax.named_scope("model/lm_head_ce"):
            return jnp.einsum(
                "bld,dv->blv",
                x,
                self.lm_head(params),
                preferred_element_type=jnp.float32,
            )

    def lm_head(self, params: dict) -> jax.Array:
        """[D, V] output projection (GPT-Neo always ties to wte); under
        tensor parallelism the vocab dim is this shard's slice."""
        return params["wte"].T

    def hidden(
        self,
        params: dict,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        L = input_ids.shape[1]  # CP: the device-local chunk length
        eps = cfg.layer_norm_epsilon
        cp = self.sequence_axis is not None
        positions, kv_positions_fn = self._cp_positions(L, attention_mask)
        with jax.named_scope("model/embed"):
            if self.tensor_axis:
                from acco_tpu.models.layers import vocab_parallel_embed

                tok = vocab_parallel_embed(
                    params["wte"], input_ids, self.tensor_axis
                )
            else:
                tok = params["wte"][input_ids]
            x = tok + params["wpe"][positions][None, :, :]

        fused, banded_local, global_bias, local_bias = (
            (False, False, None, None)
            if cp
            else self._dense_attn_plan(L, attention_mask)
        )
        windows = jnp.asarray(cfg.layer_windows, jnp.int32)
        tp = (
            jax.lax.axis_size(self.tensor_axis) if self.tensor_axis else 1
        )
        if tp > 1 and cfg.num_heads % tp:
            raise ValueError(
                f"tensor parallelism size {tp} must divide num_heads="
                f"{cfg.num_heads}"
            )
        n_heads = cfg.num_heads // tp

        def tp_psum(t):
            return jax.lax.psum(t, self.tensor_axis) if tp > 1 else t

        body = wrap_remat(
            self._block_body(
                n_heads, tp_psum,
                cp=cp,
                fused=fused,
                pad_mask=attention_mask if fused else None,
                banded_local=banded_local,
                global_bias=global_bias,
                local_bias=local_bias,
                positions=positions if cp else None,
                kv_positions_fn=kv_positions_fn,
            ),
            self.remat,
        )
        # the scope holds the scan itself, not only its body: stacking the
        # layers' saved activations and slicing them back out in the
        # backward pass is the block stack's time too
        # The qkv thirds become ONE [D, 3 Dh] matrix a layer before the scan
        # (the block's own flatten is then the identity): a [D, 3, Dh] slice
        # crossing the loop is tiled T(4,128) over its 3 rows, a pass over the
        # leaf each way between the flat vector's slab and the matmul.
        layers = dict(params["layers"])
        layers["w_qkv"] = layers["w_qkv"].reshape(*layers["w_qkv"].shape[:2], -1)
        with jax.named_scope("model/block"):
            x, _ = jax.lax.scan(
                body, x, (layers, windows), unroll=self.scan_unroll
            )
        return layer_norm(x, params["lnf_scale"], params["lnf_bias"], eps)

    def _cp_positions(self, L, attention_mask=None):
        """Shared CP prelude (``hidden``, ``pp_embed``, ``stage_blocks``):
        this shard's absolute positions in the ws*L global sequence and
        the ring's per-source-shard KV position function — contiguous or
        zig-zag layout. The learned position embedding shards for free:
        the shard layout is static, so each device's positions are
        computed and the replicated wpe is gathered at exactly them.
        Validates the CP no-padding-mask contract and the position-table
        range; outside CP, returns plain positions and no KV fn."""
        cfg = self.config
        if self.sequence_axis is None:
            positions, kv_positions_fn, global_len = (
                jnp.arange(L), None, L
            )
        else:
            if attention_mask is not None:
                raise ValueError(
                    "context parallelism does not support padding masks — "
                    "it serves const-len packed sequences; pass "
                    "attention_mask=None"
                )
            ws = jax.lax.axis_size(self.sequence_axis)
            idx = jax.lax.axis_index(self.sequence_axis)
            global_len = ws * L
            if self.zigzag:
                positions = zigzag_positions(global_len, ws, idx)
                kv_positions_fn = lambda src: zigzag_positions(
                    global_len, ws, src
                )
            else:
                positions = idx * L + jnp.arange(L)
                kv_positions_fn = lambda src: src * L + jnp.arange(L)
        if global_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {global_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}"
            )
        return positions, kv_positions_fn

    def _dense_attn_plan(self, L, attention_mask):
        """Shared by ``hidden`` and ``stage_blocks``: resolve whether the
        dense path runs the fused VMEM kernel (no [L, L] biases exist at
        all) or the einsum path with window-selected additive biases.

        Returns ``(fused, banded_local, global_bias, local_bias)``.
        ``banded_local`` extends the banded window kernel to the EINSUM
        plan: where 'auto' resolves the *global* layers to the einsum
        (past the full-tile kernel's envelope, L > 2048: at 2048 the
        fused plan measured 18-19% faster, ops/attention.py), the local
        layers' einsum would still compute the whole [L, L] it masks
        ~(L-W)/L away; the banded kernel (no L wall, parity-tested)
        replaces just those. No benchmark cell reaches this plan
        (ROADMAP D2). Requires mask-free batches (const-len) and a TPU
        (or the interpreter env) — pallas can't run on CPU test meshes."""
        fused = (
            resolve_attention_impl(
                self.attention, L, platform=self.platform,
                remat=self.remat, head_dim=self.config.head_dim,
            )
            == "fused"
        )
        if fused:
            return True, False, None, None
        import os

        from acco_tpu.ops.banded_attention import supports_banded_attention

        banded_local = (
            attention_mask is None
            # 'auto' only: an explicit 'xla' must stay the pure einsum
            # program (it is the A/B baseline and the test oracle)
            and self.attention == "auto"
            and supports_banded_attention(
                L, self.config.head_dim, self.config.window_size
            )
            and (
                (self.platform or jax.devices()[0].platform) == "tpu"
                or bool(os.environ.get("ACCO_FUSED_ATTN_INTERPRET"))
            )
        )
        return (
            False,
            banded_local,
            attention_mask_bias(L, 0, attention_mask),
            None
            if banded_local
            else attention_mask_bias(
                L, self.config.window_size, attention_mask
            ),
        )

    def _block_body(
        self, n_heads, tp_psum, *, cp=False, fused=False, pad_mask=None,
        banded_local=False, global_bias=None, local_bias=None,
        positions=None, kv_positions_fn=None, collect_kv=False,
    ):
        """One GPT-Neo block as a scan body over ``(layer, window)`` —
        shared by ``hidden`` (all layers) and ``stage_blocks`` (a
        pipeline stage's sub-stack), which run the scan under the device
        scope ``model/block``; the halves carry ``model/attn`` and
        ``model/mlp`` here. ``collect_kv``: stack each layer's
        K/V as scan outputs ([B, L, H, D] page-row layout) — the serving
        prefill's cache tap."""
        eps = self.config.layer_norm_epsilon

        def block(x, scanned):
            layer, window = scanned
            with jax.named_scope("model/attn"):
                h = layer_norm(x, layer["ln1_scale"], layer["ln1_bias"], eps)
                # [D, 3, Dh/tp] local qkv thirds, flattened to one matmul
                w_qkv = layer["w_qkv"]
                qkv = h @ w_qkv.reshape(w_qkv.shape[0], -1)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = split_heads(q, n_heads)
                k = split_heads(k, n_heads)
                v = split_heads(v, n_heads)
                # GPT-Neo quirk: no 1/sqrt(head_dim) scaling on the scores.
                if cp:
                    attn = windowed_ring_attention(
                        q, k, v, self.sequence_axis, window, positions,
                        kv_positions_fn, scale=1.0,
                    )
                elif fused:
                    from acco_tpu.ops.banded_attention import (
                        banded_dot_product_attention,
                        supports_banded_attention,
                    )
                    from acco_tpu.ops.fused_attention import (
                        fused_dot_product_attention,
                    )

                    L = q.shape[2]
                    W = self.config.window_size
                    if pad_mask is None and supports_banded_attention(
                        L, self.config.head_dim, W
                    ):
                        # The per-layer window is traced (one scanned body
                        # serves all layers) but takes only two values: 0
                        # (global) and the STATIC config window. Branch at
                        # runtime; the local branch's banded kernel computes
                        # only the [L, W+tile] key band instead of the full
                        # [L, L] tile it would mask ~3/4 away.
                        attn = jax.lax.cond(
                            window == 0,
                            lambda q, k, v: fused_dot_product_attention(
                                q, k, v, window=0, scale=1.0
                            ),
                            lambda q, k, v: banded_dot_product_attention(
                                q, k, v, window=W, scale=1.0
                            ),
                            q, k, v,
                        )
                    else:
                        # padding masks (finetune) keep the one-kernel path:
                        # the traced window rides into the kernel via SMEM;
                        # the unscaled-score quirk is preserved, scale=1.0
                        attn = fused_dot_product_attention(
                            q, k, v, pad_mask=pad_mask, window=window, scale=1.0
                        )
                elif banded_local:
                    # einsum plan, banded local layers: global layers keep
                    # the einsum, local layers skip the
                    # out-of-window score work entirely (past the full-tile
                    # kernel's envelope, L > 2048)
                    from acco_tpu.ops.banded_attention import (
                        banded_dot_product_attention,
                    )

                    attn = jax.lax.cond(
                        window == 0,
                        lambda q, k, v: dot_product_attention(
                            q, k, v, global_bias, scale=1.0
                        ),
                        lambda q, k, v: banded_dot_product_attention(
                            q, k, v, window=self.config.window_size, scale=1.0
                        ),
                        q, k, v,
                    )
                else:
                    bias = jnp.where(window == 0, global_bias, local_bias)
                    attn = dot_product_attention(q, k, v, bias, scale=1.0)
                # row-split wo: psum the partial, THEN the replicated bias
                x = x + tp_psum(merge_heads(attn) @ layer["wo"]) + layer["wo_bias"]
            with jax.named_scope("model/mlp"):
                h = layer_norm(x, layer["ln2_scale"], layer["ln2_bias"], eps)
                mlp = (
                    gelu_new(h @ layer["w_fc"] + layer["b_fc"]) @ layer["w_proj"]
                )
                out = x + tp_psum(mlp) + layer["b_proj"]
            if collect_kv:
                return out, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
            return out, None

        return block

    # -- serving surface (acco_tpu/serve) -----------------------------------

    def kv_spec(self) -> tuple[int, int, int]:
        """(n_layers, n_heads, head_dim) — the per-token KV-cache row
        shape the paged pool allocates (serve/kv_cache.CacheSpec);
        GPT-Neo has no GQA, so KV heads == query heads."""
        cfg = self.config
        return cfg.num_layers, cfg.num_heads, cfg.head_dim

    def _check_serve(self) -> None:
        if self.sequence_axis or self.tensor_axis:
            raise ValueError(
                "the serving decode path is single-replica: build the "
                "model without sequence_axis/tensor_axis"
            )

    def prefill(self, params: dict, input_ids: jax.Array):
        """Serving prefill (see LlamaModel.prefill for the padding
        contract): the plain einsum plan with per-layer window-selected
        biases — always, so the committed cache rows are bit-identical
        to what the decode step's einsum attention replays.

        Returns ``(logits [B, L, V] f32, k, v [n_layers, B, L, H, D])``.
        """
        cfg = self.config
        self._check_serve()
        L = input_ids.shape[1]
        if L > cfg.max_position_embeddings:
            raise ValueError(
                f"prefill length {L} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}"
            )
        x = params["wte"][input_ids] + params["wpe"][jnp.arange(L)][None, :, :]
        windows = jnp.asarray(cfg.layer_windows, jnp.int32)
        body = self._block_body(
            cfg.num_heads, lambda t: t,
            global_bias=attention_mask_bias(L, 0, None),
            local_bias=attention_mask_bias(L, cfg.window_size, None),
            collect_kv=True,
        )
        with jax.named_scope("model/block"):
            x, (k, v) = jax.lax.scan(body, x, (params["layers"], windows))
        x = layer_norm(
            x, params["lnf_scale"], params["lnf_bias"], cfg.layer_norm_epsilon
        )
        logits = jnp.einsum(
            "bld,dv->blv", x, self.lm_head(params),
            preferred_element_type=jnp.float32,
        )
        return logits, k, v

    def decode(
        self,
        params: dict,
        token_ids: jax.Array,  # [R] one token per request slot
        positions: jax.Array,  # [R] absolute position being decoded
        k_ctx: jax.Array,  # [n_layers, R, C, H, D] gathered cache rows
        v_ctx: jax.Array,
        kv_positions: jax.Array,  # [C] or [R, C] absolute row positions
        band=None,  # optional (k_band, v_band [n_layers, R, Cb, H, D],
        #             band_positions [R, Cb]) — the narrow window gather
    ):
        """One continuous-batching decode step. The per-layer window is
        traced data (same one-body-serves-both-kinds scheme as training);
        when the engine passes ``band``, local layers read only the
        sliding window's worth of pages (serve/kv_cache.gather_band —
        the paged analogue of the banded kernel's key band) instead of
        the full gathered context, so long-context decode cost on those
        layers stays O(window) like the training-side band structure.

        Returns ``(logits [R, V] f32, k_new, v_new [n_layers, R, H, D])``.
        """
        from acco_tpu.ops.attention import cached_attention

        cfg = self.config
        self._check_serve()
        eps = cfg.layer_norm_epsilon
        W = cfg.window_size
        x = (
            params["wte"][token_ids][:, None, :]
            + params["wpe"][positions][:, None, :]
        )
        windows = jnp.asarray(cfg.layer_windows, jnp.int32)
        if band is None:
            xs = (params["layers"], windows, k_ctx, v_ctx)
        else:
            k_band, v_band, band_positions = band
            xs = (params["layers"], windows, k_ctx, v_ctx, k_band, v_band)

        def block(x, scanned):
            if band is None:
                layer, window, kc, vc = scanned
            else:
                layer, window, kc, vc, kb, vb = scanned
            h = layer_norm(x, layer["ln1_scale"], layer["ln1_bias"], eps)
            w_qkv = layer["w_qkv"]
            qkv = h @ w_qkv.reshape(w_qkv.shape[0], -1)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = split_heads(q, cfg.num_heads)
            k = split_heads(k, cfg.num_heads)
            v = split_heads(v, cfg.num_heads)
            # GPT-Neo quirk: no 1/sqrt(head_dim) scaling (scale=1.0).
            if band is None:
                attn = cached_attention(
                    q, kc, vc, k, v, positions, kv_positions,
                    window=window, scale=1.0,
                )
            else:
                attn = jax.lax.cond(
                    window == 0,
                    lambda: cached_attention(
                        q, kc, vc, k, v, positions, kv_positions,
                        window=0, scale=1.0,
                    ),
                    lambda: cached_attention(
                        q, kb, vb, k, v, positions, band_positions,
                        window=W, scale=1.0,
                    ),
                )
            x = x + merge_heads(attn) @ layer["wo"] + layer["wo_bias"]
            h = layer_norm(x, layer["ln2_scale"], layer["ln2_bias"], eps)
            mlp = (
                gelu_new(h @ layer["w_fc"] + layer["b_fc"]) @ layer["w_proj"]
            )
            return x + mlp + layer["b_proj"], (k[:, :, 0, :], v[:, :, 0, :])

        x, (k_new, v_new) = jax.lax.scan(block, x, xs)
        x = layer_norm(
            x, params["lnf_scale"], params["lnf_bias"], cfg.layer_norm_epsilon
        )
        logits = jnp.einsum(
            "bld,dv->blv", x, self.lm_head(params),
            preferred_element_type=jnp.float32,
        )
        return logits[:, 0], k_new, v_new

    # -- pipeline-parallel surface (parallel/pp.py) -------------------------

    def pp_param_specs(self) -> dict:
        """Pipeline split spec per leaf (parallel/tp.TpLayout): stacked
        layer leaves split on the layer-stack dim 0; the tied ``wte``
        splits on the vocab dim (the pp loss is the vocab-parallel CE,
        and the lookup reconstructs by psum — see LlamaModel); the small
        learned position table and final norm stay replicated.

        Thin shim: the split choices live in the ``params:gpt_neo:pp``
        rule table (acco_tpu/sharding/tables.py)."""
        from acco_tpu.sharding import model_split_specs

        return model_split_specs(self, "pp")

    def pp_embed(self, params: dict, input_ids: jax.Array, axis_name: str):
        """Vocab-split token lookup (psum-reconstructed) + the replicated
        learned position embedding."""
        from acco_tpu.models.layers import vocab_parallel_embed

        L = input_ids.shape[1]
        # pp x sp: this shard may hold an L-token chunk of a ws*L global
        # sequence — the shared CP prelude yields its absolute positions
        # (and validates the position-table range)
        positions, _ = self._cp_positions(L)
        tok = vocab_parallel_embed(params["wte"], input_ids, axis_name)
        return tok + params["wpe"][positions][None, :, :]

    def stage_blocks(
        self,
        layers: dict,
        x: jax.Array,  # [B, L, D]
        attention_mask: Optional[jax.Array] = None,
        stage_index=None,
        pp: int = 1,
    ) -> jax.Array:
        """Run one pipeline stage's contiguous layer sub-stack. GPT-Neo's
        per-layer window pattern is absolute-layer-indexed, so the
        stage's window slice is cut from the full table at
        ``stage_index * layers_per_stage`` (a traced index —
        ``dynamic_slice`` keeps the body SPMD-uniform across stages)."""
        cfg = self.config
        L = x.shape[1]  # sp: the device-local chunk length
        cp = self.sequence_axis is not None
        # pp x sp: windowed ring attention runs INSIDE every pipeline
        # stage — the shared CP prelude yields the shard's absolute
        # positions and ring KV position fn, with the stage's window
        # slice riding the scan as traced data.
        positions, kv_positions_fn = self._cp_positions(L, attention_mask)
        if not cp:
            positions = kv_positions_fn = None
        n_stage = jax.tree.leaves(layers)[0].shape[0]
        windows_full = jnp.asarray(cfg.layer_windows, jnp.int32)
        if stage_index is None:
            if n_stage != cfg.num_layers:
                # stage 0's pattern would silently apply to every stage
                raise ValueError(
                    "stage_blocks on a layer SUB-stack needs stage_index: "
                    "GPT-Neo's global/local window pattern is absolute-"
                    "layer-indexed"
                )
            windows = windows_full
        else:
            windows = jax.lax.dynamic_slice_in_dim(
                windows_full, stage_index * n_stage, n_stage
            )
        fused, banded_local, global_bias, local_bias = (
            (False, False, None, None)
            if cp
            else self._dense_attn_plan(L, attention_mask)
        )
        # tp x pp composition: each (stage, tp-shard) holds head/ffn
        # slices of its stage's layers; same Megatron psums as hidden()
        tp = (
            jax.lax.axis_size(self.tensor_axis) if self.tensor_axis else 1
        )
        if tp > 1 and cfg.num_heads % tp:
            raise ValueError(
                f"tensor parallelism size {tp} must divide num_heads="
                f"{cfg.num_heads}"
            )
        tp_psum = (
            (lambda t: jax.lax.psum(t, self.tensor_axis))
            if tp > 1
            else (lambda t: t)
        )
        body = wrap_remat(
            self._block_body(
                cfg.num_heads // tp, tp_psum,
                cp=cp,
                fused=fused, pad_mask=attention_mask if fused else None,
                banded_local=banded_local,
                global_bias=global_bias, local_bias=local_bias,
                positions=positions, kv_positions_fn=kv_positions_fn,
            ),
            self.remat,
        )
        with jax.named_scope("model/block"):
            x, _ = jax.lax.scan(
                body, x, (layers, windows), unroll=self.scan_unroll
            )
        return x

    def finalize(self, params: dict, x: jax.Array) -> jax.Array:
        """Final layer norm over the last stage's hidden states."""
        return layer_norm(
            x, params["lnf_scale"], params["lnf_bias"],
            self.config.layer_norm_epsilon,
        )
