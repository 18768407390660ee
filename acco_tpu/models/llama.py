"""Llama-family causal LM (RMSNorm, RoPE, SwiGLU, GQA) as a pure pytree.

Capability parity with the reference's Llama finetuning path (HF
``LlamaForCausalLM``, `/root/reference/README.md:78-95`), designed
TPU-first: stacked-layer ``lax.scan`` body, bfloat16 parameters, float32
softmax, optional ``jax.checkpoint`` rematerialisation.

The block carries two options that make it OLMoE's (``model_type: "olmoe"``,
HF ``modeling_olmoe.py``): ``qk_norm`` (RMSNorm over the whole query and key
projections, before the split into heads and RoPE) and ``num_experts`` > 0
(the SwiGLU MLP becomes ``num_experts`` SwiGLU experts of width
``intermediate_size``, ``num_experts_per_tok`` a token, dropless:
ops/moe.py). With both off the block is the dense Llama block, bit for bit.
An expert model trains data-parallel (dp, ZeRO-1) only; tp, pp, context
parallelism and serving refuse it by name (sharding/tables.refuse_experts).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp

from acco_tpu.models.layers import (
    apply_rope,
    merge_heads,
    normal_init,
    rms_norm,
    rope_angles,
    split_heads,
    wrap_remat,
)
from acco_tpu.ops.attention import (
    attention_mask_bias,
    dot_product_attention,
    flash_dot_product_attention,
    normalize_attention_impl,
    resolve_attention_impl,
)
from acco_tpu.ops.ring_attention import (
    ring_attention,
    zigzag_positions,
    zigzag_ring_attention,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 12
    max_position_embeddings: int = 1024
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    bos_token_id: int = 50256
    eos_token_id: int = 50256
    # OLMoE's two departures from the Llama block (module docstring)
    qk_norm: bool = False
    num_experts: int = 0  # 0 = the dense SwiGLU MLP
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False  # renormalise the top-k gates to sum to 1
    router_aux_loss_coef: float = 0.0  # load-balancing loss
    router_z_loss_coef: float = 0.0  # router z-loss

    def __post_init__(self):
        if self.num_experts and not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError(
                f"num_experts={self.num_experts} needs 0 < num_experts_per_tok <= "
                f"num_experts, got {self.num_experts_per_tok}"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    # Hugging Face's names for this repo's keys. A file may hold both: the
    # repo's own key wins (a benchmark configuration keeps the published
    # ``num_hidden_layers`` beside the ``num_layers`` it runs).
    _HF_KEYS = {
        "num_hidden_layers": "num_layers",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads",
    }

    @classmethod
    def from_json(cls, path: str) -> "LlamaConfig":
        with open(path) as f:
            raw = json.load(f)
        unsupported = [k for k in ("attention_bias", "clip_qkv", "rope_scaling") if raw.get(k)]
        if unsupported:
            raise ValueError(f"{path}: {unsupported} set, which LlamaModel does not implement")
        for hf_key, key in cls._HF_KEYS.items():
            if hf_key in raw:
                raw.setdefault(key, raw[hf_key])
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in fields and v is not None})


class LlamaModel:
    """init/apply pair over a dict pytree; no framework module state."""

    def __init__(
        self,
        config: LlamaConfig,
        param_dtype=jnp.bfloat16,
        remat=False,
        attention: str = "auto",
        sequence_axis: str | None = None,
        scan_unroll: int | bool = 1,
        zigzag: bool = False,
        tensor_axis: str | None = None,
        vocab_pad_to: int | None = None,
        platform: str | None = None,  # pin 'tpu' for AOT proof builders
    ):
        """``remat``: False | True (full-block jax.checkpoint) | 'dots'
        (checkpoint with the dots-saveable policy: projection/MLP matmul
        outputs are stored, attention scores and elementwise ops are
        recomputed — most of the memory win at a fraction of the refetch
        FLOPs). ``attention``: 'auto' | 'flash' | 'xla' | 'ring' (see
        resolve_attention_impl). 'ring' = context parallelism: apply()
        must run inside a shard_map whose ``sequence_axis`` shards the
        sequence dim; inputs are the device-local chunks and RoPE uses
        ring-offset absolute positions.

        ``scan_unroll``: unroll factor for the layer scan (True = fully
        unrolled). A fully-unrolled stack is straight-line HLO instead of
        one opaque while op, which lets the latency-hiding scheduler
        interleave the ZeRO-1 ring hops (comm_impl='ring') with per-layer
        compute — the cross-branch overlap ACCO wants. Costs compile time;
        leave at 1 unless overlap matters (multi-chip ACCO)."""
        self.config = config
        self.param_dtype = param_dtype
        self.remat = remat
        self.attention = attention
        self.platform = platform
        self.sequence_axis = sequence_axis
        self.scan_unroll = scan_unroll
        # Zig-zag sequence layout for context parallelism: each shard
        # holds half-chunks (i, 2ws-1-i), balancing causal attention work
        # (ops.ring_attention.zigzag_ring_attention; ~2x less attention
        # compute than the contiguous ring). The TRAIN STEP permutes the
        # batch into this layout (zigzag_permutation); the model only
        # adjusts RoPE positions and the ring kernel.
        self.zigzag = bool(zigzag)
        # Megatron-style tensor parallelism (parallel/tp.py): attention
        # sharded by heads, MLP by the ffn dim, over the ``tensor_axis``
        # mesh axis. apply()/hidden() must then run inside a shard_map
        # carrying that axis, with each shard's local parameter slices
        # (TpLayout.unravel_local); embeddings and norm scales stay
        # replicated per shard.
        self.tensor_axis = tensor_axis
        # Megatron vocab padding (parallel/tp.pad_vocab): the embedding /
        # lm-head tables carry ``vocab_pad_to`` rows so the vocab dim
        # divides tp; padded positions are excluded from the loss
        # (losses real_vocab) and never looked up, so training semantics
        # are bit-identical to the unpadded model.
        self.padded_vocab = int(vocab_pad_to or config.vocab_size)
        if self.padded_vocab < config.vocab_size:
            raise ValueError(
                f"vocab_pad_to={vocab_pad_to} < vocab_size={config.vocab_size}"
            )
        if normalize_attention_impl(attention) == "ring" and not sequence_axis:
            raise ValueError("attention='ring' requires sequence_axis")

    # -- parameters ---------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        cfg, dt = self.config, self.param_dtype
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        D, F, N = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
        Dkv = cfg.num_kv_heads * cfg.head_dim
        std = cfg.initializer_range

        def stack_init(key, shape):
            keys = jax.random.split(key, N)
            return jnp.stack([normal_init(k, shape, std, dt) for k in keys])

        # an expert model draws one more key (the router's), so its other
        # leaves differ from a dense model's of the same seed; a dense
        # model's stay what they were
        E = cfg.num_experts
        ks = jax.random.split(k_layers, 8 if E else 7)
        mlp_in, mlp_out = ((E, D, F), (E, F, D)) if E else ((D, F), (F, D))
        params = {
            "wte": normal_init(k_emb, (self.padded_vocab, D), std, dt),
            "layers": {
                "attn_norm": jnp.ones((N, D), dt),
                "wq": stack_init(ks[0], (D, D)),
                "wk": stack_init(ks[1], (D, Dkv)),
                "wv": stack_init(ks[2], (D, Dkv)),
                "wo": stack_init(ks[3], (D, D)),
                "mlp_norm": jnp.ones((N, D), dt),
                "w_gate": stack_init(ks[4], mlp_in),
                "w_up": stack_init(ks[5], mlp_in),
                "w_down": stack_init(ks[6], mlp_out),
            },
            "final_norm": jnp.ones((D,), dt),
        }
        if cfg.qk_norm:  # over the whole projection, not per head
            params["layers"]["q_norm"] = jnp.ones((N, D), dt)
            params["layers"]["k_norm"] = jnp.ones((N, Dkv), dt)
        if E:
            # [E, D], the layout of HF's gate.weight: a leaf whose last dim is
            # the hidden size unpacks from the flat vector through the view
            # the other leaves use; [D, E] makes XLA re-tile the whole vector
            # as [*, 64], lane-padded to twice its size
            params["layers"]["router"] = stack_init(ks[7], (E, D))
        if not cfg.tie_word_embeddings:
            params["lm_head"] = normal_init(
                k_head, (D, self.padded_vocab), std, dt
            )
        return params

    def unpad_vocab(self, params: dict) -> dict:
        """Strip Megatron vocab padding for export (params.npz, HF
        round-trips): the unpadded pytree matches the plain config arch."""
        if self.padded_vocab == self.config.vocab_size:
            return params
        out = dict(params)
        out["wte"] = params["wte"][: self.config.vocab_size]
        if "lm_head" in params:
            out["lm_head"] = params["lm_head"][:, : self.config.vocab_size]
        return out

    def tp_param_specs(self) -> dict:
        """Tensor-parallel split spec per leaf (parallel/tp.TpLayout):
        None = replicated on every tp shard, int = axis to split. Layer
        leaves carry a leading [num_layers] stack dim, so the head/ffn
        dims are at index 2 (column-split: wq/wk/wv/w_gate/w_up) or 1
        (row-split, psum after: wo/w_down). Embeddings and the lm head
        are vocab-parallel (Megatron): the vocab dim shards over tp —
        replicating the [V, D] tables would dominate per-chip memory at
        the 128k-vocab scale (lookup/logits/CE handling: ``hidden``,
        ``apply``, ops.losses.vocab_parallel_causal_lm_loss). Only the
        tiny norm scales stay replicated. Requires vocab_size % tp == 0
        (pad the config's vocab, e.g. 50257 -> 50304, as Megatron does).

        Thin shim: the split choices live in the ``params:llama:tp``
        rule table (acco_tpu/sharding/tables.py)."""
        from acco_tpu.sharding import model_split_specs

        return model_split_specs(self, "tp")

    # -- forward ------------------------------------------------------------

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, L] int32
        attention_mask: Optional[jax.Array] = None,  # [B, L] 1=real
        *,
        with_aux: bool = False,
    ) -> jax.Array:  # [B, L, V] float32 logits ([B, L, V/tp] local under tp)
        x, aux = self.hidden(params, input_ids, attention_mask, with_aux=True)
        with jax.named_scope("model/lm_head_ce"):
            logits = jnp.einsum(
                "bld,dv->blv",
                x,
                self.lm_head(params),
                preferred_element_type=jnp.float32,
            )
        return (logits, aux) if with_aux else logits

    # -- the objective's auxiliary terms ------------------------------------

    @property
    def has_aux_loss(self) -> bool:
        """The objective holds more than the cross-entropy: ``hidden`` and
        ``apply`` take ``with_aux=True`` and return the terms beside their
        output, and :meth:`aux_loss` weighs them (ops/losses.model_ce)."""
        return self.config.num_experts > 0

    def aux_loss(self, aux: dict) -> jax.Array:
        """What the auxiliary terms add to the cross-entropy."""
        cfg = self.config
        return (
            cfg.router_aux_loss_coef * aux["moe_lb_loss"]
            + cfg.router_z_loss_coef * aux["moe_z_loss"]
        )

    @staticmethod
    def _aux_terms(stats) -> dict:
        """Scalars from the layers' per-sequence router statistics (leaves
        ``[num_layers, B]``; None for a dense model): the two losses are means
        over layers and sequences, the load is each sequence's most loaded
        expert of any layer (1.0 = balanced), averaged over sequences."""
        if stats is None:
            return {}
        return {
            "moe_lb_loss": stats.lb_loss.mean(),
            "moe_z_loss": stats.z_loss.mean(),
            "moe_max_load": stats.max_load.max(axis=0).mean(),
        }

    def lm_head(self, params: dict) -> jax.Array:
        """[D, V] output-projection matrix (wte transposed when tied);
        under tensor parallelism the vocab dim is this shard's slice."""
        if self.config.tie_word_embeddings:
            return params["wte"].T
        return params["lm_head"]

    def embed(self, params: dict, input_ids: jax.Array) -> jax.Array:
        """Token embedding lookup; vocab-parallel under ``tensor_axis``
        (layers.vocab_parallel_embed — the Megatron pattern)."""
        with jax.named_scope("model/embed"):
            if not self.tensor_axis:
                return params["wte"][input_ids]
            from acco_tpu.models.layers import vocab_parallel_embed

            return vocab_parallel_embed(params["wte"], input_ids, self.tensor_axis)

    def hidden(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, L] int32
        attention_mask: Optional[jax.Array] = None,  # [B, L] 1=real
        *,
        with_aux: bool = False,
    ) -> jax.Array:  # [B, L, D] final-norm hidden states, activation dtype
        """``with_aux``: return ``(hidden, aux)``, ``aux`` the objective's
        auxiliary terms as a dict of scalars (:meth:`_aux_terms`; empty for
        a dense model)."""
        cfg = self.config
        L = input_ids.shape[1]  # ring: the device-local chunk length
        impl = resolve_attention_impl(
            self.attention, L, platform=self.platform, remat=self.remat,
            head_dim=cfg.head_dim,
        )
        global_len = L
        if impl == "ring":
            if attention_mask is not None:
                raise ValueError(
                    "attention='ring' does not support padding masks — it "
                    "serves const-len packed sequences; pass "
                    "attention_mask=None"
                )
            # inside shard_map the axis size is static
            global_len = jax.lax.axis_size(self.sequence_axis) * L
        if global_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {global_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}"
            )
        x = self.embed(params, input_ids)  # [B, L, D]
        # flash/ring paths: no [L, L] bias is ever materialized
        bias = attention_mask_bias(L, 0, attention_mask) if impl == "xla" else None
        if impl == "ring" and self.zigzag:
            # non-contiguous shard: positions of half-chunks (i, 2ws-1-i)
            cos, sin = rope_angles(
                L,
                cfg.head_dim,
                cfg.rope_theta,
                positions=zigzag_positions(
                    global_len,
                    jax.lax.axis_size(self.sequence_axis),
                    jax.lax.axis_index(self.sequence_axis),
                ),
            )
        else:
            offset = (
                jax.lax.axis_index(self.sequence_axis) * L
                if impl == "ring"
                else 0
            )
            cos, sin = rope_angles(L, cfg.head_dim, cfg.rope_theta, offset)

        # Tensor parallelism: each shard computes heads/tp attention heads
        # and ffn/tp MLP columns from its local slices; the row-split
        # output projections produce partial sums combined by one psum per
        # sublayer (Megatron pattern; grad-correction story in
        # parallel/tp.py's module docstring).
        tp = (
            jax.lax.axis_size(self.tensor_axis) if self.tensor_axis else 1
        )
        n_heads, n_kv = cfg.num_heads // tp, cfg.num_kv_heads // tp
        if tp > 1 and (cfg.num_heads % tp or cfg.num_kv_heads % tp):
            raise ValueError(
                f"tensor parallelism size {tp} must divide num_heads="
                f"{cfg.num_heads} and num_kv_heads={cfg.num_kv_heads}"
            )

        def tp_psum(t):
            return jax.lax.psum(t, self.tensor_axis) if tp > 1 else t

        body = wrap_remat(
            self._block_body(
                impl, attention_mask, cos, sin, bias, n_heads, n_kv, tp_psum
            ),
            self.remat,
        )
        # the scope holds the scan itself, not only its body: stacking the
        # layers' saved activations and slicing them back out in the
        # backward pass is the block stack's time too
        with jax.named_scope("model/block"):
            x, stats = jax.lax.scan(
                body, x, params["layers"], unroll=self.scan_unroll
            )
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return (x, self._aux_terms(stats)) if with_aux else x

    def _block_body(
        self, impl, attention_mask, cos, sin, bias, n_heads, n_kv, tp_psum,
        *, collect_kv=False,
    ):
        """One transformer block as a scan body — shared by ``hidden`` (all
        layers) and ``stage_blocks`` (a pipeline stage's sub-stack).
        ``collect_kv``: stack each layer's post-RoPE K/V as scan outputs
        ([B, L, Hkv, D] page-row layout) — the serving prefill's cache
        tap."""
        cfg = self.config

        def block(x, layer):
            with jax.named_scope("model/attn"):
                h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)

                def projection(w, norm, heads):
                    y = h @ layer[w]
                    if norm is not None and cfg.qk_norm:
                        # over the whole projection, before the heads split
                        y = rms_norm(y, layer[norm], cfg.rms_norm_eps)
                    return split_heads(y, heads)

                q = projection("wq", "q_norm", n_heads)
                k = projection("wk", "k_norm", n_kv)
                v = projection("wv", None, n_kv)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
                if impl == "fused":
                    from acco_tpu.ops.fused_attention import (
                        fused_dot_product_attention,
                    )

                    ctx = fused_dot_product_attention(q, k, v, attention_mask)
                elif impl == "flash":
                    ctx = flash_dot_product_attention(q, k, v, attention_mask)
                elif impl == "ring":
                    ctx = (
                        zigzag_ring_attention(q, k, v, self.sequence_axis)
                        if self.zigzag
                        else ring_attention(q, k, v, self.sequence_axis)
                    )
                else:
                    ctx = dot_product_attention(q, k, v, bias)
                x = x + tp_psum(merge_heads(ctx) @ layer["wo"])
            stats = None
            with jax.named_scope("model/mlp"):
                h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
                if cfg.num_experts:
                    mlp, stats = self._expert_mlp(h, layer, attention_mask)
                else:
                    mlp = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ layer["w_down"]
                out = x + tp_psum(mlp)
            if collect_kv:
                return out, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
            return out, stats

        return block

    def _expert_mlp(self, h, layer, attention_mask):
        """The block's MLP half as sparse experts (ops/moe.py): float32
        router over all experts, top-k, every assignment computed. Returns
        ``([B, L, D], RouterStats)``."""
        from acco_tpu.ops import moe

        cfg = self.config
        B, L, D = h.shape
        with jax.named_scope("model/moe_router"):
            gates, experts, stats = moe.route(
                h, layer["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob,
                attention_mask,
            )
        k = cfg.num_experts_per_tok
        out = moe.dropless_experts(
            h.reshape(B * L, D),
            gates.reshape(B * L, k),
            experts.reshape(B * L, k),
            layer["w_gate"], layer["w_up"], layer["w_down"],
            platform=self.platform,
        )
        return out.reshape(B, L, D), stats

    # -- serving surface (acco_tpu/serve) -----------------------------------

    def kv_spec(self) -> tuple[int, int, int]:
        """(n_layers, n_kv_heads, head_dim) — the per-token KV-cache row
        shape the paged pool allocates (serve/kv_cache.CacheSpec)."""
        cfg = self.config
        return cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

    def _check_serve(self) -> None:
        from acco_tpu.sharding.tables import refuse_experts

        refuse_experts(self, "serve")
        if self.sequence_axis or self.tensor_axis:
            raise ValueError(
                "the serving decode path is single-replica: build the "
                "model without sequence_axis/tensor_axis"
            )

    def prefill(self, params: dict, input_ids: jax.Array):
        """Serving prefill: the full causal forward that additionally
        returns every layer's post-RoPE K/V for the paged cache
        (acco_tpu/serve/engine.py buckets and compiles this).

        Right-padded prompts need no mask: causal attention means pad
        positions cannot influence real ones, the engine reads logits at
        the last REAL position, and the pad rows' garbage cache entries
        are masked by decode's strict ``kv_pos < q_pos`` until the step
        that overwrites each of them.

        Returns ``(logits [B, L, V] f32, k, v [n_layers, B, L, Hkv, D])``.
        """
        cfg = self.config
        self._check_serve()
        L = input_ids.shape[1]
        if L > cfg.max_position_embeddings:
            raise ValueError(
                f"prefill length {L} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}"
            )
        x = params["wte"][input_ids]
        bias = attention_mask_bias(L, 0, None)
        cos, sin = rope_angles(L, cfg.head_dim, cfg.rope_theta)
        body = self._block_body(
            "xla", None, cos, sin, bias, cfg.num_heads, cfg.num_kv_heads,
            lambda t: t, collect_kv=True,
        )
        with jax.named_scope("model/block"):
            x, (k, v) = jax.lax.scan(body, x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
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
        k_ctx: jax.Array,  # [n_layers, R, C, Hkv, D] gathered cache rows
        v_ctx: jax.Array,
        kv_positions: jax.Array,  # [C] or [R, C] absolute row positions
    ):
        """One continuous-batching decode step over the gathered paged
        cache: each slot reads its own context rows (ops.attention.
        cached_attention — strict ``kv_pos < q_pos`` plus the current
        token via k_new/v_new) and emits this position's K/V for the
        write-back scatter.

        Returns ``(logits [R, V] f32, k_new, v_new [n_layers, R, Hkv, D])``.
        """
        from acco_tpu.models.layers import apply_rope_at
        from acco_tpu.ops.attention import cached_attention

        cfg = self.config
        self._check_serve()
        eps = cfg.rms_norm_eps
        x = params["wte"][token_ids][:, None, :]  # [R, 1, D]
        cos, sin = rope_angles(
            1, cfg.head_dim, cfg.rope_theta, positions=positions
        )  # [R, D/2] per-slot angles

        def block(x, scanned):
            layer, kc, vc = scanned
            h = rms_norm(x, layer["attn_norm"], eps)
            q = split_heads(h @ layer["wq"], cfg.num_heads)
            k = split_heads(h @ layer["wk"], cfg.num_kv_heads)
            v = split_heads(h @ layer["wv"], cfg.num_kv_heads)
            q, k = apply_rope_at(q, cos, sin), apply_rope_at(k, cos, sin)
            ctx = cached_attention(q, kc, vc, k, v, positions, kv_positions)
            x = x + merge_heads(ctx) @ layer["wo"]
            h = rms_norm(x, layer["mlp_norm"], eps)
            mlp = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ layer["w_down"]
            return x + mlp, (k[:, :, 0, :], v[:, :, 0, :])

        x, (k_new, v_new) = jax.lax.scan(
            block, x, (params["layers"], k_ctx, v_ctx)
        )
        x = rms_norm(x, params["final_norm"], eps)
        logits = jnp.einsum(
            "bld,dv->blv", x, self.lm_head(params),
            preferred_element_type=jnp.float32,
        )
        return logits[:, 0], k_new, v_new

    # -- pipeline-parallel surface (parallel/pp.py) -------------------------

    def pp_param_specs(self) -> dict:
        """Pipeline split spec per leaf (parallel/tp.TpLayout — the layout
        machinery is shared): every stacked layer leaf splits on its
        layer-stack dim 0 into ``pp`` contiguous stages.

        The embedding table and lm head split on the VOCAB dim (tied and
        untied): the lookup runs on every stage every tick anyway
        (SPMD-uniform pipeline body), so one psum reconstructs it
        (layers.vocab_parallel_embed), and the loss is the vocab-parallel
        CE over pp on the last stage's broadcast output — every stage
        computes its V/pp slice of the head matmul in parallel instead
        of the last stage serializing the full head, and nobody stores
        more than V/pp rows. At the 128k-vocab 8B this is the difference
        between fitting and not: a replicated head costs ~0.5 GB of bf16
        params plus ~4.5 GB of staged+accumulating f32 ACCO gradients
        per chip. Requires vocab % pp == 0 (pad_vocab, the Megatron
        convention). Only the tiny norm scales stay replicated.

        Thin shim: the split choices live in the ``params:llama:pp``
        rule table (acco_tpu/sharding/tables.py)."""
        from acco_tpu.sharding import model_split_specs

        return model_split_specs(self, "pp")

    def pp_embed(self, params: dict, input_ids: jax.Array, axis_name: str):
        """Token embeddings under the pp vocab-split wte: the lookup is
        SPMD-uniform across stages, reconstructed by one psum."""
        from acco_tpu.models.layers import vocab_parallel_embed

        return vocab_parallel_embed(params["wte"], input_ids, axis_name)

    def stage_blocks(
        self,
        layers: dict,
        x: jax.Array,  # [B, L, D]
        attention_mask: Optional[jax.Array] = None,
        stage_index=None,
        pp: int = 1,
    ) -> jax.Array:
        """Run a contiguous sub-stack of layers (one pipeline stage's
        slice of the scanned stack) over hidden states. Same math as the
        corresponding span of ``hidden`` (shared ``_block_body``); the
        embedding and final norm live in ``pp_embed``/``finalize``.
        ``stage_index``/``pp`` exist for models whose per-layer scanned
        data depends on the absolute layer index (GPT-Neo's windows);
        Llama blocks are position-uniform and ignore them."""
        cfg = self.config
        L = x.shape[1]  # sp: the device-local chunk length
        impl = resolve_attention_impl(
            self.attention, L, platform=self.platform, remat=self.remat,
            head_dim=cfg.head_dim,
        )
        if impl == "ring":
            # pp x sp: the sequence is sharded over sequence_axis inside
            # every pipeline stage — same ring attention + RoPE position
            # handling as hidden()'s CP path (contiguous or zig-zag).
            if attention_mask is not None:
                # same contract as hidden(): the ring carries no
                # per-token masks (const-len packed sequences only)
                raise ValueError(
                    "attention='ring' does not support padding masks — "
                    "pass attention_mask=None"
                )
            ws = jax.lax.axis_size(self.sequence_axis)
            if ws * L > cfg.max_position_embeddings:
                # same contract as hidden(): positions past the config's
                # range would silently extrapolate RoPE
                raise ValueError(
                    f"sequence length {ws * L} exceeds "
                    f"max_position_embeddings {cfg.max_position_embeddings}"
                )
            if self.zigzag:
                cos, sin = rope_angles(
                    L, cfg.head_dim, cfg.rope_theta,
                    positions=zigzag_positions(
                        ws * L, ws, jax.lax.axis_index(self.sequence_axis)
                    ),
                )
            else:
                cos, sin = rope_angles(
                    L, cfg.head_dim, cfg.rope_theta,
                    jax.lax.axis_index(self.sequence_axis) * L,
                )
            bias = None
        else:
            bias = (
                attention_mask_bias(L, 0, attention_mask)
                if impl == "xla"
                else None
            )
            cos, sin = rope_angles(L, cfg.head_dim, cfg.rope_theta)
        # tp x pp composition: each (stage, tp-shard) holds head/ffn
        # slices of its stage's layers; same Megatron psums as hidden()
        tp = (
            jax.lax.axis_size(self.tensor_axis) if self.tensor_axis else 1
        )
        if tp > 1 and (cfg.num_heads % tp or cfg.num_kv_heads % tp):
            raise ValueError(
                f"tensor parallelism size {tp} must divide num_heads="
                f"{cfg.num_heads} and num_kv_heads={cfg.num_kv_heads}"
            )
        tp_psum = (
            (lambda t: jax.lax.psum(t, self.tensor_axis))
            if tp > 1
            else (lambda t: t)
        )
        body = wrap_remat(
            self._block_body(
                impl, attention_mask, cos, sin, bias,
                cfg.num_heads // tp, cfg.num_kv_heads // tp, tp_psum,
            ),
            self.remat,
        )
        with jax.named_scope("model/block"):
            x, _ = jax.lax.scan(body, x, layers, unroll=self.scan_unroll)
        return x

    def finalize(self, params: dict, x: jax.Array) -> jax.Array:
        """Final norm over the last stage's hidden states."""
        return rms_norm(x, params["final_norm"], self.config.rms_norm_eps)
