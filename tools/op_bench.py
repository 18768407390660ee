#!/usr/bin/env python
"""In-jit repetition harness for op-level kernel timings (VERDICT r4 #6).

A per-dispatch host floor of a few ms makes single-dispatch op
timings useless (BASELINE.md: a fused fwd+bwd pair timed *below* fwd
alone), and the ring block kernel cannot be measured in-model without a
real sp>=2 mesh. This harness times the op N times INSIDE one jit —
each repetition's input depends on the previous repetition's output
(`x + out * 1e-30`: numerically a no-op at bf16, but a real data
dependency, so XLA can neither CSE the repeated op nor dead-code it) —
at two different N, and reports the slope:

    per_op_ms = (t(n2) - t(n1)) / (n2 - n1)

which cancels the dispatch floor, the jit-call overhead, and any
once-per-call prologue exactly, instead of trying to subtract an
estimate of them.

    python tools/op_bench.py --op block [--append] [--seq 512,1024,2048]
    python tools/op_bench.py --op attn
    python tools/op_bench.py --op ce

Ops (shapes default to the flagship pretrain class B=8, H=12, D=64):
  block — ops/block_attention.block_attention_partial (the ring/CP hot
          op, diag=True self-hop form) vs the jnp block it replaces
          (f32 scores in HBM, ring_attention.py:123-145), fwd and
          fwd+bwd, per Lc.
  attn  — ops/fused_attention vs the XLA einsum dataflow, same grid.
  ce    — ops/fused_ce.fused_ce_loss vs the materialized [N, V] f32
          CE, flagship vocab.
  banded — ops/banded_attention (GPT-Neo local window layers, W=256,
          the unscaled-score quirk) vs the full-tile kernel vs the
          masked einsum, per L.

Each measurement prints one JSON line; --append writes ledger rows to
results.csv (bench=op_<op>_<impl>, with the fwd / fwd+bwd passes in the
op_fwd_ms / op_fwd_bwd_ms columns, schema-merged like bench.py).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

B, H, D = 8, 12, 64
VOCAB = 50304
HIDDEN = 768


def _chain(op_fn, x0, n, static_args):
    """Run op_fn n times inside one jit with a forced data dependency:
    rep i+1 consumes ``x + out_i * 1e-30`` (bf16-exact no-op, un-CSE-able).
    Returns the compiled zero-arg callable."""

    @jax.jit
    def many(x, *rest):
        def body(c, _):
            out = op_fn(c, *rest)
            return c + (out * 1e-30).astype(c.dtype), ()

        y, _ = lax.scan(body, x, None, length=n)
        return y

    # operands ride as jit ARGUMENTS — closing over them would bake them
    # in as constants and invite multi-second XLA constant folding of
    # e.g. the padded [D, V] head matrix
    return functools.partial(many, x0, *static_args)


_REPS = (6, 30)  # overridable via --reps for CPU-interpreter smoke runs


def _slope_ms(op_fn, x0, static_args, n1=None, n2=None, tries=3):
    """per-op ms from the (n1, n2) repetition slope, best of ``tries``."""
    n1 = n1 or _REPS[0]
    n2 = n2 or _REPS[1]
    f1, f2 = (_chain(op_fn, x0, n, static_args) for n in (n1, n2))
    f1().block_until_ready()  # compile once; reused across tries
    f2().block_until_ready()
    best1 = best2 = float("inf")
    for _ in range(tries):
        # best-of per LENGTH, subtracted after — min over per-try
        # differences would let one noisy-slow n1 run fake a tiny (even
        # negative) slope
        t0 = time.perf_counter()
        f1().block_until_ready()  # lint: host-sync-ok
        t1 = time.perf_counter()
        f2().block_until_ready()  # lint: host-sync-ok
        t2 = time.perf_counter()
        best1 = min(best1, t1 - t0)
        best2 = min(best2, t2 - t1)
    return (best2 - best1) / (n2 - n1) * 1e3


def _grad_op(scalar_of_x):
    """fwd+bwd form: the chained quantity is the gradient (same shape as
    x), so every repetition runs the op's forward AND backward."""

    def op(x, *args):
        return jax.grad(lambda x_: scalar_of_x(x_, *args))(x)

    return op


# -- block: the ring/CP hot op ------------------------------------------------


def _jnp_block(q, k, v, scale):
    """The jnp block this kernel replaces — f32 scores/matmuls + diag
    mask, verbatim semantics of ring_attention.block_update's xla path."""
    scores = (
        jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        )
        * scale
    )
    Lc = q.shape[2]
    i = jnp.arange(Lc)[:, None]
    j = jnp.arange(Lc)[None, :]
    scores = scores + jnp.where(j <= i, 0.0, -1e9)
    m = scores.max(-1)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


def bench_block(seqs, append):
    from acco_tpu.ops.block_attention import block_attention_partial

    rows = []
    for Lc in seqs:
        key = jax.random.PRNGKey(0)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, Lc, D)).astype(
                jnp.bfloat16
            )
            for i in range(3)
        )
        scale = D**-0.5

        def fused_fwd(q_, k_, v_):
            o, m, l = block_attention_partial(q_, k_, v_, diag=True, scale=scale)
            return o

        def fused_scalar(q_, k_, v_):
            o, m, l = block_attention_partial(q_, k_, v_, diag=True, scale=scale)
            return (o / jnp.maximum(l, 1e-30)[..., None]).sum()

        def jnp_fwd(q_, k_, v_):
            o, m, l = _jnp_block(q_, k_, v_, scale)
            return o

        def jnp_scalar(q_, k_, v_):
            o, m, l = _jnp_block(q_, k_, v_, scale)
            return (o / jnp.maximum(l, 1e-30)[..., None]).sum()

        for impl, fwd, scalar in (
            ("fused", fused_fwd, fused_scalar),
            ("jnp", jnp_fwd, jnp_scalar),
        ):
            fwd_ms = _slope_ms(fwd, q, (k, v))
            fb_ms = _slope_ms(_grad_op(scalar), q, (k, v))
            rows.append(
                dict(op="block", impl=impl, seq=Lc, fwd_ms=round(fwd_ms, 4),
                     fwd_bwd_ms=round(fb_ms, 4))
            )
            print(json.dumps(rows[-1]))
    _emit(rows, append)
    return rows


# -- attn: full-sequence fused attention vs the einsum dataflow ---------------


def bench_attn(seqs, append):
    from acco_tpu.ops.attention import dot_product_attention
    from acco_tpu.ops.fused_attention import fused_dot_product_attention

    rows = []
    for L in seqs:
        key = jax.random.PRNGKey(1)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, L, D)).astype(
                jnp.bfloat16
            )
            for i in range(3)
        )
        i_ = jnp.arange(L)[:, None]
        j_ = jnp.arange(L)[None, :]
        bias = jnp.where(j_ <= i_, 0.0, -1e9)[None, None].astype(jnp.float32)

        for impl, fn in (
            ("fused", lambda q_, k_, v_: fused_dot_product_attention(q_, k_, v_)),
            ("xla", lambda q_, k_, v_: dot_product_attention(q_, k_, v_, bias)),
        ):
            fwd_ms = _slope_ms(fn, q, (k, v))
            fb_ms = _slope_ms(
                _grad_op(lambda q_, k_, v_, f=fn: f(q_, k_, v_).sum()),
                q, (k, v),
            )
            rows.append(
                dict(op="attn", impl=impl, seq=L, fwd_ms=round(fwd_ms, 4),
                     fwd_bwd_ms=round(fb_ms, 4))
            )
            print(json.dumps(rows[-1]))
    _emit(rows, append)
    return rows


# -- banded: GPT-Neo window layers — banded vs full-tile vs einsum ------------


def bench_banded(seqs, append):
    from acco_tpu.ops.attention import (
        attention_mask_bias,
        dot_product_attention,
    )
    from acco_tpu.ops.banded_attention import banded_dot_product_attention
    from acco_tpu.ops.fused_attention import fused_dot_product_attention

    W = 256  # GPT-Neo window; scale=1.0 (the unscaled-score quirk)
    rows = []
    for L in seqs:
        key = jax.random.PRNGKey(3)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, L, D)).astype(
                jnp.bfloat16
            )
            for i in range(3)
        )
        bias = attention_mask_bias(L, W, None)
        impls = [
            ("banded", lambda q_, k_, v_: banded_dot_product_attention(
                q_, k_, v_, window=W, scale=1.0
            )),
            ("xla", lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, bias, scale=1.0
            )),
        ]
        if L <= 2048:
            impls.insert(1, (
                "fulltile",
                lambda q_, k_, v_: fused_dot_product_attention(
                    q_, k_, v_, window=W, scale=1.0
                ),
            ))
        for impl, fn in impls:
            fwd_ms = _slope_ms(fn, q, (k, v))
            fb_ms = _slope_ms(
                _grad_op(lambda q_, k_, v_, f=fn: f(q_, k_, v_).sum()),
                q, (k, v),
            )
            rows.append(
                dict(op="banded", impl=impl, seq=L, fwd_ms=round(fwd_ms, 4),
                     fwd_bwd_ms=round(fb_ms, 4))
            )
            print(json.dumps(rows[-1]))
    _emit(rows, append)
    return rows


# -- ce: fused lm-head+CE vs materialized logits ------------------------------


def bench_ce(seqs, append):
    from acco_tpu.ops.fused_ce import fused_ce_loss
    from acco_tpu.ops.losses import causal_lm_loss

    rows = []
    for L in seqs:
        key = jax.random.PRNGKey(2)
        h = jax.random.normal(key, (B, L, HIDDEN)).astype(jnp.bfloat16)
        w = (
            jax.random.normal(jax.random.fold_in(key, 1), (HIDDEN, VOCAB))
            .astype(jnp.bfloat16)
        )
        labels = jax.random.randint(
            jax.random.fold_in(key, 2), (B, L), 0, VOCAB, dtype=jnp.int32
        )

        def fused_scalar(h_, w_, labels_):
            return fused_ce_loss(h_, w_, labels_)

        def mat_scalar(h_, w_, labels_):
            logits = jnp.einsum(
                "bld,dv->blv", h_, w_, preferred_element_type=jnp.float32
            )
            return causal_lm_loss(logits, labels_)

        for impl, scalar in (("fused", fused_scalar), ("mat", mat_scalar)):
            fb_ms = _slope_ms(_grad_op(scalar), h, (w, labels))
            rows.append(
                dict(op="ce", impl=impl, seq=L, fwd_bwd_ms=round(fb_ms, 4))
            )
            print(json.dumps(rows[-1]))
    _emit(rows, append)
    return rows


def _emit(rows, append):
    if not append:
        return
    from acco_tpu.utils.logs import create_id_run, save_result

    dev = jax.devices()[0]
    for r in rows:
        save_result(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "results.csv",
            ),
            {
                "0_id_run": create_id_run(),
                "bench": f"op_{r['op']}_{r['impl']}",
                "device": getattr(dev, "device_kind", dev.platform),
                "N_workers": 1,
                "seq": r["seq"],
                "op_fwd_ms": r.get("fwd_ms"),
                "op_fwd_bwd_ms": r.get("fwd_bwd_ms"),
            },
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", choices=("block", "attn", "ce", "banded"),
                    default="block")
    ap.add_argument("--seq", default="512,1024,2048")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--reps", default=None, help="n1,n2 slope points")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    args = ap.parse_args()
    seqs = [int(s) for s in args.seq.split(",")]
    global B, H, _REPS
    if args.reps:
        _REPS = tuple(int(x) for x in args.reps.split(","))
    if args.batch:
        B = args.batch
    if args.heads:
        H = args.heads
    platform = jax.devices()[0].platform
    print(f"# op_bench op={args.op} platform={platform}", file=sys.stderr)
    if platform != "tpu" and not (
        os.environ.get("ACCO_FUSED_ATTN_INTERPRET")
        or os.environ.get("ACCO_FUSED_CE_INTERPRET")
    ):
        print(
            "# WARNING: not on TPU — pallas ops need the interpreter "
            "(ACCO_FUSED_*_INTERPRET=1); timings here are smoke only",
            file=sys.stderr,
        )
    {"block": bench_block, "attn": bench_attn, "ce": bench_ce,
     "banded": bench_banded}[args.op](
        seqs, args.append
    )


if __name__ == "__main__":
    main()
