"""Serving entry point — continuous-batching inference from any checkpoint.

The missing half of the north star (ROADMAP item 5): training produces
checkpoints, this CLI turns them into tokens. It wires the four serve
layers together::

    python serve.py --config config/serve/tiny-cpu.yaml \
        --resume_from outputs/<run>/checkpoints
    curl -s localhost:8700/generate -d '{"prompt": "hello", "max_new_tokens": 16}'

``--resume_from`` accepts either a checkpoint root (the newest *valid*
``step_*`` wins, via the same validating fallback chain training resume
uses) or a specific ``step_*`` dir. Params load from the portable
``params.npz`` when the save exported one, else from a raw Orbax restore
of the train state's ``flat_params`` vector — so periodic saves serve too.

Cold-start overlap: the engine's AOT warmup (bucketed prefill programs +
the decode/sample programs) starts BEFORE the checkpoint restore, so by
the time params are on device the programs are compiled (or cache-served
from a previous launch of the same config — the compile-once story).

``--prompt`` runs one generation synchronously and exits (no HTTP) — the
smoke-test mode.

Resilience wiring (ISSUE 20): admission control (``max_waiting`` /
``kv_watermark`` config keys → 429/503 + Retry-After), graceful drain on
SIGTERM or ``POST /admin/drain`` (finish in-flight within
``--drain-budget-s``, then stop), and serve chaos via ``--chaos
'kind@step,...'`` or ``ACCO_SERVE_CHAOS`` (kinds: engine_raise,
slow_decode, kv_exhaust, client_abandon). Drill it with
``tools/load_harness.py``.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading

import yaml


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", default="config/serve/tiny-cpu.yaml",
                   help="serve config yaml (model + cache sizing + http)")
    p.add_argument("--resume_from", required=True,
                   help="checkpoint root or a specific step_* dir")
    p.add_argument("--host", default=None, help="override config host")
    p.add_argument("--port", type=int, default=None, help="override config port")
    p.add_argument("--prompt", default=None,
                   help="one-shot: generate for this prompt and exit")
    p.add_argument("--max-new-tokens", type=int, default=None)
    p.add_argument("--temperature", type=float, default=None)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-warmup", action="store_true",
                   help="skip AOT warmup (programs compile on first use)")
    p.add_argument("--warmup-timeout", type=float, default=600.0)
    p.add_argument("--drain-budget-s", type=float, default=None,
                   help="graceful-drain budget for SIGTERM / /admin/drain "
                        "(default: config drain_budget_s or 30)")
    p.add_argument("--chaos", default=None,
                   help="serve fault spec 'kind@step,...' "
                        "(ACCO_SERVE_CHAOS also honored)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(sys.argv[1:] if argv is None else argv)
    repo_root = os.path.dirname(os.path.abspath(__file__))

    logging.basicConfig(
        level=logging.INFO,
        format="[%(asctime)s][%(name)s][%(levelname)s] - %(message)s",
    )
    log = logging.getLogger("acco_tpu.serve")

    with open(args.config) as f:
        cfg = yaml.safe_load(f) or {}

    from acco_tpu.utils.checkpoint import resolve_serving_checkpoint

    step_dir = resolve_serving_checkpoint(args.resume_from, log=log)

    import jax

    # Persistent compile cache: a relaunch deserializes the bucket
    # programs instead of compiling them.
    cache_dir = cfg.get("compile_cache_dir")
    if cache_dir:
        from acco_tpu.compile import setup_compilation_cache

        log.info("compile cache: %s", setup_compilation_cache(cache_dir, log=log))

    import jax.numpy as jnp

    from acco_tpu.data.tokenizer import load_tokenizer
    from acco_tpu.models.registry import build_model

    model_name = cfg.get("model", "tiny")
    with open(os.path.join(repo_root, "config", "model", model_name + ".yaml")) as f:
        model_cfg = yaml.safe_load(f)
    param_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.get("param_dtype", "bfloat16")
    ]
    model = build_model(model_cfg, repo_root=repo_root, param_dtype=param_dtype)
    tokenizer = load_tokenizer(model_cfg.get("tokenizer"), log)

    from acco_tpu.serve import ContinuousBatchingScheduler, ServeEngine

    engine = ServeEngine(
        model,
        page_size=int(cfg.get("page_size", 16)),
        num_pages=int(cfg.get("num_pages", 256)),
        max_pages_per_seq=int(cfg.get("max_pages_per_seq", 8)),
        max_slots=int(cfg.get("max_slots", 4)),
        buckets=cfg.get("buckets"),
        top_k_max=int(cfg.get("top_k_max", 64)),
        cache_dtype=cfg.get("cache_dtype"),
        log=log,
    )
    log.info(
        "engine: max_context=%d (%d pages x %d), %d slots, pool %.1f MiB",
        engine.max_context, engine.max_pages_per_seq, engine.page_size,
        engine.max_slots, engine.spec.total_bytes / 2**20,
    )

    # Warmup first, THEN restore: background threads lower+compile every
    # bucket from avals while the checkpoint streams in (OVERLAP.md).
    if not args.no_warmup:
        engine.start_warmup()

    import numpy as np
    from jax.flatten_util import ravel_pytree

    from acco_tpu.utils.checkpoint import load_flat_params

    template = model.init(jax.random.PRNGKey(0))
    flat_template, unravel = ravel_pytree(template)
    flat = load_flat_params(
        step_dir, int(flat_template.size), log=log, template=template
    )
    params = unravel(jnp.asarray(np.asarray(flat), dtype=flat_template.dtype))
    del template, flat
    engine.set_params(params)

    if not args.no_warmup:
        engine.finish_warmup(timeout=args.warmup_timeout)

    from acco_tpu.resilience import ServeFaultInjector

    injector = (
        ServeFaultInjector.from_config(args.chaos, log=log)
        if args.chaos is not None
        else ServeFaultInjector.from_config(
            cfg.get("fault_injection") or os.environ.get(
                ServeFaultInjector.ENV_VAR
            ),
            log=log,
        )
    )
    if injector is not None:
        log.warning("serve chaos armed: %s", injector.specs)

    scheduler = ContinuousBatchingScheduler(
        engine,
        prefills_per_step=int(cfg.get("prefills_per_step", 1)),
        max_waiting=int(cfg.get("max_waiting", 64)),
        kv_watermark=float(cfg.get("kv_watermark", 0.95)),
        retry_after_s=float(cfg.get("retry_after_s", 1.0)),
        fault_injector=injector,
        log=log,
    )

    defaults = {
        "max_new_tokens": 32, "temperature": 0.0, "top_k": 0,
        **(cfg.get("defaults") or {}),
    }
    if args.max_new_tokens is not None:
        defaults["max_new_tokens"] = args.max_new_tokens
    if args.temperature is not None:
        defaults["temperature"] = args.temperature
    if args.top_k is not None:
        defaults["top_k"] = args.top_k

    if args.prompt is not None:
        from acco_tpu.serve import GenRequest
        from acco_tpu.serve.server import encode_prompt

        req = GenRequest(
            prompt=encode_prompt(tokenizer, args.prompt),
            max_new_tokens=int(defaults["max_new_tokens"]),
            temperature=float(defaults["temperature"]),
            top_k=int(defaults["top_k"]),
            seed=args.seed,
        )
        scheduler.submit(req)
        while not req.done.is_set():
            scheduler.step()
        text = tokenizer.decode(req.generated)
        log.info(
            "generated %d tokens (finish=%s): %r",
            len(req.generated), req.finish_reason, text,
        )
        print(text)
        return {"text": text, "tokens": req.generated,
                "finish_reason": req.finish_reason}

    from acco_tpu.serve import ServingLoop, serve_http

    loop = ServingLoop(scheduler, log=log).start()
    host = args.host or cfg.get("host", "127.0.0.1")
    port = args.port if args.port is not None else int(cfg.get("port", 8700))
    drain_budget_s = (
        args.drain_budget_s
        if args.drain_budget_s is not None
        else float(cfg.get("drain_budget_s", 30.0))
    )
    httpd = serve_http(
        loop,
        tokenizer,
        host=host,
        port=port,
        model_name=model_name,
        defaults=defaults,
        request_timeout_s=float(cfg.get("request_timeout_s", 300.0)),
        drain_budget_s=drain_budget_s,
    )

    # SIGTERM = the preemption notice (same contract as training's
    # ShutdownHandler): drain off the signal handler's thread — finish
    # in-flight requests within the budget, then unblock serve_forever.
    drain_threads: list = []

    def _sigterm(signum, frame):
        log.info("SIGTERM: draining (budget %.1fs)", drain_budget_s)

        def _drain_and_shutdown():
            try:
                loop.drain(budget_s=drain_budget_s)
            finally:
                httpd.shutdown()

        t = threading.Thread(
            target=_drain_and_shutdown, name="acco-serve-drain", daemon=True
        )
        drain_threads.append(t)
        t.start()

    signal.signal(signal.SIGTERM, _sigterm)

    log.info("serving %s from %s on http://%s:%d", model_name, step_dir,
             host, httpd.server_address[1])
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        log.info("shutting down")
    finally:
        for t in drain_threads:
            t.join(timeout=drain_budget_s + 30.0)
        httpd.server_close()
        loop.stop()
    return {}


if __name__ == "__main__":
    main()
