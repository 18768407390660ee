"""CLI entry point — parity with the reference's Hydra ``main.py``.

Usage (same surface as `/root/reference/main.py:25-71` / `README.md:54-81`)::

    python main.py train=acco data=openwebtext model=gptneo
    python main.py train=acco-ft data=alpaca model=llama3 train.batch_size=2
    python main.py train=ddp data=synthetic train.nb_steps_tot=100

Hydra itself is not a dependency here; ``acco_tpu.configuration`` provides
the same composition semantics (defaults list, group + dotted overrides).
Like Hydra, each run gets a timestamped run dir (``outputs/%Y-%m-%d/
%H-%M-%S``, `/root/reference/config/config.yaml:11-13`) where the resolved
config, TensorBoard events, checkpoints, and results.csv land.
"""

from __future__ import annotations

import datetime
import logging
import os
import sys

import yaml


def main(argv: list[str] | None = None) -> dict:
    """Compose the config, train, return the trainer's summary."""
    return run(argv)[1]


def run(argv: list[str] | None = None):
    """``main`` for a caller that wants to look at the trainer afterwards
    (chip_smoke.py reads its compiled programs and final state):
    returns ``(trainer, summary)``."""
    # The run's one clock starts here: every set-up span below, the
    # trainer's, the warmup threads' and the round loop's share this
    # tracer's zero (telemetry/trace.py). The trainer tells it later
    # what only it knows: rank, telemetry.enabled, the profiler's
    # annotation factory.
    from acco_tpu.telemetry import Tracer

    tracer = Tracer()
    with tracer.span("setup/config", cat="setup") as configured:
        argv = sys.argv[1:] if argv is None else argv
        repo_root = os.path.dirname(os.path.abspath(__file__))

        from acco_tpu.configuration import compose_config

        cfg = compose_config(os.path.join(repo_root, "config"), argv)

        run_dir_pattern = cfg.select("hydra.run.dir", "./outputs/%Y-%m-%d/%H-%M-%S")
        run_dir = datetime.datetime.now().strftime(run_dir_pattern)
        os.makedirs(run_dir, exist_ok=True)
        with open(os.path.join(run_dir, "config.yaml"), "w") as f:
            yaml.safe_dump(cfg.to_container(), f, sort_keys=False)

        logging.basicConfig(
            level=logging.INFO,
            format="[%(asctime)s][%(name)s][%(levelname)s] - %(message)s",
        )
        log = logging.getLogger("acco_tpu")
        log.info("run dir: %s", run_dir)

        # Compile-once subsystem (acco_tpu/compile): turn the persistent
        # compilation cache on BEFORE anything compiles. It lives at
        # $JAX_COMPILATION_CACHE_DIR if that is set, else at the config's
        # dir (outputs/compile_cache in config/train/*.yaml, resolved
        # against the checkout) — shared across launches and
        # preemption-resumes of the same config, so a repeat run compiles
        # nothing. Set train.compile_cache_dir='' to disable.
        cache_dir = cfg.train.get("compile_cache_dir")
        if cache_dir:
            from acco_tpu.compile import cache_dir_usage, setup_compilation_cache

            active = setup_compilation_cache(cache_dir, log=log)
            log.info("compile cache: %s", active)
            # what the dir held at the launch: less at the warmup's join
            # than this plus what the run wrote means jax evicted
            cache_bytes = cache_dir_usage()[0]
            if cache_bytes is not None:
                configured["cache_dir_bytes"] = cache_bytes

    with tracer.span("setup/imports", cat="setup"):
        import jax.numpy as jnp

        from acco_tpu.data.datasets import load_text_dataset
        from acco_tpu.data.tokenizer import load_tokenizer
        from acco_tpu.models.registry import build_model
        from acco_tpu.trainer import DecoupledTrainer

    with tracer.span("setup/build_model", cat="setup"):
        seed = int(cfg.select("seed", 12345))
        use_mp = bool(cfg.train.get("use_mixed_precision", True))
        # An 'sp' mesh axis > 1 means context parallelism: the model must be
        # built on the ring-attention path with the matching sequence axis.
        mesh_shape = cfg.train.get("mesh_shape") or {}
        use_cp = int(mesh_shape.get("sp", 1) or 1) > 1
        # A 'tp' axis > 1 means tensor parallelism: Llama layer matrices shard
        # over it (parallel/tp.py); the model is built with the matching axis.
        use_tp = int(mesh_shape.get("tp", 1) or 1) > 1
        # A 'pp' axis > 1 means pipeline parallelism (parallel/pp.py): the
        # layer stack splits into stages; vocab pads to a pp multiple (the
        # embedding/head are vocab-parallel over pp, like tp's).
        pp_size = int(mesh_shape.get("pp", 1) or 1)
        # padding multiple for the vocab-parallel embedding/head: the vocab
        # dim splits over tp, pp, or — composed — their product
        tp_size = int(mesh_shape.get("tp", 1) or 1)
        vocab_mult = max(tp_size, 1) * max(pp_size, 1)
        attention = "ring" if use_cp else cfg.train.get("use_pallas_attention", "auto")
        # remat / attention values are validated downstream (wrap_remat /
        # normalize_attention_impl) — YAML bools, None, and 'dots' all pass
        # through unmangled so typos fail loudly instead of silently coercing.
        initial_params = None
        if bool(cfg.train.get("finetune", False)):
            # finetune: True -> the model group's config_path names a local
            # pretrained HF checkpoint (reference `main.py:33-35`; hub names
            # resolve through ACCO_MODELS_ROOT, the root_path_model analogue).
            from acco_tpu.models.hf_loader import from_pretrained

            model, initial_params = from_pretrained(
                cfg.model.config_path,
                param_dtype=jnp.bfloat16 if use_mp else jnp.float32,
                remat=cfg.train.get("remat", False),
                attention=attention,
                sequence_axis="sp" if use_cp else None,
                scan_unroll=cfg.train.get("scan_unroll", 1),
                zigzag=use_cp and bool(cfg.train.get("zigzag_cp", True)),
                tensor_axis="tp" if use_tp else None,
                vocab_pad_multiple=vocab_mult,
            )
        else:
            model = build_model(
                cfg.model,
                repo_root=repo_root,
                param_dtype=jnp.bfloat16 if use_mp else jnp.float32,
                remat=cfg.train.get("remat", False),
                attention=attention,
                sequence_axis="sp" if use_cp else None,
                scan_unroll=cfg.train.get("scan_unroll", 1),
                zigzag=use_cp and bool(cfg.train.get("zigzag_cp", True)),
                tensor_axis="tp" if use_tp else None,
                vocab_pad_multiple=vocab_mult,
            )
    with tracer.span("setup/load_data", cat="setup") as loaded:
        tokenizer = load_tokenizer(cfg.model.get("tokenizer"), log)
        train_ds, eval_ds = load_text_dataset(cfg.data, log)
        loaded["train_docs"] = len(train_ds)
    log.info(
        "model=%s train_docs=%d eval_docs=%d method=%s",
        cfg.model.config_path,
        len(train_ds),
        len(eval_ds),
        cfg.train.method_name,
    )

    faults_cfg = cfg.train.get("fault_injection")
    if faults_cfg:
        # Chaos drill (acco_tpu/resilience/faults.py): deliberate state/
        # data poisoning to prove the watchdog's skip + rollback path.
        # Loudly flagged — a drill config accidentally promoted to a
        # real run must be visible in the first screen of logs.
        log.warning(
            "fault injection ACTIVE (train.fault_injection=%s): this run "
            "deliberately poisons training state to exercise the "
            "watchdog — not a production configuration", faults_cfg,
        )

    trainer = DecoupledTrainer(
        model,
        tokenizer,
        train_ds,
        eval_ds,
        cfg.train,
        log,
        seed=seed,
        run_dir=run_dir,
        initial_params=initial_params,
        tracer=tracer,
    )
    summary = trainer.train()
    if summary.get("interrupted"):
        if bool(cfg.train.get("save", False)):
            # Preemption-safe shutdown (acco_tpu/resilience): the final
            # checkpoint is committed and drained, so the kill is
            # resumable.
            log.warning(
                "training interrupted by a shutdown request at %d/%d "
                "grads; resume with train.resume_from=%s",
                summary["count_grad_tot"],
                int(cfg.train.get("nb_steps_tot", 0)),
                trainer.ckpt_dir,  # the trainer's own resolution, not a
            )                      # re-derivation that could drift
        else:
            log.warning(
                "training interrupted by a shutdown request at %d/%d "
                "grads with train.save=False: NO checkpoint was written "
                "— this progress is lost",
                summary["count_grad_tot"],
                int(cfg.train.get("nb_steps_tot", 0)),
            )
    log.info("done: %s", summary)
    return trainer, summary


if __name__ == "__main__":
    main()
