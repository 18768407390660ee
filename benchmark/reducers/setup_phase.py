"""One part of ``setup_s``, in seconds: the launch to the window's first fence,
tiled by the program's set-up spans (``acco_tpu/telemetry/trace.py``
``SETUP_SPANS``, on the clock of the loop's spans and the window's fences).
args: ``part``, one of

    launch        the harness's t0 -> the first setup/* span's start: spawn,
                  interpreter, the harness's jax import, jax.devices()
    build         setup/config + setup/imports + setup/build_model + setup/load_data
    tokenize      setup/tokenize
    trainer       setup/trainer_init less its setup/tokenize child, + setup/state_init
                  + setup/restore + setup/seed
    warmup_join   compile/warmup_join: what the main thread WAITED for the warmup
    first_rounds  the first loader/next_block's start -> the fence, less the profile spans
    profile       setup/scope_table + train/profile_start + train/profile_stop: what
                  only a traced run pays
    unnamed       setup_s less the seven above: what no span covers

The eight add up to the run's ``setup_s`` (``ctx["quantities"]``); the launch on
the trace clock is the fence's end less ``setup_s``. Only events that END at or
before the fence count. A program that recorded no ``setup/*`` span (every commit
before the one that added them) gives nothing to read: None.
"""

BUILD = ("setup/config", "setup/imports", "setup/build_model", "setup/load_data")
PROFILE = ("train/profile_start", "train/profile_stop")
PARTS = ("launch", "build", "tokenize", "trainer", "warmup_join", "first_rounds",
         "profile", "unnamed")


def parts(ctx: dict) -> dict | None:
    setup_s = ctx["quantities"].get("setup_s")
    fence_us = ctx["window"].first.end_us
    spans = [
        e for e in ctx["trace"]["traceEvents"]
        # ts and dur are each rounded to 0.1 us by the tracer
        if e.get("ph") == "X" and e["ts"] + e.get("dur", 0.0) <= fence_us + 0.25
    ]
    starts = [e["ts"] for e in spans if e["name"].startswith("setup/")]
    blocks = [e["ts"] for e in spans if e["name"] == "loader/next_block"]
    if setup_s is None or not starts or not blocks:
        return None

    def seconds(*names: str) -> float:
        return sum(e["dur"] for e in spans if e["name"] in names) / 1e6

    profile = seconds(*PROFILE)
    out = {
        "launch": min(starts) / 1e6 - (fence_us / 1e6 - setup_s),
        "build": seconds(*BUILD),
        "tokenize": seconds("setup/tokenize"),
        "trainer": seconds("setup/trainer_init", "setup/state_init", "setup/restore",
                           "setup/seed") - seconds("setup/tokenize"),
        "warmup_join": seconds("compile/warmup_join"),
        "first_rounds": (fence_us - min(blocks)) / 1e6 - profile,
        "profile": seconds("setup/scope_table") + profile,
    }
    out["unnamed"] = setup_s - sum(out.values())
    return out


def reduce(ctx: dict, args: dict):
    found = parts(ctx)
    if found is None:
        return None
    if args["part"] == PARTS[0] and ctx.get("say"):
        ctx["say"](
            f"set-up {ctx['quantities']['setup_s']:.3f} s to the window's first fence: "
            + ", ".join(f"{name} {found[name]:.3f}" for name in PARTS)
        )
    return found[args["part"]]
