"""``BENCHMARK.json`` against the contract's limits, and the harness finding a
cell, a configuration and a per-layer metric dropped in as new files."""

import json
import os
import re
import shutil

import pytest

from _paths import ROOT

from benchmark.harness.manifest import Manifest, ManifestError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_top_level_keys_and_limits(manifest):
    b = manifest.data
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["paths"]) <= 16 and len(b["command"]) <= 32
    assert 1 <= len(b["configs"]) <= 24 and 2 <= len(b["workloads"]) <= 24
    assert 1 <= len(b["end_to_end"]) <= 16 and 1 <= len(b["per_layer"]) <= 128
    # the command names no file outside paths
    script = b["command"][1]
    assert any(script.startswith(p + "/") for p in b["paths"])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)


def test_names_units_and_keys(manifest):
    b = manifest.data
    for group, keys in (
        ("configs", {"name", "source", "file", "reduced", "why"}),
        ("workloads", {"name", "config", "traffic", "chips", "why"}),
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names)), group
        for e in b[group]:
            assert set(e) - {"workloads"} == keys, (group, e["name"])
            assert NAME.match(e["name"]), e["name"]
            # free text: a why, a layer, a configuration's source
            texts = [e[k] for k in ("why", "layer") if k in e]
            texts += [e["source"]] if group == "configs" else []
            for text in texts:
                assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"]) and NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))


FORBIDDEN_REDUCED = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head|expan")


def test_configurations(manifest):
    b = manifest.data
    used = {w["config"] for w in b["workloads"]}
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for c in b["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not FORBIDDEN_REDUCED.search(key), key
        manifest.config(c["name"])  # refuses a config.json that states another source or cut


PUBLISHED = {
    # EleutherAI/gpt-neo-125m config.json: attention_types [[["global", "local"], 6]]
    "gpt-neo-125m": dict(hidden_size=768, num_heads=12, num_layers=12, window_size=256,
                         vocab_size=50257, max_position_embeddings=2048, intermediate_size=None,
                         attention_layers=["global", "local"] * 6, param_count=125_198_592),
    # EleutherAI/gpt-neo-2.7B config.json, num_layers 32 -> 4 (two whole periods)
    "gpt-neo-2.7b-l4": dict(hidden_size=2560, num_heads=20, num_layers=4, window_size=256,
                            vocab_size=50257, max_position_embeddings=2048,
                            intermediate_size=None, attention_layers=["global", "local"] * 2,
                            param_count=448_581_120),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_a_configuration_holds_what_its_source_publishes(manifest, name):
    """Against the source's numbers written out here, not against a file of
    the repo: ``config/model/gpt-neo-125M.json`` has 1024 positions where
    EleutherAI publishes 2048 (125,198,592 parameters, not 124,412,160)."""
    config = manifest.config(name)
    want = dict(PUBLISHED[name])
    count = want.pop("param_count")
    assert {k: config["model"][k] for k in want} == want
    assert config["model"]["activation_function"] == "gelu_new"
    assert config["meta"]["param_count"] == count == parameters(config["model"])
    cut = {"gpt-neo-125m": [], "gpt-neo-2.7b-l4": ["num_layers"]}[name]
    assert config["entry"]["reduced"] == config["meta"]["reduced"] == cut
    assert set(config["meta"]["assumed"]) == set(config["meta"].get("assumed_detail", {}))


def parameters(m: dict) -> int:
    D, F = m["hidden_size"], m["intermediate_size"] or 4 * m["hidden_size"]
    block = 4 * D * D + D + 2 * D * F + F + D + 4 * D  # qkv + out (+bias), MLP, two norms
    return (m["vocab_size"] + m["max_position_embeddings"]) * D + m["num_layers"] * block + 2 * D


def test_a_config_json_that_contradicts_the_manifest_is_refused(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"][1]["reduced"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    with pytest.raises(ManifestError, match="reduced"):
        Manifest(root=str(tmp_path)).config(data["configs"][1]["name"])


@pytest.mark.parametrize("cell_name", Manifest().cell_names())
def test_every_cell_resolves(manifest, cell_name):
    cell = manifest.cell(cell_name)
    manifest.config(cell["config"])
    assert len(cell["why"]) <= 200
    schedules = {s["name"] for s in cell["schedules"]}
    e2e = {m["name"] for m in manifest.end_to_end(cell_name)}
    assert set(cell["end_to_end"]) == e2e and "setup_s" in e2e and len(e2e) >= 2
    for spec in cell["end_to_end"].values():
        assert spec["schedule"] == "*" or spec["schedule"] in schedules
    every = 10  # the trainer's logging cadence
    assert cell["ref_round"] % every == 0
    assert cell["ref_round"] - 3 * every >= every
    assert cell["warmup_rounds"] >= 2 + cell["trace_rounds"]  # the traced rounds stay outside
    layer = manifest.layer_metrics(cell_name)
    assert layer
    for spec in layer:
        manifest.reducer(spec["reducer"])
        assert spec["moves"] in e2e  # reported only where the metric it moves is


def test_new_files_need_no_edit_of_code(tmp_path):
    """A later PR adds a cell, a configuration and a per-layer metric as new
    files and manifest entries. In a copy of the benchmark's directory, with no
    file of code touched, the harness finds all three."""
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)

    (bench / "configs" / "new-model").mkdir()
    (bench / "configs" / "new-model" / "model.json").write_text(
        json.dumps({"model_type": "gpt_neo", "hidden_size": 1024}))
    (bench / "configs" / "new-model" / "config.json").write_text(
        json.dumps({"source": "https://example.org/new", "reduced": [], "runs": ["checkpoints"]}))
    data["configs"].append({"name": "new-model", "source": "https://example.org/new",
                            "file": "benchmark/configs/new-model/model.json",
                            "reduced": [], "why": "test"})

    with open(bench / "workloads" / "neo125m-ddp-1chip.json") as f:
        cell = json.load(f)
    cell.update(config="new-model", traffic="ddp-bs16", batch_per_chip=16, why="test")
    (bench / "workloads" / "new-cell.json").write_text(json.dumps(cell))
    data["workloads"].append({"name": "new-cell", "config": "new-model",
                              "traffic": "ddp-bs16", "chips": 1, "why": "test"})

    (bench / "layer_metrics" / "ckpt_snapshot_ms.json").write_text(json.dumps({
        "name": "ckpt_snapshot_ms", "layer": "checkpoint", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "tokens_per_s_per_chip", "needs": "checkpoints",
        "reducer": "span_stat", "args": {"span": "ckpt/snapshot", "stat": "max"}}))
    data["per_layer"].append({"name": "ckpt_snapshot_ms", "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "checkpoint",
                              "moves": "tokens_per_s_per_chip", "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    m = Manifest(root=str(tmp_path))
    found = m.cell("new-cell")
    assert found["batch_per_chip"] == 16
    assert m.config(found["config"])["model"]["hidden_size"] == 1024
    names = [s["name"] for s in m.layer_metrics("new-cell")]
    assert "ckpt_snapshot_ms" in names and "mfu_pct" in names
    assert "ckpt_snapshot_ms" not in [s["name"] for s in m.layer_metrics("neo125m-ddp-1chip")]
    assert m.features("new-cell") == {"checkpoints"} and m.breaches(declared_scopes()) == []
    # the new metric's reader is an existing kind, found by name; it reads nothing
    # from a trace that has no such span, and says so by returning nothing
    from benchmark.harness import window as win

    with open(os.path.join(os.path.dirname(__file__), "fixtures", "trace_recorded.json")) as f:
        trace = json.load(f)
    spec = next(s for s in m.layer_metrics("new-cell") if s["name"] == "ckpt_snapshot_ms")
    ctx = {"trace": trace, "window": win.measure_window(trace, 10)}
    assert m.reducer(spec["reducer"])(ctx, spec["args"]) is None
    dispatch = next(s for s in m.layer_metrics("new-cell") if s["name"] == "dispatch_ms")
    assert m.reducer(dispatch["reducer"])(ctx, dispatch["args"]) > 0


def declared_scopes() -> list:
    from acco_tpu.telemetry import DECLARED_DEVICE_SCOPES

    return list(DECLARED_DEVICE_SCOPES)


def write_json(path, data) -> None:
    path.write_text(json.dumps(data))


def edit_json(path, change) -> None:
    with open(path) as f:
        data = json.load(f)
    change(data)
    write_json(path, data)


@pytest.fixture()
def drawn(tmp_path):
    """A copy of the benchmark with what the next configuration would bring,
    as files and manifest entries only: a model that holds a chip's share of
    its experts, runs NEITHER attention kernel the benchmark reads by name, and
    has a mixer of its own whose device scope gets a metric of its own. The
    cell is in no metric's ``workloads`` yet."""
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (bench / "configs" / "drawn-model").mkdir()
    write_json(bench / "configs" / "drawn-model" / "model.json", {"model_type": "drawn", "hidden_size": 2048})
    write_json(bench / "configs" / "drawn-model" / "config.json",
               {"source": "https://example.org/drawn", "reduced": ["num_experts"],
                "runs": ["experts", "held_experts", "made_up_mixer"]})
    data["configs"].append({"name": "drawn-model", "source": "https://example.org/drawn",
                            "file": "benchmark/configs/drawn-model/model.json",
                            "reduced": ["num_experts"], "why": "test"})
    with open(bench / "workloads" / "neo125m-acco-1chip.json") as f:
        cell = json.load(f)
    cell.update(config="drawn-model", traffic="acco-seq8192-bs1", why="test")
    write_json(bench / "workloads" / "drawn-cell.json", cell)
    data["workloads"].append({"name": "drawn-cell", "config": "drawn-model",
                              "traffic": "acco-seq8192-bs1", "chips": 1, "why": "test"})
    with open(bench / "layer_metrics" / "moe_router_ms.json") as f:
        mixer = json.load(f)
    mixer.update(name="made_up_mixer_ms", needs="made_up_mixer", what="test")
    mixer["args"]["scopes"] = ["model/made_up_mixer"]
    write_json(bench / "layer_metrics" / "made_up_mixer_ms.json", mixer)
    data["per_layer"].append({"name": "made_up_mixer_ms", "unit": "ms/round", "better": "lower",
                              "source": "device_trace", "layer": "model",
                              "moves": "tokens_per_s_per_chip", "workloads": []})
    write_json(tmp_path / "BENCHMARK.json", data)
    return tmp_path


def follow_the_sentences(root) -> list:
    """Add the cell to every list that an R1 sentence says it owes; the
    metrics so extended."""
    owed = [b for b in Manifest(root=str(root)).breaches([*declared_scopes(), "model/made_up_mixer"])
            if b.rule == "R1" and "add it to that metric's `workloads`" in b.sentence]

    def extend(data):
        for b in owed:
            next(m for m in data["per_layer"] if m["name"] == b.metric)["workloads"].append(b.cell)

    edit_json(root / "BENCHMARK.json", extend)
    return sorted(b.metric for b in owed)


def test_the_next_configuration_is_files_and_list_entries(drawn):
    """The dress rehearsal of the configuration the harness could not take:
    held experts, a mixer scope of its own, neither attention kernel. With the
    cell's name added to the lists the rule names, and to no other, every rule
    holds in the copy, and no file of code or test was touched there."""
    scopes = [*declared_scopes(), "model/made_up_mixer"]
    before = Manifest(root=str(drawn)).breaches(scopes)
    assert {b.cell for b in before} == {"drawn-cell"}  # no accepted cell is touched
    assert follow_the_sentences(drawn) == ["made_up_mixer_ms", "moe_dispatch_ms", "moe_experts_ms",
                                           "moe_experts_roofline", "moe_held_share_pct", "moe_router_ms"]
    m = Manifest(root=str(drawn))
    assert m.breaches(scopes) == []
    assert m.features("drawn-cell") == {"experts", "held_experts", "made_up_mixer"}
    names = {s["name"] for s in m.layer_metrics("drawn-cell")}
    assert {"made_up_mixer_ms", "moe_held_share_pct", "block_ms", "mfu_pct"} <= names
    assert not {"attn_kernel_ms", "attn_kernel_roofline", "flash_attn_kernel_ms", "moe_shared_ms"} & names
    # the program's list without the mixer's scope: the copy's metric owns a scope nobody declared
    assert ["made_up_mixer_ms"] == [b.metric for b in m.breaches(declared_scopes())]
    for cell in Manifest().cell_names():  # every accepted cell lists what it listed
        assert [s["name"] for s in m.layer_metrics(cell)] == [s["name"] for s in Manifest().layer_metrics(cell)]


def leave_out_of_the_held_share(root):
    edit_json(root / "BENCHMARK.json", lambda data: next(
        m for m in data["per_layer"] if m["name"] == "moe_held_share_pct")["workloads"].remove("drawn-cell"))


def list_under_the_own_kernels(root):
    edit_json(root / "BENCHMARK.json", lambda data: next(
        m for m in data["per_layer"] if m["name"] == "attn_kernel_ms")["workloads"].append("drawn-cell"))


def give_the_scope_a_second_owner(root):
    edit_json(root / "benchmark" / "layer_metrics" / "block_ms.json",
              lambda spec: spec["args"]["scopes"].append("model/made_up_mixer"))


@pytest.mark.parametrize("fault, rule, metric, cell, says", [
    (leave_out_of_the_held_share, "R1", "moe_held_share_pct", "drawn-cell",
     "cell `drawn-cell` runs `held_experts`, so it owes `moe_held_share_pct`: add it to that metric's "
     "`workloads` in BENCHMARK.json"),
    (list_under_the_own_kernels, "R1", "attn_kernel_ms", "drawn-cell",
     "cell `drawn-cell` does not run `own_attention_kernels`, which `attn_kernel_ms` needs: take it out of "
     "that metric's `workloads` in BENCHMARK.json"),
    (give_the_scope_a_second_owner, "R2", "made_up_mixer_ms", None,
     "the scope `model/made_up_mixer` is owned by `block_ms` and by `made_up_mixer_ms`"),
])
def test_a_breach_is_a_sentence_that_names_the_metric_the_cell_and_the_list(drawn, fault, rule, metric, cell, says):
    follow_the_sentences(drawn)
    fault(drawn)
    found = Manifest(root=str(drawn)).breaches([*declared_scopes(), "model/made_up_mixer"])
    assert len(found) == 1, [str(b) for b in found]
    assert (found[0].rule, found[0].metric, found[0].cell) == (rule, metric, cell)
    assert says in found[0].sentence and str(found[0]).startswith(rule + ": ")


TOY_REFERENCE = '''
"""A family the benchmark has never seen: next-token logits from the current
token alone, ``wte[ids] @ head``. Plain float32."""
import jax, jax.numpy as jnp, numpy as np

def loss(params, ids, cfg):
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    logits = (p["wte"][ids] @ p["head"])[:, :-1]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

def loss_and_grads(params, ids, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, ids, cfg)

def compared_groups(grads):
    return {k: np.asarray(v, np.float32).ravel() for k, v in grads.items()}
'''

TOY_FLOPS = '''
def train_flops_per_token(cfg, seq_len):
    return 3.0 * 2 * cfg["hidden_size"] * cfg["vocab_size"]  # the head, forward and backward
'''


class ToyProgram:
    """What the program's side of the check needs of a model object."""

    def __init__(self, cfg, head_scale=1.0):
        import types

        self.config = types.SimpleNamespace(**cfg)
        self.head_scale = head_scale

    def init(self, key):
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(key)
        V, D = self.config.vocab_size, self.config.hidden_size
        return {"wte": jax.random.normal(k1, (V, D), jnp.bfloat16),
                "head": (jax.random.normal(k2, (D, V)) / D**0.5).astype(jnp.bfloat16)}

    def apply(self, params, ids, mask=None):
        return params["wte"][ids] @ (params["head"] * self.head_scale)


def test_a_new_family_of_models_needs_no_edit_of_code(tmp_path):
    """A later ``model_config`` PR brings a model type the benchmark has no
    count and no reference for, as files its ``config.json`` names. The MFU
    reader and the reference check run on it through the harness's own code."""
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (bench / "reference" / "toy_ref.py").write_text(TOY_REFERENCE)
    (bench / "reference" / "toy_flops.py").write_text(TOY_FLOPS)
    (bench / "configs" / "toy").mkdir()
    model = {"model_type": "bigram", "hidden_size": 32, "vocab_size": 64}
    (bench / "configs" / "toy" / "model.json").write_text(json.dumps(model))
    (bench / "configs" / "toy" / "config.json").write_text(json.dumps(
        {"reference": "benchmark/reference/toy_ref.py", "flops": "benchmark/reference/toy_flops.py"}))
    data["configs"].append({"name": "toy", "source": "https://example.org/toy", "reduced": [],
                            "file": "benchmark/configs/toy/model.json", "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    m = Manifest(root=str(tmp_path))
    config = m.config("toy")
    ctx = {"quantities": {"tokens_per_s_per_chip": 1e6}, "config": config,
           "cell": {"seq_len": 16}, "peaks": {"bf16_flops_per_s": 197e12}}
    assert m.reducer("mfu")(ctx, {}) == pytest.approx(100 * 1e6 * 6 * 32 * 64 / 197e12)
    # no attention kernel in this family's trace: the roofline reader returns nothing
    assert m.reducer("kernel_roofline")({**ctx, "device_trace": None}, {"kernels": {}}) is None

    from benchmark.harness import refcheck

    said = []
    right = refcheck.compare(ToyProgram(model), False, config, 16, 3, say=said.append)
    assert right["ok"] and set(right["errors"]) == {"loss", "wte", "head"} and right["qk_scale"] == 1.0
    wrong = refcheck.compare(ToyProgram(model, head_scale=1.1), False, config, 16, 3, say=said.append)
    assert not wrong["ok"]


def test_what_is_missing_is_an_error(manifest, tmp_path):
    with pytest.raises(ManifestError):
        manifest.cell("no-such-cell")
    with pytest.raises(ManifestError):
        manifest.config("no-such-config")
    with pytest.raises(ManifestError):
        manifest.reducer("no_such_kind")
    with pytest.raises(ManifestError):
        Manifest(root=str(tmp_path))  # no BENCHMARK.json there
