"""The Trinity-Mini configuration's own files: its operations against a
hand count, its plain reference against the program on seeded weights, the
controls its comparison must refuse, its program rehearsed on the CPU at a
tiny size, and its readers — the three new ones, ``gqa_device_ms`` and the
expert and head readers the token cells share — on an excerpt recorded on
the chip (``benchmarks/testdata/v5e_afmoe_step_scopes.json``).

Membership assertions only on the benchmark's lists: a later PR appends
cells, configurations and metrics after these.

The file leaves the telemetry registry's expert-layer children as it found
them (``tests/conftest.py::expert_layer_metrics_end_with_their_module``,
every test module's): a rehearsed token program publishes a counter and a
gauge a layer, and under ``--dist loadfile`` another token file's test may
run next in the same worker."""

import json
import math
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks import run as runner
from benchmarks.harness import scopes as hs
from benchmarks.harness import spec
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from fast_autoaugment_tpu.core import compilecache, scopes, telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of

REPO = spec.ROOT

FLOPS = spec.load_module("flops", "afmoe")
REFERENCE = spec.load_module("references", "afmoe")
CONFIG = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "trinity_mini_tokens.json"))
CELL = "trinity_mini_train"
NEMOTRON_CELL = "nemotron3_nano_30b_a3b_train"
NEW_READERS = ("swa_device_ms", "gqa_attention_roofline", "swa_key_tiles_visited_share")
WINDOW, FULL = "sliding_attention", "full_attention"

#: every width cut for the CPU, the structure kept: the cut's six blocks (two
#: dense, four expert layers; five window, one full); 16 experts of which 4
#: are held, top-2; 2 query heads a key-value head; a span of 24 tokens
TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=2, vocab_size=64, sliding_window=24, num_hidden_layers=8,
    layer_types=[WINDOW, WINDOW, WINDOW, FULL] * 2)
TINY_HELD = dict(layers_held=6, experts_held=4, ids_held=48)


def tiny_conf(**top) -> dict:
    with open(os.path.join(REPO, CONFIG["repo_conf"])) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(TINY_MODEL)
    conf.update(TINY_HELD, **top)
    return conf


def tiny_sizes(conf: dict, seq_len: int) -> dict:
    return dict(FLOPS.model_from_conf(conf["model"]), expert_share=0,
                seq_len=seq_len, **{k: conf[k] for k in TINY_HELD})


# ------------------------------------------------- operations, by hand


def test_parameters_held_and_whole_against_a_hand_count():
    model = CONFIG["model"]
    mixer = 2048 * (3 * 4096 + 2 * 512) + 2 * 128
    expert = 3 * 2048 * 1024
    assert FLOPS.mixer_params(model) == mixer == 27_263_232
    assert FLOPS.dense_ffn_params(model) == 3 * 2048 * 6144 == 37_748_736
    assert FLOPS.expert_params(model) == expert == 6_291_456
    assert FLOPS.shared_expert_params(model) == expert
    dense_block = mixer + 37_748_736 + 4 * 2048
    assert dense_block == 65_020_160
    # the issue's 84,156,672 a held expert layer, and the router's bias of 128
    expert_layer = 2048 * 128 + 128 + 8 * expert + expert
    assert FLOPS.expert_layer_params(model, 8) == expert_layer
    assert mixer + 4 * 2048 + expert_layer == 84_156_672 + 128
    held = 2 * dense_block + 4 * (84_156_672 + 128) + 2 * 25024 * 2048 + 2048
    assert FLOPS.num_params(model) == held == 569_167_360 + 4 * 128
    # 16 bytes a parameter: float32 weights, gradients, AdamW's two moments
    assert 16 * held == pytest.approx(9.11e9, rel=1e-3)
    assert FLOPS.held_layers(model, WINDOW) == 5 and FLOPS.held_layers(model, FULL) == 1
    assert FLOPS.held_expert_layers(model) == 4
    whole = dict(model, layers_held=None, experts_held=None, ids_held=None)
    assert FLOPS.held_layers(whole, WINDOW) == 24 and FLOPS.held_layers(whole, FULL) == 8
    # 2 dense blocks, 30 expert blocks of 839,131,392 (+ the bias), the tables
    assert FLOPS.num_params(whole) == (2 * dense_block + 30 * (839_131_392 + 128)
                                       + 2 * 200192 * 2048 + 2048)
    assert 26.0e9 < FLOPS.num_params(whole) < 26.2e9     # the catalog's 26B
    five = dict(model, layers_held=5)                    # the issue's fall-back cut
    assert FLOPS.num_params(five) == held - (84_156_672 + 128)


def test_step_operations_count_a_window_layer_by_its_band():
    model = CONFIG["model"]
    tokens, span = 16384, 2048
    band = tokens * span - span * (span - 1) // 2
    triangle = tokens * (tokens + 1) // 2
    assert FLOPS.visible_pairs(model, WINDOW, tokens) == band == 31_458_304
    assert FLOPS.visible_pairs(model, FULL, tokens) == triangle == 134_225_920
    # a sequence the span does not outgrow: a window layer is a full one
    assert FLOPS.visible_pairs(model, WINDOW, 2048) == FLOPS.visible_pairs(
        model, FULL, 2048) == 2048 * 2049 // 2
    # the pairs by brute force at a small size
    small = dict(model, window=5)
    assert FLOPS.visible_pairs(small, WINDOW, 12) == sum(
        1 for i in range(12) for j in range(12) if 0 <= i - j < 5)
    per_pair = 2 * 32 * (128 + 128)
    assert FLOPS.gqa_attention_operations(model, WINDOW, tokens, backward=False) == (
        per_pair * band)
    assert FLOPS.gqa_attention_operations(model, FULL, tokens, backward=True) == (
        2 * per_pair * triangle)
    mixers = 2 * 2048 * (3 * 4096 + 2 * 512)
    dense = 2 * 3 * 2048 * 6144
    # 8 of 128 experts held, top-8: a token reaches 8 * 8 / 128 = 0.5 of them
    experts = 2 * (2048 * 128 + 0.5 * 3 * 2048 * 1024 + 3 * 2048 * 1024)
    head = 2 * 2048 * 25024
    forward = ((head + 6 * mixers + 2 * dense + 4 * experts) * tokens
               + per_pair * (5 * band + triangle))
    assert FLOPS.forward_flops_per_image(model) == pytest.approx(forward, rel=1e-12)
    assert FLOPS.train_flops_per_image(model) == pytest.approx(3 * forward)
    assert 46e12 < 3 * forward < 48e12                   # 46.7 TFLOP a step owed
    # skipping the band cuts the cores' work 2.8 times; masking it cuts nothing
    assert 6 * triangle / (5 * band + triangle) == pytest.approx(2.76, abs=0.01)


def test_the_cores_bytes_are_their_operands_and_result_once():
    model = CONFIG["model"]
    q = o = 32 * 128
    k = v = 4 * 128
    for kind in (WINDOW, FULL):
        assert FLOPS.gqa_attention_bytes(model, kind, 1, backward=False) == 4 * (
            q + k + v + o)
        assert FLOPS.gqa_attention_bytes(model, kind, 1, backward=True) == 4 * (
            2 * (q + k + v) + 2 * o)
    # the operations bound the cores at these sizes, window layers too
    for kind in (WINDOW, FULL):
        operations = 3 * FLOPS.gqa_attention_operations(model, kind, 16384, backward=False)
        moved = sum(FLOPS.gqa_attention_bytes(model, kind, 16384, backward=b)
                    for b in (False, True))
        assert operations / 197e12 > moved / 819e9
    assert FLOPS.moe_experts_operations(model, 1024, backward=False) == (
        2 * 3 * 2048 * 1024 * 1024)
    weights = 4 * 8 * 3 * 2048 * 1024
    assert FLOPS.moe_experts_bytes(model, 0, backward=False) == weights


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_configuration_file_states_the_published_model_and_the_cut():
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Trinity-Mini")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key                # every key as published
        assert CONFIG["conf"]["model"][key] == value, key
    assert CONFIG["reduced"] == ["layers_held", "experts_held", "ids_held"]
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [6, 8, 25024]
    assert set(CONFIG["reduced_because"]) == set(CONFIG["reduced"])
    assert CONFIG["published"] == dict(
        CONFIG["published"], num_hidden_layers=32, num_experts=128, vocab_size=200192)
    assert "16 chips" in CONFIG["deployment"] and "569,167,872" in CONFIG["deployment"]
    # the floors: both leading dense blocks, a whole period of the pattern,
    # four expert layers, 8 experts, an eighth of the vocabulary
    held = CONFIG["layer_types"][:CONFIG["layers_held"]]
    assert held[:4] == [WINDOW, WINDOW, WINDOW, FULL]
    assert CONFIG["layers_held"] - CONFIG["num_dense_layers"] >= 4
    assert CONFIG["experts_held"] >= 8 and 8 * CONFIG["ids_held"] >= CONFIG["vocab_size"]
    assert 0 < CONFIG["logit_tolerance_float32"] < CONFIG["logit_tolerance"]
    assert 0 < CONFIG["routing_margin_tolerance_float32"] < CONFIG["routing_margin_tolerance"]
    for key in ("logit_tolerance_because", "logit_tolerance_float32_because",
                "routing_margin_because", "learned_measured", "router_measured"):
        assert CONFIG[key], key
    assert {"output_gate", "qk_norm", "rotary", "four_norms", "mup_enabled",
            "load_balance_coeff", "window_convention", "initial_values", "optimizer",
            "precision"} <= set(CONFIG["assumed"])
    # no width differs from the source: what is reduced is no width
    assert not any(word in key for key in CONFIG["reduced"]
                   for word in ("_dim", "_rank", "width", "hidden", "size"))


def test_configuration_self_test_passes():
    from tests.benchmarks.test_bench_spec import check_config

    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "trinity_mini_tokens")
    check_config(REPO, entry)
    assert CONFIG["model"]["seq_len"] == 16384 and CONFIG["model"]["expert_share"] == 0
    assert CONFIG["model"]["window"] == 2048 and CONFIG["model"]["embed_scale"] == (
        pytest.approx(math.sqrt(2048)))


# --------------------------------------- the reference against the program


@pytest.fixture(scope="module")
def tiny_system():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), conf["ids_held"])
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 97), 0, 48))
    params = jax.jit(model.init, static_argnames=("train",))(
        {"params": jax.random.PRNGKey(1)}, ids[:, :-1], train=False)["params"]
    # off their initial ones and zeros, so that a norm left out shows
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    return conf, model, params, ids, tiny_sizes(conf, 96)


def _logits_and_routing(model, params, ids):
    """The system's logits under ``highest`` and the routing it sowed."""
    with jax.default_matmul_precision("highest"):
        logits, sown = jax.jit(lambda p, x: model.apply(
            {"params": p}, x, mutable=["routing"]))(params, ids[:, :-1])
    routing = {layer: np.asarray(entry["moe"]["chosen"][0])
               for layer, entry in sown["routing"].items()}
    return np.asarray(logits), routing


def _gap(ours, plain):
    return float(np.abs(ours - plain).max() / np.abs(plain).max())


def test_program_parameters_are_what_the_operations_file_counts(tiny_system):
    _, _, params, _, sizes = tiny_system
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)
    assert sorted(params) == ["embed_tokens"] + [f"layer{i}" for i in range(1, 7)] + [
        "lm_head", "norm"]


@pytest.mark.parametrize("control", [
    "no_gate", "no_qk_norm", "rotary_everywhere", "no_rotary", "interleaved_pairs",
    "two_norms", "no_embed_scale", "window_ignored", "window_plus_one",
    "one_layer_short", "bf16"])
def test_controls_the_float32_comparison_must_refuse(tiny_system, control):
    """The reference without the output gate, without the norms a head, with
    full layers rotated too, with no layer rotated, with the pairs ``(2i, 2i
    + 1)``, with two norms a block, without the embedding's multiplier, with
    the window layers as full attention, with a span one key longer, one
    layer short; and the program in bfloat16 under ``highest``: each over
    the configuration's float32 limit, the system's routing given."""
    conf, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, routing = _logits_and_routing(model, params, ids)
    assert sorted(routing) == ["layer3", "layer4", "layer5", "layer6"]
    limit = CONFIG["logit_tolerance_float32"]
    sound, margin = REFERENCE.forward_given_routing(params, inputs, sizes, routing)
    assert _gap(logits, sound) <= 1e-5 and margin < 1e-5
    if control == "bf16":
        half = get_model(dict(model_conf_of(conf), precision="bf16"), conf["ids_held"])
        low, low_routing = _logits_and_routing(half, params, ids)
        plain, _ = REFERENCE.forward_given_routing(params, inputs, sizes, low_routing)
        assert _gap(low, plain) > limit
        return
    given = routing
    if control == "one_layer_short":
        changed = dict(sizes, layers_held=5)
        given = {k: v for k, v in routing.items() if k != "layer6"}
    elif control == "no_embed_scale":
        changed = dict(sizes, embed_scale=1.0)
    elif control == "window_ignored":
        changed = dict(sizes, window=inputs.shape[1])
    elif control == "window_plus_one":
        changed = dict(sizes, window=sizes["window"] + 1)
    else:
        changed = dict(sizes, control=control)
    other, _ = REFERENCE.forward_given_routing(params, inputs, changed, given)
    assert _gap(logits, other) > limit, control


def test_reference_given_the_systems_routing_says_how_far_a_choice_is(tiny_system):
    """Given the model's own choice the reference is its plain forward, the
    margin at rounding; a choice no router made shows in the margin;
    compiled ahead from shapes it is the same program."""
    _, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    _, routing = _logits_and_routing(model, params, ids)
    assert REFERENCE.expert_layers(sizes) == sorted(routing)
    own = REFERENCE.forward(params, {}, inputs, sizes)
    given, margin = REFERENCE.forward_given_routing(params, inputs, sizes, routing)
    assert _gap(given, own) <= 1e-5 and 0.0 <= margin < 1e-5
    wrong = dict(routing, layer5=np.broadcast_to(
        np.arange(sizes["top_k"], dtype=np.int32), routing["layer5"].shape))
    _, far = REFERENCE.forward_given_routing(params, inputs, sizes, wrong)
    assert far > 0.05
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    ahead = REFERENCE.compile_forward_given_routing(
        shapes, jax.ShapeDtypeStruct(inputs.shape, jnp.int32), sizes)
    again, again_margin = ahead(params, inputs, routing)
    assert np.array_equal(again, given) and again_margin == margin


def test_reference_one_held_expert_short_is_refused(tiny_system):
    """Every held expert of every expert layer, left out of the reference
    in turn (the system's routing given): each that a token of these chose
    moves the logits over the float32 limit."""
    _, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, routing = _logits_and_routing(model, params, ids)
    held = sizes["experts_held"]
    tried = 0
    for layer in routing:
        for expert in range(held):
            if not (routing[layer] == expert).any():
                continue                     # no token of these chose it
            kept = np.ones(held, np.float32)
            kept[expert] = 0.0
            short, _ = REFERENCE.forward_given_routing(
                params, inputs, sizes, routing, {layer: kept})
            assert _gap(logits, short) > CONFIG["logit_tolerance_float32"], (
                layer, expert)
            tried += 1
    assert tried >= held


# ------------------------------------------------ the program, rehearsed


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def build_tiny_checkout(root: str) -> str:
    """A copy of the benchmark with a tiny afmoe configuration, fixture,
    traffic and cell dropped in as new files and entries."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    conf = tiny_conf(batch=2, lr=0.003)
    config = dict(CONFIG, conf=conf, model=tiny_sizes(conf, 64))
    _write(os.path.join(bench_dir, "configs", "tiny_afmoe.json"), config)
    fixture = _read(os.path.join(bench_dir, "fixtures", "tokens_markov_25024_16k.json"))
    fixture.update(train=8, test=2, length=64, ids=48)
    _write(os.path.join(bench_dir, "fixtures", "tiny_afmoe.json"), fixture)
    traffic = _read(os.path.join(bench_dir, "traffic", "train_epochs_tokens_16k.json"))
    traffic.update(fixture="tiny_afmoe", trace_seconds=1.5,
                   loss_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_afmoe_train.json"), traffic)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_afmoe", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_afmoe.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_afmoe_train", "config": "tiny_afmoe",
        "traffic": "tiny_afmoe_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_afmoe_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = build_tiny_checkout(str(tmp_path_factory.mktemp("afmoe")))
    cell = spec.resolve_cell("tiny_afmoe_train", seed=2**31 + 46, seconds=1.0,
                             trace=False, root=root)
    before = telemetry.registry().counters_snapshot()
    obs = runner.run_cell(cell, jax.devices()[:1], runner.process_start_wall())
    after = telemetry.registry().counters_snapshot()
    return obs, runner.result_line(obs), {
        key: value - before.get(key, 0.0) for key, value in after.items()}


def test_program_rehearsed_on_the_cpu_compares_the_logits(rehearsed):
    obs, line, _ = rehearsed
    assert obs.correct, obs.checks
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"           # and so: not a result
    counted = obs.checks["step_counter"]
    assert counted["checkpoint_step"] == counted["steps_counted"] == 4 + 2 + obs.attempted
    assert obs.checks["no_compile_in_window"]["compile_requests"] == 0
    assert obs.checks["learned"]["loss_of_no_learning"] == pytest.approx(math.log(48))
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "no_compile_in_window", "step_counter", "learned",
        "reference_logits", "reference_logits_float32", "routing", "routing_float32"}
    assert obs.checks["reference_logits"]["images"] == 1   # one sequence
    assert obs.checks["reference_logits_float32"]["relative_gap"] < 1e-4
    assert obs.checks["routing"]["layers"] == ["layer3", "layer4", "layer5", "layer6"]
    assert obs.checks["routing_float32"]["margin"] < 1e-5
    meta_loss = obs.checks["learned"]["loss_train"]
    assert math.isfinite(meta_loss) and meta_loss < math.log(48) + 1.0


def test_program_hands_the_counters_to_the_readers(rehearsed):
    obs, _, rise = rehearsed
    by_layer = obs.work["moe_assignments_a_step_by_layer"]
    assert sorted(by_layer) == ["layer3", "layer4", "layer5", "layer6"]
    # 128 tokens x top-2 x 4 of 16 experts held: 64 a step expected
    assert all(0 < n < 128 * 2 for n in by_layer.values())
    assert sorted(obs.work["moe_held_load_max_over_mean"]) == sorted(by_layer)
    assert obs.work["tokens_a_step"] == 128
    # trace time: the forms and spans the rehearsed programs' cores took
    assert rise['faa_attention_cores_traced_total{form="blocked_xla",span="24"}'] > 0
    assert rise['faa_attention_cores_traced_total{form="blocked_xla",span="none"}'] > 0
    assert rise['faa_mla_attention_traces_total{form="blocked_xla"}'] > 0
    assert not any("fused" in key for key in rise if "cores_traced" in key and rise[key])
    # the XLA form masks the span and skips nothing: visited is no less than causal
    visited = rise['faa_attention_key_tiles_total{kind="visited",span="24"}']
    assert visited >= rise['faa_attention_key_tiles_total{kind="causal",span="24"}'] > 0
    reader = spec.load_module("layer_metrics", "swa_key_tiles_visited_share")
    assert reader.read(obs) >= 100.0


def test_a_program_without_the_model_refuses_the_cells_conf_before_it_trains():
    """What the parent does with this cell: its registry knows no such
    model type, and ``train_tokens.ComparisonsAhead`` builds the model
    before the trainer is entered, so the run ends there, non-zero and at
    once (on the chip: exit 1 after the imports)."""
    conf = tiny_conf()
    conf["model"]["type"] = "afmoe_of_a_later_pr"
    with pytest.raises(ValueError, match="unknown model type"):
        get_model(model_conf_of(conf), 48)
    program = spec.load_module("programs", "train_tokens")
    cell = types.SimpleNamespace(
        config={"model": {"ids_held": 48}, "reference": "afmoe"},
        module=lambda kind, name: spec.load_module(kind, name))
    with pytest.raises(ValueError, match="unknown model type"):
        program.ComparisonsAhead(cell, conf, 1, 64)


# ------------------------------------------- the cell's entries and readers


#: the Kimi cell's own readers of scopes and counters this cell's program has
#: too; two self-tests hold their lists to the cells they name (PERF.md
#: section 7), so this cell joins them in the ``benchmark`` PR that may edit
#: those files; until then the tests below call them directly
SHARED_READERS = ("moe_device_ms", "lm_head_loss_device_ms",
                  "moe_experts_roofline", "moe_held_load_max_over_mean")


def test_every_metric_that_names_the_cell_has_a_reader_that_agrees():
    cell = spec.resolve_cell(CELL, trace=True)
    bench = spec.load_benchmark()
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in listed} == {m["name"] for m in cell.per_layer}
    for entry in listed:
        assert runner.reader_for(cell, entry).META["moves"] == entry["moves"]
    names = {m["name"] for m in listed}
    nemotron = {m["name"] for m in bench["per_layer"]
                if NEMOTRON_CELL in m.get("workloads", ())}
    assert set(NEW_READERS) <= names - nemotron
    assert "gqa_device_ms" in names & nemotron
    assert {"step_device_ms", "model_flops_utilization", "model_forward_device_ms",
            "model_backward_device_ms", "step_unscoped_share", "peak_hbm_bytes",
            "device_idle_share", "compile_first_call_s", "compile_cache_misses",
            "dispatch_gap_ms", "optimizer_device_ms",
            "batch_gather_device_ms"} <= names
    assert not any(n.startswith(("aug_", "shake_", "resnet_", "feed_", "host_",
                                 "kda_", "mla_", "mtp_", "mamba2_", "ssd_"))
                   for n in names)
    for name in NEW_READERS:
        entry = next(m for m in listed if m["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] == "models"
    [workload] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload["chips"] == 1
    assert workload["config"] == "trinity_mini_tokens"
    assert workload["config"] in {c["name"] for c in bench["configs"]}
    assert cell.traffic["program"] == "train_tokens"
    assert cell.traffic["fixture"] == "tokens_markov_25024_16k"
    assert cell.fixture["ids"] == 25024 and cell.fixture["length"] == 16384
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    end_to_end = next(m for m in bench["end_to_end"] if m["name"] == "train_images_per_s")
    assert CELL in end_to_end["workloads"]
    # the traffic is the Nemotron cell's but for the fixture and what is said of it
    other = spec.resolve_cell(NEMOTRON_CELL).traffic
    differing = {k for k in other if other[k] != cell.traffic[k]}
    assert differing == {"describes", "fixture", "reduced", "loss_margin_because"}
    assert "10.128" in cell.traffic["loss_margin_because"]
    assert math.log(25024) == pytest.approx(10.128, abs=1e-3)
    # the fixture is tokens_markov.json's but for the length and the count of ids
    base = _read(os.path.join(spec.BENCH_DIR, "fixtures", "tokens_markov.json"))
    assert {k for k in base if base[k] != cell.fixture[k]} == {"describes", "length", "ids"}


def test_every_scope_the_models_program_has_is_read_by_a_reader(tiny_system):
    """Whatever the tiny model's lowered step names inside ``faa_model`` is
    under a scope that a reader names in its source — one the cell lists or
    one of :data:`SHARED_READERS`."""
    import re

    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import create_train_state, make_token_step_body

    conf, model, _, ids, _ = tiny_system
    optimizer = build_optimizer(conf["optimizer"], lambda step: 1e-3)
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, jax.random.PRNGKey(0), ids[:, :-1], use_ema=False))
    text = jax.jit(make_token_step_body(model, optimizer)).lower(
        state, ids, jnp.zeros(2, jnp.int32), None, None).as_text(debug_info=True)
    chains = {scopes.scope_of(name) for name in re.findall(r'loc\("([^"]*)"', text)}
    found = {scope for chain in chains for scope in chain}
    assert {scopes.GQA, scopes.SWA, scopes.GQA_ATTENTION, scopes.MOE,
            scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS,
            scopes.OPTIMIZER} <= found
    assert scopes.MLA_ATTENTION not in found and scopes.MLA not in found
    # a window mixer only ever inside a grouped-query mixer, the cores in either
    assert all(scopes.GQA in chain for chain in chains if scopes.SWA in chain)
    assert all(scopes.GQA in chain for chain in chains if scopes.GQA_ATTENTION in chain)
    assert any(scopes.GQA_ATTENTION in chain and scopes.SWA not in chain
               for chain in chains)                      # the full layer's core
    cell = spec.resolve_cell(CELL, trace=True)
    sources = ""
    for name in [entry["name"] for entry in cell.per_layer] + list(SHARED_READERS):
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics", name + ".py")) as fh:
            sources += fh.read()
    by_value = {getattr(scopes, n): n for n in scopes.__all__
                if isinstance(getattr(scopes, n), str)}
    read = {scope for scope in found
            if scope in sources or f"names.{by_value[scope]}" in sources
            or f'"{by_value[scope]}"' in sources}
    assert {scopes.GQA, scopes.SWA, scopes.GQA_ATTENTION} <= read
    inside_the_model = [chain[1:] for chain in chains
                        if chain[:1] == (scopes.MODEL,) and len(chain) > 1]
    assert inside_the_model
    for chain in inside_the_model:
        assert read.intersection(chain), chain


RECORDED_PATH = os.path.join(spec.BENCH_DIR, "testdata", "v5e_afmoe_step_scopes.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_PATH) as fh:
        held = json.load(fh)
    names = held["names"]
    planes = tr.planes_from_json([{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[names[i], s, d] for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in held["planes"]])
    return held, tr.device_planes(planes)[0]


def _observed(held, chip, monkeypatch, tmp_path):
    cell = spec.resolve_cell(CELL, trace=True)
    obs = Observed(
        cell=cell, devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        end_to_end={}, window_s=1.0, attempted=0, failed=0, checks={},
        compile_stats={}, memory_peak_bytes=0, work=dict(held["work"]),
        step_program=held["step_program"], trace_dir=str(tmp_path))
    obs.__dict__["trace"] = TraceView([chip], tr.traced_window([chip]), None)
    monkeypatch.setattr(compilecache, "scope_map", lambda label: held["modules"])
    return obs


def test_recorded_step_splits_into_the_new_scopes(recorded):
    held, chip = recorded
    split = hs.split_plane(chip, held["step_program"], held["modules"], scopes)
    assert len(split.executions) == len(tr.program_runs(chip, held["step_program"])) - 2
    for parts, total in zip(split.executions, split.durations_ns):
        assert sum(parts.values()) == pytest.approx(total)
    chains = {hs.split_key(k)[0] for parts in split.executions for k in parts}
    for scope in (scopes.GQA, scopes.SWA, scopes.GQA_ATTENTION, scopes.MOE,
                  scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS,
                  scopes.OPTIMIZER):
        assert any(scope in chain for chain in chains), scope
    assert all(scopes.GQA in chain for chain in chains if scopes.SWA in chain)
    assert not any(scopes.MLA in chain or scopes.MLA_ATTENTION in chain
                   for chain in chains)
    # the cores' kernels run under the cores' scope, in window and full mixers
    kernels = [name for name in held["names"] if "mla_attention_" in name]
    assert any("mla_attention_forward" in n for n in kernels)
    assert any("mla_attention_backward" in n for n in kernels)
    assert split.unscoped_share() <= 6.0


def test_the_readers_old_and_new_on_the_recorded_step(recorded, monkeypatch, tmp_path):
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    trace_readers = [name for name in held["expected"]
                     if name != "swa_key_tiles_visited_share"]
    values = {name: spec.load_module("layer_metrics", name).read(obs)
              for name in trace_readers}
    for name in trace_readers:
        assert values[name] == pytest.approx(held["expected"][name], rel=1e-6), name
    assert set(NEW_READERS) | set(SHARED_READERS) | {"gqa_device_ms"} == set(
        held["expected"])
    assert 0 < values["gqa_attention_roofline"] < 100
    assert 0 < values["moe_experts_roofline"] < 100
    assert 0 < values["swa_device_ms"] < values["gqa_device_ms"]
    assert 0 < values["lm_head_loss_device_ms"] < values["gqa_device_ms"]
    assert 1.0 <= values["moe_held_load_max_over_mean"] <= 8.0
    # the cores' share by hand: the operations bound it at these sizes
    model = CONFIG["model"]
    cores_ms = hs.scope_ms(obs, scopes.GQA_ATTENTION)
    assert 0 < cores_ms < values["gqa_device_ms"]
    kinds = model["layer_types"][:model["layers_held"]]
    operations = sum(3 * FLOPS.gqa_attention_operations(model, kind, 16384, backward=False)
                     for kind in kinds)
    assert values["gqa_attention_roofline"] == pytest.approx(
        100 * (operations / 197e12) / (cores_ms / 1e3), rel=1e-6)
    # the counter's reading as the chip run's registry gave it
    assert held["expected"]["swa_key_tiles_visited_share"] == pytest.approx(
        100 * 150 / 528)
    # the latent-attention readers have nothing of theirs to read here
    assert spec.load_module("layer_metrics", "mla_device_ms").read(obs) in (None, 0.0)


def test_readers_on_a_program_from_before_the_scopes(recorded, monkeypatch, tmp_path):
    """The parent's program under this tree's benchmark files: no such
    scope in its table and no such counter in its registry, so each new
    reader returns None and does not raise; nor does the share on a
    configuration whose operations file lacks its functions."""
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    nemotron = spec.resolve_cell(NEMOTRON_CELL, trace=True)
    reader = spec.load_module("layer_metrics", "gqa_attention_roofline")
    assert reader.read(types.SimpleNamespace(
        cell=nemotron, work=obs.work, devices=obs.devices)) is None
    for name in ("SWA", "GQA_ATTENTION"):
        monkeypatch.delattr(scopes, name)
    monkeypatch.setattr(telemetry.registry(), "counters_snapshot", lambda: {
        'faa_mla_attention_traces_total{form="fused"}': 5.0})
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(obs) is None, name


def test_the_key_tile_share_is_visited_over_causal_of_the_cores_with_a_span(monkeypatch):
    reader = spec.load_module("layer_metrics", "swa_key_tiles_visited_share")
    monkeypatch.setattr(telemetry.registry(), "counters_snapshot", lambda: {
        'faa_attention_key_tiles_total{kind="visited",span="2048"}': 1500.0,
        'faa_attention_key_tiles_total{kind="causal",span="2048"}': 5280.0,
        'faa_attention_key_tiles_total{kind="visited",span="none"}': 1056.0,
        'faa_attention_key_tiles_total{kind="causal",span="none"}': 1056.0})
    assert reader.read(None) == pytest.approx(28.409, abs=1e-3)
    # the day a change masks the band and does not skip it
    monkeypatch.setattr(telemetry.registry(), "counters_snapshot", lambda: {
        'faa_attention_key_tiles_total{kind="visited",span="2048"}': 5280.0,
        'faa_attention_key_tiles_total{kind="causal",span="2048"}': 5280.0})
    assert reader.read(None) == 100.0
