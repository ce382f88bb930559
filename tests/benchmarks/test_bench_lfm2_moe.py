"""The LFM2-8B-A1B configuration's own files: its operations against a hand
count, its plain reference against the program on seeded weights, the
controls its comparison must refuse, its program rehearsed on the CPU at a
tiny size, and its readers — the two new ones, ``gqa_device_ms``,
``gqa_attention_roofline`` and the expert and head readers the token cells
share — on an excerpt recorded on the chip
(``benchmarks/testdata/v5e_lfm2_moe_step_scopes.json``).

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

FLOPS = spec.load_module("flops", "lfm2_moe")
REFERENCE = spec.load_module("references", "lfm2_moe")
CONFIG = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "lfm2_8b_a1b_tokens.json"))
CELL = "lfm2_8b_a1b_train"
TRINITY_CELL = "trinity_mini_train"
NEW_READERS = ("short_conv_device_ms", "short_conv_gate_roofline")
CONV, FULL = "conv", "full_attention"

#: every width cut for the CPU, the structure kept: the cut's seven blocks
#: (two dense, five expert layers; five convolution mixers, two attention
#: mixers of 4 heads of 8 on 2 key-value heads); 16 experts of which 4 are
#: held, top-2
TINY_MODEL = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=16, num_experts=16,
    num_experts_per_tok=2, vocab_size=64, num_hidden_layers=8,
    layer_types=[CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV])
TINY_HELD = dict(layers_held=7, experts_held=4, ids_held=48)


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
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 ** 2 + 2 * 2048 * 512 + 128
    expert = 3 * 2048 * 1792
    assert FLOPS.mixer_params(model, CONV) == conv == 16_783_360
    assert FLOPS.mixer_params(model, FULL) == attention == 10_485_888
    assert FLOPS.dense_ffn_params(model) == 3 * 2048 * 7168 == 44_040_192
    assert FLOPS.expert_params(model) == expert == 11_010_048
    dense_block = conv + 44_040_192 + 2 * 2048
    assert dense_block == 60_827_648
    expert_layer = 2048 * 32 + 32 + 8 * expert                # no shared expert
    assert FLOPS.expert_layer_params(model, 8) == expert_layer == 88_145_952
    assert conv + 2 * 2048 + expert_layer == 104_933_408
    assert attention + 2 * 2048 + expert_layer == 98_635_936
    held = (2 * dense_block + 2 * 98_635_936 + 3 * 104_933_408
            + 16384 * 2048 + 2048)                            # one table, tied
    assert FLOPS.num_params(model) == held == 667_283_872
    # 16 bytes a parameter: float32 weights, gradients, AdamW's two moments
    assert 16 * held == pytest.approx(10.68e9, rel=1e-3)
    assert FLOPS.held_layers(model, CONV) == 5 and FLOPS.held_layers(model, FULL) == 2
    assert FLOPS.held_expert_layers(model) == 5
    whole = dict(model, layers_held=None, experts_held=None, ids_held=None)
    assert FLOPS.held_layers(whole, CONV) == 18 and FLOPS.held_layers(whole, FULL) == 6
    assert FLOPS.num_params(whole) == (
        2 * dense_block + 16 * 369_174_560 + 6 * 362_877_088 + 65536 * 2048 + 2048
    ) == 8_339_930_560                                        # the published 8.3 B
    assert FLOPS.num_params(dict(whole, tied_head=False)) == 8_474_148_288
    six = dict(model, layers_held=6)                          # the issue's fall-back cut
    assert FLOPS.num_params(six) == held - 98_635_936 == 568_647_936


def test_step_operations_count_a_head_at_its_own_width():
    model = CONFIG["model"]
    tokens = 16384
    triangle = tokens * (tokens + 1) // 2
    assert FLOPS.visible_pairs(model, FULL, tokens) == triangle == 134_225_920
    assert FLOPS.visible_pairs(model, CONV, tokens) == 0
    per_pair = 2 * 32 * (64 + 64)                  # the mathematics pads no head
    assert FLOPS.gqa_attention_operations(model, FULL, tokens, backward=False) == (
        per_pair * triangle)
    assert FLOPS.gqa_attention_operations(model, FULL, tokens, backward=True) == (
        2 * per_pair * triangle)
    assert FLOPS.gqa_attention_operations(model, CONV, tokens, backward=True) == 0
    assert FLOPS.gqa_attention_bytes(model, CONV, tokens, backward=False) == 0
    conv_mixer = 2 * 4 * 2048 ** 2
    attention_mixer = 2 * 2048 * 64 * 2 * (32 + 8)
    dense = 2 * 3 * 2048 * 7168
    # 8 of 32 experts held, top-4: a token reaches 4 * 8 / 32 = 1 of them
    experts = 2 * (2048 * 32 + 1.0 * 3 * 2048 * 1792)
    head = 2 * 2048 * 16384
    gates = 8 * 2048
    forward = ((head + 5 * (conv_mixer + gates) + 2 * attention_mixer + 2 * dense
                + 5 * experts) * tokens + 2 * per_pair * triangle)
    assert FLOPS.forward_flops_per_image(model) == pytest.approx(forward, rel=1e-12)
    assert FLOPS.train_flops_per_image(model) == pytest.approx(3 * forward)
    assert 34.0e12 < 3 * forward < 34.6e12                    # 34.3 TFLOP a step owed
    # a held expert's load: 16,384 x 4 / 32 = 2,048 a step, a quarter of the
    # deployment's (four chips' tokens reach it there)
    assert tokens * model["top_k"] / model["experts"] == 2048


def test_the_cores_and_the_gates_bytes_are_their_operands_and_results_once():
    model = CONFIG["model"]
    q = o = 32 * 64
    k = v = 8 * 64
    assert FLOPS.gqa_attention_bytes(model, FULL, 1, backward=False) == 4 * (q + k + v + o)
    assert FLOPS.gqa_attention_bytes(model, FULL, 1, backward=True) == 4 * (
        2 * (q + k + v) + 2 * o)
    # the operations bound the cores at these sizes
    operations = 3 * FLOPS.gqa_attention_operations(model, FULL, 16384, backward=False)
    moved = sum(FLOPS.gqa_attention_bytes(model, FULL, 16384, backward=b)
                for b in (False, True))
    assert operations / 197e12 > moved / 819e9
    # the gates and taps: 4 words a channel a token forward, 7 backward; 8
    # operations forward, 16 backward; the bytes bound them
    assert FLOPS.short_conv_gate_bytes(model, 16384, backward=False) == 4 * 4 * 2048 * 16384
    assert FLOPS.short_conv_gate_bytes(model, 16384, backward=True) == 4 * 7 * 2048 * 16384
    assert FLOPS.short_conv_gate_operations(model, 16384, backward=False) == 8 * 2048 * 16384
    assert FLOPS.short_conv_gate_operations(model, 16384, backward=True) == 16 * 2048 * 16384
    both = sum(FLOPS.short_conv_gate_bytes(model, 16384, backward=b) for b in (False, True))
    assert both == pytest.approx(1.476e9, rel=1e-3)           # 1.48 GB a layer a step
    assert both / 819e9 == pytest.approx(1.80e-3, rel=1e-2)   # 1.8 ms at the roofline
    assert both / 819e9 > 24 * 2048 * 16384 / 197e12
    assert FLOPS.moe_experts_operations(model, 2048, backward=False) == (
        2 * 3 * 2048 * 1792 * 2048)
    weights = 4 * 8 * 3 * 2048 * 1792
    assert FLOPS.moe_experts_bytes(model, 0, backward=False) == weights


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_configuration_file_states_the_published_model_and_the_cut():
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key                # every key as published
        assert CONFIG["conf"]["model"][key] == value, key
    assert CONFIG["reduced"] == ["layers_held", "experts_held", "ids_held"]
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [7, 8, 16384]
    assert set(CONFIG["reduced_because"]) == set(CONFIG["reduced"])
    assert CONFIG["published"] == dict(
        CONFIG["published"], num_hidden_layers=24, num_experts=32, vocab_size=65536)
    assert "4 chips" in CONFIG["deployment"] and "667,283,872" in CONFIG["deployment"]
    # the floors: both leading dense blocks, a whole period of the pattern,
    # four expert layers, 8 experts, an eighth of the vocabulary
    held = CONFIG["layer_types"][:CONFIG["layers_held"]]
    assert held[2:6] == [FULL, CONV, CONV, CONV] and held[:2] == [CONV, CONV]
    assert CONFIG["layers_held"] - CONFIG["num_dense_layers"] >= 4
    assert CONFIG["experts_held"] >= 8 and 8 * CONFIG["ids_held"] >= CONFIG["vocab_size"]
    assert 0 < CONFIG["logit_tolerance_float32"] < CONFIG["logit_tolerance"]
    assert 0 < CONFIG["routing_margin_tolerance_float32"] < CONFIG["routing_margin_tolerance"]
    for key in ("logit_tolerance_because", "logit_tolerance_float32_because",
                "routing_margin_because", "learned_measured", "router_measured"):
        assert CONFIG[key] and "@@" not in CONFIG[key], key
    assert "@@" not in json.dumps(CONFIG["reduced_because"])
    assert {"tied_head", "head_dim", "qk_norm", "rotary", "short_conv", "two_norms",
            "renorm_eps", "feed_forward", "router_bias_update_rate", "initial_values",
            "optimizer", "precision"} <= set(CONFIG["assumed"])
    # no width differs from the source: what is reduced is no width
    assert not any(word in key for key in CONFIG["reduced"]
                   for word in ("_dim", "_rank", "width", "hidden", "size"))


def test_configuration_self_test_passes():
    from tests.benchmarks.test_bench_spec import check_config

    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "lfm2_8b_a1b_tokens")
    check_config(REPO, entry)
    model = CONFIG["model"]
    assert model["seq_len"] == 16384 and model["expert_share"] == 0
    # what gqa_attention_roofline takes from the block, as trinity_mini_tokens.json's has it
    assert (model["heads"], model["kv_heads"], model["head_dim"]) == (32, 8, 64)
    assert model["layer_types"][:model["layers_held"]].count(FULL) == 2
    assert model["taps"] == 3 and model["renorm_eps"] == 1e-6 and model["tied_head"]


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
    assert sorted(params) == ["embed_tokens"] + [f"layer{i}" for i in range(1, 8)] + ["norm"]


@pytest.mark.parametrize("control", [
    "untied_head", "no_qk_norm", "no_rotary", "interleaved_pairs", "split_cbz",
    "silu_after_taps", "taps_shifted", "no_final_norm", "neighbours_queries",
    "one_layer_short", "bf16"])
def test_controls_the_float32_comparison_must_refuse(tiny_system, control):
    """The reference with a head of its own, without the norms a head, with
    no rotation, with the pairs ``(2i, 2i + 1)``, with the split read as
    ``C, B, z``, with a SiLU after the taps, with the taps a token late,
    without the final norm, with every odd head asking its even
    neighbour's query, one layer short; and the program in bfloat16 under
    ``highest``: each over the configuration's float32 limit, the system's
    routing given."""
    conf, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, routing = _logits_and_routing(model, params, ids)
    assert sorted(routing) == ["layer3", "layer4", "layer5", "layer6", "layer7"]
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
        changed = dict(sizes, layers_held=6)
        given = {k: v for k, v in routing.items() if k != "layer7"}
    else:
        changed = dict(sizes, control=control)
    if control == "untied_head":
        params = dict(params, lm_head={"kernel": 0.02 * jax.random.normal(
            jax.random.PRNGKey(11), (32, 48))})
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
    """A copy of the benchmark with a tiny lfm2_moe configuration, fixture,
    traffic and cell dropped in as new files and entries."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    conf = tiny_conf(batch=2, lr=0.003)
    config = dict(CONFIG, conf=conf, model=tiny_sizes(conf, 64))
    _write(os.path.join(bench_dir, "configs", "tiny_lfm2.json"), config)
    fixture = _read(os.path.join(bench_dir, "fixtures", "tokens_markov_16384_16k.json"))
    fixture.update(train=8, test=2, length=64, ids=48)
    _write(os.path.join(bench_dir, "fixtures", "tiny_lfm2.json"), fixture)
    traffic = _read(os.path.join(bench_dir, "traffic", "train_epochs_tokens_16384_16k.json"))
    traffic.update(fixture="tiny_lfm2", trace_seconds=1.5,
                   loss_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_lfm2_train.json"), traffic)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_lfm2", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_lfm2.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_lfm2_train", "config": "tiny_lfm2",
        "traffic": "tiny_lfm2_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_lfm2_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = build_tiny_checkout(str(tmp_path_factory.mktemp("lfm2")))
    cell = spec.resolve_cell("tiny_lfm2_train", seed=2**31 + 49, seconds=1.0,
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
    assert obs.checks["routing"]["layers"] == ["layer3", "layer4", "layer5", "layer6",
                                               "layer7"]
    assert obs.checks["routing_float32"]["margin"] < 1e-5
    meta_loss = obs.checks["learned"]["loss_train"]
    assert math.isfinite(meta_loss) and meta_loss < math.log(48) + 1.0


def test_program_hands_the_counters_to_the_readers(rehearsed):
    obs, _, rise = rehearsed
    by_layer = obs.work["moe_assignments_a_step_by_layer"]
    assert sorted(by_layer) == ["layer3", "layer4", "layer5", "layer6", "layer7"]
    # 128 tokens x top-2 x 4 of 16 experts held: 64 a step expected
    assert all(0 < n < 128 * 2 for n in by_layer.values())
    assert sorted(obs.work["moe_held_load_max_over_mean"]) == sorted(by_layer)
    assert obs.work["tokens_a_step"] == 128
    # trace time: the rehearsed programs' mixers, five convolutions to two cores
    convs = rise['faa_short_conv_traces_total{taps="3"}']
    cores = rise['faa_attention_cores_traced_total{form="blocked_xla",span="none"}']
    assert 2 * convs == 5 * cores > 0
    assert not any("fused" in key for key in rise if "cores_traced" in key and rise[key])
    # these small heads take the XLA form: no block of the kernels, no pair
    assert not any(key.startswith("faa_attention_head_blocks") and rise[key]
                   for key in rise)


def test_a_program_without_the_model_refuses_the_cells_conf_before_it_trains():
    """What the parent does with this cell: its registry knows no such
    model type, and ``train_tokens.ComparisonsAhead`` builds the model
    before the trainer is entered, so the run ends there, non-zero and at
    once (on the chip: exit 1 after the imports)."""
    conf = tiny_conf()
    conf["model"]["type"] = "lfm2_moe_of_a_later_pr"
    with pytest.raises(ValueError, match="unknown model type"):
        get_model(model_conf_of(conf), 48)
    program = spec.load_module("programs", "train_tokens")
    cell = types.SimpleNamespace(
        config={"model": {"ids_held": 48}, "reference": "lfm2_moe"},
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
    trinity = {m["name"] for m in bench["per_layer"]
               if TRINITY_CELL in m.get("workloads", ())}
    assert set(NEW_READERS) <= names - trinity
    assert {"gqa_device_ms", "gqa_attention_roofline"} <= names & trinity
    assert {"step_device_ms", "model_flops_utilization", "model_forward_device_ms",
            "model_backward_device_ms", "step_unscoped_share", "peak_hbm_bytes",
            "device_idle_share", "compile_first_call_s", "compile_cache_misses",
            "dispatch_gap_ms", "optimizer_device_ms",
            "batch_gather_device_ms"} <= names
    assert not any(n.startswith(("aug_", "shake_", "resnet_", "feed_", "host_",
                                 "kda_", "mla_", "mtp_", "mamba2_", "ssd_", "swa_"))
                   for n in names)
    # the five lists two self-tests pin to the Kimi cell stay as they are
    assert not names & set(SHARED_READERS) and "mla_device_ms" not in names
    for name in NEW_READERS:
        entry = next(m for m in listed if m["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] == "models"
        assert entry["source"] == "device_trace" and entry["moves"] == "train_images_per_s"
    [workload] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload["chips"] == 1 and len(workload["why"]) <= 200
    assert workload["config"] == "lfm2_8b_a1b_tokens"
    assert workload["config"] in {c["name"] for c in bench["configs"]}
    assert cell.traffic["program"] == "train_tokens"
    assert cell.traffic["fixture"] == "tokens_markov_16384_16k"
    assert cell.fixture["ids"] == 16384 and cell.fixture["length"] == 16384
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    end_to_end = next(m for m in bench["end_to_end"] if m["name"] == "train_images_per_s")
    assert CELL in end_to_end["workloads"]
    # the traffic is the Trinity cell's but for the fixture and what is said of it
    other = spec.resolve_cell(TRINITY_CELL).traffic
    differing = {k for k in other if other[k] != cell.traffic[k]}
    assert differing == {"describes", "fixture", "reduced", "loss_margin_because"}
    assert "9.704" in cell.traffic["loss_margin_because"]
    assert math.log(16384) == pytest.approx(9.704, abs=1e-3)
    # the fixture is tokens_markov_25024_16k.json's but for the count of ids
    base = _read(os.path.join(spec.BENCH_DIR, "fixtures", "tokens_markov_25024_16k.json"))
    assert {k for k in base if base[k] != cell.fixture[k]} == {"describes", "ids"}


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
    assert {scopes.SHORT_CONV, scopes.SHORT_CONV_GATE, scopes.GQA, scopes.GQA_ATTENTION,
            scopes.MOE, scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD,
            scopes.LOSS, scopes.OPTIMIZER} <= found
    assert scopes.MLA_ATTENTION not in found and scopes.SWA not in found
    # the gates and taps only ever inside a convolution mixer, the cores in an attention one
    assert all(scopes.SHORT_CONV in chain for chain in chains
               if scopes.SHORT_CONV_GATE in chain)
    assert all(scopes.GQA in chain for chain in chains if scopes.GQA_ATTENTION in chain)
    assert not any(scopes.GQA in chain and scopes.SHORT_CONV in chain for chain in chains)
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
    assert {scopes.SHORT_CONV, scopes.SHORT_CONV_GATE, scopes.GQA,
            scopes.GQA_ATTENTION} <= read
    inside_the_model = [chain[1:] for chain in chains
                        if chain[:1] == (scopes.MODEL,) and len(chain) > 1]
    assert inside_the_model
    for chain in inside_the_model:
        assert read.intersection(chain), chain


RECORDED_PATH = os.path.join(spec.BENCH_DIR, "testdata", "v5e_lfm2_moe_step_scopes.json")


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
    for scope in (scopes.SHORT_CONV, scopes.SHORT_CONV_GATE, scopes.GQA,
                  scopes.GQA_ATTENTION, scopes.MOE, scopes.MOE_ROUTER,
                  scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS, scopes.OPTIMIZER):
        assert any(scope in chain for chain in chains), scope
    assert all(scopes.SHORT_CONV in chain for chain in chains
               if scopes.SHORT_CONV_GATE in chain)
    assert not any(scopes.MLA in chain or scopes.MLA_ATTENTION in chain
                   or scopes.SWA in chain for chain in chains)
    # the cores' kernels run under the cores' scope
    kernels = [name for name in held["names"] if "mla_attention_" in name]
    assert any("mla_attention_forward" in n for n in kernels)
    assert any("mla_attention_backward" in n for n in kernels)
    # the run's own share is 3.97%; the 15,636 short operations the excerpt leaves out
    # (the grouped experts' loops', 22 ms a step) read as unscoped here
    assert split.unscoped_share() <= 9.0


def test_the_readers_old_and_new_on_the_recorded_step(recorded, monkeypatch, tmp_path):
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    values = {name: spec.load_module("layer_metrics", name).read(obs)
              for name in held["expected"]}
    for name in held["expected"]:
        assert values[name] == pytest.approx(held["expected"][name], rel=1e-6), name
    assert set(NEW_READERS) | set(SHARED_READERS) | {
        "gqa_device_ms", "gqa_attention_roofline"} == set(held["expected"])
    assert 0 < values["gqa_attention_roofline"] < 100
    assert 0 < values["short_conv_gate_roofline"] < 100
    assert 0 < values["moe_experts_roofline"] < 100
    gates_ms = hs.scope_ms(obs, scopes.SHORT_CONV_GATE)
    assert 0 < gates_ms < values["short_conv_device_ms"]
    assert 0 < values["lm_head_loss_device_ms"]
    assert 1.0 <= values["moe_held_load_max_over_mean"] <= 8.0
    # the gates' share by hand: the bytes bound it, five layers' 11 words a channel a token
    model = CONFIG["model"]
    assert values["short_conv_gate_roofline"] == pytest.approx(
        100 * (5 * 11 * 4 * 2048 * 16384 / 819e9) / (gates_ms / 1e3), rel=1e-6)
    # the cores' share by hand: the operations bound it, at a head's own width
    cores_ms = hs.scope_ms(obs, scopes.GQA_ATTENTION)
    assert 0 < cores_ms < values["gqa_device_ms"]
    operations = 2 * 3 * FLOPS.gqa_attention_operations(model, FULL, 16384, backward=False)
    assert values["gqa_attention_roofline"] == pytest.approx(
        100 * (operations / 197e12) / (cores_ms / 1e3), rel=1e-6)
    # the latent-attention and window readers have nothing of theirs to read here
    assert spec.load_module("layer_metrics", "mla_device_ms").read(obs) in (None, 0.0)
    assert spec.load_module("layer_metrics", "swa_device_ms").read(obs) in (None, 0.0)


def test_readers_on_a_program_from_before_the_scopes(recorded, monkeypatch, tmp_path):
    """The parent's program under this tree's benchmark files: no such
    scope in its table, so each new reader returns None and does not raise;
    nor does the gates' share on a configuration whose operations file
    lacks its functions."""
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    trinity = spec.resolve_cell(TRINITY_CELL, trace=True)
    reader = spec.load_module("layer_metrics", "short_conv_gate_roofline")
    assert reader.read(types.SimpleNamespace(
        cell=trinity, work=obs.work, devices=obs.devices)) is None
    for name in ("SHORT_CONV", "SHORT_CONV_GATE"):
        monkeypatch.delattr(scopes, name)
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(obs) is None, name
