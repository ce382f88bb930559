"""The Kimi Linear configuration's own files: its operations against a hand
count, its plain reference against the program on seeded weights, its
fixture, its program rehearsed on the CPU at a tiny size, and its seven
readers on an excerpt recorded on the chip
(``benchmarks/testdata/v5e_kimi_linear_step_scopes.json``)."""

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
from benchmarks.harness import fixture_tokens, spec
from benchmarks.harness import scopes as hs
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from fast_autoaugment_tpu.core import compilecache, scopes
from fast_autoaugment_tpu.models import get_model, model_conf_of

REPO = spec.ROOT

FLOPS = spec.load_module("flops", "kimi_linear")
REFERENCE = spec.load_module("references", "kimi_linear")
CONFIG = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "kimi_linear_48b_a3b_tokens.json"))
CELL = "kimi_linear_48b_a3b_train"
NEW_READERS = ("kda_device_ms", "mla_device_ms", "moe_device_ms",
               "lm_head_loss_device_ms", "kda_scan_roofline",
               "moe_experts_roofline", "moe_held_load_max_over_mean")

#: every width cut for the CPU, the structure kept: five layers (KDA +
#: dense, KDA, KDA, MLA, KDA), 16 experts of which 4 are held, top-4
TINY_MODEL = dict(
    hidden_size=64, intermediate_size=96, kv_lora_rank=16,
    moe_intermediate_size=32, num_attention_heads=2, num_experts=16,
    num_experts_per_token=4, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, vocab_size=64)
TINY_HELD = dict(layers_held=5, experts_held=4, ids_held=48)


def tiny_conf(**top) -> dict:
    with open(os.path.join(REPO, CONFIG["repo_conf"])) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(TINY_MODEL)
    conf["model"]["linear_attn_config"].update(head_dim=8, num_heads=2)
    conf.update(TINY_HELD, **top)
    return conf


def tiny_sizes(conf: dict, seq_len: int) -> dict:
    return dict(FLOPS.model_from_conf(conf["model"]), expert_share=0,
                seq_len=seq_len, **{k: conf[k] for k in TINY_HELD})


# ------------------------------------------------- operations, by hand


def test_parameters_held_and_whole_against_a_hand_count():
    model = CONFIG["model"]
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 3 * 4 * 4096 + 32 + 4096 + 128)
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304 + 512
    expert = 3 * 2304 * 1024
    assert FLOPS.kda_mixer_params(model) == kda == 39_514_272
    assert FLOPS.mla_mixer_params(model) == mla == 29_114_880
    assert FLOPS.expert_params(model) == expert == 7_077_888
    expert_layer = 2304 * 256 + 256 + 9 * expert + 2 * 2304
    held = (2 * 20480 * 2304 + 2304                      # embedding, head, norm
            + kda + 3 * 2304 * 9216 + 2 * 2304           # layer 1
            + 3 * (kda + expert_layer) + (mla + expert_layer))
    assert FLOPS.num_params(model) == held == 602_434_432
    whole = dict(model, layers_held=None, experts_held=None, ids_held=None)
    assert 48e9 < FLOPS.num_params(whole) < 50e9         # "48B"
    # 16 bytes a parameter: float32 weights, gradients, AdamW's two moments
    assert 16 * held == pytest.approx(9.64e9, rel=1e-3)


def test_forward_operations_against_a_hand_count():
    model = CONFIG["model"]
    tokens = 8192
    kda_products = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    mla_products = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)
    expert = 2 * 3 * 2304 * 1024
    # 8 of 256 experts held, top-8: a token reaches 8 * 8 / 256 = 0.25 of them
    expert_layer = 2 * 2304 * 256 + (0.25 + 1) * expert
    per_token = (2 * 2304 * 20480 + 4 * kda_products + mla_products
                 + 2 * 3 * 2304 * 9216 + 4 * expert_layer)
    recurrence = 4 * 7 * 128 * 128 * 32 * tokens
    attention = 2 * 32 * (192 + 128) * tokens * (tokens + 1) / 2
    forward = per_token * tokens + recurrence + attention
    assert FLOPS.forward_flops_per_image(model) == pytest.approx(forward, rel=1e-12)
    assert FLOPS.train_flops_per_image(model) == pytest.approx(3 * forward)
    assert 0.7e9 < forward / tokens < 0.8e9              # 0.77 GFLOP a token


def test_the_two_kernels_operations_and_bytes_are_the_mathematics():
    model = CONFIG["model"]
    assert FLOPS.kda_scan_operations(model, 8192, backward=False) == (
        7 * 128 * 128 * 32 * 8192)
    assert FLOPS.kda_scan_operations(model, 8192, backward=True) == (
        2 * 7 * 128 * 128 * 32 * 8192)
    # q, k, g, v (128 each) and beta in, o out; backward: the five and do
    # in, five gradients out
    assert FLOPS.kda_scan_bytes(model, 1, backward=False) == 4 * 32 * (513 + 128)
    assert FLOPS.kda_scan_bytes(model, 1, backward=True) == 4 * 32 * (2 * 513 + 128)
    assert FLOPS.moe_experts_operations(model, 2048, backward=False) == (
        2 * 3 * 2304 * 1024 * 2048)
    weights = 4 * 8 * 3 * 2304 * 1024
    assert FLOPS.moe_experts_bytes(model, 0, backward=False) == weights
    assert FLOPS.moe_experts_bytes(model, 2048, backward=True) == 2 * (
        weights + 4 * 2 * 2304 * 2048)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_configuration_file_states_the_published_model_and_the_cut():
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key                # every key as published
        assert CONFIG["conf"]["model"][key] == value, key
    assert CONFIG["reduced"] == ["layers_held", "experts_held", "ids_held"]
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [5, 8, 20480]
    assert set(CONFIG["reduced_because"]) == set(CONFIG["reduced"])
    assert CONFIG["published"]["num_experts"] == 256 and "32 chips" in CONFIG["deployment"]
    # the floors: a whole period after the dense layer, 8 experts, an eighth
    held_kinds = ["kda" if i in CONFIG["model"]["kda_layers"] else "mla"
                  for i in range(1, 6)]
    assert held_kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert CONFIG["experts_held"] >= 8 and 8 * CONFIG["ids_held"] >= CONFIG["vocab_size"]
    assert 0 < CONFIG["logit_tolerance_float32"] < CONFIG["logit_tolerance"]


# --------------------------------------- the reference against the program


@pytest.fixture(scope="module")
def tiny_system():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), conf["ids_held"])
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 129), 0, 48))
    params = jax.jit(model.init, static_argnames=("train",))(
        {"params": jax.random.PRNGKey(1)}, ids[:, :-1], train=False)["params"]
    # off their initial ones and zeros, so that a norm or a bias left out shows
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    return conf, model, params, ids, tiny_sizes(conf, 128)


def test_program_parameters_are_what_the_operations_file_counts(tiny_system):
    _, _, params, _, sizes = tiny_system
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)


def test_reference_logits_loss_and_gradients_agree_with_the_program(tiny_system):
    _, model, params, ids, sizes = tiny_system

    def loss(p):
        logits = model.apply({"params": p}, ids[:, :-1])
        picked = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(lambda p, x: model.apply({"params": p}, x))(
            params, ids[:, :-1]))
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
    plain = REFERENCE.forward(params, {}, ids[:, :-1], sizes)
    assert np.abs(logits - plain).max() <= 1e-5 * np.abs(plain).max()
    plain_loss, plain_grads = REFERENCE.loss_and_grads(params, ids, sizes)
    assert float(value) == pytest.approx(plain_loss, rel=1e-6)
    gaps = jax.tree.map(lambda a, b: float(np.abs(a - b).max() / np.abs(b).max()),
                        {k: v for k, v in grads.items()}, plain_grads)
    # the correction bias has no gradient: 0 / 0 there
    worst = max(g for g in jax.tree.leaves(gaps) if math.isfinite(g))
    assert worst < 2e-4, gaps


def test_reference_one_held_expert_short_is_refused(tiny_system):
    """Every held expert of every expert layer, left out of the reference
    in turn (the system's routing given): each that a token of these
    chose reads over the float32 limit."""
    _, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, sown = model.apply({"params": params}, inputs, mutable=["routing"])
    routing = {layer: np.asarray(entry["moe"]["chosen"][0])
               for layer, entry in sown["routing"].items()}
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
            gap = np.abs(np.asarray(logits) - short).max() / np.abs(short).max()
            assert gap > CONFIG["logit_tolerance_float32"], (layer, expert, gap)
            tried += 1
    assert tried >= held


def test_reference_given_the_systems_routing_is_the_forward_and_says_how_far_a_choice_is(
        tiny_system):
    """The reference with every layer's choice of experts handed in:
    given the model's own (``sow``n into ``routing``) it is the plain
    forward, margin at rounding; given a choice no router made, the
    margin is the scores' size; `kept` drops a held expert without
    another compilation; compiled ahead from shapes it is the same
    program."""
    _, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, sown = model.apply({"params": params}, inputs, mutable=["routing"])
    routing = {layer: np.asarray(entry["moe"]["chosen"][0])
               for layer, entry in sown["routing"].items()}
    layers = sorted(routing)
    assert layers == [f"layer{i}" for i in range(2, sizes["layers_held"] + 1)]
    assert routing[layers[0]].shape == inputs.shape + (sizes["top_k"],)
    own = REFERENCE.forward(params, {}, inputs, sizes)
    given, margin = REFERENCE.forward_given_routing(params, inputs, sizes, routing)
    assert np.abs(given - own).max() <= 1e-5 * np.abs(own).max()
    assert 0.0 <= margin < 1e-5
    assert np.abs(np.asarray(logits) - given).max() <= 1e-4 * np.abs(given).max()
    # a choice the scores do not support: every token to the experts 0..k-1
    wrong = dict(routing)
    wrong[layers[-1]] = np.broadcast_to(
        np.arange(sizes["top_k"], dtype=np.int32), routing[layers[-1]].shape)
    _, far = REFERENCE.forward_given_routing(params, inputs, sizes, wrong)
    assert far > 0.05
    # one held expert of one layer dropped: that layer's tokens move
    held = sizes["experts_held"]
    kept = {layers[0]: np.asarray([0.0] + [1.0] * (held - 1), np.float32)}
    dropped, _ = REFERENCE.forward_given_routing(params, inputs, sizes, routing, kept)
    assert np.abs(dropped - given).max() > CONFIG["logit_tolerance_float32"] * np.abs(given).max()
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    ahead = REFERENCE.compile_forward_given_routing(
        shapes, jax.ShapeDtypeStruct(inputs.shape, jnp.int32), sizes)
    again, margin_again = ahead(params, inputs, routing)
    assert np.array_equal(again, given) and margin_again == margin
    assert np.array_equal(ahead(params, inputs, routing, kept)[0], dropped)


def test_the_checkpoints_params_entry_is_read_alone(tmp_path, tiny_system):
    """``programs/train_tokens.py::checkpoint_params`` on a file the
    trainer's writer wrote: the parameters, and not the moments after them."""
    from fast_autoaugment_tpu.core.checkpoint import save_checkpoint
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import create_train_state

    conf, model, _, ids, _ = tiny_system
    state = create_train_state(
        model, build_optimizer(conf["optimizer"], lambda step: 1e-3),
        jax.random.PRNGKey(3), ids[:, :-1], use_ema=False, jit_init=True)
    path = str(tmp_path / "model.msgpack")
    save_checkpoint(path, state, {"step": 0})
    program = spec.load_module("programs", "train_tokens")
    read = program.checkpoint_params(path)
    assert jax.tree.structure(read) == jax.tree.structure(
        jax.tree.map(np.asarray, state.params))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(read), jax.tree.leaves(state.params)))
    with open(path, "wb") as fh:
        fh.write(b"\x80")  # an empty map
    with pytest.raises(KeyError):
        program.checkpoint_params(path)


# ----------------------------------------------------------- the fixture


def test_token_fixture_is_seeded_and_in_the_programs_layout(tmp_path):
    from fast_autoaugment_tpu.data.datasets import load_dataset

    fixture = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "fixtures", "tokens_markov.json")), train=6, test=2,
        length=256, ids=512)
    wrote = fixture_tokens.write_fixture(str(tmp_path / "a"), fixture, 2**31 + 5)
    assert wrote["train"] == 6 and wrote["length"] == 256
    train, test = load_dataset("tokens", str(tmp_path / "a"))
    assert train.tokens and train.images.shape == (6, 257) and test.images.shape == (2, 257)
    assert train.images.dtype == np.int32 and 0 <= train.images.min()
    assert train.images.max() < 512 and train.num_classes <= 512
    fixture_tokens.write_fixture(str(tmp_path / "b"), fixture, 2**31 + 5)
    again = load_dataset("tokens", str(tmp_path / "b"))[0]
    assert np.array_equal(train.images, again.images)    # same seed, same ids
    fixture_tokens.write_fixture(str(tmp_path / "b"), fixture, 7)
    other = load_dataset("tokens", str(tmp_path / "b"))[0]
    assert not np.array_equal(train.images, other.images)
    # a Zipf head: id 0 is the most frequent, and the chain is learnable —
    # a token's followers are few
    counts = np.bincount(train.images.ravel(), minlength=512)
    assert counts.argmax() == 0
    assert 0 < wrote["unigram_entropy_nats"] < math.log(512)


# ------------------------------------------------ the program, rehearsed


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def build_tiny_token_checkout(root: str) -> str:
    """A copy of the benchmark with a tiny Kimi Linear configuration,
    fixture, traffic and cell dropped in as new files and entries."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    conf = tiny_conf(batch=2, lr=0.003)
    config = dict(CONFIG, conf=conf, model=tiny_sizes(conf, 64))
    _write(os.path.join(bench_dir, "configs", "tiny_tokens.json"), config)
    fixture = _read(os.path.join(bench_dir, "fixtures", "tokens_markov.json"))
    fixture.update(train=8, test=2, length=64, ids=48)
    _write(os.path.join(bench_dir, "fixtures", "tiny_tokens.json"), fixture)
    traffic = _read(os.path.join(bench_dir, "traffic", "train_epochs_tokens.json"))
    traffic.update(fixture="tiny_tokens", trace_seconds=1.5,
                   loss_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_tokens_train.json"), traffic)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_tokens", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_tokens.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_tokens_train", "config": "tiny_tokens",
        "traffic": "tiny_tokens_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_tokens_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = build_tiny_token_checkout(str(tmp_path_factory.mktemp("tokens")))
    cell = spec.resolve_cell("tiny_tokens_train", seed=2**31 + 11, seconds=1.0,
                             trace=False, root=root)
    obs = runner.run_cell(cell, jax.devices()[:1], runner.process_start_wall())
    return obs, runner.result_line(obs)


def test_token_program_rehearsed_on_the_cpu(rehearsed):
    obs, line = rehearsed
    assert obs.correct, obs.checks
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"           # and so: not a result
    counted = obs.checks["step_counter"]
    assert counted["checkpoint_step"] == counted["steps_counted"]
    # 8 sequences / batch 2 = 4 steps an epoch; the window opened two
    # dispatches after the first boundary
    assert counted["steps_counted"] == 4 + 2 + obs.attempted
    assert obs.checks["no_compile_in_window"]["compile_requests"] == 0
    learned = obs.checks["learned"]
    assert math.isfinite(learned["loss_train"])
    assert learned["loss_of_no_learning"] == pytest.approx(math.log(48))
    assert obs.checks["reference_logits"]["images"] == 1   # one sequence
    assert obs.checks["reference_logits_float32"]["relative_gap"] < 1e-4
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "no_compile_in_window", "step_counter", "learned", "reference_logits",
        "reference_logits_float32", "routing", "routing_float32"}
    # the reference took the system's choice of experts, and found it its own
    assert obs.checks["routing_float32"]["margin"] < 1e-5
    assert obs.checks["routing"]["layers"] == ["layer2", "layer3", "layer4", "layer5"]
    # an example is a sequence: rate = steps x batch / window
    assert line["metrics"]["train_images_per_s"]["value"] == pytest.approx(
        obs.attempted * 2 / obs.window_s)
    assert obs.checks["finite_loss"]["tokens_per_s_per_chip"] == pytest.approx(
        64 * obs.attempted * 2 / obs.window_s)


def test_token_program_hands_the_counters_to_the_readers(rehearsed):
    obs, _ = rehearsed
    work = obs.work
    assert work["tokens_a_step"] == 2 * 64 and work["counted_steps"] >= obs.attempted
    by_layer = work["moe_assignments_a_step_by_layer"]
    assert sorted(by_layer) == ["layer2", "layer3", "layer4", "layer5"]
    # 128 tokens x top-4 x 4 of 16 experts held: 128 a step expected
    assert all(0 < n < 128 * 4 for n in by_layer.values())
    assert work["moe_assignments_a_step"] == pytest.approx(sum(by_layer.values()))
    loads = work["moe_held_load_max_over_mean"]
    assert sorted(loads) == sorted(by_layer) and all(1.0 <= v <= 4.0 for v in loads.values())
    reader = spec.load_module("layer_metrics", "moe_held_load_max_over_mean")
    assert reader.read(obs) == max(loads.values())


def test_a_program_without_the_counters_gives_the_readers_nothing():
    obs = types.SimpleNamespace(work={"images_per_s_per_chip": 1.0}, trace=None,
                                step_program="^jit_multi_fn")
    for name in ("moe_held_load_max_over_mean", "moe_experts_roofline",
                 "kda_scan_roofline"):
        assert spec.load_module("layer_metrics", name).read(obs) is None


# ------------------------------------------- the cell's entries and readers


def test_the_cell_lists_its_seven_readers_and_no_augmentation_metric():
    cell = spec.resolve_cell(CELL, trace=True)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= names and len(names) == 19
    assert not any(n.startswith(("aug_", "shake_", "resnet_", "feed_", "host_"))
                   for n in names)
    assert cell.traffic["program"] == "train_tokens"
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    bench = spec.load_benchmark()
    for name in NEW_READERS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_images_per_s"
        assert runner.reader_for(cell, entry).META["layer"] == "models"


RECORDED_PATH = os.path.join(spec.BENCH_DIR, "testdata",
                             "v5e_kimi_linear_step_scopes.json")


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
    keys = {k for parts in split.executions for k in parts}
    chains = {hs.split_key(k)[0] for k in keys}
    for scope in (scopes.KDA, scopes.KDA_SCAN, scopes.MLA, scopes.MOE,
                  scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD):
        assert any(scope in chain for chain in chains), scope
        # every one of them nested under the model
        assert all(chain[0] == scopes.MODEL for chain in chains if scope in chain)
    assert split.unscoped_share() < 10.0


def test_the_seven_readers_on_the_recorded_step(recorded, monkeypatch, tmp_path):
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    values = {name: spec.load_module("layer_metrics", name).read(obs)
              for name in NEW_READERS}
    for name, expected in held["expected"].items():
        assert values[name] == pytest.approx(expected, rel=1e-6), name
    assert set(held["expected"]) == set(NEW_READERS)
    step_ms = spec.load_module("layer_metrics", "step_device_ms").read(obs)
    forward = spec.load_module("layer_metrics", "model_forward_device_ms").read(obs)
    backward = spec.load_module("layer_metrics", "model_backward_device_ms").read(obs)
    # the four scope times are parts of the model's, which is most of the step
    parts = sum(values[n] for n in NEW_READERS[:4])
    assert parts <= forward + backward <= step_ms
    assert 0 < values["kda_scan_roofline"] < 100
    assert 0 < values["moe_experts_roofline"] < 100
    assert values["moe_held_load_max_over_mean"] >= 1.0
    # the roofline shares by hand: bytes bound both at these sizes
    model, tokens = CONFIG["model"], held["work"]["tokens_a_step"]
    scan_ms = hs.scope_ms(obs, scopes.KDA_SCAN)
    moved = 4 * (FLOPS.kda_scan_bytes(model, tokens, backward=False)
                 + FLOPS.kda_scan_bytes(model, tokens, backward=True))
    assert values["kda_scan_roofline"] == pytest.approx(
        100 * (moved / 819e9) / (scan_ms / 1e3), rel=1e-6)


def test_readers_on_a_program_from_before_the_scopes(recorded, monkeypatch, tmp_path):
    """The parent's program under this tree's benchmark files: no such
    scope in its table, so each reader returns None and does not raise."""
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    for name in ("KDA", "KDA_SCAN", "MLA", "MOE", "MOE_EXPERTS", "LM_HEAD"):
        monkeypatch.delattr(scopes, name)
    obs.work = {"images_per_s_per_chip": 1.0}
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(obs) is None, name
