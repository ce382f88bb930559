"""The GLM-4.7-Flash configuration's own files: its operations against a hand
count, its plain reference against the program on seeded weights (both
heads, the two-term loss, every gradient leaf), the controls its comparison
must refuse, its program rehearsed on the CPU at a tiny size, and its
readers on an excerpt recorded on the chip
(``benchmarks/testdata/v5e_glm4_moe_lite_step_scopes.json``)."""

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
from fast_autoaugment_tpu.core import compilecache, scopes
from fast_autoaugment_tpu.models import get_model, model_conf_of

REPO = spec.ROOT

FLOPS = spec.load_module("flops", "glm4_moe_lite")
REFERENCE = spec.load_module("references", "glm4_moe_lite")
CONFIG = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "glm47_flash_tokens.json"))
CELL = "glm47_flash_train"
KIMI_CELL = "kimi_linear_48b_a3b_train"
NEW_READERS = ("mtp_device_ms", "mla_attention_roofline")

#: every width cut for the CPU, the structure kept: a dense layer, four
#: expert layers and the module; 8 experts of which 4 are held, top-2
TINY_MODEL = dict(
    hidden_size=64, intermediate_size=96, kv_lora_rank=16, q_lora_rank=24,
    moe_intermediate_size=32, num_attention_heads=2, n_routed_experts=8,
    num_experts_per_tok=2, qk_nope_head_dim=12, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=64)
TINY_HELD = dict(layers_held=5, experts_held=4, ids_held=48)


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
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
           + 20 * 256 * 2048)
    expert = 3 * 2048 * 1536
    assert FLOPS.mla_mixer_matrices(model) == mla == 21_757_952
    assert FLOPS.mla_mixer_params(model) == mla + 768 + 512
    assert FLOPS.expert_params(model) == expert == 9_437_184
    expert_block = mla + 768 + 512 + 2 * 2048 + 2048 * 64 + 64 + 9 * expert
    dense_block = mla + 768 + 512 + 2 * 2048 + 3 * 2048 * 10240
    assert FLOPS.expert_block_params(model, 8) == expert_block
    module = 3 * 2048 + 4096 * 2048 + expert_block
    assert FLOPS.mtp_params(model, 8) == module
    held = (2 * 19360 * 2048 + 2048 + dense_block + 4 * expert_block + module)
    assert FLOPS.num_params(model) == held == 706_518_848
    # 16 bytes a parameter: float32 weights, gradients, AdamW's two moments
    assert 16 * held == pytest.approx(11.30e9, rel=1e-3)
    whole = dict(model, layers_held=None, experts_held=None, ids_held=None)
    without_module = FLOPS.num_params(dict(whole, mtp_modules=0))
    assert 29.9e9 < without_module < 30.0e9              # the published "30B"
    assert FLOPS.num_params(whole) - without_module == FLOPS.mtp_params(model, 64)
    assert 0.63e9 < FLOPS.mtp_params(model, 64) < 0.65e9


def test_step_operations_against_a_hand_count():
    model = CONFIG["model"]
    tokens = 8192
    mla = 2 * 21_757_952
    expert = 2 * 3 * 2048 * 1536
    # 8 of 64 experts held, top-4: a token reaches 4 * 8 / 64 = 0.5 of them
    expert_ffn = 2 * 2048 * 64 + (0.5 + 1) * expert
    attention = 2 * 20 * (256 + 256) * tokens * tokens / 2
    head = 2 * 2048 * 19360
    forward = ((head + 5 * mla + 2 * 3 * 2048 * 10240 + 4 * expert_ffn) * tokens
               + 5 * attention)
    assert FLOPS.forward_flops_per_image(model) == pytest.approx(forward, rel=1e-12)
    # a training step: the module's eh_proj and block, and the head once more
    module = (2 * 4096 * 2048 + mla + expert_ffn + head) * tokens + attention
    assert FLOPS.forward_flops_per_image(model, training=True) == pytest.approx(
        forward + module, rel=1e-12)
    assert FLOPS.train_flops_per_image(model) == pytest.approx(3 * (forward + module))
    assert 0.9e9 < forward / tokens < 1.0e9              # 0.96 GFLOP a token


def test_the_two_kernels_operations_and_bytes_are_the_mathematics():
    model = CONFIG["model"]
    forward = 8192 * 8192 / 2 * 20 * (256 + 256) * 2
    assert FLOPS.mla_attention_operations(model, 8192, backward=False) == forward
    assert FLOPS.mla_attention_operations(model, 8192, backward=True) == 2 * forward
    q, k, v = 20 * 256, 20 * 192 + 64, 20 * 256
    assert FLOPS.mla_attention_bytes(model, 1, backward=False) == 4 * (q + k + v + v)
    assert FLOPS.mla_attention_bytes(model, 1, backward=True) == 4 * (
        2 * (q + k + v) + 2 * v)
    assert FLOPS.moe_experts_operations(model, 2048, backward=False) == (
        2 * 3 * 2048 * 1536 * 2048)
    weights = 4 * 8 * 3 * 2048 * 1536
    assert FLOPS.moe_experts_bytes(model, 0, backward=False) == weights
    assert FLOPS.moe_experts_bytes(model, 2048, backward=True) == 2 * (
        weights + 4 * 2 * 2048 * 2048)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_configuration_file_states_the_published_model_and_the_cut():
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "GLM-4.7-Flash")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key                # every key as published
        assert CONFIG["conf"]["model"][key] == value, key
    assert CONFIG["reduced"] == ["layers_held", "experts_held", "ids_held"]
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [5, 8, 19360]
    assert set(CONFIG["reduced_because"]) == set(CONFIG["reduced"])
    assert CONFIG["published"]["n_routed_experts"] == 64
    assert "8 chips" in CONFIG["deployment"] and "706,518,848" in CONFIG["deployment"]
    # the floors: four expert layers after the dense one, 8 experts, an eighth
    assert CONFIG["layers_held"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["experts_held"] >= 8 and 8 * CONFIG["ids_held"] >= CONFIG["vocab_size"]
    for head in ("", "mtp_"):
        assert 0 < CONFIG[head + "logit_tolerance_float32"] < CONFIG[head + "logit_tolerance"]
        assert CONFIG[head + "logit_tolerance_because"]
    assert {"rotary_pairs", "eh_proj_order", "mtp_loss_weight"} <= set(CONFIG["assumed"])


def test_configuration_self_test_passes():
    from tests.benchmarks.test_bench_spec import check_config

    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "glm47_flash_tokens")
    check_config(REPO, entry)


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


def _both_heads(model, params, ids):
    """The system's two heads under ``highest`` and the routing it sowed."""
    with jax.default_matmul_precision("highest"):
        (logits, mtp_logits), sown = jax.jit(lambda p, x, y: model.apply(
            {"params": p}, x, y, method="logits_and_mtp_logits",
            mutable=["routing"]))(params, ids[:, :-1], ids[:, 1:])
    routing = {layer: np.asarray(entry["moe"]["chosen"][0])
               for layer, entry in sown["routing"].items()}
    return np.asarray(logits), np.asarray(mtp_logits), routing


def _gap(ours, plain):
    return float(np.abs(ours - plain).max() / np.abs(plain).max())


def test_program_parameters_are_what_the_operations_file_counts(tiny_system):
    _, _, params, _, sizes = tiny_system
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)
    assert sorted(k for k in params if k.startswith("mtp")) == [
        "mtp", "mtp_eh_proj", "mtp_enorm", "mtp_hnorm", "mtp_norm"]


def test_reference_agrees_with_the_program_on_both_heads_loss_and_every_gradient(
        tiny_system):
    """Float32 under ``highest`` on both sides: rounding alone is left, 1e-5
    of the largest logit; the two-term loss to 1e-6; every gradient leaf to
    2e-4 of its largest element (sums of 128 positions' float32 products in
    another order), the embedding's and the head's — which have two
    sources — by name."""
    _, model, params, ids, sizes = tiny_system
    logits, mtp_logits, _ = _both_heads(model, params, ids)
    plain, plain_mtp = REFERENCE.forward(params, {}, ids[:, :-1], sizes,
                                         next_ids=ids[:, 1:])
    assert _gap(logits, plain) <= 1e-5 and _gap(mtp_logits, plain_mtp) <= 1e-5
    # the main head alone, as evaluation computes it
    assert _gap(np.asarray(model.apply({"params": params}, ids[:, :-1])),
                REFERENCE.forward(params, {}, ids[:, :-1], sizes)) <= 1e-4

    def loss(p):
        (nll, _, further), _ = model.apply(
            {"params": p}, ids[:, :-1], ids[:, 1:], method="loss_terms",
            mutable=["step_stats"])
        mtp, weight = further["mtp_loss"]
        return nll.mean() + weight * mtp.mean(), (nll.mean(), mtp.mean())

    with jax.default_matmul_precision("highest"):
        (value, (main, mtp)), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
    plain_loss, plain_grads, terms = REFERENCE.loss_and_grads(params, ids, sizes)
    assert float(value) == pytest.approx(plain_loss, rel=1e-6)
    assert float(main) == pytest.approx(terms["main"], rel=1e-6)
    assert float(mtp) == pytest.approx(terms["mtp"], rel=1e-6)
    assert plain_loss == pytest.approx(terms["main"] + 0.3 * terms["mtp"], rel=1e-6)
    with np.errstate(invalid="ignore"):   # the correction bias has no gradient: 0 / 0
        gaps = jax.tree.map(
            lambda a, b: float(np.abs(a - b).max() / np.abs(b).max()),
            dict(grads), plain_grads)
    worst = max(g for g in jax.tree.leaves(gaps) if math.isfinite(g))
    assert worst < 2e-4, gaps
    assert gaps["embed_tokens"] < 2e-4 and gaps["lm_head"]["kernel"] < 2e-4
    # both sources reach the shared arrays: the main term's gradient alone
    # is another one
    main_only = REFERENCE.loss_and_grads(params, ids, dict(sizes, mtp_weight=0.0))[1]
    for leaf in (lambda g: g["embed_tokens"], lambda g: g["lm_head"]["kernel"]):
        assert _gap(np.asarray(leaf(grads)), np.asarray(leaf(main_only))) > 1e-2
    assert not np.any(np.asarray(main_only["mtp_eh_proj"]["kernel"]))


def test_controls_the_float32_comparison_must_refuse(tiny_system):
    """The reference without rotary, with the embedding's half of
    ``eh_proj`` zeroed (the module's head), one layer short, and the
    program in bfloat16 under ``highest``: each over the float32 limit of
    the head it touches, the system's routing given."""
    conf, model, params, ids, sizes = tiny_system
    inputs, after = np.asarray(ids[:, :-1]), np.asarray(ids[:, 1:])
    logits, mtp_logits, routing = _both_heads(model, params, ids)
    assert sorted(routing) == ["layer2", "layer3", "layer4", "layer5", "mtp"]
    limit, mtp_limit = (CONFIG["logit_tolerance_float32"],
                        CONFIG["mtp_logit_tolerance_float32"])

    def given(p, model_sizes, routing=routing):
        (plain, plain_mtp), margins = REFERENCE.forward_given_routing(
            p, inputs, model_sizes, routing, next_ids=after)
        return _gap(logits, plain), _gap(mtp_logits, plain_mtp), margins

    sound = given(params, sizes)
    assert sound[0] <= 1e-5 and sound[1] <= 1e-5 and max(sound[2]) < 1e-5
    no_rotary = given(params, dict(sizes, rope_theta=None))
    assert no_rotary[0] > limit and no_rotary[1] > mtp_limit
    no_embedding = dict(params, mtp_eh_proj={"kernel": np.asarray(
        params["mtp_eh_proj"]["kernel"]).copy()})
    no_embedding["mtp_eh_proj"]["kernel"][:sizes["hidden"]] = 0.0
    zeroed = given(no_embedding, sizes)
    assert zeroed[0] <= 1e-5 and zeroed[1] > mtp_limit   # the module's head alone
    short = given(params, dict(sizes, layers_held=4),
                  {k: v for k, v in routing.items() if k != "layer5"})
    assert short[0] > limit and short[1] > mtp_limit
    half = get_model(dict(model_conf_of(conf), precision="bf16"), conf["ids_held"])
    low, low_mtp, low_routing = _both_heads(half, params, ids)
    (plain, plain_mtp), _ = REFERENCE.forward_given_routing(
        params, inputs, sizes, low_routing, next_ids=after)
    assert _gap(low, plain) > limit and _gap(low_mtp, plain_mtp) > mtp_limit


def test_reference_one_held_expert_short_is_refused(tiny_system):
    """Every held expert of every expert layer and of the module's block,
    left out of the reference in turn (the system's routing given): each
    that a token of these chose moves a head over its float32 limit — the
    module's block its own head alone."""
    _, model, params, ids, sizes = tiny_system
    inputs, after = np.asarray(ids[:, :-1]), np.asarray(ids[:, 1:])
    logits, mtp_logits, routing = _both_heads(model, params, ids)
    held = sizes["experts_held"]
    tried = 0
    for layer in routing:
        for expert in range(held):
            if not (routing[layer] == expert).any():
                continue                     # no token of these chose it
            kept = np.ones(held, np.float32)
            kept[expert] = 0.0
            (short, short_mtp), _ = REFERENCE.forward_given_routing(
                params, inputs, sizes, routing, {layer: kept}, next_ids=after)
            assert _gap(mtp_logits, short_mtp) > CONFIG["mtp_logit_tolerance_float32"], (
                layer, expert)
            if layer == "mtp":
                assert _gap(logits, short) <= 1e-5
            else:
                assert _gap(logits, short) > CONFIG["logit_tolerance_float32"], (
                    layer, expert)
            tried += 1
    assert tried >= held


def test_reference_given_the_systems_routing_says_how_far_a_choice_is(tiny_system):
    """Given the model's own choice the reference is its plain forward,
    both margins at rounding; a choice no router made in the module's block
    shows in the module's margin alone; compiled ahead from shapes it is the
    same program, for the main head alone too."""
    _, model, params, ids, sizes = tiny_system
    inputs, after = np.asarray(ids[:, :-1]), np.asarray(ids[:, 1:])
    _, _, routing = _both_heads(model, params, ids)
    own, own_mtp = REFERENCE.forward(params, {}, inputs, sizes, next_ids=after)
    (given, given_mtp), (margin, mtp_margin) = REFERENCE.forward_given_routing(
        params, inputs, sizes, routing, next_ids=after)
    assert _gap(given, own) <= 1e-5 and _gap(given_mtp, own_mtp) <= 1e-5
    assert 0.0 <= margin < 1e-5 and 0.0 <= mtp_margin < 1e-5
    wrong = dict(routing, mtp=np.broadcast_to(
        np.arange(sizes["top_k"], dtype=np.int32), routing["mtp"].shape))
    _, (near, far) = REFERENCE.forward_given_routing(
        params, inputs, sizes, wrong, next_ids=after)
    assert near == margin and far > 0.05
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    some_ids = jax.ShapeDtypeStruct(inputs.shape, jnp.int32)
    ahead = REFERENCE.compile_forward_given_routing(shapes, some_ids, sizes, mtp=True)
    (again, again_mtp), margins = ahead(params, inputs, routing, next_ids=after)
    assert np.array_equal(again, given) and np.array_equal(again_mtp, given_mtp)
    assert margins == (margin, mtp_margin)
    main_layers = {k: v for k, v in routing.items() if k != "mtp"}
    main_alone = REFERENCE.compile_forward_given_routing(shapes, some_ids, sizes)
    alone, alone_margin = main_alone(params, inputs, main_layers)
    assert _gap(alone, given) <= 1e-6 and alone_margin == pytest.approx(margin, abs=1e-6)


# ------------------------------------------------ the program, rehearsed


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def build_tiny_checkout(root: str) -> str:
    """A copy of the benchmark with a tiny GLM configuration, fixture,
    traffic and cell dropped in as new files and entries."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    conf = tiny_conf(batch=2, lr=0.003)
    config = dict(CONFIG, conf=conf, model=tiny_sizes(conf, 64))
    _write(os.path.join(bench_dir, "configs", "tiny_mtp.json"), config)
    fixture = _read(os.path.join(bench_dir, "fixtures", "tokens_markov_19360.json"))
    fixture.update(train=8, test=2, length=64, ids=48)
    _write(os.path.join(bench_dir, "fixtures", "tiny_mtp.json"), fixture)
    traffic = _read(os.path.join(bench_dir, "traffic", "train_epochs_tokens_mtp.json"))
    traffic.update(fixture="tiny_mtp", trace_seconds=1.5,
                   loss_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_mtp_train.json"), traffic)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_mtp", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_mtp.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_mtp_train", "config": "tiny_mtp",
        "traffic": "tiny_mtp_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_mtp_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    from fast_autoaugment_tpu.core import telemetry

    root = build_tiny_checkout(str(tmp_path_factory.mktemp("mtp")))
    cell = spec.resolve_cell("tiny_mtp_train", seed=2**31 + 13, seconds=1.0,
                             trace=False, root=root)
    targets = telemetry.registry().counter("faa_mtp_targets_total")
    before = targets.value
    obs = runner.run_cell(cell, jax.devices()[:1], runner.process_start_wall())
    return obs, runner.result_line(obs), targets.value - before


def test_program_rehearsed_on_the_cpu_compares_both_heads(rehearsed):
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
        "reference_logits", "reference_logits_float32", "routing", "routing_float32",
        "reference_mtp_logits", "reference_mtp_logits_float32", "routing_mtp",
        "routing_mtp_float32"}
    for head in ("reference_logits", "reference_mtp_logits"):
        assert obs.checks[head]["images"] == 1             # one sequence
        assert obs.checks[head + "_float32"]["relative_gap"] < 1e-4
    assert obs.checks["routing"]["layers"] == ["layer2", "layer3", "layer4", "layer5"]
    assert obs.checks["routing_mtp"]["layers"] == ["mtp"]
    assert obs.checks["routing_float32"]["margin"] < 1e-5
    assert obs.checks["routing_mtp_float32"]["margin"] < 1e-5
    # `learned` read the main head's loss, not the two-term sum
    meta_loss = obs.checks["learned"]["loss_train"]
    assert math.isfinite(meta_loss) and meta_loss < math.log(48) + 1.0


def test_program_hands_the_modules_block_to_the_readers_as_a_layer(rehearsed):
    obs, _, targets = rehearsed
    work = obs.work
    by_layer = work["moe_assignments_a_step_by_layer"]
    assert sorted(by_layer) == ["layer2", "layer3", "layer4", "layer5", "mtp"]
    # 128 tokens x top-2 x 4 of 8 experts held: 128 a step expected
    assert all(0 < n < 128 * 2 for n in by_layer.values())
    loads = work["moe_held_load_max_over_mean"]
    assert sorted(loads) == sorted(by_layer)
    # the term was taken over T - 1 = 63 positions of each of 2 sequences a step
    steps = obs.checks["step_counter"]["steps_counted"]
    assert targets == 2 * 63 * steps


def test_a_program_without_the_model_refuses_the_cells_conf_before_it_trains():
    """What the parent does with this cell: its registry knows no such
    model type, and ``train_tokens.ComparisonsAhead`` builds the model
    before the trainer is entered, so the run ends there, non-zero."""
    conf = tiny_conf()
    conf["model"]["type"] = "glm4_moe_lite_of_a_later_pr"
    with pytest.raises(ValueError, match="unknown model type"):
        get_model(model_conf_of(conf), 48)
    program = spec.load_module("programs", "train_tokens_mtp")
    cell = types.SimpleNamespace(
        config={"model": {"ids_held": 48}, "reference": "glm4_moe_lite"},
        module=lambda kind, name: spec.load_module(kind, name))
    with pytest.raises(ValueError, match="unknown model type"):
        program.BothHeadsAhead(cell, conf, 1, 64)


# ------------------------------------------- the cell's entries and readers


#: the Kimi cell's own readers of scopes and counters this cell's program has
#: too; ``test_bench_kimi_linear.py`` holds their lists to the Kimi cell alone,
#: so this cell joins them in the ``benchmark`` PR that may edit that file
#: (PERF.md section 7); until then the tests below call them directly
SHARED_READERS = ("mla_device_ms", "moe_device_ms", "lm_head_loss_device_ms",
                  "moe_experts_roofline", "moe_held_load_max_over_mean")


def test_every_metric_that_names_the_cell_has_a_reader_that_agrees():
    """The cell's metric set by a rule, not a count: every per-layer metric
    that names the cell has a reader file whose ``META`` agrees, none is an
    augmentation's or another family's, and the Kimi cell's list is this
    one's but for its own seven readers and this cell's two."""
    cell = spec.resolve_cell(CELL, trace=True)
    bench = spec.load_benchmark()
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in listed} == {m["name"] for m in cell.per_layer}
    for entry in listed:
        assert runner.reader_for(cell, entry).META["moves"] == entry["moves"]
    names = {m["name"] for m in listed}
    kimi = {m["name"] for m in bench["per_layer"] if KIMI_CELL in m.get("workloads", ())}
    assert names - kimi == set(NEW_READERS)
    assert kimi - names == {"kda_device_ms", "kda_scan_roofline", *SHARED_READERS}
    assert {"step_device_ms", "model_flops_utilization", "model_forward_device_ms",
            "model_backward_device_ms", "step_unscoped_share", "peak_hbm_bytes",
            "device_idle_share"} <= names
    assert not any(n.startswith(("aug_", "shake_", "resnet_", "feed_", "host_", "kda_"))
                   for n in names)
    for name in NEW_READERS:
        entry = next(m for m in listed if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == "models"
    assert cell.traffic["program"] == "train_tokens_mtp"
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_images_per_s")["workloads"]


def test_every_scope_the_models_program_has_is_read_by_a_reader(tiny_system):
    """Whatever the tiny model's lowered step names inside ``faa_model`` is
    under a scope that a reader names in its source — one the cell lists or
    one of :data:`SHARED_READERS` (a scope nested in a read one, as the
    router in ``faa_moe``, is read with it)."""
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
    assert {scopes.MTP, scopes.MLA_ATTENTION, scopes.MLA, scopes.MOE,
            scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS} <= found
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
    assert {scopes.MTP, scopes.MLA_ATTENTION} <= read
    inside_the_model = [chain[1:] for chain in chains
                        if chain[:1] == (scopes.MODEL,) and len(chain) > 1]
    assert inside_the_model
    for chain in inside_the_model:
        assert read.intersection(chain), chain


RECORDED_PATH = os.path.join(spec.BENCH_DIR, "testdata",
                             "v5e_glm4_moe_lite_step_scopes.json")


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
    for scope in (scopes.MTP, scopes.MLA, scopes.MLA_ATTENTION, scopes.MOE,
                  scopes.MOE_EXPERTS, scopes.LM_HEAD):
        assert any(scope in chain for chain in chains), scope
    # the attention core only ever inside the mixer, the module's block's
    # mixer and experts inside the module
    assert all(scopes.MLA in chain for chain in chains if scopes.MLA_ATTENTION in chain)
    assert any(scopes.MTP in chain and scopes.MLA in chain for chain in chains)
    assert any(scopes.MTP in chain and scopes.MOE in chain for chain in chains)


def test_the_readers_on_the_recorded_step(recorded, monkeypatch, tmp_path):
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    values = {name: spec.load_module("layer_metrics", name).read(obs)
              for name in held["expected"]}
    for name, expected in held["expected"].items():
        assert values[name] == pytest.approx(expected, rel=1e-6), name
    assert set(NEW_READERS) <= set(held["expected"])
    assert 0 < values["mla_attention_roofline"] < 100
    assert 0 < values["moe_experts_roofline"] < 100
    assert 0 < values["mtp_device_ms"] < values["mla_device_ms"] + values["moe_device_ms"]
    # the share by hand: the operations bound it at these sizes
    model = CONFIG["model"]
    core_ms = hs.scope_ms(obs, scopes.MLA_ATTENTION)
    operations = 6 * 3 * FLOPS.mla_attention_operations(model, 8192, backward=False)
    moved = 6 * sum(FLOPS.mla_attention_bytes(model, 8192, backward=b)
                    for b in (False, True))
    assert operations / 197e12 > moved / 819e9
    assert values["mla_attention_roofline"] == pytest.approx(
        100 * (operations / 197e12) / (core_ms / 1e3), rel=1e-6)


def test_readers_on_a_program_from_before_the_scopes(recorded, monkeypatch, tmp_path):
    """The parent's program under this tree's benchmark files: no such
    scope in its table, so each new reader returns None and does not raise;
    nor does the share on a configuration whose operations file lacks its
    functions (the Kimi cell's)."""
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    kimi = spec.resolve_cell(KIMI_CELL, trace=True)
    reader = spec.load_module("layer_metrics", "mla_attention_roofline")
    assert reader.read(types.SimpleNamespace(
        cell=kimi, work=obs.work, devices=obs.devices)) is None
    for name in ("MTP", "MLA_ATTENTION"):
        monkeypatch.delattr(scopes, name)
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(obs) is None, name
