"""The Nemotron 3 Nano configuration's own files: its operations against a
hand count, its plain reference against the program on seeded weights
(logits, loss, every gradient leaf), the controls its comparison must
refuse, its program rehearsed on the CPU at a tiny size, and its readers —
the three new ones and the expert and head readers the token cells share —
on an excerpt recorded on the chip
(``benchmarks/testdata/v5e_nemotron_h_step_scopes.json``).

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

FLOPS = spec.load_module("flops", "nemotron_h")
REFERENCE = spec.load_module("references", "nemotron_h")
CONFIG = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "nemotron3_nano_30b_a3b_tokens.json"))
CELL = "nemotron3_nano_30b_a3b_train"
KIMI_CELL = "kimi_linear_48b_a3b_train"
NEW_READERS = ("mamba2_device_ms", "ssd_scan_roofline", "gqa_device_ms")
CONTROLS = ("no_d_skip", "no_gate", "no_conv_bias", "rotary", "one_kv_head")

#: every width cut for the CPU, the structure kept: the cut's nine layers
#: (four Mamba-2, four expert layers, one attention); 16 experts of which 4
#: are held, top-2; 2 heads a group in the scan, 2 query heads a key-value head
TINY_MODEL = dict(
    hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
    ssm_state_size=16, chunk_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
    num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, vocab_size=64,
    hybrid_override_pattern="MEMEM*EMEM", num_hidden_layers=10)
TINY_HELD = dict(layers_held=9, experts_held=4, ids_held=48)


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
    mamba = (2688 * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096
             + 4096 * 2688)
    attention = 2688 * 4096 * 2 + 2688 * 256 * 2
    expert = 2 * 2688 * 1856
    assert FLOPS.mamba_params(model) == mamba == 38_744_896 - 2688
    assert FLOPS.attention_matrices(model) == attention == 23_399_040 - 2688
    assert FLOPS.expert_params(model) == expert == 9_977_856
    assert FLOPS.shared_expert_params(model) == 2 * 2688 * 3712 == 19_955_712
    expert_layer = 2688 * 128 + 128 + 8 * expert + 19_955_712
    assert FLOPS.expert_layer_params(model, 8) == expert_layer == 100_125_312 - 2688 + 128
    held = (2 * 16384 * 2688 + 2688 + 4 * (2688 + mamba) + (2688 + attention)
            + 4 * (2688 + expert_layer))
    assert FLOPS.num_params(model) == held == 666_963_456
    # 16 bytes a parameter: float32 weights, gradients, AdamW's two moments
    assert 16 * held == pytest.approx(10.67e9, rel=1e-3)
    assert FLOPS.held_layers(model, "M") == 4 and FLOPS.held_layers(model, "E") == 4
    assert FLOPS.held_layers(model, "*") == 1
    whole = dict(model, layers_held=None, experts_held=None, ids_held=None)
    assert FLOPS.held_layers(whole, "M") == 23 and FLOPS.held_layers(whole, "*") == 6
    assert 31.5e9 < FLOPS.num_params(whole) < 31.7e9     # the published 31.6 B
    seven = dict(model, layers_held=7)                   # the issue's fall-back cut
    assert 16 * FLOPS.num_params(seven) == pytest.approx(8.45e9, rel=1e-3)


def test_step_operations_against_a_hand_count():
    model = CONFIG["model"]
    tokens = 8192
    mamba = 2 * (2688 * (4096 + 6144 + 64) + 4096 * 2688)
    scan = 2 * (8 * 128 * 128 + 64 * (128 * 64 + 2 * 64 * 128)) * tokens
    attention = 2 * (2688 * 4096 * 2 + 2688 * 256 * 2)
    core = 2 * 32 * (128 + 128) * tokens * tokens / 2
    # 8 of 128 experts held, top-6: a token reaches 6 * 8 / 128 = 0.375 of them
    experts = 2 * (2688 * 128 + 0.375 * 2 * 2688 * 1856 + 2 * 2688 * 3712)
    head = 2 * 2688 * 16384
    forward = ((head + 4 * mamba + attention + 4 * experts) * tokens
               + 4 * scan + core)
    assert FLOPS.ssd_scan_operations(model, tokens, backward=False) == scan
    assert FLOPS.forward_flops_per_image(model) == pytest.approx(forward, rel=1e-12)
    assert FLOPS.train_flops_per_image(model) == pytest.approx(3 * forward)
    assert 17e12 < 3 * forward < 18e12                   # 17.6 TFLOP a step owed
    # the issue's "45% of the products a token owes are the Mamba-2 layers'"
    mixers = 4 * (mamba * tokens + scan)
    assert 0.40 < mixers / forward < 0.50


def test_the_two_kernels_operations_and_bytes_are_the_mathematics():
    model = CONFIG["model"]
    forward = FLOPS.ssd_scan_operations(model, 8192, backward=False)
    assert FLOPS.ssd_scan_operations(model, 8192, backward=True) == 2 * forward
    # a sequence shorter than a chunk is one chunk of its own length
    assert FLOPS.ssd_scan_operations(model, 64, backward=False) == (
        2 * (8 * 64 * 128 + 64 * (64 * 64 + 2 * 64 * 128)) * 64)
    x = y = 64 * 64
    b = c = 8 * 128
    assert FLOPS.ssd_scan_bytes(model, 1, backward=False) == 4 * (x + b + c + 64 + y)
    assert FLOPS.ssd_scan_bytes(model, 1, backward=True) == 4 * (
        2 * (x + b + c + 64) + y)
    # the bytes bound the scan at these sizes
    assert (sum(FLOPS.ssd_scan_bytes(model, 8192, backward=k) for k in (False, True))
            / 819e9) > 3 * forward / 197e12
    assert FLOPS.moe_experts_operations(model, 3072, backward=False) == (
        2 * 2 * 2688 * 1856 * 3072)
    weights = 4 * 8 * 2 * 2688 * 1856
    assert FLOPS.moe_experts_bytes(model, 0, backward=False) == weights
    assert FLOPS.moe_experts_bytes(model, 3072, backward=True) == 2 * (
        weights + 4 * 2 * 2688 * 3072)


def test_the_scans_counted_bytes_are_its_operands_and_result_once():
    """Why ``ssd_scan_roofline`` cannot pass 100% by construction: the
    bytes counted are exactly the float32 arrays ``ops/ssd.py::chunk_ssd``
    is handed and hands back (``x``, the step, ``B``, ``C``; ``y``), each
    once — what any form of the scan must read and write — and the
    operations are the chunked form's products at the published chunk, all
    of which the program's own trace holds (it does no fewer)."""
    from fast_autoaugment_tpu.ops.ssd import chunk_ssd

    sizes = tiny_sizes(tiny_conf(), 64)
    heads, width = sizes["mamba_heads"], sizes["mamba_head_dim"]
    groups, size, chunk = sizes["mamba_groups"], sizes["state_size"], sizes["chunk"]
    x = jnp.ones((1, 64, heads, width))
    dt = jnp.ones((1, 64, heads))
    b = c = jnp.ones((1, 64, groups, size))
    a = d = -jnp.ones((heads,))
    y = jax.eval_shape(lambda *args: chunk_ssd(*args, chunk=chunk), x, dt, a, b, c, d)
    assert FLOPS.ssd_scan_bytes(sizes, 64, backward=False) == 4 * (
        x.size + dt.size + b.size + c.size + math.prod(y.shape))
    # the products the traced program holds: every dot_general's
    # multiply-accumulates, summed, are what the operations file counts
    jaxpr = jax.make_jaxpr(lambda *args: chunk_ssd(*args, chunk=chunk))(
        x, dt, a, b, c, d)

    def products(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (contract, _), _ = eqn.params["dimension_numbers"]
                left = eqn.invars[0].aval.shape
                out = math.prod(eqn.outvars[0].aval.shape)
                total += out * math.prod(left[i] for i in contract)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += products(sub)
        return total

    assert 2 * products(jaxpr.jaxpr) == FLOPS.ssd_scan_operations(
        sizes, 64, backward=False)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_configuration_file_states_the_published_model_and_the_cut():
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key                # every key as published
        assert CONFIG["conf"]["model"][key] == value, key
    assert CONFIG["reduced"] == ["layers_held", "experts_held", "ids_held"]
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [9, 8, 16384]
    assert set(CONFIG["reduced_because"]) == set(CONFIG["reduced"])
    assert CONFIG["published"] == dict(
        CONFIG["published"], num_hidden_layers=52, n_routed_experts=128,
        vocab_size=131072)
    assert "16 chips" in CONFIG["deployment"] and "666,963,456" in CONFIG["deployment"]
    # the floors: a whole period of the pattern (the driver's count: 9), four
    # of each repeating kind, 8 experts, an eighth of the vocabulary
    held = CONFIG["hybrid_override_pattern"][:CONFIG["layers_held"]]
    assert held == "MEMEM*EME" and (held.count("M"), held.count("E")) == (4, 4)
    assert CONFIG["experts_held"] >= 8 and 8 * CONFIG["ids_held"] >= CONFIG["vocab_size"]
    assert 0 < CONFIG["logit_tolerance_float32"] < CONFIG["logit_tolerance"]
    assert 0 < CONFIG["routing_margin_tolerance_float32"] < CONFIG["routing_margin_tolerance"]
    for key in ("logit_tolerance_because", "logit_tolerance_float32_because",
                "routing_margin_because", "learned_measured", "router_measured"):
        assert CONFIG[key], key
    assert {"position_encoding", "initial_values", "e_score_correction_bias",
            "optimizer", "precision"} <= set(CONFIG["assumed"])
    # no width differs from the source: what is reduced is no width
    assert not any(word in key for key in CONFIG["reduced"]
                   for word in ("_dim", "_rank", "width", "hidden", "size"))


def test_configuration_self_test_passes():
    from tests.benchmarks.test_bench_spec import check_config

    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "nemotron3_nano_30b_a3b_tokens")
    check_config(REPO, entry)
    assert CONFIG["model"]["seq_len"] == 8192 and CONFIG["model"]["expert_share"] == 0


# --------------------------------------- the reference against the program


@pytest.fixture(scope="module")
def tiny_system():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), conf["ids_held"])
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 129), 0, 48))
    params = jax.jit(model.init, static_argnames=("train",))(
        {"params": jax.random.PRNGKey(1)}, ids[:, :-1], train=False)["params"]
    # off their initial ones and zeros, so that a norm, a bias or the skip
    # left out shows
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    return conf, model, params, ids, tiny_sizes(conf, 128)


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
    assert sorted(params) == ["embed_tokens"] + [f"layer{i}" for i in range(1, 10)] + [
        "lm_head", "norm"]


def test_reference_agrees_with_the_program_on_logits_loss_and_every_gradient(
        tiny_system):
    """Float32 under ``highest`` on both sides, 128 tokens in 8 chunks of 16
    against the recurrence token by token: rounding alone is left, 1e-5 of
    the largest logit; the loss to 1e-6; every gradient leaf to 2e-4 of its
    largest element."""
    _, model, params, ids, sizes = tiny_system
    logits, _ = _logits_and_routing(model, params, ids)
    assert _gap(logits, REFERENCE.forward(params, {}, ids[:, :-1], sizes)) <= 1e-5

    def loss(p):
        (nll, _, _), _ = model.apply({"params": p}, ids[:, :-1], ids[:, 1:],
                                     method="loss_terms", mutable=["step_stats"])
        return nll.mean()

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
    plain_loss, plain_grads = REFERENCE.loss_and_grads(params, ids, sizes)
    assert float(value) == pytest.approx(plain_loss, rel=1e-6)
    with np.errstate(invalid="ignore"):   # the correction bias has no gradient: 0 / 0
        gaps = jax.tree.map(
            lambda a, b: float(np.abs(a - b).max() / np.abs(b).max()),
            dict(grads), plain_grads)
    worst = max(g for g in jax.tree.leaves(gaps) if math.isfinite(g))
    assert worst < 2e-4, gaps


@pytest.mark.parametrize("control", CONTROLS + ("one_layer_short", "bf16"))
def test_controls_the_float32_comparison_must_refuse(tiny_system, control):
    """The reference with the ``D`` skip, the gate of the gated norm or the
    convolution's bias left out, with rotary applied to the attention's
    queries and keys, with key-value head 0 serving every query head, one
    layer short; and the program in bfloat16 under ``highest``: each over
    the configuration's float32 limit, the system's routing given."""
    conf, model, params, ids, sizes = tiny_system
    inputs = np.asarray(ids[:, :-1])
    logits, routing = _logits_and_routing(model, params, ids)
    assert sorted(routing) == ["layer2", "layer4", "layer7", "layer9"]
    limit = CONFIG["logit_tolerance_float32"]
    sound, margin = REFERENCE.forward_given_routing(params, inputs, sizes, routing)
    assert _gap(logits, sound) <= 1e-5 and margin < 1e-5
    if control == "bf16":
        half = get_model(dict(model_conf_of(conf), precision="bf16"), conf["ids_held"])
        low, low_routing = _logits_and_routing(half, params, ids)
        plain, _ = REFERENCE.forward_given_routing(params, inputs, sizes, low_routing)
        assert _gap(low, plain) > limit
        return
    if control == "one_layer_short":
        changed = dict(sizes, layers_held=8)
        given = {k: v for k, v in routing.items() if k != "layer9"}
    else:
        changed, given = dict(sizes, control=control), routing
    other, _ = REFERENCE.forward_given_routing(params, inputs, changed, given)
    assert _gap(logits, other) > limit, control


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
    wrong = dict(routing, layer7=np.broadcast_to(
        np.arange(sizes["top_k"], dtype=np.int32), routing["layer7"].shape))
    _, far = REFERENCE.forward_given_routing(params, inputs, sizes, wrong)
    assert far > 0.05
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    ahead = REFERENCE.compile_forward_given_routing(
        shapes, jax.ShapeDtypeStruct(inputs.shape, jnp.int32), sizes)
    again, again_margin = ahead(params, inputs, routing)
    assert np.array_equal(again, given) and again_margin == margin


# ------------------------------------------------ the program, rehearsed


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def build_tiny_checkout(root: str) -> str:
    """A copy of the benchmark with a tiny Nemotron-H configuration,
    fixture, traffic and cell dropped in as new files and entries."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    conf = tiny_conf(batch=2, lr=0.003)
    config = dict(CONFIG, conf=conf, model=tiny_sizes(conf, 64))
    _write(os.path.join(bench_dir, "configs", "tiny_hybrid.json"), config)
    fixture = _read(os.path.join(bench_dir, "fixtures", "tokens_markov_16384.json"))
    fixture.update(train=8, test=2, length=64, ids=48)
    _write(os.path.join(bench_dir, "fixtures", "tiny_hybrid.json"), fixture)
    traffic = _read(os.path.join(bench_dir, "traffic",
                                 "train_epochs_tokens_16384.json"))
    traffic.update(fixture="tiny_hybrid", trace_seconds=1.5,
                   loss_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_hybrid_train.json"), traffic)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_hybrid", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_hybrid.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_hybrid_train", "config": "tiny_hybrid",
        "traffic": "tiny_hybrid_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_hybrid_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = build_tiny_checkout(str(tmp_path_factory.mktemp("hybrid")))
    cell = spec.resolve_cell("tiny_hybrid_train", seed=2**31 + 17, seconds=1.0,
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
    assert obs.checks["routing"]["layers"] == ["layer2", "layer4", "layer7", "layer9"]
    assert obs.checks["routing_float32"]["margin"] < 1e-5
    meta_loss = obs.checks["learned"]["loss_train"]
    assert math.isfinite(meta_loss) and meta_loss < math.log(48) + 1.0


def test_program_hands_the_expert_layers_counters_to_the_readers(rehearsed):
    obs, _, rise = rehearsed
    by_layer = obs.work["moe_assignments_a_step_by_layer"]
    assert sorted(by_layer) == ["layer2", "layer4", "layer7", "layer9"]
    # 128 tokens x top-2 x 4 of 16 experts held: 64 a step expected
    assert all(0 < n < 128 * 2 for n in by_layer.values())
    assert sorted(obs.work["moe_held_load_max_over_mean"]) == sorted(by_layer)
    assert obs.work["tokens_a_step"] == 128
    # trace time: the forms the rehearsed programs' kernels took
    assert rise['faa_ssd_scan_traces_total{form="chunked_xla"}'] > 0
    assert rise.get('faa_ssd_scan_traces_total{form="recurrent"}', 0) == 0
    assert rise['faa_mla_attention_traces_total{form="blocked_xla"}'] > 0


def test_a_program_without_the_model_refuses_the_cells_conf_before_it_trains():
    """What the parent does with this cell: its registry knows no such
    model type, and ``train_tokens.ComparisonsAhead`` builds the model
    before the trainer is entered, so the run ends there, non-zero and at
    once (on the chip: exit 1 after the imports)."""
    conf = tiny_conf()
    conf["model"]["type"] = "nemotron_h_of_a_later_pr"
    with pytest.raises(ValueError, match="unknown model type"):
        get_model(model_conf_of(conf), 48)
    program = spec.load_module("programs", "train_tokens")
    cell = types.SimpleNamespace(
        config={"model": {"ids_held": 48}, "reference": "nemotron_h"},
        module=lambda kind, name: spec.load_module(kind, name))
    with pytest.raises(ValueError, match="unknown model type"):
        program.ComparisonsAhead(cell, conf, 1, 64)


# ------------------------------------------- the cell's entries and readers


#: the Kimi cell's own readers of scopes and counters this cell's program has
#: too; ``test_bench_kimi_linear.py`` holds their lists to the Kimi cell alone,
#: so this cell joins them in the ``benchmark`` PR that may edit that file
#: (PERF.md section 7); until then the tests below call them directly
SHARED_READERS = ("moe_device_ms", "lm_head_loss_device_ms",
                  "moe_experts_roofline", "moe_held_load_max_over_mean")


def test_every_metric_that_names_the_cell_has_a_reader_that_agrees():
    """The cell's metric set by a rule, not a count or a place in a list
    (a later PR appends cells, configurations and metrics after these, and
    may list this cell in more readers): every per-layer metric that names
    the cell has a reader file whose ``META`` agrees, the twelve every
    training cell lists and this cell's three are among them, and none is
    an augmentation's or another family's."""
    cell = spec.resolve_cell(CELL, trace=True)
    bench = spec.load_benchmark()
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in listed} == {m["name"] for m in cell.per_layer}
    for entry in listed:
        assert runner.reader_for(cell, entry).META["moves"] == entry["moves"]
    names = {m["name"] for m in listed}
    kimi = {m["name"] for m in bench["per_layer"] if KIMI_CELL in m.get("workloads", ())}
    assert set(NEW_READERS) <= names - kimi
    assert {"kda_device_ms", "kda_scan_roofline", "mla_device_ms"} <= kimi - names
    assert {"step_device_ms", "model_flops_utilization", "model_forward_device_ms",
            "model_backward_device_ms", "step_unscoped_share", "peak_hbm_bytes",
            "device_idle_share", "compile_first_call_s", "compile_cache_misses",
            "dispatch_gap_ms", "optimizer_device_ms",
            "batch_gather_device_ms"} <= names
    assert not any(n.startswith(("aug_", "shake_", "resnet_", "feed_", "host_",
                                 "kda_", "mla_", "mtp_")) for n in names)
    for name in NEW_READERS:
        entry = next(m for m in listed if m["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] == "models"
        assert entry["source"] == "device_trace"
    [workload] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload["chips"] == 1
    assert workload["config"] == "nemotron3_nano_30b_a3b_tokens"
    assert workload["config"] in {c["name"] for c in bench["configs"]}
    assert cell.traffic["program"] == "train_tokens"
    assert cell.traffic["fixture"] == "tokens_markov_16384" and cell.fixture["ids"] == 16384
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    end_to_end = next(m for m in bench["end_to_end"] if m["name"] == "train_images_per_s")
    assert CELL in end_to_end["workloads"]
    # the traffic is the Kimi cell's but for the fixture and what is said of it
    kimi_traffic = spec.resolve_cell(KIMI_CELL).traffic
    differing = {k for k in kimi_traffic if kimi_traffic[k] != cell.traffic[k]}
    assert differing == {"describes", "fixture", "reduced", "loss_margin_because"}
    assert "9.704" in cell.traffic["loss_margin_because"]
    assert math.log(16384) == pytest.approx(9.704, abs=1e-3)


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
    assert {scopes.MAMBA2, scopes.SSD_SCAN, scopes.GQA, scopes.MOE,
            scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS,
            scopes.OPTIMIZER} <= found
    assert scopes.MLA_ATTENTION not in found and scopes.MLA not in found
    # the scan only ever inside the mixer
    assert all(scopes.MAMBA2 in chain for chain in chains if scopes.SSD_SCAN in chain)
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
    assert {scopes.MAMBA2, scopes.SSD_SCAN, scopes.GQA} <= read
    inside_the_model = [chain[1:] for chain in chains
                        if chain[:1] == (scopes.MODEL,) and len(chain) > 1]
    assert inside_the_model
    for chain in inside_the_model:
        assert read.intersection(chain), chain


RECORDED_PATH = os.path.join(spec.BENCH_DIR, "testdata",
                             "v5e_nemotron_h_step_scopes.json")


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
    for scope in (scopes.MAMBA2, scopes.SSD_SCAN, scopes.GQA, scopes.MOE,
                  scopes.MOE_ROUTER, scopes.MOE_EXPERTS, scopes.LM_HEAD, scopes.LOSS,
                  scopes.OPTIMIZER):
        assert any(scope in chain for chain in chains), scope
    assert all(scopes.MAMBA2 in chain for chain in chains if scopes.SSD_SCAN in chain)
    assert not any(scopes.MLA in chain or scopes.MLA_ATTENTION in chain
                   for chain in chains)
    # the attention's kernels run under the mixer's scope
    kernels = [name for name in held["names"] if "mla_attention_" in name]
    assert any("mla_attention_forward" in n for n in kernels)
    assert any("mla_attention_backward" in n for n in kernels)
    assert split.unscoped_share() <= 6.0


def test_the_readers_old_and_new_on_the_recorded_step(recorded, monkeypatch, tmp_path):
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    values = {name: spec.load_module("layer_metrics", name).read(obs)
              for name in held["expected"]}
    for name, expected in held["expected"].items():
        assert values[name] == pytest.approx(expected, rel=1e-6), name
    assert set(NEW_READERS) | set(SHARED_READERS) == set(held["expected"])
    assert 0 < values["ssd_scan_roofline"] < 100
    assert 0 < values["moe_experts_roofline"] < 100
    assert 0 < values["gqa_device_ms"] < values["mamba2_device_ms"]
    assert 0 < values["lm_head_loss_device_ms"] < values["moe_device_ms"]
    assert 1.0 <= values["moe_held_load_max_over_mean"] <= 8.0
    # the scan's share by hand: the bytes bound it at these sizes
    model = CONFIG["model"]
    scan_ms = hs.scope_ms(obs, scopes.SSD_SCAN)
    assert 0 < scan_ms < values["mamba2_device_ms"]
    moved = 4 * sum(FLOPS.ssd_scan_bytes(model, 8192, backward=b) for b in (False, True))
    operations = 4 * 3 * FLOPS.ssd_scan_operations(model, 8192, backward=False)
    assert moved / 819e9 > operations / 197e12
    assert values["ssd_scan_roofline"] == pytest.approx(
        100 * (moved / 819e9) / (scan_ms / 1e3), rel=1e-6)
    # the latent-attention readers have nothing of theirs to read here
    assert spec.load_module("layer_metrics", "mla_device_ms").read(obs) in (None, 0.0)


def test_readers_on_a_program_from_before_the_scopes(recorded, monkeypatch, tmp_path):
    """The parent's program under this tree's benchmark files: no such
    scope in its table, so each new reader returns None and does not raise;
    nor does the share on a configuration whose operations file lacks its
    functions (the Kimi cell's)."""
    held, chip = recorded
    obs = _observed(held, chip, monkeypatch, tmp_path)
    kimi = spec.resolve_cell(KIMI_CELL, trace=True)
    reader = spec.load_module("layer_metrics", "ssd_scan_roofline")
    assert reader.read(types.SimpleNamespace(
        cell=kimi, work=obs.work, devices=obs.devices)) is None
    for name in ("MAMBA2", "SSD_SCAN", "GQA"):
        monkeypatch.delattr(scopes, name)
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(obs) is None, name
