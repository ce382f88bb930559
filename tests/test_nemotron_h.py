"""Nemotron-H's parts against their plain forms, at a small size on the CPU
with seeded random inputs: the chunked state-space scan against the
recurrence (values, every argument's gradient, an initial state, a decay
that a factorised form would overflow on), the causal convolution against
a direct sum, the gated grouped norm against its definition, 2 key-value
heads against the same attention with the heads written out, each kind of
layer and the whole cut model against the plain reference
(``benchmarks/references/nemotron_h.py``: logits, loss, every gradient
leaf), the shipped conf against the published model, and what the family
file has not written down, refused.  Through ``train_and_eval``:
``tests/test_token_training.py``; the configuration's files:
``tests/benchmarks/test_bench_nemotron_h.py``."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks.harness import spec
from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models import nemotron_h as family
from fast_autoaugment_tpu.models.token_blocks import ROUTING, STEP_STATS
from fast_autoaugment_tpu.ops import kda, ssd
from fast_autoaugment_tpu.ops.ssd import chunk_ssd, recurrent_ssd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = spec.load_module("references", "nemotron_h")
FLOPS = spec.load_module("flops", "nemotron_h")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


# ------------------------------------------------------------------ the scan

_SCAN_ARGUMENTS = ["x", "dt", "a", "b", "c", "d"]

#: the tests' own small shape (the ``jnp`` form takes it), and the shape
#: every published layer of the family states: chunks of 128, a state of
#: 128, heads of 64 that fill whole tiles of 128 lanes a group (two tiles
#: a group here, two groups), which goes through the fused kernels
#: (interpreted here: the code the chip runs)
SMALL = dict(length=32)
NATIVE = dict(length=256, batch=1, heads=8, width=64, groups=2, size=128)


def _scan_inputs(length=32, *, dt_low=1e-3, dt_high=0.1, batch=2, heads=6,
                 width=8, groups=2, size=16, seed=0):
    """Three heads a group; the step log-uniform in ``[dt_low, dt_high]``
    (the published initial range is [0.001, 0.1]) and ``A`` in -[1, 64]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, length, heads, width))
    dt = jnp.exp(jax.random.uniform(keys[1], (batch, length, heads))
                 * (math.log(dt_high) - math.log(dt_low)) + math.log(dt_low))
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,)) * math.log(64.0))
    b = jax.random.normal(keys[3], (batch, length, groups, size))
    c = jax.random.normal(keys[4], (batch, length, groups, size))
    d = jax.random.normal(keys[5], (heads,))
    return x, dt, a, b, c, d


def _form_counts():
    snapshot = telemetry.registry().counters_snapshot()
    return {form: snapshot.get(f'faa_ssd_scan_traces_total{{form="{form}"}}', 0.0)
            for form in ("fused", "chunked_xla", "recurrent")}


@pytest.mark.parametrize("dt_high", [0.1, 4.0], ids=["published_steps", "large_steps"])
@pytest.mark.parametrize("shape, chunk", [(SMALL, 32), (SMALL, 16), (SMALL, 4), (NATIVE, 128)],
                         ids=["one_chunk", "two", "eight", "native"])
def test_chunked_scan_gives_the_recurrences_output(shape, chunk, dt_high):
    """Heads-a-group 3, a chunk that divides the sequence 1, 2 and 8 times
    (and the fused kernels' two chunks of 128, four heads a group), the
    step at both ends of its range: softplus's small end (1e-3: a head
    that forgets nothing in a chunk) and steps of up to 4 against ``A`` of
    up to -64 (a head whose state is gone within a token)."""
    x, dt, a, b, c, d = arguments = _scan_inputs(dt_high=dt_high, **shape)
    half = x.shape[1] // 2
    y = recurrent_ssd(*arguments)
    ours = chunk_ssd(*arguments, chunk=chunk)
    assert ours.shape == y.shape == x.shape
    # 128 large steps a chunk: both forms read the same 4.6e-5 there, a
    # running sum of -3,000 less another
    rel = 1e-4 if chunk == 128 and dt_high > 1 else 2e-5
    _close(ours, y, rel)
    # and the state that chunks hand on is read: the later half of the
    # sequence alone, from S = 0, is another result
    alone = chunk_ssd(x[:, half:], dt[:, half:], a, b[:, half:], c[:, half:], d,
                      chunk=min(chunk, half))
    assert float(jnp.abs(alone - y[:, half:]).max()) > 1e-3
    _close(alone, recurrent_ssd(x[:, half:], dt[:, half:], a, b[:, half:],
                                c[:, half:], d), rel)


@pytest.mark.parametrize("shape, form", [(SMALL, "chunked_xla"), (NATIVE, "fused")],
                         ids=["small", "native"])
def test_a_decay_that_would_overflow_a_factorised_form_does_not(shape, form):
    """Steps of 8 against ``A = -64``: ``exp(-sum)`` of a chunk's running
    sum is ``exp(16,384)``; every exponent the chunked form takes is a sum
    over a span and non-positive, so nothing is inf or nan and the result
    is the recurrence's.  One head forgets at ``Δ A = -50`` a token beside
    heads that forget nothing."""
    x, dt, a, b, c, d = _scan_inputs(**shape)
    length = x.shape[1]
    whole = 128 if form == "fused" else length
    dt, a = jnp.full_like(dt, 8.0), jnp.full_like(a, -64.0)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(8.0 * 64.0 * 32)))
    for chunk in (whole, whole // 4):
        ours = chunk_ssd(x, dt, a, b, c, d, chunk=chunk)
        assert bool(jnp.isfinite(ours).all())
        _close(ours, recurrent_ssd(x, dt, a, b, c, d), 1e-5)
    mixed = a.at[1].set(-50.0 / 8.0).at[2:].set(-1e-4)
    every = tuple(range(6))
    grads = jax.grad(lambda *args: jnp.sum(chunk_ssd(*args, chunk=whole) ** 2),
                     argnums=every)(x, dt, mixed, b, c, d)
    plain = jax.grad(lambda *args: jnp.sum(recurrent_ssd(*args) ** 2),
                     argnums=every)(x, dt, mixed, b, c, d)
    for ours, theirs in zip(grads, plain):
        assert bool(jnp.isfinite(ours).all())
        _close(ours, theirs, 2e-4)


def _scan_gradients(shape, chunk):
    # steps of up to 1 in chunks of up to 32; the published range in chunks
    # of 128, where a running sum is as long as at the published sizes
    arguments = _scan_inputs(dt_high=1.0 if chunk < 128 else 0.1, **shape)

    def objective(fn):
        def loss(*args):
            return jnp.sum(jnp.sin(fn(*args)))
        return jax.grad(loss, argnums=tuple(range(6)))(*arguments)

    return (objective(recurrent_ssd),
            objective(lambda *args: chunk_ssd(*args, chunk=chunk)))


@pytest.fixture(scope="module", params=[(SMALL, 8), (SMALL, 32), (NATIVE, 128)],
                ids=["four_chunks", "one_chunk", "native"])
def scan_gradients(request):
    with jax.default_matmul_precision("highest"):
        return _scan_gradients(*request.param)


@pytest.mark.parametrize("argnum, name", list(enumerate(_SCAN_ARGUMENTS)))
def test_chunked_scans_gradient_is_the_recurrences(scan_gradients, argnum, name):
    plain, ours = scan_gradients
    assert ours[argnum].shape == plain[argnum].shape
    _close(ours[argnum], plain[argnum], 1e-4)
    assert float(jnp.abs(plain[argnum]).max()) > 0


#: how far the fused form may lie from the ``jnp`` form in float32:
#: float32 products (what ``highest`` asks) as close as two orders of
#: summation, bfloat16 operands (the chip's default) inside the deployed
#: limit of the configuration's comparison
_FUSED_TOLERANCES = {"highest": (1e-5, 2e-5), "default": (0.02, 0.05)}


@pytest.fixture(scope="module", params=sorted(_FUSED_TOLERANCES))
def fused_and_jnp(request):
    """``(ambient, the fused form's, the jnp form's)``: each the output
    and every argument's gradient, two sequences of three chunks."""
    arguments = _scan_inputs(**dict(NATIVE, length=384, batch=2, heads=4))

    def both(fn):
        return (fn(*arguments),) + jax.grad(
            lambda *args: jnp.sum(jnp.sin(fn(*args))), argnums=tuple(range(6)))(*arguments)

    # off the chip the ambient precision is float32 whatever it says: the
    # operands are rounded as the chip's default would round them
    exact = {"highest": kda._float32_products, "default": lambda: False}[request.param]
    with pytest.MonkeyPatch.context() as patch, jax.default_matmul_precision("highest"):
        patch.setattr(kda, "_float32_products", exact)
        return (request.param, both(jax.jit(lambda *args: chunk_ssd(*args))),
                both(lambda *args: ssd._chunk_ssd_xla(*args, 128)))


@pytest.mark.parametrize("which, name", list(enumerate(["y"] + _SCAN_ARGUMENTS)))
def test_fused_scan_is_the_jnp_form_under_either_precision(fused_and_jnp, which, name):
    ambient, fused, plain = fused_and_jnp
    assert fused[which].shape == plain[which].shape
    _close(fused[which], plain[which], _FUSED_TOLERANCES[ambient][which > 0])


def test_two_sequences_in_a_batch_are_each_their_own():
    arguments = _scan_inputs(**dict(NATIVE, batch=2, heads=4))
    both = chunk_ssd(*arguments)
    for i in range(2):
        alone = chunk_ssd(*(v[i:i + 1] if v.ndim > 1 else v for v in arguments))
        np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(both[i]))


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused():
    arguments = _scan_inputs(length=24)
    with pytest.raises(ValueError, match="no whole number"):
        chunk_ssd(*arguments, chunk=16)
    short = chunk_ssd(*_scan_inputs(length=8), chunk=128)     # one short chunk
    _close(short, recurrent_ssd(*_scan_inputs(length=8)), 2e-5)
    x, dt, a, b, c, d = _scan_inputs(heads=6, groups=2)
    with pytest.raises(ValueError, match="groups"):
        chunk_ssd(x[:, :, :5], dt[:, :, :5], a[:5], b, c, d[:5], chunk=16)


@pytest.mark.parametrize("shape, chunk, form", [
    (SMALL, 16, "chunked_xla"), (NATIVE, 128, "fused"),
    # the kernels' widths in a sequence under a chunk, in chunks of 64, with
    # a state of 64, and with three heads of 64 a group (a tile and a half)
    (dict(NATIVE, length=64), 128, "chunked_xla"), (NATIVE, 64, "chunked_xla"),
    (dict(NATIVE, size=64), 128, "chunked_xla"),
    (dict(NATIVE, heads=6), 128, "chunked_xla")],
    ids=["small", "native", "short", "chunk-64", "state-64", "half-tile"])
def test_the_scans_counter_counts_the_form_the_trace_took(shape, chunk, form):
    arguments = _scan_inputs(**shape)
    before = _form_counts()
    jax.eval_shape(functools.partial(chunk_ssd, chunk=chunk), *arguments)
    middle = _form_counts()
    jax.eval_shape(lambda *args: recurrent_ssd(*args), *arguments)   # a trace of its own
    after = _form_counts()
    assert {f: middle[f] - before[f] for f in middle} == {
        f: float(f == form) for f in middle}
    assert {f: after[f] - middle[f] for f in after} == {
        f: float(f == "recurrent") for f in after}


# ---------------------------------------- convolution, norm, the three mixers


def test_causal_convolution_is_the_direct_sum():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (2, 12, 5))
    kernel = jax.random.normal(keys[1], (4, 5))
    bias = jax.random.normal(keys[2], (5,))
    ours = np.asarray(family.causal_conv(x, kernel, bias))
    x, kernel, bias = (np.asarray(a, np.float64) for a in (x, kernel, bias))
    plain = np.zeros_like(x)
    for t in range(12):
        for tap in range(4):
            source = t - 3 + tap          # the last tap meets the token itself
            if source >= 0:               # zeros before the sequence
                plain[:, t] += kernel[tap] * x[:, source]
        plain[:, t] += bias
    _close(ours, plain, 1e-6)
    # causal: a later token moves no earlier output
    moved = np.array(x, np.float32)
    moved[:, 7] += 1.0
    again = np.asarray(family.causal_conv(jnp.asarray(moved), jnp.asarray(
        kernel, jnp.float32), jnp.asarray(bias, jnp.float32)))
    assert np.array_equal(again[:, :7], ours[:, :7]) and not np.array_equal(
        again[:, 7], ours[:, 7])


def test_gated_grouped_norm_is_its_definition():
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    y = jax.random.normal(keys[0], (2, 6, 24)) * 3.0
    z = jax.random.normal(keys[1], (2, 6, 24))
    weight = jax.random.normal(keys[2], (24,))
    ours = np.asarray(family.gated_group_norm(y, z, weight, 3, 1e-5))
    y, z, weight = (np.asarray(a, np.float64) for a in (y, z, weight))
    gated = y * (z / (1.0 + np.exp(-z)))                  # the gate first
    plain = np.zeros_like(gated)
    for group in range(3):                                # then each group's own RMS
        part = gated[..., 8 * group:8 * (group + 1)]
        plain[..., 8 * group:8 * (group + 1)] = part / np.sqrt(
            (part ** 2).mean(-1, keepdims=True) + 1e-5)
    _close(ours, plain * weight, 1e-5)
    # not the norm over all 24 channels, and not the norm before the gate
    whole = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5) * weight
    assert np.abs(ours - whole).max() > 0.05


TINY_MODEL = {
    "type": "nemotron_h", "remat": True, "router_bias_update_rate": 0.02,
    "hidden_size": 32, "hybrid_override_pattern": "MEMEM*EMEM",
    "num_hidden_layers": 10, "layer_norm_epsilon": 1e-5, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
    "chunk_size": 8, "use_conv_bias": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "n_routed_experts": 16,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 24, "n_shared_experts": 1,
    "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "vocab_size": 64,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4}


def tiny_conf(**model):
    return {"model": dict(TINY_MODEL, **model), "dataset": "synthetic_tokens",
            "layers_held": 9, "experts_held": 4, "ids_held": 48}


def tiny_sizes(conf, length):
    return dict(FLOPS.model_from_conf(conf["model"]), expert_share=0, seq_len=length,
                **{k: conf[k] for k in ("layers_held", "experts_held", "ids_held")})


@pytest.fixture(scope="module")
def tiny():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), 48)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 48)
    params = model.init({"params": jax.random.PRNGKey(3)}, ids[:, :-1])["params"]
    # off their initial ones and zeros, so that a norm, a bias or a skip
    # left out shows
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    return conf, model, params, ids, tiny_sizes(conf, 32)


def test_the_cut_is_the_patterns_first_nine_layers(tiny):
    _, _, params, _, sizes = tiny
    kinds = {name: sorted(set(layer) - {"norm"}) for name, layer in params.items()
             if name.startswith("layer")}
    assert kinds == {
        "layer1": ["mamba"], "layer2": ["moe"], "layer3": ["mamba"],
        "layer4": ["moe"], "layer5": ["mamba"], "layer6": ["attn"],
        "layer7": ["moe"], "layer8": ["mamba"], "layer9": ["moe"]}
    assert sorted(params["layer1"]["mamba"]) == [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
        "norm_weight", "out_proj"]
    assert params["layer1"]["mamba"]["in_proj"]["kernel"].shape == (
        32, 32 + (32 + 2 * 2 * 16) + 4)                  # z, xBC, dt
    assert sorted(params["layer2"]["moe"]) == [
        "e_score_correction_bias", "experts_down", "experts_up", "router",
        "shared_experts"]
    assert params["layer2"]["moe"]["shared_experts"]["up_proj"]["kernel"].shape == (32, 24)
    assert params["layer6"]["attn"]["k_proj"]["kernel"].shape == (32, 2 * 8)
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)


@pytest.mark.parametrize("layer, kind", [("layer1", "M"), ("layer6", "*"),
                                         ("layer2", "E")])
def test_each_kind_of_layer_is_the_references(tiny, layer, kind):
    conf, model, params, _, sizes = tiny
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 32))
    ours = family.Layer(model.sizes, kind).apply({"params": params[layer]}, x)
    for row in range(2):
        plain, _ = REFERENCE._layer(x[row], params[layer], kind, sizes)
        _close(ours[row], plain, 2e-5)
    assert float(jnp.abs(ours - x).max()) > 1e-3


def test_two_key_value_heads_are_the_attention_with_the_heads_written_out(tiny):
    """Key-value head ``g`` serves the query heads ``[2g, 2g + 1]``: the
    same mixer with ``k_proj`` and ``v_proj`` written out once a query head
    (4 key-value heads) is the same function, and key-value head 0 for all
    four is another."""
    conf, model, params, _, _ = tiny
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 32, 32))
    attn = params["layer6"]["attn"]
    def mixer(sizes):   # ``token_blocks.GQAMixer``, bare, at the model's sizes
        return family.GQAMixer(sizes.heads, sizes.kv_heads, sizes.head_dim)

    ours = mixer(model.sizes).apply({"params": attn}, x)

    def written_out(order):
        full = get_model(model_conf_of(tiny_conf(num_key_value_heads=4)), 48)
        out = dict(attn)
        for name in ("k_proj", "v_proj"):
            heads = np.asarray(attn[name]["kernel"]).reshape(32, 2, 8)
            out[name] = {"kernel": jnp.asarray(heads[:, order].reshape(32, 32))}
        return mixer(full.sizes).apply({"params": out}, x)

    _close(written_out([0, 0, 1, 1]), ours, 1e-5)
    assert float(jnp.abs(written_out([0, 0, 0, 0]) - ours).max()) > 1e-3
    assert float(jnp.abs(written_out([0, 1, 0, 1]) - ours).max()) > 1e-3


def test_the_whole_cut_model_is_the_reference_logits_loss_and_every_gradient(tiny):
    """Float32 under ``highest`` on both sides: rounding alone is left —
    1e-5 of the largest logit, the loss to 1e-6, every gradient leaf to 2e-4
    of its largest element (the chunked scan against the recurrence token by
    token, sums in another order)."""
    _, model, params, ids, sizes = tiny
    logits = model.apply({"params": params}, ids[:, :-1])
    plain = REFERENCE.forward(params, {}, ids[:, :-1], sizes)
    assert logits.shape == (2, 32, 48) and logits.dtype == jnp.float32
    _close(logits, plain, 1e-5)

    def loss(p):
        (nll, top1, further), sown = model.apply(
            {"params": p}, ids[:, :-1], ids[:, 1:], method="loss_terms",
            mutable=[STEP_STATS])
        assert further == {} and top1.shape == (2,)
        return nll.mean()

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    plain_loss, plain_grads = REFERENCE.loss_and_grads(params, ids, sizes)
    assert float(value) == pytest.approx(plain_loss, rel=1e-6)
    with np.errstate(invalid="ignore"):   # the correction bias has no gradient: 0 / 0
        gaps = jax.tree.map(
            lambda a, b: float(np.abs(a - b).max() / np.abs(b).max()),
            dict(grads), plain_grads)
    worst = max(g for g in jax.tree.leaves(gaps) if math.isfinite(g))
    assert worst < 2e-4, gaps
    for layer in ("layer2", "layer4", "layer7", "layer9"):
        assert not np.any(np.asarray(grads[layer]["moe"]["e_score_correction_bias"]))
    # every other leaf learns: none is cut off from the loss
    for path, leaf in jax.tree_util.tree_flatten_with_path(plain_grads)[0]:
        if "e_score_correction_bias" not in jax.tree_util.keystr(path):
            assert np.abs(leaf).max() > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("control", ["no_d_skip", "no_gate", "no_conv_bias",
                                     "rotary", "one_kv_head", "one_layer_short"])
def test_the_references_controls_are_other_functions(tiny, control):
    _, model, params, ids, sizes = tiny
    logits, sown = model.apply({"params": params}, ids[:, :-1], mutable=[ROUTING])
    routing = {layer: np.asarray(entry["moe"]["chosen"][0])
               for layer, entry in sown[ROUTING].items()}
    sound, margin = REFERENCE.forward_given_routing(params, ids[:, :-1], sizes, routing)
    _close(logits, sound, 1e-5)
    assert 0.0 <= margin < 1e-5
    if control == "one_layer_short":
        changed, given = dict(sizes, layers_held=8), {
            k: v for k, v in routing.items() if k != "layer9"}
    else:
        changed, given = dict(sizes, control=control), routing
    other, _ = REFERENCE.forward_given_routing(params, ids[:, :-1], changed, given)
    gap = float(np.abs(np.asarray(logits) - other).max() / np.abs(other).max())
    assert gap > 1e-3, gap


def test_after_step_moves_every_router_by_the_balancing_rule(tiny):
    from fast_autoaugment_tpu.ops import moe

    _, model, params, ids, _ = tiny
    _, sown = model.apply({"params": params}, ids[:, :-1], mutable=[STEP_STATS])
    stats = sown[STEP_STATS]
    assert sorted(stats) == ["layer2", "layer4", "layer7", "layer9"]
    moved, counts = model.after_step(params, stats)
    for layer, entry in stats.items():
        (load,) = entry["moe"]["load"]
        assert load.shape == (16,) and int(load.sum()) == 2 * 32 * 2
        np.testing.assert_array_equal(
            np.asarray(moved[layer]["moe"]["e_score_correction_bias"]),
            np.asarray(moe.balance_bias(
                params[layer]["moe"]["e_score_correction_bias"], load, 0.02)))
        assert float(counts[f"moe_assigned/{layer}"]) == int(load[:4].sum())
    assert moved["layer1"] is params["layer1"]


# ------------------------------------------- the shipped conf, and refusals


def shipped_conf() -> dict:
    with open(os.path.join(REPO, "confs", "nemotron3_nano_30b_a3b.yaml")) as fh:
        return yaml.safe_load(fh)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_the_shipped_conf_is_the_published_model_key_for_key():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == PUBLISHED)
    model = shipped_conf()["model"]
    for key, value in row["config"].items():
        assert model[key] == value, key
    ours = set(model) - set(row["config"])
    assert ours == {"type", "remat", "router_bias_update_rate"}


def test_the_shipped_conf_builds_the_whole_model_and_the_cut():
    """No width is set here: the parameter count of the whole model and of
    one chip's cut from shapes alone (nothing is allocated), against the
    operations file's count and the published 31.6 B."""
    conf = shipped_conf()
    assert not any(key in conf for key in ("layers_held", "experts_held", "ids_held"))
    assert conf["dataset"] == "tokens" and conf["optimizer"]["type"] == "adamw"
    pattern = conf["model"]["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)

    def shapes_of(conf, ids):
        module = get_model(model_conf_of(conf), ids)
        return jax.eval_shape(lambda: module.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 128), jnp.int32)))["params"]

    def count(shapes):
        return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))

    whole = shapes_of(conf, 131072)
    assert len([k for k in whole if k.startswith("layer")]) == 52
    assert 31.5e9 < count(whole) < 31.7e9
    sizes = FLOPS.model_from_conf(conf["model"])
    assert count(whole) == FLOPS.num_params(sizes)
    cut = shapes_of(dict(conf, layers_held=9, experts_held=8, ids_held=16384), 16384)
    assert count(cut) == 666_963_456 == FLOPS.num_params(
        dict(sizes, layers_held=9, experts_held=8, ids_held=16384))
    assert count(cut["layer1"]) == 38_744_896 and count(cut["layer6"]) == 23_399_040
    assert count(cut["layer2"]) == 100_125_312 + 128     # + the correction bias
    assert cut["layer1"]["mamba"]["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert cut["layer1"]["mamba"]["conv_kernel"].shape == (4, 6144)
    assert cut["layer2"]["moe"]["experts_up"].shape == (8, 2688, 1856)
    assert cut["layer2"]["moe"]["router"].shape == (2688, 128)
    assert cut["layer2"]["moe"]["shared_experts"]["down_proj"]["kernel"].shape == (3712, 2688)
    assert cut["layer6"]["attn"]["v_proj"]["kernel"].shape == (2688, 256)
    seven = shapes_of(dict(conf, layers_held=7, experts_held=8, ids_held=16384), 16384)
    assert count(seven) == 528_092_736 + 3 * 128         # the issue's fall-back cut


def test_initial_values_are_the_assumed_ones():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), 48)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 16), jnp.int32))["params"]
    mamba = params["layer1"]["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(mamba["A_log"])), [1, 2, 3, 4], rtol=1e-6)
    assert np.all(np.asarray(mamba["D"]) == 1) and np.all(np.asarray(mamba["norm_weight"]) == 1)
    assert not np.any(np.asarray(mamba["conv_bias"]))
    steps = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))   # softplus
    assert np.all(steps >= 1e-3 * 0.999) and np.all(steps <= 0.1 * 1.001)
    wide = get_model(model_conf_of(tiny_conf(hidden_size=512)), 48).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32))["params"]
    out, into = (np.asarray(wide["layer1"]["mamba"][k]["kernel"]).std()
                 for k in ("out_proj", "in_proj"))
    assert into == pytest.approx(0.02, rel=0.1)
    assert out == pytest.approx(0.02 / math.sqrt(10), rel=0.1)   # / sqrt(layers)
    assert not np.any(np.asarray(params["layer2"]["moe"]["e_score_correction_bias"]))


@pytest.mark.parametrize("bad, says", [
    ({"hybrid_override_pattern": "MEMEM*EME-"}, "dense feed-forward"),
    ({"hybrid_override_pattern": "MEMEM*EMEX"}, "unknown layer kinds"),
    ({"hybrid_override_pattern": "MEM"}, "num_hidden_layers"),
    ({"n_group": 2}, "more than one group"),
    ({"topk_group": 2}, "more than one group"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"sliding_window": 512}, "sliding_window"),
    ({"time_step_limit": [0.0, 0.5]}, "clamp on the step"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"mlp_hidden_act": "silu"}, "squared"),
    ({"num_key_value_heads": 3}, "no whole number"),
    ({"n_groups": 3}, "no whole number"),
])
def test_what_the_family_file_has_not_written_down_is_refused(bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_conf_of(tiny_conf(**bad)), 48)


@pytest.mark.parametrize("bad", [
    {"experts_held": 5}, {"experts_held": 4, "expert_share": 4},
    {"layers_held": 11}, {"ids_held": 128}])
def test_a_share_the_model_cannot_hold_is_refused(bad):
    conf = tiny_conf(expert_share=bad.pop("expert_share", 0))
    conf.update(bad)
    with pytest.raises(ValueError):
        get_model(model_conf_of(conf), 48)


def test_an_unknown_expert_form_is_refused():
    from fast_autoaugment_tpu.ops import moe

    x = jnp.zeros((4, 8))
    chosen, weights = jnp.zeros((4, 2), jnp.int32), jnp.ones((4, 2))
    with pytest.raises(ValueError, match="unknown expert form"):
        moe.held_experts(x, chosen, weights, jnp.zeros((2, 8, 4)),
                         jnp.zeros((2, 4, 8)), first=0, form="gelu")


def test_time_step_limit_without_a_clamp_is_taken():
    for limit in (None, [0.0, float("inf")], (0, math.inf)):
        get_model(model_conf_of(tiny_conf(time_step_limit=limit)), 48)
