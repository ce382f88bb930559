"""lfm2_moe's parts against their plain forms, at a small size on the CPU
with seeded random inputs: the short-convolution mixer against three
shifted copies, the one ``causal_conv`` the three families with a short
convolution share, the router's renormalisation with its epsilon as an
argument, the whole cut model against the plain reference
(``benchmarks/references/lfm2_moe.py``: logits, loss, every gradient leaf —
the table's the sum of the embedding's and the head's), the points the
configuration file lists under ``assumed`` each with a control that the
comparison refuses, four chips' shares adding up to the uncut expert layer,
the shipped conf against the published model, and what the family file has
not written down, refused.  Heads of 64 in the fused kernels:
``tests/test_attention_pairs.py``; through ``train_and_eval``:
``tests/test_lfm2_moe_training.py``; the configuration's files:
``tests/benchmarks/test_bench_lfm2_moe.py``."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks.harness import spec
from fast_autoaugment_tpu.core import scopes, telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models import lfm2_moe as family
from fast_autoaugment_tpu.models.token_blocks import (
    STEP_STATS,
    ExpertLayer,
    ShortConvMixer,
    causal_conv,
)
from fast_autoaugment_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = spec.load_module("references", "lfm2_moe")
FLOPS = spec.load_module("flops", "lfm2_moe")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONV, FULL = family.CONV, family.FULL


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def shipped_conf() -> dict:
    with open(os.path.join(REPO, "confs", "lfm2_8b_a1b.yaml")) as fh:
        return yaml.safe_load(fh)


#: every width cut for the CPU, the structure kept: the cut's seven blocks
#: (two dense, five expert layers; five convolution mixers, two attention
#: mixers of 4 heads of 8 on 2 key-value heads); 16 experts of which 4 are
#: held, top-2
TINY_MODEL = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=48, moe_intermediate_size=16, num_experts=16,
                  num_experts_per_tok=2, vocab_size=64, num_hidden_layers=8)
TINY_HELD = dict(layers_held=7, experts_held=4, ids_held=48)


def tiny_conf(**model) -> dict:
    conf = shipped_conf()
    conf["model"].update(TINY_MODEL, layer_types=conf["model"]["layer_types"][:8])
    conf["model"].update(model)
    conf.update(TINY_HELD)
    return conf


def tiny_sizes(conf: dict, seq_len: int) -> dict:
    return dict(FLOPS.model_from_conf(conf["model"]), expert_share=0, seq_len=seq_len,
                **{k: conf[k] for k in TINY_HELD})


# ------------------------------------------------- the short convolution


def test_causal_convolution_is_the_direct_sum_with_a_bias_and_without():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = np.asarray(jax.random.normal(keys[0], (2, 9, 5)))
    kernel = np.asarray(jax.random.normal(keys[1], (3, 5)))
    bias = np.asarray(jax.random.normal(keys[2], (5,)))
    direct = np.zeros_like(x)
    for t in range(9):
        for i in range(3):
            if t - 2 + i >= 0:
                direct[:, t] += kernel[i] * x[:, t - 2 + i]
    _close(causal_conv(jnp.asarray(x), jnp.asarray(kernel)), direct, 1e-6)
    _close(causal_conv(jnp.asarray(x), jnp.asarray(kernel), jnp.asarray(bias)),
           direct + bias, 1e-6)
    # the two families that had one of their own call this one
    from fast_autoaugment_tpu.models import kimi_linear, nemotron_h

    assert nemotron_h.causal_conv is causal_conv and kimi_linear.causal_conv is causal_conv


def test_the_mixer_is_two_gates_round_three_taps_and_nothing_else():
    """Against the equations by hand: ``(B, C, z) = split3(W_in u)``, ``c_t
    = k_0 s_(t-2) + k_1 s_(t-1) + k_2 s_t`` with ``s = B * z``, ``W_out (C *
    c)``; no bias, no activation; a token's output depends on itself and
    the two before it alone."""
    mixer = ShortConvMixer(3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 8))
    before = telemetry.registry().counters_snapshot()
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    after = telemetry.registry().counters_snapshot()
    key = 'faa_short_conv_traces_total{taps="3"}'
    assert after[key] == before.get(key, 0.0) + 1
    assert jax.tree.map(lambda a: a.shape, params) == {
        "in_proj": {"kernel": (8, 24)}, "conv_kernel": (3, 8),
        "out_proj": {"kernel": (8, 8)}}
    assert np.asarray(mixer.init(jax.random.PRNGKey(2), jnp.zeros((1, 4, 512)))[
        "params"]["conv_kernel"]).std() == pytest.approx(1 / math.sqrt(3), rel=0.1)
    ours = mixer.apply({"params": params}, x)
    w_in, taps, w_out = (np.asarray(params["in_proj"]["kernel"], np.float64),
                         np.asarray(params["conv_kernel"], np.float64),
                         np.asarray(params["out_proj"]["kernel"], np.float64))
    u = np.asarray(x, np.float64) @ w_in
    b, c, z = u[..., :8], u[..., 8:16], u[..., 16:]
    s = np.pad(b * z, ((0, 0), (2, 0), (0, 0)))
    conv = sum(taps[i] * s[:, i:i + 12] for i in range(3))
    _close(ours, (c * conv) @ w_out, 1e-5)
    _close(ours[0], REFERENCE._conv_mixer(x[0], params, {}), 1e-5)
    moved = x.at[:, 5].add(1.0)
    again = mixer.apply({"params": params}, moved)
    changed = np.abs(np.asarray(again - ours)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [t in (5, 6, 7) for t in range(12)]


def test_the_gates_and_taps_are_under_their_own_scope_inside_the_mixers():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), 48)
    ids = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda x: model.init({"params": jax.random.PRNGKey(0)}, x)).lower(
        ids).as_text(debug_info=True)
    import re

    chains = {scopes.scope_of(name) for name in re.findall(r'loc\("([^"]*)"', text)}
    assert any(scopes.SHORT_CONV_GATE in chain for chain in chains)
    assert all(scopes.SHORT_CONV in chain for chain in chains
               if scopes.SHORT_CONV_GATE in chain)
    assert any(chain == (scopes.SHORT_CONV,) for chain in chains)    # the projections
    assert any(scopes.GQA_ATTENTION in chain and scopes.GQA in chain for chain in chains)
    assert not any(scopes.SHORT_CONV in chain and scopes.GQA in chain for chain in chains)


# ----------------------------------------------------------------- the router


def test_the_renormalisations_epsilon_is_an_argument_and_defaults_to_todays():
    """``route`` with `eps` is the formula — the chosen scores over their
    sum + eps — and without it what it was (1e-20: nothing, in float32)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (40, 16))
    router = jax.random.normal(keys[1], (16, 32))
    bias = 0.1 * jax.random.normal(keys[2], (32,))
    scores = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    order = np.argsort(-(scores + np.asarray(bias)), -1)[:, :4]
    picked = np.take_along_axis(scores, order, -1)
    for eps in (1e-6, 0.5):
        chosen, weights = moe.route(x, router, bias, top_k=4, scale=1.0, eps=eps)
        assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(order, -1))
        _close(np.sort(np.asarray(weights), -1),
               np.sort(picked / (picked.sum(-1, keepdims=True) + eps), -1), 1e-6)
    default = moe.route(x, router, bias, top_k=4, scale=2.5)
    explicit = moe.route(x, router, bias, top_k=4, scale=2.5, eps=1e-20)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(default, explicit))
    assert np.asarray(default[1]).sum(-1) == pytest.approx(2.5, rel=1e-6)
    # the family's layers hand theirs on (assumed 7): 1e-6, too small for any
    # float32 comparison of logits to show
    assert family.RENORM_EPS == 1e-6
    layer = ExpertLayer(32, 32, 0, 4, 8, 0, 1.0, True, renorm_eps=0.5, name="moe")
    x3 = x[None]
    params = layer.init(jax.random.PRNGKey(4), x3)["params"]
    assert "shared_experts" not in params
    loose = layer.apply({"params": params}, x3)
    tight = ExpertLayer(32, 32, 0, 4, 8, 0, 1.0, True, name="moe").apply(
        {"params": params}, x3)
    assert float(jnp.abs(loose - tight).max()) > 0.05 * float(jnp.abs(tight).max())


# ------------------------------------------ the cut model against the reference


@pytest.fixture(scope="module")
def tiny():
    conf = tiny_conf()
    model = get_model(model_conf_of(conf), 48)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0, 48)
    params = model.init({"params": jax.random.PRNGKey(3)}, ids[:, :-1])["params"]
    # off their initial ones and zeros, so that a norm left out shows
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    return conf, model, params, ids, tiny_sizes(conf, 64)


def _gap(ours, plain):
    return float(np.abs(ours - plain).max() / np.abs(plain).max())


def _loss_and_grads(model, params, ids):
    def loss(p):
        (nll, _, _), _ = model.apply({"params": p}, ids[:, :-1], ids[:, 1:],
                                     method="loss_terms", mutable=[STEP_STATS])
        return nll.mean()

    return jax.jit(jax.value_and_grad(loss))(params)


def _worst_gradient_gap(grads, plain_grads):
    with np.errstate(invalid="ignore"):   # the correction bias has no gradient: 0 / 0
        gaps = jax.tree.map(
            lambda a, b: float(np.abs(a - b).max() / np.abs(b).max()),
            dict(grads), plain_grads)
    return max(g for g in jax.tree.leaves(gaps) if math.isfinite(g)), gaps


def test_the_cut_is_the_first_seven_blocks_by_kind(tiny):
    _, _, params, _, sizes = tiny
    kinds = {name: sorted(set(block) & {"mlp", "moe", "conv", "attn"})
             for name, block in params.items() if name.startswith("layer")}
    assert kinds == {
        "layer1": ["conv", "mlp"], "layer2": ["conv", "mlp"], "layer3": ["attn", "moe"],
        "layer4": ["conv", "moe"], "layer5": ["conv", "moe"], "layer6": ["conv", "moe"],
        "layer7": ["attn", "moe"]}
    for block in (params[name] for name in kinds):
        assert {"operator_norm", "ffn_norm"} <= set(block) and len(block) == 4
    assert sorted(params) == ["embed_tokens"] + sorted(kinds) + ["norm"]   # no lm_head
    assert sorted(params["layer3"]["attn"]) == ["k_norm", "k_proj", "o_proj", "q_norm",
                                                "q_proj", "v_proj"]
    assert params["layer3"]["attn"]["q_norm"]["weight"].shape == (8,)     # 32 / 4
    assert params["layer3"]["attn"]["k_proj"]["kernel"].shape == (32, 2 * 8)
    assert sorted(params["layer1"]["conv"]) == ["conv_kernel", "in_proj", "out_proj"]
    assert sorted(params["layer3"]["moe"]) == [
        "e_score_correction_bias", "experts_down", "experts_gate", "experts_up", "router"]
    assert params["layer3"]["moe"]["experts_gate"].shape == (4, 32, 16)
    assert params["layer3"]["moe"]["router"].shape == (32, 16)
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)


def test_the_whole_cut_model_is_the_reference_logits_loss_and_every_gradient(tiny):
    """Float32 under ``highest`` on both sides, 64 tokens: rounding alone is
    left, 1e-5 of the largest logit; the loss to 1e-6; every gradient leaf
    to 2e-4 of its largest element — the one table's among them, the sum of
    what reaches it as the embedding and as the head."""
    _, model, params, ids, sizes = tiny
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids[:, :-1])
    assert _gap(np.asarray(logits), REFERENCE.forward(params, {}, ids[:, :-1], sizes)) <= 1e-5
    value, grads = _loss_and_grads(model, params, ids)
    plain_loss, plain_grads = REFERENCE.loss_and_grads(params, ids, sizes)
    assert float(value) == pytest.approx(plain_loss, rel=1e-6)
    worst, gaps = _worst_gradient_gap(grads, plain_grads)
    assert worst < 2e-4, gaps
    assert set(jax.tree.leaves(jax.tree.map(lambda a: a.shape, dict(grads)))) == set(
        jax.tree.leaves(jax.tree.map(lambda a: a.shape, plain_grads)))
    # the table's gradient has both parts: rows of ids the inputs never hold
    # (the head's alone) and the embedding's on top where they do
    table = np.asarray(grads["embed_tokens"])
    unseen = sorted(set(range(48)) - set(np.asarray(ids[:, :-1]).ravel().tolist()))
    assert np.abs(table).min(axis=-1).max() > 0 and (not unseen or np.any(table[unseen]))


#: the configuration file's ``assumed`` points that are the forward pass's,
#: each as the change of the reference's `model` dict that leaves it out
ASSUMED_CONTROLS = {
    "1_tied_head": {"control": "untied_head"},
    "3_norms_a_head": {"control": "no_qk_norm"},
    "4_rotary_at_all": {"control": "no_rotary"},
    "4_pairs_i_and_i_plus_half": {"control": "interleaved_pairs"},
    "5_split_order_b_c_z": {"control": "split_cbz"},
    "5_no_activation_after_the_taps": {"control": "silu_after_taps"},
    "5_last_tap_on_the_token_itself": {"control": "taps_shifted"},
    "6_final_norm": {"control": "no_final_norm"},
    "kernel_pairs_each_head_its_own_query": {"control": "neighbours_queries"},
}


@pytest.fixture(scope="module")
def tiny_logits_and_loss(tiny):
    _, model, params, ids, _ = tiny
    logits = np.asarray(jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, ids[:, :-1]))
    return logits, float(_loss_and_grads(model, params, ids)[0])


def _next_token_loss(logits, ids):
    """The mean next-token cross-entropy of float32 `logits` ``[B, T, V]``."""
    logits = np.asarray(logits, np.float64)
    top = logits.max(-1, keepdims=True)
    log_sum = np.log(np.exp(logits - top).sum(-1)) + top[..., 0]
    picked = np.take_along_axis(logits, np.asarray(ids)[:, 1:, None], -1)[..., 0]
    return float((log_sum - picked).mean())


@pytest.mark.parametrize("point", sorted(ASSUMED_CONTROLS))
def test_an_assumed_point_left_out_fails_the_comparison(tiny, tiny_logits_and_loss, point):
    """Logits over 1e-3 of the largest (a hundred times the sound gap's
    limit) and the loss off by more than 1e-5 of itself, where the sound
    reference's is within 1e-6."""
    _, _, params, ids, sizes = tiny
    logits, loss = tiny_logits_and_loss
    sound = REFERENCE.forward(params, {}, ids[:, :-1], sizes)
    assert abs(_next_token_loss(sound, ids) - loss) <= 1e-6 * loss
    if point == "1_tied_head":     # a head of its own, seeded as the program seeds one
        params = dict(params, lm_head={"kernel": 0.02 * jax.random.normal(
            jax.random.PRNGKey(11), (32, 48))})
    other = REFERENCE.forward(params, {}, ids[:, :-1],
                              dict(sizes, **ASSUMED_CONTROLS[point]))
    assert _gap(logits, other) > 1e-3, point
    assert abs(_next_token_loss(other, ids) - loss) > 1e-5 * loss, point


def test_assumed_7_the_epsilon_is_under_any_float32_reading(tiny, tiny_logits_and_loss):
    """1e-6 on a sum of four sigmoids of about a half: 5e-7 of a weight.
    The reference at 1e-20 is inside the sound comparison's rounding, so no
    comparison of logits can hold the point; `route`'s own test does."""
    _, _, params, ids, sizes = tiny
    logits, _ = tiny_logits_and_loss
    other = REFERENCE.forward(params, {}, ids[:, :-1], dict(sizes, renorm_eps=1e-20))
    assert _gap(logits, other) <= 1e-5


def test_assumed_9_the_bias_moves_by_the_confs_rate_a_step(tiny):
    """The row gives ``use_expert_bias`` and no rate: the conf's
    ``router_bias_update_rate`` 0.02 is the step of the rule that moves the
    routers' correction bias, after a step every expert layer's bias is
    ``balance_bias`` at that rate."""
    _, model, params, ids, _ = tiny
    assert model.sizes.bias_update_rate == 0.02
    _, sown = model.apply({"params": params}, ids[:, :-1], mutable=[STEP_STATS])
    stats = sown[STEP_STATS]
    assert sorted(stats) == ["layer3", "layer4", "layer5", "layer6", "layer7"]
    moved, counts = model.after_step(params, stats)
    for layer, entry in stats.items():
        (load,) = entry["moe"]["load"]
        assert load.shape == (16,) and int(load.sum()) == 2 * 64 * 2
        before = params[layer]["moe"]["e_score_correction_bias"]
        after = np.asarray(moved[layer]["moe"]["e_score_correction_bias"])
        np.testing.assert_array_equal(after, np.asarray(moe.balance_bias(before, load, 0.02)))
        assert float(counts[f"moe_assigned/{layer}"]) == int(load[:4].sum())
    assert moved["layer1"] is params["layer1"]


def test_assumed_10_initial_values_precision_and_remat():
    """The repo's normal(0.02) for every matrix and the table, the taps
    normal(1 / sqrt(3)), every norm's weight 1, the correction bias 0;
    float32 parameters and activations; every block under ``nn.remat`` (a
    model that is not is the same function)."""
    conf = tiny_conf(hidden_size=256, intermediate_size=64)
    model = get_model(model_conf_of(conf), 48)
    assert model.remat and model.dtype == jnp.float32
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.jit(model.init)({"params": jax.random.PRNGKey(0)}, ids)["params"]
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype(jnp.float32)}
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        assert np.asarray(params["layer3"]["attn"][name]["kernel"]).std() == (
            pytest.approx(0.02, rel=0.1)), name
    for name in ("in_proj", "out_proj"):
        assert np.asarray(params["layer1"]["conv"][name]["kernel"]).std() == (
            pytest.approx(0.02, rel=0.1)), name
    assert np.asarray(params["layer1"]["conv"]["conv_kernel"]).std() == (
        pytest.approx(1 / math.sqrt(3), rel=0.15))
    assert np.asarray(params["embed_tokens"]).std() == pytest.approx(0.02, rel=0.1)
    assert np.asarray(params["layer3"]["moe"]["router"]).std() == pytest.approx(0.02, rel=0.1)
    norms = [params["norm"]["weight"]] + [
        params["layer4"][name]["weight"] for name in ("operator_norm", "ffn_norm")] + [
        params["layer3"]["attn"][name]["weight"] for name in ("q_norm", "k_norm")]
    assert all(np.all(np.asarray(w) == 1) for w in norms)
    assert not np.any(np.asarray(params["layer3"]["moe"]["e_score_correction_bias"]))
    plain = get_model(dict(model_conf_of(conf), remat=False), 48)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 48)
    ours, theirs = (jax.jit(lambda p, x, m=m: m.apply({"params": p}, x))(params, ids)
                    for m in (model, plain))
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert ours.dtype == jnp.float32


def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The guide's test of the cut: 32 experts over 4 chips of 8, top-4,
    renormalised over the sum + 1e-6; there is no shared expert, so the four
    shares' sum is what the reference gives for the whole layer with all 32
    experts held."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 16))

    def layer(held, share):
        return ExpertLayer(32, held, share, 4, 8, 0, 1.0, True,
                           renorm_eps=family.RENORM_EPS, name="moe")

    whole = layer(32, 0)
    params = whole.init(jax.random.PRNGKey(6), x)["params"]
    params = dict(params, e_score_correction_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(7), (32,)))
    uncut = whole.apply({"params": params}, x)
    sizes = dict(top_k=4, routed_scale=1.0, renormalize=True, expert_share=0,
                 renorm_eps=1e-6)
    plain, _ = REFERENCE._experts(np.asarray(x[0]), params, sizes)
    _close(uncut[0], plain, 1e-5)
    shares = []
    for share in range(4):
        held = dict(params, **{name: params[name][share * 8:(share + 1) * 8]
                               for name in ("experts_gate", "experts_up", "experts_down")})
        shares.append(layer(8, share).apply({"params": held}, x)[0])
        part, _ = REFERENCE._experts(np.asarray(x[0]), held, dict(sizes, expert_share=share))
        _close(shares[-1], part, 1e-5)
    assert all(float(jnp.abs(part).max()) > 0 for part in shares)
    _close(sum(shares), plain, 1e-5)


# ------------------------------------------- the shipped conf, and refusals


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_the_shipped_conf_is_the_published_model_key_for_key():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-8B-A1B")
    model = shipped_conf()["model"]
    for key, value in row["config"].items():
        assert model[key] == value, key
    assert set(model) - set(row["config"]) == {
        "type", "remat", "router_bias_update_rate", "tie_word_embeddings"}
    assert row["head_dim"] is None and row["hidden_size"] // row["num_attention_heads"] == 64


def test_the_shipped_conf_builds_the_whole_model_and_the_cut():
    """No width is set here: the parameter count of the whole model and of
    one chip's cut from shapes alone (nothing is allocated), against the
    operations file's count and the published 8.3 B — the tied count."""
    conf = shipped_conf()
    assert not any(key in conf for key in ("layers_held", "experts_held", "ids_held"))
    assert conf["dataset"] == "tokens" and conf["optimizer"]["type"] == "adamw"
    kinds = conf["model"]["layer_types"]
    assert (len(kinds), kinds.count(CONV), kinds.count(FULL)) == (24, 18, 6)
    assert [i for i, kind in enumerate(kinds) if kind == FULL] == [2, 6, 10, 14, 18, 21]

    def shapes_of(conf, ids):
        module = get_model(model_conf_of(conf), ids)
        return module, jax.eval_shape(lambda: module.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 128), jnp.int32)))["params"]

    def count(shapes):
        return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))

    module, whole = shapes_of(conf, 65536)
    assert len([k for k in whole if k.startswith("layer")]) == 24 and "lm_head" not in whole
    sizes = FLOPS.model_from_conf(conf["model"])
    assert count(whole) == FLOPS.num_params(sizes) == 8_339_930_560
    assert FLOPS.num_params(dict(sizes, tied_head=False)) == 8_339_930_560 + 65536 * 2048
    s = module.sizes
    assert (s.heads, s.kv_heads, s.head_dim, s.taps) == (32, 8, 64, 3)
    assert s.rope_theta == 1e6 and s.eps == 1e-5 and s.bias_update_rate == 0.02
    assert (s.experts, s.top_k, s.expert_width, s.dense_width) == (32, 4, 1792, 7168)
    assert s.routed_scale == 1.0 and s.renormalize
    _, cut = shapes_of(dict(conf, layers_held=7, experts_held=8, ids_held=16384), 16384)
    assert count(cut) == 667_283_872 == FLOPS.num_params(
        dict(sizes, layers_held=7, experts_held=8, ids_held=16384))
    assert count(cut["layer1"]) == count(cut["layer2"]) == 60_827_648
    assert count(cut["layer3"]) == count(cut["layer7"]) == 98_635_936
    assert count(cut["layer4"]) == 104_933_408
    assert count(cut["layer1"]["conv"]) == 16_783_360
    assert count(cut["layer3"]["attn"]) == 10_485_888
    assert cut["layer1"]["conv"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert cut["layer1"]["conv"]["conv_kernel"].shape == (3, 2048)
    assert cut["layer3"]["attn"]["q_proj"]["kernel"].shape == (2048, 2048)
    assert cut["layer3"]["attn"]["v_proj"]["kernel"].shape == (2048, 512)
    assert cut["layer3"]["attn"]["q_norm"]["weight"].shape == (64,)
    assert cut["layer1"]["mlp"]["down_proj"]["kernel"].shape == (7168, 2048)
    assert cut["layer3"]["moe"]["experts_up"].shape == (8, 2048, 1792)
    assert cut["layer3"]["moe"]["router"].shape == (2048, 32)
    assert cut["embed_tokens"].shape == (16384, 2048)
    _, six = shapes_of(dict(conf, layers_held=6, experts_held=8, ids_held=16384), 16384)
    assert count(six) == 568_647_936                     # the issue's fall-back cut


@pytest.mark.parametrize("bad, says", [
    ({"layer_types": [CONV] * 7}, "layer_types has 7"),
    ({"layer_types": [CONV] * 7 + ["sliding_attention"]}, "unknown kinds"),
    ({"n_group": 2}, "grouped top-k"),
    ({"score_func": "softmax"}, "sigmoid router"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"num_key_value_heads": 3}, "no whole number"),
])
def test_what_the_family_file_has_not_written_down_is_refused(bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_conf_of(tiny_conf(**bad)), 48)


@pytest.mark.parametrize("bad", [
    {"layers_held": 9}, {"ids_held": 65}, {"experts_held": 5}])
def test_a_share_the_model_cannot_hold_is_refused(bad):
    conf = dict(tiny_conf(), **bad)
    with pytest.raises(ValueError):
        get_model(model_conf_of(conf), 48)
