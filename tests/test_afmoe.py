"""afmoe's parts against their plain forms, at a small size on the CPU with
seeded random inputs: the key span in both forms of ``ops/attention.py``
against an explicit mask (values and every argument's gradient; spans that
are and are not whole tiles; a span the sequence does not outgrow bit for
bit the whole causal past; the kernels interpreted, the code the chip
runs), rotary in both pairings, the shared grouped-query mixer's optional
parts, the whole cut model against the plain reference
(``benchmarks/references/afmoe.py``: logits, loss, every gradient leaf),
the eight points the configuration file lists under ``assumed`` each with
a control that the comparison refuses, one chip's shares adding up to the
uncut expert layer, the shipped conf against the published model, and what
the family file has not written down, refused.  Through ``train_and_eval``:
``tests/test_token_training.py``; the configuration's files:
``tests/benchmarks/test_bench_afmoe.py``."""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks.harness import spec
from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import afmoe as family
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models.token_blocks import (
    STEP_STATS,
    ExpertLayer,
    GQAMixer,
    rotate_by_position,
)
from fast_autoaugment_tpu.ops import attention
from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = spec.load_module("references", "afmoe")
FLOPS = spec.load_module("flops", "afmoe")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WINDOW, FULL = family.WINDOW, family.FULL


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


# ------------------------------------------------------------ the key span

#: the tests' own small heads (the XLA form takes them; blocks of 16), and
#: heads of whole lanes over five tiles of 128 (the fused kernels,
#: interpreted here)
SMALL = dict(length=64, heads=3, dim=8, vdim=4, block=16)
NATIVE = dict(length=640, heads=1, dim=128, vdim=128)
#: spans by what they are to a tile (16 or 128): under one, one, between,
#: whole tiles, one key over, the query's own key alone, two keys
SPANS = {"small": (16, 32, 10, 1, 2, 17, 40), "native": (128, 256, 100, 1, 129, 300)}


def _cores_inputs(length, heads, dim, vdim, **_):
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    return (jax.random.normal(keys[0], (1, length, heads, dim)),
            jax.random.normal(keys[1], (1, length, heads, dim)),
            jax.random.normal(keys[2], (1, length, heads, vdim)),
            jax.random.normal(keys[3], (1, length, heads, vdim)))


def _explicit(q, k, v, scale, window):
    """The whole ``[T, T]`` score matrix with the span as a mask."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    ahead = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    seen = (ahead >= 0) & (ahead < (window or q.shape[1]))
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _value_and_grads(fn, q, k, v, cotangent):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(cotangent * fn(q, k, v)), argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("shape, window", [
    (name, window) for name in ("small", "native") for window in SPANS[name]])
def test_a_key_span_is_the_explicit_mask_forward_and_backward(shape, window):
    sizes = SMALL if shape == "small" else NATIVE
    q, k, v, cotangent = _cores_inputs(**sizes)
    scale = sizes["dim"] ** -0.5
    kwargs = {"block": sizes["block"]} if "block" in sizes else {}
    before = telemetry.registry().counters_snapshot()
    ours, ours_grads = _value_and_grads(
        lambda q, k, v: blocked_causal_attention(q, k, v, scale=scale, window=window,
                                                 **kwargs), q, k, v, cotangent)
    after = telemetry.registry().counters_snapshot()
    form = "blocked_xla" if shape == "small" else "fused"
    key = f'faa_attention_cores_traced_total{{form="{form}",span="{window}"}}'
    assert after[key] > before.get(key, 0)
    whole, whole_grads = _value_and_grads(
        lambda q, k, v: _explicit(q, k, v, scale, window), q, k, v, cotangent)
    assert float(ours) == pytest.approx(float(whole), rel=1e-5, abs=1e-4)
    for mine, theirs in zip(ours_grads, whole_grads):
        # a span of one key has no gradient in q and k: rounding alone
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() <= 1e-5 * max(
            np.abs(np.asarray(theirs)).max(), 1.0)


@pytest.mark.parametrize("shape", ["small", "native"])
@pytest.mark.parametrize("beyond", [0, 1, 1000])
def test_a_span_the_sequence_does_not_outgrow_is_no_span_bit_for_bit(shape, beyond):
    sizes = SMALL if shape == "small" else NATIVE
    q, k, v, cotangent = _cores_inputs(**sizes)
    kwargs = {"block": sizes["block"]} if "block" in sizes else {}

    def run(window):
        return _value_and_grads(lambda q, k, v: blocked_causal_attention(
            q, k, v, scale=0.2, window=window, **kwargs), q, k, v, cotangent)

    plain, plain_grads = run(None)
    spanned, spanned_grads = run(sizes["length"] + beyond)
    assert np.array_equal(np.asarray(plain), np.asarray(spanned))
    for a, b in zip(plain_grads, spanned_grads):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", ["small", "native"])
def test_no_span_lowers_to_the_program_a_call_without_the_argument_lowers_to(
        shape, monkeypatch):
    """The three token cells that have no window lower what they lowered
    before the argument: without it, with None and with a span the sequence
    does not outgrow, forward and backward, the text is one text (against
    the tree before the argument: PR 46's scratch comparison, CHANGES.md).
    And the names the fused forward rule gives its two products (PR 47) are
    the identity there: with no policy round the call to keep them, the
    text is the text without them, but for the serial numbers MLIR's symbol
    table gives private functions of one name (``@_pad_53``: a count of the
    renamings before it, which the names' equations move by one)."""
    sizes = SMALL if shape == "small" else NATIVE
    q, k, v, cotangent = _cores_inputs(**sizes)
    kwargs = {"block": sizes["block"]} if "block" in sizes else {}

    def text(**span):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(cotangent * blocked_causal_attention(
            q, k, v, scale=0.2, **kwargs, **span)), argnums=(0, 1, 2))).lower(q, k, v).as_text()

    assert text() == text(window=None) == text(window=sizes["length"])
    assert text() != text(window=sizes["length"] - 1)
    named = text()
    monkeypatch.setattr(attention, "checkpoint_name", lambda value, name: value)
    unnumbered = lambda text: re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    assert unnumbered(named) == unnumbered(text())


def test_a_span_under_one_key_is_refused():
    q, k, v, _ = _cores_inputs(**SMALL)
    with pytest.raises(ValueError, match="window=0"):
        blocked_causal_attention(q, k, v, scale=1.0, window=0)


@pytest.mark.parametrize("length, tile, window, visited", [
    (16384, 512, 2048, 150),      # the cell's window layers: 150 of 528
    (16384, 512, None, 528),
    (16384, 512, 2049, 150),      # one key over still ends in the same tile
    (16384, 512, 2050, 177),      # two over: one more tile a query tile
    (16384, 512, 1, 32),          # the diagonal tiles alone
    (640, 128, 300, 14),          # 300 keys reach three tiles back
])
def test_the_key_tiles_the_loops_meet(length, tile, window, visited):
    count = length // tile
    assert attention.key_tiles(length, tile, window) == (visited, count * (count + 1) // 2)
    if window is not None:
        # by brute force: a query tile meets a key tile iff some pair is seen
        met = sum(1 for i in range(count) for j in range(i + 1)
                  if (i - j) * tile - (tile - 1) < window)
        assert met == visited


def test_the_key_tile_counter_tells_skipping_from_masking():
    """The fused form counts the tiles its loops meet, the XLA form every
    tile up to the end of a scan's stretch: it masks a span and skips
    nothing."""
    def rise(sizes, window, **kwargs):
        before = telemetry.registry().counters_snapshot()
        q, k, v, _ = _cores_inputs(**sizes)
        jax.eval_shape(lambda q, k, v: blocked_causal_attention(
            q, k, v, scale=1.0, window=window, **kwargs), q, k, v)
        after = telemetry.registry().counters_snapshot()
        return {kind: after[key] - before.get(key, 0.0) for kind in ("visited", "causal")
                for key in [f'faa_attention_key_tiles_total{{kind="{kind}",'
                            f'span="{window or "none"}"}}']}

    assert rise(NATIVE, 128) == {"visited": 9.0, "causal": 15.0}
    assert rise(NATIVE, None) == {"visited": 15.0, "causal": 15.0}
    # four blocks of 16 in four scans: every block meets its scan's keys
    assert rise(SMALL, 16, block=16) == {"visited": 10.0, "causal": 10.0}
    masked = rise(SMALL, 16, block=8, spans=2)            # eight blocks in two scans
    assert masked == {"visited": 48.0, "causal": 36.0}


# ------------------------------------------------ rotary, in both pairings


def test_rotary_in_halves_is_the_rotation_of_the_pairs_i_and_i_plus_half():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    turned = np.asarray(rotate_by_position(x, 100.0, "halves"))
    x = np.asarray(x)
    for t in (0, 5, 11):
        for i in range(4):
            angle = t * 100.0 ** (-2 * i / 8)
            a, b = x[:, t, :, i], x[:, t, :, i + 4]
            np.testing.assert_allclose(
                turned[:, t, :, i], a * math.cos(angle) - b * math.sin(angle), atol=1e-5)
            np.testing.assert_allclose(
                turned[:, t, :, i + 4], a * math.sin(angle) + b * math.cos(angle), atol=1e-5)


def test_the_two_pairings_turn_the_same_pairs_laid_out_otherwise():
    """``interleaved`` on channels ``(a0 b0 a1 b1 ..)`` is ``halves`` on
    ``(a0 a1 .. b0 b1 ..)``, and the default is today's ``interleaved``."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, 8))
    apart = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    assert np.array_equal(np.asarray(rotate_by_position(x, 50.0)),
                          np.asarray(rotate_by_position(x, 50.0, "interleaved")))
    np.testing.assert_allclose(np.asarray(rotate_by_position(x, 50.0)),
                               np.asarray(rotate_by_position(apart, 50.0, "halves")),
                               atol=1e-6)
    assert not np.allclose(np.asarray(rotate_by_position(x, 50.0)),
                           np.asarray(rotate_by_position(x, 50.0, "halves")), atol=1e-3)
    with pytest.raises(ValueError, match="pairs="):
        rotate_by_position(x, 50.0, "quarters")


def test_scores_of_rotated_queries_and_keys_depend_on_the_distance_alone():
    q = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16)),
                         (1, 20, 1, 16))
    k = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 16)),
                         (1, 20, 1, 16))
    q, k = (rotate_by_position(a, 10000.0, "halves") for a in (q, k))
    scores = np.asarray(jnp.einsum("bqhd,bkhd->qk", q, k))
    for distance in (0, 3, 7):
        diagonal = np.diagonal(scores, -distance)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=1e-4, atol=1e-4)


# ------------------------------------------------- the shared mixer's parts


def test_the_bare_mixer_holds_four_matrices_and_each_part_adds_its_own():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 32))

    def tree(**parts):
        mixer = GQAMixer(4, 2, 8, **parts)
        return jax.tree.map(lambda a: a.shape, mixer.init(jax.random.PRNGKey(0), x)["params"])

    assert sorted(tree()) == ["k_proj", "o_proj", "q_proj", "v_proj"]
    assert sorted(tree(rope_theta=100.0, window=4)) == sorted(tree())
    assert set(tree(gated=True)) - set(tree()) == {"gate_proj"}
    normed = tree(qk_norm_eps=1e-5)
    assert set(normed) - set(tree()) == {"q_norm", "k_norm"}
    assert normed["q_norm"]["weight"] == normed["k_norm"]["weight"] == (8,)


# ----------------------------------------- the cut model and its reference

#: every width cut, the structure kept: eight blocks of which six are held
#: (two dense, four expert layers; five window, one full), 16 experts of
#: which 4 are held, top-2, 2 query heads a key-value head, a span of 24
TINY_MODEL = {
    "type": "afmoe", "hidden_size": 32, "rms_norm_eps": 1e-5, "mup_enabled": True,
    "num_hidden_layers": 8, "layer_types": [WINDOW, WINDOW, WINDOW, FULL] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 10000, "sliding_window": 24, "num_dense_layers": 2,
    "intermediate_size": 48, "num_experts": 16, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "num_shared_experts": 1, "route_scale": 2.826,
    "route_norm": True, "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "load_balance_coeff": 0.001, "vocab_size": 64}


def tiny_conf(**model):
    return {"model": dict(TINY_MODEL, **model), "dataset": "synthetic_tokens",
            "layers_held": 6, "experts_held": 4, "ids_held": 48}


def tiny_sizes(conf, length):
    return dict(FLOPS.model_from_conf(conf["model"]), expert_share=0, seq_len=length,
                **{k: conf[k] for k in ("layers_held", "experts_held", "ids_held")})


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


def test_the_cut_is_the_first_six_blocks_two_dense_and_four_of_experts(tiny):
    _, _, params, _, sizes = tiny
    kinds = {name: sorted(set(block) & {"mlp", "moe"}) for name, block in params.items()
             if name.startswith("layer")}
    assert kinds == {"layer1": ["mlp"], "layer2": ["mlp"], "layer3": ["moe"],
                     "layer4": ["moe"], "layer5": ["moe"], "layer6": ["moe"]}
    for block in (params[name] for name in kinds):
        assert {"input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
                "attn"} <= set(block)                       # four norms a block
        assert sorted(block["attn"]) == ["gate_proj", "k_norm", "k_proj", "o_proj",
                                         "q_norm", "q_proj", "v_proj"]
    assert params["layer1"]["attn"]["k_proj"]["kernel"].shape == (32, 2 * 8)
    assert params["layer1"]["attn"]["gate_proj"]["kernel"].shape == (32, 4 * 8)
    assert params["layer1"]["attn"]["q_norm"]["weight"].shape == (8,)
    assert sorted(params["layer3"]["moe"]) == [
        "e_score_correction_bias", "experts_down", "experts_gate", "experts_up",
        "router", "shared_experts"]
    assert params["layer3"]["moe"]["experts_gate"].shape == (4, 32, 16)
    assert params["layer3"]["moe"]["router"].shape == (32, 16)
    assert sum(p.size for p in jax.tree.leaves(params)) == FLOPS.num_params(sizes)


def test_the_whole_cut_model_is_the_reference_logits_loss_and_every_gradient(tiny):
    """Float32 under ``highest`` on both sides, 64 tokens under a span of
    24: rounding alone is left, 1e-5 of the largest logit; the loss to
    1e-6; every gradient leaf to 2e-4 of its largest element."""
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


#: the configuration file's ``assumed`` points that are the forward pass's,
#: each as the change of the reference's `model` dict that leaves it out
ASSUMED_CONTROLS = {
    "1_output_gate": {"control": "no_gate"},
    "2_norms_a_head": {"control": "no_qk_norm"},
    "3_rotary_on_window_layers_alone": {"control": "rotary_everywhere"},
    "3_rotary_at_all": {"control": "no_rotary"},
    "3_pairs_i_and_i_plus_half": {"control": "interleaved_pairs"},
    "4_four_norms_a_block": {"control": "two_norms"},
    "5_embedding_times_sqrt_hidden": {"embed_scale": 1.0},
    "7_span_holds_the_querys_own_key": {"window": 25},
    "7_span_is_no_shorter": {"window": 23},
    "7_window_layers_have_a_span": {"window": 64},
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
    other = REFERENCE.forward(params, {}, ids[:, :-1],
                              dict(sizes, **ASSUMED_CONTROLS[point]))
    assert _gap(logits, other) > 1e-3, point
    assert abs(_next_token_loss(other, ids) - loss) > 1e-5 * loss, point


def test_assumed_6_the_bias_moves_by_load_balance_coeff_a_step(tiny):
    """``load_balance_coeff`` 0.001 as the step of the rule that moves the
    routers' correction bias: after a step every expert layer's bias is
    ``balance_bias`` at that rate, and at the other token configurations'
    0.02 it is another."""
    from fast_autoaugment_tpu.ops import moe

    _, model, params, ids, _ = tiny
    assert model.sizes.bias_update_rate == 0.001
    # this repo's key, where a conf sets it, goes before the published one
    assert get_model(model_conf_of(tiny_conf(router_bias_update_rate=0.02)),
                     48).sizes.bias_update_rate == 0.02
    _, sown = model.apply({"params": params}, ids[:, :-1], mutable=[STEP_STATS])
    stats = sown[STEP_STATS]
    assert sorted(stats) == ["layer3", "layer4", "layer5", "layer6"]
    moved, counts = model.after_step(params, stats)
    for layer, entry in stats.items():
        (load,) = entry["moe"]["load"]
        assert load.shape == (16,) and int(load.sum()) == 2 * 64 * 2
        before = params[layer]["moe"]["e_score_correction_bias"]
        after = np.asarray(moved[layer]["moe"]["e_score_correction_bias"])
        np.testing.assert_array_equal(after, np.asarray(moe.balance_bias(before, load, 0.001)))
        assert np.abs(after - np.asarray(before)).max() == pytest.approx(0.001, rel=1e-3)
        assert not np.allclose(after, np.asarray(moe.balance_bias(before, load, 0.02)),
                               atol=1e-3)
        assert float(counts[f"moe_assigned/{layer}"]) == int(load[:4].sum())
    assert moved["layer1"] is params["layer1"]


def test_assumed_8_initial_values_precision_and_remat():
    """The repo's normal(0.02) for every matrix, the embedding and the head,
    every norm's weight 1, the correction bias 0; float32 parameters and
    activations; every block under ``nn.remat`` (a model that is not is
    the same function)."""
    conf = tiny_conf(hidden_size=256, intermediate_size=64)
    model = get_model(model_conf_of(conf), 48)
    assert model.remat and model.dtype == jnp.float32
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.jit(model.init)({"params": jax.random.PRNGKey(0)}, ids)["params"]
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype(jnp.float32)}
    for name in ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"):
        assert np.asarray(params["layer1"]["attn"][name]["kernel"]).std() == (
            pytest.approx(0.02, rel=0.1)), name
    assert np.asarray(params["embed_tokens"]).std() == pytest.approx(0.02, rel=0.1)
    assert np.asarray(params["layer3"]["moe"]["router"]).std() == pytest.approx(0.02, rel=0.1)
    norms = [params["norm"]["weight"]] + [
        params["layer4"][name]["weight"] for name in (
            "input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")] + [
        params["layer4"]["attn"][name]["weight"] for name in ("q_norm", "k_norm")]
    assert all(np.all(np.asarray(w) == 1) for w in norms)
    assert not np.any(np.asarray(params["layer3"]["moe"]["e_score_correction_bias"]))
    plain = get_model(dict(model_conf_of(conf), remat=False), 48)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 48)
    ours, theirs = (jax.jit(lambda p, x, m=m: m.apply({"params": p}, x))(params, ids)
                    for m in (model, plain))
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert ours.dtype == jnp.float32


def test_sixteen_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The guide's test of the cut: 128 experts over 16 chips of 8, top-8 x
    2.826; every share computes its own experts' part and the shared expert
    alike, so the shares' sum less fifteen shared experts is what the
    reference gives for the whole layer with all 128 experts held."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 16))

    def layer(held, share):
        return ExpertLayer(128, held, share, 8, 8, 1, 2.826, True, name="moe")

    whole = layer(128, 0)
    params = whole.init(jax.random.PRNGKey(6), x)["params"]
    params = dict(params, e_score_correction_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(7), (128,)))
    uncut = whole.apply({"params": params}, x)
    sizes = dict(top_k=8, routed_scale=2.826, renormalize=True, expert_share=0)
    plain, _ = REFERENCE._experts(np.asarray(x[0]), params, sizes)
    _close(uncut[0], plain, 1e-5)
    shared = plain - REFERENCE._experts(
        np.asarray(x[0]), {k: v for k, v in params.items() if k != "shared_experts"},
        sizes)[0]
    shares = []
    for share in range(16):
        held = dict(params, **{name: params[name][share * 8:(share + 1) * 8]
                               for name in ("experts_gate", "experts_up", "experts_down")})
        shares.append(layer(8, share).apply({"params": held}, x)[0])
    assert all(float(jnp.abs(part - shared).max()) > 0 for part in shares)
    _close(sum(shares) - 15 * shared, plain, 1e-5)


# ------------------------------------------- the shipped conf, and refusals


def shipped_conf() -> dict:
    with open(os.path.join(REPO, "confs", "trinity_mini.yaml")) as fh:
        return yaml.safe_load(fh)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog on this machine")
def test_the_shipped_conf_is_the_published_model_key_for_key():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Trinity-Mini")
    model = shipped_conf()["model"]
    for key, value in row["config"].items():
        assert model[key] == value, key
    assert set(model) - set(row["config"]) == {"type", "remat", "router_bias_update_rate"}


def test_the_shipped_conf_builds_the_whole_model_and_the_cut():
    """No width is set here: the parameter count of the whole model and of
    one chip's cut from shapes alone (nothing is allocated), against the
    operations file's count and the catalog's 26B."""
    conf = shipped_conf()
    assert not any(key in conf for key in ("layers_held", "experts_held", "ids_held"))
    assert conf["dataset"] == "tokens" and conf["optimizer"]["type"] == "adamw"
    kinds = conf["model"]["layer_types"]
    assert (len(kinds), kinds.count(WINDOW), kinds.count(FULL)) == (32, 24, 8)
    assert all((kind == FULL) == (index % 4 == 3) for index, kind in enumerate(kinds))

    def shapes_of(conf, ids):
        module = get_model(model_conf_of(conf), ids)
        return module, jax.eval_shape(lambda: module.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 128), jnp.int32)))["params"]

    def count(shapes):
        return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))

    module, whole = shapes_of(conf, 200192)
    assert len([k for k in whole if k.startswith("layer")]) == 32
    assert 26.0e9 < count(whole) < 26.2e9
    sizes = FLOPS.model_from_conf(conf["model"])
    assert count(whole) == FLOPS.num_params(sizes)
    assert module.sizes.window == 2048 and module.sizes.rope_theta == 10000.0
    assert module.sizes.embed_scale == pytest.approx(math.sqrt(2048))
    # the repo's key over the published load_balance_coeff 0.001 (assumed 6)
    assert module.sizes.bias_update_rate == 0.02 and conf["model"]["load_balance_coeff"] == 0.001
    _, cut = shapes_of(dict(conf, layers_held=6, experts_held=8, ids_held=25024), 25024)
    assert count(cut) == 569_167_360 + 4 * 128 == FLOPS.num_params(
        dict(sizes, layers_held=6, experts_held=8, ids_held=25024))
    assert count(cut["layer1"]) == count(cut["layer2"]) == 65_020_160
    assert count(cut["layer3"]) == 84_156_672 + 128      # + the correction bias
    assert count(cut["layer1"]["attn"]) == 27_263_232
    assert cut["layer1"]["attn"]["q_proj"]["kernel"].shape == (2048, 4096)
    assert cut["layer1"]["attn"]["v_proj"]["kernel"].shape == (2048, 512)
    assert cut["layer1"]["attn"]["gate_proj"]["kernel"].shape == (2048, 4096)
    assert cut["layer1"]["mlp"]["down_proj"]["kernel"].shape == (6144, 2048)
    assert cut["layer3"]["moe"]["experts_up"].shape == (8, 2048, 1024)
    assert cut["layer3"]["moe"]["router"].shape == (2048, 128)
    assert cut["layer3"]["moe"]["shared_experts"]["down_proj"]["kernel"].shape == (1024, 2048)
    _, five = shapes_of(dict(conf, layers_held=5, experts_held=8, ids_held=25024), 25024)
    assert count(five) == count(cut) - 84_156_672 - 128  # a cut of five blocks


@pytest.mark.parametrize("bad, says", [
    ({"layer_types": [WINDOW] * 7}, "layer_types has 7"),
    ({"layer_types": [WINDOW] * 7 + ["chunked_attention"]}, "unknown kinds"),
    ({"n_group": 2}, "grouped top-k"),
    ({"num_expert_groups": 2}, "grouped top-k"),
    ({"score_func": "softmax"}, "sigmoid router"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
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


def test_a_model_without_the_multiplier_embeds_its_rows_as_they_are():
    """``mup_enabled: false`` is the other published value of the key: no
    multiplier, and the same tree."""
    conf = tiny_conf(mup_enabled=False)
    model = get_model(model_conf_of(conf), 48)
    assert model.sizes.embed_scale == 1.0
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 17), 0, 48)
    params = model.init({"params": jax.random.PRNGKey(3)}, ids[:, :-1])["params"]
    logits = model.apply({"params": params}, ids[:, :-1])
    sizes = tiny_sizes(conf, 16)
    assert sizes["embed_scale"] == 1.0
    assert _gap(np.asarray(logits), REFERENCE.forward(params, {}, ids[:, :-1], sizes)) <= 1e-5
