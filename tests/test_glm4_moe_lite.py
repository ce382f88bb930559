"""GLM-4.7-Flash's parts against their plain forms, at a small size on the CPU
with seeded random inputs: rotary's defining property and its two layouts,
the low-rank query against the product of its two factors, the blocked head
and cross-entropy against the whole one, the multi-token-prediction term
(its mask, its absence in evaluation, its counts), and what neither token
model has written down, refused.  The model against its reference as a
whole: ``tests/benchmarks/test_bench_glm4_moe_lite.py``; through
``train_and_eval``: ``tests/test_token_training.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models.token_blocks import (
    ROUTING,
    STEP_STATS,
    MLAMixer,
    rotate_by_position,
)
from fast_autoaugment_tpu.ops.lm_head import blocked_next_token_sums
from fast_autoaugment_tpu.train.steps import _next_token_sums

REFERENCE = spec.load_module("references", "glm4_moe_lite")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def tiny_conf(**model):
    conf = {
        "model": {
            "type": "glm4_moe_lite", "remat": True, "first_k_dense_replace": 1,
            "hidden_size": 32, "intermediate_size": 48, "kv_lora_rank": 8,
            "q_lora_rank": 12, "moe_intermediate_size": 16, "norm_topk_prob": True,
            "topk_method": "noaux_tc", "num_attention_heads": 2, "n_group": 1,
            "n_routed_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 3,
            "n_shared_experts": 1, "num_nextn_predict_layers": 1,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-5,
            "rope_theta": 1e6, "routed_scaling_factor": 1.8, "topk_group": 1,
            "v_head_dim": 8, "vocab_size": 32, "router_bias_update_rate": 0.02},
        "dataset": "synthetic_tokens", "experts_held": 4}
    conf["model"].update(model)
    return conf


@pytest.fixture(scope="module")
def tiny():
    model = get_model(model_conf_of(tiny_conf()), 32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 32)
    params = model.init({"params": jax.random.PRNGKey(3)}, ids[:, :-1])["params"]
    return model, params, ids


# ---------------------------------------------------------------- rotary


def test_rotated_scores_depend_on_the_distance_between_positions_alone():
    """The same vectors at positions shifted by 16 give the same scores:
    ``rot(q)_i . rot(k)_j`` is a function of ``i - j``."""
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jnp.tile(jax.random.normal(keys[0], (1, 16, 3, 8)), (1, 2, 1, 1))
    k = jnp.tile(jax.random.normal(keys[1], (1, 16, 8)), (1, 2, 1))
    rq, rk = rotate_by_position(q, 100.0), rotate_by_position(k, 100.0)
    scores = jnp.einsum("bqhd,bkd->bhqk", rq, rk)
    _close(scores[..., 16:, 16:], scores[..., :16, :16], 1e-5)
    plain = jnp.einsum("bqhd,bkd->bhqk", q, k)
    assert float(jnp.abs(scores - plain).max()) > 0.1      # and it does rotate
    _close(jnp.diagonal(scores, axis1=-2, axis2=-1),
           jnp.diagonal(plain, axis1=-2, axis2=-1), 1e-5)  # distance zero: none
    # lengths kept: a rotation
    _close(jnp.linalg.norm(rq, axis=-1), jnp.linalg.norm(q, axis=-1), 1e-5)


def test_the_programs_layout_and_the_references_give_the_same_products():
    """The program leaves the pairs' members in two halves, the reference in
    place: another order of the same 8 numbers, for queries and keys alike."""
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(keys[0], (1, 24, 2, 8))
    k = jax.random.normal(keys[1], (1, 24, 8))
    ours = jnp.einsum("bqhd,bkd->bhqk", rotate_by_position(q, 1e6),
                      rotate_by_position(k, 1e6))
    plain = jnp.einsum("qhd,kd->hqk", REFERENCE._rotate(q[0], 1e6),
                       REFERENCE._rotate(k[0], 1e6))
    _close(ours[0], plain, 1e-5)
    in_place = REFERENCE._rotate(k[0], 1e6)
    _close(rotate_by_position(k, 1e6)[0],
           jnp.concatenate([in_place[:, 0::2], in_place[:, 1::2]], -1), 1e-6)


def test_a_mixer_without_rotary_is_another_function_and_kimis_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 16))
    sizes = dict(heads=2, nope_dim=8, pe_dim=4, v_dim=8, kv_rank=8, eps=1e-5)
    rotary = MLAMixer(**sizes, q_rank=6, rope_theta=1e4)
    params = rotary.init(jax.random.PRNGKey(5), x)["params"]
    assert sorted(params) == ["kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj",
                              "q_a_norm", "q_a_proj", "q_b_proj"]
    nope = MLAMixer(**sizes, q_rank=6)
    turned, still = rotary.apply({"params": params}, x), nope.apply({"params": params}, x)
    assert float(jnp.abs(turned - still).max()) > 1e-3 * float(jnp.abs(still).max())
    _close(turned[:, 0], still[:, 0], 1e-5)        # position 0 is turned by nothing
    whole = MLAMixer(**sizes)                      # Kimi Linear's: one product, no rotary
    assert sorted(whole.init(jax.random.PRNGKey(5), x)["params"]) == [
        "kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj", "q_proj"]


def test_the_low_rank_query_is_the_product_of_its_two_factors():
    """``q = RMSNorm(x W_qa) W_qb``: a token's query is the full product
    ``x (W_qa diag(w) W_qb)`` scaled by that token's one norm."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 16))
    mixer = MLAMixer(heads=2, nope_dim=8, pe_dim=4, v_dim=8, kv_rank=8, eps=1e-5,
                     q_rank=6, rope_theta=1e4)
    params = mixer.init(jax.random.PRNGKey(7), x)["params"]
    params["q_a_norm"]["weight"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(8), (6,))
    _, state = mixer.apply({"params": params}, x, capture_intermediates=True)
    (q,) = state["intermediates"]["q_b_proj"]["__call__"]
    assert q.shape == (2, 16, 2 * 12)
    latent = x @ params["q_a_proj"]["kernel"]
    assert latent.shape[-1] == 6                   # the rank
    scale = jax.lax.rsqrt(jnp.mean(latent * latent, -1, keepdims=True) + 1e-5)
    full = (params["q_a_proj"]["kernel"] * params["q_a_norm"]["weight"]
            ) @ params["q_b_proj"]["kernel"]       # [16, 24], of rank 6
    assert np.linalg.matrix_rank(np.asarray(full), tol=1e-5) == 6
    _close(q, scale * (x @ full), 1e-5)


# ------------------------------------------------- the blocked head and loss


@pytest.mark.parametrize("block", [8, 16, 64])
def test_blocked_head_and_loss_are_the_whole_ones(block):
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    x = jax.random.normal(keys[0], (2, 64, 16))
    kernel = jax.random.normal(keys[1], (16, 40))
    targets = jax.random.randint(keys[2], (2, 64), 0, 40)
    weight = (jax.random.uniform(keys[3], (2, 64)) > 0.3).astype(jnp.float32)

    def whole(x, kernel, weight):
        logits = x @ kernel
        picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - picked
        hit = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        return (nll * weight).sum(-1), (hit * weight).sum(-1)

    ours = blocked_next_token_sums(x, kernel, targets, weight, block=block)
    plain = whole(x, kernel, weight)
    _close(ours[0], plain[0], 1e-5)
    assert np.array_equal(np.asarray(ours[1]), np.asarray(plain[1]))
    grads = jax.grad(lambda x, k: blocked_next_token_sums(
        x, k, targets, weight, block=block)[0].sum(), argnums=(0, 1))(x, kernel)
    plain_grads = jax.grad(lambda x, k: whole(x, k, weight)[0].sum(),
                           argnums=(0, 1))(x, kernel)
    _close(grads[0], plain_grads[0], 1e-5)
    _close(grads[1], plain_grads[1], 1e-5)
    # unweighted it is the step body's own mean, times the length
    nll, hits = blocked_next_token_sums(x, kernel, targets, block=block)
    mean_nll, mean_hits = _next_token_sums(x @ kernel, targets)
    _close(nll / 64, mean_nll, 1e-5)
    _close(hits / 64, mean_hits, 1e-6)


def test_a_length_that_is_no_multiple_of_the_position_block_is_refused():
    with pytest.raises(ValueError, match="position block"):
        blocked_next_token_sums(jnp.zeros((1, 24, 4)), jnp.zeros((4, 8)),
                                jnp.zeros((1, 24), jnp.int32), block=16)


# ---------------------------------------------- multi-token prediction


def test_the_mtp_term_is_masked_at_the_last_position(tiny):
    """``loss_terms`` against both heads' whole logits: the main term over
    all T positions, the module's over the T - 1 with a target two ahead;
    the last position's output weighs nothing."""
    model, params, ids = tiny
    inputs, targets = ids[:, :-1], ids[:, 1:]
    (nll, top1, further), sown = model.apply(
        {"params": params}, inputs, targets, method="loss_terms",
        mutable=[STEP_STATS])
    logits, mtp_logits = model.apply({"params": params}, inputs, targets,
                                     method="logits_and_mtp_logits")

    def cross_entropy(logits, targets):
        picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return jax.nn.logsumexp(logits, -1) - picked

    _close(nll, cross_entropy(logits, targets).mean(-1), 1e-5)
    mtp, weight = further["mtp_loss"]
    assert weight == 0.3 and further["mtp_top1"][1] == 0.0
    # position i embedded targets[i] and predicts targets[i + 1]
    _close(mtp, cross_entropy(mtp_logits[:, :-1], targets[:, 1:]).mean(-1), 1e-5)
    hits = (jnp.argmax(mtp_logits[:, :-1], -1) == targets[:, 1:]).mean(-1)
    _close(further["mtp_top1"][0], hits, 1e-6)
    unmasked = cross_entropy(
        mtp_logits, jnp.concatenate([targets[:, 1:], targets[:, :1]], 1)).mean(-1)
    assert float(jnp.abs(unmasked - mtp).max()) > 1e-3
    stats = sown[STEP_STATS]
    assert float(stats["mtp_targets"][0]) == 2 * 31
    _close(stats["mtp_nll"][0], mtp.sum() * 31, 1e-5)
    assert sorted(k for k in stats if k.startswith(("layer", "mtp"))) == [
        "layer2", "layer3", "mtp", "mtp_nll", "mtp_targets"]


def test_the_module_is_not_computed_in_evaluation(tiny):
    model, params, ids = tiny
    inputs = ids[:, :-1]
    logits, sown = model.apply({"params": params}, inputs, train=False,
                               mutable=[ROUTING, STEP_STATS])
    assert sorted(sown[ROUTING]) == ["layer2", "layer3"]      # no module's block
    both, _ = model.apply({"params": params}, inputs, ids[:, 1:],
                          method="logits_and_mtp_logits", mutable=[ROUTING])
    _close(logits, both[0], 1e-6)
    text = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False)).lower(
        params, inputs).as_text(debug_info=True)
    assert "faa_mtp" not in text and "faa_mla" in text
    # a model without a module has no such parameters, terms or counts
    bare = get_model(model_conf_of(tiny_conf(num_nextn_predict_layers=0)), 32)
    bare_params = bare.init({"params": jax.random.PRNGKey(3)}, inputs)["params"]
    assert not any(k.startswith("mtp") for k in bare_params)
    (_, _, further), _ = bare.apply({"params": bare_params}, inputs, ids[:, 1:],
                                    method="loss_terms", mutable=[STEP_STATS])
    assert further == {}


def test_after_step_moves_every_router_and_counts_the_module(tiny):
    model, params, ids = tiny
    _, sown = model.apply({"params": params}, ids[:, :-1], ids[:, 1:],
                          method="loss_terms", mutable=[STEP_STATS])
    moved, counts = model.after_step(params, sown[STEP_STATS])
    for layer in ("layer2", "layer3", "mtp"):
        bias = np.asarray(moved[layer]["moe"]["e_score_correction_bias"])
        assert np.all(np.isin(np.abs(bias).round(6), np.float32([0.0, 0.02])))
        assert np.abs(bias).max() == pytest.approx(0.02)
        assert f"moe_assigned/{layer}" in counts and f"moe_largest/{layer}" in counts
    assert float(counts["mtp_targets"]) == 2 * 31 and float(counts["mtp_nll"]) > 0
    registry = telemetry.registry()
    before = registry.counter("faa_mtp_targets_total").value
    model.publish_counts({k: float(v) for k, v in counts.items()}, registry)
    assert registry.counter("faa_mtp_targets_total").value - before == 62
    gauges = registry.snapshot()["gauges"]
    assert gauges["faa_mtp_loss"] == pytest.approx(float(counts["mtp_nll"]) / 62)
    assert any(k.startswith("faa_moe_held_load_max_over_mean{") and 'layer="mtp"' in k
               for k in gauges)


# ------------------------------------------------- what is not written down


def _kimi_conf(**model):
    from tests.test_kimi_linear import _tiny_layer_conf

    conf = _tiny_layer_conf(4, 0)
    conf["model"].update(model)
    return conf


@pytest.mark.parametrize("family, bad, says", [
    ("kimi_linear", {"num_expert_group": 2}, "grouped top-k"),
    ("kimi_linear", {"topk_group": 2}, "grouped top-k"),
    ("kimi_linear", {"moe_router_activation_func": "softmax"}, "sigmoid router"),
    ("kimi_linear", {"moe_layer_freq": 2}, "moe_layer_freq"),
    ("kimi_linear", {"q_lora_rank": 12}, "glm4_moe_lite"),
    ("kimi_linear", {"mla_use_nope": False}, "glm4_moe_lite"),
    ("glm4_moe_lite", {"n_group": 2}, "grouped top-k"),
    ("glm4_moe_lite", {"topk_group": 2}, "grouped top-k"),
    ("glm4_moe_lite", {"topk_method": "greedy"}, "sigmoid router"),
    ("glm4_moe_lite", {"moe_layer_freq": 2}, "moe_layer_freq"),
    ("glm4_moe_lite", {"q_lora_rank": None}, "q_lora_rank"),
    ("glm4_moe_lite", {"partial_rotary_factor": 0.5}, "rotary"),
    ("glm4_moe_lite", {"rope_scaling": {"type": "yarn"}}, "rotary"),
    ("glm4_moe_lite", {"attention_bias": True}, "attention_bias"),
    ("glm4_moe_lite", {"num_nextn_predict_layers": 2}, "depth greater than one"),
    ("glm4_moe_lite", {"qk_rope_head_dim": 5}, "pairs"),
])
def test_what_no_token_model_has_written_down_is_refused(family, bad, says):
    conf = _kimi_conf(**bad) if family == "kimi_linear" else tiny_conf(**bad)
    with pytest.raises(ValueError, match=says):
        get_model(model_conf_of(conf), 32)


@pytest.mark.parametrize("bad", [
    {"experts_held": 3}, {"experts_held": 4, "expert_share": 2},
    {"layers_held": 4}, {"ids_held": 64}])
def test_a_share_the_model_cannot_hold_is_refused(bad):
    conf = tiny_conf(expert_share=bad.pop("expert_share", 0))
    conf.update(bad)
    with pytest.raises(ValueError):
        get_model(model_conf_of(conf), 32)
