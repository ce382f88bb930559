"""``train_and_eval`` on a token data set: ids in, a next-token loss, no
policy, AdamW, through the device cache and through the host feed; the
loss falls, a preempted run resumes bit-equal, an ``only_eval`` restore
takes the checkpoint, the counters say what was trained — and the model
from the shipped conf is the published one.

A tiny Kimi Linear (every width cut, the structure kept: KDA + dense,
KDA, KDA, MLA, KDA; 4 of 16 experts held) on ``synthetic_tokens``: 32
sequences of 32 tokens over 64 ids, batch 4 on one CPU device, 8 steps an
epoch.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from fast_autoaugment_tpu.core import compilecache, resilience, telemetry
from fast_autoaugment_tpu.core.checkpoint import read_metadata
from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.data.datasets import (
    is_token_dataset,
    load_dataset,
)
from fast_autoaugment_tpu.data.pipeline import DeviceCache, resolve_device_cache
from fast_autoaugment_tpu.models import get_model, model_conf_of, num_class
from fast_autoaugment_tpu.ops.optim import build_optimizer
from fast_autoaugment_tpu.parallel.mesh import make_mesh
from fast_autoaugment_tpu.train.steps import (
    COUNT_PREFIX,
    create_train_state,
    make_token_step_body,
    slice_state,
    stack_states,
)
from fast_autoaugment_tpu.train.trainer import train_and_eval

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, STEPS, LENGTH = 4, 8, 32


def shipped_conf() -> dict:
    with open(os.path.join(REPO, "confs", "kimi_linear_48b_a3b.yaml")) as fh:
        return yaml.safe_load(fh)


def _conf(epochs=2, **top):
    conf = shipped_conf()
    conf["model"].update(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8,
        moe_intermediate_size=16, num_attention_heads=2, num_experts=16,
        num_experts_per_token=4, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, vocab_size=64)
    conf["model"]["linear_attn_config"].update(head_dim=8, num_heads=2)
    conf.update(layers_held=5, experts_held=4, dataset="synthetic_tokens",
                batch=BATCH, epoch=epochs, lr=0.02, **top)
    return Config(conf)


def _train(save_path, conf=None, **kw):
    return train_and_eval(
        conf or _conf(), "/nonexistent", save_path=save_path,
        mesh=make_mesh(jax.devices()[:1]), seed=3, evaluation_interval=1, **kw)


def _digest(path):
    meta = read_metadata(path)
    assert meta and "digest" in meta
    return meta["digest"]


@pytest.fixture(scope="module")
def unbroken(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("unbroken") / "full.msgpack")
    tokens = telemetry.registry().counter("faa_tokens_total")
    before = tokens.value
    # the seam's labels are the process's: under ``--dist loadfile`` a
    # host-fed run of another file may have left its ``train_step`` here
    compilecache._reset_stats_for_tests()
    result = _train(path)
    return path, result, tokens.value - before


def test_token_data_set_rides_the_image_containers():
    assert is_token_dataset("tokens") and is_token_dataset("synthetic_tokens")
    assert not is_token_dataset("cifar10") and not is_token_dataset("synthetic")
    train, test = load_dataset("synthetic_tokens", "/nonexistent")
    assert train.tokens and not train.lazy
    assert train.images.shape == (32, LENGTH + 1) and train.images.dtype == np.int32
    assert train.labels.shape == (32,) and test.images.shape == (8, LENGTH + 1)
    assert train.num_classes <= 64
    assert resolve_device_cache("auto", train)           # eager: cached by itself
    cache = DeviceCache(train, make_mesh(jax.devices()[:1]))
    assert cache.images.shape == (32, LENGTH + 1) and cache.images.dtype == jnp.int32
    with pytest.raises(ValueError, match="token data set"):
        num_class("synthetic_tokens")


def test_tokens_npy_files_are_read_and_bad_ones_refused(tmp_path):
    os.makedirs(tmp_path / "tokens")
    ids = np.arange(40, dtype=np.int64).reshape(4, 10) % 7
    np.save(tmp_path / "tokens" / "train.npy", ids)
    np.save(tmp_path / "tokens" / "test.npy", ids[:2])
    train, test = load_dataset("tokens", str(tmp_path))
    assert train.images.dtype == np.int32 and train.num_classes == 7
    assert len(train) == 4 and len(test) == 2
    np.save(tmp_path / "tokens" / "test.npy", -ids[:2] - 1)
    with pytest.raises(ValueError, match="non-negative"):
        load_dataset("tokens", str(tmp_path))


def test_loss_falls_through_the_device_cache(unbroken):
    _, result, tokens = unbroken
    assert result["steps"] == 2 * STEPS and result["epoch"] == 2
    assert math.isfinite(result["loss_train"]) and math.isfinite(result["loss_test"])
    # the second epoch's mean under ln(64), which no learning reads
    assert result["loss_train"] < math.log(64) - 0.1
    assert 0.0 <= result["top1_train"] <= 1.0 and "top5_train" not in result
    assert tokens == 2 * STEPS * BATCH * LENGTH
    compiled = result["compile_cache"]["labels"]
    assert "train_dispatch" in compiled and "train_step" not in compiled


def test_the_expert_layers_counters_are_published_at_the_boundary(unbroken):
    text = telemetry.registry().prometheus_text()
    snapshot = telemetry.registry().counters_snapshot()
    for layer in ("layer2", "layer3", "layer4", "layer5"):
        key = f'faa_moe_assignments_total{{held="true",layer="{layer}"}}'
        # 128 tokens x top-4 a step, 4 of 16 experts held: some, not all
        assert 0 < snapshot[key] < snapshot["faa_tokens_total"] * 4
        assert f'faa_moe_held_load_max_over_mean{{layer="{layer}"}}' in text
    assert "layer1" not in "".join(k for k in snapshot if k.startswith("faa_moe"))
    loads = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
             if line.startswith("faa_moe_held_load_max_over_mean{")]
    assert loads and all(1.0 <= v <= 4.0 for v in loads)


@pytest.mark.parametrize("k", [3, 11])
def test_preempted_at_dispatch_k_and_resumed_equals_the_unbroken_run(
        unbroken, tmp_path, k):
    full, result, _ = unbroken
    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_k():
        beats.append(1)
        if len(beats) == k:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(part, heartbeat=stop_at_k)
    finally:
        resilience.clear_preemption()
    meta = read_metadata(part)
    # a beat after every dispatch and one more at each epoch boundary
    step = k - k // (STEPS + 1)
    assert meta["preempted"] is True and meta["step"] == step
    assert meta["in_epoch"]["pos"] == step % STEPS
    sums = meta["in_epoch"]["sums"]
    assert sums["num"] == (step % STEPS) * BATCH
    assert sums[f"{COUNT_PREFIX}tokens"] == (step % STEPS) * BATCH * LENGTH

    tokens = telemetry.registry().counter("faa_tokens_total")
    before = tokens.value
    resumed = _train(part)
    # what the preempted run had counted is not counted again
    assert tokens.value - before == (2 * STEPS - step) * BATCH * LENGTH
    assert resumed["steps"] == 2 * STEPS
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "top1_train", "loss_test"):
        assert resumed[key] == result[key], key


def test_an_only_eval_restore_takes_the_checkpoint(unbroken):
    full, result, _ = unbroken
    evaluated = _train(full, only_eval=True)
    assert evaluated["steps"] == 2 * STEPS
    assert evaluated["loss_test"] == result["loss_test"]
    assert evaluated["num_test"] == 8


def test_host_feed_trains_the_same_model(unbroken, tmp_path):
    """``device_cache=off``: one batch a dispatch through ``train_step``;
    the cached path with ``steps_per_dispatch`` 1 is pinned to it."""
    full, result, _ = unbroken
    path = str(tmp_path / "hostfed.msgpack")
    fed = _train(path, device_cache="off")
    assert "train_step" in fed["compile_cache"]["labels"]
    assert _digest(path) == _digest(full)
    assert fed["loss_train"] == result["loss_train"]


def test_a_policy_named_for_a_token_data_set_is_refused(tmp_path):
    with pytest.raises(ValueError, match="augmentation policy"):
        _train(str(tmp_path / "m.msgpack"), conf=_conf(aug="fa_reduced_cifar10"))


def test_ids_the_model_does_not_hold_are_refused(tmp_path):
    with pytest.raises(ValueError, match="holds ids up to"):
        _train(str(tmp_path / "m.msgpack"), conf=_conf(ids_held=16))


def test_ema_and_fold_stacking_take_an_empty_batch_stats():
    conf = _conf()
    model = get_model(model_conf_of(conf), 64)
    optimizer = build_optimizer(conf["optimizer"], lambda step: 1e-3)
    ids = jnp.asarray(load_dataset("synthetic_tokens", "")[0].images[:BATCH])
    state = create_train_state(model, optimizer, jax.random.PRNGKey(0),
                               ids[:, :-1], use_ema=True, jit_init=True)
    assert state.batch_stats == {} and state.ema["batch_stats"] == {}
    body = jax.jit(make_token_step_body(model, optimizer, ema_mu=0.99))
    new, sums = body(state, ids, jnp.zeros(BATCH, jnp.int32), None, None)
    assert int(new.step) == 1 and float(sums["num"]) == BATCH
    assert float(sums[f"{COUNT_PREFIX}tokens"]) == BATCH * LENGTH
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         new.ema["params"], state.ema["params"])
    assert max(jax.tree.leaves(moved)) > 0                # the shadow follows
    stacked = stack_states([state, new])
    assert stacked.batch_stats == {} and stacked.step.shape == (2,)
    back = slice_state(stacked, 1)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     back.params, new.params))


def test_the_step_moves_the_routers_bias_by_the_balancing_rule_and_nothing_else_does():
    """One step of the token body: every expert layer's correction bias
    moves by the conf's rate towards the mean load of *that step's*
    routing (``ops/moe.py::balance_bias`` on what the layer sowed), the
    optimizer leaves it alone (no gradient, no decay), and with the rate
    at zero it stays where it was."""
    from fast_autoaugment_tpu.models.kimi_linear import STEP_STATS
    from fast_autoaugment_tpu.ops import moe

    ids = jnp.asarray(load_dataset("synthetic_tokens", "")[0].images[:BATCH])

    def one_step(rate):
        conf = _conf()
        model = get_model(dict(model_conf_of(conf), router_bias_update_rate=rate), 64)
        optimizer = build_optimizer(conf["optimizer"], lambda step: 1e-3)
        state = create_train_state(model, optimizer, jax.random.PRNGKey(0),
                                   ids[:, :-1], use_ema=False, jit_init=True)
        _, sown = model.apply({"params": state.params}, ids[:, :-1], train=True,
                              mutable=[STEP_STATS])
        new, sums = jax.jit(make_token_step_body(model, optimizer))(
            state, ids, jnp.zeros(BATCH, jnp.int32), None, None)
        return state, new, sums, sown[STEP_STATS]

    state, new, sums, sown = one_step(0.01)
    assert sorted(sown) == ["layer2", "layer3", "layer4", "layer5"]
    for layer, entry in sown.items():
        (load,) = entry["moe"]["load"]
        assert load.shape == (16,) and int(load.sum()) == BATCH * LENGTH * 4
        before = state.params[layer]["moe"]["e_score_correction_bias"]
        after = new.params[layer]["moe"]["e_score_correction_bias"]
        np.testing.assert_array_equal(
            np.asarray(after), np.asarray(moe.balance_bias(before, load, 0.01)))
        assert float(jnp.abs(after - before).max()) == pytest.approx(0.01)
        # 4 of 16 experts held, share 0: the counts are the first four's
        assert float(sums[f"{COUNT_PREFIX}moe_assigned/{layer}"]) == int(load[:4].sum())
        assert float(sums[f"{COUNT_PREFIX}moe_largest/{layer}"]) == int(load[:4].max())
    frozen_state, frozen_new, _, _ = one_step(0.0)
    for layer in sown:
        np.testing.assert_array_equal(
            np.asarray(frozen_new.params[layer]["moe"]["e_score_correction_bias"]),
            np.asarray(frozen_state.params[layer]["moe"]["e_score_correction_bias"]))


def test_a_token_model_without_a_rule_between_steps_trains_through_the_same_body():
    """The step body and the trainer's publisher know a model by three
    optional names (``step_collection``, ``after_step``,
    ``publish_counts``); a model with none of them counts its tokens and
    nothing else."""
    from flax import linen as nn

    from fast_autoaugment_tpu.train.trainer import _CountPublisher

    class Bigram(nn.Module):
        @nn.compact
        def __call__(self, ids, train=False):
            table = self.param("table", nn.initializers.normal(0.02), (64, 64))
            return jnp.take(table, ids, axis=0)

    model = Bigram()
    optimizer = build_optimizer({"type": "adamw", "decay": 0.0}, lambda step: 0.05)
    ids = jnp.asarray(load_dataset("synthetic_tokens", "")[0].images[:BATCH])
    state = create_train_state(model, optimizer, jax.random.PRNGKey(0),
                               ids[:, :-1], use_ema=False, jit_init=True)
    body = jax.jit(make_token_step_body(model, optimizer))
    losses = []
    for _ in range(12):
        state, sums = body(state, ids, jnp.zeros(BATCH, jnp.int32), None, None)
        losses.append(float(sums["loss"]) / BATCH)
    assert losses[-1] < losses[0] - 0.2
    assert sorted(sums) == ["loss", f"{COUNT_PREFIX}tokens", "num", "top1"]
    tokens = telemetry.registry().counter("faa_tokens_total")
    before = tokens.value
    publisher = _CountPublisher(model)
    publisher.new_epoch()
    publisher.publish({f"{COUNT_PREFIX}tokens": 256.0})
    publisher.publish({f"{COUNT_PREFIX}tokens": 384.0})   # the epoch's sum so far
    assert tokens.value - before == 384.0


def test_shipped_conf_is_the_published_model():
    """No width is set here: the conf's model block against the catalog's
    numbers, and the parameter count of the whole model from shapes alone
    (nothing is allocated)."""
    conf = shipped_conf()
    model = conf["model"]
    assert (model["hidden_size"], model["num_hidden_layers"], model["num_experts"],
            model["num_experts_per_token"], model["vocab_size"]) == (
                2304, 27, 256, 8, 163840)
    assert model["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert conf["dataset"] == "tokens" and conf["aug"] == "default"
    assert conf["optimizer"]["type"] == "adamw"
    assert not any(k in conf for k in ("layers_held", "experts_held", "ids_held"))
    cut = dict(conf, layers_held=5, experts_held=8, ids_held=20480)
    module = get_model(model_conf_of(cut), 20480)
    shapes = jax.eval_shape(lambda: module.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 64), jnp.int32)))["params"]
    assert sum(math.prod(s.shape) for s in jax.tree.leaves(shapes)) == 602_434_432
    assert sorted(shapes) == ["embed_tokens", "layer1", "layer2", "layer3",
                              "layer4", "layer5", "lm_head", "norm"]
    assert "mla" in shapes["layer4"] and "kda" in shapes["layer5"]
    assert "mlp" in shapes["layer1"] and "moe" in shapes["layer2"]
    assert shapes["layer2"]["moe"]["experts_gate"].shape == (8, 2304, 1024)
    assert shapes["layer2"]["moe"]["router"].shape == (2304, 256)
    assert shapes["lm_head"]["kernel"].shape == (2304, 20480)


# --------------------------------- a model with a second loss term (PR 40)


def glm_conf(epochs=3, **top):
    """A tiny GLM-4.7-Flash (every width cut, the structure kept: a dense
    layer, four expert layers of which 4 of 8 experts are held, the
    multi-token-prediction module) on ``synthetic_tokens``."""
    with open(os.path.join(REPO, "confs", "glm47_flash.yaml")) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8, q_lora_rank=12,
        moe_intermediate_size=16, num_attention_heads=2, n_routed_experts=8,
        num_experts_per_tok=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, vocab_size=64)
    conf.update(layers_held=5, experts_held=4, dataset="synthetic_tokens",
                batch=BATCH, epoch=epochs, lr=0.02, **top)
    return Config(conf)


@pytest.fixture(scope="module")
def glm_unbroken(tmp_path_factory):
    import logging

    path = str(tmp_path_factory.mktemp("glm") / "full.msgpack")
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    trainer_log = logging.getLogger("faa_tpu.train")
    handler = Keep()
    trainer_log.addHandler(handler)
    registry = telemetry.registry()
    targets = registry.counter("faa_mtp_targets_total")
    before = targets.value
    try:
        result = _train(path, conf=glm_conf())
    finally:
        trainer_log.removeHandler(handler)
    return path, result, targets.value - before, lines


def test_the_shipped_glm_conf_is_the_published_model():
    with open(os.path.join(REPO, "confs", "glm47_flash.yaml")) as fh:
        conf = yaml.safe_load(fh)
    assert not any(key in conf for key in ("layers_held", "experts_held", "ids_held"))
    shapes = jax.eval_shape(lambda: get_model(model_conf_of(conf), 154880).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert len([k for k in shapes if k.startswith("layer")]) == 47
    assert shapes["layer47"]["moe"]["experts_gate"].shape == (64, 2048, 1536)
    assert shapes["layer1"]["mlp"]["up_proj"]["kernel"].shape == (2048, 10240)
    assert shapes["layer9"]["mla"]["q_b_proj"]["kernel"].shape == (768, 20 * 256)
    assert shapes["mtp_eh_proj"]["kernel"].shape == (4096, 2048)
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 30.5e9 < count < 30.7e9           # 29.94 B and 0.64 B in the module


def test_a_second_loss_term_trains_through_train_and_eval(glm_unbroken):
    _, result, targets, lines = glm_unbroken
    assert result["steps"] == 3 * STEPS and result["epoch"] == 3
    # the reported loss stays the main head's; the module's is beside it
    assert result["loss_train"] < math.log(64) - 0.1
    assert result["mtp_loss_train"] < math.log(64) and result["mtp_loss_train"] > 0
    assert 0.0 <= result["mtp_top1_train"] <= 1.0
    # evaluation computes no module: its sums are the main head's alone
    assert math.isfinite(result["loss_test"]) and "mtp_loss_test" not in result
    assert targets == 3 * STEPS * BATCH * (LENGTH - 1)
    epochs = [line for line in lines if line.startswith("[train")]
    assert len(epochs) == 3 and all(" mtp_loss=" in line and " mtp_top1=" in line
                                    for line in epochs)
    losses = [float(line.split(" loss=")[1].split()[0]) for line in epochs]
    assert losses[-1] < losses[0]
    text = telemetry.registry().prometheus_text()
    assert "faa_mtp_loss " in text
    assert 'faa_moe_assignments_total{held="true",layer="mtp"}' in text
    # the trainer's stage tree (PR 38) takes the model as it stands
    stages = result["stages"]
    assert stages["train_and_eval.epoch"]["n"] == 3
    assert stages["train_and_eval.state_init"]["n"] == 1
    assert any(name.endswith("first_call:train_dispatch") for name in stages)


def test_a_preempted_glm_run_resumes_to_the_same_loss(glm_unbroken, tmp_path):
    full, result, _, _ = glm_unbroken
    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_11():
        beats.append(1)
        if len(beats) == 11:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(part, conf=glm_conf(), heartbeat=stop_at_11)
    finally:
        resilience.clear_preemption()
    sums = read_metadata(part)["in_epoch"]["sums"]
    assert {"loss", "mtp_loss", "mtp_top1", f"{COUNT_PREFIX}mtp_targets"} <= set(sums)
    resumed = _train(part, conf=glm_conf())
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "mtp_loss_train", "loss_test"):
        assert resumed[key] == result[key], key


def test_a_model_without_loss_terms_lowers_to_the_step_it_had():
    """The seam widened for any model: one without ``loss_terms`` (Kimi
    Linear) is applied to the inputs and its logits go into one
    cross-entropy, as before — the body's lowered text is the text of the
    body as PR 35 wrote it, rebuilt here."""
    from fast_autoaugment_tpu.core import scopes
    from fast_autoaugment_tpu.train.steps import _advance, _next_token_sums

    conf = _conf()
    model = get_model(model_conf_of(conf), 64)
    assert not hasattr(model, "loss_terms")
    optimizer = build_optimizer(conf["optimizer"], lambda step: 1e-3)
    collection = model.step_collection

    def body_before(state, ids, labels, policy, key):
        def loss_fn(params, batch_stats, ids):
            inputs, targets = ids[:, :-1], ids[:, 1:]
            with jax.named_scope(scopes.MODEL):
                logits, mutated = model.apply(
                    {"params": params, "batch_stats": batch_stats}, inputs,
                    train=True, mutable=["batch_stats", collection])
            with jax.named_scope(scopes.LOSS):
                nll, correct = _next_token_sums(logits, targets)
            return nll.mean(), (nll.sum(), correct.sum(),
                                mutated.get("batch_stats", batch_stats),
                                mutated.get(collection, {}))

        (_, (nll, correct, new_batch_stats, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, state.batch_stats, ids)
        new_state = _advance(state, optimizer, grads, new_batch_stats, 0.0)
        counts = {"tokens": ids.shape[0] * (ids.shape[1] - 1)}
        with jax.named_scope(scopes.OPTIMIZER):
            params, counted = model.after_step(
                new_state.params, jax.lax.stop_gradient(stats))
        new_state = new_state.replace(params=params)
        counts.update(counted)
        with jax.named_scope(scopes.METRICS):
            metrics = {"loss": nll, "top1": correct,
                       "num": jnp.float32(ids.shape[0]),
                       **{f"{COUNT_PREFIX}{name}": jnp.float32(value)
                          for name, value in counts.items()}}
        return new_state, metrics

    ids = jnp.asarray(load_dataset("synthetic_tokens", "")[0].images[:BATCH])
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, jax.random.PRNGKey(0), ids[:, :-1], use_ema=False))
    arguments = (state, ids, jnp.zeros(BATCH, jnp.int32), None, None)

    def lowered(body):
        text = jax.jit(body).lower(*arguments).as_text()
        return text.replace("jit_body_before", "jit_step_fn")

    now = lowered(make_token_step_body(model, optimizer))
    assert now == lowered(body_before)
    assert len(now) > 100_000


# ------------------------------------------ a third token model: nemotron_h


def nemotron_conf(epochs=2, **top):
    """A tiny Nemotron-H (every width cut, the structure kept: the cut's
    nine layers by the pattern — four Mamba-2, four expert layers of which
    4 of 16 relu2 experts are held, one attention layer on 2 key-value
    heads) on ``synthetic_tokens``."""
    with open(os.path.join(REPO, "confs", "nemotron3_nano_30b_a3b.yaml")) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(
        hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, n_routed_experts=16,
        num_experts_per_tok=2, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=24, vocab_size=64,
        hybrid_override_pattern="MEMEM*EMEM", num_hidden_layers=10)
    conf.update(layers_held=9, experts_held=4, dataset="synthetic_tokens",
                batch=BATCH, epoch=epochs, lr=0.02, **top)
    return Config(conf)


@pytest.fixture(scope="module")
def nemotron_unbroken(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nemotron") / "full.msgpack")
    before = telemetry.registry().counters_snapshot()
    result = _train(path, conf=nemotron_conf())
    after = telemetry.registry().counters_snapshot()
    return path, result, {k: v - before.get(k, 0.0) for k, v in after.items()}


def test_one_mixer_a_layer_trains_through_train_and_eval(nemotron_unbroken):
    _, result, rise = nemotron_unbroken
    assert result["steps"] == 2 * STEPS and result["epoch"] == 2
    assert math.isfinite(result["loss_train"]) and math.isfinite(result["loss_test"])
    assert result["loss_train"] < math.log(64) - 0.1
    assert rise["faa_tokens_total"] == 2 * STEPS * BATCH * LENGTH
    # the expert layers are the pattern's, not "every layer after the first"
    layers = sorted(key.split('layer="')[1].split('"')[0] for key in rise
                    if key.startswith("faa_moe_assignments_total") and rise[key] > 0)
    assert layers == ["layer2", "layer4", "layer7", "layer9"]
    # trace time: every program's scans took the chunked form
    assert rise['faa_ssd_scan_traces_total{form="chunked_xla"}'] >= 4
    assert rise.get('faa_ssd_scan_traces_total{form="recurrent"}', 0.0) == 0
    assert "train_dispatch" in result["compile_cache"]["labels"]
    assert result["stages"]["train_and_eval.epoch"]["n"] == 2


def test_a_preempted_nemotron_run_resumes_to_the_same_digest_and_losses(
        nemotron_unbroken, tmp_path):
    full, result, _ = nemotron_unbroken
    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_11():
        beats.append(1)
        if len(beats) == 11:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(part, conf=nemotron_conf(), heartbeat=stop_at_11)
    finally:
        resilience.clear_preemption()
    meta = read_metadata(part)
    assert meta["preempted"] is True and meta["step"] == 10
    assert f"{COUNT_PREFIX}moe_assigned/layer7" in meta["in_epoch"]["sums"]
    resumed = _train(part, conf=nemotron_conf())
    assert resumed["steps"] == 2 * STEPS
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "top1_train", "loss_test"):
        assert resumed[key] == result[key], key


def test_an_only_eval_restore_takes_the_nemotron_checkpoint(nemotron_unbroken):
    full, result, _ = nemotron_unbroken
    evaluated = _train(full, conf=nemotron_conf(), only_eval=True)
    assert evaluated["steps"] == 2 * STEPS
    assert evaluated["loss_test"] == result["loss_test"]

