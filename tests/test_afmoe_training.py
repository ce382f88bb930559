"""``train_and_eval`` on a token data set with a fourth token model, afmoe
(``models/afmoe.py``): window and full grouped-query mixers in one model;
the loss falls, a preempted run resumes bit-equal, an ``only_eval`` restore
takes the checkpoint, the counters say which span each attention core got.
``tests/test_token_training.py``'s helpers and sizes; a file of its own so
that ``--dist loadfile`` can run it beside that one."""

import math
import os

import pytest
import yaml

from fast_autoaugment_tpu.core import resilience, telemetry
from fast_autoaugment_tpu.core.checkpoint import read_metadata
from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.train.steps import COUNT_PREFIX
from tests.test_token_training import BATCH, LENGTH, REPO, STEPS, _digest, _train


def afmoe_conf(epochs=2, **top):
    """A tiny afmoe (every width cut, the structure kept: the cut's six
    blocks — two dense, four expert layers of which 4 of 16 experts are
    held; five window mixers with rotary and a span of 8 keys, one full —
    on 2 key-value heads) on ``synthetic_tokens``."""
    with open(os.path.join(REPO, "confs", "trinity_mini.yaml")) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, vocab_size=64, sliding_window=8, num_hidden_layers=8,
        layer_types=conf["model"]["layer_types"][:8])
    conf.update(layers_held=6, experts_held=4, dataset="synthetic_tokens",
                batch=BATCH, epoch=epochs, lr=0.02, **top)
    return Config(conf)


@pytest.fixture(scope="module")
def afmoe_unbroken(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("afmoe") / "full.msgpack")
    before = telemetry.registry().counters_snapshot()
    result = _train(path, conf=afmoe_conf())
    after = telemetry.registry().counters_snapshot()
    return path, result, {k: v - before.get(k, 0.0) for k, v in after.items()}


def test_window_and_full_mixers_train_through_train_and_eval(afmoe_unbroken):
    _, result, rise = afmoe_unbroken
    assert result["steps"] == 2 * STEPS and result["epoch"] == 2
    assert math.isfinite(result["loss_train"]) and math.isfinite(result["loss_test"])
    assert result["loss_train"] < math.log(64) - 0.1
    assert rise["faa_tokens_total"] == 2 * STEPS * BATCH * LENGTH
    # the expert layers are the blocks after the two leading dense ones
    layers = sorted(key.split('layer="')[1].split('"')[0] for key in rise
                    if key.startswith("faa_moe_assignments_total") and rise[key] > 0)
    assert layers == ["layer3", "layer4", "layer5", "layer6"]
    # trace time: every program's cores by their span, five with one to each without
    spanned = rise['faa_attention_cores_traced_total{form="blocked_xla",span="8"}']
    whole = rise['faa_attention_cores_traced_total{form="blocked_xla",span="none"}']
    assert spanned == 5 * whole > 0
    assert rise['faa_mla_attention_traces_total{form="blocked_xla"}'] == spanned + whole
    assert "train_dispatch" in result["compile_cache"]["labels"]
    assert result["stages"]["train_and_eval.epoch"]["n"] == 2


def test_a_preempted_afmoe_run_resumes_to_the_same_digest_and_losses(
        afmoe_unbroken, tmp_path):
    full, result, _ = afmoe_unbroken
    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_11():
        beats.append(1)
        if len(beats) == 11:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(part, conf=afmoe_conf(), heartbeat=stop_at_11)
    finally:
        resilience.clear_preemption()
    meta = read_metadata(part)
    assert meta["preempted"] is True and meta["step"] == 10
    assert f"{COUNT_PREFIX}moe_assigned/layer5" in meta["in_epoch"]["sums"]
    resumed = _train(part, conf=afmoe_conf())
    assert resumed["steps"] == 2 * STEPS
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "top1_train", "loss_test"):
        assert resumed[key] == result[key], key


def test_an_only_eval_restore_takes_the_afmoe_checkpoint(afmoe_unbroken):
    full, result, _ = afmoe_unbroken
    evaluated = _train(full, conf=afmoe_conf(), only_eval=True)
    assert evaluated["steps"] == 2 * STEPS
    assert evaluated["loss_test"] == result["loss_test"]
