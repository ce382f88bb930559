"""``train_and_eval`` on a token data set with a fifth token model, lfm2_moe
(``models/lfm2_moe.py``): short-convolution and attention mixers in one
model, a tied head, expert layers with no shared expert; the loss falls, a
preempted run resumes bit-equal, an ``only_eval`` restore takes the
checkpoint, the counters say what each program traced.
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


def lfm2_conf(epochs=2, **top):
    """A tiny lfm2_moe (every width cut, the structure kept: the cut's seven
    blocks — two dense, five expert layers of which 4 of 16 experts are
    held; five convolution mixers of 3 taps, two rotary attention mixers of
    4 heads of 8 on 2 key-value heads) on ``synthetic_tokens``."""
    with open(os.path.join(REPO, "confs", "lfm2_8b_a1b.yaml")) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, vocab_size=64, num_hidden_layers=8,
        layer_types=conf["model"]["layer_types"][:8])
    conf.update(layers_held=7, experts_held=4, dataset="synthetic_tokens",
                batch=BATCH, epoch=epochs, lr=0.02, **top)
    return Config(conf)


@pytest.fixture(scope="module")
def lfm2_unbroken(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("lfm2") / "full.msgpack")
    before = telemetry.registry().counters_snapshot()
    result = _train(path, conf=lfm2_conf())
    after = telemetry.registry().counters_snapshot()
    return path, result, {k: v - before.get(k, 0.0) for k, v in after.items()}


def test_convolution_and_attention_mixers_train_through_train_and_eval(lfm2_unbroken):
    path, result, rise = lfm2_unbroken
    assert result["steps"] == 2 * STEPS and result["epoch"] == 2
    assert math.isfinite(result["loss_train"]) and math.isfinite(result["loss_test"])
    assert result["loss_train"] < math.log(64) - 0.1
    assert rise["faa_tokens_total"] == 2 * STEPS * BATCH * LENGTH
    # the expert layers are the blocks after the two leading dense ones
    layers = sorted(key.split('layer="')[1].split('"')[0] for key in rise
                    if key.startswith("faa_moe_assignments_total") and rise[key] > 0)
    assert layers == ["layer3", "layer4", "layer5", "layer6", "layer7"]
    # trace time: five convolution mixers to two attention cores a program,
    # the cores of these small heads in the XLA form and so in no pair
    convs = rise['faa_short_conv_traces_total{taps="3"}']
    cores = rise['faa_attention_cores_traced_total{form="blocked_xla",span="none"}']
    assert 2 * convs == 5 * cores > 0
    assert not any(key.startswith("faa_attention_head_blocks") and rise[key]
                   for key in rise)
    assert "train_dispatch" in result["compile_cache"]["labels"]
    assert result["stages"]["train_and_eval.epoch"]["n"] == 2
    # the checkpoint holds one table and no head of its own
    assert read_metadata(path)["step"] == 2 * STEPS


def test_a_preempted_lfm2_run_resumes_to_the_same_digest_and_losses(
        lfm2_unbroken, tmp_path):
    full, result, _ = lfm2_unbroken
    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_11():
        beats.append(1)
        if len(beats) == 11:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(part, conf=lfm2_conf(), heartbeat=stop_at_11)
    finally:
        resilience.clear_preemption()
    meta = read_metadata(part)
    assert meta["preempted"] is True and meta["step"] == 10
    assert f"{COUNT_PREFIX}moe_assigned/layer7" in meta["in_epoch"]["sums"]
    resumed = _train(part, conf=lfm2_conf())
    assert resumed["steps"] == 2 * STEPS
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "top1_train", "loss_test"):
        assert resumed[key] == result[key], key


def test_an_only_eval_restore_takes_the_lfm2_checkpoint(lfm2_unbroken):
    full, result, _ = lfm2_unbroken
    evaluated = _train(full, conf=lfm2_conf(), only_eval=True)
    assert evaluated["steps"] == 2 * STEPS
    assert evaluated["loss_test"] == result["loss_test"]
