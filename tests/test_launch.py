"""CLI and fleet launcher tests (local process supervision — no real SSH)."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from fast_autoaugment_tpu.launch.fleet import expand_hosts


def test_expand_hosts():
    assert expand_hosts("3") == ["task1", "task2", "task3"]
    assert expand_hosts("a, b,c") == ["a", "b", "c"]


def test_train_cli_smoke(tmp_path):
    from fast_autoaugment_tpu.launch.train_cli import main

    conf = tmp_path / "conf.yaml"
    conf.write_text(
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 1\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n  nesterov: true\n"
    )
    save = tmp_path / "ck.msgpack"
    result = main([
        "-c", str(conf), "--dataroot", str(tmp_path), "--save", str(save),
        "--cv-ratio", "0.2", "--evaluation-interval", "1",
    ])
    assert result["epoch"] == 1
    assert os.path.exists(save)

    # --only-eval on the saved checkpoint
    result2 = main([
        "-c", str(conf), "--dataroot", str(tmp_path), "--save", str(save),
        "--cv-ratio", "0.2", "--only-eval",
    ])
    assert result2["top1_test"] == pytest.approx(result["top1_test"], abs=1e-6)


def test_train_cli_overrides(tmp_path):
    from fast_autoaugment_tpu.launch.train_cli import main

    conf = tmp_path / "conf.yaml"
    conf.write_text(
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 2\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n  nesterov: true\n"
    )
    result = main([
        "-c", str(conf), "--dataroot", str(tmp_path), "epoch=1", "batch=16",
    ])
    assert result["epoch"] == 1


def test_all_conf_presets_parse():
    from fast_autoaugment_tpu.core.config import load_config
    from fast_autoaugment_tpu.data.datasets import is_token_dataset
    from fast_autoaugment_tpu.models import get_model, model_conf_of, num_class

    confdir = os.path.join(os.path.dirname(__file__), "..", "confs")
    presets = sorted(os.listdir(confdir))
    # the 16 reference presets must all be present (reference confs/);
    # extra repo-local presets (e.g. the search-validation config) are fine
    reference_presets = {
        "efficientnet_b0.yaml", "efficientnet_b0_condconv.yaml",
        "efficientnet_b1.yaml", "efficientnet_b2.yaml",
        "efficientnet_b3.yaml", "efficientnet_b4.yaml",
        "pyramid272_cifar.yaml", "resnet200.yaml", "resnet50.yaml",
        "resnet50_mixup.yaml", "shake26_2x112d_cifar.yaml",
        "shake26_2x32d_cifar.yaml", "shake26_2x96d_cifar.yaml",
        "wresnet28x10_cifar.yaml", "wresnet28x10_svhn.yaml",
        "wresnet40x2_cifar.yaml",
    }
    assert reference_presets <= set(presets)
    for name in presets:
        conf = load_config(os.path.join(confdir, name))
        assert conf["model"]["type"]
        # every preset's model must be constructible; a token model's
        # classes are its ids (PR 35)
        tokens = is_token_dataset(conf["dataset"])
        get_model(model_conf_of(conf), int(conf["model"]["vocab_size"]) if tokens
                  else num_class(conf["dataset"]))
        assert conf["optimizer"]["type"] in ("sgd", "rmsprop", "adamw")
