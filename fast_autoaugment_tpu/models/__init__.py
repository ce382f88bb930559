"""Model registry.

Capability match for the reference ``networks/__init__.py:19-103``:
string model types map to Flax modules.  Unlike the reference (which
also wraps models in DDP/.cuda() here), device placement and sharding
are the train step's concern — a module is pure structure.

Supported types (reference parity): resnet50, resnet200, wresnet40_2,
wresnet28_10, shakeshake26_2x32d / 2x64d / 2x96d / 2x112d,
shakeshake26_2x96d_next, pyramid, efficientnet-b0..b7 (+condconv).
Beyond the reference, five token models: kimi_linear
(``models/kimi_linear.py``), glm4_moe_lite (``models/glm4_moe_lite.py``),
nemotron_h (``models/nemotron_h.py``), afmoe (``models/afmoe.py``) and
lfm2_moe (``models/lfm2_moe.py``).
"""

from __future__ import annotations

from typing import Any

from flax import linen as nn

from fast_autoaugment_tpu.models.pyramidnet import PyramidNet
from fast_autoaugment_tpu.models.resnet import ResNet
from fast_autoaugment_tpu.models.shake_resnet import ShakeResNet, ShakeResNeXt
from fast_autoaugment_tpu.models.wideresnet import WideResNet

__all__ = ["get_model", "model_conf_of", "num_class", "input_image_size"]


def model_conf_of(conf: Any) -> dict:
    """The mapping :func:`get_model` takes, from a whole conf: its
    ``model`` block with the data set, the precision, and — for a model
    that can hold a share of itself — what this chip holds
    (``models/token_blocks.py::CUT_KEYS``, top-level conf keys)."""
    from fast_autoaugment_tpu.models.token_blocks import CUT_KEYS

    model_conf = dict(conf["model"], dataset=conf["dataset"])
    model_conf.setdefault("precision", conf.get("precision", "f32"))
    for key in CUT_KEYS:
        if conf.get(key) is not None:
            model_conf[key] = conf[key]
    return model_conf


def num_class(dataset: str) -> int:
    """Class count per dataset (reference ``networks/__init__.py:93-103``)."""
    if dataset.endswith("tokens"):
        raise ValueError(
            f"{dataset!r} is a token data set: its classes are the ids the "
            "model holds (conf ids_held, else model.vocab_size)")
    if dataset.startswith("synthetic_shapes"):
        return 10  # glyph task is always 10-class (any _nN train size)
    if dataset.startswith("synthetic"):
        return 100 if dataset.endswith("100") else 10
    return {
        "cifar10": 10,
        "reduced_cifar10": 10,
        "cifar10.1": 10,
        "cifar100": 100,
        "svhn": 10,
        "reduced_svhn": 10,
        "imagenet": 1000,
        "reduced_imagenet": 120,
    }[dataset]


def input_image_size(dataset: str, model_type: str) -> int:
    """Native input resolution for dataset/model."""
    if dataset.endswith("imagenet"):
        if model_type.startswith("efficientnet"):
            from fast_autoaugment_tpu.models.efficientnet import efficientnet_params

            return efficientnet_params(model_type.replace("-condconv", ""))[2]
        return 224
    return 32


def get_model(conf: Any, num_classes: int) -> nn.Module:
    """Build a Flax module from a model config mapping.

    `conf` needs `.type` plus model-specific fields (reference conf
    schema: `model{type, (depth, alpha, bottleneck) | (condconv_num_expert)}`).
    """
    name = conf["type"]
    dataset = conf.get("dataset", "cifar")
    # mixed precision: 'bf16' runs activations in bfloat16 (params, BN
    # statistics and logits stay float32) — threaded through every family
    precision = str(conf.get("precision", "f32") or "f32").lower()
    import jax.numpy as jnp

    if precision in ("bf16", "bfloat16"):
        dtype = jnp.bfloat16
    elif precision in ("f32", "fp32", "float32"):
        dtype = jnp.float32
    else:
        raise ValueError(
            f"unknown precision {precision!r}; use 'f32' or 'bf16'"
        )

    if name in ("resnet50", "resnet200"):
        return ResNet(dataset="imagenet", depth=int(name[len("resnet"):]),
                      num_classes=num_classes, bottleneck=True, dtype=dtype)
    if name.startswith("wresnet"):
        # wresnet{depth}_{widen}
        depth, widen = name[len("wresnet"):].split("_")
        return WideResNet(
            depth=int(depth),
            widen_factor=int(widen),
            num_classes=num_classes,
            dropout_rate=0.0,
            dtype=dtype,
        )
    if name.startswith("shakeshake26_2x"):
        rest = name[len("shakeshake26_2x"):]
        if rest.endswith("d_next"):
            return ShakeResNeXt(
                depth=26, w_base=int(rest[:-len("d_next")]), cardinality=4,
                num_classes=num_classes, dtype=dtype,
            )
        assert rest.endswith("d")
        return ShakeResNet(depth=26, w_base=int(rest[:-1]), num_classes=num_classes,
                           dtype=dtype)
    if name == "pyramid":
        return PyramidNet(
            dataset=dataset if dataset.startswith("cifar") else "cifar10",
            depth=int(conf["depth"]),
            alpha=float(conf["alpha"]),
            num_classes=num_classes,
            bottleneck=bool(conf.get("bottleneck", True)),
            dtype=dtype,
        )
    if name == "kimi_linear":
        from fast_autoaugment_tpu.models.kimi_linear import kimi_linear_from_conf

        return kimi_linear_from_conf(conf, dtype=dtype)
    if name == "glm4_moe_lite":
        from fast_autoaugment_tpu.models.glm4_moe_lite import glm4_moe_lite_from_conf

        return glm4_moe_lite_from_conf(conf, dtype=dtype)
    if name == "nemotron_h":
        from fast_autoaugment_tpu.models.nemotron_h import nemotron_h_from_conf

        return nemotron_h_from_conf(conf, dtype=dtype)
    if name == "afmoe":
        from fast_autoaugment_tpu.models.afmoe import afmoe_from_conf

        return afmoe_from_conf(conf, dtype=dtype)
    if name == "lfm2_moe":
        from fast_autoaugment_tpu.models.lfm2_moe import lfm2_moe_from_conf

        return lfm2_moe_from_conf(conf, dtype=dtype)
    if name.startswith("efficientnet"):
        from fast_autoaugment_tpu.models.efficientnet import EfficientNet

        condconv = "condconv" in name
        base = name.replace("-condconv", "")
        return EfficientNet.from_name(
            base,
            num_classes=num_classes,
            condconv_num_expert=int(conf.get("condconv_num_expert", 0)) if condconv else 0,
            dtype=dtype,
        )
    raise ValueError(f"unknown model type {name!r}")
