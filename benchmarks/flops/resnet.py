"""Operations of a bottleneck ResNet in its ImageNet layout from its shapes alone.

He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), as this system runs it (``models/resnet.py``, after
torchvision's): a 7x7 stride-2 stem of 64 channels on a 3-channel image,
a 3x3 stride-2 max-pool, four stages of bottleneck blocks at widths
64/128/256/512 (1x1 to the width, 3x3 at the width carrying the stage's
stride in its first block, 1x1 to four times the width), a 1x1
projection on the shortcut of every stage's first block, global average
pooling and one linear layer.

Counted: the multiply-accumulates of every convolution and of the linear
layer, two operations each.  Not counted: BatchNorm, ReLU, the max-pool,
the residual additions, pooling, the loss and the augmentation — a
utilization from these numbers is model operations over peak.  A
backward pass is taken as twice the forward pass, so a training step is
three forward passes per image; nothing is recomputed.
"""

from __future__ import annotations

import re

#: depth -> bottleneck blocks a stage (the paper's table 1)
BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3),
          200: (3, 24, 36, 3)}
WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below need, as the conf's ``model`` mapping
    gives them: ``{"type": "resnet50"}`` -> depth 50, its blocks a stage,
    the four widths and the expansion.  The basic-block depths (18, 34)
    and the CIFAR layout are other families."""
    named = re.fullmatch(r"resnet(\d+)", str(conf_model.get("type")))
    if not named or int(named[1]) not in BLOCKS:
        raise ValueError(f"not a bottleneck ImageNet ResNet: model {conf_model!r}")
    depth = int(named[1])
    return {"depth": depth, "blocks": list(BLOCKS[depth]),
            "widths": list(WIDTHS), "expansion": EXPANSION}


def _convs(model: dict):
    """Yield ``(name, kernel, c_in, c_out, out_size)`` per convolution."""
    size = -(-int(model["image"]) // 2)            # 7x7, stride 2, pad 3
    yield "conv1", 7, 3, 64, size
    size = -(-size // 2)                           # 3x3 max-pool, stride 2, pad 1
    c_in, expansion = 64, int(model["expansion"])
    for stage, (width, count) in enumerate(zip(model["widths"], model["blocks"])):
        for i in range(int(count)):
            name = f"layer{stage + 1}_{i}"
            stride = 2 if (stage > 0 and i == 0) else 1
            out = -(-size // stride)
            yield f"{name}/conv1", 1, c_in, width, size
            yield f"{name}/conv2", 3, width, width, out
            yield f"{name}/conv3", 1, width, width * expansion, out
            if i == 0:
                yield f"{name}/downsample_conv", 1, c_in, width * expansion, out
            c_in, size = width * expansion, out


def forward_macs_per_image(model: dict) -> int:
    """Multiply-accumulates of one forward pass of one image."""
    macs = sum(k * k * c_in * c_out * size * size
               for _, k, c_in, c_out, size in _convs(model))
    return macs + (int(model["widths"][-1]) * int(model["expansion"])
                   * int(model["num_classes"]))


def forward_flops_per_image(model: dict) -> float:
    return 2.0 * forward_macs_per_image(model)


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward: three forward passes' worth."""
    return 3.0 * forward_flops_per_image(model)


def num_params(model: dict) -> int:
    """Trainable parameters: every kernel, scale and bias of the BatchNorm
    after every convolution, the linear layer and its bias."""
    total = sum(k * k * c_in * c_out + 2 * c_out
                for _, k, c_in, c_out, _ in _convs(model))
    features = int(model["widths"][-1]) * int(model["expansion"])
    classes = int(model["num_classes"])
    return total + features * classes + classes
