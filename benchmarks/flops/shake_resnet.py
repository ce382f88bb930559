"""Operations of Shake-Shake-26 2x{w}d from its shapes alone.

Gastaldi, "Shake-Shake regularization" (arXiv:1705.07485), in the CIFAR
form this system runs (``models/shake_resnet.py``, after the reference
implementation's ``shake_resnet.py``): a 3x3 stem of 16 channels, three
stages of ``n = (depth - 2) / 6`` blocks at widths w, 2w, 4w and strides
1, 2, 2.  A block is two branches of relu-conv3x3-BN-relu-conv3x3-BN
(the stride in the first convolution), mixed per image, plus the input;
where a block changes width or stride the input goes through the
two-path shortcut instead: two 1x1 convolutions of half the width each,
on the input subsampled at even and at odd pixels, concatenated, then
BatchNorm.  Global average pooling and one linear layer end it.

Counted: the multiply-accumulates of every convolution and of the linear
layer, two operations each.  Not counted: BatchNorm, ReLU, the mix and
its noise, the residual additions, pooling, the loss and the
augmentation — a utilization from these numbers is model operations
over peak.  A backward pass is taken as twice the forward pass, so a
training step is three forward passes per image; nothing is recomputed.
"""

from __future__ import annotations

import re


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below need, as the conf's ``model`` mapping
    gives them: ``{"type": "shakeshake26_2x96d"}`` -> depth 26, base width
    96.  The ResNeXt form (``..._next``) is another family."""
    named = re.fullmatch(r"shakeshake(\d+)_2x(\d+)d", str(conf_model.get("type")))
    if not named:
        raise ValueError(f"not a Shake-Shake ResNet: model {conf_model!r}")
    return {"depth": int(named[1]), "w_base": int(named[2])}


def _blocks(depth: int, w_base: int, image: int):
    """Yield ``(name, c_in, c_out, in_size, out_size)`` per block."""
    if (depth - 2) % 6:
        raise ValueError(f"Shake-Shake depth must be 6n+2, got {depth}")
    c_in, size = 16, image
    for stage, (width, stride) in enumerate(
            zip((w_base, 2 * w_base, 4 * w_base), (1, 2, 2))):
        for i in range((depth - 2) // 6):
            out = size // (stride if i == 0 else 1)
            yield f"s{stage}_{i}", c_in, width, size, out
            c_in, size = width, out


def _convs(depth: int, w_base: int, image: int, in_channels: int = 3):
    """Yield ``(name, kernel, c_in, c_out, out_size)`` per convolution."""
    yield "c_in", 3, in_channels, 16, image
    for name, c_in, c_out, _, out in _blocks(depth, w_base, image):
        for branch in ("branch1", "branch2"):
            yield f"{name}_{branch}/conv1", 3, c_in, c_out, out
            yield f"{name}_{branch}/conv2", 3, c_out, c_out, out
        if c_in != c_out:
            yield f"{name}_shortcut/conv1", 1, c_in, c_out // 2, out
            yield f"{name}_shortcut/conv2", 1, c_in, c_out // 2, out


def _sizes(model: dict) -> tuple[int, int, int]:
    return int(model["depth"]), int(model["w_base"]), int(model["image"])


def forward_macs_per_image(model: dict) -> int:
    """Multiply-accumulates of one forward pass of one image."""
    macs = sum(k * k * c_in * c_out * size * size
               for _, k, c_in, c_out, size in _convs(*_sizes(model)))
    return macs + 4 * int(model["w_base"]) * int(model["num_classes"])


def forward_flops_per_image(model: dict) -> float:
    return 2.0 * forward_macs_per_image(model)


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward: three forward passes' worth."""
    return 3.0 * forward_flops_per_image(model)


def num_params(model: dict) -> int:
    """Trainable parameters: every kernel, the stem's and the linear
    layer's bias (no other convolution has one), scale and bias of the
    BatchNorm after every branch convolution and every shortcut."""
    total = 0
    for name, k, c_in, c_out, _ in _convs(*_sizes(model)):
        total += k * k * c_in * c_out
        if name == "c_in":
            total += c_out
        elif "_branch" in name:
            total += 2 * c_out
        elif name.endswith("_shortcut/conv1"):
            total += 2 * 2 * c_out     # one BatchNorm over both halves
    classes = int(model["num_classes"])
    return total + 4 * int(model["w_base"]) * classes + classes


def num_mixes(model: dict) -> int:
    """Blocks, so per-image (alpha, beta) pairs a training step draws."""
    return sum(1 for _ in _blocks(*_sizes(model)))
