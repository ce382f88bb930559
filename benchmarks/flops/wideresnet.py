"""Operations and bytes of WRN-d-k from its shapes alone.

Zagoruyko & Komodakis, "Wide Residual Networks" (arXiv:1605.07146), in
the CIFAR form this system runs: a 3x3 stem of 16 channels, three stages
of ``n = (depth - 4) / 6`` pre-activation basic blocks at widths 16k,
32k, 64k and strides 1, 2, 2, a 1x1 shortcut convolution wherever a
block changes width or stride, global average pooling and one linear
layer.  Counted: the multiply-accumulates of every convolution and of
the linear layer, two operations each.  Not counted: BatchNorm, ReLU,
the residual additions, pooling, the loss and the augmentation — a
utilization from these numbers is model operations over peak, and says
so in its name.

A backward pass is taken as twice the forward pass (one product for the
gradient of the input and one for the gradient of the weights), so a
training step is three forward passes per image; nothing is recomputed
by this count.
"""

from __future__ import annotations

import re


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below need, as the conf's ``model`` mapping
    gives them: ``{"type": "wresnet28_10"}`` -> depth 28, widen factor 10.
    A configuration file's ``model`` block has to agree on these keys."""
    named = re.fullmatch(r"wresnet(\d+)_(\d+)", str(conf_model.get("type")))
    if not named:
        raise ValueError(f"not a Wide ResNet: model {conf_model!r}")
    return {"depth": int(named[1]), "widen_factor": int(named[2])}


def _convs(depth: int, widen: int, image: int, in_channels: int = 3):
    """Yield ``(name, kernel, c_in, c_out, out_size)`` per convolution."""
    if (depth - 4) % 6:
        raise ValueError(f"WRN depth must be 6n+4, got {depth}")
    n = (depth - 4) // 6
    yield "conv1", 3, in_channels, 16, image
    c_in, size = 16, image
    for stage, (width, stride) in enumerate(
            zip((16 * widen, 32 * widen, 64 * widen), (1, 2, 2)), start=1):
        for i in range(n):
            s = stride if i == 0 else 1
            out = size // s
            yield f"layer{stage}_{i}/conv1", 3, c_in, width, size
            yield f"layer{stage}_{i}/conv2", 3, width, width, out
            if s != 1 or c_in != width:
                yield f"layer{stage}_{i}/shortcut", 1, c_in, width, out
            c_in, size = width, out


def forward_macs_per_image(model: dict) -> int:
    """Multiply-accumulates of one forward pass of one image."""
    depth, widen = int(model["depth"]), int(model["widen_factor"])
    macs = sum(k * k * c_in * c_out * size * size
               for _, k, c_in, c_out, size in
               _convs(depth, widen, int(model["image"])))
    return macs + 64 * widen * int(model["num_classes"])


def forward_flops_per_image(model: dict) -> float:
    return 2.0 * forward_macs_per_image(model)


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward: three forward passes' worth."""
    return 3.0 * forward_flops_per_image(model)


def num_params(model: dict) -> int:
    """Trainable parameters: kernels and biases of every convolution and
    of the linear layer, scale and bias of every BatchNorm."""
    depth, widen = int(model["depth"]), int(model["widen_factor"])
    total = 0
    for name, k, c_in, c_out, _ in _convs(depth, widen, int(model["image"])):
        total += k * k * c_in * c_out + c_out
        if name.endswith("/conv1"):
            total += 2 * c_in      # the block's bn1 acts on its input
        elif name.endswith("/conv2"):
            total += 2 * c_in      # bn2 acts on conv1's output
    total += 2 * 64 * widen        # the final BatchNorm
    total += 64 * widen * int(model["num_classes"]) + int(model["num_classes"])
    return total


def gradient_bytes(model: dict, bytes_per_param: int = 4) -> int:
    """What a data-parallel step all-reduces: one gradient per parameter."""
    return num_params(model) * bytes_per_param
