"""Median device time of one train step under ``faa_resnet_stem``, nested in
``faa_model``: the ImageNet ResNet's 7x7 stride-2 convolution on the 224-px image,
its BatchNorm, ReLU and 3x3 stride-2 max-pool (``models/resnet.py``), forward and
backward together (part of the two ``model_*_device_ms``).  A program from before
the scope (``core/scopes.py::RESNET_STEM``, PR 32) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "RESNET_STEM", None)
    return None if scope is None else scope_ms(obs, scope)
