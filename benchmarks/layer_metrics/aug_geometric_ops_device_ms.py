"""Median device time of one train step in the seven geometric operations of the
policy: ShearX/Y, TranslateX/Y, Rotate, TranslateX/YAbs, each under its
``faa_aug_op_<Name>`` (part of ``aug_policy_device_ms``)."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "geometric_ops")
