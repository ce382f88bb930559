"""Median device time of one train step in the two histogram operations of the
policy, ``faa_aug_op_AutoContrast`` and ``faa_aug_op_Equalize`` (part of
``aug_policy_device_ms``)."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "histogram_ops")
