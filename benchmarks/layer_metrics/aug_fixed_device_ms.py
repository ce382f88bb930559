"""Median device time of one train step in the augmentation every recipe pays,
``faa_aug_fixed``: crop, flip, normalize, cutout."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "fixed")
