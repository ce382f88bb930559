"""Median time of the augmentation alone on one per-chip training batch: ``cifar_train_batch``
with the cell's policy tensor and cutout, jitted by itself, each call
ending in ``block_until_ready``, after the window.  Timed from outside:
it goes when spans inside the program give the scope's share of the step."""

from benchmarks.harness.aug_alone import train_batch_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "host_clock",
        "moves": "train_images_per_s"}


def read(obs):
    return train_batch_ms(obs)
