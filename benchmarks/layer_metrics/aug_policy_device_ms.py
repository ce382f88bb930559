"""Median device time of one train step under the policy augmentation: everything
traced under ``faa_aug_policy`` (sub-policy draw, gates, the switch over the 19
operations and its select), from the trace's ``XLA Ops`` events joined to the
program's scope map."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "policy")
