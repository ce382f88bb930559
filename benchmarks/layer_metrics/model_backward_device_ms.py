"""Median device time of one train step in the backward pass of the model and the
loss: ``faa_model`` and ``faa_loss`` with every scope a model nests under them,
under ``transpose(jvp(...))``."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "backward")
