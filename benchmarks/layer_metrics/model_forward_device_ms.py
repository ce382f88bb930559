"""Median device time of one train step in the model's forward pass and the loss:
``faa_model`` and ``faa_loss`` with every scope a model nests under them, where
they do not sit under ``transpose(``."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "forward")
