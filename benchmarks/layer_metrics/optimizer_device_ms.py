"""Median device time of one train step in the update: ``faa_optimizer`` (update and
parameter add), ``faa_ema`` and ``faa_metrics`` (top-k and sums)."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "step_programs", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "optimizer")
