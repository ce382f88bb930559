"""Median device time of one train step in ``faa_batch_gather``: the ``jnp.take`` of
the batch from the device-resident dataset."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "epoch_driver_data_feed", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "gather")
