"""Median time the device waits between two consecutive executions of
the train step program: what the host does between dispatches, as far as the
device's queue does not hide it."""

from benchmarks.harness.readers import dispatch_gap_ms as read  # noqa: F401

META = {"layer": "epoch_driver_data_feed", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}
