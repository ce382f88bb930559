"""Milliseconds an epoch boundary in which nothing ran on the device, from the
trace alone: its idle stretches under a ``train_and_eval.epoch.epoch_boundary``
annotation on the host line.  None where the trace has no host plane."""

from benchmarks.harness.stages import epoch_boundary_device_idle_ms as read  # noqa: F401

META = {"layer": "epoch_driver_data_feed", "unit": "ms", "source": "program_span",
        "moves": "train_images_per_s"}
