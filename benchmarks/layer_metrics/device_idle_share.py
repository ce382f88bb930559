"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""

from benchmarks.harness.readers import device_idle_share as read  # noqa: F401

META = {"layer": "device", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}
