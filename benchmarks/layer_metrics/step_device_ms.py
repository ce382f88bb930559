"""Median device time of one execution of the train step program, found on the
trace's ``XLA Modules`` line by the name pattern in the traffic file."""

from benchmarks.harness.readers import step_device_ms as read  # noqa: F401

META = {"layer": "step_programs", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}
