"""Percent of the train step program's device time that no named scope explains:
operations without a ``faa_`` scope or not in the scope map, and the device
waiting inside the program.  Over 20%, the eight per-scope readers report nothing."""

from benchmarks.harness.scopes import step_unscoped_share as read  # noqa: F401

META = {"layer": "step_programs", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}
