"""Model operations per second over the chip's peak: the operations the
forward and backward passes of the images completed in the window require, computed from
shapes (``flops/<family>.py``), over the published bfloat16 peak of the
chip.  A utilization of the whole run, idle time included — not a
kernel's share of its roofline."""

from benchmarks.harness.readers import model_flops_utilization as read  # noqa: F401

META = {"layer": "models", "unit": "%", "source": "host_clock",
        "moves": "train_images_per_s"}
