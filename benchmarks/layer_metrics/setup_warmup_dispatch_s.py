"""Seconds of ``setup_s`` under every ``index_matrix`` and ``dispatch_loop``
stage before the window opened (the warm-up epoch's steps, and the few after
its boundary), less the first calls nested in them."""

from benchmarks.harness import stages

META = {"layer": "epoch_driver_data_feed", "unit": "s", "source": "program_span",
        "moves": "setup_s"}


def read(obs):
    return stages.setup_part_s(obs, "warmup_dispatch")
