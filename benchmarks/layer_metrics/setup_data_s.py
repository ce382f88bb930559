"""Seconds of ``setup_s`` under the stages ``load_dataset``, ``split`` and
``cache_upload``, less the first calls nested in them."""

from benchmarks.harness import stages

META = {"layer": "epoch_driver_data_feed", "unit": "s", "source": "program_span",
        "moves": "setup_s"}


def read(obs):
    return stages.setup_part_s(obs, "data")
