"""Seconds of ``setup_s`` under the first ``epoch_boundary`` stage (metric
sync, logging, evaluation, checkpoint), less the first calls nested in it."""

from benchmarks.harness import stages

META = {"layer": "epoch_driver_data_feed", "unit": "s", "source": "program_span",
        "moves": "setup_s"}


def read(obs):
    return stages.setup_part_s(obs, "first_boundary")
