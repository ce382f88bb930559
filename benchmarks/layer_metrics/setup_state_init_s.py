"""Seconds of ``setup_s`` under the stages ``build``, ``state_init``,
``restore`` and ``place_state``, less the first calls nested in them."""

from benchmarks.harness import stages

META = {"layer": "step_programs", "unit": "s", "source": "program_span",
        "moves": "setup_s"}


def read(obs):
    return stages.setup_part_s(obs, "state_init")
