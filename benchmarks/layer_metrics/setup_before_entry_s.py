"""Seconds of ``setup_s`` before ``train_and_eval`` was entered: process
creation to the root stage's start — the interpreter, the imports, the
benchmark's fixture."""

from benchmarks.harness import stages

META = {"layer": "entry_points", "unit": "s", "source": "program_span",
        "moves": "setup_s"}


def read(obs):
    return stages.setup_part_s(obs, "before_entry")
