"""Percent of ``setup_s`` that no stage explains: ``setup_s`` less the five
``setup_*_s`` parts and every first call (``first_call:<label>``) before the
window opened.  Over 20%, the five report nothing."""

from benchmarks.harness.stages import setup_unaccounted_share as read  # noqa: F401

META = {"layer": "entry_points", "unit": "%", "source": "program_span",
        "moves": "setup_s"}
