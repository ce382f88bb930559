# Makes tools/ importable as a package so `python -m tools.faalint`
# works from the repo root.  Standalone script entry points
# (`python tools/lint_robustness.py`, `python tools/faa_status.py`) are
# unaffected.
