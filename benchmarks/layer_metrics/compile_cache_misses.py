"""Executables compiled afresh (not found in the persistent cache)
before the window opened.  A warm run has none."""

META = {"layer": "compile_seam", "unit": "count", "source": "program_counter",
        "moves": "setup_s"}


def read(obs):
    if not obs.compile_stats:
        return None
    return obs.compile_stats["misses"]
