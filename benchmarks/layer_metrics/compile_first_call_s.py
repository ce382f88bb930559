"""Seconds this process spent in the first call of each jitted entry
point before the window opened: compilation on a cold cache, loading the
executable on a warm one."""

META = {"layer": "compile_seam", "unit": "s", "source": "program_counter",
        "moves": "setup_s"}


def read(obs):
    labels = (obs.compile_stats or {}).get("labels")
    if not labels:
        return None
    return sum(rec["sec"] for rec in labels.values())
