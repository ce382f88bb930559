"""Peak bytes in use on the fullest chip when the window closed.  It
moves no end-to-end metric today: it guards the room later
configurations need, and is tied to ``setup_s`` only because every cell
reports that."""

META = {"layer": "device", "unit": "bytes", "source": "program_counter",
        "moves": "setup_s"}


def read(obs):
    return obs.memory_peak_bytes or None
