"""Share of the traced window the chips' operation lines spent inside
collectives (all-reduce and kin): on that line operations run one after
another, so this is the part of the exchange compute did not hide."""

from benchmarks.harness import trace as tr

META = {"layer": "placement", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    view = obs.trace
    if view is None or view.window_s <= 0 or len(view.planes) < 2:
        return None
    seconds = sum(tr.collective_seconds(p) for p in view.planes) / len(view.planes)
    return 100.0 * seconds / view.window_s
