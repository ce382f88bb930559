"""How unevenly the router loads the experts this chip holds: the assignments of the
most loaded held expert over the held experts' mean, a step at a time, in the worst of
the expert layers — the program's gauge ``faa_moe_held_load_max_over_mean{layer}``
(``train/trainer.py``, from the sums the step keeps on the device; published at an epoch
boundary and at the preemption snapshot, no sync in the step loop), read after the
trainer stopped (``programs/train_tokens.py``, handed over in ``Observed.work``).  1.0 is
an even load; the step's time follows the most loaded expert once experts are exchanged
across chips.  A program from before the gauge (PR 35) has nothing to read."""

META = {"layer": "models", "unit": "ratio", "source": "program_counter",
        "moves": "train_images_per_s"}


def read(obs):
    by_layer = obs.work.get("moe_held_load_max_over_mean")
    return max(by_layer.values()) if by_layer else None
