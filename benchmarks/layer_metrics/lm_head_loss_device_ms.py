"""Median device time of one train step under ``faa_lm_head`` (the output head's product
over the ids this chip holds, nested in ``faa_model``) plus ``faa_loss`` (the next-token
cross-entropy over ``[B, T, ids]``), forward and backward together: what a step pays for
the vocabulary (part of the two ``model_*_device_ms``).  A program from before the scope
(``core/scopes.py::LM_HEAD``, PR 35) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    names = program_scopes()
    head = getattr(names, "LM_HEAD", None)
    if head is None:
        return None
    parts = [scope_ms(obs, head), scope_ms(obs, names.LOSS)]
    return None if None in parts else sum(parts)
