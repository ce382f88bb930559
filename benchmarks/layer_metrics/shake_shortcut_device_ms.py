"""Median device time of one train step under ``faa_shake_shortcut``, nested in
``faa_model``: the two-path strided shortcut of a Shake-Shake stage's first block
(slice, shift by crop-and-pad, two 1x1 convolutions, concatenate, BatchNorm), forward
and backward together.  A program from before the scope
(``core/scopes.py::SHAKE_SHORTCUT``) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SHAKE_SHORTCUT", None)
    return None if scope is None else scope_ms(obs, scope)
