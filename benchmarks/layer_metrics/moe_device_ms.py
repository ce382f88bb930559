"""Median device time of one train step under ``faa_moe``, nested in ``faa_model``: the
expert layers this chip holds (``models/kimi_linear.py::ExpertLayer``: the router, the
held experts' part of ``ops/moe.py`` and the shared expert), forward and backward
together, what is computed again included (part of the two ``model_*_device_ms``).  A
program from before the scope (``core/scopes.py::MOE``, PR 35) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MOE", None)
    return None if scope is None else scope_ms(obs, scope)
