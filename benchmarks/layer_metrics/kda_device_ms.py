"""Median device time of one train step under ``faa_kda``, nested in ``faa_model``: the
KDA mixers of the layers this chip holds (``models/kimi_linear.py``: projections, short
convolutions, gates, the chunked delta-rule recurrence, output norm and gate), forward
and backward together, what ``nn.remat`` computes again included (part of the two
``model_*_device_ms``).  A program from before the scope (``core/scopes.py::KDA``, PR 35)
has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "KDA", None)
    return None if scope is None else scope_ms(obs, scope)
