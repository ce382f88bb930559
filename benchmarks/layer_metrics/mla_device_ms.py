"""Median device time of one train step under ``faa_mla``, nested in ``faa_model``: the
latent-attention mixers of the layers this chip holds (``models/kimi_linear.py``: the
query, latent and key-value projections, the blocked causal softmax of
``ops/attention.py``, the output projection), forward and backward together, what is
computed again included (part of the two ``model_*_device_ms``).  A program from before
the scope (``core/scopes.py::MLA``, PR 35) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MLA", None)
    return None if scope is None else scope_ms(obs, scope)
