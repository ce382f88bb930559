"""Median device time of one train step under ``faa_mamba2``, nested in ``faa_model``: the
Mamba-2 mixers of the layers this chip holds (``models/nemotron_h.py``: ``in_proj``, the
causal convolution, the step's softplus, the chunked state-space scan of ``ops/ssd.py``,
the gated grouped norm, ``out_proj``), forward and backward together, what ``nn.remat``
computes again included (part of the two ``model_*_device_ms``).  A program from before
the scope (``core/scopes.py::MAMBA2``, PR 42) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MAMBA2", None)
    return None if scope is None else scope_ms(obs, scope)
