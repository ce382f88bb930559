"""Median device time of one train step under ``faa_gqa``, nested in ``faa_model``: the
grouped-query attention mixers of the layers this chip holds (``models/nemotron_h.py``:
the four projections, the key-value heads repeated to every query head, the causal
softmax of ``ops/attention.py`` — its fused kernels at the configuration's shapes — and
the sum of a group's gradient), forward and backward together, what ``nn.remat`` computes
again included (part of the two ``model_*_device_ms``).  A program from before the scope
(``core/scopes.py::GQA``, PR 42) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "GQA", None)
    return None if scope is None else scope_ms(obs, scope)
