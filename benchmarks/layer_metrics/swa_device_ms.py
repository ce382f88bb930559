"""Median device time of one train step under ``faa_swa``, nested in ``faa_gqa`` and
``faa_model``: the window layers' mixers of the blocks this chip holds (``models/afmoe.py``:
the five projections, the two norms a head, rotary, the causal softmax of
``ops/attention.py`` under a key span — its fused kernels, whose loops start at the band's
trailing edge and not at key 0 — the gate and the sum of a group's gradient), forward and
backward together, what ``nn.remat`` computes again included (part of ``gqa_device_ms`` and
of the two ``model_*_device_ms``).  A program from before the scope
(``core/scopes.py::SWA``, PR 46) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SWA", None)
    return None if scope is None else scope_ms(obs, scope)
