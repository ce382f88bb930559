"""Median device time of one train step under ``faa_short_conv``, nested in ``faa_model``:
the short-convolution mixers of the blocks this chip holds (``models/lfm2_moe.py`` round
``models/token_blocks.py::ShortConvMixer``: ``in_proj`` 2,048 -> 6,144, the split, the gate
``B * z``, the three depthwise causal taps, the gate ``C * c``, ``out_proj``), forward and
backward together, what ``nn.remat`` computes again included (part of the two
``model_*_device_ms``).  A program from before the scope (``core/scopes.py::SHORT_CONV``,
PR 49) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SHORT_CONV", None)
    return None if scope is None else scope_ms(obs, scope)
