"""Median device time of one train step in the mixers' projections: what is under
``faa_mixer_proj`` (``models/token_blocks.py::proj``: the products of a mixer's input or
output with one of its weight matrices — ``q_proj`` .. ``o_proj``, ``in_proj`` /
``out_proj`` — always nested in the mixer's own scope) in all three passes, forward,
backward and what ``nn.remat`` computes again.  A mixer's scope less its core's less this is
what is neither kernel nor product: norms, rotary, gates, taps, casts and layout copies.  A
program from before the scope (``core/scopes.py::MIXER_PROJ``, PR 51) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MIXER_PROJ", None)
    return None if scope is None else scope_ms(obs, scope)
