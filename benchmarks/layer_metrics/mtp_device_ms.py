"""Median device time of one train step under ``faa_mtp``, nested in ``faa_model``: the
multi-token-prediction module this chip holds (``models/glm4_moe_lite.py``: the embedding of
the next token, the two norms, ``eh_proj``, the module's block — whose own ``faa_mla`` and
``faa_moe`` nest inside it and are counted by ``mla_device_ms`` and ``moe_device_ms`` too —
and its last norm), forward and backward together, what is computed again included; the
module's head and loss are under ``faa_lm_head`` and ``faa_loss`` with the main head's
(``lm_head_loss_device_ms``).  Part of the two ``model_*_device_ms``.  A program from before
the scope (``core/scopes.py::MTP``, PR 40) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MTP", None)
    return None if scope is None else scope_ms(obs, scope)
