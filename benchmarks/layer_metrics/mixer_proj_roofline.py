"""The mixers' projections' share of their roofline: the least time the chip could take for
what the *mathematics* of the products needs in one train step — the larger of their
operations over the chip's bfloat16 peak and their bytes over the memory's peak
(``harness/projections.py``: ``2 T n m`` a product forward and twice that backward; ``x``,
``W`` read and ``y`` written forward, the cotangent, ``x``, ``W`` read and ``dx``, ``dW``
written backward, in float32; nothing for what is computed again), over the layers this chip
holds — over the median device time of a step under ``faa_mixer_proj``
(``mixer_proj_device_ms``: forward, backward and what ``nn.remat`` computes again).  The
operations bound it at these sizes.  A share over 100% would mean products fused under a
root outside the scope.  A program from before the scope (``core/scopes.py::MIXER_PROJ``,
PR 51), or a family ``harness/projections.py`` has no table for, has nothing to read."""

from benchmarks.harness import projections
from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MIXER_PROJ", None)
    tokens = obs.work.get("tokens_a_step")
    if scope is None or not tokens:
        return None
    products = projections.held_products(obs.cell.config["flops"],
                                         obs.cell.config["model"])
    if not products:
        return None
    measured_ms = scope_ms(obs, scope)
    if not measured_ms:
        return None
    operations = sum(projections.operations(products, tokens, backward=b)
                     for b in (False, True))
    moved = sum(projections.moved_bytes(products, tokens, backward=b)
                for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
