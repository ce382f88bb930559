"""Median device time of one train step under ``faa_aug_jitter`` and
``faa_aug_lighting``, both nested in ``faa_aug_fixed`` (part of
``aug_fixed_device_ms``): the ImageNet stack's ColorJitter (brightness, contrast and
saturation in a drawn order: under ``vmap`` all six orders and a select) and the PCA
lighting noise (``ops/preprocess_imagenet.py``).  A program from before the scopes
(``core/scopes.py::AUG_JITTER``, PR 32) has nothing to read."""

from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    names = program_scopes()
    if not (hasattr(names, "AUG_JITTER") and hasattr(names, "AUG_LIGHTING")):
        return None
    jitter = scope_ms(obs, names.AUG_JITTER)
    return None if jitter is None else jitter + scope_ms(obs, names.AUG_LIGHTING)
