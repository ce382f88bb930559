"""Median device time of one train step in the seven geometric operations of the
policy: ShearX/Y, TranslateX/Y, Rotate, TranslateX/YAbs.  Each builds a 2x3 matrix
under its ``faa_aug_op_<Name>`` and one resampling an op slot applies the matrices,
under ``faa_aug_warp`` (``ops/augment.py::_warp_affine_nearest``, PR 29): the metric
is the eight scopes together, the resampling all but the whole of it (part of
``aug_policy_device_ms``).  Before PR 34 the reader left ``faa_aug_warp`` out and
read the matrices alone, 0.008-0.011 ms: a jump in the ledger at PR 34 is the reader
corrected, not a slower kernel.  A program from before PR 29 has no ``faa_aug_warp``
and its seven scopes hold a gather each."""

from benchmarks.harness.scopes import family_ms

META = {"layer": "augmentation_kernels", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    return family_ms(obs, "geometric_ops")
