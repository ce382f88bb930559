"""``programs/train.py`` as it is, and one more comparison after its checks:
``reference_logits_float32``, which holds the program's own arithmetic to the
precision the configuration states.

Why ``reference_logits`` alone cannot.  It runs the system as deployed: on a TPU a
float32 convolution takes bfloat16 operands (XLA's default precision).  Rounding at
every one of 26 layers is chaotic — an operand that lands on the other side of a
rounding boundary is off by 0.4%, not by the 1e-7 that moved it, and the next layer
rounds again — so a sound run sits 0.2-0.9% from *any* second computation of the
same function: from the float32 reference, and from a reference whose products
round the same operands the same way (0.11%; a single convolution of the two is
equal bit for bit; my chip runs, PR 28).  A model with bfloat16 activations sits
0.19% away.  No limit on that gap tells the two apart, and one wide enough for the
sound runs (2%) catches a wrong weight or a missing layer and nothing finer.

So this file asks the other question.  Under ``jax.default_matmul_precision(
"highest")`` XLA rounds no operand, and what is left of the gap is what the
*program* rounds: 1.8e-7 for float32 activations, 0.19% once an activation, a
BatchNorm or a weight is stored or computed in bfloat16 — the nearest precision
below the configuration's.  The same weights (the preemption checkpoint the window
ended on), the same images, the same reference and the same evaluation path as
``harness/window.py::reference_check``, which does the work; the limit is the
configuration's ``logit_tolerance_float32``.

A stop-gap with a date: the comparison belongs in ``reference_check`` itself, for
every cell, and that file is a ``benchmark`` PR's to edit (PERF.md, Open
questions).  Then this file and its traffic file go.
"""

from __future__ import annotations

import os

from benchmarks.harness import window as win
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell
from benchmarks.programs import train

CHECK = "reference_logits_float32"


def float32_check(cell: Cell, conf, params, batch_stats, images) -> dict:
    """``reference_check`` with no operand of a product rounded by XLA,
    judged by the configuration's ``logit_tolerance_float32``."""
    import jax

    with jax.default_matmul_precision("highest"):
        verdict = win.reference_check(cell, conf, params, batch_stats, images)
    limit = float(cell.config["logit_tolerance_float32"])
    gap = verdict.get("relative_gap", float("inf"))
    return dict(verdict, tolerance=limit, ok=bool(gap <= limit))


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    from flax import serialization

    from fast_autoaugment_tpu.data.datasets import load_dataset

    obs = train.run(cell, devices, start_wall)
    if "reference_logits" not in obs.checks:  # the run ended before its checks
        return obs
    conf = cell.conf_dict()
    with open(os.path.join(cell.work, "ckpt", "model.msgpack"), "rb") as fh:
        saved = serialization.msgpack_restore(fh.read())
    images = load_dataset(conf["dataset"], os.path.join(cell.work, "data"))[
        1].images[:int(cell.traffic["reference_images"])]
    obs.checks[CHECK] = float32_check(
        cell, conf, saved["params"], saved["batch_stats"], images)
    return obs
