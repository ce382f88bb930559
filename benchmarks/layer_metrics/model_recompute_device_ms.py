"""Median device time of one train step in what ``nn.remat`` computes again: the
instructions of ``faa_model`` and ``faa_loss``, with every scope a model nests under them,
whose path holds JAX's ``rematted_computation`` (``core/scopes.py::pass_of`` reads
``recompute``; ``models/token_blocks.py::remat_block`` keeps a block's input and the
attention cores' output and log-sum-exp and runs the rest of the block's forward pass a
second time in the backward one).  A part of ``model_backward_device_ms``, which files it
under ``transpose(jvp(...))``; ``harness/passes.py`` splits the same executions by pass.  A
program from before ``pass_of`` (PR 51) has nothing to read."""

from benchmarks.harness.passes import program_passes, scope_pass_ms

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    names = program_passes()
    if names is None:
        return None
    return scope_pass_ms(obs, (names.MODEL, names.LOSS), "recompute")
