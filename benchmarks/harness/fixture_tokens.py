"""A seeded token data set in the layout the program reads.

``<dataroot>/tokens/train.npy`` and ``test.npy``: int32 ids ``[N, T + 1]``
(a sequence and the id that follows its last token), read by
``data/datasets.py::load_dataset("tokens")`` as a tokenizer's packed
output would be.

The ids are learnable, so the training loss falls inside a window: a
Zipf unigram over the ``ids`` this chip holds (rank r with weight
``1 / r^zipf_exponent``: what a vocabulary's head looks like), and a
first-order Markov table drawn from ``--seed`` that gives every id
``successors`` followers, themselves unigram draws.  A token is, with
probability ``follow``, one of its predecessor's followers and otherwise
a fresh unigram draw.  No padding and no document boundary: a sequence
is one stream.  A model that has learned the unigram alone reads the
unigram's entropy, one that has learned the table less.
"""

from __future__ import annotations

import json
import os

import numpy as np


def unigram(spec: dict) -> np.ndarray:
    """The Zipf distribution over the held ids, float64 ``[ids]``."""
    ranks = np.arange(1, int(spec["ids"]) + 1, dtype=np.float64)
    weights = ranks ** -float(spec["zipf_exponent"])
    return weights / weights.sum()


def make_split(spec: dict, count: int, table: np.ndarray,
               rng: np.random.Generator) -> np.ndarray:
    """``[count, length + 1]`` int32 ids from the chain `table` ``[ids,
    successors]`` describes."""
    length, follow = int(spec["length"]), float(spec["follow"])
    cdf = np.cumsum(unigram(spec))
    fresh = np.minimum(np.searchsorted(cdf, rng.random((count, length + 1))),
                       len(cdf) - 1).astype(np.int32)
    follows = rng.random((count, length + 1)) < follow
    which = rng.integers(0, table.shape[1], (count, length + 1))
    ids = np.empty((count, length + 1), np.int32)
    ids[:, 0] = fresh[:, 0]
    for t in range(1, length + 1):
        ids[:, t] = np.where(follows[:, t], table[ids[:, t - 1], which[:, t]],
                             fresh[:, t])
    return ids


def write_fixture(dataroot: str, spec: dict, seed: int) -> dict:
    """Write the fixture for `seed` under `dataroot` (overwriting another
    seed's) and say what was written: the counts and the unigram's
    entropy in nats.  A fixture already there for the same seed and spec
    is left alone."""
    base = os.path.join(dataroot, "tokens")
    stamp_path = os.path.join(base, "fixture.json")
    probs = unigram(spec)
    want = {"seed": int(seed), "spec": spec}
    wrote = {"train": int(spec["train"]), "test": int(spec["test"]),
             "length": int(spec["length"]), "ids": int(spec["ids"]),
             "unigram_entropy_nats": float(-(probs * np.log(probs)).sum())}
    try:
        with open(stamp_path) as fh:
            if json.load(fh) == want:
                return wrote
    except (OSError, ValueError):
        pass
    os.makedirs(base, exist_ok=True)
    if os.path.exists(stamp_path):
        os.remove(stamp_path)  # a torn rewrite must not read as complete
    rng = np.random.default_rng(int(seed))
    cdf = np.cumsum(probs)
    table = np.minimum(
        np.searchsorted(cdf, rng.random((int(spec["ids"]), int(spec["successors"])))),
        len(cdf) - 1).astype(np.int32)
    for split in ("train", "test"):
        np.save(os.path.join(base, f"{split}.npy"),
                make_split(spec, int(spec[split]), table, rng))
    with open(stamp_path, "w") as fh:
        json.dump(want, fh)
    return wrote
