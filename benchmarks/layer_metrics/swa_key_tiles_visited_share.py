"""Of the key tiles the causal half of a window layer's attention core holds, the percent
its loops meet: the program's trace-time counter ``faa_attention_key_tiles_total{span,
kind}`` (``ops/attention.py``: for every core traced, the key tiles its loops meet over one
head's sequence, ``kind="visited"``, beside the causal half's, ``kind="causal"``), the cores
with a key span alone (``span`` other than ``none``), visited over causal, read from the
program's registry after the run.  150 of 528 at 16,384 tokens, a tile of 512 and a span of
2,048: 28.4%; it reads 100 the day a change masks the band and does not skip it.  A ratio
of counts at trace time: how often a program was traced cancels.  A program from before the
counter (PR 46), or one that traced no core with a span, has nothing to read."""

META = {"layer": "models", "unit": "%", "source": "program_counter",
        "moves": "train_images_per_s"}

COUNTER = "faa_attention_key_tiles_total"


def read(obs):
    del obs
    try:
        from fast_autoaugment_tpu.core import telemetry
    except ImportError:
        return None
    tiles = {"visited": 0.0, "causal": 0.0}
    for key, value in telemetry.registry().counters_snapshot().items():
        if key.startswith(COUNTER + "{") and 'span="none"' not in key:
            tiles[key.split('kind="')[1].split('"')[0]] += value
    return 100.0 * tiles["visited"] / tiles["causal"] if tiles["causal"] else None
