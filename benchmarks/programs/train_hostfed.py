"""``train_and_eval`` on a lazy data set of JPEG files: the host-fed path
(decode, crop and resize on host threads, one batch a dispatch through the
single-step program), a window taken between two of its dispatches.

``programs/train.py``'s heartbeat, markers and window, by import and
unchanged; what differs is what that file cannot do for this path:

- the fixture is ``harness/fixture_jpeg.py``'s ImageFolder of JPEG files,
  and set-up builds the native loader once (``native_loader.build()``:
  what ``make -C native`` is to an install) and says which decoder ran;
- **the heartbeat holds the trainer to one beat a dispatch**.  The window
  opens and closes on per-dispatch beats, and a stop request is honoured
  at the next one.  A trainer that beats once an epoch (the host-fed
  branch before PR 32) cannot be measured and would train on toward its
  270 epochs: the first beat that finds more than ``steps_per_dispatch``
  dispatches since the beat before raises :class:`BeatsTooRarely`, which
  ``train_and_eval`` lets through, and the run exits non-zero there;
- a traced run leaves the profiler's host tracer off: the traffic file
  gives ``host_tracer_level`` 0 and says what the host plane costs on a
  path that copies a batch to the chip every step;
- the feed's counters (``faa_feed_*``, ``faa_decode_*``: the program's
  registry) are read when the window opens and when it closes, and what
  they say of the window goes into the ``feed`` check of every run and,
  through ``Observed.work``, to the readers ``feed_wait_ms`` and
  ``host_decode_images_per_s``;
- ``harness/window.py::reference_check``'s two comparisons go through
  ``imagenet_eval_batch`` on centre-cropped validation files.
"""

from __future__ import annotations

import inspect
import os
import shutil

import numpy as np

from benchmarks.harness import window as win
from benchmarks.harness.device import memory_peak_bytes
from benchmarks.harness.fixture_jpeg import write_fixture
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell
from benchmarks.programs import train

#: the program's counters the ``feed`` check reads
FEED_COUNTERS = ("faa_feed_", "faa_decode_")


class BeatsTooRarely(RuntimeError):
    """The trainer ran more than one dispatch between two heartbeats."""


def feed_counters() -> dict[str, float]:
    """The feed's counters now, ``{name{labels}: value}``; empty where the
    program has none."""
    from fast_autoaugment_tpu.core import telemetry

    return {key: value
            for key, value in telemetry.registry().counters_snapshot().items()
            if key.startswith(FEED_COUNTERS)}


class OneBeatADispatch:
    """``train._Beat`` behind a guard: every beat may find at most
    `steps_per_dispatch` new dispatches; and the feed's counters at the
    window's two ends."""

    def __init__(self, beat: "train._Beat", steps_per_dispatch: int):
        self.beat, self.allowed = beat, int(steps_per_dispatch)
        self.last = beat.first_count
        self.counters: dict[str, dict] = {}

    def __call__(self) -> None:
        count = int(self.beat.counter.value)
        if count - self.last > self.allowed:
            raise BeatsTooRarely(
                f"{count - self.last} dispatches since the heartbeat before "
                f"(at most {self.allowed} allowed): this trainer does not "
                f"beat after every dispatch of its host-fed loop, so no "
                f"window can be taken and no stop request is seen in time")
        self.last = count
        before = self.beat.state
        self.beat()
        if self.beat.state != before:  # "warmup" -> "open" -> "closed"
            self.counters[self.beat.state] = feed_counters()

    def feed_over_the_window(self) -> dict[str, float]:
        """What the feed's counters say of the window: milliseconds a
        step the trainer's loop was blocked in ``next()`` on the prefetch
        feed, and the images a second the decode worker gave while it
        worked (what the host could give, beside what the device took).
        Empty where the window has no two ends."""
        opened, closed = self.counters.get("open"), self.counters.get("closed")
        if opened is None or closed is None:
            return {}

        def rise(name):
            return sum(value - opened.get(key, 0.0) for key, value in
                       closed.items() if key.split("{", 1)[0] == name)

        batches, busy = rise("faa_feed_batches_total"), rise("faa_decode_seconds_total")
        out = {}
        if batches:
            out["wait_ms_a_step"] = 1e3 * rise("faa_feed_wait_seconds_total") / batches
        if busy:
            out["decode_images_per_s"] = rise("faa_decode_images_total") / busy
        return out


def validation_images(conf, dataroot: str, count: int) -> np.ndarray:
    """The first `count` validation files as the trainer's evaluation
    feed hands them over: decoded, centre-cropped, resized, uint8."""
    from fast_autoaugment_tpu.data.datasets import load_dataset
    from fast_autoaugment_tpu.data.pipeline import BatchIterator
    from fast_autoaugment_tpu.models import input_image_size
    from fast_autoaugment_tpu.ops.preprocess_imagenet import center_crop_box

    image = int(conf.get("imgsize", 0) or 0) or input_image_size(
        conf["dataset"], conf["model"]["type"])
    feed = BatchIterator(
        load_dataset(conf["dataset"], dataroot)[1],
        eval_box_fn=lambda rng, w, h: center_crop_box(w, h, image),
        imgsize=image)
    images, _, _ = next(iter(feed.eval_epoch(count)))
    return images


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    from flax import serialization

    from fast_autoaugment_tpu.core.checkpoint import read_metadata
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.core.resilience import (
        PreemptedError,
        clear_preemption,
    )
    from fast_autoaugment_tpu.data import native_loader
    from fast_autoaugment_tpu.models import num_class
    from fast_autoaugment_tpu.ops.preprocess_imagenet import imagenet_eval_batch
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    traffic = cell.traffic
    configure_compile_cache()
    # an install's `make -C native`; PIL threads where no compiler is to be had
    decoder = "native" if (native_loader.available() or native_loader.build()
                           ) else "pil"
    dataroot = os.path.join(cell.work, "data")
    wrote = write_fixture(dataroot, cell.fixture, cell.seed)
    conf = Config(cell.conf_dict())
    mesh = make_mesh(devices)
    ckpt_dir = os.path.join(cell.work, "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    save_path = os.path.join(ckpt_dir, "model.msgpack")
    entry_args = dict(traffic.get("entry_args") or {})
    steps_per_dispatch = int(entry_args.get(
        "steps_per_dispatch",
        inspect.signature(train_and_eval).parameters["steps_per_dispatch"].default))

    beat = train._Beat(cell, devices, mesh, start_wall)
    guarded = OneBeatADispatch(beat, steps_per_dispatch)
    diverged = None
    clear_preemption()
    try:
        train_and_eval(conf, dataroot, save_path=save_path, mesh=mesh,
                       seed=cell.seed, heartbeat=guarded,
                       evaluation_interval=int(traffic["evaluation_interval"]),
                       **entry_args)
        raise RuntimeError("the trainer finished its epochs before the "
                           "window closed: the configuration's horizon is "
                           "shorter than set-up plus window")
    except PreemptedError:
        pass
    except RuntimeError as e:
        if "diverged" not in str(e):
            raise
        diverged = str(e)
    finally:
        if beat.tracer is not None and beat.tracer.running:
            beat.tracer.stop()
        clear_preemption()

    global_batch = int(conf["batch"]) * len(devices)
    low, high = cell.fixture["mean_file_bytes_must_lie_in"]
    # the files have ImageNet's size, and the decoder is the install's: PIL
    # threads pass only where there is nothing to build the loader with
    feed = guarded.feed_over_the_window()
    checks: dict[str, dict] = {"feed": dict(
        feed,
        ok=low <= wrote["mean_file_bytes"] <= high and (
            decoder == "native" or not (shutil.which("make")
                                        and shutil.which("g++"))),
        decoder=decoder, cpu_count=os.cpu_count(), fixture=wrote,
        mean_file_bytes_must_lie_in=[low, high],
        compared=win.compared(wrote["mean_file_bytes"], "in", [low, high]))}
    if diverged or beat.state != "closed":
        steps = 0 if beat.d0 is None else (beat.last_count - beat.d0) * steps_per_dispatch
        checks["finite_loss"] = {"ok": False, "why": diverged or
                                 f"window state {beat.state!r} at exit"}
        return Observed(
            cell=cell, devices=devices, end_to_end={}, window_s=0.0,
            attempted=steps, failed=steps, checks=checks,
            compile_stats=beat.compile_stats,
            memory_peak_bytes=memory_peak_bytes(devices))

    window_s = beat.t1 - beat.t0
    steps = (beat.d1 - beat.d0) * steps_per_dispatch
    rate = steps * global_batch / window_s / len(devices)
    checks["finite_loss"] = {"ok": True}
    checks["no_compile_in_window"] = train.no_compile_check(beat)

    # -- outside the window: the weights the window ended on ------------
    meta = read_metadata(save_path) or {}
    counted = (beat.last_count - beat.first_count) * steps_per_dispatch
    checks["step_counter"] = train.step_counter_check(meta, counted)
    evaluated = train_and_eval(conf, dataroot, save_path=save_path, mesh=mesh,
                               seed=cell.seed, only_eval=True)
    # that the steps counted were training: top-1 over chance (one in the
    # head's 1,000 outputs) by the traffic file's margin, in either of
    # programs/train.py's two readings
    checks["learned"] = train.learned_check(
        meta, evaluated,
        1.0 / num_class(conf["dataset"]) + float(traffic["accuracy_margin"]))

    with open(save_path, "rb") as fh:
        saved = serialization.msgpack_restore(fh.read())
    checks.update(win.reference_check(
        cell, conf, saved["params"], saved["batch_stats"],
        validation_images(conf, dataroot, int(traffic["reference_images"])),
        preprocess=imagenet_eval_batch))

    return Observed(
        cell=cell, devices=devices,
        end_to_end={"train_images_per_s": rate, "setup_s": beat.setup_s},
        window_s=window_s, attempted=steps, failed=0, checks=checks,
        compile_stats=beat.compile_stats,
        memory_peak_bytes=beat.memory_peak,
        work=dict(feed, images_per_s_per_chip=rate, passes="train",
                  steps=steps),
        step_program=traffic["step_program"],
        trace_dir=beat.tracer.directory if beat.tracer else None,
        host_spans=beat.host_spans,
        marker_perf=beat.tracer.marker_perf if beat.tracer else None)
