"""Phase 3 (and phase 1): ``train_and_eval`` driven through its public
arguments, a window taken between two of its dispatch boundaries.

The trainer's loop is the trainer's: this file passes ``heartbeat=`` (the
trainer calls it after every dispatch and at every epoch boundary, and
lets its exception propagate) and ends the run with
``core.resilience.request_preemption()``, which makes the trainer write a
checkpoint at the next dispatch boundary and raise ``PreemptedError``.
Nothing of the epoch loop, the index feed or the step is re-implemented
here, so a change to any of them shows in the cell.

Set-up runs the trainer through its first epoch boundary before the
window opens: the boundary's host work (metric sync, logging the
learning rate) compiles a few small programs the first time, and nothing
may compile inside the window.  A boundary is recognised from outside as
a heartbeat with no dispatch since the last one.

JAX returns from a dispatch at the enqueue, and inside an epoch nothing
makes the trainer wait: on the chip the host enqueued a whole epoch of
one-second steps in milliseconds and then sat in the epoch-end sync (my
chip run, PR 22), so its heartbeats say nothing of where the device is,
and a stop request is seen an epoch late.  So every heartbeat enqueues a
marker, a one-element program that the device runs, in order, after the
dispatch before it, and waits for the marker ``max_dispatches_in_flight``
heartbeats back.  That bounds how far the host runs ahead (far enough
that the device never waits for it), lets the window open and close on a
marker — at a step the device has finished, a few steps past
``--seconds`` at most — and lets the preemption request be seen at once.
What it costs is one ~10-microsecond program per dispatch, the same on
both sides of any comparison.

With ``--trace 1`` the window is the traced stretch (``trace_seconds`` of
the traffic file), closed before the profiler writes its file.
"""

from __future__ import annotations

import collections
import inspect
import math
import os
import shutil
import time

from benchmarks.harness import window as win
from benchmarks.harness.device import device_barrier, memory_peak_bytes
from benchmarks.harness.fixture import write_fixture
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell


def bench_marker(x):
    """The marker program (its name shows on the trace's module line)."""
    return x + 1


class _Markers:
    """One tiny program per heartbeat, on every chip of the mesh, each
    queued behind the dispatch that preceded it."""

    def __init__(self, mesh, in_flight: int):
        import jax
        import jax.numpy as jnp
        from fast_autoaugment_tpu.parallel.mesh import replicated

        self.in_flight = in_flight
        self.fn = jax.jit(bench_marker)
        self.value = jax.device_put(jnp.zeros((), jnp.int32), replicated(mesh))
        self.queued: collections.deque = collections.deque()

    def mark_and_throttle(self) -> None:
        self.value = self.fn(self.value)
        self.queued.append(self.value)
        while len(self.queued) > self.in_flight:
            self.queued.popleft().block_until_ready()

    def wait_for_newest(self) -> None:
        self.value.block_until_ready()
        self.queued.clear()


class _Beat:
    """The heartbeat: counts dispatches from the program's own counter,
    opens the window after the warm-up, closes it after `seconds`."""

    def __init__(self, cell: Cell, devices: list, mesh, start_wall: float):
        from fast_autoaugment_tpu.core import telemetry

        traffic = cell.traffic
        self.devices, self.start_wall = devices, start_wall
        self.markers = _Markers(mesh, int(traffic["max_dispatches_in_flight"]))
        self.counter = telemetry.registry().counter(
            "faa_dispatches_total", label=traffic["dispatch_label"])
        self.warmup_after_boundary = int(traffic["warmup_dispatches_after_boundary"])
        self.seconds, self.tracer = win.window_plan(cell)
        self.state = "warmup"
        # the counter is the process's: count from where this run starts
        self.first_count = self.last_count = int(self.counter.value)
        self.boundary_at: int | None = None   # dispatch count at 1st boundary
        self.prev_beat: tuple[float, bool] | None = None  # (perf, was boundary)
        self.host_spans: list[tuple[str, float, float]] = []
        self.setup_s = self.t0 = self.t1 = None
        self.d0 = self.d1 = None
        self.compile_stats: dict = {}
        self.compiles0 = self.compiles1 = None
        self.memory_peak = 0

    def __call__(self) -> None:
        now = time.perf_counter()
        count = int(self.counter.value)
        boundary = count == self.last_count
        self.last_count = count
        self.markers.mark_and_throttle()
        if self.state == "open" and self.prev_beat is not None:
            self.host_spans.append((
                "epoch boundary" if self.prev_beat[1] else "dispatch loop",
                self.prev_beat[0], now))
        if self.state == "warmup":
            if boundary and self.boundary_at is None:
                self.boundary_at = count
            if (self.boundary_at is not None
                    and count - self.boundary_at >= self.warmup_after_boundary):
                self._open(count)
        elif self.state == "open" and now - self.t0 >= self.seconds:
            self._close(count)
        self.prev_beat = (time.perf_counter(), boundary)
        if self.state == "open":
            # the benchmark's own time inside this call (barriers, the
            # profiler starting) is not the trainer's
            self.host_spans.append(("benchmark", now, self.prev_beat[0]))

    def _open(self, count: int) -> None:
        from fast_autoaugment_tpu.core.compilecache import compile_cache_stats

        self.markers.wait_for_newest()
        device_barrier()
        self.compile_stats = compile_cache_stats()
        self.compiles0 = win.compile_requests(self.compile_stats)
        if self.tracer is not None:
            self.tracer.start()
        self.setup_s = time.time() - self.start_wall
        self.d0, self.t0 = count, time.perf_counter()
        self.state = "open"

    def _close(self, count: int) -> None:
        from fast_autoaugment_tpu.core.resilience import request_preemption

        self.markers.wait_for_newest()
        self.t1, self.d1 = time.perf_counter(), count
        self.compiles1 = win.compile_requests()
        self.memory_peak = memory_peak_bytes(self.devices)
        if self.tracer is not None:
            self.tracer.stop()
        self.state = "closed"
        request_preemption()


def training_top1(meta: dict) -> float | None:
    """The trainer's own training top-1 as its preemption checkpoint
    records it: the running mean of the epoch in progress (the partial
    sums a mid-epoch snapshot carries so that a resume continues them),
    or the finished epoch's where the stop fell on an epoch boundary."""
    sums = (meta.get("in_epoch") or {}).get("sums")
    if sums:
        return float(sums["top1"]) / float(sums["num"])
    value = (meta.get("metrics") or {}).get("top1_train")
    return None if value is None else float(value)


def no_compile_check(beat: _Beat) -> dict:
    """Nothing asked the compiler or the cache for a program in the window."""
    requests = beat.compiles1 - beat.compiles0
    return {"ok": requests == 0, "compile_requests": requests,
            "compared": win.compared(requests, "==", 0)}


def step_counter_check(meta: dict, counted: int) -> dict:
    """The preemption checkpoint's step counter is the dispatches counted."""
    return {"ok": meta.get("step") == counted,
            "checkpoint_step": meta.get("step"), "steps_counted": counted,
            "compared": win.compared(meta.get("step"), "==", counted)}


def learned_check(meta: dict, evaluated: dict, floor: float) -> dict:
    """That the steps counted were training: a finite test loss through the
    ``only_eval`` restore, and top-1 at `floor` or over in the better of
    two readings (the evaluation's, the trainer's own at the checkpoint)."""
    top1_train = training_top1(meta)
    loss_test = float(evaluated.get("loss_test", float("nan")))
    top1_test = float(evaluated.get("top1_test", float("nan")))
    best = max((v for v in (top1_test, top1_train)
                if v is not None and math.isfinite(v)), default=float("nan"))
    return {"ok": math.isfinite(loss_test) and best >= floor,
            "top1_train": top1_train, "top1_test": top1_test,
            "top1_must_reach": floor, "loss_test": loss_test,
            "num_test": evaluated.get("num_test"),
            "restored_steps": evaluated.get("steps"),
            "compared": win.compared(best, ">=", floor)}


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    from flax import serialization

    from fast_autoaugment_tpu.core.checkpoint import read_metadata
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.core.resilience import (
        PreemptedError,
        clear_preemption,
    )
    from fast_autoaugment_tpu.data.datasets import load_dataset
    from fast_autoaugment_tpu.models import num_class
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    traffic = cell.traffic
    configure_compile_cache()
    dataroot = write_fixture(os.path.join(cell.work, "data"), cell.fixture,
                             cell.seed)
    conf = Config(cell.conf_dict())
    mesh = make_mesh(devices)
    ckpt_dir = os.path.join(cell.work, "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    save_path = os.path.join(ckpt_dir, "model.msgpack")
    entry_args = dict(traffic.get("entry_args") or {})
    steps_per_dispatch = int(entry_args.get(
        "steps_per_dispatch",
        inspect.signature(train_and_eval).parameters["steps_per_dispatch"].default))

    beat = _Beat(cell, devices, mesh, start_wall)
    diverged = None
    clear_preemption()
    try:
        train_and_eval(conf, dataroot, save_path=save_path, mesh=mesh,
                       seed=cell.seed, heartbeat=beat,
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
    checks: dict[str, dict] = {}
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
    checks["no_compile_in_window"] = no_compile_check(beat)

    # -- outside the window: the weights the window ended on ------------
    meta = read_metadata(save_path) or {}
    counted = (beat.last_count - beat.first_count) * steps_per_dispatch
    checks["step_counter"] = step_counter_check(meta, counted)
    evaluated = train_and_eval(conf, dataroot, save_path=save_path, mesh=mesh,
                               seed=cell.seed, only_eval=True)
    # that the steps counted were training: top-1 over chance by the
    # traffic file's margin, in either of two readings.  Test top-1
    # through the evaluation path is erratic some tens of steps in, when
    # the running BatchNorm statistics lag the weights (14-57% at 36-54
    # steps, once with a loss of 7.05; 14 seeds, my chip runs, PR 22);
    # the trainer's own training top-1 at the checkpoint is steady.  A
    # collapsed model reads chance in both.
    checks["learned"] = learned_check(
        meta, evaluated,
        1.0 / num_class(conf["dataset"]) + float(traffic["accuracy_margin"]))

    with open(save_path, "rb") as fh:
        saved = serialization.msgpack_restore(fh.read())
    images = load_dataset(conf["dataset"], dataroot)[1].images[
        :int(traffic["reference_images"])]
    checks.update(win.reference_check(
        cell, conf, saved["params"], saved["batch_stats"], images))

    return Observed(
        cell=cell, devices=devices,
        end_to_end={"train_images_per_s": rate, "setup_s": beat.setup_s},
        window_s=window_s, attempted=steps, failed=0, checks=checks,
        compile_stats=beat.compile_stats,
        memory_peak_bytes=beat.memory_peak,
        work={"images_per_s_per_chip": rate, "passes": "train"},
        step_program=traffic["step_program"],
        trace_dir=beat.tracer.directory if beat.tracer else None,
        host_spans=beat.host_spans,
        marker_perf=beat.tracer.marker_perf if beat.tracer else None)
