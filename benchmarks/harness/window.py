"""What every program's window needs: the process's start time, the
count of compilations, the profiler around a stretch of the window, and
the comparison with the plain reference that decides ``correct``."""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmarks.harness.observed import CLOCK_MARKER


def process_start_wall() -> float:
    """``time.time()`` at which this process was created, from
    ``/proc/self/stat`` (so interpreter start-up counts as set-up);
    the time of this call where ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat") as fh:
            # the command may hold spaces: fields are counted after ")"
            fields = fh.read().rsplit(")", 1)[1].split()
        ticks = float(fields[19])  # field 22, starttime
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.time()


def compile_requests(stats: dict | None = None) -> int:
    """Executables this process has asked the compiler or the persistent
    cache for, so far (from `stats`, a ``compile_cache_stats()`` stamp,
    or a fresh one).  Every XLA compile goes through the program's cache
    listener (``core/compilecache.py``) as a hit or a miss, so a
    difference of this number over a stretch counts its compilations."""
    if stats is None:
        from fast_autoaugment_tpu.core.compilecache import compile_cache_stats

        stats = compile_cache_stats()
    return int(stats["hits"]) + int(stats["misses"])


def window_plan(cell) -> tuple[float, "Tracer | None"]:
    """``(seconds, tracer)``: a traced run measures the traced stretch
    (``trace_seconds`` of the traffic file, ``--seconds`` at most), an
    untraced one ``--seconds``."""
    if not cell.trace:
        return cell.seconds, None
    return (min(cell.seconds, float(cell.traffic["trace_seconds"])),
            Tracer(os.path.join(cell.work, "trace")))


class Tracer:
    """``jax.profiler`` over a stretch of the window, with the Python
    tracer off (it would slow the very dispatch loop being measured) and
    a marker span that ties ``time.perf_counter`` to the trace's clock."""

    def __init__(self, directory: str):
        self.directory = directory
        self.marker_perf: float | None = None
        self.started_perf: float | None = None
        self.stopped_perf: float | None = None

    @property
    def running(self) -> bool:
        return self.started_perf is not None and self.stopped_perf is None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_perf = time.perf_counter()
        self.marker_perf = time.perf_counter()
        with jax.profiler.TraceAnnotation(CLOCK_MARKER):
            pass

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.stopped_perf = time.perf_counter()


def logits_agreement(system: np.ndarray, reference: np.ndarray,
                     tolerance: float) -> dict:
    """The check that decides ``correct`` for a model: the largest
    difference between two sets of logits, over the largest reference
    logit in magnitude.  Logits and not predicted classes, because with
    few training steps many classes score alike and the largest flips on
    rounding."""
    system = np.asarray(system, np.float64)
    reference = np.asarray(reference, np.float64)
    if system.shape != reference.shape:
        return {"ok": False, "why": f"shapes {system.shape} vs {reference.shape}"}
    scale = float(np.max(np.abs(reference)))
    gap = float(np.max(np.abs(system - reference)))
    rel = gap / scale if scale > 0 else float("inf")
    return {"ok": bool(np.isfinite(rel) and rel <= tolerance),
            "max_abs_diff": gap, "max_abs_reference": scale,
            "relative_gap": rel, "tolerance": tolerance,
            "images": int(system.shape[0])}


def reference_check(cell, conf, params, batch_stats, images) -> dict:
    """The system's model (``get_model`` and the evaluation
    preprocessing, jitted as the evaluation step runs them) against the
    configuration's plain reference, on `images`."""
    import jax

    from fast_autoaugment_tpu.models import get_model, num_class
    from fast_autoaugment_tpu.ops.preprocess import cifar_eval_batch

    model_conf = dict(conf["model"], dataset=conf["dataset"])
    model_conf.setdefault("precision", conf.get("precision", "f32"))
    model = get_model(model_conf, num_class(conf["dataset"]))
    system = jax.jit(lambda p, s, x: model.apply(
        {"params": p, "batch_stats": s}, cifar_eval_batch(x), train=False))(
            params, batch_stats, images)
    reference = cell.module("references", cell.config["reference"])
    return logits_agreement(
        np.asarray(system),
        reference.forward(jax.device_get(params), jax.device_get(batch_stats),
                          images, cell.config["model"]),
        float(cell.config["logit_tolerance"]))
