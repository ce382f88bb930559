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
            Tracer(os.path.join(cell.work, "trace"),
                   int(cell.traffic.get("host_tracer_level", 2))))


class Tracer:
    """``jax.profiler`` over a stretch of the window, with the Python
    tracer off (it would slow the very dispatch loop being measured) and
    a marker span that ties ``time.perf_counter`` to the trace's clock.
    `host_tracer_level` is the profiler's own (the traffic file's
    ``host_tracer_level``): at 0 the trace has no host plane, so the
    marker is not in it and the idle gaps come out ``unattributed``."""

    def __init__(self, directory: str, host_tracer_level: int = 2):
        self.directory = directory
        self.host_tracer_level = host_tracer_level
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
        options.host_tracer_level = self.host_tracer_level
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
            "images": int(system.shape[0]),
            "compared": compared(rel, "<=", tolerance)}


def compared(value, must: str, limit) -> dict:
    """One number a check compared, beside its limit: what a check hands
    over under its ``compared`` key (`must` is ``<=``, ``>=``, ``==`` or
    ``in`` for a ``[low, high]`` limit)."""
    return {"value": value, "must": must, "limit": limit}


def reference_check(cell, conf, params, batch_stats, images,
                    preprocess=None) -> dict[str, dict]:
    """The system's model (``get_model`` behind the evaluation
    preprocessing, jitted as the evaluation step runs them; `preprocess`
    is ``cifar_eval_batch`` unless the program brings its own) against the
    configuration's plain reference on `images`, as named checks:

    ``reference_logits``
        the system as deployed, judged by the configuration's
        ``logit_tolerance``.  On a TPU a float32 convolution takes
        bfloat16 operands (XLA's default precision), and rounding at
        every layer is chaotic: a sound run sits 0.2-0.9% from *any*
        second computation of the same function, a model with bfloat16
        activations 0.19-0.34% (my chip runs, PRs 22 and 28).  A limit
        wide enough for the sound runs catches a wrong weight or a
        missing layer and nothing finer.

    ``reference_logits_float32``
        for a configuration that states ``logit_tolerance_float32``: the
        same weights, images and reference with the system under
        ``jax.default_matmul_precision("highest")``.  XLA then rounds no
        operand, and what is left of the gap is what the *program*
        rounds: 1e-7 for float32 activations, 1e-3 once an activation, a
        BatchNorm or a weight is stored or computed in bfloat16, the
        nearest precision below the configuration's.
    """
    import jax

    from fast_autoaugment_tpu.models import get_model, num_class

    if preprocess is None:
        from fast_autoaugment_tpu.ops.preprocess import cifar_eval_batch as preprocess

    model_conf = dict(conf["model"], dataset=conf["dataset"])
    model_conf.setdefault("precision", conf.get("precision", "f32"))
    model = get_model(model_conf, num_class(conf["dataset"]))

    def system():  # traced anew each call: the precision in force is read then
        return np.asarray(jax.jit(lambda p, s, x: model.apply(
            {"params": p, "batch_stats": s}, preprocess(x), train=False))(
                params, batch_stats, images))

    plain = cell.module("references", cell.config["reference"]).forward(
        jax.device_get(params), jax.device_get(batch_stats), images,
        cell.config["model"])
    checks = {"reference_logits": logits_agreement(
        system(), plain, float(cell.config["logit_tolerance"]))}
    if "logit_tolerance_float32" in cell.config:
        with jax.default_matmul_precision("highest"):
            highest = system()
        checks["reference_logits_float32"] = logits_agreement(
            highest, plain, float(cell.config["logit_tolerance_float32"]))
    return checks
