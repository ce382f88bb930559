"""Profiling, step timing, and TPU-hours accounting.

The reference's observability is wall-clock only: total run time
(``train.py:345-354``), per-phase stopwatches in search
(``search.py:139-140,172,206,263``) and the per-trial "GPU-seconds"
``wall x device_count`` that feed its headline GPU-hours numbers
(``search.py:132-133,251-252``).  TPU-native equivalents:

- :func:`trace` — ``jax.profiler`` trace capture around any region
  (view in TensorBoard/XProf); the reference has no profiler at all;
- :class:`StepTimer` — per-step wall timing with warmup skip, giving
  steady-state images/sec;
- :class:`PhaseStopwatch` — named phase accounting in device-seconds
  (``wall x device_count``), the reference's GPU-hours ledger
  generalized.
"""

from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["trace", "StepTimer", "PhaseStopwatch"]


@contextlib.contextmanager
def trace(logdir: str | None):
    """Capture a jax.profiler trace into `logdir` (no-op if None)."""
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Steady-state step timing: skips `warmup` steps (compilation),
    then tracks mean step time and throughput.

    With a `registry` (``core/telemetry.py``) and a `name`, every
    steady-state stop mirrors ``faa_step_seconds{timer=name}`` into the
    shared metrics registry — the same numbers any ``/metrics`` scrape
    or bench stamp reads."""

    def __init__(self, warmup: int = 3, *, name: str | None = None,
                 registry=None):
        self.warmup = warmup
        self.count = 0
        self.total = 0.0
        self._last = None
        self._name = name
        self._hist = None
        if registry is not None and name is not None:
            self._hist = registry.histogram(
                "faa_step_seconds", "steady-state per-step wall seconds",
                timer=name)

    def start(self):
        self._last = time.perf_counter()

    def stop(self, items: int = 0) -> float:
        dt = time.perf_counter() - self._last
        self.count += 1
        if self.count > self.warmup:
            self.total += dt
            self._items = getattr(self, "_items", 0) + items
            if self._hist is not None:
                self._hist.observe(dt)
        return dt

    @property
    def steps_timed(self) -> int:
        return max(0, self.count - self.warmup)

    @property
    def mean_step_seconds(self) -> float:
        return self.total / self.steps_timed if self.steps_timed else 0.0

    @property
    def items_per_second(self) -> float:
        return getattr(self, "_items", 0) / self.total if self.total else 0.0


class PhaseStopwatch:
    """Named-phase wall + device-seconds ledger (the reference's
    pystopwatch2 + GPU-hours accounting).

    With a `registry` (``core/telemetry.py``), every :meth:`stop`
    mirrors the accumulated totals into the shared metrics registry —
    ``faa_phase_wall_seconds{phase=name}`` and
    ``faa_phase_device_seconds{phase=name}`` gauges — so the
    device-hours the artifacts stamp and the numbers a ``/metrics``
    scrape reports come from ONE ledger (the
    ``device_secs_phase1_per_fold`` identity in ``search/driver.py`` is
    pinned to this class by tests)."""

    def __init__(self, device_count: int | None = None, *, registry=None):
        # which backend these device-seconds were measured on: a ledger
        # without provenance reads CPU wall-time as accelerator-hours.
        # Only queried when jax must be touched anyway (no explicit
        # device_count): an offline ledger with an explicit count must
        # not initialize a backend — a process that touches JAX takes
        # the chip from whoever needs it.
        if device_count is None:
            self.device_count = jax.device_count()
            dev0 = jax.devices()[0]
            self.backend = dev0.platform
            self.device_kind = getattr(dev0, "device_kind", dev0.platform)
        else:
            self.device_count = device_count
            self.backend = "unspecified"
            self.device_kind = "unspecified"
        self.phases: dict[str, float] = {}
        self._open: dict[str, float] = {}
        self._registry = registry

    @contextlib.contextmanager
    def phase(self, name: str):
        self.start(name)
        try:
            yield
        finally:
            self.stop(name)

    def start(self, name: str):
        self._open[name] = time.time()

    def stop(self, name: str):
        if name in self._open:
            self.phases[name] = self.phases.get(name, 0.0) + (time.time() - self._open.pop(name))
            if self._registry is not None:
                w = self.phases[name]
                self._registry.gauge(
                    "faa_phase_wall_seconds",
                    "accumulated wall seconds per named phase",
                    phase=name).set(w)
                self._registry.gauge(
                    "faa_phase_device_seconds",
                    "accumulated wall x device_count per named phase",
                    phase=name).set(w * self.device_count)

    def wall_seconds(self, name: str) -> float:
        return self.phases.get(name, 0.0)

    def device_seconds(self, name: str) -> float:
        return self.wall_seconds(name) * self.device_count

    def device_hours(self, name: str) -> float:
        return self.device_seconds(name) / 3600.0

    def summary(self) -> dict:
        out = {
            name: {
                "wall_sec": round(w, 2),
                "device_sec": round(w * self.device_count, 2),
                "device_hours": round(w * self.device_count / 3600.0, 4),
            }
            for name, w in self.phases.items()
        }
        out["_meta"] = {"backend": self.backend,
                        "device_kind": self.device_kind,
                        "device_count": self.device_count}
        return out
