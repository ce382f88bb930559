"""TPU-hours accounting.

The reference's observability is wall-clock only: total run time
(``train.py:345-354``), per-phase stopwatches in search
(``search.py:139-140,172,206,263``) and the per-trial "GPU-seconds"
``wall x device_count`` that feed its headline GPU-hours numbers
(``search.py:132-133,251-252``).  The TPU-native equivalent:

- :class:`PhaseStopwatch` — named phase accounting in device-seconds
  (``wall x device_count``), the reference's GPU-hours ledger
  generalized.

Device time inside a step is read from a profiler trace by the named
scopes of ``core/scopes.py`` (docs/OBSERVABILITY.md), not timed here.
"""

from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["PhaseStopwatch"]


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
