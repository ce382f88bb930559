"""The device as JAX reports it: the platform gate, the peaks table,
peak memory, and the barrier a window opens and closes on."""

from __future__ import annotations

import os

from benchmarks.harness.spec import load_json

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


class NoAcceleratorError(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for.
    There is no fallback: the run prints no result."""


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by the exact ``device_kind``.
    A kind that is not in the table is an error, never a default."""
    table = load_json(_PEAKS_FILE)
    if device_kind not in table:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}: add it "
            f"to {_PEAKS_FILE} with its source")
    return table[device_kind]


def require_devices(chips: int, *, platform: str = "tpu") -> list:
    """The first `chips` devices, or :class:`NoAcceleratorError`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoAcceleratorError(
            f"JAX reports platform {devices[0].platform!r}, not {platform!r}")
    if len(devices) < chips:
        raise NoAcceleratorError(
            f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices[:chips]


def device_stamp(devices: list) -> dict:
    import jax

    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices: list) -> int:
    """Peak bytes occupied on the fullest of `devices` (0 where the
    backend keeps no such statistic, as the CPU backend does not).

    On a TPU the allocator counts two things apart (read off a v5e, PR
    22): ``bytes_in_use`` are buffers — arguments, outputs, the resident
    data set — and ``bytes_reserved`` is the memory set aside for loaded
    programs, their temporaries above all (the activations a training
    step keeps for its backward pass live there).  ``peak_bytes_in_use``
    alone read 0.33 GB under a step program with 6.4 GB of temporaries.
    What a chip holds is the sum; taken when the window closes, with the
    step program loaded, and never below the buffers' own peak."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        held = (int(stats.get("bytes_in_use", 0))
                + int(stats.get("bytes_reserved", 0)))
        peak = max(peak, held, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_barrier() -> None:
    """Return once every array alive in this process is computed.

    JAX returns from a dispatch at the enqueue, so a clock read right
    after it times the queue.  Every program the entry points enqueue
    leaves live outputs (the carried state, the metric handles), so
    waiting on all live arrays waits for all enqueued work."""
    import jax

    for array in jax.live_arrays():
        try:
            array.block_until_ready()
        except RuntimeError:
            pass  # donated to a later dispatch since the list was taken
