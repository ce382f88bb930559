"""AOT-compiled policy application + the batch-coalescing server.

Two layers:

:class:`AotPolicyApplier` — the learned policy baked into
ahead-of-time-compiled executables (``jax.jit(...).lower().compile()``
through :func:`core.compilecache.aot_compile`) over a SMALL fixed set
of padded batch shapes.  Compile cost lands entirely at load time (and,
with the persistent compile cache enabled, is a deserialization after
the first process); the serving loop only ever dispatches — the
Anakin/Podracer execution style (PAPERS.md, arXiv:2104.06272).  Two
kernels from ``ops/augment.py``:

- ``exact``: per-image keys, ``vmap`` of the per-image apply path —
  :func:`~fast_autoaugment_tpu.ops.augment.apply_policy_scalar_single`
  for a single-sub policy (scalar ``lax.switch`` dispatch, the fast
  shape) or :func:`~fast_autoaugment_tpu.ops.augment.apply_policy` for
  multi-sub.  Lane i depends ONLY on (image i, key i), so padded lanes
  cannot leak into results by construction, and every served output is
  bitwise what a direct ``apply_policy(image, policy, key)`` call
  produces — the contract ``tests/test_serve.py`` holds.
- ``grouped``: one key per dispatch,
  :func:`~fast_autoaugment_tpu.ops.augment.apply_policy_batch_grouped`
  — the PR-3 scalar-dispatch kernel for multi-sub policies (one switch
  branch executes; stratified per-chunk sub-policy draws with identical
  per-image marginals).  Served outputs match the grouped kernel run on
  the same padded batch, sliced to the real rows.

:class:`PolicyServer` — a request-coalescing queue in front of the
applier: requests accumulate until ``max_batch`` images or
``max_wait_ms`` after the first arrival, the batch pads UP to the
smallest AOT shape that holds it, ONE program dispatches, and results
scatter back to each request in FIFO order.  That is the
latency/throughput knob heavy traffic needs: big offered load rides the
large shapes at full device efficiency, a lone request still completes
within ``max_wait_ms`` + one dispatch.

The server is OVERLOAD-SAFE (docs/RESILIENCE.md "Serving under
overload"): admission is non-blocking and fail-fast (bounded queue,
typed :class:`ServerOverloadedError`), per-request deadlines shed
already-dead work before it reaches the device, depth/age watermarks
flip the drain order to adaptive-LIFO under sustained saturation, a
circuit breaker (:class:`~fast_autoaugment_tpu.core.resilience.
CircuitBreaker`) contains a failing/hanging backend, and
:meth:`PolicyServer.swap_applier` hot-reloads a new policy with zero
dropped requests and no half-policy batch.  Every knob defaults off =
the clean-weather PR-7 stream.
"""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Sequence

import numpy as np

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.core.resilience import CircuitBreaker, CircuitOpenError
from fast_autoaugment_tpu.core.telemetry import mono
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = ["AotPolicyApplier", "PolicyServer", "ServeError",
           "ServerOverloadedError", "ServerStoppedError",
           "DeadlineExpiredError", "TenantNotResidentError",
           "CircuitOpenError", "TenantPool",
           "DEFAULT_SHAPES", "pick_shape", "policy_digest"]

logger = get_logger("faa_tpu.serve")

#: padded batch shapes the applier AOT-compiles by default: powers of
#: four-ish so padding waste stays < 4x at every load level
DEFAULT_SHAPES = (1, 8, 32, 128)

#: bucket schema for ``faa_serve_stage_seconds`` — the data-plane
#: stages are µs-to-ms scale, far below DEFAULT_BUCKETS_SEC's 1ms floor
_STAGE_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2,
                  0.1, 0.5)

#: per-process server index: labels each PolicyServer's registry
#: counters so multiple instances (tests, embedders) never share counts
_SERVER_SEQ = 0
_SERVER_SEQ_LOCK = threading.Lock()


class ServeError(RuntimeError):
    """A serving dispatch failed; carried to every coalesced request."""


def pick_shape(shapes: Sequence[int], n: int) -> int:
    """The smallest AOT shape holding `n` images (callers chunk at the
    largest shape first, so `n` <= max(shapes) always)."""
    for s in shapes:
        if s >= n:
            return s
    raise ValueError(f"batch of {n} exceeds the largest AOT shape "
                     f"{max(shapes)} — chunk before dispatching")


def policy_digest(policy) -> str:
    """The canonical 12-hex policy identity: sha256 over the float32
    ``[num_sub, num_op, 3]`` tensor's shape and bytes.

    ONE digest names one AOT-warm policy everywhere in the serving
    plane: the ``X-FAA-Policy-Digest`` request header selects the
    tenant, the tenancy LRU keys residents by it, and the router's
    rendezvous hash maps it to the replicas most likely to hold that
    tenant warm (docs/SERVING.md)."""
    arr = np.ascontiguousarray(np.asarray(policy, np.float32))
    h = hashlib.sha256()
    h.update(str(arr.shape).encode())
    h.update(arr.tobytes())
    return h.hexdigest()[:12]


def _acc_stage(stages: dict | None, name: str, sec: float) -> None:
    """Accumulate one stage wall into the caller's per-dispatch stage
    dict (None = instrumentation off for this call)."""
    if stages is not None:
        stages[name] = stages.get(name, 0.0) + sec


class _AsyncApply:
    """An un-materialized :meth:`AotPolicyApplier.apply` result: every
    chunk has been DISPATCHED (JAX async dispatch — device work is in
    flight) but nothing has been copied back.  ``materialize()``
    blocks on the device and assembles the ``[n, H, W, C]`` float32
    output.  The double-buffered server holds one of these per
    in-flight batch and materializes it only after the NEXT batch has
    been staged and dispatched."""

    __slots__ = ("n", "tail", "parts")

    def __init__(self, n: int, tail: tuple, parts: list):
        self.n = int(n)
        self.tail = tuple(tail)
        self.parts = parts  # [(padded_device_result, lo, hi), ...]

    def materialize(self, stages: dict | None = None) -> np.ndarray:
        t0 = mono()
        out = np.empty((self.n,) + self.tail, np.float32)
        for got, lo, hi in self.parts:
            out[lo:hi] = np.asarray(got)[:hi - lo]
        self.parts = []
        _acc_stage(stages, "scatter", mono() - t0)
        return out


class _EagerApply:
    """A pre-materialized result behind the :class:`_AsyncApply`
    interface, for duck-typed appliers that expose only ``apply``."""

    __slots__ = ("out",)

    def __init__(self, out: np.ndarray):
        self.out = out

    def materialize(self, stages: dict | None = None) -> np.ndarray:
        return self.out


class AotPolicyApplier:
    """The learned policy as a set of AOT-compiled executables.

    ``policy`` is the ``[num_sub, num_op, 3]`` tensor
    (``policies.archive.policy_to_tensor``); it is baked into the
    compiled programs as a constant — a serving process loads ONE
    policy and serves it, which is what lets XLA fold the op table.

    ``dispatch``: ``"exact"`` / ``"grouped"`` / ``"auto"`` (exact for a
    single-sub policy — it IS the scalar path there — grouped
    otherwise).  ``shapes`` are the padded batch sizes compiled,
    ascending.  ``watchdog`` (optional
    :class:`~fast_autoaugment_tpu.core.watchdog.DispatchWatchdog`) gets
    every serving label marked compile-warm — the executables are
    AOT-loaded, so their first dispatch must not inherit the blind
    compile allowance.
    """

    def __init__(self, policy, *, image: int = 32, channels: int = 3,
                 shapes: Sequence[int] = DEFAULT_SHAPES,
                 dispatch: str = "auto", groups: int = 8, watchdog=None,
                 donate: bool = False):
        import jax
        import jax.numpy as jnp

        from fast_autoaugment_tpu.core.compilecache import aot_compile
        from fast_autoaugment_tpu.ops.augment import (
            apply_policy,
            apply_policy_batch_grouped,
            apply_policy_scalar_single,
        )

        policy = jnp.asarray(np.asarray(policy, np.float32))
        if policy.ndim != 3 or policy.shape[-1] != 3:
            raise ValueError(
                f"policy must be [num_sub, num_op, 3], got {policy.shape}")
        self.policy = policy
        #: the serving-plane identity of this applier's policy (tenancy
        #: LRU key, router affinity key, X-FAA-Policy-Digest value)
        self.digest = policy_digest(np.asarray(policy))
        self.num_sub = int(policy.shape[0])
        if dispatch == "auto":
            dispatch = "exact" if self.num_sub == 1 else "grouped"
        if dispatch not in ("exact", "grouped"):
            raise ValueError(f"dispatch must be exact/grouped/auto, "
                             f"got {dispatch!r}")
        self.dispatch = dispatch
        self.groups = max(1, int(groups))
        self.image, self.channels = int(image), int(channels)
        self.shapes = tuple(sorted(set(int(s) for s in shapes)))
        if not self.shapes or self.shapes[0] < 1:
            raise ValueError(f"need at least one positive shape, "
                             f"got {shapes!r}")
        self.max_batch = self.shapes[-1]
        self._watchdog = watchdog

        if dispatch == "exact":
            per_image = (apply_policy_scalar_single if self.num_sub == 1
                         else apply_policy)

            def kernel(images, keys):
                return jax.vmap(per_image, in_axes=(0, None, 0))(
                    images, policy, keys)
        else:
            def kernel(images, key):
                return apply_policy_batch_grouped(
                    images, policy, key, groups=self.groups)

        #: donated-buffer dispatch (opt-in): the executables alias
        #: input onto output memory (``donate_argnums=(0,)``) and the
        #: host pads into STANDING double-buffered staging arrays —
        #: steady-state dispatch allocates nothing per request.  OFF =
        #: the PR-7 bit-for-bit path (fresh pad allocation, undonated).
        self.donate = bool(donate)
        #: per-shape compile evidence: {shape: {"sec", "verdict"}} — the
        #: bench stamps it next to the compile_cache block
        self.compile_log: dict[int, dict] = {}
        self._exec: dict[int, object] = {}
        img_dt = jnp.float32
        for s in self.shapes:
            spec_img = jax.ShapeDtypeStruct(
                (s, self.image, self.image, self.channels), img_dt)
            if dispatch == "exact":
                spec_key = jax.ShapeDtypeStruct((s, 2), jnp.uint32)
            else:
                spec_key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            label = f"serve_{dispatch}_b{s}"
            self._exec[s], rec = aot_compile(  # robust: allow — startup-only: one AOT executable per padded batch shape, never in the dispatch path
                kernel, label=label, example_args=(spec_img, spec_key),
                donate_argnums=((0,) if self.donate else None))
            self.compile_log[s] = rec
            if watchdog is not None:
                # AOT-loaded: the first dispatch is compile-free and
                # must not hide behind the 600s compile window
                watchdog.mark_compile_warm(label)
        # two alternating host staging buffers per AOT shape: batch k+1
        # pads into the slot batch k-1 used while k's donated buffer is
        # still owned by the device — the writer never touches a buffer
        # whose dispatch might still read it (double-buffer invariant,
        # pinned by tests/test_serve_donation.py)
        self._staging: dict[int, list] = {}
        self._staging_keys: dict[int, list] = {}
        self._slot = 0
        if self.donate:
            for s in self.shapes:
                geom = (s, self.image, self.image, self.channels)
                self._staging[s] = [np.zeros(geom, np.float32)
                                    for _ in range(2)]
                if dispatch == "exact":
                    self._staging_keys[s] = [np.zeros((s, 2), np.uint32)
                                             for _ in range(2)]
        logger.info(
            "AOT policy applier ready: %d sub-policies, dispatch=%s, "
            "shapes=%s, donate=%s, compile %s",
            self.num_sub, dispatch, list(self.shapes), self.donate,
            {s: r["sec"] for s, r in self.compile_log.items()})

    def _pad(self, arr: np.ndarray, target: int) -> np.ndarray:
        pad = target - arr.shape[0]
        if pad <= 0:
            return arr
        return np.concatenate(
            [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])

    def _stage(self, images: np.ndarray, keys: np.ndarray | None,
               s: int) -> tuple[np.ndarray, np.ndarray | None]:
        """Pad into the next standing staging slot (donated path): copy
        the batch in, ZERO the pad rows (a reused buffer must never
        leak the previous batch into the padded lanes), flip the slot.
        No allocation — ``np.copyto`` into preallocated arrays."""
        n = images.shape[0]
        slot = self._slot
        self._slot = 1 - slot
        buf = self._staging[s][slot]
        np.copyto(buf[:n], images)
        if n < s:
            buf[n:] = 0.0
        kbuf = None
        if keys is not None:
            kbuf = self._staging_keys[s][slot]
            np.copyto(kbuf[:n], keys)
            if n < s:
                kbuf[n:] = 0
        return buf, kbuf

    def apply(self, images: np.ndarray, keys: np.ndarray,
              stages: dict | None = None) -> np.ndarray:
        """Apply the policy to ``images [n, H, W, C]`` (uint8 or
        integral float32 in [0, 255]).

        ``exact`` dispatch: `keys` is ``[n, 2]`` uint32 — one PRNG key
        per image; row i of the output is bitwise
        ``apply_policy(images[i], policy, keys[i])``.  ``grouped``
        dispatch: `keys` is a single ``[2]`` key for the whole
        dispatch.  Batches larger than the largest AOT shape are
        chunked; smaller ones pad up (zero images / zero keys in the
        padded lanes, results sliced away).  Returns float32
        integral-valued images.  `stages` (optional dict) accumulates
        per-stage walls (pad / h2d / dispatch / scatter seconds) for
        the ``faa_serve_stage_seconds`` family.
        """
        return self.apply_async(images, keys,
                                stages=stages).materialize(stages=stages)

    def apply_async(self, images: np.ndarray, keys: np.ndarray,
                    stages: dict | None = None) -> "_AsyncApply":
        """The pipelined half of :meth:`apply`: dispatches every chunk
        to the device WITHOUT materializing results (JAX async
        dispatch) and returns a handle whose ``materialize()`` blocks
        and scatters.  The double-buffered server dispatches batch
        k+1's chunks while batch k's handle is still computing."""
        images = np.asarray(images)
        if images.ndim != 4:
            raise ValueError(f"images must be [n, H, W, C], got "
                             f"{images.shape}")
        expect = (self.image, self.image, self.channels)
        if images.shape[1:] != expect:
            raise ValueError(
                f"images are {images.shape[1:]}, this applier serves "
                f"{expect} — resize/crop client-side")
        images = images.astype(np.float32, copy=False)
        keys = np.asarray(keys, np.uint32)
        n = images.shape[0]
        parts: list[tuple[object, int, int]] = []
        lo, chunk_idx = 0, 0
        while lo < n:
            hi = min(lo + self.max_batch, n)
            if self.dispatch == "exact":
                k = keys[lo:hi]
            elif chunk_idx == 0:
                k = keys
            else:
                # over-large grouped batches: fresh program key per
                # chunk, or every chunk would replay one permutation
                import jax

                k = np.asarray(jax.random.fold_in(keys, chunk_idx),
                               np.uint32)
            got = self._dispatch_one(images[lo:hi], k, stages)
            if self.donate and n > self.max_batch:
                # multi-chunk donated call: two staging slots only
                # guarantee one overlap step, so chunk i+2 would reuse
                # chunk i's slot while its H2D may still be in flight —
                # force each chunk synchronous (the server never takes
                # this path; its batches fit one chunk)
                got = np.asarray(got)
            parts.append((got, lo, hi))
            lo = hi
            chunk_idx += 1
        return _AsyncApply(n, images.shape[1:], parts)

    def _dispatch_one(self, images: np.ndarray, keys: np.ndarray,
                      stages: dict | None = None):
        """Pad + dispatch one chunk; returns the PADDED device result
        (not materialized).  The donated path stages into the standing
        double buffers and pays an explicit, timed H2D transfer; the
        default path is the PR-7 allocation shape, bit for bit."""
        n = images.shape[0]
        s = pick_shape(self.shapes, n)
        t0 = mono()
        if self.dispatch == "exact":
            keys = np.asarray(keys, np.uint32).reshape(n, 2)
        if self.donate:
            padded, kp = self._stage(
                images, keys if self.dispatch == "exact" else None, s)
            if kp is not None:
                keys = kp
        else:
            padded = self._pad(images, s)
            if self.dispatch == "exact":
                keys = self._pad(keys, s)
        t1 = mono()
        _acc_stage(stages, "pad", t1 - t0)
        if self.donate:
            # explicit H2D: the donated executable consumes a device
            # buffer (aliased onto its output); staging stays host-side
            # and reusable.  Timed as its own stage.
            import jax

            padded = jax.device_put(padded)
            t2 = mono()
            _acc_stage(stages, "h2d", t2 - t1)
            t1 = t2
        fn = self._exec[s]
        label = f"serve_{self.dispatch}_b{s}"
        if self._watchdog is not None and self._watchdog.enabled:
            got = self._watchdog.run(label, fn, padded, keys)
        else:
            got = fn(padded, keys)
        _acc_stage(stages, "dispatch", mono() - t1)
        return got

    def _apply_one(self, images: np.ndarray, keys: np.ndarray) -> np.ndarray:
        n = images.shape[0]
        return np.asarray(self._dispatch_one(images, keys))[:n]

    # ------------------------------------------------ export round-trip

    def export_serialized(self, shape: int | None = None) -> bytes:
        """``jax.export`` serialization of one shape's program — the
        ship-an-executable story (a consumer process calls
        :func:`deserialize_apply` without this package's tracing code).
        Defaults to the largest shape."""
        import jax
        import jax.numpy as jnp
        from jax import export as jax_export

        from fast_autoaugment_tpu.ops.augment import (
            apply_policy,
            apply_policy_batch_grouped,
            apply_policy_scalar_single,
        )

        s = self.shapes[-1] if shape is None else int(shape)
        if s not in self._exec:
            raise KeyError(f"shape {s} not compiled (have {self.shapes})")
        policy = self.policy
        if self.dispatch == "exact":
            per_image = (apply_policy_scalar_single if self.num_sub == 1
                         else apply_policy)

            def kernel(images, keys):
                return jax.vmap(per_image, in_axes=(0, None, 0))(
                    images, policy, keys)

            spec_key = jax.ShapeDtypeStruct((s, 2), jnp.uint32)
        else:
            groups = self.groups

            def kernel(images, key):
                return apply_policy_batch_grouped(images, policy, key,
                                                  groups=groups)

            spec_key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        spec_img = jax.ShapeDtypeStruct(
            (s, self.image, self.image, self.channels), jnp.float32)
        # jax.export needs the raw jitted fn; it lowers without running,
        # so there is no first call for the seam to time
        exported = jax_export.export(jax.jit(kernel))(spec_img, spec_key)  # robust: allow
        return exported.serialize()


def deserialize_apply(blob: bytes):
    """Rehydrate an :meth:`AotPolicyApplier.export_serialized` program:
    returns ``fn(images, keys) -> images`` at the exported padded
    shape."""
    from jax import export as jax_export

    exported = jax_export.deserialize(blob)
    return lambda images, keys: exported.call(images, keys)


class ServerOverloadedError(ServeError):
    """Admission rejected: the bounded request queue is full.  The
    caller should back off ``retry_after_s`` and retry (HTTP 429 +
    ``Retry-After`` in ``serve_cli``).  Raised IMMEDIATELY — admission
    never blocks the caller on a full queue."""

    def __init__(self, msg: str, retry_after_s: float = 0.05):
        super().__init__(msg)
        self.retry_after_s = max(0.0, float(retry_after_s))


class ServerStoppedError(ServeError):
    """Submitted to a stopped or draining server: no new work is
    admitted (the graceful-drain contract — in-flight requests still
    complete)."""


class DeadlineExpiredError(ServeError):
    """The request's deadline passed before its dispatch: it was SHED
    (never reached the device — dead work must not burn a dispatch) or
    completed hopelessly late."""


class TenantNotResidentError(ServeError):
    """The request named a policy digest with no resident AOT-warm
    applier on this replica.  The HTTP layer answers a structured 503
    (``tenant_cold``) — and, when a warm recipe exists, kicks a
    BACKGROUND warm so a later retry hits the tenant resident; the
    router treats it as a failover signal (another replica may hold
    the tenant warm).  Carries the requested `digest` and the
    replica's `resident` digest list for those decisions."""

    def __init__(self, msg: str, digest: str | None = None,
                 resident: Sequence[str] = ()):
        super().__init__(msg)
        self.digest = digest
        self.resident = tuple(resident)


class TenantPool:
    """The multi-policy tenancy LRU: resident AOT-warm appliers keyed
    by policy digest (the 1 -> N generalization of PR-8 hot reload).

    Admission (:meth:`admit`) takes an applier the caller AOT-warmed
    OFF TO THE SIDE — cold policies never compile on the dispatch
    path.  Over ``capacity`` the least-recently-used digest starts
    RETIRING: invisible to NEW submissions immediately (the router
    stops seeing it resident), while requests already queued under it
    still dispatch on its applier — eviction takes memory effect only
    when the server worker :meth:`sweep`\\ s at a dispatch boundary
    and the tenant's queued work has drained.  An in-flight or queued
    request NEVER loses its applier mid-dispatch.

    Thread-safe: HTTP handler threads look up, a warm thread admits,
    the server worker sweeps."""

    def __init__(self, capacity: int, server_id: str = "0"):
        self.capacity = max(1, int(capacity))
        self._server_id = str(server_id)
        self._lock = threading.Lock()
        self._resident: collections.OrderedDict[str, object] = \
            collections.OrderedDict()
        self._retiring: dict[str, object] = {}
        self._inflight: dict[str, int] = {}
        reg = telemetry.registry()
        self._events = {name: reg.counter(
            "faa_tenant_events_total",
            "tenancy events (admit/evict/hit/miss) per server",
            event=name, server=self._server_id)
            for name in ("admit", "evict", "hit", "miss")}
        self._resident_gauge = reg.gauge(
            "faa_tenant_resident", "tenants resident in the LRU",
            server=self._server_id)

    def lookup_submit(self, digest: str):
        """Resident-only lookup (MRU bump) for NEW submissions — a
        retiring tenant reads as not resident, so fresh traffic routes
        elsewhere while its queued work drains."""
        with self._lock:
            ap = self._resident.get(digest)
            if ap is not None:
                self._resident.move_to_end(digest)
                self._events["hit"].inc()
                return ap
            self._events["miss"].inc()
            return None

    def lookup_dispatch(self, digest: str):
        """Resident-or-retiring lookup for the dispatch boundary —
        queued requests under a retiring tenant still get its applier."""
        with self._lock:
            ap = self._resident.get(digest)
            if ap is None:
                ap = self._retiring.get(digest)
            return ap

    def admit(self, digest: str, applier) -> list[str]:
        """Flip an AOT-warm applier into the LRU; returns the digests
        that started retiring (evicted from residency) as a result."""
        with self._lock:
            already = digest in self._resident
            self._resident[digest] = applier
            self._resident.move_to_end(digest)
            self._retiring.pop(digest, None)  # a re-admit resurrects
            evicted: list[str] = []
            while len(self._resident) > self.capacity:
                old_digest, old_ap = self._resident.popitem(last=False)
                self._retiring[old_digest] = old_ap
                evicted.append(old_digest)
            if not already:
                self._events["admit"].inc()
            if evicted:
                self._events["evict"].inc(len(evicted))
            self._resident_gauge.set(len(self._resident))
            n_resident = len(self._resident)
        for old in evicted:
            telemetry.emit("tenant", f"serve{self._server_id}",
                           action="evict", digest=old)
        telemetry.emit("tenant", f"serve{self._server_id}",
                       action="admit", digest=digest,
                       resident=n_resident)
        return evicted

    # -- queued-work accounting (what makes retirement safe) ----------

    def track_submit(self, digest: str) -> None:
        with self._lock:
            self._inflight[digest] = self._inflight.get(digest, 0) + 1

    def track_done(self, digest: str) -> None:
        with self._lock:
            n = self._inflight.get(digest, 0) - 1
            if n <= 0:
                self._inflight.pop(digest, None)
            else:
                self._inflight[digest] = n

    def sweep(self) -> list[str]:
        """Dispatch-boundary eviction: release retiring appliers whose
        queued work has fully drained.  Called by the server worker
        BETWEEN dispatches — never while one is in flight."""
        with self._lock:
            dead = [d for d in self._retiring
                    if self._inflight.get(d, 0) <= 0]
            for d in dead:
                del self._retiring[d]
        return dead

    def resident_digests(self) -> list[str]:
        """Resident digests, LRU-first / MRU-last."""
        with self._lock:
            return list(self._resident)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "resident": list(self._resident),
                "retiring": sorted(self._retiring),
                "admits": int(self._events["admit"].value),
                "evicts": int(self._events["evict"].value),
                "hits": int(self._events["hit"].value),
                "misses": int(self._events["miss"].value),
            }


class _Pending:
    """One in-flight request: `n` images, completion event, result or
    error, submit/done walls for the latency record, and an optional
    absolute deadline (``mono()`` seconds)."""

    __slots__ = ("images", "keys", "event", "result", "error",
                 "t_submit", "t_done", "deadline", "digest")

    def __init__(self, images: np.ndarray, keys: np.ndarray | None,
                 deadline: float | None = None,
                 digest: str | None = None):
        self.images = images
        self.keys = keys
        self.event = threading.Event()
        self.result: np.ndarray | None = None
        self.error: BaseException | None = None
        self.t_submit = mono()
        self.t_done = 0.0
        self.deadline = deadline
        # tenancy: the policy digest this request is pinned to (None =
        # the server's pinned default applier — the historical stream)
        self.digest = digest

    @property
    def n(self) -> int:
        return self.images.shape[0]

    def latency(self) -> float:
        return self.t_done - self.t_submit

    def expired(self, now: float | None = None) -> bool:
        if self.deadline is None:
            return False
        return (mono() if now is None else now) >= self.deadline


class _InflightBatch:
    """A coalesced batch whose device work has been DISPATCHED but not
    materialized (double-buffered mode): holds the request list, the
    async handle, a strong applier reference (so eviction/reload can't
    retire the executables mid-flight) and the per-stage walls so far.
    The worker finalizes it after staging the NEXT batch."""

    __slots__ = ("batch", "handle", "applier", "digest", "n_images",
                 "t0", "stages", "images")

    def __init__(self, batch, handle, applier, digest, n_images, t0,
                 stages, images=None):
        self.batch = batch
        self.handle = handle
        self.applier = applier
        self.digest = digest
        self.n_images = n_images
        self.t0 = t0
        self.stages = stages
        # the concatenated input batch — kept ONLY when traffic_stats
        # needs it at finalize time (None otherwise: no lifetime tax)
        self.images = images


class _RequestQueue:
    """Bounded request buffer with NON-BLOCKING admission and
    watermark-selected drain order.

    ``offer`` never blocks: it returns False on a full (or closed)
    queue and the caller sheds the request with a typed error — the
    blocking-admission bug class (an HTTP handler thread parked on
    ``Queue.put(timeout=30)``) is impossible by construction.

    ``take`` drains FIFO in clean weather.  Under sustained overload —
    depth at/above ``lifo_depth`` or the OLDEST queued request older
    than ``lifo_age_ms`` — it switches to adaptive-LIFO (newest-first):
    when the queue is deep, the oldest requests are the ones whose
    clients have most likely already given up, so serving the newest
    first maximizes goodput while the shed pass retires the expired
    tail.  Both watermarks default to 0 = off (pure FIFO, the
    bit-for-bit PR-7 drain order).
    """

    def __init__(self, depth: int, *, lifo_depth: int = 0,
                 lifo_age_ms: float = 0.0, lifo_counter=None):
        self.depth = int(depth)
        self.lifo_depth = int(lifo_depth)
        self.lifo_age_ms = float(lifo_age_ms)
        self._items: collections.deque[_Pending] = collections.deque()
        self._cond = threading.Condition()
        # takes served newest-first: a telemetry registry counter when
        # the owning server provides one (the /stats and /metrics views
        # then read the SAME number), else a local int
        self._lifo_counter = lifo_counter
        self._lifo_takes_local = 0

    @property
    def lifo_takes(self) -> int:
        if self._lifo_counter is not None:
            return int(self._lifo_counter.value)
        return self._lifo_takes_local

    def _count_lifo_take(self) -> None:
        if self._lifo_counter is not None:
            self._lifo_counter.inc()
        else:
            self._lifo_takes_local += 1

    def __len__(self) -> int:
        with self._cond:
            return len(self._items)

    def empty(self) -> bool:
        return len(self) == 0

    def offer(self, item: _Pending) -> bool:
        """Admit `item` or return False NOW (full) — never blocks."""
        with self._cond:
            if len(self._items) >= self.depth:
                return False
            self._items.append(item)
            self._cond.notify()
            return True

    def _lifo_active(self) -> bool:
        if self.lifo_depth > 0 and len(self._items) >= self.lifo_depth:
            return True
        if self.lifo_age_ms > 0 and self._items:
            oldest_age = mono() - self._items[0].t_submit
            if oldest_age * 1e3 >= self.lifo_age_ms:
                return True
        return False

    def take(self, timeout: float) -> _Pending | None:
        """Pop one request (None on timeout).  Drain order is FIFO, or
        newest-first while a watermark holds."""
        with self._cond:
            if not self._items:
                self._cond.wait(timeout=timeout)
            if not self._items:
                return None
            if self._lifo_active():
                self._count_lifo_take()
                return self._items.pop()
            return self._items.popleft()

    def drain(self) -> list[_Pending]:
        with self._cond:
            items = list(self._items)
            self._items.clear()
            return items


class PolicyServer:
    """Batch-coalescing, overload-safe request front for an
    :class:`AotPolicyApplier`.

    The worker collects requests until ``max_batch`` images are queued
    or ``max_wait_ms`` has passed since the FIRST queued request, pads
    to the nearest AOT shape, dispatches one program and scatters the
    rows back in FIFO order.  A request that would overflow the batch
    is carried to the next dispatch intact (requests are never split,
    so per-request key streams stay contiguous).

    Overload behavior (all knobs default OFF = the PR-7 clean-weather
    stream, except that admission now FAILS FAST on a full queue
    instead of blocking the caller — the blocking-admission bug fix):

    - **admission**: ``submit`` on a full queue raises the typed
      :class:`ServerOverloadedError` immediately; after :meth:`stop` /
      :meth:`begin_drain` it raises :class:`ServerStoppedError`; while
      the breaker is open it raises
      :class:`~fast_autoaugment_tpu.core.resilience.CircuitOpenError`;
    - **deadlines**: ``submit(..., deadline_ms=D)`` stamps an absolute
      deadline; the collector SHEDS already-expired requests before
      padding/dispatch (typed :class:`DeadlineExpiredError`, counted
      ``expired``) so dead work never reaches the device;
    - **drain order**: ``lifo_depth`` / ``lifo_age_ms`` watermarks
      switch the queue to adaptive-LIFO under sustained overload
      (:class:`_RequestQueue`);
    - **failure containment**: ``breaker_threshold`` consecutive
      dispatch failures (errors, or walls above ``dispatch_timeout_s``)
      open a :class:`~fast_autoaugment_tpu.core.resilience.
      CircuitBreaker` — queued requests fail fast with typed errors
      until a half-open probe succeeds;
    - **hot reload**: :meth:`swap_applier` atomically swaps in a new
      (pre-warmed) applier between dispatches — no dropped requests,
      no half-policy batch (each dispatch binds ONE applier);
    - **multi-policy tenancy**: ``tenant_capacity`` > 0 arms a
      :class:`TenantPool` LRU of resident AOT-warm appliers keyed by
      policy digest; ``submit(..., digest=...)`` selects the tenant,
      :meth:`warm_tenant` admits one warmed off to the side, batches
      never mix tenants, and eviction takes effect only at dispatch
      boundaries (docs/SERVING.md).  0 (default) = the single-policy
      bit-for-bit stream.

    The ``FAA_FAULT`` verbs ``serve_error@dispatch=N`` and
    ``serve_slow@dispatch=N,factor=F`` are consulted at the dispatch
    seam (``utils/faultinject.py``) so every path above is driven
    deterministically in tests.
    """

    def __init__(self, applier: AotPolicyApplier, *,
                 max_batch: int | None = None, max_wait_ms: float = 5.0,
                 queue_depth: int = 4096, seed: int = 0,
                 default_deadline_ms: float | None = None,
                 lifo_depth: int = 0, lifo_age_ms: float = 0.0,
                 breaker_threshold: int = 0,
                 breaker_cooldown_s: float = 5.0,
                 dispatch_timeout_s: float = 0.0,
                 tenant_capacity: int = 0,
                 traffic_stats: bool = False,
                 double_buffer: bool = False,
                 dispatch_floor_ms: float = 0.0):
        self.applier = applier
        self.max_batch = int(max_batch or applier.max_batch)
        # deliberate per-dispatch service-time floor: caps throughput
        # at max_batch / floor images/s so game-day drills can emulate
        # a heavy model and reach REAL overload on a 1-core CI host
        # deterministically.  0.0 (default) = off, bit-for-bit.
        self.dispatch_floor_s = max(0.0, float(dispatch_floor_ms)) / 1e3
        if self.max_batch > applier.max_batch:
            raise ValueError(
                f"max_batch {self.max_batch} exceeds the largest AOT "
                f"shape {applier.max_batch}")
        self.max_wait_ms = float(max_wait_ms)
        self.queue_depth = int(queue_depth)
        # robustness counters live in the process-wide telemetry
        # registry (core/telemetry.py): /stats, the bench JSON and a
        # Prometheus /metrics scrape all read the SAME counters the hot
        # path bumps (one source of truth; equality pinned by tests).
        # Each server instance gets its own label so per-instance stats
        # stay exact when tests build many servers in one process.
        with _SERVER_SEQ_LOCK:
            global _SERVER_SEQ
            self._server_id = str(_SERVER_SEQ)
            _SERVER_SEQ += 1
        reg = telemetry.registry()

        def _ctr(name: str) -> telemetry.Counter:
            return reg.counter(
                "faa_serve_robustness_total",
                "serving robustness counters (admission/shed/breaker/"
                "reload)", counter=name, server=self._server_id)

        self._ctr = {name: _ctr(name) for name in (
            "admitted", "shed_overload", "shed_breaker", "shed_stopped",
            "expired", "deadline_misses", "lifo_takes", "reloads")}
        self._dispatches_ctr = reg.counter(
            "faa_serve_dispatches_total", "coalesced device dispatches",
            server=self._server_id)
        self._requests_ctr = reg.counter(
            "faa_serve_requests_total", "requests served",
            server=self._server_id)
        self._images_ctr = reg.counter(
            "faa_serve_images_total", "images served",
            server=self._server_id)
        self._q = _RequestQueue(self.queue_depth, lifo_depth=lifo_depth,
                                lifo_age_ms=lifo_age_ms,
                                lifo_counter=self._ctr["lifo_takes"])
        self._carry: _Pending | None = None
        self._stop = threading.Event()
        # admission gate: set by stop() AND begin_drain() — a submit
        # after either gets the typed error instead of racing the drain
        self._closed = threading.Event()
        self._worker: threading.Thread | None = None
        self._seed = int(seed)
        self._auto_key_counter = 0
        self._lock = threading.Lock()
        self.default_deadline_ms = (None if default_deadline_ms is None
                                    else float(default_deadline_ms))
        self.dispatch_timeout_s = float(dispatch_timeout_s)
        self.breaker = CircuitBreaker(threshold=breaker_threshold,
                                      cooldown_s=breaker_cooldown_s,
                                      name=f"serve{self._server_id}")
        # multi-policy tenancy (docs/SERVING.md): an LRU of resident
        # AOT-warm appliers keyed by policy digest.  0 = off — the
        # single-policy bit-for-bit historical stream.  The __init__
        # applier is the PINNED default tenant (never evicted); its
        # digest answers requests that name it explicitly.
        self.tenant_capacity = int(tenant_capacity)
        self._tenants = (TenantPool(self.tenant_capacity, self._server_id)
                         if self.tenant_capacity > 0 else None)
        self.default_digest = getattr(applier, "digest", None)
        # per-tenant serve counters, cached per digest; written only
        # by the worker thread (reads via registry snapshots)
        self._tenant_ctrs: dict[str, tuple] = {}
        # scrape-visible queue depth for the autoscaler (the PR-10
        # telemetry consumer): updated at admission and collection
        self._qdepth_gauge = reg.gauge(
            "faa_serve_queue_depth", "requests queued awaiting dispatch",
            server=self._server_id)
        # double-buffered dispatch (opt-in): the worker overlaps batch
        # k's device compute with batch k+1's collect/pad/dispatch —
        # Podracer's keep-the-accelerator-fed lesson applied to the
        # coalescer.  OFF = the strictly sequential PR-7 loop.
        self.double_buffer = bool(double_buffer)
        # per-stage data-plane overhead histograms (one child per
        # stage label), registered lazily on first observation
        self._stage_hist: dict[str, object] = {}
        #: grace past a request's deadline that result() still waits —
        #: covers the shed pass delivering the typed error
        self.deadline_grace_s = 1.0
        # serving accounting for the bench/stats endpoints (volume
        # counters also live in the registry; the wall/batch lists stay
        # local — they feed percentile math, not counters)
        self.batch_sizes: list[int] = []
        self.dispatch_walls: list[float] = []
        self._dispatch_attempts = 0  # incl. fast-fails + injected errors
        self._wall_ema: float | None = None
        # served-traffic statistics (control/drift.py's signal source,
        # docs/CONTROL.md): per-dispatch input moments + a reward proxy
        # (mean normalized |out - in| — the augmentation-effect
        # magnitude), published as gauges and stamped onto the journal's
        # serve dispatch events.  OFF by default: the historical journal
        # stream and /stats surface are byte-identical without the flag.
        self.traffic_stats = bool(traffic_stats)
        self._traffic_ema: dict[str, float | None] = {
            "input_mean": None, "input_std": None, "reward_proxy": None}
        self._traffic_samples = 0
        self._traffic_gauges = None
        if self.traffic_stats:
            self._traffic_gauges = {
                name: reg.gauge(
                    f"faa_serve_{name}",
                    "served-traffic statistic (EMA over dispatches; "
                    "the drift monitor / canary comparator signal)",
                    server=self._server_id)
                for name in ("input_mean", "input_std", "reward_proxy")}

    # ------------------------------------------------------- lifecycle

    def start(self) -> "PolicyServer":
        if self._worker is not None and self._worker.is_alive():
            return self
        self._stop.clear()
        self._closed.clear()
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="policy-server")
        self._worker.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._closed.set()
        self._stop.set()
        if self._worker is not None:
            # bounded join (lint R4/R6): a wedged dispatch must not
            # hang shutdown — the worker is a daemon either way
            self._worker.join(timeout=timeout)

    def begin_drain(self) -> None:
        """Stop admitting (submit raises :class:`ServerStoppedError`);
        queued and in-flight requests still complete.  The worker exits
        once the queue is empty."""
        self._closed.set()

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful drain: stop admitting, finish everything queued,
        stop the worker.  Returns True when fully drained within
        `timeout` (False = a dispatch is stuck; the worker is a daemon
        and the caller should exit anyway)."""
        self.begin_drain()
        deadline = time.monotonic() + max(0.0, float(timeout))
        if self._worker is not None:
            while self._worker.is_alive():
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._worker.join(timeout=min(0.2, left))
        drained = self._q.empty() and self._carry is None \
            and (self._worker is None or not self._worker.is_alive())
        self._stop.set()
        return drained

    @property
    def draining(self) -> bool:
        return self._closed.is_set()

    # --------------------------------------------------------- clients

    def _auto_keys(self, n: int) -> np.ndarray:
        """Server-derived per-image keys: ``fold_in(PRNGKey(seed), i)``
        over a process-monotonic counter — distinct stream per image
        without client coordination."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            base = self._auto_key_counter
            self._auto_key_counter += n
        root = jax.random.PRNGKey(self._seed)
        idx = jnp.arange(base, base + n)
        return np.asarray(
            jax.vmap(lambda i: jax.random.fold_in(root, i))(idx), np.uint32)

    def submit(self, images: np.ndarray,
               keys: np.ndarray | None = None, *,
               deadline_ms: float | None = None,
               digest: str | None = None) -> _Pending:
        """Queue ``images [n, H, W, C]`` (or one ``[H, W, C]`` image).

        `keys` (``[n, 2]`` uint32) pins the per-image PRNG streams —
        the reproducible-serving contract; None lets the server derive
        them.  `deadline_ms` (relative; default the server's
        ``default_deadline_ms``) stamps the deadline after which the
        request is shed instead of dispatched.  `digest` pins the
        request to a resident TENANT policy (``X-FAA-Policy-Digest``);
        None — or the default applier's own digest — serves the pinned
        default.  A digest with no resident applier raises the typed
        :class:`TenantNotResidentError` immediately (the router's
        failover signal).  Returns a pending handle for
        :meth:`result`.  NEVER blocks: a full queue raises
        :class:`ServerOverloadedError`, a stopped/draining server
        :class:`ServerStoppedError`, an open breaker
        :class:`~fast_autoaugment_tpu.core.resilience.CircuitOpenError`
        — all immediately."""
        images = np.asarray(images)
        if images.ndim == 3:
            images = images[None]
        n = images.shape[0]
        if n < 1:
            raise ValueError("empty request")
        if n > self.max_batch:
            raise ValueError(
                f"request of {n} images exceeds max_batch "
                f"{self.max_batch} — split client-side")
        if digest is not None and digest == self.default_digest:
            digest = None  # the pinned default serves its own digest
        if digest is not None:
            if self._tenants is None:
                raise TenantNotResidentError(
                    f"policy {digest} requested but this replica serves "
                    f"a single policy (tenancy disabled)", digest,
                    resident=([self.default_digest]
                              if self.default_digest else ()))
            if self._tenants.lookup_submit(digest) is None:
                raise TenantNotResidentError(
                    f"policy {digest} is not resident on this replica",
                    digest, resident=self._tenants.resident_digests())
        if self._closed.is_set():
            self._ctr["shed_stopped"].inc()
            telemetry.emit("shed", f"serve{self._server_id}",
                           reason="stopped", n=int(n))
            raise ServerStoppedError(
                "server is stopped/draining — not admitting requests")
        if self.breaker.is_open():
            self._ctr["shed_breaker"].inc()
            telemetry.emit("shed", f"serve{self._server_id}",
                           reason="breaker_open", n=int(n))
            raise CircuitOpenError(
                "circuit breaker open — backend failing, not admitting "
                "requests", retry_after_s=self.breaker.retry_after_s())
        if keys is None and self.applier.dispatch == "exact":
            keys = self._auto_keys(n)
        elif keys is not None:
            keys = np.asarray(keys, np.uint32).reshape(n, 2)
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        deadline = (None if deadline_ms is None
                    else mono() + float(deadline_ms) / 1e3)
        pending = _Pending(images, keys, deadline, digest)
        if not self._q.offer(pending):
            self._ctr["shed_overload"].inc()
            telemetry.emit("shed", f"serve{self._server_id}",
                           reason="overload", n=int(n))
            raise ServerOverloadedError(
                f"queue full ({self.queue_depth} requests) — shedding",
                retry_after_s=max(0.05, self.max_wait_ms / 1e3))
        self._ctr["admitted"].inc()
        if self._tenants is not None and digest is not None:
            # retirement safety: a tenant with queued work is never
            # swept (the dispatch-boundary eviction contract)
            self._tenants.track_submit(digest)
        self._qdepth_gauge.set(len(self._q))
        return pending

    def result(self, pending: _Pending, timeout: float = 60.0) -> np.ndarray:
        """Block for a submitted request's augmented images.  A request
        with a deadline never waits (much) past it: the effective
        timeout is bounded by the deadline plus a small grace for the
        shed pass to deliver the typed error."""
        if pending.deadline is not None:
            left = pending.deadline - mono()
            timeout = min(timeout, max(0.0, left) + self.deadline_grace_s)
        if not pending.event.wait(timeout=timeout):
            raise TimeoutError(
                f"no result within {timeout:.3f}s ({pending.n} images)")
        if pending.error is not None:
            if isinstance(pending.error, (ServeError, CircuitOpenError)):
                raise pending.error
            raise ServeError(str(pending.error)) from pending.error
        return pending.result

    def augment(self, images: np.ndarray, keys: np.ndarray | None = None,
                timeout: float = 60.0,
                deadline_ms: float | None = None,
                digest: str | None = None) -> np.ndarray:
        """Submit + wait — the one-call client path."""
        return self.result(self.submit(images, keys, deadline_ms=deadline_ms,
                                       digest=digest),
                           timeout=timeout)

    # ------------------------------------------------------ hot reload

    def swap_applier(self, new_applier: AotPolicyApplier) -> dict:
        """Atomically swap the serving applier (hot policy reload).

        The caller builds (and thereby AOT-warms) `new_applier` OFF TO
        THE SIDE; this method only flips the reference.  The worker
        binds ``self.applier`` ONCE per dispatch, so every coalesced
        batch is served by exactly one policy — zero half-policy
        batches — and queued requests are never dropped (they simply
        dispatch under whichever applier is live at their turn).  Old
        executables retire once their in-flight dispatch completes
        (nothing else holds a reference).

        The new applier must serve the same request contract: equal
        image/channels, the SAME dispatch mode (a request's key shape
        depends on it), and a ``max_batch`` covering the server's."""
        self._validate_applier(new_applier, verb="reload")
        digest = getattr(new_applier, "digest", None)
        with self._lock:
            self.applier = new_applier
            self.default_digest = digest
            self._ctr["reloads"].inc()
            n = self.reloads
        telemetry.emit("reload", f"serve{self._server_id}", reloads=n,
                       num_sub=new_applier.num_sub, digest=digest)
        logger.info("hot reload #%d: applier swapped (%d sub-policies, "
                    "digest %s)", n, new_applier.num_sub, digest)
        # the digest echo is the canary comparator's verification that
        # the intended policy is actually resident (docs/CONTROL.md) —
        # a reload that succeeds without saying WHAT is now serving
        # cannot be audited
        return {"reloads": n, "num_sub": new_applier.num_sub,
                "digest": digest}

    # ---------------------------------------------------------- tenancy

    def _validate_applier(self, new_applier, verb: str = "tenant") -> None:
        """The shared serving contract every applier entering this
        server must satisfy (reload AND tenant admission): equal
        geometry, the same dispatch mode, AOT coverage of max_batch."""
        old = self.applier
        if (new_applier.image, new_applier.channels) != (old.image,
                                                         old.channels):
            raise ValueError(
                f"{verb} changes served geometry "
                f"{(old.image, old.channels)} -> "
                f"{(new_applier.image, new_applier.channels)}")
        if new_applier.dispatch != old.dispatch:
            raise ValueError(
                f"{verb} changes dispatch mode {old.dispatch!r} -> "
                f"{new_applier.dispatch!r} — queued keys would not fit")
        if new_applier.max_batch < self.max_batch:
            raise ValueError(
                f"new applier's largest AOT shape {new_applier.max_batch} "
                f"is below the server's max_batch {self.max_batch}")

    @property
    def tenancy_enabled(self) -> bool:
        return self._tenants is not None

    def resident_tenants(self) -> list[str]:
        """Resident tenant digests (LRU-first), default tenant
        excluded — it is pinned, not pooled."""
        return ([] if self._tenants is None
                else self._tenants.resident_digests())

    def warm_tenant(self, new_applier) -> dict:
        """Admit an AOT-warm applier as a resident TENANT — the 1 -> N
        generalization of :meth:`swap_applier`.

        The caller builds (and thereby AOT-warms) `new_applier` OFF TO
        THE SIDE — a cold policy never compiles on the dispatch path
        and warm tenants keep dispatching throughout.  This method
        validates the serving contract and flips the applier into the
        LRU.  Over capacity the least-recently-used tenant starts
        retiring: invisible to new submissions at once, its memory
        released at the next dispatch boundary once its queued work
        has drained (:class:`TenantPool`)."""
        if self._tenants is None:
            raise RuntimeError(
                "tenancy is disabled on this server (tenant_capacity=0)")
        digest = getattr(new_applier, "digest", None)
        if not digest:
            raise ValueError("tenant applier carries no policy digest")
        if digest == self.default_digest:
            raise ValueError(
                f"policy {digest} is the pinned default tenant — "
                "use swap_applier/reload to change it")
        self._validate_applier(new_applier, verb="tenant admit")
        evicted = self._tenants.admit(digest, new_applier)
        info = {"digest": digest, "evicted": evicted,
                "resident": self._tenants.resident_digests()}
        logger.info("tenant %s admitted (%d sub-policies; evicted: %s)",
                    digest, getattr(new_applier, "num_sub", 0),
                    ",".join(evicted) or "-")
        return info

    def _tenant_done(self, p: _Pending) -> None:
        if self._tenants is not None and p.digest is not None:
            self._tenants.track_done(p.digest)

    def _tenant_counters(self, digest: str) -> tuple:
        c = self._tenant_ctrs.get(digest)
        if c is None:
            reg = telemetry.registry()
            c = (reg.counter("faa_tenant_requests_total",
                             "requests served per tenant",
                             digest=digest, server=self._server_id),
                 reg.counter("faa_tenant_images_total",
                             "images served per tenant",
                             digest=digest, server=self._server_id))
            self._tenant_ctrs[digest] = c
        return c

    # ---------------------------------------------------------- worker

    def _take_first(self) -> _Pending | None:
        if self._carry is not None:
            first, self._carry = self._carry, None
            return first
        # bounded take: the stop/drain flags are polled between waits
        return self._q.take(timeout=0.05)

    def _shed(self, p: _Pending, now: float) -> None:
        """Retire one expired request BEFORE padding/dispatch — dead
        work never reaches the device."""
        p.error = DeadlineExpiredError(
            f"deadline passed {now - p.deadline:.3f}s ago while queued "
            f"({p.n} images) — request shed before dispatch")
        p.t_done = now
        p.event.set()
        self._tenant_done(p)
        self._ctr["expired"].inc()
        telemetry.emit("shed", f"serve{self._server_id}",
                       reason="deadline_expired", n=int(p.n))

    def _collect(self, first: _Pending) -> list[_Pending]:
        """Coalesce: up to ``max_batch`` images or ``max_wait_ms`` after
        the FIRST request of the batch arrived.  Expired requests are
        shed as they are encountered and never join the batch.  A batch
        holds requests for ONE tenant digest only — each dispatch binds
        exactly one applier (the reload/tenancy atomicity contract)."""
        batch: list[_Pending] = []
        count = 0
        digest = first.digest
        now = mono()
        if first.expired(now):
            self._shed(first, now)
        else:
            batch.append(first)
            count = first.n
        deadline = mono() + self.max_wait_ms / 1e3
        while count < self.max_batch:
            remaining = deadline - mono()
            if remaining <= 0:
                break
            nxt = self._q.take(timeout=remaining)
            if nxt is None:
                break
            now = mono()
            if nxt.expired(now):
                self._shed(nxt, now)
                continue
            if not batch:
                digest = nxt.digest  # first admitted member sets the tenant
            elif nxt.digest != digest:
                # one tenant per dispatch: carry the first request of
                # the NEXT tenant whole (FIFO preserved — the carry is
                # taken first at the next collection)
                self._carry = nxt
                break
            if count + nxt.n > self.max_batch:
                # never split a request: carry it whole to the next
                # dispatch (FIFO preserved — the carry is taken first)
                self._carry = nxt
                break
            batch.append(nxt)
            count += nxt.n
        self._qdepth_gauge.set(len(self._q))
        return batch

    def _fail_batch(self, batch: list[_Pending], err: BaseException) -> None:
        done = mono()
        for p in batch:
            p.error = err
            p.t_done = done
            p.event.set()
            self._tenant_done(p)

    def _injected_fault(self) -> tuple[str, float] | None:
        """Consult the FAA_FAULT serve verbs with the 1-based dispatch
        attempt counter (fast None path with FAA_FAULT unset)."""
        from fast_autoaugment_tpu.utils.faultinject import active_plan

        plan = active_plan()
        if plan is None:
            return None
        return plan.serve_fault(self._dispatch_attempts)

    def _injected_drift(self, images: np.ndarray) -> np.ndarray:
        """The ``drift@dispatch=N,shift=S`` seam: from the matching
        dispatch onward every input batch is pixel-shifted by S before
        statistics and device work — the deterministic distribution
        shift the control plane's acceptance drill detects
        (utils/faultinject.py, docs/CONTROL.md)."""
        from fast_autoaugment_tpu.utils.faultinject import active_plan

        plan = active_plan()
        if plan is None:
            return images
        # the counter was already bumped for this dispatch by _dispatch
        shift = plan.drift_shift(self._dispatch_attempts)
        if shift is None:
            return images
        return np.clip(images.astype(np.float32, copy=False) + shift,
                       0.0, 255.0)

    def _observe_traffic(self, images: np.ndarray,
                         out: np.ndarray) -> dict:
        """Update the served-traffic EMAs/gauges from one dispatched
        batch and return the journal fields for its dispatch event.
        Host-side numpy over an already-materialized batch (~µs at
        serving batch sizes); only runs with ``traffic_stats`` on."""
        m = float(np.mean(images))
        s = float(np.std(images))
        proxy = float(np.mean(np.abs(
            np.asarray(out, np.float32) - images))) / 255.0
        for name, v in (("input_mean", m), ("input_std", s),
                        ("reward_proxy", proxy)):
            prev = self._traffic_ema[name]
            ema = v if prev is None else 0.2 * v + 0.8 * prev
            self._traffic_ema[name] = ema
            self._traffic_gauges[name].set(ema)
        self._traffic_samples += 1
        return {"input_mean": round(m, 4), "input_std": round(s, 4),
                "reward_proxy": round(proxy, 6)}

    def observe_stage(self, stage: str, sec: float) -> None:
        """One observation into the ``faa_serve_stage_seconds{stage=}``
        family — the per-stage data-plane overhead breakdown
        (queue_wait / pad / h2d / dispatch / scatter server-side;
        decode / serialize from the HTTP front in serve_cli).
        Children are registered lazily per stage label."""
        h = self._stage_hist.get(stage)
        if h is None:
            h = telemetry.registry().histogram(
                "faa_serve_stage_seconds",
                "serving data-plane per-stage overhead (seconds; "
                "docs/SERVING.md 'Serving data plane')",
                buckets=_STAGE_BUCKETS, stage=stage,
                server=self._server_id)
            self._stage_hist[stage] = h
        h.observe(sec)

    def _dispatch(self, batch: list[_Pending]) -> None:
        """The strictly sequential dispatch (default mode): stage +
        dispatch + materialize + scatter in one call."""
        inf = self._dispatch_begin(batch)
        if inf is not None:
            self._dispatch_finish(inf)

    def _dispatch_begin(self, batch: list[_Pending]) -> _InflightBatch | None:
        """Bind the applier, pad/stage the batch and DISPATCH it to the
        device without materializing (JAX async dispatch).  Returns the
        in-flight handle, or None when the batch already failed (typed
        error delivered).  Double-buffered mode finalizes the PREVIOUS
        batch after this returns — batch k computes while k+1 stages."""
        # ONE applier per dispatch (the reload AND tenancy seam): the
        # binding is taken once here and holds a strong reference, so a
        # concurrent reload/eviction can never swap it mid-batch
        digest = batch[0].digest
        if digest is None:
            applier = self.applier
        else:
            applier = self._tenants.lookup_dispatch(digest) \
                if self._tenants is not None else None
            if applier is None:
                # admitted-then-swept race (submit raced an admit's
                # eviction before its track_submit): typed error, the
                # router fails over to a replica holding the tenant
                self._fail_batch(batch, TenantNotResidentError(
                    f"policy {digest} was evicted before dispatch",
                    digest,
                    resident=(self._tenants.resident_digests()
                              if self._tenants else ())))
                return None
        self._dispatch_attempts += 1
        if self.breaker.enabled and not self.breaker.allow():
            # open circuit: fail the whole batch fast — no device work
            err = CircuitOpenError(
                "circuit breaker open — dispatch failed fast",
                retry_after_s=self.breaker.retry_after_s())
            self._ctr["shed_breaker"].inc(len(batch))
            telemetry.emit("shed", f"serve{self._server_id}",
                           reason="breaker_open", n=len(batch))
            self._fail_batch(batch, err)
            return None
        stages: dict[str, float] = {
            "queue_wait": mono() - batch[0].t_submit}
        images = np.concatenate([p.images for p in batch])
        images = self._injected_drift(images)
        if applier.dispatch == "exact":
            keys = np.concatenate([p.keys for p in batch])
        else:
            # one program key per dispatch, derived server-side
            keys = self._auto_keys(1)[0]
        fault = self._injected_fault()
        t0 = mono()
        try:
            if fault is not None and fault[0] == "error":
                raise ServeError(
                    f"faultinject: serve_error at dispatch "
                    f"{self._dispatch_attempts}")
            if fault is not None and fault[0] == "slow":
                base = self._wall_ema if self._wall_ema else 1.0
                time.sleep(min(fault[1] * base, 300.0))
            if self.dispatch_floor_s > 0.0:
                time.sleep(self.dispatch_floor_s)
            fn = getattr(applier, "apply_async", None)
            if fn is not None:
                handle = fn(images, keys, stages=stages)
            else:
                # duck-typed appliers (hot-reload stand-ins, tenancy
                # dummies) expose only .apply — eager dispatch, wrapped
                # so the finish path is uniform
                handle = _EagerApply(applier.apply(images, keys))
        except Exception as e:  # noqa: BLE001 — delivered to every caller
            logger.error("serving dispatch failed (%d images): %s",
                         images.shape[0], e)
            self.breaker.record_failure()
            self._fail_batch(batch, e)
            return None
        return _InflightBatch(
            batch, handle, applier, digest, int(images.shape[0]), t0,
            stages, images=(images if self.traffic_stats else None))

    def _dispatch_finish(self, inf: _InflightBatch) -> None:
        """Materialize an in-flight batch and scatter results (FIFO);
        all accounting — breaker verdict, counters, latency lists,
        stage histograms, the dispatch journal event — lands here."""
        batch, digest = inf.batch, inf.digest
        try:
            out = inf.handle.materialize(stages=inf.stages)
        except Exception as e:  # noqa: BLE001 — delivered to every caller
            logger.error("serving dispatch failed (%d images): %s",
                         inf.n_images, e)
            self.breaker.record_failure()
            self._fail_batch(batch, e)
            return
        t0 = inf.t0
        wall = mono() - t0
        if self.dispatch_timeout_s > 0 and wall > self.dispatch_timeout_s:
            # a straggler past the dispatch budget counts toward the
            # breaker even though its results are delivered — repeated
            # near-hangs must open the circuit before a real one wedges
            logger.warning(
                "dispatch took %.3fs > dispatch_timeout %.3fs — counted "
                "as a breaker failure", wall, self.dispatch_timeout_s)
            self.breaker.record_failure()
        else:
            self.breaker.record_success()
        t_sc = mono()
        lo = 0
        done = t_sc
        misses = 0
        for p in batch:
            p.result = out[lo:lo + p.n]
            lo += p.n
            p.t_done = done
            if p.deadline is not None and done > p.deadline:
                misses += 1
            p.event.set()
            self._tenant_done(p)
        _acc_stage(inf.stages, "scatter", mono() - t_sc)
        self._dispatches_ctr.inc()
        self._requests_ctr.inc(len(batch))
        self._images_ctr.inc(inf.n_images)
        if digest is not None:
            t_reqs, t_imgs = self._tenant_counters(digest)
            t_reqs.inc(len(batch))
            t_imgs.inc(inf.n_images)
        if misses:
            self._ctr["deadline_misses"].inc(misses)
        with self._lock:
            self.batch_sizes.append(inf.n_images)
            self.dispatch_walls.append(wall)
        for stage, sec in inf.stages.items():
            self.observe_stage(stage, sec)
        # served-traffic statistics ride the dispatch event (the drift
        # monitor's journal-derived signal); OFF = no new journal keys
        traffic = (self._observe_traffic(inf.images, out)
                   if self.traffic_stats and inf.images is not None
                   else {})
        # the serve arm of the span seam: same record shape as the
        # trainer/TTA dispatch windows (core/telemetry.py)
        telemetry.record_dispatch("serve_dispatch", t0, done,
                                  batch=inf.n_images,
                                  requests=len(batch), **traffic)
        self._wall_ema = (wall if self._wall_ema is None
                          else 0.2 * wall + 0.8 * self._wall_ema)

    def _run(self) -> None:
        # double-buffered mode: at most ONE batch is in flight on the
        # device while the worker collects/stages the next.  The
        # in-flight handle holds a strong applier reference, so the
        # tenant sweep below stays safe at every boundary.
        inflight: _InflightBatch | None = None
        while not self._stop.is_set():
            first = self._take_first()
            if first is None:
                if inflight is not None:
                    self._dispatch_finish(inflight)
                    inflight = None
                if self._closed.is_set():
                    break  # draining and the queue ran dry: done
                continue
            batch = self._collect(first)
            if batch:
                if self.double_buffer:
                    nxt = self._dispatch_begin(batch)
                    if inflight is not None:
                        # batch k+1 is dispatched; NOW block on batch k
                        # — its device time overlapped k+1's collect,
                        # pad and dispatch (the Podracer overlap)
                        self._dispatch_finish(inflight)
                    inflight = nxt
                    if inflight is not None and self._carry is None \
                            and self._q.empty():
                        # nothing to overlap with: deliver immediately
                        # rather than parking clients on the next poll
                        self._dispatch_finish(inflight)
                        inflight = None
                else:
                    self._dispatch(batch)
            if self._tenants is not None:
                # the dispatch boundary: retiring tenants whose queued
                # work has drained release their appliers HERE, never
                # while a dispatch is in flight
                self._tenants.sweep()
        if inflight is not None:
            self._dispatch_finish(inflight)
        # drain on stop: in-flight clients must not hang forever
        leftovers = [self._carry] if self._carry is not None else []
        self._carry = None
        leftovers.extend(self._q.drain())
        if leftovers:
            self._ctr["shed_stopped"].inc(len(leftovers))
            telemetry.emit("shed", f"serve{self._server_id}",
                           reason="stopped", n=len(leftovers))
        for p in leftovers:
            p.error = ServerStoppedError("server stopped")
            p.t_done = mono()
            p.event.set()
            self._tenant_done(p)

    # ----------------------------------------------------------- stats

    # Read-only views onto the registry counters: the historical
    # attribute surface (tests, benches) keeps working, and every
    # reader — /stats, bench JSON, a Prometheus /metrics scrape — sees
    # the ONE number the hot path bumped.
    @property
    def admitted(self) -> int:
        return int(self._ctr["admitted"].value)

    @property
    def shed_overload(self) -> int:
        return int(self._ctr["shed_overload"].value)

    @property
    def shed_breaker(self) -> int:
        return int(self._ctr["shed_breaker"].value)

    @property
    def shed_stopped(self) -> int:
        return int(self._ctr["shed_stopped"].value)

    @property
    def expired(self) -> int:
        return int(self._ctr["expired"].value)

    @property
    def deadline_misses(self) -> int:
        return int(self._ctr["deadline_misses"].value)

    @property
    def reloads(self) -> int:
        return int(self._ctr["reloads"].value)

    @property
    def dispatches(self) -> int:
        return int(self._dispatches_ctr.value)

    @property
    def requests(self) -> int:
        return int(self._requests_ctr.value)

    @property
    def images_served(self) -> int:
        return int(self._images_ctr.value)

    def stats(self) -> dict:
        with self._lock:
            sizes = list(self.batch_sizes)
            walls = list(self.dispatch_walls)
        out = {
            "dispatches": self.dispatches,
            "requests": self.requests,
            "images_served": self.images_served,
            "max_batch": self.max_batch,
            "max_wait_ms": self.max_wait_ms,
            "dispatch": self.applier.dispatch,
            "shapes": list(self.applier.shapes),
            # robustness counters (admission / shed / breaker /
            # reload) — sourced from the telemetry registry, the same
            # counters /metrics exports (docs/OBSERVABILITY.md)
            "admission": {
                "queue_depth": self.queue_depth,
                "queued": len(self._q),
                "admitted": self.admitted,
                "shed_overload": self.shed_overload,
                "shed_breaker": self.shed_breaker,
                "shed_stopped": self.shed_stopped,
                "expired": self.expired,
                "deadline_misses": self.deadline_misses,
                "lifo_takes": self._q.lifo_takes,
                "lifo_depth": self._q.lifo_depth,
                "lifo_age_ms": self._q.lifo_age_ms,
                "default_deadline_ms": self.default_deadline_ms,
            },
            "breaker": self.breaker.snapshot(),
            "reloads": self.reloads,
            "draining": self._closed.is_set(),
        }
        out["default_digest"] = self.default_digest
        # the explicit resident-policy identity (the canary comparator
        # reads this name; default_digest stays as the PR-12 alias)
        out["policy_digest"] = self.default_digest
        if getattr(self.applier, "donate", False) or self.double_buffer:
            # zero-copy data-plane knobs (opt-in; absent = the
            # historical PR-7 /stats surface, byte for byte)
            out["data_plane"] = {
                "donate": bool(getattr(self.applier, "donate", False)),
                "double_buffer": self.double_buffer}
        if self.traffic_stats:
            out["traffic"] = {
                "samples": self._traffic_samples,
                **{k: (None if v is None else round(v, 6))
                   for k, v in self._traffic_ema.items()}}
        if self._tenants is not None:
            out["tenancy"] = self._tenants.snapshot()
        if sizes:
            out["mean_batch"] = round(float(np.mean(sizes)), 2)
            out["mean_dispatch_ms"] = round(float(np.mean(walls)) * 1e3, 3)
        return out
