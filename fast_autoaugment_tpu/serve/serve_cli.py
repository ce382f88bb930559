"""Policy-serving CLI — turn a ``final_policy.json`` into an HTTP
augmentation endpoint.

    python -m fast_autoaugment_tpu.serve.serve_cli \
        --policy search_out/final_policy.json --image 32 --port 8765

Loads the learned policy, AOT-compiles the application kernels over the
padded batch shapes (through the compile seam — the persistent cache
under ``JAX_COMPILATION_CACHE_DIR`` lets a restarted server deserialize
them instead), and serves:

- ``POST /augment`` — body is an ``.npz`` with ``images``
  (``[n, H, W, C]`` uint8 or float32) and optionally ``seeds``
  (``[n]`` int, pinning per-image PRNG streams for reproducible
  serving), OR the zero-copy raw tensor format
  (``application/x-faa-raw``, serve/wire.py: dtype/shape header +
  contiguous bytes + optional ``[n, 2]`` uint32 PRNG keys — decoded as
  ``np.frombuffer`` views, no per-request copy), OR a same-host
  shared-memory descriptor (``application/x-faa-shm``, requires
  ``--shm-ingest``).  An ``X-FAA-Deadline-Ms`` header stamps the
  request's deadline: expired requests are SHED before dispatch
  instead of burning device work.  Response mirrors the request format
  (npz in -> npz out; raw in -> raw uint8 out of a pooled buffer).
  Requests from concurrent clients COALESCE into shared device
  dispatches (:class:`~fast_autoaugment_tpu.serve.PolicyServer`).
  Errors are structured JSON — 400 (malformed), 413 (body too large —
  refused on Content-Length BEFORE the body is read), 429 + a
  ``Retry-After`` header (queue full — back off), 503 (breaker open /
  draining / deadline missed), never a bare traceback.
- ``POST /augment_batch`` — N framed sub-requests in one body
  (``application/x-faa-frames``): the router's pipelined forwarding
  unit.  All sub-requests are submitted before any result is awaited,
  so they coalesce into shared dispatches; per-part status/body come
  back in response frames.
- ``POST /reload`` — hot policy reload: body is optional JSON
  ``{"policy": PATH}`` (default: the ``--policy`` the server started
  with, re-read).  The new policy AOT-warms off to the side and swaps
  in atomically — zero dropped requests, no half-policy batch.  SIGHUP
  triggers the same reload.
- ``POST /tenants/warm`` — multi-policy tenancy preload (requires
  ``--tenant-capacity``): body ``{"policy": PATH}`` AOT-warms the
  policy OFF TO THE SIDE and admits it as a resident tenant.  Requests
  then select it via the ``X-FAA-Policy-Digest`` header; a cold digest
  answers a structured 503 (``tenant_cold``) and — with a
  ``--policy-dir`` recipe — kicks a background warm
  (docs/SERVING.md "Multi-policy tenancy").
- ``GET /stats`` — serving accounting (admission/shed/breaker/reload
  counters included) + the ``compile_cache`` stamp and the device
  (``platform``/``device_kind``/``device_count``) the replica runs on.
- ``GET /healthz`` — LIVENESS: 200 while the process runs.
- ``GET /readyz`` — READINESS: 200 only while the server is admitting
  and the circuit breaker is closed; 503 while draining or broken (a
  load balancer stops routing here while ``/healthz`` still says the
  replica is alive).

SIGTERM triggers a graceful drain — stop admitting, finish in-flight
requests, exit **0** (the serving arm of the exit-code contract,
``core/resilience.py``).  ``--breaker-exit`` maps a latched-open
breaker to exit **77** ("restart me"): under
``launch/fleet.py --no-rank-args`` supervision the replica is
relaunched and returns to ready (docs/RESILIENCE.md "Serving under
overload").
"""

from __future__ import annotations

import argparse
import io
import json
import os
import signal
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from fast_autoaugment_tpu.core.telemetry import mono, wall
from fast_autoaugment_tpu.core.resilience import (
    PREEMPTED_EXIT_CODE,
    CircuitOpenError,
)
from fast_autoaugment_tpu.utils.logging import get_logger

logger = get_logger("faa_tpu.serve_cli")

#: default POST body bound: 64 MiB holds a 128-image float32 batch at
#: 224px with generous npz overhead; bigger bodies are a client bug or
#: an attack, either way 413
DEFAULT_MAX_BODY_MB = 64

DEADLINE_HEADER = "X-FAA-Deadline-Ms"

#: selects the TENANT policy a request is served by (multi-policy
#: tenancy, docs/SERVING.md); absent = the replica's default policy
DIGEST_HEADER = "X-FAA-Policy-Digest"


def build_policy_tensor(spec: str) -> np.ndarray:
    """``--policy`` -> [num_sub, num_op, 3] tensor.

    Accepts a path to a ``final_policy.json`` (the search's decoded
    sub-policy list) or a shipped archive name
    (``policies/archive.py``, e.g. ``fa_reduced_cifar10``)."""
    from fast_autoaugment_tpu.policies.archive import (
        load_policy,
        policy_to_tensor,
    )

    if os.path.exists(spec):
        with open(spec) as fh:
            raw = json.load(fh)
        if not raw:
            raise ValueError(f"{spec} holds an empty policy set")
        subs = [[(str(op), float(p), float(lv)) for op, p, lv in sub]
                for sub in raw]
        return np.asarray(policy_to_tensor(subs), np.float32)
    return np.asarray(policy_to_tensor(load_policy(spec)), np.float32)


def load_policy_provenance(spec: str) -> dict | None:
    """The provenance sidecar for a policy file, or None.

    ``<stem>.provenance.json`` next to ``<stem>.json`` (the control
    plane's re-search writes it — ``control/research.py`` owns the
    schema); archive names and policies without a sidecar simply have
    no provenance.  Unreadable sidecars read as None — serving must
    never fail on provenance bookkeeping."""
    if not spec or not str(spec).endswith(".json") \
            or not os.path.exists(spec):
        return None
    from fast_autoaugment_tpu.control.research import load_provenance

    return load_provenance(spec)


def _seed_keys(seeds) -> np.ndarray:
    """Per-image seeds -> [n, 2] uint32 PRNG keys (one PRNGKey per
    seed — the reproducible-serving contract)."""
    import jax
    import jax.numpy as jnp

    seeds = jnp.asarray(np.asarray(seeds, np.int64) & 0x7FFFFFFF,
                        jnp.uint32)
    return np.asarray(jax.vmap(jax.random.PRNGKey)(seeds), np.uint32)


class ServeState:
    """The mutable serving-process state the handler, the signal
    handlers and the supervision threads share: the live server, the
    reload recipe, the shutdown path and the process exit code."""

    def __init__(self, server, policy_spec: str, build_applier=None,
                 policy_dir: str | None = None):
        self.server = server
        self.policy_spec = policy_spec
        self.build_applier = build_applier  # policy tensor -> applier
        # provenance sidecar of the resident policy (written by the
        # control plane's warm-started re-search next to the policy
        # JSON — control/research.py): /stats and the /reload response
        # carry it so the canary comparator can verify WHICH policy
        # generation is actually answering (docs/CONTROL.md)
        self.provenance = load_policy_provenance(policy_spec)
        self.httpd = None
        self.exit_code = 0
        self.stop_event = threading.Event()
        self.reload_lock = threading.Lock()
        self.started_at = wall()
        # tenancy: cold-policy recipes (--policy-dir) + the
        # single-flight background-warm bookkeeping
        self.policy_dir = policy_dir
        self.tenant_warm_lock = threading.Lock()
        self.warming: set[str] = set()
        self._digest_cache: dict[str, tuple[float, str]] = {}

    # ------------------------------------------------------- readiness

    def ready(self) -> tuple[bool, str]:
        srv = self.server
        worker = srv._worker
        if worker is None or not worker.is_alive():
            return False, "worker not running"
        if srv.draining:
            return False, "draining"
        if srv.breaker.is_open():
            return False, "circuit breaker open"
        return True, "ok"

    # ------------------------------------------------------ hot reload

    def reload_policy(self, spec: str | None = None) -> dict:
        """Build a fresh applier (AOT-warming every padded shape OFF TO
        THE SIDE — live traffic keeps dispatching on the old one) and
        atomically swap it in.  Serialized: a concurrent reload gets a
        loud error instead of racing."""
        if self.build_applier is None:
            raise RuntimeError("reload not configured on this server")
        if not self.reload_lock.acquire(blocking=False):
            raise BlockingIOError("a reload is already in progress")
        try:
            spec = spec or self.policy_spec
            t0 = mono()
            policy = build_policy_tensor(spec)
            applier = self.build_applier(policy)
            info = self.server.swap_applier(applier)
            # info already echoes the resident digest (swap_applier);
            # attach the policy's provenance sidecar so the caller can
            # verify which GENERATION is now serving, not just which
            # bytes
            self.provenance = load_policy_provenance(spec)
            info.update(policy=spec, provenance=self.provenance,
                        warm_sec=round(mono() - t0, 3))
            logger.info("reload complete: %s", info)
            return info
        finally:
            self.reload_lock.release()

    # --------------------------------------------------------- tenancy

    def tenant_recipe(self, digest: str) -> str | None:
        """Resolve a cold digest to a policy file under
        ``--policy-dir``: ``<digest>.json`` directly, else any
        ``*.json`` whose tensor digest matches (mtime-cached scan)."""
        if not self.policy_dir:
            return None
        direct = os.path.join(self.policy_dir, f"{digest}.json")
        if os.path.exists(direct):
            return direct
        from fast_autoaugment_tpu.serve.policy_server import policy_digest

        try:
            names = sorted(os.listdir(self.policy_dir))
        except OSError:
            return None
        for name in names:
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.policy_dir, name)
            try:
                mtime = os.path.getmtime(path)
                cached = self._digest_cache.get(path)
                if cached is None or cached[0] != mtime:
                    cached = (mtime,
                              policy_digest(build_policy_tensor(path)))
                    self._digest_cache[path] = cached
                if cached[1] == digest:
                    return path
            except (OSError, ValueError) as e:
                logger.warning("policy-dir scan skipped %s: %s", path, e)
        return None

    def warm_tenant(self, spec: str) -> dict:
        """Build an applier for `spec` OFF TO THE SIDE (AOT-warming
        every padded shape while warm tenants keep dispatching) and
        admit it into the tenancy LRU."""
        if self.build_applier is None:
            raise RuntimeError("tenancy warm not configured")
        t0 = mono()
        policy = build_policy_tensor(spec)
        applier = self.build_applier(policy)
        info = self.server.warm_tenant(applier)
        info.update(policy=spec, warm_sec=round(mono() - t0, 3))
        logger.info("tenant warm complete: %s", info)
        return info

    def kick_background_warm(self, digest: str) -> bool:
        """Single-flight background AOT warm for a cold digest with a
        known recipe.  True when a warm is already running or was just
        kicked — the 503 answer then carries ``warming: true`` so the
        client (or router) retries once the tenant is resident."""
        if not getattr(self.server, "tenancy_enabled", False):
            return False
        with self.tenant_warm_lock:
            if digest in self.warming:
                return True
            spec = self.tenant_recipe(digest)
            if spec is None:
                return False
            self.warming.add(digest)

        def _go():
            try:
                self.warm_tenant(spec)
            except (ValueError, OSError, RuntimeError) as e:
                logger.error("background tenant warm %s failed: %s",
                             digest, e)
            finally:
                with self.tenant_warm_lock:
                    self.warming.discard(digest)

        threading.Thread(target=_go, daemon=True,
                         name=f"tenant-warm-{digest}").start()
        return True

    # -------------------------------------------------------- shutdown

    def initiate_shutdown(self, *, drain: bool, exit_code: int = 0,
                          drain_timeout: float = 30.0) -> None:
        """Run the shutdown sequence in a daemon thread: optionally
        drain (finish in-flight work), then stop the HTTP loop."""
        self.exit_code = exit_code
        self.stop_event.set()

        def _go():
            if drain:
                drained = self.server.drain(timeout=drain_timeout)
                logger.info("graceful drain %s",
                            "complete" if drained else "TIMED OUT")
            else:
                self.server.stop(timeout=5.0)
            if self.httpd is not None:
                self.httpd.shutdown()

        threading.Thread(target=_go, daemon=True,
                         name="serve-shutdown").start()


def make_handler(server, applier, state: ServeState | None = None,
                 max_body_bytes: int = DEFAULT_MAX_BODY_MB * 1024 * 1024,
                 max_inflight: int = 0, shm_ingest: bool = False):
    """The request handler bound to one PolicyServer instance.

    `state` arms the hardened surface (/readyz, /reload); without it
    (library/test use) those endpoints answer with a structured 503.
    `max_inflight` > 0 bounds concurrent /augment handler threads — a
    burst beyond it gets an immediate 503 instead of a parked thread
    (the threaded HTTP server must not hold a thread per queued
    request; admission itself never blocks either).  `shm_ingest`
    enables the same-host shared-memory lane (``application/x-faa-shm``
    descriptor bodies) — off by default because mapping client-named
    segments is a same-trust-domain contract (serve/wire.py)."""
    from fast_autoaugment_tpu.serve import wire
    from fast_autoaugment_tpu.serve.policy_server import (
        DeadlineExpiredError,
        ServeError,
        ServerOverloadedError,
        ServerStoppedError,
        TenantNotResidentError,
    )

    inflight = (threading.BoundedSemaphore(max_inflight)
                if max_inflight > 0 else None)
    # pooled response buffers: steady-state raw serialization checks a
    # standing buffer out, fills it in place and checks it back in —
    # zero per-request allocation on the serialize stage
    arena = wire.BufferArena()

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 => keep-alive by default: one TCP connection serves
        # many requests (every response carries Content-Length).  The
        # old HTTP/1.0 default closed the socket per request — the
        # fresh-TCP tax the data-plane rework removes.
        protocol_version = "HTTP/1.1"
        # reap idle keep-alive connections so each does not pin a
        # handler thread forever
        timeout = 60
        # persistent connections leave Linux's initial TCP quickack
        # mode; without TCP_NODELAY the headers/body write pair then
        # hits Nagle + delayed-ACK (~40ms per response)
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):  # route through our logger
            logger.info("http: " + fmt, *args)

        def _send(self, code: int, body: bytes, ctype: str,
                  headers: dict | None = None) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, code: int, obj,
                       headers: dict | None = None) -> None:
            self._send(code, json.dumps(obj).encode(), "application/json",
                       headers)

        def _send_error_json(self, code: int, err_type: str, msg: str,
                             retry_after_s: float | None = None) -> None:
            headers = {}
            if retry_after_s is not None:
                # ceil to whole seconds (Retry-After is integral)
                headers["Retry-After"] = str(max(1, int(retry_after_s + 0.999)))
            self._send_json(code, {"error": msg, "type": err_type}, headers)

        def _refuse(self, code: int, err_type: str, msg: str,
                    retry_after_s: float | None = None) -> None:
            """Refuse a request WITHOUT having read its body (the 413
            up-front path): under HTTP/1.1 keep-alive the unread bytes
            would poison the next request on this connection, so the
            refusal closes it — `Connection: close` on the wire plus
            ``close_connection`` so the handler loop stops reading."""
            self.close_connection = True
            headers = {"Connection": "close"}
            if retry_after_s is not None:
                headers["Retry-After"] = str(max(1, int(retry_after_s + 0.999)))
            self._send_json(code, {"error": msg, "type": err_type}, headers)

        # ------------------------------------------------------- GETs

        def do_GET(self):
            if self.path == "/healthz":
                # LIVENESS: the process is running — stays 200 through
                # overload, drain and an open breaker (that is what
                # /readyz is for)
                self._send_json(200, {"ok": True})
                return
            if self.path == "/readyz":
                if state is None:
                    self._send_error_json(503, "not_configured",
                                          "readiness not configured")
                    return
                ok, reason = state.ready()
                self._send_json(200 if ok else 503,
                                {"ready": ok, "reason": reason})
                return
            if self.path == "/stats":
                from fast_autoaugment_tpu.core.compilecache import (
                    compile_cache_stats,
                )
                from fast_autoaugment_tpu.parallel.mesh import device_stamp

                stats = server.stats()
                stats.update(device_stamp())
                stats["compile_cache"] = compile_cache_stats()
                stats["aot_compile"] = {
                    str(s): r for s, r in getattr(
                        server.applier, "compile_log", {}).items()}
                # resident-policy identity + provenance: the canary
                # comparator's check that THIS replica answers with the
                # generation it was told to serve (docs/CONTROL.md)
                stats["policy_provenance"] = (state.provenance
                                              if state is not None
                                              else None)
                self._send_json(200, stats)
                return
            if self.path == "/metrics":
                # Prometheus text exposition of the process-wide
                # telemetry registry — the SAME counters /stats reads
                # (core/telemetry.py; docs/OBSERVABILITY.md)
                from fast_autoaugment_tpu.core import telemetry

                self._send(200,
                           telemetry.registry().prometheus_text().encode(),
                           telemetry.PROMETHEUS_CONTENT_TYPE)
                return
            self._send_error_json(404, "unknown_path",
                                  f"unknown path {self.path}")

        # ------------------------------------------------------ POSTs

        def _read_body(self) -> bytes | None:
            """Bounded body read; answers 413/400 itself on refusal.
            The bound is enforced on Content-Length BEFORE any byte is
            buffered — an oversized request costs one header parse,
            never ``length`` bytes of memory — and every refusal closes
            the connection (the body was never read)."""
            try:
                length = int(self.headers.get("Content-Length", "0"))
            except ValueError:
                self._refuse(400, "bad_request",
                             "malformed Content-Length")
                return None
            if length <= 0:
                self._refuse(400, "bad_request",
                             "empty or missing body")
                return None
            if length > max_body_bytes:
                self._refuse(
                    413, "body_too_large",
                    f"body of {length} bytes exceeds the "
                    f"{max_body_bytes}-byte bound")
                return None
            return self.rfile.read(length)

        def _deadline_ms(self) -> float | None:
            raw = self.headers.get(DEADLINE_HEADER)
            if raw is None:
                return None
            try:
                ms = float(raw)
            except ValueError:
                raise ValueError(
                    f"malformed {DEADLINE_HEADER} header {raw!r}")
            if ms <= 0:
                raise ValueError(f"{DEADLINE_HEADER} must be > 0, got {ms}")
            return ms

        def _map_serve_error(self, e: BaseException
                             ) -> tuple[int, dict, dict] | None:
            """The typed serving error ladder as data: ``(status,
            json_obj, headers)``, or None for a non-serving exception
            (re-raise).  Shared by /augment, the shm lane and
            /augment_batch so every ingestion path speaks the same
            structured errors."""
            if isinstance(e, TimeoutError):
                # NOTE: checked before OSError — TimeoutError IS an
                # OSError subclass and must not read as a 400
                return 503, {"error": str(e), "type": "timeout"}, {}
            if isinstance(e, TenantNotResidentError):
                # cold tenant: structured 503 + (when a recipe exists)
                # a BACKGROUND warm — the request path never blocks on
                # an AOT compile; the router fails over to a replica
                # already holding the tenant
                warming = (state.kick_background_warm(e.digest)
                           if state is not None and e.digest
                           else False)
                headers = {"Retry-After": "1"} if warming else {}
                return 503, {"error": str(e), "type": "tenant_cold",
                             "digest": e.digest,
                             "resident": list(e.resident),
                             "warming": warming}, headers
            if isinstance(e, ServerOverloadedError):
                return 429, {"error": str(e), "type": "overloaded"}, \
                    {"Retry-After": str(max(1, int(e.retry_after_s + 0.999)))}
            if isinstance(e, CircuitOpenError):
                return 503, {"error": str(e), "type": "breaker_open"}, \
                    {"Retry-After": str(max(1, int(e.retry_after_s + 0.999)))}
            if isinstance(e, ServerStoppedError):
                return 503, {"error": str(e), "type": "draining"}, {}
            if isinstance(e, DeadlineExpiredError):
                return 503, {"error": str(e), "type": "deadline_expired"}, {}
            if isinstance(e, ServeError):
                return 500, {"error": str(e), "type": "dispatch_error"}, {}
            if isinstance(e, (KeyError, ValueError, OSError)):
                return 400, {"error": f"{type(e).__name__}: {e}",
                             "type": "bad_request"}, {}
            return None

        def _parse_images(self, body, ctype: str
                          ) -> tuple[np.ndarray, np.ndarray | None, bool]:
            """Decode one request body -> ``(images, keys, was_raw)``.

            Raw bodies (``application/x-faa-raw`` or the FAAR1 magic)
            decode as zero-copy ``np.frombuffer`` views — images plus
            optional per-image ``[n, 2]`` uint32 PRNG keys.  Everything
            else is the legacy npz fallback (a full decode copy per
            request — kept for compatibility, flagged in new serve code
            by faalint D4)."""
            if ctype == wire.RAW_CONTENT_TYPE \
                    or bytes(body[:len(wire.RAW_MAGIC)]) == wire.RAW_MAGIC:
                images, keys = wire.decode_raw(body)
                if images.ndim == 3:
                    images = images[None]
                return images, keys, True
            payload = np.load(io.BytesIO(body), allow_pickle=False)  # robust: allow — the legacy npz fallback lane; raw-format requests never take this branch
            images = np.asarray(payload["images"])
            if images.ndim == 3:
                images = images[None]
            keys = None
            if "seeds" in payload.files:
                keys = _seed_keys(payload["seeds"])
            return images, keys, False

        def _send_result(self, out: np.ndarray, was_raw: bool) -> None:
            """Serialize + send one 200 result, timing the serialize
            stage.  Raw responses assemble header + uint8 payload into
            a pooled arena buffer (one fused clip-cast copy, zero
            allocation); npz requests get the legacy npz response."""
            t0 = mono()
            if was_raw:
                np.clip(out, 0, 255, out=out)
                view, lease = wire.encode_raw_into(arena, out,
                                                   as_dtype=np.uint8)
                try:
                    self._send(200, view, wire.RAW_CONTENT_TYPE)
                finally:
                    arena.checkin(lease)
            else:
                buf = io.BytesIO()
                np.savez(buf, images=np.clip(out, 0, 255).astype(np.uint8))  # robust: allow — the legacy npz fallback lane (response mirrors the request format)
                self._send(200, buf.getvalue(), "application/octet-stream")
            server.observe_stage("serialize", mono() - t0)

        def _do_augment(self) -> None:
            if inflight is not None and not inflight.acquire(blocking=False):
                self._refuse(
                    503, "handler_overloaded",
                    "all handler slots busy — retry", retry_after_s=0.1)
                return
            try:
                body = self._read_body()
                if body is None:
                    return
                ctype = (self.headers.get("Content-Type") or "") \
                    .split(";")[0].strip().lower()
                if ctype == wire.SHM_CONTENT_TYPE:
                    self._do_augment_shm(body)
                    return
                try:
                    deadline_ms = self._deadline_ms()
                    t0 = mono()
                    images, keys, was_raw = self._parse_images(body, ctype)
                    server.observe_stage("decode", mono() - t0)
                    digest = self.headers.get(DIGEST_HEADER)
                    pending = server.submit(images, keys,
                                            deadline_ms=deadline_ms,
                                            digest=digest)
                    out = server.result(pending)
                except Exception as e:  # noqa: BLE001 — mapped to the typed ladder
                    resp = self._map_serve_error(e)
                    if resp is None:
                        raise
                    status, obj, headers = resp
                    self._send_json(status, obj, headers)
                    return
                self._send_result(out, was_raw)
            finally:
                if inflight is not None:
                    inflight.release()

        def _do_augment_shm(self, body: bytes) -> None:
            """The same-host shared-memory lane: the body is a tiny
            JSON descriptor naming a client-created shm segment; the
            tensor never touches the socket.  The uint8 result is
            written back over the segment in place and the response is
            a descriptor echo."""
            from multiprocessing import shared_memory

            if not shm_ingest:
                self._send_error_json(
                    403, "shm_disabled",
                    "shared-memory ingestion requires --shm-ingest")
                return
            seg = None
            pending = None
            images = None
            try:
                try:
                    name, dtype, shape, keys = wire.decode_shm_request(body)
                    t0 = mono()
                    seg = shared_memory.SharedMemory(name=name)
                    # attaching registers the segment with OUR resource
                    # tracker (bpo-39959), which would unlink the
                    # CLIENT's segment at replica exit — the client
                    # owns the lifecycle, so unregister immediately
                    try:
                        from multiprocessing import resource_tracker
                        resource_tracker.unregister(
                            seg._name, "shared_memory")
                    except (ImportError, AttributeError, KeyError):
                        pass
                    images = np.ndarray(shape, dtype, buffer=seg.buf)
                    server.observe_stage("decode", mono() - t0)
                    deadline_ms = self._deadline_ms()
                    digest = self.headers.get(DIGEST_HEADER)
                    pending = server.submit(images, keys,
                                            deadline_ms=deadline_ms,
                                            digest=digest)
                    images = None  # drop our view; the pending holds one
                    out = server.result(pending)
                except FileNotFoundError as e:
                    self._send_error_json(400, "bad_request",
                                          f"unknown shm segment: {e}")
                    return
                except Exception as e:  # noqa: BLE001 — mapped to the typed ladder
                    resp = self._map_serve_error(e)
                    if resp is None:
                        raise
                    status, obj, headers = resp
                    self._send_json(status, obj, headers)
                    return
                t0 = mono()
                result_region = np.ndarray(shape, np.uint8, buffer=seg.buf)
                np.clip(out, 0, 255, out=out)
                np.copyto(result_region, out.reshape(shape),
                          casting="unsafe")
                del result_region
                server.observe_stage("serialize", mono() - t0)
                self._send_json(200, {"ok": True, "shm": name,
                                      "dtype": "uint8",
                                      "shape": list(shape)})
            finally:
                # drop OUR view first: on the error-response paths
                # above (shed/cold-tenant/bad-shape) the local still
                # pins the mapping, and a BufferError'd close() would
                # leak the map until a GC pass — under a flash crowd
                # that is a real /dev/shm-backed memory leak
                images = None  # the rebind releases the view
                if pending is not None:
                    # drop the pending's zero-copy view into the
                    # segment so close() below can release the mapping
                    pending.images = None
                if seg is not None:
                    try:
                        seg.close()
                    except BufferError:
                        pass  # a live view still pins the map; the GC releases it (narrow except: no lint rule fires)

        def _do_augment_batch(self) -> None:
            """POST /augment_batch: N framed sub-requests in ONE body
            (serve/wire.py frames) — the router's pipelined forwarding
            unit.  ALL sub-requests are submitted before any result is
            awaited, so they coalesce into shared device dispatches;
            the response frames carry per-part status/body."""
            body = self._read_body()
            if body is None:
                return
            try:
                parts = wire.decode_frames(body)
            except ValueError as e:
                self._send_error_json(400, "bad_request",
                                      f"bad frame payload: {e}")
                return
            slots: list = [None] * len(parts)
            submitted: list = []
            t0 = mono()
            for i, (meta, pbody) in enumerate(parts):
                try:
                    images, keys, was_raw = self._parse_images(
                        pbody, str(meta.get("ctype", "")).lower())
                    deadline_ms = meta.get("deadline_ms")
                    pending = server.submit(
                        images, keys,
                        deadline_ms=(None if deadline_ms is None
                                     else float(deadline_ms)),
                        digest=meta.get("digest"))
                    submitted.append((i, pending, was_raw))
                except Exception as e:  # noqa: BLE001 — mapped per part
                    resp = self._map_serve_error(e)
                    if resp is None:
                        raise
                    status, obj, headers = resp
                    slots[i] = ({"status": status,
                                 "ctype": "application/json",
                                 "headers": headers},
                                json.dumps(obj).encode())
            server.observe_stage("decode", mono() - t0)
            for i, pending, was_raw in submitted:
                try:
                    out = server.result(pending)
                except Exception as e:  # noqa: BLE001 — mapped per part
                    resp = self._map_serve_error(e)
                    if resp is None:
                        raise
                    status, obj, headers = resp
                    slots[i] = ({"status": status,
                                 "ctype": "application/json",
                                 "headers": headers},
                                json.dumps(obj).encode())
                    continue
                t1 = mono()
                np.clip(out, 0, 255, out=out)
                if was_raw:
                    pb = wire.encode_raw(out.astype(np.uint8))
                    ct = wire.RAW_CONTENT_TYPE
                else:
                    buf = io.BytesIO()
                    np.savez(buf, images=out.astype(np.uint8))  # robust: allow — legacy npz fallback lane for npz sub-requests
                    pb = buf.getvalue()
                    ct = "application/octet-stream"
                server.observe_stage("serialize", mono() - t1)
                slots[i] = ({"status": 200, "ctype": ct, "headers": {}},
                            pb)
            self._send(200, wire.encode_frames(slots),
                       wire.FRAME_CONTENT_TYPE)

        def _do_reload(self) -> None:
            if state is None:
                self._send_error_json(503, "not_configured",
                                      "reload not configured")
                return
            spec = None
            length = int(self.headers.get("Content-Length", "0") or 0)
            if length > 0:
                if length > max_body_bytes:
                    self._send_error_json(413, "body_too_large",
                                          "reload body too large")
                    return
                try:
                    req = json.loads(self.rfile.read(length) or b"{}")
                    spec = req.get("policy")
                except (ValueError, AttributeError):
                    self._send_error_json(400, "bad_request",
                                          "reload body must be JSON "
                                          '{"policy": PATH}')
                    return
            try:
                info = state.reload_policy(spec)
            except BlockingIOError as e:
                self._send_error_json(409, "reload_in_progress", str(e))
                return
            except (KeyError, ValueError, OSError, RuntimeError) as e:
                # KeyError: an unknown policy-archive name — same
                # client-error class as a bad path
                self._send_error_json(400, "reload_failed",
                                      f"{type(e).__name__}: {e}")
                return
            self._send_json(200, {"reloaded": True, **info})

        def _do_tenant_warm(self) -> None:
            """POST /tenants/warm {"policy": PATH}: AOT-warm a policy
            off to the side and admit it as a resident tenant (the
            operator/router preload path)."""
            if state is None:
                self._send_error_json(503, "not_configured",
                                      "tenancy warm not configured")
                return
            length = int(self.headers.get("Content-Length", "0") or 0)
            spec = None
            if length > 0:
                if length > max_body_bytes:
                    self._send_error_json(413, "body_too_large",
                                          "warm body too large")
                    return
                try:
                    req = json.loads(self.rfile.read(length) or b"{}")
                    spec = req.get("policy")
                except (ValueError, AttributeError):
                    spec = None
            if not spec:
                self._send_error_json(400, "bad_request",
                                      "warm body must be JSON "
                                      '{"policy": PATH}')
                return
            try:
                info = state.warm_tenant(spec)
            except (KeyError, ValueError, OSError, RuntimeError) as e:
                # KeyError: an unknown policy-archive name from
                # build_policy_tensor — a client error, not a crash
                self._send_error_json(400, "warm_failed",
                                      f"{type(e).__name__}: {e}")
                return
            self._send_json(200, {"warmed": True, **info})

        def do_POST(self):
            try:
                if self.path == "/augment":
                    self._do_augment()
                elif self.path == "/augment_batch":
                    self._do_augment_batch()
                elif self.path == "/reload":
                    self._do_reload()
                elif self.path == "/tenants/warm":
                    self._do_tenant_warm()
                else:
                    self._send_error_json(404, "unknown_path",
                                          f"unknown path {self.path}")
            except Exception as e:  # noqa: BLE001 — never a bare traceback
                logger.error("http handler failed on %s: %s", self.path, e)
                try:
                    self._send_error_json(500, "internal",
                                          f"{type(e).__name__}: {e}")
                except OSError:
                    pass  # client already gone (narrow except: no lint rule fires)

    return Handler


class _ServeHTTPServer(ThreadingHTTPServer):
    # handler threads must not block interpreter exit after shutdown()
    daemon_threads = True


# ---------------------------------------------------- fleet integration


def _write_beat(path: str, tag: str, done: bool = False) -> None:
    """Atomic host-beat write in the fleet/workqueue schema
    (``hosts/<tag>.json``) so ``launch/fleet.py --heartbeat-timeout``
    can SIGKILL a wedged serving replica."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump({"owner": tag, "heartbeat": wall(), "done": done}, fh)
    os.replace(tmp, path)


def _beat_loop(state: ServeState, beat_dir: str, tag: str,
               interval_s: float) -> None:
    host_dir = os.path.join(beat_dir, "hosts")
    os.makedirs(host_dir, exist_ok=True)
    path = os.path.join(host_dir, f"{tag}.json")
    while not state.stop_event.wait(interval_s):
        try:
            _write_beat(path, tag)
        except OSError as e:
            logger.warning("host beat write failed: %s", e)
    try:
        _write_beat(path, tag, done=True)
    except OSError as e:
        logger.warning("final host beat write failed: %s", e)


def _breaker_exit_loop(state: ServeState, poll_s: float = 0.2) -> None:
    """``--breaker-exit``: a latched-open breaker turns into exit 77 —
    "restart me" — so a fleet supervisor relaunches the replica instead
    of load-balancers routing at a permanently-broken backend."""
    while not state.stop_event.wait(poll_s):
        snap = state.server.breaker.snapshot()
        if snap["state"] == "open":
            logger.error(
                "circuit breaker open with --breaker-exit: shutting down "
                "with exit %d for supervised restart", PREEMPTED_EXIT_CODE)
            state.initiate_shutdown(drain=False,
                                    exit_code=PREEMPTED_EXIT_CODE)
            return


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="fast-autoaugment-tpu policy-serving endpoint")
    p.add_argument("--policy", required=True,
                   help="final_policy.json path or a shipped archive name")
    p.add_argument("--image", type=int, default=32,
                   help="served image resolution (client resizes)")
    p.add_argument("--shapes", default="1,8,32,128",
                   help="comma-separated padded batch shapes to AOT-compile")
    p.add_argument("--dispatch", default="auto",
                   choices=("auto", "exact", "grouped"),
                   help="policy-application kernel: 'exact' = per-image "
                        "keys, bitwise apply_policy per lane; 'grouped' = "
                        "scalar-dispatch batch kernel (one switch branch "
                        "executes); 'auto' (default) = exact for a "
                        "single-sub policy, grouped otherwise")
    p.add_argument("--groups", type=int, default=8,
                   help="chunk count for the grouped kernel")
    p.add_argument("--max-batch", type=int, default=None,
                   help="coalescer cap (default: the largest AOT shape)")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="coalescing window after the first queued request")
    p.add_argument("--telemetry", default="off", metavar="{off,DIR}",
                   help="flight-recorder journal dir (core/telemetry.py): "
                        "typed dispatch/shed/breaker/reload events with "
                        "rotation-bounded size, renderable via tools/"
                        "trace_export.py.  'off' (default) = no journal "
                        "I/O (still honors an inherited FAA_TELEMETRY); "
                        "GET /metrics exposes the in-memory registry "
                        "either way")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8765)
    # ---------------- overload / resilience knobs (defaults = PR-7
    # clean-weather behavior, except fail-fast admission) ------------
    p.add_argument("--queue-depth", type=int, default=4096,
                   help="bounded request queue; a full queue answers 429 "
                        "+ Retry-After IMMEDIATELY (admission never "
                        "blocks a handler thread)")
    p.add_argument("--default-deadline-ms", type=float, default=None,
                   help="deadline stamped on requests without an "
                        f"{DEADLINE_HEADER} header; expired requests are "
                        "shed before dispatch (default: none)")
    p.add_argument("--lifo-depth", type=int, default=0,
                   help="queue depth at/above which draining flips to "
                        "adaptive-LIFO (newest-first) — under sustained "
                        "overload the oldest requests are the ones whose "
                        "clients already gave up.  0 = pure FIFO")
    p.add_argument("--lifo-age-ms", type=float, default=0.0,
                   help="oldest-queued-request age that flips draining to "
                        "adaptive-LIFO.  0 = off")
    p.add_argument("--breaker-threshold", type=int, default=0,
                   help="consecutive dispatch failures that open the "
                        "circuit breaker (fail fast + /readyz 503).  "
                        "0 = breaker disabled")
    p.add_argument("--breaker-cooldown", type=float, default=5.0,
                   help="seconds an open breaker fails fast before "
                        "admitting one half-open probe")
    p.add_argument("--dispatch-timeout", type=float, default=0.0,
                   help="dispatch wall above this counts as a breaker "
                        "failure even when results arrive (a straggler "
                        "budget; pairs with --watchdog for true hangs).  "
                        "0 = off")
    p.add_argument("--dispatch-floor-ms", type=float, default=0.0,
                   help="deliberate per-dispatch service-time floor in "
                        "ms (game-day drills: emulates a heavy model so "
                        "a 1-core host reaches real overload "
                        "deterministically).  0 = off")
    p.add_argument("--breaker-exit", action="store_true",
                   help="exit 77 ('restart me') when the breaker opens — "
                        "under fleet supervision (--no-rank-args) the "
                        "replica is relaunched and returns to ready")
    p.add_argument("--watchdog", default="off", metavar="{off,auto,SECONDS}",
                   help="deadline-guard each AOT dispatch "
                        "(core/watchdog.py); serve labels are AOT-loaded "
                        "so their first call gets the bounded warm "
                        "allowance, and a fired watchdog is a breaker "
                        "failure")
    p.add_argument("--max-body-mb", type=int, default=DEFAULT_MAX_BODY_MB,
                   help="POST body bound; larger bodies answer 413")
    p.add_argument("--max-inflight", type=int, default=0,
                   help="bound on concurrent /augment handler threads; a "
                        "burst beyond it answers 503 immediately instead "
                        "of parking a thread per queued request.  0 = "
                        "unbounded (historical)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="seconds the SIGTERM graceful drain waits for "
                        "in-flight requests before exiting anyway")
    p.add_argument("--serve-seconds", type=float, default=0.0,
                   help="gracefully drain and exit 0 after this many "
                        "seconds (bounded drills / tests).  0 = serve "
                        "forever")
    p.add_argument("--heartbeat-dir", default=None, metavar="DIR",
                   help="write fleet-schema host beats to "
                        "DIR/hosts/<tag>.json so a fleet supervisor's "
                        "--heartbeat-timeout can SIGKILL a wedged replica")
    p.add_argument("--host-tag", default=None,
                   help="host beat tag (default host<FAA_HOST_ID or 0>)")
    p.add_argument("--port-file", default=None, metavar="PATH",
                   help="write the BOUND port (supports --port 0) to PATH "
                        "— how supervised tests find the replica")
    p.add_argument("--port-dir", default=None, metavar="DIR",
                   help="atomically write DIR/<tag>.json ({tag, host, "
                        "port, pid}) on bind and remove it on exit — "
                        "the shared replica-discovery dir the serving "
                        "ROUTER (serve/router.py) and faa_status census "
                        "watch; fleet --no-rank-args replicas need no "
                        "static port plan (docs/SERVING.md)")
    # ---------------- multi-policy tenancy (defaults off = the
    # single-policy PR-11 byte-identical stream) ----------------------
    p.add_argument("--tenant-capacity", type=int, default=0,
                   help="resident-tenant LRU capacity for multi-policy "
                        "tenancy: requests select a policy via the "
                        f"{DIGEST_HEADER} header, cold policies AOT-warm "
                        "off to the side, the LRU tenant retires at a "
                        "dispatch boundary.  0 = single-policy serving "
                        "(historical)")
    p.add_argument("--policy-dir", default=None, metavar="DIR",
                   help="cold-tenant recipes: a requested-but-cold "
                        "digest resolving to DIR/<digest>.json (or any "
                        "*.json with a matching tensor digest) kicks a "
                        "BACKGROUND warm; the 503 answer carries "
                        "warming=true so clients/routers retry once "
                        "resident")
    # ---------------- closed-loop control plane (defaults off = the
    # historical journal/stats stream byte-identical) ------------------
    # ---------------- zero-copy data plane (defaults off = the
    # historical npz + synchronous-dispatch path, bit-for-bit) --------
    p.add_argument("--donate", action="store_true",
                   help="donated-buffer dispatch: the AOT executables "
                        "compile with donate_argnums over the image "
                        "batch and the coalescer stages each batch into "
                        "standing double buffers — steady-state serving "
                        "allocates nothing per dispatch (bitwise outputs "
                        "pinned against the undonated path)")
    p.add_argument("--double-buffer", action="store_true",
                   help="pipelined dispatch: the coalescer pads/stages "
                        "batch k+1 while batch k computes on device "
                        "(JAX async dispatch) — host staging overlaps "
                        "device work instead of serializing with it")
    p.add_argument("--shm-ingest", action="store_true",
                   help="enable the same-host shared-memory ingestion "
                        "lane: application/x-faa-shm descriptor bodies "
                        "map a client-created segment and write the "
                        "uint8 result back in place (same trust domain "
                        "only — the server maps client-named segments)")
    p.add_argument("--traffic-stats", action="store_true",
                   help="publish served-traffic statistics: per-dispatch "
                        "input moments + a reward proxy (mean normalized "
                        "|out-in|) as faa_serve_{input_mean,input_std,"
                        "reward_proxy} gauges, /stats 'traffic', and "
                        "fields on the journal's serve dispatch events — "
                        "the drift monitor / canary comparator signal "
                        "(control/, docs/CONTROL.md)")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    from fast_autoaugment_tpu.core.compilecache import (
        compile_cache_stats,
        configure_compile_cache,
    )
    from fast_autoaugment_tpu.core.watchdog import resolve_watchdog
    from fast_autoaugment_tpu.serve.policy_server import (
        AotPolicyApplier,
        PolicyServer,
    )

    configure_compile_cache()
    from fast_autoaugment_tpu.core.telemetry import configure_telemetry

    configure_telemetry(args.telemetry)
    shapes = tuple(int(s) for s in str(args.shapes).split(",") if s)
    watchdog = resolve_watchdog(args.watchdog)

    def build_applier(policy_tensor):
        return AotPolicyApplier(
            policy_tensor, image=args.image, shapes=shapes,
            dispatch=args.dispatch, groups=args.groups,
            watchdog=watchdog if watchdog.enabled else None,
            donate=args.donate)

    policy = build_policy_tensor(args.policy)
    applier = build_applier(policy)
    server = PolicyServer(
        applier, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        queue_depth=args.queue_depth,
        default_deadline_ms=args.default_deadline_ms,
        lifo_depth=args.lifo_depth, lifo_age_ms=args.lifo_age_ms,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown,
        dispatch_timeout_s=args.dispatch_timeout,
        tenant_capacity=args.tenant_capacity,
        traffic_stats=args.traffic_stats,
        double_buffer=args.double_buffer,
        dispatch_floor_ms=args.dispatch_floor_ms).start()
    state = ServeState(server, args.policy, build_applier,
                       policy_dir=args.policy_dir)
    cc = compile_cache_stats()
    logger.info(
        "serving %d sub-policies (dispatch=%s) at http://%s:%d — AOT "
        "compile paid up front (%s; cache hits=%d misses=%d)",
        applier.num_sub, applier.dispatch, args.host, args.port,
        {s: r["sec"] for s, r in applier.compile_log.items()},
        cc["hits"], cc["misses"])

    httpd = _ServeHTTPServer(
        (args.host, args.port),
        make_handler(server, applier, state=state,
                     max_body_bytes=args.max_body_mb * 1024 * 1024,
                     max_inflight=args.max_inflight,
                     shm_ingest=args.shm_ingest))
    state.httpd = httpd
    bound_port = httpd.server_address[1]
    if args.port_file:
        with open(args.port_file, "w") as fh:
            fh.write(str(bound_port))
    replica_tag = args.host_tag or f"host{os.environ.get('FAA_HOST_ID', '0')}"
    port_dir_path = None
    if args.port_dir:
        # atomic replica-discovery record: the router's census scans
        # these; a relaunch (same tag) atomically overwrites its
        # predecessor's record
        os.makedirs(args.port_dir, exist_ok=True)
        port_dir_path = os.path.join(args.port_dir, f"{replica_tag}.json")
        tmp = f"{port_dir_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump({"tag": replica_tag, "host": args.host,
                       "port": bound_port, "pid": os.getpid(),
                       "started_at": wall()}, fh)
        os.replace(tmp, port_dir_path)
    logger.info("listening on http://%s:%d (readyz/healthz/stats/"
                "augment/reload/tenants)", args.host, bound_port)

    def shutdown(signum, frame):
        # graceful drain: stop admitting, finish in-flight, exit 0 —
        # the serving arm of the exit-code contract
        logger.info("signal %d: draining and shutting down", signum)
        state.initiate_shutdown(drain=True, exit_code=0,
                                drain_timeout=args.drain_timeout)

    def reload_sig(signum, frame):
        logger.info("SIGHUP: hot policy reload")

        def _go():
            try:
                state.reload_policy()
            except (BlockingIOError, ValueError, OSError,
                    RuntimeError) as e:
                logger.error("SIGHUP reload failed: %s", e)

        threading.Thread(target=_go, daemon=True, name="sighup-reload").start()

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)
    signal.signal(signal.SIGHUP, reload_sig)

    if args.breaker_exit:
        threading.Thread(target=_breaker_exit_loop, args=(state,),
                         daemon=True, name="breaker-exit").start()
    if args.heartbeat_dir:
        threading.Thread(target=_beat_loop,
                         args=(state, args.heartbeat_dir, replica_tag, 1.0),
                         daemon=True, name="host-beat").start()
    if args.serve_seconds > 0:
        timer = threading.Timer(
            args.serve_seconds,
            lambda: state.initiate_shutdown(
                drain=True, exit_code=0, drain_timeout=args.drain_timeout))
        timer.daemon = True
        timer.start()

    try:
        httpd.serve_forever()
    finally:
        state.stop_event.set()
        httpd.server_close()
        server.stop()
        if port_dir_path is not None:
            try:
                # leave no stale discovery record: the router census
                # drops this replica instead of health-polling a ghost
                os.remove(port_dir_path)
            except OSError as e:
                logger.warning("could not remove port-dir record: %s", e)
    return state.exit_code


if __name__ == "__main__":
    sys.exit(main())
