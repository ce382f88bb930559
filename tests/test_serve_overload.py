"""Overload-safe policy serving (docs/RESILIENCE.md "Serving under
overload"): admission control, deadline shedding, adaptive-LIFO
watermarks, the circuit breaker, hot policy reload, graceful drain and
the fleet-supervised replica-restart path.

The fast tests drive :class:`PolicyServer` with a host-only dummy
applier (no XLA compiles — tier-1 stays inside its 870s wall); the
chaos/e2e drills that need real AOT executables or subprocess replicas
are ``slow``-marked.
"""

from __future__ import annotations

import io
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fast_autoaugment_tpu.core.resilience import (
    PREEMPTED_EXIT_CODE,
    CircuitBreaker,
    CircuitOpenError,
)
from fast_autoaugment_tpu.serve.policy_server import (
    DeadlineExpiredError,
    PolicyServer,
    ServeError,
    ServerOverloadedError,
    ServerStoppedError,
    _RequestQueue,
)
from fast_autoaugment_tpu.utils import faultinject

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

IMG = 8


class DummyApplier:
    """Host-only applier standing in for the AOT executables: shifts
    pixel values by `delta` so tests can tell WHICH applier served a
    request (the hot-reload atomicity check)."""

    def __init__(self, delta=1.0, dispatch="exact", max_batch=4,
                 wall_s=0.0):
        self.delta = float(delta)
        self.dispatch = dispatch
        self.max_batch = max_batch
        self.image = IMG
        self.channels = 3
        self.num_sub = 1
        self.shapes = (max_batch,)
        self.wall_s = float(wall_s)
        self.calls = 0

    def apply(self, images, keys):
        self.calls += 1
        if self.wall_s:
            time.sleep(self.wall_s)
        return np.asarray(images, np.float32) + self.delta


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, IMG, IMG, 3)).astype(np.float32)


def _keys(n, base=0):
    # fixed host-side keys: the dummy applier ignores them
    return np.full((n, 2), base, np.uint32)


@pytest.fixture(autouse=True)
def _clean_fault_env():
    saved = os.environ.pop("FAA_FAULT", None)
    saved_at = os.environ.pop("FAA_ATTEMPT", None)
    faultinject.reset()
    yield
    if saved is None:
        os.environ.pop("FAA_FAULT", None)
    else:
        os.environ["FAA_FAULT"] = saved
    if saved_at is None:
        os.environ.pop("FAA_ATTEMPT", None)
    else:
        os.environ["FAA_ATTEMPT"] = saved_at
    faultinject.reset()


# ------------------------------------------------- admission control


def test_submit_never_blocks_on_full_queue():
    """The blocking-admission bug fix: a full queue rejects IMMEDIATELY
    with the typed overload error (the old path parked the caller for
    up to 30s)."""
    srv = PolicyServer(DummyApplier(), queue_depth=2)
    srv.submit(_images(1), _keys(1))
    srv.submit(_images(1), _keys(1))
    t0 = time.perf_counter()
    with pytest.raises(ServerOverloadedError) as ei:
        srv.submit(_images(1), _keys(1))
    assert time.perf_counter() - t0 < 1.0  # fail-fast, not a 30s park
    assert ei.value.retry_after_s > 0
    assert srv.stats()["admission"]["shed_overload"] == 1
    assert srv.stats()["admission"]["admitted"] == 2


def test_submit_after_stop_is_typed_not_racing():
    srv = PolicyServer(DummyApplier()).start()
    srv.stop()
    with pytest.raises(ServerStoppedError):
        srv.submit(_images(1), _keys(1))
    assert srv.stats()["admission"]["shed_stopped"] >= 1


def test_validation_errors_still_valueerror():
    """Bad requests stay ValueError (HTTP 400), not overload errors."""
    srv = PolicyServer(DummyApplier(max_batch=4))
    with pytest.raises(ValueError):
        srv.submit(_images(5), _keys(5))  # oversize
    with pytest.raises(ValueError):
        srv.submit(np.zeros((0, IMG, IMG, 3), np.float32))  # empty


# --------------------------------------------- deadline-aware shedding


def test_expired_requests_shed_before_dispatch():
    """Dead work never reaches the device: requests whose deadline
    passed while queued are retired with the typed error and ZERO
    applier calls."""
    ap = DummyApplier()
    srv = PolicyServer(ap)
    p1 = srv.submit(_images(1), _keys(1), deadline_ms=1)
    p2 = srv.submit(_images(1), _keys(1), deadline_ms=1)
    time.sleep(0.05)  # both deadlines pass while the worker is down
    srv.start()
    for p in (p1, p2):
        with pytest.raises(DeadlineExpiredError):
            srv.result(p)
    assert ap.calls == 0
    st = srv.stats()["admission"]
    assert st["expired"] == 2 and st["deadline_misses"] == 0
    srv.stop()


def test_result_wait_is_deadline_bounded():
    """A client never hangs past its deadline (plus the shed grace):
    even with the worker down, result() times out promptly."""
    srv = PolicyServer(DummyApplier())
    srv.deadline_grace_s = 0.2
    p = srv.submit(_images(1), _keys(1), deadline_ms=50)
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        srv.result(p, timeout=60.0)
    assert time.perf_counter() - t0 < 5.0


def test_default_deadline_applies():
    srv = PolicyServer(DummyApplier(), default_deadline_ms=25.0)
    p = srv.submit(_images(1), _keys(1))
    assert p.deadline is not None
    srv2 = PolicyServer(DummyApplier())
    assert srv2.submit(_images(1), _keys(1)).deadline is None


def test_deadline_miss_counted_on_late_completion():
    """A dispatch that finishes past the deadline still delivers, but
    the miss is counted (the bench's deadline-miss-rate source)."""
    srv = PolicyServer(DummyApplier(wall_s=0.08), max_wait_ms=1)
    p = srv.submit(_images(1), _keys(1), deadline_ms=20)
    srv.start()
    out = srv.result(p, timeout=10.0)  # grace covers the late scatter
    assert out.shape == (1, IMG, IMG, 3)
    assert srv.stats()["admission"]["deadline_misses"] == 1
    srv.stop()


# ------------------------------------------------ adaptive-LIFO drain


def test_lifo_depth_watermark_serves_newest_first():
    srv = PolicyServer(DummyApplier(), max_batch=1, max_wait_ms=1,
                       lifo_depth=2)
    pend = [srv.submit(_images(1), _keys(1)) for _ in range(3)]
    srv.start()
    for p in pend:
        srv.result(p, timeout=10.0)
    # newest (index 2) served first, oldest (index 0) last
    assert pend[2].t_done < pend[1].t_done < pend[0].t_done
    assert srv.stats()["admission"]["lifo_takes"] >= 1
    srv.stop()


def test_fifo_is_default_drain_order():
    srv = PolicyServer(DummyApplier(), max_batch=1, max_wait_ms=1)
    pend = [srv.submit(_images(1), _keys(1)) for _ in range(3)]
    srv.start()
    for p in pend:
        srv.result(p, timeout=10.0)
    assert pend[0].t_done < pend[1].t_done < pend[2].t_done
    assert srv.stats()["admission"]["lifo_takes"] == 0
    srv.stop()


def test_request_queue_age_watermark():
    q = _RequestQueue(10, lifo_age_ms=20.0)
    from fast_autoaugment_tpu.serve.policy_server import _Pending

    a = _Pending(_images(1), None)
    q.offer(a)
    b = _Pending(_images(1), None)
    q.offer(b)
    assert q.take(0.01) is a  # young queue: FIFO
    q.offer(a)
    time.sleep(0.03)  # oldest age crosses the watermark
    c = _Pending(_images(1), None)
    q.offer(c)
    assert q.take(0.01) is c  # newest-first now
    assert q.lifo_takes == 1


# ---------------------------------------------------- circuit breaker


def test_circuit_breaker_unit():
    b = CircuitBreaker(threshold=0)
    assert not b.enabled and b.allow() and not b.is_open()
    b.record_failure()  # disabled: never opens
    assert b.snapshot()["state"] == "disabled"

    b = CircuitBreaker(threshold=2, cooldown_s=0.1)
    assert b.allow()
    b.record_failure()
    assert not b.is_open()  # one failure below threshold
    b.record_failure()
    assert b.is_open() and b.fires == 1 and not b.allow()
    time.sleep(0.12)
    assert not b.is_open()  # cooldown elapsed: probe-eligible
    assert b.allow()        # the single half-open probe
    assert not b.allow()    # second concurrent probe refused
    b.record_failure()      # probe failed: re-open
    assert b.fires == 2 and b.is_open()
    time.sleep(0.12)
    assert b.allow()
    b.record_success()      # probe succeeded: closed
    assert b.snapshot()["state"] == "closed" and b.allow()
    # success resets the consecutive-failure count
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert not b.is_open()


def test_breaker_opens_on_injected_errors_and_recovers():
    """serve_error x threshold opens the breaker: admission fails fast
    with the typed error, a post-cooldown probe closes it again."""
    os.environ["FAA_FAULT"] = "serve_error@dispatch=1;serve_error@dispatch=2"
    faultinject.reset()
    srv = PolicyServer(DummyApplier(), max_wait_ms=1,
                       breaker_threshold=2, breaker_cooldown_s=0.3).start()
    try:
        for _ in range(2):
            with pytest.raises(ServeError):
                srv.augment(_images(1), _keys(1), timeout=10.0)
        snap = srv.stats()["breaker"]
        assert snap["state"] == "open" and snap["fires"] == 1
        with pytest.raises(CircuitOpenError) as ei:
            srv.submit(_images(1), _keys(1))
        assert ei.value.retry_after_s > 0
        assert srv.stats()["admission"]["shed_breaker"] >= 1
        time.sleep(0.35)
        out = srv.augment(_images(1), _keys(1), timeout=10.0)  # probe
        assert out.shape == (1, IMG, IMG, 3)
        assert srv.stats()["breaker"]["state"] == "closed"
    finally:
        srv.stop()


def test_breaker_fails_queued_batch_fast_when_open():
    """Requests already queued when the breaker opens get the typed
    error without a device call."""
    os.environ["FAA_FAULT"] = "serve_error@dispatch=1"
    faultinject.reset()
    ap = DummyApplier()
    srv = PolicyServer(ap, max_batch=1, max_wait_ms=1,
                       breaker_threshold=1, breaker_cooldown_s=30.0)
    p1 = srv.submit(_images(1), _keys(1))
    p2 = srv.submit(_images(1), _keys(1))
    srv.start()
    with pytest.raises(ServeError):
        srv.result(p1, timeout=10.0)
    with pytest.raises(CircuitOpenError):
        srv.result(p2, timeout=10.0)
    assert ap.calls == 0  # injected error + fast-fail: no device work
    srv.stop()


def test_dispatch_timeout_counts_as_breaker_failure():
    """A straggler past dispatch_timeout_s delivers results but feeds
    the breaker — repeated near-hangs open the circuit."""
    srv = PolicyServer(DummyApplier(wall_s=0.05), max_wait_ms=1,
                       breaker_threshold=1, breaker_cooldown_s=30.0,
                       dispatch_timeout_s=0.01).start()
    out = srv.augment(_images(1), _keys(1), timeout=10.0)
    assert out.shape == (1, IMG, IMG, 3)  # results still delivered
    assert srv.stats()["breaker"]["state"] == "open"
    srv.stop()


def test_serve_slow_verb_delays_dispatch():
    os.environ["FAA_FAULT"] = "serve_slow@dispatch=1,factor=0.2"
    faultinject.reset()
    srv = PolicyServer(DummyApplier(), max_wait_ms=1).start()
    t0 = time.perf_counter()
    srv.augment(_images(1), _keys(1), timeout=10.0)
    # no EMA yet -> factor seconds of injected delay
    assert time.perf_counter() - t0 >= 0.2
    srv.stop()


# ------------------------------------------------- FAA_FAULT grammar


def test_parse_serve_verbs():
    faults = faultinject.parse_fault_spec(
        "serve_error@dispatch=3;serve_slow@dispatch=5,factor=2.5")
    assert [f["kind"] for f in faults] == ["serve_error", "serve_slow"]
    assert faults[0]["dispatch"] == 3 and faults[1]["factor"] == 2.5
    with pytest.raises(ValueError):
        faultinject.parse_fault_spec("serve_error@step=3")  # wrong key
    with pytest.raises(ValueError):
        faultinject.parse_fault_spec("serve_slow@dispatch=1")  # no factor


def test_serve_verbs_attempt_gated():
    os.environ["FAA_FAULT"] = "serve_error@dispatch=1,attempt=2"
    os.environ["FAA_ATTEMPT"] = "1"
    faultinject.reset()
    plan = faultinject.active_plan()
    assert plan.serve_fault(1) is None  # gated to attempt 2
    os.environ["FAA_ATTEMPT"] = "2"
    assert plan.serve_fault(1) == ("error", 0.0)
    assert plan.serve_fault(1) is None  # fire-once


def test_serve_fault_consume_order():
    os.environ["FAA_FAULT"] = (
        "serve_error@dispatch=1;serve_slow@dispatch=2,factor=3.0")
    faultinject.reset()
    plan = faultinject.active_plan()
    assert plan.serve_fault(1) == ("error", 0.0)
    assert plan.serve_fault(2) == ("slow", 3.0)
    assert plan.serve_fault(3) is None


# ------------------------------------------------------ hot reload


def test_swap_applier_between_dispatches():
    a, b = DummyApplier(1.0), DummyApplier(5.0)
    srv = PolicyServer(a, max_wait_ms=1).start()
    imgs = _images(1)
    assert srv.augment(imgs, _keys(1), timeout=10.0)[0, 0, 0, 0] \
        == imgs[0, 0, 0, 0] + 1.0
    info = srv.swap_applier(b)
    assert info["reloads"] == 1
    assert srv.augment(imgs, _keys(1), timeout=10.0)[0, 0, 0, 0] \
        == imgs[0, 0, 0, 0] + 5.0
    assert srv.stats()["reloads"] == 1
    srv.stop()


def test_swap_applier_validates_contract():
    srv = PolicyServer(DummyApplier(max_batch=4))
    with pytest.raises(ValueError):  # smaller AOT coverage
        srv.swap_applier(DummyApplier(max_batch=2))
    with pytest.raises(ValueError):  # dispatch-mode change
        srv.swap_applier(DummyApplier(dispatch="grouped"))
    bad = DummyApplier()
    bad.image = 16
    with pytest.raises(ValueError):  # geometry change
        srv.swap_applier(bad)


def test_reload_atomic_under_concurrent_traffic_dummy():
    """Hammer requests while swapping appliers: every response must be
    ENTIRELY one applier's output (delta 1 or delta 5) — no half-policy
    batch, zero dropped requests."""
    a, b = DummyApplier(1.0, max_batch=8), DummyApplier(5.0, max_batch=8)
    srv = PolicyServer(a, max_wait_ms=2).start()
    imgs = _images(4, seed=3)
    results = []
    errors = []

    def client():
        for _ in range(40):
            try:
                results.append(srv.augment(imgs, _keys(4), timeout=10.0))
            except ServeError as e:  # pragma: no cover — would fail below
                errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(3)]
    for t in threads:
        t.start()
    for i in range(6):
        time.sleep(0.01)
        srv.swap_applier(b if i % 2 == 0 else a)
    for t in threads:
        t.join(timeout=30.0)
    srv.stop()
    assert not errors and len(results) == 120  # zero dropped requests
    for out in results:
        deltas = np.unique(out - imgs)
        assert deltas.size == 1 and deltas[0] in (1.0, 5.0), \
            "half-policy response: mixed deltas within one request"
    assert srv.reloads == 6


# --------------------------------------------------- graceful drain


def test_drain_finishes_inflight_then_rejects():
    ap = DummyApplier()
    srv = PolicyServer(ap, max_batch=1, max_wait_ms=1)
    pend = [srv.submit(_images(1), _keys(1)) for _ in range(3)]
    srv.start()
    assert srv.drain(timeout=10.0)
    for p in pend:
        assert p.result is not None  # in-flight completed, not errored
    assert ap.calls == 3
    with pytest.raises(ServerStoppedError):
        srv.submit(_images(1), _keys(1))
    assert srv.stats()["draining"] is True


def test_stop_errors_leftovers_with_typed_error():
    srv = PolicyServer(DummyApplier())
    p = srv.submit(_images(1), _keys(1))
    srv.start()  # worker may or may not pick it up before stop
    srv.stop()
    # either served before the stop won the race, or typed-stopped
    if p.error is not None:
        assert isinstance(p.error, ServerStoppedError)


# ------------------------------------------------------- serve_cli


def test_serve_cli_parser_overload_defaults():
    from fast_autoaugment_tpu.serve.serve_cli import build_parser

    args = build_parser().parse_args(["--policy", "x.json"])
    # bit-for-bit defaults: every overload knob off
    assert args.queue_depth == 4096 and args.default_deadline_ms is None
    assert args.lifo_depth == 0 and args.lifo_age_ms == 0.0
    assert args.breaker_threshold == 0 and not args.breaker_exit
    assert args.dispatch_timeout == 0.0 and args.watchdog == "off"
    assert args.max_inflight == 0 and args.serve_seconds == 0.0
    assert args.heartbeat_dir is None and args.port_file is None


def _http(port, method, path, body=None, headers=None, timeout=30):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp, data


def _start_http(server, state=None, **kw):
    from http.server import ThreadingHTTPServer

    from fast_autoaugment_tpu.serve.serve_cli import make_handler

    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        make_handler(server, server.applier, state=state, **kw))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, httpd.server_address[1]


def test_http_structured_errors_and_readyz():
    """Handler hardening on a host-only dummy server: 400/413/429
    structured JSON, /healthz vs /readyz split."""
    from fast_autoaugment_tpu.serve.serve_cli import ServeState

    srv = PolicyServer(DummyApplier(dispatch="grouped"), queue_depth=1)
    state = ServeState(srv, "unused.json")
    httpd, port = _start_http(srv, state, max_body_bytes=4096)
    try:
        # liveness vs readiness: worker not started -> alive, not ready
        resp, data = _http(port, "GET", "/healthz")
        assert resp.status == 200 and json.loads(data)["ok"] is True
        resp, data = _http(port, "GET", "/readyz")
        body = json.loads(data)
        assert resp.status == 503 and body["ready"] is False
        assert "worker" in body["reason"]

        # malformed body -> 400 structured
        resp, data = _http(port, "POST", "/augment", body=b"not-an-npz")
        assert resp.status == 400
        assert json.loads(data)["type"] == "bad_request"

        # oversized body -> 413 without reading it all
        resp, data = _http(port, "POST", "/augment", body=b"x" * 8192)
        assert resp.status == 413
        assert json.loads(data)["type"] == "body_too_large"

        # malformed deadline header -> 400
        buf = io.BytesIO()
        np.savez(buf, images=_images(1).astype(np.uint8))
        resp, data = _http(port, "POST", "/augment", body=buf.getvalue(),
                           headers={"X-FAA-Deadline-Ms": "soon"})
        assert resp.status == 400

        # queue full (depth 1, worker down) -> 429 + Retry-After
        srv.submit(_images(1))
        resp, data = _http(port, "POST", "/augment", body=buf.getvalue())
        assert resp.status == 429
        assert json.loads(data)["type"] == "overloaded"
        assert int(resp.getheader("Retry-After")) >= 1

        # unknown path POST -> structured 404
        resp, data = _http(port, "POST", "/nope", body=b"{}")
        assert resp.status == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_http_deadline_header_propagates_and_sheds():
    """An expired X-FAA-Deadline-Ms request is shed with a structured
    503 — the handler thread is released at the deadline, not 60s
    later."""
    srv = PolicyServer(DummyApplier(dispatch="grouped"))
    srv.deadline_grace_s = 0.2
    httpd, port = _start_http(srv)  # worker never started: must expire
    try:
        buf = io.BytesIO()
        np.savez(buf, images=_images(1).astype(np.uint8))
        t0 = time.perf_counter()
        resp, data = _http(port, "POST", "/augment", body=buf.getvalue(),
                           headers={"X-FAA-Deadline-Ms": "100"})
        wall = time.perf_counter() - t0
        assert resp.status == 503
        assert json.loads(data)["type"] in ("deadline_expired", "timeout")
        assert wall < 5.0
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_http_stats_carries_robustness_counters():
    srv = PolicyServer(DummyApplier(dispatch="grouped"), queue_depth=1)
    httpd, port = _start_http(srv)
    try:
        srv.submit(_images(1))
        with pytest.raises(ServerOverloadedError):
            srv.submit(_images(1))
        resp, data = _http(port, "GET", "/stats")
        stats = json.loads(data)
        assert resp.status == 200
        assert stats["admission"]["shed_overload"] == 1
        assert stats["breaker"]["state"] == "disabled"
        assert stats["reloads"] == 0 and stats["draining"] is False
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_http_metrics_scrape_prometheus_exposition():
    """GET /metrics returns every serve_robustness counter in
    Prometheus text format, and the scraped values match /stats — one
    registry behind both views (core/telemetry.py)."""
    srv = PolicyServer(DummyApplier(dispatch="grouped"), queue_depth=1)
    httpd, port = _start_http(srv)
    try:
        srv.submit(_images(1))
        with pytest.raises(ServerOverloadedError):
            srv.submit(_images(1))
        resp, data = _http(port, "GET", "/metrics")
        text = data.decode()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        assert "# TYPE faa_serve_robustness_total counter" in text
        for name in ("admitted", "shed_overload", "shed_breaker",
                     "shed_stopped", "expired", "deadline_misses",
                     "lifo_takes", "reloads"):
            assert f'counter="{name}"' in text, name
        # scraped values == /stats values for THIS server's label
        sid = srv._server_id
        scraped = {}
        for line in text.splitlines():
            if line.startswith("faa_serve_robustness_total") \
                    and f'server="{sid}"' in line:
                key = line.split('counter="', 1)[1].split('"', 1)[0]
                scraped[key] = float(line.rsplit(" ", 1)[1])
        adm = srv.stats()["admission"]
        assert scraped["admitted"] == adm["admitted"] == 1
        assert scraped["shed_overload"] == adm["shed_overload"] == 1
        assert scraped["expired"] == adm["expired"] == 0
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_http_reload_not_configured_and_max_inflight():
    srv = PolicyServer(DummyApplier(dispatch="grouped"))
    httpd, port = _start_http(srv, max_inflight=1)
    try:
        resp, data = _http(port, "POST", "/reload", body=b"")
        assert resp.status == 503
        assert json.loads(data)["type"] == "not_configured"
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


# ------------------------------------------- fleet replica supervision


def test_fleet_no_rank_args_replica_restart(tmp_path, monkeypatch):
    """The serving-replica supervision contract: --no-rank-args launches
    the command VERBATIM (no --coordinator suffix), exit 77 is
    retry-eligible, and the relaunch (attempt 2) succeeds -> fleet exit
    0 with two attempts."""
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    def _argv(host, wire):
        return ["bash", "-c", wire]

    monkeypatch.setattr(fleet_mod, "_remote_argv", _argv)
    # $1 set => rank args were appended => exit 9 (contract violation);
    # attempt 1 exits 77 (breaker-exit), attempt 2 serves fine (exit 0)
    script = ("if [ -n \"$1\" ]; then exit 9; fi; "
              "if [ \"$FAA_ATTEMPT\" = \"1\" ]; then exit 77; fi; "
              "exit 0")
    code = fleet_mod.launch_fleet(
        ["replica"], ["bash", "-c", script], None,
        host_retries=1, retry_backoff=0.05, rank_args=False)
    assert code == 0


def test_fleet_rank_args_still_default(monkeypatch):
    """Without --no-rank-args the historical rank suffix is appended."""
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    def _argv(host, wire):
        return ["bash", "-c", wire]

    monkeypatch.setattr(fleet_mod, "_remote_argv", _argv)
    script = "if [ -n \"$1\" ]; then exit 0; fi; exit 9"
    code = fleet_mod.launch_fleet(["h"], ["bash", "-c", script], None)
    assert code == 0


def test_fleet_cli_no_rank_args_flag_parses(monkeypatch, capsys):
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    called = {}

    def fake_launch(hosts, command, coordinator, **kw):
        called.update(kw, hosts=hosts, command=command)
        return 0

    monkeypatch.setattr(fleet_mod, "launch_fleet", fake_launch)
    with pytest.raises(SystemExit) as ei:
        fleet_mod.main(["--hosts", "2", "--no-rank-args", "--", "echo", "x"])
    assert ei.value.code == 0
    assert called["rank_args"] is False and called["command"] == ["echo", "x"]


# ------------------------------------------------- slow chaos drills


SINGLE_SUB = np.array([[[4, 0.8, 0.7], [10, 0.5, 0.3]]], np.float32)
ALT_SUB = np.array([[[0, 0.9, 0.5], [1, 0.6, 0.4]]], np.float32)


@pytest.mark.slow
def test_http_chaos_breaker_readyz_flip():
    """The chaos drill on real AOT executables: injected serve_error
    opens the breaker, /readyz flips to 503 while /healthz stays 200,
    requests fail fast with typed JSON, and the post-cooldown probe
    returns the replica to ready."""
    from fast_autoaugment_tpu.serve.policy_server import AotPolicyApplier
    from fast_autoaugment_tpu.serve.serve_cli import ServeState

    os.environ["FAA_FAULT"] = "serve_error@dispatch=1;serve_error@dispatch=2"
    faultinject.reset()
    applier = AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(4,))
    srv = PolicyServer(applier, max_wait_ms=2, breaker_threshold=2,
                       breaker_cooldown_s=0.5).start()
    state = ServeState(srv, "unused.json")
    httpd, port = _start_http(srv, state)
    try:
        buf = io.BytesIO()
        np.savez(buf, images=_images(1, seed=4).astype(np.uint8))
        body = buf.getvalue()
        # two injected dispatch errors -> breaker opens
        for _ in range(2):
            resp, data = _http(port, "POST", "/augment", body=body)
            assert resp.status == 500
            assert json.loads(data)["type"] == "dispatch_error"
        resp, data = _http(port, "GET", "/readyz")
        assert resp.status == 503
        assert json.loads(data)["reason"] == "circuit breaker open"
        resp, _ = _http(port, "GET", "/healthz")
        assert resp.status == 200  # alive through the whole episode
        # fast-fail while open: typed JSON + Retry-After, no hang
        resp, data = _http(port, "POST", "/augment", body=body)
        assert resp.status == 503
        assert json.loads(data)["type"] == "breaker_open"
        assert resp.getheader("Retry-After") is not None
        time.sleep(0.6)
        # post-cooldown probe succeeds -> ready again
        resp, _ = _http(port, "POST", "/augment", body=body)
        assert resp.status == 200
        resp, data = _http(port, "GET", "/readyz")
        assert resp.status == 200 and json.loads(data)["ready"] is True
        resp, data = _http(port, "GET", "/stats")
        stats = json.loads(data)
        assert stats["breaker"]["fires"] == 1
        assert stats["admission"]["shed_breaker"] >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


@pytest.mark.slow
def test_reload_under_traffic_bitwise_per_applier():
    """Hot reload on real AOT executables under concurrent traffic:
    zero dropped requests and every response BITWISE one applier's
    output — never a mixture (the per-applier verification the
    acceptance demands)."""
    from fast_autoaugment_tpu.serve.policy_server import AotPolicyApplier

    ap_a = AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(4,))
    ap_b = AotPolicyApplier(ALT_SUB, image=IMG, shapes=(4,))
    srv = PolicyServer(ap_a, max_wait_ms=2).start()
    imgs = _images(2, seed=9)
    keys = np.stack([_jax_key(7), _jax_key(8)])
    ref_a = ap_a.apply(imgs, keys)
    ref_b = ap_b.apply(imgs, keys)
    assert not np.array_equal(ref_a, ref_b)  # the policies do differ
    results, errors = [], []

    def client():
        for _ in range(25):
            try:
                results.append(srv.augment(imgs, keys, timeout=30.0))
            except ServeError as e:  # pragma: no cover
                errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(2)]
    for t in threads:
        t.start()
    for i in range(4):
        time.sleep(0.02)
        srv.swap_applier(ap_b if i % 2 == 0 else ap_a)
    for t in threads:
        t.join(timeout=60.0)
    srv.stop()
    assert not errors and len(results) == 50
    n_a = n_b = 0
    for out in results:
        if np.array_equal(out, ref_a):
            n_a += 1
        elif np.array_equal(out, ref_b):
            n_b += 1
        else:
            raise AssertionError("response matches NEITHER applier "
                                 "bitwise — half-policy batch")
    assert n_a + n_b == 50


def _jax_key(i):
    import jax

    return np.asarray(jax.random.PRNGKey(i), np.uint32)


def _write_tiny_policy(path):
    subs = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]
    path.write_text(json.dumps(subs))
    return str(path)


def _wait_port_file(path, proc, timeout=120.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if os.path.exists(path) and open(path).read().strip():
            return int(open(path).read().strip())
        if proc.poll() is not None:
            raise AssertionError(
                f"serve replica died before binding: rc={proc.returncode}")
        time.sleep(0.2)
    raise AssertionError("serve replica never wrote its port file")


@pytest.mark.slow
def test_serve_replica_breaker_exit_restart_ready(tmp_path):
    """The replica-restart drill as the fleet supervisor runs it:
    attempt 1 hits an attempt-gated serve_error, the breaker opens,
    --breaker-exit maps it to exit 77 (restart me); attempt 2 (the
    relaunch) serves cleanly, /readyz returns 200, and SIGTERM drains
    to exit 0."""
    policy = _write_tiny_policy(tmp_path / "p.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FAA_FAULT="serve_error@dispatch=1,attempt=1")
    base_cmd = [
        sys.executable, "-m", "fast_autoaugment_tpu.serve.serve_cli",
        "--policy", policy, "--image", str(IMG), "--shapes", "1,4",
        "--max-wait-ms", "2", "--queue-depth", "16",
        "--breaker-threshold", "1", "--breaker-cooldown", "60",
        "--breaker-exit", "--port", "0",
        "--heartbeat-dir", str(tmp_path / "q"),
    ]
    buf = io.BytesIO()
    np.savez(buf, images=_images(1, seed=5).astype(np.uint8))
    body = buf.getvalue()

    # ---- attempt 1: injected dispatch error -> breaker -> exit 77
    port_file = tmp_path / "port1"
    env["FAA_ATTEMPT"] = "1"
    p1 = subprocess.Popen(base_cmd + ["--port-file", str(port_file)],
                          env=env, cwd=_REPO)
    try:
        port = _wait_port_file(str(port_file), p1)
        resp, data = _http(port, "POST", "/augment", body=body, timeout=60)
        assert resp.status == 500  # the injected failure
        rc = p1.wait(timeout=60)
        assert rc == PREEMPTED_EXIT_CODE  # 77: restart me
    finally:
        if p1.poll() is None:
            p1.kill()
            p1.wait(timeout=10)

    # ---- attempt 2 (the supervisor's relaunch): clean and ready
    port_file2 = tmp_path / "port2"
    env["FAA_ATTEMPT"] = "2"
    p2 = subprocess.Popen(base_cmd + ["--port-file", str(port_file2)],
                          env=env, cwd=_REPO)
    try:
        port = _wait_port_file(str(port_file2), p2)
        resp, data = _http(port, "GET", "/readyz", timeout=60)
        assert resp.status == 200 and json.loads(data)["ready"] is True
        resp, _ = _http(port, "POST", "/augment", body=body, timeout=60)
        assert resp.status == 200
        # host beats flow in the fleet schema the supervisor consumes
        # (first beat lands one interval after startup — poll briefly)
        beat_path = tmp_path / "q" / "hosts" / "host0.json"
        t0 = time.monotonic()
        while not beat_path.exists() and time.monotonic() - t0 < 15:
            time.sleep(0.2)
        beat = json.load(open(beat_path))
        assert beat["heartbeat"] > 0
        # SIGTERM: graceful drain, exit 0 (the serving exit contract)
        p2.send_signal(signal.SIGTERM)
        assert p2.wait(timeout=60) == 0
    finally:
        if p2.poll() is None:
            p2.kill()
            p2.wait(timeout=10)


@pytest.mark.slow
def test_http_reload_endpoint_roundtrip(tmp_path):
    """POST /reload swaps to a new final_policy.json under live HTTP:
    the response reports the swap and subsequent requests serve the new
    policy bitwise."""
    from fast_autoaugment_tpu.serve.policy_server import AotPolicyApplier
    from fast_autoaugment_tpu.serve.serve_cli import (
        ServeState,
        build_policy_tensor,
    )

    p_a = tmp_path / "a.json"
    p_a.write_text(json.dumps([[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]))
    p_b = tmp_path / "b.json"
    p_b.write_text(json.dumps([[["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]]))

    def build_applier(policy_tensor):
        return AotPolicyApplier(policy_tensor, image=IMG, shapes=(4,),
                                dispatch="exact")

    ap = build_applier(build_policy_tensor(str(p_a)))
    srv = PolicyServer(ap, max_wait_ms=2).start()
    state = ServeState(srv, str(p_a), build_applier)
    httpd, port = _start_http(srv, state)
    try:
        imgs = _images(2, seed=11)
        seeds = np.arange(2)
        buf = io.BytesIO()
        np.savez(buf, images=imgs.astype(np.uint8), seeds=seeds)
        body = buf.getvalue()

        resp, data = _http(port, "POST", "/augment", body=body, timeout=60)
        assert resp.status == 200

        resp, data = _http(port, "POST", "/reload",
                           body=json.dumps({"policy": str(p_b)}).encode(),
                           timeout=120)
        assert resp.status == 200
        info = json.loads(data)
        assert info["reloaded"] is True and info["policy"] == str(p_b)

        resp, data = _http(port, "POST", "/augment", body=body, timeout=60)
        assert resp.status == 200
        got = np.load(io.BytesIO(data))["images"]
        from fast_autoaugment_tpu.serve.serve_cli import _seed_keys

        ap_b = build_applier(build_policy_tensor(str(p_b)))
        ref = np.clip(ap_b.apply(imgs, _seed_keys(seeds)),
                      0, 255).astype(np.uint8)
        assert np.array_equal(got, ref)
        assert srv.reloads == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()
